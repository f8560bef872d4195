"""What the benchmark knows of each architecture, found by the configuration
file's ``architecture`` key: ``arch/<architecture>/`` holds

* ``program.py``: ``arch_config(cfg)``, the program's ``ArchConfig`` for the
  configuration (the only module here that imports the program; it raises
  ``ValueError`` for every value the program cannot run);
* ``weights.py``: ``param_shapes(cfg)`` and ``init_leaf(name, shape, key,
  cfg)``, the leaves the seeded weights are drawn for;
* ``model.py``: ``row_loss(params, tokens, labels, mask, cfg, policy)``, the
  plain float32 forward pass and summed token loss of one row (it imports
  nothing of the program);
* ``flops.py``: ``forward_per_token(cfg, seq_len)``, the model FLOPs of the
  forward pass per token.

A configuration of a new architecture is a config file and such a
directory; nothing else of the harness changes.
"""
from __future__ import annotations

import importlib
import re

PARTS = ("program", "weights", "model", "flops")


def part(cfg: dict, name: str):
    """The module ``name`` of ``cfg``'s architecture."""
    if name not in PARTS:
        raise ValueError(f"no architecture part {name!r}; one of {PARTS}")
    arch = cfg.get("architecture")
    where = f"benchmarks/chip/arch/{arch}/"
    if not isinstance(arch, str) or not re.fullmatch(r"[A-Za-z_]\w*", arch):
        raise ValueError(f"{cfg.get('name')}: architecture {arch!r} is not a "
                         "name a directory under benchmarks/chip/arch/ can have")
    try:
        return importlib.import_module(f"{__name__}.{arch}.{name}")
    except ModuleNotFoundError as e:
        if not e.name or not e.name.startswith(f"{__name__}.{arch}"):
            raise
        raise ValueError(
            f"{cfg.get('name')}: no architecture {arch!r}: add {where} with "
            f"{', '.join(p + '.py' for p in PARTS)}") from None
