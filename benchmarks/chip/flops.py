"""Model FLOPs of a training step per token: forward and backward (backward
= twice forward), with no recomputation. The forward count is the
architecture's (``arch/<architecture>/flops.py``)."""
from __future__ import annotations

from benchmarks.chip import arch


def train_per_token(cfg: dict, seq_len: int) -> float:
    return 3.0 * arch.part(cfg, "flops").forward_per_token(cfg, seq_len)
