"""The gradient sync and the codec stages are named in the compiled step.

Compiles the toy Mamba-2 train step of the benchmark's tests
(tests/bench_chip/data) on the CPU for three sync presets and both sync
schedules, and reads each instruction's scopes from its ``op_name``
(benchmarks/chip/scopes.py): every bucket is ``grad_sync/b<j>`` with its
``pack``/``unpack`` steps, every operation of a codec round sits in a
stage, and both schedules name the same scopes.
"""
import functools
import json
import pathlib
import re
import sys

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path[0:0] = [str(ROOT)]
DATA = ROOT / "tests" / "bench_chip" / "data"

PRESETS = {
    "train.ef_rotated_binary": {"ef", "rotate", "encode", "exchange",
                                "decode", "unrotate"},
    "train.bernoulli_seed_1bit": {"encode", "exchange", "decode"},
    "train.exact": set(),
}
STEPS = {"pack", "exact", "codec", "unpack"}


@functools.lru_cache(maxsize=None)
def compiled(traffic: str, overlap: bool):
    """(bucket plan, {instruction: scopes}) of the toy step."""
    import jax
    import numpy as np

    from benchmarks.chip import harness, scopes
    from benchmarks.chip import traffic as tf

    t = json.loads((DATA / f"tiny.{traffic}.json").read_text())
    t["compression"]["bucket"]["overlap"] = overlap
    cell = harness.Cell(
        name=f"tiny.{traffic}",
        config=json.loads((DATA / "mamba2-tiny.json").read_text()),
        traffic=t, chips=1, per_layer=[], limits=None)
    prog = harness.Program(cell, jax.devices())
    assert prog.trainer.overlap == overlap
    state = jax.eval_shape(prog.trainer.init_fn, jax.random.PRNGKey(0))
    batch = prog.put(tf.ring(1, t, cell.config["vocab_size"], 1)[0])
    text = prog.trainer.step_fn.lower(*state, batch, np.int32(0)) \
        .compile().as_text()
    return prog.trainer.sync_plan, scopes.scope_map(text)


def named(path):
    """The path's components that the sync and the codec set."""
    from benchmarks.chip import scopes
    keep = STEPS | set(scopes.STAGES) | {"grad_sync", "trim"}
    return tuple(c for c in path if c in keep or re.fullmatch(r"b\d+", c))


@pytest.mark.parametrize("overlap", [True, False],
                         ids=["overlapped", "post_backward"])
@pytest.mark.parametrize("traffic", sorted(PRESETS))
def test_every_bucket_is_scoped(traffic, overlap):
    """Each bucket's round is under ``grad_sync/b<j>/codec`` (compressed)
    or ``grad_sync/b<j>/exact``; a one-leaf bucket's pack and unpack are
    reshapes that leave no instruction, so those two are asked of the
    step as a whole."""
    from benchmarks.chip import scopes
    plan, smap = compiled(traffic, overlap)
    steps = set()
    for j, b in enumerate(plan.buckets):
        got = {p[p.index(f"b{j}") + 1] for p in smap.values()
               if scopes.under(p, ("grad_sync", f"b{j}"))
               and p.index(f"b{j}") + 1 < len(p)} & STEPS
        assert ("codec" if b.kind == "compressed" else "exact") in got, \
            (b.bid, got)
        if b.kind == "exact":
            assert "codec" not in got, b.bid
        steps |= got
    assert steps >= {"pack", "unpack"}


@pytest.mark.parametrize("traffic", sorted(PRESETS))
def test_codec_stages(traffic):
    from benchmarks.chip import scopes
    _, smap = compiled(traffic, True)
    codec = [p for p in smap.values() if scopes.under(p, scopes.CODEC)]
    assert {scopes.stage(p) for p in codec} == PRESETS[traffic]
    assert all(scopes.stage(p) is not None for p in codec)
    if not PRESETS[traffic]:
        assert not codec
        assert any(scopes.under(p, scopes.SYNC) for p in smap.values())


@pytest.mark.parametrize("traffic", sorted(PRESETS))
def test_schedules_name_the_same_scopes(traffic):
    paths = {
        overlap: {named(p) for p in compiled(traffic, overlap)[1].values()
                  if "grad_sync" in p}
        for overlap in (True, False)}
    assert paths[True] == paths[False]
    assert paths[True]


FOUR_DEVICES = """
import json, pathlib, sys
sys.path[0:0] = [%(root)r, %(root)r + "/src"]
import jax, numpy as np
from benchmarks.chip import harness, scopes, trace_reduce
from benchmarks.chip import traffic as tf
data = pathlib.Path(%(root)r) / "tests/bench_chip/data"
t = json.loads((data / "tiny.train-dp4.ef_rotated_binary.json").read_text())
cell = harness.Cell(name="tiny.dp4", traffic=t, chips=4, per_layer=[],
                    limits=None, config=json.loads(
                        (data / "mamba2-tiny.json").read_text()))
prog = harness.Program(cell, jax.devices())
state = jax.eval_shape(prog.trainer.init_fn, jax.random.PRNGKey(0))
batch = prog.put(tf.ring(1, t, cell.config["vocab_size"], 4)[0])
text = prog.trainer.step_fn.lower(*state, batch, np.int32(0)) \\
    .compile().as_text()
smap = scopes.scope_map(text)
print(json.dumps([[n, smap.get(n, [])] for n, c in
                  trace_reduce.classify_hlo(text).items()
                  if c == "collective"]))
"""


def test_four_device_collectives_are_sync_steps():
    """On a data = 4 mesh every collective of the toy step is a bucket's
    exact pmean or its codec round's exchange."""
    import os
    import subprocess

    from benchmarks.chip import scopes

    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    p = subprocess.run([sys.executable, "-c",
                        FOUR_DEVICES % {"root": str(ROOT)}],
                       env=env, capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    colls = json.loads(p.stdout.strip().splitlines()[-1])
    assert colls
    for name, path in colls:
        assert (scopes.under(path, ("grad_sync", "exact"))
                or scopes.stage(path) == "exchange"), (name, path)
    assert any(scopes.stage(path) == "exchange" for _, path in colls)
