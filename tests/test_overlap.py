"""Overlapped bucket sync: fast plan/readiness invariants (single device)
plus the multi-device subprocess check (distributed_checks/overlap_check.py,
which proves overlapped == post-backward bit-for-bit per preset and that
the per-bucket collectives interleave with backward at the HLO level)."""
import functools
import os
import pathlib
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import types as core_types
from repro.train import bucketing

ROOT = pathlib.Path(__file__).resolve().parent.parent

SHAPES = {f"w_{i:02d}": (64, 64) for i in range(6)}
SHAPES.update({f"b_{i:02d}": (64,) for i in range(6)})
SPECS = {n: (None,) * len(s) for n, s in SHAPES.items()}
CMP = core_types.CompressionConfig(
    encoder=core_types.EncoderSpec(kind="fixed_k", fraction=0.25),
    mode="shared_support", axes=("data",), min_compress_size=1024,
    bucket=core_types.BucketSpec(capacity=2 * 64 * 64))


def test_readiness_schedule_orders_backward():
    """ready = backward index of the bucket's last-produced leaf; the
    schedule issues latest-sorted (earliest-backward) buckets first."""
    plan = bucketing.build_plan(SHAPES, SPECS, ("data",), {"data": 8}, CMP)
    n_leaves = len(SHAPES)
    names = sorted(SHAPES)
    for b in plan.buckets:
        want = max(n_leaves - 1 - names.index(s.name) for s in b.slots)
        assert b.ready == want, b.bid
    sched = plan.schedule()
    assert sorted(sched) == sorted(b.bid for b in plan.buckets)
    readiness = {b.bid: b.ready for b in plan.buckets}
    assert [readiness[bid] for bid in sched] == sorted(readiness.values())
    # the last weight pair has the smallest backward index -> issued first
    first = next(b for b in plan.buckets if b.bid == sched[0])
    assert any(s.name == "w_05" for s in first.slots)


def test_overlap_identity_on_one_device():
    """1-device mesh, mode none: differentiating through the sync points
    returns the unsynced grads exactly (identity collective)."""
    from jax.sharding import PartitionSpec as P

    mesh = jax.make_mesh((1,), ("data",))
    cmp = core_types.CompressionConfig(
        mode="none", bucket=core_types.BucketSpec(capacity=1 << 12))
    shapes = {"a": (32, 8), "b": (256,)}
    specs = {n: (None,) * len(s) for n, s in shapes.items()}
    plan = bucketing.build_plan(shapes, specs, ("data",), {"data": 1}, cmp)
    params = {n: jax.random.normal(jax.random.fold_in(jax.random.PRNGKey(0),
                                                      i), s)
              for i, (n, s) in enumerate(sorted(shapes.items()))}
    pspec = {n: P() for n in shapes}

    def loss(p):
        return jnp.sum(p["a"]) + jnp.sum(jnp.sin(p["b"]))

    @functools.partial(jax.shard_map, mesh=mesh, in_specs=(pspec, P()),
                       out_specs=(pspec, pspec), check_vma=False)
    def grads_both(p, key):
        g_ref = jax.grad(loss)(p)
        g_ovl = jax.grad(
            lambda q: loss(bucketing.overlap_params(q, plan, cmp, key)))(p)
        return g_ref, g_ovl

    g_ref, g_ovl = jax.jit(grads_both)(params, jax.random.PRNGKey(1))
    for n in shapes:
        np.testing.assert_array_equal(np.asarray(g_ref[n]),
                                      np.asarray(g_ovl[n]), err_msg=n)


@pytest.mark.distributed
def test_overlap_multidevice():
    env = dict(os.environ)
    env["PYTHONPATH"] = str(ROOT / "src")
    env.pop("XLA_FLAGS", None)  # the script sets its own
    res = subprocess.run(
        [sys.executable,
         str(ROOT / "tests" / "distributed_checks" / "overlap_check.py")],
        env=env, capture_output=True, text=True, timeout=900)
    assert res.returncode == 0, f"\nSTDOUT:\n{res.stdout}\nSTDERR:\n{res.stderr}"
    assert "ALL OVERLAP CHECKS PASSED" in res.stdout
