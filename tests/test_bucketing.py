"""Gradient-bucketing plan + pack/scatter tests (single device), plus the
multi-device subprocess check (distributed_checks/bucketing_check.py).

The plan invariants and the bit-exact pack→scatter round trip run against
*every config in the registry* (smoke-scale param trees for materialized
round trips; the plan is a pure function of abstract shapes, so full-scale
trees are covered by construction)."""
import functools
import os
import pathlib
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import RunConfig
from repro.configs.registry import list_archs, smoke_config
from repro.core import types as core_types
from repro.models import model as model_lib
from repro.train import bucketing
from repro.train import train_step as ts

ROOT = pathlib.Path(__file__).resolve().parent.parent

MSIZES = {"data": 1, "model": 1}
MESH_AXES = ("data", "model")

CMP = core_types.CompressionConfig(
    encoder=core_types.EncoderSpec(kind="fixed_k", fraction=0.25),
    mode="shared_support", axes=("data",), min_compress_size=2048,
    bucket=core_types.BucketSpec(capacity=1 << 15))


@functools.lru_cache(maxsize=None)
def _abstract_tree(arch: str):
    cfg = smoke_config(arch)
    run = RunConfig(model_parallel=arch != "mamba2-130m", seq_shard=False,
                    attn_chunk_q=16, attn_chunk_k=16, compression=CMP)
    ctx = model_lib.make_ctx(cfg, run, MSIZES)
    aparams, specs = ts.abstract_specs(jax.random.PRNGKey(0), cfg, ctx,
                                       MSIZES, run)
    return aparams, specs


def _materialize(aparams):
    rng = np.random.default_rng(0)
    out = {}
    for k, v in aparams.items():
        if jnp.issubdtype(v.dtype, jnp.floating):
            out[k] = jnp.asarray(
                rng.standard_normal(v.shape, dtype=np.float32)).astype(v.dtype)
        else:
            out[k] = jnp.zeros(v.shape, v.dtype)
    return out


# --------------------------------------------------------------------------- #
# Plan invariants — every registry config.
# --------------------------------------------------------------------------- #

@pytest.mark.parametrize("arch", list_archs())
def test_plan_invariants(arch):
    aparams, specs = _abstract_tree(arch)
    plan = bucketing.build_plan(aparams, specs, MESH_AXES, MSIZES, CMP)

    # coverage: every leaf exactly once (buckets + passthrough)
    placed = [s.name for b in plan.buckets for s in b.slots]
    assert sorted(placed + list(plan.passthrough)) == sorted(aparams)
    assert plan.leaf_names() == tuple(sorted(aparams))

    cap = CMP.bucket.capacity
    for b in plan.buckets:
        # offsets are contiguous and sum to the bucket size
        off = 0
        for s in b.slots:
            assert s.offset == off
            assert s.size == int(np.prod(s.shape)) if s.shape else s.size == 1
            off += s.size
        assert off == b.size
        # capacity respected except for dedicated oversize buckets
        assert b.size <= cap or len(b.slots) == 1
        if b.kind == "compressed":
            assert b.caxes and all(a in CMP.axes for a in b.caxes)
            assert all(s.size >= CMP.min_compress_size for s in b.slots)
        else:
            assert b.caxes == ()
            assert b.eaxes

    # deterministic: the plan is a pure function of its inputs
    assert plan == bucketing.build_plan(aparams, specs, MESH_AXES, MSIZES, CMP)


def test_plan_respects_min_compress_and_mode():
    aparams, specs = _abstract_tree("qwen3-4b")
    cmp_none = core_types.CompressionConfig(
        mode="none", bucket=core_types.BucketSpec(capacity=1 << 15))
    plan = bucketing.build_plan(aparams, specs, MESH_AXES, MSIZES, cmp_none)
    assert all(b.kind == "exact" for b in plan.buckets)
    assert bucketing.plan_for_run(
        aparams, specs, MESH_AXES, MSIZES,
        core_types.CompressionConfig(
            mode="none",
            bucket=core_types.BucketSpec(enabled=False))) is None


def test_local_shape_divides_sharded_dims():
    assert bucketing.local_shape((8, 6), ("data", "model"),
                                 {"data": 4, "model": 3}) == (2, 2)
    assert bucketing.local_shape((8,), (("data", "model"),),
                                 {"data": 2, "model": 2}) == (2,)
    with pytest.raises(ValueError):
        bucketing.local_shape((7,), ("data",), {"data": 2})


# --------------------------------------------------------------------------- #
# Pack → scatter round trip — bit-exact, every registry config.
# --------------------------------------------------------------------------- #

@pytest.mark.parametrize("arch", list_archs())
def test_pack_scatter_roundtrip_bit_exact(arch):
    aparams, specs = _abstract_tree(arch)
    plan = bucketing.build_plan(aparams, specs, MESH_AXES, MSIZES, CMP)
    grads = _materialize(aparams)

    out = {n: grads[n] for n in plan.passthrough}
    for b in plan.buckets:
        vec = bucketing.pack_bucket(grads, b)
        assert vec.shape == (b.size,) and vec.dtype == jnp.float32
        out.update(bucketing.unpack_bucket(vec, b, grads))

    assert set(out) == set(grads)
    for n in grads:
        assert out[n].dtype == grads[n].dtype, n
        assert out[n].shape == grads[n].shape, n
        np.testing.assert_array_equal(np.asarray(out[n]),
                                      np.asarray(grads[n]), err_msg=n)


def test_bucketed_sync_identity_on_one_device():
    """mode 'none' on a 1-device mesh: sync must be the exact identity."""
    mesh = jax.make_mesh((1,), ("data",))
    shapes = {"a": (256, 17), "b": (4096,), "c": (3,)}
    specs = {n: (None,) * len(s) for n, s in shapes.items()}
    cmp = core_types.CompressionConfig(
        mode="none", bucket=core_types.BucketSpec(capacity=1 << 12))
    plan = bucketing.build_plan(shapes, specs, ("data",), {"data": 1}, cmp)
    grads = {n: jax.random.normal(jax.random.fold_in(jax.random.PRNGKey(0), i),
                                  s).astype(jnp.bfloat16 if n == "c"
                                            else jnp.float32)
             for i, (n, s) in enumerate(sorted(shapes.items()))}

    from jax.sharding import PartitionSpec as P
    pspecs = {n: P() for n in shapes}

    @functools.partial(jax.shard_map, mesh=mesh, in_specs=(pspecs, P()),
                       out_specs=pspecs, check_vma=False)
    def sync(g, key):
        est, _ = bucketing.sync_grads_bucketed(g, plan, cmp, key)
        return est

    out = jax.jit(sync)(grads, jax.random.PRNGKey(0))
    for n in grads:
        np.testing.assert_array_equal(np.asarray(out[n]),
                                      np.asarray(grads[n]), err_msg=n)


# --------------------------------------------------------------------------- #
# Multi-device behavior (subprocess: 8 fake CPU devices).
# --------------------------------------------------------------------------- #

@pytest.mark.distributed
def test_bucketed_sync_multidevice():
    env = dict(os.environ)
    env["PYTHONPATH"] = str(ROOT / "src")
    env.pop("XLA_FLAGS", None)  # the script sets its own
    res = subprocess.run(
        [sys.executable,
         str(ROOT / "tests" / "distributed_checks" / "bucketing_check.py")],
        env=env, capture_output=True, text=True, timeout=900)
    assert res.returncode == 0, f"\nSTDOUT:\n{res.stdout}\nSTDERR:\n{res.stderr}"
    assert "ALL BUCKETING CHECKS PASSED" in res.stdout
