"""The benchmark's plain reference against the program, on the CPU.

The reference imports nothing of the program; these tests tie the two
together at small sizes: the same model in float32, the same bucket plan,
the same rotation and the same decoded buckets for each codec a cell uses.
"""
import json
import pathlib
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path[0:0] = [str(ROOT)]

from benchmarks.chip import reference, traffic, weights  # noqa: E402

DATA = ROOT / "tests" / "bench_chip" / "data"
WORKLOADS = ROOT / "benchmarks" / "chip" / "workloads"
CONFIGS = ROOT / "benchmarks" / "chip" / "configs"


def tiny():
    return json.loads((DATA / "mamba2-tiny.json").read_text())


def tiny_traffic(name):
    return json.loads((DATA / f"tiny.{name}.json").read_text())


def program_model(cfg):
    from repro.configs.base import ArchConfig, RunConfig
    from repro.models import model as model_lib
    from repro.models.common import ShardCtx
    from repro.models.ssm import SSMCfg

    arch = ArchConfig(
        name="t", family="ssm", num_layers=cfg["n_layer"],
        d_model=cfg["d_model"], num_heads=1, num_kv_heads=1,
        head_dim=cfg["headdim"], d_ff=0, vocab_size=cfg["vocab_size"],
        tie_embeddings=True,
        ssm=SSMCfg(d_state=cfg["d_state"], head_dim=cfg["headdim"],
                   expand=cfg["expand"], n_groups=cfg["ngroups"],
                   conv_width=cfg["d_conv"], chunk=cfg["chunk_size"]))
    run = RunConfig(model_parallel=False, seq_shard=False, remat=False)
    _, specs = model_lib.init(jax.random.PRNGKey(0), arch, ShardCtx(),
                              {"data": 1, "model": 1}, run, abstract=True)
    return arch, run, specs, model_lib


def test_model_matches_program_in_float32():
    cfg = tiny()
    arch, run, specs, model_lib = program_model(cfg)
    from repro.models.common import ShardCtx

    params = weights.make(cfg, 7)
    rows = traffic.token_rows(7, 3, 64, cfg["vocab_size"], 7, 16)
    batch = {"tokens": jnp.asarray(rows),
             "labels": jnp.asarray(np.roll(rows, -1, 1)),
             "mask": jnp.ones(rows.shape)}
    ctx = ShardCtx(tp=1, fsdp=False, compute_dtype=jnp.float32,
                   seq_shard=False)
    with jax.default_matmul_precision("highest"):
        lp, gp = jax.value_and_grad(lambda p: model_lib.train_loss(
            ctx, p, specs, arch, run, batch, float(rows.size))[0])(params)
        lr, gr = reference.node_loss_grad(params, batch, cfg, "f32",
                                          float(rows.size))
    # Same equations in float32; only the summation order differs.
    assert abs(float(lp) - float(lr)) <= 1e-5 * abs(float(lr))
    for k in gr:
        err = float(jnp.linalg.norm(gp[k] - gr[k]))
        assert err <= 1e-4 * float(jnp.linalg.norm(gr[k])), k


@pytest.mark.parametrize("config,traffic_name", [
    ("mamba2-130m", "train.ef_rotated_binary"),
    ("mamba2-370m", "train.bernoulli_seed_1bit"),
    ("mamba2-130m", "train.exact"),
])
def test_bucket_plan_matches_program(config, traffic_name):
    from repro.core import types as ct
    from repro.train import bucketing

    cfg = json.loads((CONFIGS / f"{config}.json").read_text())
    comp = json.loads(
        (WORKLOADS / f"{traffic_name}.json").read_text())["compression"]
    cc = dict(comp)
    cmp = ct.CompressionConfig(
        encoder=ct.EncoderSpec(**cc.pop("encoder")),
        bucket=ct.BucketSpec(**cc.pop("bucket")),
        axes=tuple(cc.pop("axes")), inner_axes=tuple(cc.pop("inner_axes")),
        **cc)
    shapes = weights.param_shapes(cfg)
    specs = {k: (None,) * len(s) for k, s in shapes.items()}
    plan = bucketing.build_plan(shapes, specs, ("data", "model"),
                                {"data": 1, "model": 1}, cmp)
    want = [(b.kind, [s.name for s in b.slots]) for b in plan.buckets]
    assert reference.plan(shapes, comp) == want


@pytest.mark.parametrize("d", [1000, 4096, (1 << 20) + 3])
def test_rotation_matches_program(d):
    from repro.core import rotation

    key = jax.random.fold_in(jax.random.PRNGKey(3), 11)
    x = jax.random.normal(jax.random.PRNGKey(1), (d,))
    want = rotation.rotate(key, x)
    with jax.threefry_partitionable(False):
        got = reference.rotate(key, x[None])[0]
    np.testing.assert_allclose(got, want, rtol=0, atol=1e-5)


@pytest.mark.parametrize("name", ["train.ef_rotated_binary",
                                  "train.bernoulli_seed_1bit"])
def test_sync_matches_program(name):
    """Two steps of the program's bucketed sync (EF residual carried) on one
    node equal the reference's, leaf by leaf."""
    from repro.core import types as ct
    from repro.train import bucketing

    cfg = tiny()
    comp = tiny_traffic(name)["compression"]
    cc = dict(comp)
    cmp = ct.CompressionConfig(
        encoder=ct.EncoderSpec(**cc.pop("encoder")),
        bucket=ct.BucketSpec(**cc.pop("bucket")),
        axes=tuple(cc.pop("axes")), inner_axes=tuple(cc.pop("inner_axes")),
        **cc)
    shapes = weights.param_shapes(cfg)
    specs = {k: (None,) * len(s) for k, s in shapes.items()}
    mesh = jax.make_mesh((1, 1), ("data", "model"))
    plan = bucketing.build_plan(shapes, specs, ("data", "model"),
                                {"data": 1, "model": 1}, cmp)
    ef_prog = (bucketing.init_ef_state(plan, cmp) if cmp.error_feedback
               else None)
    ef_ref = reference.ef_init(shapes, comp, 1)
    for step in range(2):
        grads = weights.make(cfg, 100 + step)
        key = jax.random.fold_in(jax.random.PRNGKey(0), step)

        def prog_sync(g, ef):
            return bucketing.sync_grads_bucketed(g, plan, cmp, key, ef)

        gspec = {k: P() for k in grads}
        efspec = None if ef_prog is None else {k: P() for k in ef_prog}
        got, ef_prog = jax.jit(jax.shard_map(
            prog_sync, mesh=mesh, in_specs=(gspec, efspec),
            out_specs=(gspec, efspec), check_vma=False))(grads, ef_prog)
        with jax.threefry_partitionable(False):
            want, ef_ref = reference.sync(
                {k: v[None] for k, v in grads.items()}, ef_ref, comp, key)
        for k in grads:
            # bf16 wire values: a sum order that differs can move a cluster
            # mean across a rounding boundary, one bf16 ulp (2**-8) of it.
            err = float(jnp.max(jnp.abs(got[k] - want[k][0])))
            assert err <= 2 ** -8 * float(jnp.max(jnp.abs(want[k][0]))), k
