"""Multi-device (8 fake CPU devices) validation of the packed bit-plane
binary/ternary wire paths (repro.core.bitplane + collectives).  Run by
tests/test_quantized_wire.py in a subprocess:

    XLA_FLAGS=--xla_force_host_platform_device_count=8 python quantized_wire_check.py

Checks:
  * binary/ternary gather_decode means == dense_sim_mean to fp tolerance
    (same keys, f32 wire: the packed planes reproduce the dense encoders
    bit-for-bit, so only summation-order noise remains);
  * exactly ONE collective launch per bucket in the lowered HLO of a
    bucketed sync (one all-gather per compressed bucket, one all-reduce
    per exact bucket);
  * HLO-measured gather bits per bucket == bucketing.bucket_wire_bits ==
    comm_cost.cost_binary_packed / cost_ternary_packed (no seed-bit term:
    the planes travel explicitly, unlike the §4.4 Bernoulli path);
  * the packed wire is honestly sub-dense (binary < 1/8 of f32 bits).
Exits non-zero on failure.
"""
import os

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"

import functools  # noqa: E402
import re  # noqa: E402

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from jax.sharding import PartitionSpec as P  # noqa: E402

from repro.core import bitplane, collectives, comm_cost, types  # noqa: E402
from repro.train import bucketing  # noqa: E402

N = 8
D = 5000                # deliberately NOT a multiple of 32: exercises tails
BIG = 4096
SMALL = 64

mesh = jax.make_mesh((N,), ("data",))
MESH_AXES = ("data",)
MSIZES = {"data": N}

XS = jax.random.normal(jax.random.PRNGKey(0), (N, D)) * 0.3


def check(name, ok, detail=""):
    print(f"[{'ok' if ok else 'FAIL'}] {name} {detail}")
    if not ok:
        raise SystemExit(f"FAILED: {name} {detail}")


def mkcfg(kind, mode, frac=0.125):
    return types.CompressionConfig(
        encoder=types.EncoderSpec(kind=kind, fraction=frac, center="min"),
        mode=mode, axes=("data",), wire_dtype="float32", min_compress_size=0)


def run_mean(cfg):
    @functools.partial(jax.shard_map, mesh=mesh, in_specs=(P("data"), P()),
                       out_specs=P(), check_vma=False)
    def f(xs, key):
        return collectives.compressed_mean(xs.reshape(D), key, cfg)
    return jax.jit(f)


# ---- wire path == dense simulation, per mode --------------------------------
for kind in ("binary", "ternary"):
    key = jax.random.PRNGKey(11)
    y_wire = np.asarray(run_mean(mkcfg(kind, "gather_decode"))(XS, key))
    y_dense = np.asarray(run_mean(mkcfg(kind, "dense_sim"))(XS, key))
    err = float(np.max(np.abs(y_wire - y_dense)))
    check(f"{kind}.wire_eq_dense", err < 1e-5, f"max|diff|={err:.2e}")
    # and both are plausible mean estimates (not garbage): bounded error
    mse = float(np.mean((y_wire - np.asarray(jnp.mean(XS, axis=0))) ** 2))
    check(f"{kind}.wire_sane", np.isfinite(mse) and mse < 1.0,
          f"mse={mse:.3e}")

# ---- one collective launch per bucket + exact bit accounting ----------------
SHAPES = {f"big_{i}": (BIG,) for i in range(4)}
SHAPES.update({f"small_{i}": (SMALL,) for i in range(6)})
SPECS = {n: (None,) for n in SHAPES}
key0 = jax.random.PRNGKey(1)
GXS = {n: jax.random.normal(jax.random.fold_in(key0, h), (N,) + SHAPES[n])
       for h, n in enumerate(sorted(SHAPES))}
IN_SPECS = {n: P("data", None) for n in SHAPES}
OUT_SPECS = {n: P() for n in SHAPES}

for kind in ("binary", "ternary"):
    cfg = types.CompressionConfig(
        encoder=types.EncoderSpec(kind=kind, fraction=0.125, center="min"),
        mode="gather_decode", axes=("data",), wire_dtype="float32",
        min_compress_size=1024, bucket=types.BucketSpec(capacity=2 * BIG))
    plan = bucketing.build_plan(SHAPES, SPECS, MESH_AXES, MSIZES, cfg)
    n_cmp = sum(1 for b in plan.buckets if b.kind == "compressed")
    n_ex = sum(1 for b in plan.buckets if b.kind == "exact")
    check(f"{kind}.plan", n_cmp == 2 and n_ex == 1,
          f"compressed={n_cmp} exact={n_ex}")

    txt = jax.jit(
        functools.partial(jax.shard_map, mesh=mesh,
                          in_specs=(IN_SPECS, P()), out_specs=OUT_SPECS,
                          check_vma=False)(
            lambda xs, key, plan=plan, cfg=cfg: bucketing.sync_grads_bucketed(
                {n: xs[n].reshape(SHAPES[n]) for n in xs}, plan, cfg, key)[0])
    ).lower(GXS, jax.random.PRNGKey(0)).compile().as_text()

    # exactly one collective launch per bucket: one all-gather per
    # compressed bucket, one all-reduce per exact bucket.
    n_ag = len(re.findall(r"= \S+ all-gather(?:-start)?\(", txt))
    n_ar = len(re.findall(r"= \S+ all-reduce(?:-start)?\(", txt))
    check(f"{kind}.one_launch_per_bucket", n_ag == n_cmp and n_ar == n_ex,
          f"all-gather={n_ag} (want {n_cmp}) all-reduce={n_ar} (want {n_ex})")

    # HLO-measured gather bits == bucket_wire_bits == comm_cost packed form.
    want_bits = bucketing.bucket_wire_bits(plan, cfg, N)
    spec32 = types.CommSpec(protocol=kind, r_bits=32)
    measured = 0.0
    expect_cost = 0.0
    for b in plan.buckets:
        if b.kind != "compressed":
            continue
        if kind == "binary":
            w = bitplane.binary_wire_words(b.size, cfg.wire_dtype)
            expect_cost += comm_cost.cost_binary_packed(N, b.size, spec32)
        else:
            cap = comm_cost.bernoulli_capacity(b.size, 0.125)
            w = bitplane.ternary_wire_words(b.size, cap, cfg.wire_dtype)
            expect_cost += comm_cost.cost_ternary_packed(N, b.size, cap,
                                                         spec32)
        check(f"{kind}.hlo_gather[{b.bid}]", f"u32[{N},{w}]" in txt,
              f"expected an all-gather result u32[{N},{w}] on the wire")
        measured += N * w * 32
        check(f"{kind}.bucket_wire_bits[{b.bid}]",
              want_bits[b.bid] == N * w * 32,
              f"accounting={want_bits[b.bid]:.0f} wire={N * w * 32}")
    check(f"{kind}.bit_accounting", measured == expect_cost,
          f"measured={measured:.0f} want={expect_cost:.0f}")
    if kind == "binary":
        dense_bits = sum(32 * N * b.size for b in plan.buckets
                         if b.kind == "compressed")
        check("binary.sub_dense", measured * 8 < dense_bits,
              f"wire={measured:.0f} dense={dense_bits:.0f}")

print("ALL QUANTIZED WIRE CHECKS PASSED")
