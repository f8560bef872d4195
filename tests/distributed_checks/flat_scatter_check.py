"""Multi-device (8 fake CPU devices) validation of the FLAT-mesh
reduce-scatter decode (docs/DESIGN.md §12).  Run by
tests/test_decode_scatter.py in a subprocess:

    python flat_scatter_check.py

Checks:
  * for every coordinate-partitionable flat-scatter config (bernoulli —
    the shipped `bernoulli_seed_1bit` preset — fixed_k, and the §13
    word-aligned bit-plane pair binary/ternary), the scatter-decode mean
    is BIT-exact vs the no-scatter flat reference across n ∈ {2, 4, 8}:
    each node decodes only its shard (⌈d/n⌉, word-aligned for the packed
    planes) of all n peer rows and one all_gather of decoded shards
    reassembles the mean;
  * per lowered HLO at n = 8: the scatter round launches exactly the
    expected extra all-gathers on top of the wire-row gather (bernoulli /
    ternary: i32 counts + decoded f32 shard; fixed_k / binary: decoded
    shard only — their coordinate windows are analytic), and the total
    gathered payload bits == codec.wire_bits + codec.scatter_bits ==
    cost_config − seed_bits — the honest billing of the extra intra-mesh
    traffic;
  * bucketed sync (sync_grads_bucketed) with a flat-scatter config
    launches exactly 3 gathers per compressed bucket and the summed HLO
    gather bits equal Σ bucket_wire_bits(plan, cfg, n) — per-bucket
    accounting includes the scatter collectives.
Exits non-zero on failure.
"""
import os

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"

import dataclasses  # noqa: E402
import functools  # noqa: E402
import re  # noqa: E402

import jax  # noqa: E402
import numpy as np  # noqa: E402
from jax.sharding import Mesh  # noqa: E402
from jax.sharding import PartitionSpec as P  # noqa: E402

from repro.configs import registry as cfg_registry  # noqa: E402
from repro.core import collectives, comm_cost, types, wire  # noqa: E402
from repro.train import bucketing  # noqa: E402

D = 5000                # NOT a multiple of 8: the tail shard is short
SWEEP = (2, 4, 8)


def check(name, ok, detail=""):
    print(f"[{'ok' if ok else 'FAIL'}] {name} {detail}")
    if not ok:
        raise SystemExit(f"FAILED: {name} {detail}")


def scatter_cfg(kind):
    return types.CompressionConfig(
        encoder=types.EncoderSpec(kind=kind, fraction=1.0 / 16,
                                  center="mean"),
        mode="gather_decode", axes=("data",), scatter_decode=True,
        wire_dtype="float32", min_compress_size=0)


def plane_cfg(kind):
    enc = (types.EncoderSpec(kind="binary", center="min")
           if kind == "binary" else
           types.EncoderSpec(kind="ternary", fraction=1.0 / 16,
                             center="min"))
    return types.CompressionConfig(
        encoder=enc, mode="gather_decode", axes=("data",),
        scatter_decode=True, wire_dtype="float32", min_compress_size=0)


# extra all-gathers the scatter round adds on top of the wire-row gather:
# bernoulli ships the i32 rank-offset counts + the decoded shard, ternary
# its i32 pass-through counts + the decoded shard; fixed_k's dump-row
# window and binary's word window are analytic, so only the decoded shard
# travels.
PRESETS = {
    "bernoulli": (scatter_cfg("bernoulli"), 2),
    "fixed_k": (scatter_cfg("fixed_k"), 1),
    "binary": (plane_cfg("binary"), 1),
    "ternary": (plane_cfg("ternary"), 2),
}


def mesh_for(n):
    return Mesh(np.array(jax.devices()[:n]), ("data",))


def run_mean(cfg, n, xs, key):
    @functools.partial(jax.shard_map, mesh=mesh_for(n),
                       in_specs=(P("data"), P()), out_specs=P(),
                       check_vma=False)
    def f(x, k):
        return collectives.compressed_mean(x.reshape(D), k, cfg)
    return jax.jit(f)


def gathers(txt):
    """[(dtype, bits)] of every all-gather in the lowered HLO."""
    nbits = {"f32": 32, "u32": 32, "s32": 32, "bf16": 16}
    out = []
    for dt, dims in re.findall(
            r"= (f32|u32|s32|bf16)\[([\d,]+)\]\S* all-gather"
            r"(?:-start)?\(", txt):
        b = nbits[dt]
        for v in dims.split(","):
            b *= int(v)
        out.append((dt, b))
    return out


# ---- scatter == no-scatter flat reference, bit for bit, across n ------------
for name, (cfg, _) in PRESETS.items():
    flat = dataclasses.replace(cfg, scatter_decode=False)
    for n in SWEEP:
        xs = jax.random.normal(jax.random.PRNGKey(n), (n, D)) * 0.3
        key = jax.random.PRNGKey(17)
        y_sc = np.asarray(run_mean(cfg, n, xs, key)(xs, key))
        y_fl = np.asarray(run_mean(flat, n, xs, key)(xs, key))
        check(f"{name}.scatter_bitexact[n={n}]",
              np.array_equal(y_sc, y_fl),
              f"max|diff|={np.max(np.abs(y_sc - y_fl)):.2e}")

# the shipped presets engage the flat scatter path out of the box
for pname in ("bernoulli_seed_1bit", "binary_packed", "ternary_packed",
              "ef_binary", "ef_ternary", "ef_rotated_binary"):
    preset = dataclasses.replace(
        cfg_registry.compression_preset(pname, axes=("data",)),
        wire_dtype="float32", min_compress_size=0)
    check(f"preset.{pname}_is_flat_scatter",
          preset.scatter_decode and not preset.inner_axes, f"{preset.mode}")

# ---- HLO: 3 gathers, payload == wire_bits + scatter_bits --------------------
N = 8
for name, (cfg, extra) in PRESETS.items():
    codec = wire.resolve(cfg)
    txt = run_mean(cfg, N, None, None).lower(
        jax.ShapeDtypeStruct((N, D), np.float32),
        jax.ShapeDtypeStruct((2,), np.uint32)).compile().as_text()
    ag = gathers(txt)
    flat_txt = run_mean(dataclasses.replace(cfg, scatter_decode=False),
                        N, None, None).lower(
        jax.ShapeDtypeStruct((N, D), np.float32),
        jax.ShapeDtypeStruct((2,), np.uint32)).compile().as_text()
    n_flat = len(gathers(flat_txt))
    check(f"{name}.extra_gathers", len(ag) == n_flat + extra,
          f"scatter round: {len(ag)} gathers (flat: {n_flat}, "
          f"want +{extra}); {ag}")
    want = codec.wire_bits(N, D, cfg) + codec.scatter_bits(N, D, cfg)
    got = sum(b for _, b in ag)
    check(f"{name}.payload_bits", got == want,
          f"hlo={got:.0f} accounting={want:.0f}")
    # cost_config bills exactly the HLO payload plus the out-of-band seeds
    cost = comm_cost.cost_config(cfg, n=N, d=D)
    check(f"{name}.cost_config", cost == want + codec.seed_bits(N, cfg),
          f"cost={cost:.0f} payload+seeds="
          f"{want + codec.seed_bits(N, cfg):.0f}")

# ---- bucketed sync: 3 gathers + honest bits per compressed bucket -----------
BIG, SMALL = 4096, 64
SHAPES = {f"big_{i}": (BIG,) for i in range(4)}
SHAPES.update({f"small_{i}": (SMALL,) for i in range(6)})
SPECS = {nm: (None,) for nm in SHAPES}
BCFG = dataclasses.replace(
    scatter_cfg("bernoulli"), min_compress_size=1024,
    bucket=types.BucketSpec(capacity=2 * BIG))
plan = bucketing.build_plan(SHAPES, SPECS, ("data",), {"data": N}, BCFG)
n_cmp = sum(1 for b in plan.buckets if b.kind == "compressed")
check("bucketed.plan", n_cmp == 2, f"compressed buckets={n_cmp} (want 2)")

key0 = jax.random.PRNGKey(1)
GXS = {nm: jax.random.normal(jax.random.fold_in(key0, h), (N,) + SHAPES[nm])
       for h, nm in enumerate(sorted(SHAPES))}
txt = jax.jit(
    functools.partial(jax.shard_map, mesh=mesh_for(N),
                      in_specs=({nm: P("data", None) for nm in SHAPES}, P()),
                      out_specs={nm: P() for nm in SHAPES},
                      check_vma=False)(
        lambda xs, key: bucketing.sync_grads_bucketed(
            {nm: xs[nm].reshape(SHAPES[nm]) for nm in xs},
            plan, BCFG, key)[0])
).lower(GXS, jax.random.PRNGKey(0)).compile().as_text()
ag = gathers(txt)
check("bucketed.three_gathers_per_bucket", len(ag) == 3 * n_cmp,
      f"gathers={len(ag)} (want {3 * n_cmp})")
want_bits = bucketing.bucket_wire_bits(plan, BCFG, N)
check("bucketed.wire_bits_match_hlo",
      sum(b for _, b in ag) == sum(want_bits.values()),
      f"hlo={sum(b for _, b in ag):.0f} "
      f"accounting={sum(want_bits.values()):.0f}")

# bucketed scatter sync stays bit-exact vs the no-scatter bucketed sync
FCFG = dataclasses.replace(BCFG, scatter_decode=False)
fplan = bucketing.build_plan(SHAPES, SPECS, ("data",), {"data": N}, FCFG)


def sync(plan_, cfg_):
    @functools.partial(jax.shard_map, mesh=mesh_for(N),
                       in_specs=({nm: P("data", None) for nm in SHAPES},
                                 P()),
                       out_specs={nm: P() for nm in SHAPES},
                       check_vma=False)
    def f(xs, key):
        return bucketing.sync_grads_bucketed(
            {nm: xs[nm].reshape(SHAPES[nm]) for nm in xs},
            plan_, cfg_, key)[0]
    return jax.jit(f)(GXS, jax.random.PRNGKey(0))


got = sync(plan, BCFG)
ref = sync(fplan, FCFG)
for nm in sorted(SHAPES):
    check(f"bucketed.bitexact[{nm}]",
          np.array_equal(np.asarray(got[nm]), np.asarray(ref[nm])), "")

print("ALL FLAT SCATTER CHECKS PASSED")
