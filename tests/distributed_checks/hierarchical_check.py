"""Multi-device (16 fake CPU devices) validation of the hierarchical
two-level + reduce-scatter compressed collectives (docs/DESIGN.md §11).
Run by tests/test_hierarchical.py in a subprocess:

    python hierarchical_check.py

Checks:
  * node-count sweep n ∈ {4, 8, 16} over (pod, data) = (n/2, 2) meshes:
    the hierarchical path (exact pmean inside the data axis, codec across
    the pod axis, reduce-scatter decode sharded over the inner group) is
    BIT-exact vs the flat reference — pmean over the inner axis followed
    by the flat codec over the pod axis — for every linear preset,
    including the rotated and error-feedback compositions;
  * per lowered HLO at n = 8: exactly ONE cross-host collective per round
    (replica-groups classifier: a collective is cross-host iff some group
    spans two inner blocks), its payload bits == codec.wire_bits at the
    effective node count == cost_config(..., mesh_sizes) − seed_bits, and
    the cross-host bytes shrink by exactly the inner-group factor vs the
    flat all-axes config;
  * bucketed sync (sync_grads_bucketed) on the 2-level mesh issues exactly
    one cross-host collective per compressed bucket, with
    bucket_wire_bits(plan, cfg, n, mesh_sizes) matching the HLO bits.
Exits non-zero on failure.
"""
import os

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=16"

import dataclasses  # noqa: E402
import functools  # noqa: E402
import re  # noqa: E402

import jax  # noqa: E402
import numpy as np  # noqa: E402
from jax.sharding import Mesh  # noqa: E402
from jax.sharding import PartitionSpec as P  # noqa: E402

from repro.core import collectives, comm_cost, types, wire  # noqa: E402
from repro.train import bucketing  # noqa: E402

D = 5000                # NOT a power of two: exercises shard-pad tails
N_IN = 2                # inner (intra-host) group size of every sweep mesh
SWEEP = (4, 8, 16)      # total node counts; (pod, data) = (n/2, 2)


def check(name, ok, detail=""):
    print(f"[{'ok' if ok else 'FAIL'}] {name} {detail}")
    if not ok:
        raise SystemExit(f"FAILED: {name} {detail}")


def enc(kind, **kw):
    return types.EncoderSpec(kind=kind, fraction=1.0 / 16, center="mean",
                             **kw)


def hier_cfg(encoder, **kw):
    return types.CompressionConfig(
        encoder=encoder, mode="gather_decode", axes=("pod",),
        inner_axes=("data",), scatter_decode=True, wire_dtype="float32",
        min_compress_size=0, **kw)


# every linear preset + its rotated / EF compositions, plus the two-level
# schedule without the scatter decode (hierarchy and scatter are
# independently selectable).
PRESETS = {
    "fixed_k": hier_cfg(enc("fixed_k")),
    "bernoulli": hier_cfg(enc("bernoulli")),
    "rotated_fixed_k": hier_cfg(enc("fixed_k", rotation=True)),
    "ef_bernoulli": hier_cfg(enc("bernoulli"), error_feedback=True),
    "fixed_k_noscatter": dataclasses.replace(hier_cfg(enc("fixed_k")),
                                             scatter_decode=False),
}


def mesh_for(n):
    return Mesh(np.array(jax.devices()[:n]).reshape(n // N_IN, N_IN),
                ("pod", "data"))


def run_hier(cfg, mesh, xs, key):
    @functools.partial(jax.shard_map, mesh=mesh,
                       in_specs=(P(("pod", "data")), P()), out_specs=P(),
                       check_vma=False)
    def f(xs, key):
        return collectives.compressed_mean(xs.reshape(D), key, cfg)
    return jax.jit(f)


def run_ref(cfg, mesh):
    """pmean over the inner axis, then the FLAT codec across pod."""
    flat = dataclasses.replace(cfg, inner_axes=(), scatter_decode=False)

    @functools.partial(jax.shard_map, mesh=mesh,
                       in_specs=(P(("pod", "data")), P()), out_specs=P(),
                       check_vma=False)
    def f(xs, key):
        v = jax.lax.pmean(xs.reshape(D), ("data",))
        return collectives.compressed_mean(v, key, flat)
    return jax.jit(f)


def parse_collectives(txt):
    """[(kind, bits, groups)] for every collective in the HLO text."""
    out = []
    for line in txt.splitlines():
        m = re.search(r"= (f32|bf16|u32|s32|u16|u8|pred)\[([\d,]*)\]\S* "
                      r"(all-gather|all-reduce|reduce-scatter)"
                      r"(?:-start)?\(", line)
        if not m:
            continue
        width = {"f32": 32, "u32": 32, "s32": 32, "bf16": 16,
                 "u16": 16, "u8": 8, "pred": 8}[m.group(1)]
        size = 1
        for v in m.group(2).split(","):
            if v:
                size *= int(v)
        g = re.search(r"replica_groups=\{\{([\d,{} ]*)\}\}", line)
        groups = []
        if g:
            for grp in g.group(1).split("},{"):
                groups.append([int(v) for v in grp.split(",") if v.strip()])
        out.append((m.group(3), size * width, groups))
    return out


def cross_host(txt, n_in):
    """Collectives whose replica groups span two inner blocks (the slow
    link): device linear id = pod·n_in + data, so a group is cross-host
    iff its ids disagree on id // n_in."""
    return [(kind, bits, groups)
            for kind, bits, groups in parse_collectives(txt)
            if any(len({i // n_in for i in grp}) > 1 for grp in groups)]


# ---- node-count sweep: hierarchical bit-exact vs the flat reference ---------
KEY = jax.random.PRNGKey(7)
for n in SWEEP:
    mesh = mesh_for(n)
    xs = jax.random.normal(jax.random.PRNGKey(n), (n, D)) * 0.5
    for name, cfg in PRESETS.items():
        got = np.asarray(run_hier(cfg, mesh, xs, KEY)(xs, KEY))
        want = np.asarray(run_ref(cfg, mesh)(xs, KEY))
        check(f"n{n}.{name}.bit_exact", np.array_equal(got, want),
              f"max|diff|={float(np.max(np.abs(got - want))):.2e}")

# ---- HLO: one cross-host collective, exact effective-n accounting -----------
N = 8
N_OUT = N // N_IN
mesh = mesh_for(N)
MSIZES = {"pod": N_OUT, "data": N_IN}
xs = jax.random.normal(jax.random.PRNGKey(N), (N, D)) * 0.5
for name, cfg in PRESETS.items():
    codec = wire.resolve(cfg)
    txt = run_hier(cfg, mesh, xs, KEY).lower(xs, KEY).compile().as_text()
    cross = cross_host(txt, N_IN)
    check(f"hlo.{name}.one_cross_host", len(cross) == 1,
          f"cross-host collectives={[(k, b) for k, b, _ in cross]}")
    bits = cross[0][1]
    want = codec.wire_bits(N_OUT, D, cfg)
    check(f"hlo.{name}.bits_eq_wire_bits", bits == want,
          f"hlo={bits} wire_bits(n_eff={N_OUT})={want:.0f}")
    cost = comm_cost.cost_config(cfg, n=N, d=D, mesh_sizes=MSIZES)
    check(f"hlo.{name}.bits_eq_cost_config",
          bits == cost - codec.seed_bits(N_OUT, cfg),
          f"hlo={bits} cost={cost:.0f} seed={codec.seed_bits(N_OUT, cfg):.0f}")

    # the flat all-axes config ships n messages over the slow link — the
    # hierarchy shrinks cross-host bytes by exactly the inner-group factor.
    flat_all = dataclasses.replace(cfg, axes=("pod", "data"), inner_axes=(),
                                   scatter_decode=False)
    txt_flat = run_hier(flat_all, mesh, xs, KEY).lower(
        xs, KEY).compile().as_text()
    flat_bits = sum(b for _, b, _ in cross_host(txt_flat, N_IN))
    check(f"hlo.{name}.shrink_by_inner_factor", flat_bits == N_IN * bits,
          f"flat={flat_bits} hier={bits} factor={flat_bits / bits:.2f} "
          f"(want {N_IN})")

# ---- bucketed sync: one cross-host collective per compressed bucket ---------
BIG, SMALL = 4096, 64
SHAPES = {f"big_{i}": (BIG,) for i in range(4)}
SHAPES.update({f"small_{i}": (SMALL,) for i in range(6)})
SPECS = {nm: (None,) for nm in SHAPES}
BCFG = dataclasses.replace(
    hier_cfg(enc("bernoulli")), min_compress_size=1024,
    bucket=types.BucketSpec(capacity=2 * BIG))
plan = bucketing.build_plan(SHAPES, SPECS, ("pod", "data"), MSIZES, BCFG)
n_cmp = sum(1 for b in plan.buckets if b.kind == "compressed")
check("bucketed.plan", n_cmp == 2,
      f"compressed buckets={n_cmp} (want 2)")

key0 = jax.random.PRNGKey(1)
GXS = {nm: jax.random.normal(jax.random.fold_in(key0, h), (N,) + SHAPES[nm])
       for h, nm in enumerate(sorted(SHAPES))}
txt = jax.jit(
    functools.partial(jax.shard_map, mesh=mesh,
                      in_specs=({nm: P(("pod", "data"), None)
                                 for nm in SHAPES}, P()),
                      out_specs={nm: P() for nm in SHAPES},
                      check_vma=False)(
        lambda xs, key: bucketing.sync_grads_bucketed(
            {nm: xs[nm].reshape(SHAPES[nm]) for nm in xs},
            plan, BCFG, key)[0])
).lower(GXS, jax.random.PRNGKey(0)).compile().as_text()
cross = cross_host(txt, N_IN)
cross_ag = [c for c in cross if c[0] == "all-gather"]
cross_ar = [c for c in cross if c[0] != "all-gather"]
check("bucketed.one_cross_gather_per_compressed_bucket",
      len(cross_ag) == n_cmp,
      f"cross-host gathers={len(cross_ag)} (want {n_cmp})")
# the exact bucket's single pmean spans both axes — one cross-host
# all-reduce; nothing else may touch the slow link.
check("bucketed.exact_bucket_single_cross_reduce", len(cross_ar) == 1,
      f"cross-host reduces={[(k, b) for k, b, _ in cross_ar]} (want 1)")
want_bits = bucketing.bucket_wire_bits(plan, BCFG, N, MSIZES)
check("bucketed.wire_bits_match_hlo",
      sorted(b for _, b, _ in cross_ag) == sorted(want_bits.values()),
      f"hlo={sorted(b for _, b, _ in cross_ag)} "
      f"accounting={sorted(want_bits.values())}")

print("ALL HIERARCHICAL CHECKS PASSED")
