"""Multi-device (8 fake CPU devices) validation of the bucketed gradient
sync (repro.train.bucketing).  Run by tests/test_bucketing.py in a
subprocess:

    XLA_FLAGS=--xla_force_host_platform_device_count=8 python bucketing_check.py

Checks:
  * mode "none": bucketed sync == per-leaf exact pmean, elementwise;
  * shared_support: unbiased per leaf + per-bucket closed-form MSE
    (mse_fixed_k_shared on the concatenated bucket vectors);
  * gather_decode with the Bernoulli wire path: unbiased, and the gathered
    wire buffer's measured bits == comm_cost.cost(sparse_seed, cap=…) minus
    the seed bits (which ride the implicit PRNG — the §4.4 seed trick);
  * error feedback keyed by bucket id: time-averaged estimates converge on
    constant inputs.
Exits non-zero on failure.
"""
import os

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"

import functools  # noqa: E402

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from jax.sharding import PartitionSpec as P  # noqa: E402

from repro.core import collectives, comm_cost, mse, types  # noqa: E402
from repro.train import bucketing  # noqa: E402

N = 8
BIG = 4096              # = 4 blocks of fk.BLOCK; >= min_compress_size below
SMALL = 64
TRIALS = 200

mesh = jax.make_mesh((N,), ("data",))
MESH_AXES = ("data",)
MSIZES = {"data": N}

SHAPES = {f"big_{i:02d}": (BIG,) for i in range(6)}
SHAPES.update({f"small_{i:02d}": (SMALL,) for i in range(20)})
SPECS = {n: (None,) for n in SHAPES}

key0 = jax.random.PRNGKey(0)
XS = {n: jax.random.normal(jax.random.fold_in(key0, h), (N,) + SHAPES[n]) * 0.3
      for h, n in enumerate(sorted(SHAPES))}
TRUE = {n: np.asarray(jnp.mean(XS[n], axis=0)) for n in XS}

IN_SPECS = {n: P("data", None) for n in SHAPES}
OUT_SPECS = {n: P() for n in SHAPES}


def check(name, ok, detail=""):
    print(f"[{'ok' if ok else 'FAIL'}] {name} {detail}")
    if not ok:
        raise SystemExit(f"FAILED: {name} {detail}")


def mkcfg(**kw):
    kw.setdefault("axes", ("data",))
    kw.setdefault("min_compress_size", 1024)
    kw.setdefault("wire_dtype", "float32")
    kw.setdefault("bucket", types.BucketSpec(capacity=2 * BIG))
    return types.CompressionConfig(**kw)


def local_tree(xs):
    return {n: xs[n].reshape(SHAPES[n]) for n in xs}


# ---- plan shape sanity ------------------------------------------------------
cfg = mkcfg(encoder=types.EncoderSpec(kind="fixed_k", fraction=0.25,
                                      center="mean"),
            mode="shared_support")
plan = bucketing.build_plan(SHAPES, SPECS, MESH_AXES, MSIZES, cfg)
n_cmp = sum(1 for b in plan.buckets if b.kind == "compressed")
n_ex = sum(1 for b in plan.buckets if b.kind == "exact")
check("plan.shape", n_cmp == 3 and n_ex == 1 and not plan.passthrough,
      f"compressed={n_cmp} exact={n_ex} (6 big / cap 2·BIG; 20 small)")
check("plan.coverage", set(plan.leaf_names()) == set(SHAPES))

# ---- mode none: bucketed == exact pmean ------------------------------------
cfg_none = mkcfg(mode="none")
plan_none = bucketing.build_plan(SHAPES, SPECS, MESH_AXES, MSIZES, cfg_none)
check("plan.none_all_exact",
      all(b.kind == "exact" for b in plan_none.buckets))


@functools.partial(jax.shard_map, mesh=mesh, in_specs=(IN_SPECS, P()),
                   out_specs=OUT_SPECS, check_vma=False)
def sync_once_none(xs, key):
    est, _ = bucketing.sync_grads_bucketed(local_tree(xs), plan_none,
                                           cfg_none, key)
    return est


est = jax.jit(sync_once_none)(XS, jax.random.PRNGKey(1))
err = max(float(jnp.max(jnp.abs(est[n] - TRUE[n]))) for n in SHAPES)
check("none.exact", err < 1e-5, f"max|err|={err:.2e}")


# ---- shared_support: unbiased + per-bucket closed-form MSE ------------------
@functools.partial(jax.shard_map, mesh=mesh, in_specs=(IN_SPECS, P()),
                   out_specs=(OUT_SPECS, P(), P()), check_vma=False)
def trial_stats(xs, key):
    grads = local_tree(xs)

    def one(i, carry):
        acc, sq, small_err = carry
        est, _ = bucketing.sync_grads_bucketed(
            grads, plan, cfg, jax.random.fold_in(key, i))
        sq_i = sum(jnp.sum((est[n] - jnp.asarray(TRUE[n])) ** 2)
                   for n in SHAPES if n.startswith("big"))
        sm_i = jnp.max(jnp.stack(
            [jnp.max(jnp.abs(est[n] - jnp.asarray(TRUE[n])))
             for n in SHAPES if n.startswith("small")]))
        return ({n: acc[n] + est[n] for n in acc}, sq + sq_i,
                jnp.maximum(small_err, sm_i))

    zero = {n: jnp.zeros(SHAPES[n]) for n in SHAPES}
    acc, sq, small_err = jax.lax.fori_loop(
        0, TRIALS, one, (zero, jnp.zeros(()), jnp.zeros(())))
    return {n: acc[n] / TRIALS for n in acc}, sq / TRIALS, small_err


mean_est, mse_emp, small_err = jax.jit(trial_stats)(XS, jax.random.PRNGKey(7))
check("shared.small_leaves_exact", float(small_err) < 1e-5,
      f"max|err|={float(small_err):.2e}")

# per-bucket closed form: each compressed bucket concatenates two big
# leaves; the shared-support MSE adds across buckets (independent keys).
want = 0.0
for b in plan.buckets:
    if b.kind != "compressed":
        continue
    xs_b = jnp.concatenate([XS[s.name] for s in b.slots], axis=1)
    k = int(0.25 * (b.size // 1024)) * 1024
    want += float(mse.mse_fixed_k_shared(xs_b, k, jnp.mean(xs_b, axis=-1)))
D_big = 6 * BIG
bias = max(float(jnp.max(jnp.abs(mean_est[n] - jnp.asarray(TRUE[n]))))
           for n in SHAPES if n.startswith("big"))
check("shared.unbiased", bias < 6 * np.sqrt(want / D_big),
      f"max|bias|={bias:.4f}")
check("shared.bucket_mse", abs(float(mse_emp) - want) < 0.15 * want,
      f"emp={float(mse_emp):.4f} want={want:.4f}")

# ---- gather_decode + bernoulli: the wire path under bucketing ---------------
cfg_b = mkcfg(encoder=types.EncoderSpec(kind="bernoulli", fraction=0.25,
                                        center="mean"),
              mode="gather_decode")
plan_b = bucketing.build_plan(SHAPES, SPECS, MESH_AXES, MSIZES, cfg_b)


@functools.partial(jax.shard_map, mesh=mesh, in_specs=(IN_SPECS, P()),
                   out_specs=OUT_SPECS, check_vma=False)
def trial_mean_bern(xs, key):
    grads = local_tree(xs)

    def one(i, acc):
        est, _ = bucketing.sync_grads_bucketed(
            grads, plan_b, cfg_b, jax.random.fold_in(key, i))
        return {n: acc[n] + est[n] for n in acc}

    zero = {n: jnp.zeros(SHAPES[n]) for n in SHAPES}
    acc = jax.lax.fori_loop(0, TRIALS, one, zero)
    return {n: acc[n] / TRIALS for n in acc}


mean_b = jax.jit(trial_mean_bern)(XS, jax.random.PRNGKey(11))
want_b = 0.0
for b in plan_b.buckets:
    if b.kind != "compressed":
        continue
    xs_b = jnp.concatenate([XS[s.name] for s in b.slots], axis=1)
    want_b += float(mse.mse_bernoulli(xs_b, 0.25, jnp.mean(xs_b, axis=-1)))
bias_b = max(float(jnp.max(jnp.abs(mean_b[n] - jnp.asarray(TRUE[n]))))
             for n in SHAPES if n.startswith("big"))
check("bern.unbiased", bias_b < 6 * np.sqrt(want_b / D_big),
      f"max|bias|={bias_b:.4f}")

# ---- bernoulli bit accounting: measured wire == cost − seed bits ------------
# Lower one bucketed sync and read the gathered buffer straight from HLO:
# each compressed bucket all_gathers (cap + 1) f32 slots per node (values +
# μ); supports never travel (regenerated from fold_in — the §4.4 trick), so
# measured bits must equal cost_sparse_seed_capacity minus n·r̄_s exactly.
txt = jax.jit(
    functools.partial(jax.shard_map, mesh=mesh, in_specs=(IN_SPECS, P()),
                      out_specs=OUT_SPECS, check_vma=False)(
        lambda xs, key: bucketing.sync_grads_bucketed(
            local_tree(xs), plan_b, cfg_b, key)[0])
).lower(XS, jax.random.PRNGKey(0)).compile().as_text()
spec_f32 = types.CommSpec(protocol="sparse_seed", r_bits=32, rbar_bits=32)
measured_bits = 0.0
expect_bits = 0.0
for b in plan_b.buckets:
    if b.kind != "compressed":
        continue
    cap = comm_cost.bernoulli_capacity(b.size, 0.25)
    check(f"bern.hlo_gather[{b.bid}]", f"f32[{N},{cap + 1}]" in txt,
          f"expected an all-gather result f32[{N},{cap + 1}] on the wire")
    measured_bits += N * (cap + 1) * 32
    expect_bits += (comm_cost.cost(spec_f32, n=N, d=b.size, cap=cap)
                    - N * spec_f32.rseed_bits)
check("bern.bit_accounting", measured_bits == expect_bits,
      f"measured={measured_bits:.0f} want={expect_bits:.0f}")
# and the wire is honestly sub-dense: < 0.5 · naive f32 bits at p = 0.25
naive_bits = sum(32 * N * b.size for b in plan_b.buckets
                 if b.kind == "compressed")
check("bern.sub_dense", measured_bits < 0.5 * naive_bits,
      f"wire={measured_bits:.0f} dense={naive_bits:.0f}")

# ---- error feedback keyed by bucket id --------------------------------------
cfg_ef = mkcfg(encoder=types.EncoderSpec(kind="fixed_k", fraction=0.25,
                                         center="mean"),
               mode="shared_support", error_feedback=True)
plan_ef = bucketing.build_plan(SHAPES, SPECS, MESH_AXES, MSIZES, cfg_ef)
check("ef.state_keys",
      set(bucketing.init_ef_state(plan_ef, cfg_ef))
      == {b.bid for b in plan_ef.buckets if b.kind == "compressed"})


@functools.partial(jax.shard_map, mesh=mesh, in_specs=(IN_SPECS, P()),
                   out_specs=OUT_SPECS, check_vma=False)
def ef_many(xs, key):
    grads = local_tree(xs)

    def body(i, carry):
        ef, acc = carry
        est, ef = bucketing.sync_grads_bucketed(
            grads, plan_ef, cfg_ef, jax.random.fold_in(key, i), ef)
        return ef, {n: acc[n] + est[n] for n in acc}

    zero = {n: jnp.zeros(SHAPES[n]) for n in SHAPES}
    _, acc = jax.lax.fori_loop(
        0, 64, body, (bucketing.init_ef_state(plan_ef, cfg_ef), zero))
    return {n: acc[n] / 64 for n in acc}


avg = jax.jit(ef_many)(XS, jax.random.PRNGKey(9))
ef_rmse = max(
    float(jnp.sqrt(jnp.mean((avg[n] - jnp.asarray(TRUE[n])) ** 2)))
    for n in SHAPES if n.startswith("big"))
check("ef.converges", ef_rmse < 0.05, f"rmse={ef_rmse:.4f}")

print("ALL BUCKETING CHECKS PASSED")
