"""Multi-device (8 fake CPU devices) validation of the error-feedback wire
layer (repro.core.wire.ef) — every registered EF codec end-to-end.  Run by
tests/test_ef_wire.py in a subprocess and directly in the CI matrix:

    python ef_wire_check.py

Checks, per EF codec:
  * payload identity: the lowered HLO of the STATEFUL round (residual as a
    real carried input) gathers buffers of EXACTLY the inner codec's
    shapes, in exactly one launch — the residual never travels, EF is
    wire-free by construction;
  * analytic accounting: wire_bits / comm_cost_bits equal the inner
    codec's, and bucket-style accounting (bucket_wire_bits) agrees;
  * multi-step contraction: over T rounds on constant inputs the
    time-averaged EF estimate's bias falls strictly below the EF-free
    codec's Monte-Carlo average at the same wire budget (the telescoping
    (1/T)Σ m̄_t = x̄ + (ē_0 − ē_T)/T versus the unbiased codec's √(MSE/T)
    noise floor), and below an absolute floor;
  * residual sanity: finite, nonzero (the compressor is lossy), and the
    state pytree round-trips through the shard_map carry.
Exits non-zero on failure.
"""
import os

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"

import dataclasses  # noqa: E402
import functools  # noqa: E402
import re  # noqa: E402

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from jax.sharding import PartitionSpec as P  # noqa: E402

from repro.configs import registry as cfg_registry  # noqa: E402
from repro.core import collectives, types, wire  # noqa: E402

N = 8
D = 8192                # power of two: rotated payloads equal un-rotated
FRAC = 0.25
TRIALS = 64

mesh = jax.make_mesh((N,), ("data",))

# anisotropic inputs: spiky coordinates are where the quantizer twins and
# the rotation earn their keep, and where the EF-free MC noise floor is
# highest — the regime EF is for.
XS = jax.random.normal(jax.random.PRNGKey(0), (N, D)) * 0.3
XS = XS.at[:, :4].add(jnp.array([6.0, -5.0, 4.0, -3.0]))
TRUE = np.asarray(jnp.mean(XS, axis=0))

# every registered EF codec, as a config the registry resolves back to it.
EF_PRESETS = {
    "ef_fixed_k": ("fixed_k", "gather_decode", {"center": "mean"}),
    "ef_fixed_k_shared": ("fixed_k", "shared_support", {"center": "mean"}),
    "ef_bernoulli": ("bernoulli", "gather_decode", {"center": "mean"}),
    "ef_binary": ("binary", "gather_decode", {"center": "min"}),
    "ef_ternary": ("ternary", "gather_decode", {"center": "min"}),
    "ef_rotated_binary": ("binary", "gather_decode",
                          {"center": "min", "rotation": True}),
}


def check(name, ok, detail=""):
    print(f"[{'ok' if ok else 'FAIL'}] {name} {detail}")
    if not ok:
        raise SystemExit(f"FAILED: {name} {detail}")


def mkcfg(kind, mode, extra, ef):
    enc = types.EncoderSpec(kind=kind, fraction=FRAC, **extra)
    return types.CompressionConfig(
        encoder=enc, mode=mode, axes=("data",), wire_dtype="float32",
        min_compress_size=0, error_feedback=ef)


def lower_stateful_text(cfg):
    """Lower ONE stateful round with the residual as a real carried input
    (not a constant-folded zero) — what the train step executes."""
    @functools.partial(jax.shard_map, mesh=mesh,
                       in_specs=(P("data"), P("data"), P()),
                       out_specs=(P(), P("data")), check_vma=False)
    def f(xs, state, key):
        return collectives.compressed_mean_stateful(
            xs.reshape(D), state.reshape(D), key, cfg)
    return jax.jit(f).lower(
        jax.ShapeDtypeStruct((N, D), jnp.float32),
        jax.ShapeDtypeStruct((N, D), jnp.float32),
        jax.ShapeDtypeStruct((2,), jnp.uint32)).compile().as_text()


def lower_plain_text(cfg):
    @functools.partial(jax.shard_map, mesh=mesh, in_specs=(P("data"), P()),
                       out_specs=P(), check_vma=False)
    def f(xs, key):
        return collectives.compressed_mean(xs.reshape(D), key, cfg)
    return jax.jit(f).lower(
        jax.ShapeDtypeStruct((N, D), jnp.float32),
        jax.ShapeDtypeStruct((2,), jnp.uint32)).compile().as_text()


def gathered(txt):
    """(shape, bits) of every collective wire op in the lowered HLO."""
    bits_of = {"f32": 32, "u32": 32, "bf16": 16}
    out = []
    for dt, dims, op in re.findall(
            r"= (f32|u32|bf16)\[([\d,]+)\]\S* (all-gather|all-reduce)"
            r"(?:-start)?\(", txt):
        b = bits_of[dt]
        for x in dims.split(","):
            b *= int(x)
        out.append((f"{dt}[{dims}]:{op}", b * (N if op == "all-reduce" else 1)))
    return sorted(out)


K0 = jax.random.PRNGKey(13)
for name, (kind, mode, extra) in EF_PRESETS.items():
    cfg_ef = mkcfg(kind, mode, extra, ef=True)
    cfg_plain = mkcfg(kind, mode, extra, ef=False)
    codec = wire.resolve(cfg_ef)
    inner = wire.resolve(cfg_plain)
    check(f"{name}.resolves", codec.name == name and codec.inner is inner
          and codec.stateful and codec.state_shape(D, cfg_ef) == (D,))

    # ---- HLO: the stateful round's wire == the inner codec's, 1 launch --- #
    g_ef = gathered(lower_stateful_text(cfg_ef))
    g_plain = gathered(lower_plain_text(cfg_plain))
    check(f"{name}.one_launch", len(g_ef) == 1 and len(g_plain) == 1,
          f"ef={g_ef} plain={g_plain}")
    check(f"{name}.residual_never_travels", g_ef == g_plain,
          f"ef={g_ef} plain={g_plain}")
    check(f"{name}.hlo_bits_match_accounting",
          g_ef[0][1] == codec.wire_bits(N, D, cfg_ef)
          and codec.wire_bits(N, D, cfg_ef) == inner.wire_bits(N, D, cfg_plain),
          f"hlo={g_ef[0][1]} codec={codec.wire_bits(N, D, cfg_ef):.0f}")

    # ---- contraction: EF time-average beats the EF-free MC average -------- #
    @functools.partial(jax.shard_map, mesh=mesh,
                       in_specs=(P("data"), P()), out_specs=(P(), P(), P()),
                       check_vma=False)
    def trial(xs, key, cfg_ef=cfg_ef, cfg_plain=cfg_plain):
        x = xs.reshape(D)

        def body(t, carry):
            err, acc_ef, acc_pl = carry
            kt = jax.random.fold_in(key, t)
            est, err = collectives.compressed_mean_stateful(
                x, err, kt, cfg_ef)
            est_pl = collectives.compressed_mean(x, kt, cfg_plain)
            return err, acc_ef + est, acc_pl + est_pl

        err, acc_ef, acc_pl = jax.lax.fori_loop(
            0, TRIALS, body, (jnp.zeros(D), jnp.zeros(D), jnp.zeros(D)))
        return acc_ef / TRIALS, acc_pl / TRIALS, jnp.sum(err * err)

    avg_ef, avg_pl, err_ss = jax.jit(trial)(XS, K0)
    rmse_ef = float(np.sqrt(np.mean((np.asarray(avg_ef) - TRUE) ** 2)))
    rmse_pl = float(np.sqrt(np.mean((np.asarray(avg_pl) - TRUE) ** 2)))
    check(f"{name}.ef_beats_plain_time_average", rmse_ef < 0.6 * rmse_pl,
          f"ef={rmse_ef:.5f} plain={rmse_pl:.5f}")
    # absolute floor: un-rotated 1-bit keeps an O(range) residual on spiky
    # inputs (its two cluster centers can't capture the outliers — exactly
    # the deficiency §7.2 rotation fixes, cf. ef_rotated_binary's floor).
    floor = 0.12 if name == "ef_binary" else 0.02
    check(f"{name}.ef_converges", rmse_ef < floor, f"rmse={rmse_ef:.5f}")
    check(f"{name}.residual_finite_nonzero",
          np.isfinite(float(err_ss)) and float(err_ss) > 0.0,
          f"|e|^2={float(err_ss):.3e}")

# ---- the registry presets resolve to these codecs end-to-end --------------- #
for pname in ("ef_fixed_k", "ef_bernoulli", "ef_binary", "ef_ternary",
              "ef_rotated_binary"):
    pcfg = cfg_registry.compression_preset(pname, axes=("data",))
    check(f"preset.{pname}", wire.resolve(pcfg).name == pname
          and pcfg.error_feedback)

print("ALL EF WIRE CHECKS PASSED")
