"""Multi-device (8 fake CPU devices) adversarial matrix for the robust
decode subsystem (docs/DESIGN.md §14).  Run by tests/test_robust_decode.py
in a subprocess:

    python robust_decode_check.py

Checks, per gather preset (axes re-pointed at the flat 8-device mesh):
  * Byzantine round — one adversarial peer of 8 replaces its REAL wire row
    (post-pack, pre-gather: integer planes corrupted through the f32
    bitcast) per corruption mode {nan, inf, sign_flip, boost}.  The
    trim(1) decode's error stays ≤ 2× its own clean-round error, while
    the plain mean decode blows past 10× (nan/inf/boost) or takes a
    bounded hit (sign_flip — a pure −row against a mean of 8 shifts the
    estimate by −2·row/8, which for zero-mean quantized rows may not
    even raise the error).
    The clean-decode yardstick is the max of the mean, trim(1) and
    trim(2) decoders' clean (no-adversary) errors — the protocol's clean
    accuracy contract.  trim(2) belongs in the set because an
    f-consuming extreme adversary (nan/inf/boost) occupies one trim slot
    per coordinate, turning trim(1) over 8 rows into an asymmetric
    1-and-2 trim of the 7 honest rows — bracketed by the symmetric
    trim(2) clean decode; the damage stays ≤ 2× that ceiling.  An
    interior adversary (sign_flip: a quantized row's flipped values land
    inside the honest per-coordinate hull) cannot be trimmed at all —
    the order statistics can't tell it from an honest row — so its
    guarantee is containment in the hull, whose width on binary/ternary
    codecs is the quantization range: empirically ≤ 2.5× clean, asserted
    at ≤ 4× (hull-slack factor);
  * clean trim(1) error within the §14 ``mse_trimmed`` closed-form bound
    for the presets with exact base MSE forms (bernoulli, binary);
  * drop_mask decode: a dropped peer's data has ZERO bit influence —
    poisoning the dead peers' inputs leaves the masked output
    bit-identical (same jit cache entry, so the survivor computation is
    literally the same program on the same bytes = the survivor re-run);
    and the value equals the survivors-only host rerun with original
    peer indices (the seed-trick chains must not re-index) to f32
    tolerance — mesh-vs-eager-host bit equality is NOT the contract
    (XLA FMA-fuses the decode affine math under jit);
  * zero recompiles across masks and across adversary/mode operands: the
    mask, the adversary rank and the corruption selector are traced
    operands, so the jit cache stays at ONE entry for any schedule;
  * the robust round's lowered HLO carries exactly the mean round's
    all-gather payload — decode policies never touch the wire;
  * the mode="none" exact path renormalizes over survivors through the
    same drop_mask operand (partial_mean contract).
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
from jax.sharding import Mesh  # noqa: E402
from jax.sharding import PartitionSpec as P  # noqa: E402

from repro.configs import registry as cfg_registry  # noqa: E402
from repro.core import collectives, mse, types, wire  # noqa: E402
from repro.core.wire import base as wire_base  # noqa: E402
from repro.distributed import fault_tolerance as ft  # noqa: E402

N, D = 8, 5000
ROUNDS = 4
MODES = ft.CORRUPTION_MODES          # ("nan", "inf", "sign_flip", "boost")
NONFINITE_OR_BOOST = ("nan", "inf", "boost")

GATHER_PRESETS = sorted(
    nm for nm in cfg_registry.COMPRESSION_PRESETS
    if wire.resolve(cfg_registry.robust_preset(nm, "mean", axes=("data",)))
    .reduce == "all_gather")


def check(name, ok, detail=""):
    print(f"[{'ok' if ok else 'FAIL'}] {name} {detail}")
    if not ok:
        raise SystemExit(f"FAILED: {name} {detail}")


def preset(nm, policy):
    return dataclasses.replace(
        cfg_registry.robust_preset(nm, policy, axes=("data",)),
        wire_dtype="float32", min_compress_size=0)


MESH = Mesh(np.array(jax.devices()[:N]), ("data",))


def adversarial_round(cfg):
    """jit'd round: pack → corrupt the adversary's wire row → gather →
    policy decode.  ``adv`` (−1 = nobody), ``mode_idx`` and ``mask`` are
    all traced operands — one cache entry serves the whole matrix."""
    codec = wire.resolve(cfg)

    @functools.partial(jax.shard_map, mesh=MESH,
                       in_specs=(P("data"), P(), P(), P(), P()),
                       out_specs=P(), check_vma=False)
    def f(x, key, adv, mode_idx, mask):
        rank, n = wire_base.axis_rank_size(cfg.axes)
        buf = codec.pack(x.reshape(D), key, rank, cfg)
        variants = jnp.stack([ft.corrupt_wire_row(buf, m) for m in MODES])
        buf = jnp.where(rank == adv, variants[mode_idx], buf)
        return codec.gather_decode(buf, key, cfg, D, n, mask)
    return jax.jit(f)


def masked_mean_round(cfg):
    @functools.partial(jax.shard_map, mesh=MESH,
                       in_specs=(P("data"), P(), P()), out_specs=P(),
                       check_vma=False)
    def f(x, key, mask):
        return collectives.compressed_mean(x.reshape(D), key, cfg,
                                           drop_mask=mask)
    return jax.jit(f)


def gather_bits(txt):
    nbits = {"f32": 32, "u32": 32, "s32": 32, "bf16": 16}
    out = []
    for dt, dims in re.findall(
            r"= (f32|u32|s32|bf16)\[([\d,]+)\]\S* all-gather"
            r"(?:-start)?\(", txt):
        b = nbits[dt]
        for v in dims.split(","):
            b *= int(v)
        out.append(b)
    return sorted(out)


XS = jax.random.normal(jax.random.PRNGKey(1), (N, D), jnp.float32)
XBAR = np.asarray(XS.mean(0))
KEYS = [jax.random.PRNGKey(100 + r) for r in range(ROUNDS)]
NO_ADV = jnp.int32(-1)
FULL = jnp.ones((N,), jnp.float32)


def sq_err(y):
    return float(((np.asarray(y) - XBAR) ** 2).sum())


# ---- the Byzantine matrix ---------------------------------------------------
for nm in GATHER_PRESETS:
    cfg_t = preset(nm, "trim(1)")
    cfg_m = preset(nm, "mean")
    f_t = adversarial_round(cfg_t)
    f_m = adversarial_round(cfg_m)
    f_t2 = adversarial_round(preset(nm, "trim(2)"))
    clean_t = np.mean([sq_err(f_t(XS, k, NO_ADV, jnp.int32(0), FULL))
                       for k in KEYS])
    clean_m = np.mean([sq_err(f_m(XS, k, NO_ADV, jnp.int32(0), FULL))
                       for k in KEYS])
    clean_t2 = np.mean([sq_err(f_t2(XS, k, NO_ADV, jnp.int32(0), FULL))
                        for k in KEYS])
    ceiling = max(clean_m, clean_t, clean_t2)
    for mi, mode in enumerate(MODES):
        adv, midx = jnp.int32(3), jnp.int32(mi)
        err_t = np.mean([sq_err(f_t(XS, k, adv, midx, FULL)) for k in KEYS])
        errs_m = [sq_err(f_m(XS, k, adv, midx, FULL)) for k in KEYS]
        err_m = np.mean(errs_m)
        fac = 4.0 if mode == "sign_flip" else 2.0
        check(f"{nm}.trim_contained[{mode}]",
              np.isfinite(err_t) and err_t <= fac * ceiling,
              f"adv={err_t:.4f} clean_mean={clean_m:.4f} "
              f"clean_trim={clean_t:.4f} clean_trim2={clean_t2:.4f}")
        if mode in NONFINITE_OR_BOOST:
            blown = (not np.isfinite(err_m)) or err_m > 10.0 * clean_m
            check(f"{nm}.mean_blows_up[{mode}]", blown,
                  f"adv={err_m:.4g} clean={clean_m:.4g}")
        else:
            # sign_flip against the mean is a bounded −2·row/n hit, not
            # nuclear — and for zero-mean quantized rows (ternary) the
            # flipped row is statistically just another plausible row,
            # so the error may not even rise.  Assert finite + bounded.
            check(f"{nm}.mean_bounded[{mode}]",
                  np.isfinite(err_m) and err_m <= 4.0 * ceiling,
                  f"adv={err_m:.4f} clean={clean_m:.4f}")
    # one cache entry served the whole (adv, mode, mask) matrix
    for f, tag in ((f_t, "trim"), (f_m, "mean")):
        check(f"{nm}.no_recompiles[{tag}]", f._cache_size() == 1,
              f"cache={f._cache_size()}")

# ---- clean trim error within the §14 closed-form bound ----------------------
for nm, bound in (
        ("bernoulli_seed_1bit", lambda cfg: mse.mse_trimmed_bernoulli(
            XS, float(cfg.encoder.fraction), jnp.mean(XS, axis=-1), 1)),
        ("binary_packed", lambda cfg: mse.mse_trimmed_binary(XS, 1))):
    cfg_t = preset(nm, "trim(1)")
    f_t = adversarial_round(cfg_t)
    errs = [sq_err(f_t(XS, k, NO_ADV, jnp.int32(0), FULL)) for k in KEYS]
    b = float(bound(cfg_t))
    check(f"{nm}.within_mse_trimmed", np.mean(errs) <= b,
          f"err={np.mean(errs):.4f} bound={b:.4f}")

# ---- drop_mask: bit-identical to the survivors-only rerun, no recompiles ----
for nm in GATHER_PRESETS:
    cfg = preset(nm, "mean")
    codec = wire.resolve(cfg)
    f = masked_mean_round(cfg)
    key = KEYS[0]
    masks = [FULL,
             jnp.asarray([1, 1, 1, 0, 1, 1, 1, 1], jnp.float32),
             jnp.asarray([0, 1, 1, 1, 0, 1, 1, 1], jnp.float32),
             jnp.asarray([1, 0, 0, 1, 1, 1, 0, 1], jnp.float32)]
    outs = [np.asarray(f(XS, key, m)) for m in masks]
    check(f"{nm}.mask_no_recompiles", f._cache_size() == 1,
          f"cache={f._cache_size()}")
    # the FULL mask equals the unmasked production round in value (the
    # unmasked path lowers the FUSED decode, the masked path the stacked
    # reduction — different programs, same mean).
    @functools.partial(jax.shard_map, mesh=MESH,
                       in_specs=(P("data"), P()), out_specs=P(),
                       check_vma=False)
    def plain_f(x, k):
        return collectives.compressed_mean(x.reshape(D), k, cfg)

    plain_out = np.asarray(jax.jit(plain_f)(XS, key))
    check(f"{nm}.full_mask_matches_unmasked",
          np.allclose(outs[0], plain_out, rtol=1e-5, atol=1e-5),
          f"max|diff|={np.max(np.abs(outs[0] - plain_out)):.2e}")
    # host-side survivor rerun: pack per rank with ORIGINAL indices, keep
    # only surviving rows, decode through the same policy hook.
    rows = jnp.stack([codec.pack(XS[i], key, i, cfg) for i in range(N)])
    for m, out in zip(masks[1:], outs[1:]):
        tag = "".join(str(int(v)) for v in m)
        # zero bit influence: poison the dropped peers' inputs; the same
        # cache entry must produce the identical bits.
        mm = np.asarray(m)
        xs_p = np.array(XS)
        xs_p[mm == 0] = 1e9 + np.arange(D, dtype=np.float32)
        out_p = np.asarray(f(jnp.asarray(xs_p), key, m))
        check(f"{nm}.mask_bitexact[{tag}]", np.array_equal(out, out_p),
              f"max|diff|={np.max(np.abs(out - out_p)):.2e}")
        ref = np.asarray(codec.decode_rows_reduce(
            rows, key, cfg, D, N, drop_mask=m))
        check(f"{nm}.mask_matches_host[{tag}]",
              np.allclose(out, ref, rtol=1e-5, atol=1e-5),
              f"max|diff|={np.max(np.abs(out - ref)):.2e}")
    check(f"{nm}.mask_no_recompiles[poisoned]", f._cache_size() == 1,
          f"cache={f._cache_size()}")

# the hook itself equals an ascending survivors-only loop (the "re-run
# without the dropped peer" reference), bit for bit — meshless companion
# assertions live in tests/test_robust_decode.py for every preset; here we
# close the chain through the mesh for one linear and one rotated preset.
for nm in ("bernoulli_seed_1bit", "rotated_binary"):
    cfg = preset(nm, "mean")
    codec = wire.resolve(cfg)
    key = KEYS[1]
    m = jnp.asarray([1, 1, 0, 1, 1, 1, 1, 1], jnp.float32)
    got = np.asarray(masked_mean_round(cfg)(XS, key, m))
    rows = jnp.stack([codec.pack(XS[i], key, i, cfg) for i in range(N)])
    from repro.core import rotation
    inner = codec.inner if isinstance(codec, wire.RotatedCodec) else codec
    dim = rotation.padded_dim(D) if inner is not codec else D
    stack = inner.decode_rows(rows, key, cfg, dim, N)
    acc = jnp.zeros((dim,), jnp.float32)
    for i in range(N):
        if float(m[i]) > 0:
            acc = acc + stack[i]
    ref = acc / float(m.sum())
    if inner is not codec:
        ref = rotation.unrotate(rotation.rotation_key(key), ref, D)
    ref = np.asarray(ref)
    check(f"{nm}.mask_equals_survivor_rerun",
          np.allclose(got, ref, rtol=1e-5, atol=1e-5),
          f"max|diff|={np.max(np.abs(got - ref)):.2e}")

# ---- decode policies never touch the wire (HLO payload identity) ------------
for nm in ("bernoulli_seed_1bit", "binary_packed", "ef_rotated_binary"):
    bits = {}
    for policy in ("mean", "trim(1)", "median"):
        cfg = preset(nm, policy)
        txt = masked_mean_round(cfg).lower(
            jax.ShapeDtypeStruct((N, D), np.float32),
            jax.ShapeDtypeStruct((2,), np.uint32),
            jax.ShapeDtypeStruct((N,), np.float32)).compile().as_text()
        bits[policy] = gather_bits(txt)
    check(f"{nm}.policy_blind_payload",
          bits["mean"] == bits["trim(1)"] == bits["median"],
          f"{bits}")

# ---- exact path + FailurePlan integration -----------------------------------
cfg_none = types.CompressionConfig(mode="none", axes=("data",))
f_none = masked_mean_round(cfg_none)
m = jnp.asarray([1, 0, 1, 1, 1, 1, 0, 1], jnp.float32)
got = np.asarray(f_none(XS, KEYS[0], m))
want = np.asarray(XS[np.asarray(m) > 0].mean(0))
check("none.masked_exact_mean",
      np.allclose(got, want, rtol=1e-6, atol=1e-6),
      f"max|diff|={np.max(np.abs(got - want)):.2e}")
check("none.mask_no_recompiles",
      f_none(XS, KEYS[0], FULL) is not None and f_none._cache_size() == 1,
      f"cache={f_none._cache_size()}")

plan = ft.FailurePlan(rate=0.5, seed=4)
cfg_b = preset("bernoulli_seed_1bit", "trim(1)")


@functools.partial(jax.shard_map, mesh=MESH,
                   in_specs=(P("data"), P()), out_specs=P(),
                   check_vma=False)
def plan_round(x, key):
    return ft.robust_compressed_mean(x.reshape(D), key, cfg_b, 3, plan)


out = np.asarray(jax.jit(plan_round)(XS, KEYS[2]))
alive = np.asarray(plan.alive_mask(3, N))
check("failure_plan.robust_round_finite",
      np.isfinite(out).all() and alive.sum() >= 1,
      f"alive={alive.astype(int)}")

print("ALL ROBUST DECODE CHECKS PASSED")
