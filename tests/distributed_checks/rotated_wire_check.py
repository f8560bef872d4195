"""Multi-device (8 fake CPU devices) validation of the §7.2 rotated wire
codecs (repro.core.wire.rotated) — the rotated_binary / rotated_fixed_k
presets end-to-end.  Run by tests/test_rotation_wire.py in a subprocess:

    python rotated_wire_check.py

Checks:
  * payload equality: the lowered HLO of the rotated presets gathers
    buffers of EXACTLY the un-rotated codec's shape (seed-only overhead —
    the rotation seed regenerates from the shared per-step key, the §4.4
    trick applied to Q), and exactly one all-gather launch either way;
  * analytic accounting: codec.wire_bits == bucket-style payload ==
    un-rotated wire_bits at the power-of-two bucket size, and
    comm_cost.cost_config == payload + seed bits;
  * Monte-Carlo wire-path MSE over the mesh == the §7.2 closed forms
    (the base protocol's exact form evaluated at QX, averaged over the
    same rotation seeds the wire draws: mse.mse_rotated_*);
  * unbiasedness of both rotated estimators.
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
from repro.core import collectives, comm_cost, mse, rotation, types, wire  # noqa: E402

N = 8
D = 8192                # power of two: payload must equal the un-rotated codec
FRAC = 0.25             # fixed-k: kb = round(0.25 · 8 blocks) = 2 → k = 2048
TRIALS = 200

mesh = jax.make_mesh((N,), ("data",))

# anisotropic inputs: a few spiky coordinates — the regime §7.2 targets.
XS = jax.random.normal(jax.random.PRNGKey(0), (N, D)) * 0.3
XS = XS.at[:, :4].add(jnp.array([6.0, -5.0, 4.0, -3.0]))
TRUE = np.asarray(jnp.mean(XS, axis=0))


def check(name, ok, detail=""):
    print(f"[{'ok' if ok else 'FAIL'}] {name} {detail}")
    if not ok:
        raise SystemExit(f"FAILED: {name} {detail}")


def preset(name):
    cfg = cfg_registry.compression_preset(name, axes=("data",))
    enc = dataclasses.replace(cfg.encoder, fraction=FRAC)
    return dataclasses.replace(cfg, encoder=enc, wire_dtype="float32",
                               min_compress_size=0)


def lower_text(cfg):
    @functools.partial(jax.shard_map, mesh=mesh, in_specs=(P("data"), P()),
                       out_specs=P(), check_vma=False)
    def f(xs, key):
        return collectives.compressed_mean(xs.reshape(D), key, cfg)
    return jax.jit(f).lower(
        jax.ShapeDtypeStruct((N, D), jnp.float32),
        jax.ShapeDtypeStruct((2,), jnp.uint32)).compile().as_text()


def gathered_shapes(txt):
    return sorted(m.group(1) for m in
                  re.finditer(r"= (\S+\[[\d,]+\])\{[^}]*\} all-gather", txt))


K0 = jax.random.PRNGKey(13)
for name in ("rotated_binary", "rotated_fixed_k"):
    cfg_rot = preset(name)
    cfg_plain = dataclasses.replace(
        cfg_rot, encoder=dataclasses.replace(cfg_rot.encoder, rotation=False))
    codec_rot = wire.resolve(cfg_rot)
    codec_plain = wire.resolve(cfg_plain)
    check(f"{name}.resolves", codec_rot.name == name
          and codec_rot.reduce == codec_plain.reduce)

    # ---- HLO: gathered payload identical to the un-rotated codec ---------- #
    txt_rot = lower_text(cfg_rot)
    txt_plain = lower_text(cfg_plain)
    gr, gp = gathered_shapes(txt_rot), gathered_shapes(txt_plain)
    check(f"{name}.one_launch", len(gr) == 1 and len(gp) == 1,
          f"rot={gr} plain={gp}")
    check(f"{name}.payload_eq_unrotated_hlo", gr == gp,
          f"rot={gr} plain={gp}")

    # ---- analytic accounting --------------------------------------------- #
    wb_rot = codec_rot.wire_bits(N, D, cfg_rot)
    wb_plain = codec_plain.wire_bits(N, D, cfg_plain)
    check(f"{name}.payload_eq_unrotated_bits", wb_rot == wb_plain,
          f"rot={wb_rot:.0f} plain={wb_plain:.0f}")
    cost = comm_cost.cost_config(cfg_rot, n=N, d=D)
    seed = codec_rot.seed_bits(N, cfg_rot)
    check(f"{name}.seed_only_overhead",
          cost == wb_rot + seed
          and cost == comm_cost.cost_config(cfg_plain, n=N, d=D)
          + N * types.DEFAULT_RSEED_BITS,
          f"cost={cost:.0f} wire={wb_rot:.0f} seed={seed:.0f}")

    # ---- Monte-Carlo wire MSE == §7.2 closed form ------------------------- #
    @functools.partial(jax.shard_map, mesh=mesh, in_specs=(P("data"), P()),
                       out_specs=(P(), P()), check_vma=False)
    def trial_stats(xs, key, cfg=cfg_rot):
        x = xs.reshape(D)

        def one(t, carry):
            acc, sq = carry
            y = collectives.compressed_mean(x, jax.random.fold_in(key, t),
                                            cfg)
            err = y - jnp.asarray(TRUE)
            return acc + y, sq + jnp.sum(err * err)

        acc, sq = jax.lax.fori_loop(
            0, TRIALS, one, (jnp.zeros((D,)), jnp.zeros(())))
        return acc / TRIALS, sq / TRIALS

    mean_est, mse_emp = jax.jit(trial_stats)(XS, K0)
    mean_est, mse_emp = np.asarray(mean_est), float(mse_emp)

    # the same rotation seeds the wire derives: fold_in(key, t) → ROT tag.
    k_blocks = wire.get("fixed_k").wire_slots(D, cfg_rot) - 1  # kb·BLOCK

    def closed_form(t, name=name):
        krot = rotation.rotation_key(jax.random.fold_in(K0, t))
        if name == "rotated_binary":
            return mse.mse_rotated_binary(XS, krot)
        return mse.mse_rotated_fixed_k(XS, k_blocks, krot)

    want = float(jnp.mean(jax.lax.map(jax.jit(closed_form),
                                      jnp.arange(TRIALS))))
    check(f"{name}.mse_matches_72_closed_form",
          abs(mse_emp - want) < 0.15 * want,
          f"emp={mse_emp:.4f} want={want:.4f}")

    bias = float(np.max(np.abs(mean_est - TRUE)))
    check(f"{name}.unbiased", bias < 6 * np.sqrt(want / D),
          f"max|bias|={bias:.4f}")

# rotation must pay off where §7.2 says it does: rotated binary beats plain
# binary on these spiky inputs (compare the exact conditional forms).
want_plain = float(mse.mse_binary(XS))
want_rot = float(jnp.mean(jax.lax.map(
    jax.jit(lambda t: mse.mse_rotated_binary(
        XS, rotation.rotation_key(jax.random.fold_in(K0, t)))),
    jnp.arange(64))))
check("rotation_helps_binary", want_rot < want_plain,
      f"rotated={want_rot:.4f} plain={want_plain:.4f}")

print("ALL ROTATED WIRE CHECKS PASSED")
