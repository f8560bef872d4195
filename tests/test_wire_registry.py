"""The WireCodec registry (repro.core.wire): dispatch, accounting, wire
formats.

The accounting test is the one parametrized check that replaced the
per-protocol copies in test_comm_cost.py: for EVERY registered codec,

    comm_cost_bits == wire_bits + seed_bits        (analytic identity)
    wire_bits      == HLO-measured gathered bits   (gather codecs, one
                                                    8-device subprocess)
"""
import dataclasses
import json
import os
import pathlib
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import simulate_wire_round as _simulate_round
from repro.configs import registry as cfg_registry
from repro.core import collectives, comm_cost, encoders, rotation, types, wire

ROOT = pathlib.Path(__file__).resolve().parent.parent
N, D = 8, 5000  # D deliberately NOT a power of two nor a multiple of 32


def _cfg(kind, *, rotation=False, frac=0.125, center="min", wire="float32",
         mode="gather_decode", probs="uniform", ef=False):
    return types.CompressionConfig(
        encoder=types.EncoderSpec(kind=kind, fraction=frac, center=center,
                                  rotation=rotation, probs=probs),
        mode=mode, axes=("data",), wire_dtype=wire, min_compress_size=0,
        error_feedback=ef)


# one config per registered codec, used by both accounting tests below.
CODEC_CFGS = {
    "fixed_k": _cfg("fixed_k"),
    "fixed_k_shared": _cfg("fixed_k", mode="shared_support"),
    "bernoulli": _cfg("bernoulli", center="mean"),
    "binary": _cfg("binary"),
    "ternary": _cfg("ternary"),
    "ternary_opt": _cfg("ternary", probs="optimal"),
    "dense": _cfg("bernoulli", center="mean", probs="optimal"),
    "rotated_binary": _cfg("binary", rotation=True),
    "rotated_fixed_k": _cfg("fixed_k", rotation=True),
    "ef_fixed_k": _cfg("fixed_k", ef=True),
    "ef_fixed_k_shared": _cfg("fixed_k", mode="shared_support", ef=True),
    "ef_bernoulli": _cfg("bernoulli", center="mean", ef=True),
    "ef_binary": _cfg("binary", ef=True),
    "ef_ternary": _cfg("ternary", ef=True),
    "ef_rotated_binary": _cfg("binary", rotation=True, ef=True),
}


# --------------------------------------------------------------------------- #
# Dispatch: resolve() is THE rule.
# --------------------------------------------------------------------------- #

def test_registry_contains_all_production_codecs():
    assert set(wire.names()) >= set(CODEC_CFGS)


def test_resolve_matches_expected_codec():
    for name, cfg in CODEC_CFGS.items():
        assert wire.resolve(cfg).name == name, (name, wire.resolve(cfg).name)


def test_resolve_rejects_uncompressed_modes():
    with pytest.raises(ValueError):
        wire.resolve(types.CompressionConfig(mode="none"))


def test_rotation_wraps_any_codec_without_nesting():
    rot = wire.resolve(_cfg("ternary", rotation=True))
    assert rot.name == "rotated_ternary" and rot.inner.name == "ternary"
    with pytest.raises(ValueError):
        wire.RotatedCodec(rot)


def test_ef_wraps_any_codec_without_nesting():
    # EF composes outermost over any base or rotated codec, on the fly for
    # combinations without a registered instance, and never over itself.
    eft = wire.resolve(_cfg("ternary", ef=True))
    assert eft.name == "ef_ternary" and eft.inner.name == "ternary"
    efo = wire.resolve(_cfg("ternary", probs="optimal", ef=True))
    assert efo.name == "ef_ternary_opt" and efo.inner.name == "ternary_opt"
    efr = wire.resolve(_cfg("fixed_k", rotation=True, ef=True))
    assert (efr.name == "ef_rotated_fixed_k"
            and efr.inner.name == "rotated_fixed_k")
    with pytest.raises(ValueError):
        wire.EFCodec(eft)
    with pytest.raises(ValueError):
        wire.EFCodec(wire.RotatedCodec(wire.get("ef_binary")))


@pytest.mark.parametrize("kind,rot", [("binary", False), ("ternary", False),
                                      ("binary", True), ("bernoulli", False)])
@pytest.mark.parametrize("wire_dtype", ["float32", "bfloat16"])
def test_ef_twin_recon_matches_unpack(kind, rot, wire_dtype):
    """The fused EF residual reconstruction (derived from the twin's own
    intermediates, no plane unpack — DESIGN.md §13) is bit-for-bit the
    inner codec's unpack of the shipped bytes, so residual semantics and
    the golden wire bytes are unchanged."""
    from repro.core.wire import ef as ef_mod
    center = "mean" if kind == "bernoulli" else "min"
    cfg = _cfg(kind, rotation=rot, center=center, wire=wire_dtype)
    codec = wire.resolve(cfg)
    d = 1000
    key = jax.random.PRNGKey(11)
    v = jax.random.normal(jax.random.PRNGKey(12), (d,)) * 3.0
    buf, recon = ef_mod._twin_pack_recon(codec, v, key, 0, cfg)
    want_buf = ef_mod._twin_pack(codec, v, key, 0, cfg)
    assert np.array_equal(np.asarray(buf), np.asarray(want_buf))
    want = codec.unpack(buf, 0, key, cfg, d)
    assert np.array_equal(np.asarray(recon), np.asarray(want))
    assert ef_mod.twin_recon_fused(codec) == (kind in ("binary", "ternary"))


def test_gather_wire_kind_delegates_to_registry():
    # the historical dispatch-rule API survives, now registry-backed.
    assert collectives.gather_wire_kind(_cfg("binary")) == "binary"
    # §6 ternary optimal probs are wire-modelled now (the branch choices
    # ride the 2-bit plane): no more dense fallback.
    assert collectives.gather_wire_kind(
        _cfg("ternary", probs="optimal")) == "ternary_opt"
    assert collectives.gather_wire_kind(
        _cfg("bernoulli", center="optimal")) == "dense"
    # rotation composes on top; the base kind is unchanged.
    assert collectives.gather_wire_kind(_cfg("binary", rotation=True)) == "binary"


def test_rotated_presets_resolve_to_registered_instances():
    for name in ("rotated_binary", "rotated_fixed_k"):
        cfg = cfg_registry.compression_preset(name, axes=("data",))
        assert wire.resolve(cfg) is wire.get(name)


# --------------------------------------------------------------------------- #
# Accounting identity: analytic cost == wire payload + implicit seed bits.
# --------------------------------------------------------------------------- #

@pytest.mark.parametrize("name", sorted(CODEC_CFGS))
@pytest.mark.parametrize("wire_dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("d", [31, 4096, 5000])
def test_wire_bits_plus_seed_is_comm_cost(name, wire_dtype, d):
    codec = wire.get(name)
    cfg = dataclasses.replace(CODEC_CFGS[name], wire_dtype=wire_dtype)
    got = codec.comm_cost_bits(N, d, cfg)
    want = codec.wire_bits(N, d, cfg) + codec.seed_bits(N, cfg)
    assert got == want, (name, d, got, want)
    # and cost_config routes the same number through the registry.
    assert comm_cost.cost_config(cfg, n=N, d=d) == got


def test_hierarchical_cost_is_billed_at_effective_nodes():
    """The flat-world-size accounting bugfix: a hierarchical config charges
    the codec at the cross-host group size — one helper
    (wire.effective_nodes) feeds cost_config and bucket_wire_bits, so the
    identity holds at n_eff, not at the flat n."""
    msz = {"pod": 4, "data": 2}
    for kind in ("fixed_k", "bernoulli"):
        cfg = dataclasses.replace(
            CODEC_CFGS[kind], axes=("pod",), inner_axes=("data",),
            scatter_decode=True)
        codec = wire.resolve(cfg)
        assert wire.effective_nodes(cfg, N, msz) == 4
        got = comm_cost.cost_config(cfg, n=N, d=D, mesh_sizes=msz)
        assert got == codec.wire_bits(4, D, cfg) + codec.seed_bits(4, cfg)
        # exactly half the flat bill at the same world size (both linear
        # in n), and the scatter decode never changes what's on the wire.
        flat = dataclasses.replace(cfg, inner_axes=(), scatter_decode=False)
        assert 2 * got == comm_cost.cost_config(flat, n=N, d=D)


def test_flat_scatter_cost_adds_scatter_bits():
    """§12/§13 accounting identity: a flat-scatter config bills its wire
    payload + seeds + the extra main-axis collectives (scatter_bits);
    hierarchical scatter bills 0 scatter (free inner link, §11)."""
    for kind in ("bernoulli", "fixed_k", "binary", "ternary"):
        cfg = dataclasses.replace(CODEC_CFGS[kind], scatter_decode=True)
        codec = wire.resolve(cfg)
        sb = codec.scatter_bits(N, D, cfg)
        assert sb > 0
        align = wire.scatter_word_align(cfg)
        ds = wire.scatter_shard_len(D, N, align)
        if kind == "bernoulli":
            # i32 rank-offset counts + the decoded f32 shard gather
            assert align == 1
            assert sb == N * N * 32 + N * ds * 32
        if kind == "binary":
            # word-aligned shard gather only — the plane travels, so no
            # bookkeeping exchange (§13)
            assert align == 32 and ds % 32 == 0
            assert sb == N * ds * 32
        if kind == "ternary":
            # i32 pass-through counts + the word-aligned shard gather
            assert align == 16 and ds % 16 == 0
            assert sb == N * N * 32 + N * ds * 32
        got = comm_cost.cost_config(cfg, n=N, d=D)
        assert got == (codec.wire_bits(N, D, cfg) + codec.seed_bits(N, cfg)
                       + sb)
        # scatter costs MORE than the plain flat config — never hidden.
        flat = dataclasses.replace(cfg, scatter_decode=False)
        assert got == comm_cost.cost_config(flat, n=N, d=D) + sb
        # hierarchical scatter: same codec, 0 scatter bill.
        hier = dataclasses.replace(cfg, axes=("pod",), inner_axes=("data",))
        assert wire.resolve(hier).scatter_bits(4, D, hier) == 0.0


def test_flat_scatter_preset_identity_holds():
    """The shipped flat-scatter presets satisfy the full §12/§13 identity
    and EF delegates scatter_bits verbatim (residuals are local)."""
    for name in ("bernoulli_seed_1bit", "ef_bernoulli", "binary_packed",
                 "ternary_packed", "ef_binary", "ef_ternary",
                 "ef_rotated_binary"):
        cfg = cfg_registry.compression_preset(name, axes=("data",))
        assert cfg.scatter_decode and not cfg.inner_axes
        codec = wire.resolve(cfg)
        assert codec.scatter_supported
        assert comm_cost.cost_config(cfg, n=N, d=D) == (
            codec.wire_bits(N, D, cfg) + codec.seed_bits(N, cfg)
            + codec.scatter_bits(N, D, cfg))
    for plain_name, ef_name in [("bernoulli_seed_1bit", "ef_bernoulli"),
                                ("binary_packed", "ef_binary"),
                                ("ternary_packed", "ef_ternary")]:
        plain = cfg_registry.compression_preset(plain_name, axes=("data",))
        ef = cfg_registry.compression_preset(ef_name, axes=("data",))
        assert wire.resolve(ef).scatter_bits(N, D, ef) == \
            wire.resolve(plain).scatter_bits(N, D, plain)


def test_rotated_scatter_bits_are_inner_at_padded_dim():
    # §13: rotated decodes scatter in rotated space, so the shard gather
    # is the inner codec's at the padded length.
    cfg = cfg_registry.compression_preset("ef_rotated_binary",
                                          axes=("data",))
    codec = wire.resolve(cfg)
    dp = rotation.padded_dim(D)
    ds = wire.scatter_shard_len(dp, N, wire.scatter_word_align(cfg))
    assert codec.scatter_bits(N, D, cfg) == N * ds * 32


def test_hier_presets_resolve_and_flatten():
    for name in ("hier_fixed_k", "hier_bernoulli"):
        cfg = cfg_registry.compression_preset(name)
        assert cfg.inner_axes == ("data",) and cfg.scatter_decode
        assert wire.resolve(cfg).scatter_supported
        # re-pointing onto the inner axis flattens the hierarchy but KEEPS
        # the scatter decode — it re-targets the flat-mesh form (§12), so
        # the flattened preset bills its shard collectives via
        # scatter_bits instead of falling back to the O(n·d) flat unpack.
        flat = cfg_registry.compression_preset(name, axes=("data",))
        assert flat.inner_axes == () and flat.scatter_decode
        codec = wire.resolve(flat)
        assert codec.scatter_bits(N, D, flat) > 0


def test_rotated_wire_bits_are_inner_at_padded_dim():
    for name in ("rotated_binary", "rotated_fixed_k"):
        codec = wire.get(name)
        cfg = CODEC_CFGS[name]
        for d in (31, 4096, 5000):
            dp = rotation.padded_dim(d)
            assert codec.wire_bits(N, d, cfg) == \
                codec.inner.wire_bits(N, dp, cfg)
            # power of two ⇒ payload identical to the un-rotated codec.
            if d == dp:
                plain = dataclasses.replace(
                    cfg, encoder=dataclasses.replace(cfg.encoder,
                                                     rotation=False))
                assert codec.wire_bits(N, d, cfg) == \
                    wire.resolve(plain).wire_bits(N, d, plain)


def test_ef_accounting_delegates_to_inner_exactly():
    """Residuals are wire-free: every EF codec's slots / payload / seed /
    analytic cost equal its inner codec's, at every probed geometry."""
    for name, cfg in CODEC_CFGS.items():
        if not name.startswith("ef_"):
            continue
        codec = wire.get(name)
        plain = dataclasses.replace(cfg, error_feedback=False)
        inner = wire.resolve(plain)
        assert codec.inner is inner, (name, codec.inner, inner)
        for d in (31, 4096, 5000):
            assert codec.wire_slots(d, cfg) == inner.wire_slots(d, plain)
            assert codec.wire_bits(N, d, cfg) == inner.wire_bits(N, d, plain)
            assert codec.comm_cost_bits(N, d, cfg) == \
                inner.comm_cost_bits(N, d, plain)
        assert codec.seed_bits(N, cfg) == inner.seed_bits(N, plain)


def test_ternary_opt_wire_bits_equal_ternary():
    """The §6-optimal split changes branch probabilities only — the plane,
    capacity and cost are the plain ternary codec's."""
    opt, plain = wire.get("ternary_opt"), wire.get("ternary")
    cfg_o, cfg_p = CODEC_CFGS["ternary_opt"], CODEC_CFGS["ternary"]
    for d in (31, 4096, 5000):
        assert opt.wire_slots(d, cfg_o) == plain.wire_slots(d, cfg_p)
        assert opt.wire_bits(N, d, cfg_o) == plain.wire_bits(N, d, cfg_p)
        assert opt.comm_cost_bits(N, d, cfg_o) == \
            plain.comm_cost_bits(N, d, cfg_p)


# --------------------------------------------------------------------------- #
# HLO: gathered bits == wire_bits, one subprocess for every gather codec.
# --------------------------------------------------------------------------- #

GATHER_CODECS = ["fixed_k", "bernoulli", "binary", "ternary", "ternary_opt",
                 "rotated_binary", "rotated_fixed_k",
                 "ef_fixed_k", "ef_bernoulli", "ef_binary", "ef_ternary",
                 "ef_rotated_binary"]

_INNER = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import dataclasses, functools, json, re
import jax, jax.numpy as jnp
from jax.sharding import PartitionSpec as P
from repro.core import collectives, types

N, D = 8, 5000
mesh = jax.make_mesh((N,), ("data",))
CFGS = json.loads(os.environ["WIRE_CFGS"])
out = {}
for name, kw in CFGS.items():
    cfg = types.CompressionConfig(
        encoder=types.EncoderSpec(**kw["encoder"]), mode="gather_decode",
        axes=("data",), wire_dtype=kw["wire_dtype"], min_compress_size=0,
        error_feedback=kw["error_feedback"])
    @functools.partial(jax.shard_map, mesh=mesh, in_specs=(P("data"), P()),
                       out_specs=P(), check_vma=False)
    def f(xs, key):
        return collectives.compressed_mean(xs.reshape(D), key, cfg)
    txt = jax.jit(f).lower(
        jax.ShapeDtypeStruct((N, D), jnp.float32),
        jax.ShapeDtypeStruct((2,), jnp.uint32)).compile().as_text()
    bits_of = {"f32": 32, "u32": 32, "bf16": 16}
    ms = re.findall(r"= (f32|u32|bf16)\[(\d+),(\d+)\]\{[^}]*\} all-gather",
                    txt)
    gathered = [int(n) * int(s) * bits_of[dt] for dt, n, s in ms]
    out[name] = {"launches": len(gathered), "bits": sum(gathered)}
print(json.dumps(out))
"""


@pytest.fixture(scope="module")
def hlo_gathered_bits():
    cfgs = {}
    for name in GATHER_CODECS:
        cfg = CODEC_CFGS[name]
        cfgs[name] = {"encoder": dataclasses.asdict(cfg.encoder),
                      "wire_dtype": cfg.wire_dtype,
                      "error_feedback": cfg.error_feedback}
    env = dict(os.environ)
    env["PYTHONPATH"] = str(ROOT / "src")
    env["WIRE_CFGS"] = json.dumps(cfgs)
    env.pop("XLA_FLAGS", None)
    res = subprocess.run([sys.executable, "-c", _INNER], env=env,
                         capture_output=True, text=True, timeout=600)
    assert res.returncode == 0, \
        f"\nSTDOUT:\n{res.stdout}\nSTDERR:\n{res.stderr}"
    return json.loads(res.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("name", GATHER_CODECS)
def test_hlo_gathered_bits_match_wire_bits(name, hlo_gathered_bits):
    got = hlo_gathered_bits[name]
    codec = wire.get(name)
    cfg = CODEC_CFGS[name]
    assert got["launches"] == 1, got
    assert got["bits"] == codec.wire_bits(N, D, cfg), \
        (name, got, codec.wire_bits(N, D, cfg))


# --------------------------------------------------------------------------- #
# Wire formats are meshless-testable: pack rows → decode_gathered.
# --------------------------------------------------------------------------- #

@pytest.mark.parametrize("name", ["fixed_k", "bernoulli", "binary", "ternary",
                                  "ternary_opt"])
def test_decode_gathered_equals_dense_encoders(name):
    """At f32 wire the codec wire path reproduces the dense per-node
    encoders exactly: decode_gathered == mean_i encode(fold_in(key, i))."""
    cfg = CODEC_CFGS[name]
    key = jax.random.PRNGKey(5)
    xs = jax.random.normal(jax.random.PRNGKey(6), (N, 999)) * 0.4
    got = _simulate_round(wire.get(name), cfg, xs, key)

    def dense_y(i):
        kenc = jax.random.fold_in(key, i)
        if name == "fixed_k":
            codec = wire.get(name)
            return codec.unpack(codec.pack(xs[i], key, i, cfg), i, key, cfg,
                                xs.shape[1])
        if name == "bernoulli":
            return encoders.encode_bernoulli(
                kenc, xs[i], cfg.encoder.fraction, jnp.mean(xs[i])).y
        if name == "binary":
            return encoders.encode_binary(kenc, xs[i]).y
        return encoders.encode(kenc, xs[i], cfg.encoder).y

    want = jnp.mean(jnp.stack([dense_y(i) for i in range(N)]), axis=0)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


# --------------------------------------------------------------------------- #
# EF residual contract: one step's residual ≤ the inner codec's worst-case
# per-step error (hypothesis property; the contraction EF stability needs).
# --------------------------------------------------------------------------- #

EF_CODECS = [n for n in CODEC_CFGS if n.startswith("ef_")]


@pytest.mark.parametrize("name", EF_CODECS)
def test_ef_one_step_residual_bounded(name):
    pytest.importorskip("hypothesis")
    from hypothesis import given, settings, strategies as st

    codec = wire.get(name)
    cfg = CODEC_CFGS[name]

    @settings(max_examples=10, deadline=None)
    @given(seed=st.integers(0, 2**31 - 1), d=st.integers(33, 1500),
           scale=st.floats(1e-3, 1e3), spike=st.floats(0.0, 50.0))
    def prop(seed, d, scale, spike):
        key = jax.random.PRNGKey(seed)
        v = jax.random.normal(jax.random.fold_in(key, 1), (d,)) * scale
        v = v.at[0].add(spike * scale)  # anisotropy stresses the quantizers
        buf = codec.pack(v, key, 0, cfg)
        recon = codec.unpack(buf, 0, key, cfg, d)
        res = float(jnp.linalg.norm(v - recon))
        bound = float(codec.residual_bound(v, key, cfg))
        assert res <= bound * (1 + 1e-5) + 1e-5 * scale, \
            (name, d, res, bound)

    prop()
