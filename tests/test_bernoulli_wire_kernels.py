"""Oracle-equivalence + roundtrip properties for the fused §4.4 Bernoulli
wire kernels (repro.kernels.bernoulli_wire), mirroring tests/test_bitplane.py.

Three layers of pinning:

* ``rank_select`` (the searchsorted gather that replaced the historical
  d-wide scatter) is byte-identical to that scatter — tested against an
  inline reimplementation of the old op chain;
* the Pallas encode/decode kernels in interpret mode equal their jnp
  oracles — EXACTLY when 1/p is a power of two (every shipped preset;
  x·(1/p) is then exact so XLA's FMA contraction of the rescale is a
  no-op) or when ``scaled=False`` (EF twin, no rescale).  For arbitrary
  scaled p the rescale's contraction is fusion-context-dependent, so the
  contract weakens to exact fill structure + allclose values (see
  kernels/bernoulli_wire/kernel.py);
* the full codec roundtrip (pack → 16-bit bfloat16 packed-halves wire →
  decode) stays consistent between the batched decode and the sequential
  unpack chain, including the cap-overflow drop path.

The deterministic parametrized sweeps below always run (they are what the
CI kernel-interpret job exercises); the hypothesis layer widens the input
space when hypothesis is installed."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import comm_cost, types as t
from repro.core.wire import codecs
from repro.kernels.bernoulli_wire import kernel, ops, ref
from repro.kernels.threefry import ref as tf_ref

try:
    from hypothesis import given, settings, strategies as st
    HAS_HYPOTHESIS = True
except ImportError:  # optional dep — the parametrized sweeps still pin
    HAS_HYPOTHESIS = False

# edge lengths: scalar, around the Threefry half split, around the kernel's
# (8, 128) = 1024-coordinate block, and a generic non-round size
DIMS = (1, 31, 33, 1000, 1023, 1024, 1025, 4096, 5000)
# 1/p power of two (exact contract) — every production preset is 1/16
P_POW2 = (0.5, 0.0625)
P_ANY = (0.3, 0.9)


def _flat(seed, d):
    return jax.random.normal(jax.random.PRNGKey(seed), (d,)) * 0.7


def _key(seed, rank=0):
    return jax.random.fold_in(jax.random.PRNGKey(seed), rank)


def _legacy_scatter(values, sent, cap):
    """The historical core.bitplane.rank_scatter op chain, verbatim."""
    pos = jnp.cumsum(sent.astype(jnp.int32)) - 1
    slot = jnp.where(sent & (pos < cap), pos, cap)
    return jnp.zeros((cap,), jnp.float32).at[slot].set(
        values.astype(jnp.float32), mode="drop")


# --------------------------------------------------------------------------- #
# rank_select == legacy scatter, byte for byte.
# --------------------------------------------------------------------------- #

@pytest.mark.parametrize("d", DIMS)
@pytest.mark.parametrize("cap_frac", (0.1, 1.0, 2.0))
def test_rank_select_equals_legacy_scatter(d, cap_frac):
    vals = _flat(d, d)
    sent = jax.random.uniform(_key(d + 1), (d,)) < 0.3
    cap = max(1, int(d * cap_frac))
    got = ref.rank_select(vals.astype(jnp.float32), sent, cap)
    want = _legacy_scatter(vals, sent, cap)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


# --------------------------------------------------------------------------- #
# Encode kernel vs oracle (interpret mode).
# --------------------------------------------------------------------------- #

# one grid step of the kernels at full length: MAX_SUB sub-blocks of 1024.
# Cases at a step less one and a step and one below; three steps and one in
# test_shard_decode_exact_off_the_grid_step.
STEP = kernel.MAX_SUB * kernel.BM


@pytest.mark.parametrize(
    "scaled,p,d",
    [(s, p, d) for s in (True, False) for p in P_POW2 for d in DIMS]
    + [(True, 0.0625, STEP - 1), (False, 0.0625, STEP + 1)])
def test_encode_kernel_exact_for_pow2_inv_p(d, p, scaled):
    cap = comm_cost.bernoulli_capacity(d, p)
    flat = _flat(d, d)
    mu = jnp.mean(flat)
    want = ref.encode(flat, _key(d), p, cap, mu, scaled=scaled)
    got = ops.encode(flat, _key(d), p, cap, mu, scaled=scaled,
                     force_pallas=True)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("d", (33, 1000, 4096))
@pytest.mark.parametrize("p", P_ANY)
def test_encode_kernel_structure_and_values_any_p(d, p):
    """Arbitrary scaled p: exact fill structure (same slots populated, same
    zeros), values allclose — the FMA-contraction carve-out."""
    cap = comm_cost.bernoulli_capacity(d, p)
    flat = _flat(d, d)
    mu = jnp.mean(flat)
    want = np.asarray(ref.encode(flat, _key(d), p, cap, mu))
    got = np.asarray(ops.encode(flat, _key(d), p, cap, mu,
                                force_pallas=True))
    np.testing.assert_array_equal(got == 0.0, want == 0.0)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)


def test_encode_cap_overflow_drops_tail_ranks():
    """cap below the realized support size: the kernel and oracle drop the
    identical overflow tail, keeping exactly the first cap support ranks."""
    d, p = 2048, 0.5
    flat = _flat(3, d)
    mu = jnp.mean(flat)
    sent = np.asarray(tf_ref.uniform(_key(3), d) < p)
    cap = int(sent.sum()) // 2          # force overflow
    want = ref.encode(flat, _key(3), p, cap, mu)
    got = ops.encode(flat, _key(3), p, cap, mu, force_pallas=True)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    # every slot is filled and holds the rescale of the first cap sent
    # coordinates, in support order.
    idx = np.nonzero(sent)[0][:cap]
    vals = np.asarray(flat)[idx] / p - (1 - p) / p * float(mu)
    np.testing.assert_allclose(np.asarray(got), vals, rtol=1e-5, atol=1e-6)


# --------------------------------------------------------------------------- #
# Decode kernel vs oracle (interpret mode).
# --------------------------------------------------------------------------- #

@pytest.mark.parametrize(
    "n,d",
    [(n, d) for n in (1, 3, 8) for d in (1, 33, 1000, 1025, 4096)]
    + [(3, STEP + 1)])
def test_decode_kernel_exact_vs_sequential_oracle(d, n):
    p = 0.0625 if d > 64 else 0.5
    cap = comm_cost.bernoulli_capacity(d, p)
    keys = jnp.stack([jax.random.key_data(_key(d, i)) for i in range(n)])
    mus = jnp.stack([jnp.mean(_flat(d + i, d)) for i in range(n)])
    bufs = jnp.stack([
        ref.encode(_flat(d + i, d), _key(d, i), p, cap, mus[i])
        for i in range(n)])
    want = ref.decode_sum_sequential(bufs, mus, keys, p, cap, d)
    got = ops.decode_sum(bufs, mus, keys, p, cap, d, force_pallas=True)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    # the tiled production decode accumulates peers in the same linear
    # order (ref._peer_sum) — bit-exact vs the sequential oracle, not
    # merely allclose.
    batched = ref.decode_sum(bufs, mus, keys, p, cap, d)
    np.testing.assert_array_equal(np.asarray(batched), np.asarray(want))


# --------------------------------------------------------------------------- #
# Every f32 bit pattern; shards off the grid step; cap inside a step.
# --------------------------------------------------------------------------- #

# -0.0, subnormals, ±inf, quiet and signalling NaNs with payloads, the
# largest finite values, and values with four nonzero bytes
SPECIAL_BITS = np.array(
    [0x80000000, 0x00000001, 0x807FFFFF, 0x7F800000, 0xFF800000,
     0x7FC12345, 0xFFE54321, 0x7FA00001, 0x7F7FFFFF, 0xFF7FFFFF,
     0x3F8F1F2F, 0xC1A2B3C4], np.uint32)


def _special_flat(d):
    """Normal draws with every third coordinate one of SPECIAL_BITS."""
    out = np.asarray(_flat(d, d)).copy()
    slots = out[::3].view(np.uint32)     # a view: writes land in ``out``
    slots[:] = np.resize(SPECIAL_BITS, slots.shape)
    return jnp.asarray(out)


# the oracles of the long cases, jitted (run eagerly they compile op by op)
_ref_encode = jax.jit(ref.encode, static_argnums=(2, 3),
                      static_argnames=("scaled",))
_ref_support = jax.jit(ref.support_shard, static_argnums=(1, 2, 4))
_ref_shard_sum = jax.jit(ref.decode_sum_shard, static_argnums=(4,))


def _assert_bits(got, want):
    np.testing.assert_array_equal(np.asarray(got).view(np.uint32),
                                  np.asarray(want).view(np.uint32))


@pytest.mark.parametrize("d", (5000,))
def test_kernels_exact_on_every_f32_bit_pattern(d):
    """Unscaled values (the EF twin) pass encode and both decodes bit for
    bit whatever their bits: the compaction moves bytes and never adds or
    multiplies a value, and one peer's decode is written, not added to
    0.0 (which would turn -0.0 into +0.0).  d = 5000 walks two grid steps
    of four sub-blocks."""
    n, p = 1, 0.5
    cap = comm_cost.bernoulli_capacity(d, p)
    flat, mus = _special_flat(d), jnp.asarray([0.25], jnp.float32)
    keys = jax.random.key_data(_key(d)).reshape(1, 2)
    want = _ref_encode(flat, _key(d), p, cap, mus[0], scaled=False)
    _assert_bits(ops.encode(flat, _key(d), p, cap, mus[0], scaled=False,
                            force_pallas=True), want)
    bufs = want.reshape(1, cap)
    assert set(SPECIAL_BITS) <= set(np.asarray(want).view(np.uint32))
    _assert_bits(ops.decode_sum(bufs, mus, keys, p, cap, d,
                                force_pallas=True),
                 ref.decode_sum_sequential(bufs, mus, keys, p, cap, d))
    sent = _ref_support(keys, p, d, 0, d)
    prior = jnp.zeros((n,), jnp.int32)
    _assert_bits(ops.decode_sum_shard(bufs, mus, keys, sent, prior, 0, p=p,
                                      cap=cap, d=d, force_pallas=True),
                 _ref_shard_sum(bufs, mus, sent, prior, cap))


@pytest.mark.parametrize("start,ds,cap_at", [
    (2 * STEP + 100, STEP + 5, 2 * STEP + 16 * kernel.BM + 500),
])
def test_shard_decode_exact_off_the_grid_step(start, ds, cap_at):
    """A shard that starts off every step, sub-block and lane boundary,
    with each peer's support count before it as ``prior``, over two grid
    steps; the second runs past d, and its cap is reached at ``cap_at``,
    mid sub-block inside a grid step, so encode drops the tail ranks there
    (the overflow path) and the shard decodes them to μ."""
    d, p, n = 3 * STEP + 1, 0.0625, 2
    keys = jnp.stack([jax.random.key_data(_key(d, i)) for i in range(n)])
    full = _ref_support(keys, p, d, 0, d)
    cap = int(jnp.min(jnp.sum(full[:, :cap_at], axis=1)))
    mus = jnp.stack([jnp.mean(_flat(d + i, d)) for i in range(n)])
    bufs = []
    for i in range(n):
        want = _ref_encode(_flat(d + i, d), keys[i], p, cap, mus[i])
        _assert_bits(ops.encode(_flat(d + i, d), keys[i], p, cap, mus[i],
                                force_pallas=True), want)
        bufs.append(want)
    bufs = jnp.stack(bufs)
    prior = jnp.sum(full[:, :start], axis=1).astype(jnp.int32)
    sent = _ref_support(keys, p, d, jnp.int32(start), ds)
    got = ops.decode_sum_shard(bufs, mus, keys, sent, prior,
                               jnp.int32(start), p=p, cap=cap, d=d,
                               force_pallas=True)
    _assert_bits(got, _ref_shard_sum(bufs, mus, sent, prior, cap))


# --------------------------------------------------------------------------- #
# Full codec roundtrip on the 16-bit (bfloat16 packed-halves) wire.
# --------------------------------------------------------------------------- #

@pytest.mark.parametrize("wire_dtype", ("float32", "bfloat16"))
def test_codec_roundtrip_decode_gathered_matches_unpack(wire_dtype):
    """BernoulliCodec.decode_gathered (fused batched path) must equal the
    sequential unpack→mean chain on the wire-dtype-quantized rows."""
    d, n = 1500, 4
    cfg = t.CompressionConfig(
        encoder=t.EncoderSpec(kind="bernoulli", fraction=1.0 / 16,
                              center="mean"),
        mode="gather_decode", wire_dtype=wire_dtype)
    codec = codecs.BernoulliCodec()
    key = jax.random.PRNGKey(11)
    rows = jnp.stack([codec.pack(_flat(100 + i, d), key, i, cfg)
                      for i in range(n)])
    assert rows.dtype == jnp.dtype(wire_dtype)
    want = jnp.mean(jnp.stack([codec.unpack(rows[i], i, key, cfg, d)
                               for i in range(n)]), axis=0)
    got = codec.decode_gathered(rows, key, cfg, d, n)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


# --------------------------------------------------------------------------- #
# Hypothesis layer (optional): widens the sweep when available.
# --------------------------------------------------------------------------- #

if HAS_HYPOTHESIS:
    SET = settings(max_examples=15, deadline=None)

    @SET
    @given(seed=st.integers(0, 2**31 - 1), d=st.integers(1, 3000),
           cap_frac=st.sampled_from((0.1, 0.5, 1.0, 2.0)),
           p=st.sampled_from((0.05, 0.3, 0.9)))
    def test_hyp_rank_select_equals_legacy_scatter(seed, d, cap_frac, p):
        vals = _flat(seed, d)
        sent = jax.random.uniform(_key(seed + 1), (d,)) < p
        cap = max(1, int(d * cap_frac))
        got = ref.rank_select(vals.astype(jnp.float32), sent, cap)
        np.testing.assert_array_equal(
            np.asarray(got), np.asarray(_legacy_scatter(vals, sent, cap)))

    @SET
    @given(seed=st.integers(0, 2**31 - 1), d=st.integers(1, 5000),
           p=st.sampled_from(P_POW2), scaled=st.booleans())
    def test_hyp_encode_kernel_exact_for_pow2_inv_p(seed, d, p, scaled):
        cap = comm_cost.bernoulli_capacity(d, p)
        flat = _flat(seed, d)
        mu = jnp.mean(flat)
        want = ref.encode(flat, _key(seed), p, cap, mu, scaled=scaled)
        got = ops.encode(flat, _key(seed), p, cap, mu, scaled=scaled,
                         force_pallas=True)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
