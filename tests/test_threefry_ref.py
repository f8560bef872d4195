"""Bit-exactness of the repo's Threefry-2x32 stream
(repro.kernels.threefry.ref) against JAX's own PRNG in its legacy layout.

The stream is defined as jax's non-partitionable layout (the one the
golden wire bytes were generated with).  Every comparison with
``jax.random`` therefore runs inside ``jax.threefry_partitionable(False)``,
scoped to the test; the library itself never reads that setting.  Any
drift here silently changes the wire format — every check is exact
uint32/float32 equality, never allclose."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.threefry import ref as tref

SEEDS = (0, 1, 7, 123456789, 2**31 - 1)
# odd and even lengths, tiny through multi-block, around the half split
LENGTHS = (1, 2, 3, 31, 32, 33, 255, 256, 1000, 1001, 4096, 5000)


def _raw(seed):
    return jax.random.key_data(jax.random.PRNGKey(seed))


def _legacy():
    return jax.threefry_partitionable(False)


@pytest.mark.parametrize("seed", SEEDS)
@pytest.mark.parametrize("d", LENGTHS)
def test_random_bits_bit_exact(seed, d):
    key = jax.random.PRNGKey(seed)
    with _legacy():
        want = jax.random.bits(key, (d,), jnp.uint32)
    got = tref.random_bits(_raw(seed), d)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("seed", SEEDS)
@pytest.mark.parametrize("d", LENGTHS)
def test_uniform_bit_exact(seed, d):
    key = jax.random.PRNGKey(seed)
    with _legacy():
        want = jax.random.uniform(key, (d,), jnp.float32)
    got = tref.uniform(_raw(seed), d)
    assert got.dtype == jnp.float32
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("seed", (0, 42))
def test_uniform_after_fold_in(seed):
    """The wire paths always draw from fold_in(key, rank) — the folded raw
    key words must reproduce the same stream."""
    for rank in (0, 1, 5):
        key = jax.random.fold_in(jax.random.PRNGKey(seed), rank)
        with _legacy():
            want = jax.random.uniform(key, (777,), jnp.float32)
        got = tref.uniform(jax.random.key_data(key), 777)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("seed", SEEDS[:3])
@pytest.mark.parametrize("d", (1, 31, 32, 33, 1000))
def test_counter_words_match_flat_layout(seed, d):
    """counter_words(idx, d) evaluated at scattered idx must reproduce the
    exact per-coordinate bits of the flat (d,) draw — this is the identity
    the in-kernel blocks rely on."""
    key = _raw(seed)
    flat = tref.random_bits(key, d)
    idx = jnp.asarray(
        np.random.default_rng(seed).permutation(d).astype(np.uint32))
    c0, c1, lo = tref.counter_words(idx, d)
    o0, o1 = tref.threefry2x32(key[0], key[1], c0, c1)
    got = jnp.where(lo, o0, o1)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(flat[idx]))


@pytest.mark.parametrize("seed", SEEDS[:3])
@pytest.mark.parametrize("d", (1, 31, 32, 33, 1000))
def test_uniform_at_random_access_bit_exact(seed, d):
    """uniform_at(key, idx, d) — the random-access draw the reduce-scatter
    Bernoulli decode regenerates shard supports from (DESIGN.md §11) —
    must equal the flat (d,) uniform at those indices, bit for bit."""
    key = jax.random.PRNGKey(seed)
    flat = tref.uniform(key, d)
    idx = jnp.asarray(
        np.random.default_rng(seed).permutation(d).astype(np.int32))
    got = tref.uniform_at(key, idx, d)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(flat)[idx])


def test_bits_to_uniform_edge_values():
    """All-ones bits stay < 1; all-zero bits clamp at exactly 0."""
    u = tref.bits_to_uniform(jnp.array([0, 0xFFFFFFFF, 1 << 9], jnp.uint32))
    vals = np.asarray(u)
    assert vals[0] == 0.0
    assert 0.0 < vals[2] < vals[1] < 1.0


@pytest.mark.parametrize("seed", SEEDS[:3])
@pytest.mark.parametrize("shape", ((4096,), (5000,), (33,), (3, 7), (2, 3, 5)))
def test_gumbel_bit_exact(seed, shape):
    """Fixed-k supports are Gumbel top-k: jax's legacy formula
    (uniform with minval=tiny, then −log(−log u)) reproduced op for op."""
    key = jax.random.PRNGKey(seed)
    with _legacy():
        want = jax.random.gumbel(key, shape, jnp.float32)
    got = tref.gumbel(key, shape)
    assert got.shape == shape and got.dtype == jnp.float32
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("seed", SEEDS[:3])
@pytest.mark.parametrize("d", (1, 33, 1000, 4096))
@pytest.mark.parametrize("dtype", (jnp.float32, jnp.bfloat16))
def test_rademacher_bit_exact(seed, d, dtype):
    """The rotation's sign diagonal D."""
    key = jax.random.PRNGKey(seed)
    with _legacy():
        want = jax.random.rademacher(key, (d,), dtype=dtype)
    got = tref.rademacher(key, (d,), dtype)
    assert got.dtype == dtype
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("shape", ((3, 7), (2, 3, 5), (8, 128)))
def test_uniform_shaped_draw_is_flat_draw_reshaped(shape):
    """encoders draw ``uniform(key, x.shape)``: the legacy layout draws
    prod(shape) lanes and reshapes row-major."""
    key = jax.random.PRNGKey(5)
    with _legacy():
        want = jax.random.uniform(key, shape, jnp.float32)
    got = tref.uniform(key, shape)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    np.testing.assert_array_equal(
        np.asarray(got).reshape(-1),
        np.asarray(tref.uniform(key, int(np.prod(shape)))))


def test_typed_keys_match_raw_keys():
    raw = jax.random.PRNGKey(11)
    typed = jax.random.wrap_key_data(raw)
    np.testing.assert_array_equal(np.asarray(tref.uniform(typed, 300)),
                                  np.asarray(tref.uniform(raw, 300)))


@pytest.mark.parametrize("seed", SEEDS)
def test_fold_in_does_not_depend_on_layout(seed):
    """Keys are derived with ``jax.random.fold_in`` — one cipher call on
    the key words, so the partitionable setting must not change it.  This
    is what lets the repo keep fold_in while owning the draws."""
    key = jax.random.PRNGKey(seed)
    outs = []
    for partitionable in (False, True):
        with jax.threefry_partitionable(partitionable):
            k = key
            for data in (0, 1, 7, 0x524F54, 2**31 - 1):
                k = jax.random.fold_in(k, data)
            outs.append(np.asarray(jax.random.key_data(k)))
    np.testing.assert_array_equal(outs[0], outs[1])


def test_stream_ignores_the_global_layout():
    """The repo stream is the same whatever jax's own default is."""
    key = jax.random.PRNGKey(3)
    got = []
    for partitionable in (False, True):
        with jax.threefry_partitionable(partitionable):
            got.append(np.asarray(tref.uniform(key, 1001)))
    np.testing.assert_array_equal(got[0], got[1])


def test_golden_inputs_are_jax_legacy_normal():
    """tests/golden/regen_golden_wire.py draws its input rows from the repo
    stream with jax's normal formula; they must stay the rows the committed
    golden_wire.npz was generated from — jax.random.normal in the legacy
    layout."""
    import importlib.util
    import pathlib

    path = (pathlib.Path(__file__).resolve().parent / "golden"
            / "regen_golden_wire.py")
    spec = importlib.util.spec_from_file_location("regen_golden_wire", path)
    regen = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(regen)
    with _legacy():
        want = 0.5 * jax.random.normal(jax.random.PRNGKey(regen.X_SEED),
                                       (regen.N_RANKS, regen.D))
    got = regen.golden_inputs()
    np.testing.assert_array_equal(np.asarray(got).view(np.uint32),
                                  np.asarray(want).view(np.uint32))
