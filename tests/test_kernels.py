"""Pallas kernel validation: interpret-mode sweeps vs pure-jnp oracles."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import prng
from repro.kernels.bernoulli_encode import bernoulli_encode as bern_kernel
from repro.kernels.bernoulli_encode import ops as bern_ops
from repro.kernels.bernoulli_encode import ref as bern_ref
from repro.kernels.binary_quant import binary_quant as bq_kernel
from repro.kernels.binary_quant import ops as bq_ops
from repro.kernels.binary_quant import ref as bq_ref
from repro.kernels.fixed_k_encode import ops as fk_ops
from repro.kernels.fixed_k_encode import ref as fk_ref
from repro.kernels.hadamard import hadamard as h_kernel
from repro.kernels.hadamard import ref as h_ref

KEY = jax.random.PRNGKey(0)


# --------------------------- hadamard ------------------------------------ #

@pytest.mark.parametrize("d", [1024, 2048, 4096, 8192, 1 << 16, 1 << 17])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_fwht_pallas_matches_ref(d, dtype):
    """The butterfly kernel runs ref.fwht's f32 add chain: exact, across
    the one-pass (≤ 512 rows) and two-pass (tile-crossing) layouts."""
    x = jax.random.normal(KEY, (3, d)).astype(dtype)
    got = h_kernel.fwht_pallas(x, interpret=True)
    want = h_ref.fwht(x.astype(jnp.float32))
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_fwht_ref_matches_matrix():
    d = 32
    x = jax.random.normal(KEY, (2, d))
    H = h_ref.hadamard_matrix(d)
    np.testing.assert_allclose(h_ref.fwht(x), x @ H.T, atol=1e-4)


def test_fwht_involution():
    """H·H = d·I  ⇒  fwht(fwht(x)) = d·x."""
    d = 128
    x = jax.random.normal(KEY, (d,))
    np.testing.assert_allclose(h_ref.fwht(h_ref.fwht(x)), d * x, atol=1e-3)


def test_rotation_roundtrip():
    from repro.core import rotation
    x = jax.random.normal(KEY, (5, 200))  # non-power-of-two: pads to 256
    z = rotation.rotate(jax.random.PRNGKey(7), x)
    back = rotation.unrotate(jax.random.PRNGKey(7), z, 200)
    np.testing.assert_allclose(back, x, atol=1e-4)


def test_rotation_preserves_norm():
    from repro.core import rotation
    x = jax.random.normal(KEY, (256,))
    z = rotation.rotate(jax.random.PRNGKey(7), x)
    np.testing.assert_allclose(jnp.linalg.norm(z), jnp.linalg.norm(x), rtol=1e-5)


# --------------------------- bernoulli_encode ----------------------------- #

@pytest.mark.parametrize("rows", [512, 1024])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_bernoulli_kernel_matches_ref(rows, dtype):
    x = jax.random.normal(KEY, (rows, 128)).astype(dtype)
    seed_u = jnp.uint32(0xDEADBEEF)
    scal = jnp.stack([jnp.float32(0.3), jnp.float32(0.1),
                      (seed_u >> jnp.uint32(16)).astype(jnp.float32),
                      (seed_u & jnp.uint32(0xFFFF)).astype(jnp.float32)]
                     ).reshape(1, 4)
    got = bern_kernel.bernoulli_encode_2d(x, scal, interpret=True)
    want = bern_ref.bernoulli_encode(x, 0.3, 0.1, 0xDEADBEEF)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               atol=0.02 if dtype == jnp.bfloat16 else 1e-6)


def test_bernoulli_ops_arbitrary_shape():
    x = jax.random.normal(KEY, (3, 1000))
    got = bern_ops.bernoulli_encode(x, 0.5, 0.0, 123, force_pallas=True)
    want = bern_ref.bernoulli_encode(x.reshape(-1), 0.5, 0.0, 123)[:3000]
    np.testing.assert_allclose(got.reshape(-1), want, atol=1e-6)


def test_mask_statistics():
    """The in-kernel hash PRNG produces p-fraction masks, unbiased values."""
    n = 1 << 18
    x = jnp.ones((n,))
    for p in [0.1, 0.5]:
        y = bern_ref.bernoulli_encode(x, p, 0.0, 77)
        frac = float(jnp.mean((y != 0.0).astype(jnp.float32)))
        assert abs(frac - p) < 0.01
        assert abs(float(jnp.mean(y)) - 1.0) < 0.02  # unbiased


def test_hash_uniformity():
    u = prng.uniform_hash(jnp.uint32(9), jnp.arange(1 << 16, dtype=jnp.uint32))
    # mean ≈ 1/2, var ≈ 1/12, no mass outside [0, 1)
    assert abs(float(jnp.mean(u)) - 0.5) < 0.01
    assert abs(float(jnp.var(u)) - 1 / 12) < 0.01
    assert float(jnp.min(u)) >= 0.0 and float(jnp.max(u)) < 1.0


# --------------------------- binary_quant --------------------------------- #

@pytest.mark.parametrize("rows", [512])
def test_binary_kernel_matches_ref(rows):
    x = jax.random.normal(KEY, (rows, 128))
    vmin, vmax = jnp.min(x).astype(jnp.float32), jnp.max(x).astype(jnp.float32)
    seed_u = jnp.uint32(42)
    scal = jnp.stack([vmin, vmax,
                      (seed_u >> jnp.uint32(16)).astype(jnp.float32),
                      (seed_u & jnp.uint32(0xFFFF)).astype(jnp.float32)]
                     ).reshape(1, 4)
    got = bq_kernel.binary_encode_2d(x, scal, interpret=True)
    want, _, _ = bq_ref.binary_encode(x, 42)
    np.testing.assert_array_equal(np.asarray(got).reshape(-1), np.asarray(want))


def test_binary_roundtrip_values():
    x = jax.random.normal(KEY, (4, 512))
    packed, vmin, vmax = bq_ops.binary_encode(x, 7)
    y = bq_ops.binary_decode(packed, vmin, vmax, x.shape)
    vals = np.unique(np.asarray(y))
    assert all(np.isclose(v, float(vmin)) or np.isclose(v, float(vmax))
               for v in vals), vals


def test_binary_unbiased_via_kernel():
    """Signed error averaged over seeds & coordinates ≈ 0 (unbiased)."""
    x = jax.random.normal(KEY, (1 << 14,))
    recon = []
    for seed in range(64):
        packed, vmin, vmax = bq_ops.binary_encode(x, seed)
        recon.append(bq_ops.binary_decode(packed, vmin, vmax, x.shape))
    err = jnp.mean(jnp.stack(recon), axis=0) - x
    # per-coordinate std ~ Δ/2/√64 ≈ 0.45: the signed grand mean over
    # 2^14 coordinates has std ≈ 0.45/√2^14 ≈ 0.004.
    assert abs(float(jnp.mean(err))) < 0.02
    assert float(jnp.mean(jnp.abs(err))) < 0.6


# --------------------------- fixed_k_encode ------------------------------- #

@pytest.mark.parametrize("d_blocks,kb", [(8, 2), (32, 8), (64, 64)])
def test_fixed_k_kernel_matches_ref(d_blocks, kb):
    d = d_blocks * fk_ref.BLOCK
    x = jax.random.normal(KEY, (d,))
    ids = fk_ref.sample_blocks(jax.random.PRNGKey(1), d_blocks, kb)
    got = fk_ops.fixed_k_encode(x, ids, 0.25, force_pallas=True)
    want = fk_ref.fixed_k_encode(x, ids, 0.25)
    np.testing.assert_allclose(got, want, rtol=1e-5)


def test_fixed_k_roundtrip_unbiased():
    d = 16 * fk_ref.BLOCK
    x = jax.random.normal(KEY, (d,))
    mu = float(jnp.mean(x))
    recons = []
    for seed in range(200):
        ids = fk_ref.sample_blocks(jax.random.PRNGKey(seed), 16, 4)
        vals = fk_ops.fixed_k_encode(x, ids, mu)
        recons.append(fk_ops.fixed_k_decode(vals, ids, mu, (d,)))
    est = jnp.mean(jnp.stack(recons), axis=0)
    assert float(jnp.mean(jnp.abs(est - x))) < 0.25


def test_block_mse_matches_lemma34():
    """Block-structured support has exactly the Lemma 3.4 MSE (DESIGN §2)."""
    from repro.core import mse as mse_lib
    n, nb = 8, 16
    d = nb * fk_ref.BLOCK
    kb = 4
    k = kb * fk_ref.BLOCK
    xs = jax.random.normal(jax.random.PRNGKey(3), (n, d)) * 0.1
    mus = jnp.mean(xs, axis=-1)
    x_true = jnp.mean(xs, axis=0)

    def one(trial):
        ys = []
        for i in range(n):
            ids = fk_ref.sample_blocks(
                jax.random.fold_in(jax.random.PRNGKey(trial), i), nb, kb)
            vals = fk_ref.fixed_k_encode(xs[i], ids, mus[i])
            ys.append(fk_ref.fixed_k_decode(vals, ids, mus[i], d))
        err = jnp.mean(jnp.stack(ys), axis=0) - x_true
        return jnp.sum(err * err)

    errs = jnp.stack([jax.jit(one)(t) for t in range(300)])
    got = float(jnp.mean(errs))
    want = float(mse_lib.mse_fixed_k(xs, k, mus))
    se = float(jnp.std(errs)) / np.sqrt(300)
    assert abs(got - want) < max(5 * se, 0.05 * want), (got, want, se)
