"""Oracle-equivalence for the fused §7.2 rotate+encode kernel pair and
consistency of the fused dispatch with the CPU production chain.

The rotation kernel runs the butterfly FWHT of kernels/hadamard/ref.py
with the same f32 add chain, and the threshold uses the same correctly
rounded quotient (repro.kernels.numerics), so kernel ↔ production is EXACT
— every plane bit and the (vmin, vmax) tail (interpret mode, CPU)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import bitplane, rotation
from repro.kernels.hadamard import hadamard as hk
from repro.kernels.hadamard import ops as hops
from repro.kernels.rotated_encode import kernel, ops, ref


def _setup(seed, dp):
    c = min(dp, hops.MAX_D)
    key = jax.random.PRNGKey(seed)
    krot = rotation.rotation_key(key)
    x = jax.random.normal(jax.random.PRNGKey(seed + 50), (dp,), jnp.float32)
    signs = rotation.rademacher_diag(krot, dp, jnp.float32)
    return key, krot, x, x.reshape(-1, c), signs.reshape(-1, c), c


@pytest.mark.parametrize("seed", (0, 5))
@pytest.mark.parametrize("dp", (1024, 4096, 1 << 16))
def test_rotate_minmax_kernel_exact(seed, dp):
    """Fused signs + butterfly + scale + (min, max) == rotation.rotate."""
    key, krot, x, x2, s2, c = _setup(seed, dp)
    want = np.asarray(rotation.rotate(krot, x)).reshape(-1, c)
    z_k, mm = hk.fwht_pallas(x2, s2, inv_scale=rotation.inv_scale(c),
                             with_minmax=True, interpret=True)
    np.testing.assert_array_equal(np.asarray(z_k), want)
    np.testing.assert_array_equal(np.asarray(mm[:, 0]), want.min(axis=1))
    np.testing.assert_array_equal(np.asarray(mm[:, 1]), want.max(axis=1))


@pytest.mark.parametrize("seed", (0, 5))
@pytest.mark.parametrize("dp", (256, 1024, 4096))
def test_encode_pack_kernel_exact(seed, dp):
    key, krot, x, _, _, _ = _setup(seed, dp)
    z = rotation.rotate(krot, x)
    vmin, vmax = jnp.min(z), jnp.max(z)
    kenc = jax.random.fold_in(key, 2)
    want = ref.binary_plane(z, kenc, vmin, vmax, dp)
    got = kernel.encode_pack_pallas(z, kenc, vmin, vmax, dp=dp,
                                    interpret=True)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    # the oracle is the production encoder's plane
    prod = bitplane.binary_pack(z, kenc, "float32")[:-2]
    np.testing.assert_array_equal(np.asarray(got), np.asarray(prod))


def test_encode_pack_degenerate_delta_zero():
    """Constant z ⇒ Δ = 0 ⇒ p = 0 everywhere ⇒ an all-zero plane (the
    guarded-threshold branch of encode_binary)."""
    dp = 512
    z = jnp.full((dp,), 0.25, jnp.float32)
    got = kernel.encode_pack_pallas(z, jax.random.PRNGKey(0),
                                    jnp.float32(0.25), jnp.float32(0.25),
                                    dp=dp, interpret=True)
    assert not np.asarray(got).any()


@pytest.mark.parametrize("seed", (0, 3))
@pytest.mark.parametrize("d", (300, 1000, 4096, 5000))
@pytest.mark.parametrize("wire_dtype", ("float32", "bfloat16"))
def test_fused_pack_binary_consistent_with_production(seed, d, wire_dtype):
    key = jax.random.PRNGKey(seed)
    x = jax.random.normal(jax.random.PRNGKey(seed + 9), (d,), jnp.float32)
    prod = ops.pack_binary(x, key, 1, wire_dtype)
    fused = ops.pack_binary(x, key, 1, wire_dtype, force_pallas=True)
    assert prod.shape == fused.shape and prod.dtype == fused.dtype
    # every plane bit and both tail scalars identical
    np.testing.assert_array_equal(np.asarray(fused), np.asarray(prod))


def test_small_dp_uses_production_chain_verbatim():
    """dp below one (8, 128) tile must fall back to the exact CPU chain
    even under force_pallas."""
    d = 100
    key = jax.random.PRNGKey(1)
    x = jax.random.normal(jax.random.PRNGKey(2), (d,), jnp.float32)
    a = ops.pack_binary(x, key, 0, "float32")
    b = ops.pack_binary(x, key, 0, "float32", force_pallas=True)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
