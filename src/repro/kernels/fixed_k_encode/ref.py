"""Pure-jnp oracle for the block-structured fixed-k encoder (Eq. (4), TPU form).

TPU adaptation (DESIGN.md §2): instead of k independent coordinates, the
support is kb = k/BLOCK tile-aligned blocks of BLOCK = 1024 contiguous
coordinates (one (8, 128) f32 TPU tile each), sampled uniformly without
replacement from the d/BLOCK blocks.  Every coordinate still has inclusion
probability exactly k/d, and since the MSE (Lemma 2.3) is a sum of
per-coordinate second moments, the Lemma 3.4 closed form
(d−k)/k · Σ(X−μ)²/n² holds *unchanged* — block sampling only introduces
cross-coordinate error correlations, which the squared-norm objective never
sees (verified: tests/test_kernel_fixed_k.py::test_block_mse_matches_lemma34).

encode: gather the selected blocks, rescaled to the unbiased wire values
        v = (d/k)·(x − μ) (so the decoder reconstructs Y = μ + scatter(v));
decode: scatter back, add μ.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels.threefry import ref as threefry

BLOCK = 1024


def sample_blocks(key, num_blocks: int, kb: int):
    """Uniform kb-subset of block ids (Gumbel top-k), sorted."""
    g = threefry.gumbel(key, (num_blocks,))
    _, ids = jax.lax.top_k(g, kb)
    return jnp.sort(ids)


def fixed_k_encode(x, block_ids, mu):
    """x: flat (d,) with d % BLOCK == 0 -> wire values (kb, BLOCK)."""
    d = x.shape[0]
    kb = block_ids.shape[0]
    k = kb * BLOCK
    blocks = x.reshape(-1, BLOCK)[block_ids]  # (kb, BLOCK)
    return (d / k) * (blocks - jnp.asarray(mu, x.dtype))


def fixed_k_decode(values, block_ids, mu, d: int):
    """Reconstruct dense Y_i = μ + scatter(values).  values: (kb, BLOCK)."""
    out = jnp.zeros((d // BLOCK, BLOCK), values.dtype).at[block_ids].set(values)
    return (out + jnp.asarray(mu, values.dtype)).reshape(d)
