"""jit'd wrapper: block-structured fixed-k encode/decode for any-shape arrays.

k is expressed in *blocks* (kb) of ref.BLOCK coordinates; the flat input is
zero-padded to a BLOCK multiple (padding joins the population like real
coordinates — harmless: its deviations are (0 − μ), reconstructed exactly
as μ-centred noise that is sliced away before use).

Backend policy: repro.kernels.backend (resolved once at import, like every
other dispatcher) — TPU → the Pallas gather kernel, elsewhere → jnp.
"""
from __future__ import annotations

import jax.numpy as jnp

from repro.kernels import backend
from repro.kernels.fixed_k_encode import fixed_k_encode as _kernel
from repro.kernels.fixed_k_encode import ref as _ref
from repro.kernels.fixed_k_encode.ref import sample_blocks  # noqa: F401  (re-export)

BLOCK = _ref.BLOCK


def num_blocks(n: int) -> int:
    return (n + BLOCK - 1) // BLOCK


def fixed_k_encode(x, block_ids, mu, *, scale=None, force_pallas: bool = False):
    """Gather-encode: returns wire values scale·(x[S] − μ), (kb, BLOCK) f32.

    ``scale=None`` uses the unbiased d/k rescale of Eq. (4); ``scale=1.0``
    gives the *contractive* (biased) sparsifier used by error feedback.
    """
    use_pallas, interpret = backend.choose(force_pallas)
    flat = x.reshape(-1).astype(jnp.float32)
    n = flat.shape[0]
    flat = jnp.pad(flat, (0, (-n) % BLOCK))
    d = flat.shape[0]
    k = block_ids.shape[0] * BLOCK
    if scale is None:
        scale = d / k
    if not use_pallas:
        blocks = flat.reshape(-1, BLOCK)[block_ids]
        return scale * (blocks - jnp.asarray(mu, jnp.float32))
    scal = jnp.stack([jnp.asarray(scale, jnp.float32),
                      jnp.asarray(mu, jnp.float32)]).reshape(1, 2)
    x3 = flat.reshape(-1, _kernel.ROWS, _kernel.BS)
    out = _kernel.fixed_k_gather_2d(x3, block_ids, scal, interpret=interpret)
    return out.reshape(-1, BLOCK)


def fixed_k_decode(values, block_ids, mu, shape, dtype=jnp.float32):
    """Scatter-decode dense Y_i and restore the original shape."""
    n = 1
    for s in shape:
        n *= s
    d = num_blocks(n) * BLOCK
    y = _ref.fixed_k_decode(values, block_ids, mu, d)
    return y[:n].reshape(shape).astype(dtype)
