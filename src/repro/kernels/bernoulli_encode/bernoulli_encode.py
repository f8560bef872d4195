"""Pallas TPU kernel: fused Bernoulli sparsification encoder (Eq. (1)).

Fuses PRNG → mask → affine rescale into a single pass: one HBM read of the
gradient block and one write of the encoded block.  The unfused jnp version
materializes the uniform field and the mask (≥3 HBM round-trips on a purely
memory-bound op) — the fusion is a ~3× HBM-traffic reduction, which is the
relevant roofline term for encoder throughput at gradient scale (§1.1's
O(d) encode-time claim).

Layout: the flat gradient is viewed as (rows, LANES) with LANES = 128 and
tiled (BM, 128) per program; the PRNG counter is the global coordinate
index, so results are independent of the tiling.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import prng

LANES = 128
BM = 512  # rows per program: (512, 128) f32 = 256 KiB in, 256 KiB out.


def _kernel(x_ref, scal_ref, o_ref):
    i = pl.program_id(0)
    x = x_ref[...]  # (BM, LANES)
    p = scal_ref[0, 0]
    mu = scal_ref[0, 1]
    # seed travels as two exact 16-bit halves (f32 represents ints < 2^24).
    seed = (scal_ref[0, 2].astype(jnp.uint32) * jnp.uint32(65536)
            + scal_ref[0, 3].astype(jnp.uint32))
    bm, bn = x.shape
    row = jax.lax.broadcasted_iota(jnp.uint32, (bm, bn), 0)
    col = jax.lax.broadcasted_iota(jnp.uint32, (bm, bn), 1)
    base = (jnp.uint32(i) * jnp.uint32(bm)) * jnp.uint32(bn)
    idx = base + row * jnp.uint32(bn) + col
    u = prng.uniform_hash(seed, idx)
    sent = u < p
    xf = x.astype(jnp.float32)
    y = jnp.where(sent, xf / p - (1.0 - p) / p * mu, mu)
    o_ref[...] = y.astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret",))
def bernoulli_encode_2d(x, scal, *, interpret: bool = False):
    """x: (R, 128) with R % BM == 0; scal: (1, 4) f32 [p, mu, seed_bits, _]."""
    r, c = x.shape
    assert c == LANES and r % BM == 0, (r, c)
    return pl.pallas_call(
        _kernel,
        grid=(r // BM,),
        in_specs=[
            pl.BlockSpec((BM, LANES), lambda i: (i, 0)),
            pl.BlockSpec((1, 4), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((BM, LANES), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((r, c), x.dtype),
        name="bernoulli_encode",
        interpret=interpret,
    )(x, scal)
