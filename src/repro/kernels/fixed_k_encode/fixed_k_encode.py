"""Pallas TPU kernel: block-structured fixed-k gather-encode.

The selected block ids arrive via scalar prefetch and drive the input
BlockSpec's index_map — the classic Pallas gather pattern.  Each program
DMAs exactly one selected BLOCK-coordinate block (one (8, 128) f32 tile)
HBM→VMEM, applies the unbiased rescale v = (d/k)(x − μ), and writes the
compacted wire buffer.  HBM traffic is therefore k reads + k writes; the
dense-mask alternative reads all d coordinates.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

BS = 128   # lane width
ROWS = 8   # sublane rows; ROWS*BS == ref.BLOCK


def _kernel(ids_ref, x_ref, scal_ref, o_ref):
    del ids_ref  # consumed by the index_map
    scale = scal_ref[0, 0]
    mu = scal_ref[0, 1]
    o_ref[...] = (scale * (x_ref[...].astype(jnp.float32) - mu)).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret",))
def fixed_k_gather_2d(x, block_ids, scal, *, interpret: bool = False):
    """x: (NB, ROWS, BS); block_ids: (kb,) int32; scal: (1, 2) [scale, mu].

    Returns (kb, ROWS, BS) wire values.
    """
    nb, rows, bs = x.shape
    assert rows == ROWS and bs == BS, (rows, bs)
    kb = block_ids.shape[0]
    return pl.pallas_call(
        _kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(kb,),
            in_specs=[
                pl.BlockSpec((1, ROWS, BS), lambda i, ids: (ids[i], 0, 0)),
                pl.BlockSpec((1, 2), lambda i, ids: (0, 0)),
            ],
            out_specs=pl.BlockSpec((1, ROWS, BS), lambda i, ids: (i, 0, 0)),
        ),
        out_shape=jax.ShapeDtypeStruct((kb, ROWS, BS), x.dtype),
        name="fixed_k_encode",
        interpret=interpret,
    )(block_ids, x, scal)
