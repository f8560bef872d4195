"""Pallas TPU kernels for the Walsh–Hadamard transform (butterfly order).

The kernels run the classic radix-2 butterfly of :mod:`.ref` — stage by
stage, stride 1, 2, 4, …, with ``x[i] + x[i+h]`` in the lower and
``x[i-h] − x[i]`` in the upper half of every 2h-group — so every output is
the same chain of f32 adds as the CPU path and the transform is
bit-identical on every backend.  That is what keeps the rotated wire
formats (DESIGN.md §7) backend-independent: a matmul formulation
(H_{d1} ⊗ H_{d2} on the MXU) sums in the MXU's own order and differs from
the butterfly in the last bits of nearly every coordinate.

Layout: a length-c vector (c a power of two, c ≥ 1024) is viewed row-major
as (c/128, 128).  Strides below 128 pair lanes (``pltpu.roll`` along the
lanes), strides below the tile height pair rows (roll along the sublanes);
both rolls stay inside one VMEM tile.  Strides of a whole tile or more pair
tiles with each other and run in a second kernel whose block stacks the
same rows of every tile.  Each element's butterfly partner is found by
rolling in both directions and selecting on the stride bit, exactly as in
the jnp oracle.

Fused extras for the rotation (repro.kernels.rotated_encode): an optional
±1 sign vector multiplied in before the first stage, an optional
``inv_scale`` multiplied in after the last, and per-block (min, max)
partials of the result.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128
MIN_D = 8 * LANES        # one (8, 128) f32 tile
TILE_ROWS = 512          # pass-1 block: (512, 128) f32 = 256 KiB
CROSS_ROWS = 64          # pass-2 block: (tiles, 64, 128)


def _butterfly(x, h: int, axis: int):
    """One radix-2 stage at stride h along ``axis`` (h < extent)."""
    n = x.shape[axis]
    pos = jax.lax.broadcasted_iota(jnp.int32, x.shape, axis)
    below = pltpu.roll(x, h, axis)        # below[i] = x[i − h]
    above = pltpu.roll(x, n - h, axis)    # above[i] = x[i + h]
    return jnp.where((pos & h) != 0, below - x, x + above)


def _tile_stages(x):
    """All stages with stride < rows·128 on a row-major (rows, 128) tile."""
    h = 1
    while h < LANES:
        x = _butterfly(x, h, 1)
        h *= 2
    h = 1
    while h < x.shape[0]:
        x = _butterfly(x, h, 0)
        h *= 2
    return x


def _minmax(lo, hi):
    """(2, 128) block: row 0 = min(lo), row 1 = max(hi), broadcast."""
    mn = jnp.min(jnp.min(lo, axis=0, keepdims=True), axis=1, keepdims=True)
    mx = jnp.max(jnp.max(hi, axis=0, keepdims=True), axis=1, keepdims=True)
    row = jax.lax.broadcasted_iota(jnp.int32, (2, LANES), 0)
    return jnp.where(row == 0, mn, mx)


def _tile_kernel(*refs, signed: bool, last: bool, inv_scale, with_mm: bool):
    x_ref = refs[0]
    s_ref = refs[1] if signed else None
    o_ref = refs[1 + signed]
    x = x_ref[...].astype(jnp.float32)
    if signed:
        x = x * s_ref[...]
    z = _tile_stages(x)
    if last and inv_scale is not None:
        z = z * jnp.float32(inv_scale)
    o_ref[...] = z
    if last and with_mm:
        refs[2 + signed][0] = _minmax(z, z)


def _cross_kernel(x_ref, *out_refs, inv_scale, with_mm: bool):
    o_ref = out_refs[0]
    tiles = x_ref.shape[1]
    parts = [x_ref[0, i] for i in range(tiles)]
    h = 1
    while h < tiles:
        parts = [parts[i - h] - parts[i] if i & h else parts[i] + parts[i + h]
                 for i in range(tiles)]
        h *= 2
    if inv_scale is not None:
        parts = [p * jnp.float32(inv_scale) for p in parts]
    for i in range(tiles):
        o_ref[0, i] = parts[i]
    if with_mm:
        out_refs[1][0] = _minmax(functools.reduce(jnp.minimum, parts),
                                 functools.reduce(jnp.maximum, parts))


@functools.partial(jax.jit, static_argnames=("inv_scale", "with_minmax",
                                             "interpret"))
def fwht_pallas(x, signs=None, *, inv_scale=None, with_minmax: bool = False,
                interpret: bool = False):
    """Unnormalized Walsh–Hadamard transform of each row of x: (B, c).

    c must be a power of two ≥ :data:`MIN_D`.  ``signs`` (B, c) is
    multiplied in first and ``inv_scale`` (a Python float, applied as an
    f32 multiply) last.  Returns the (B, c) f32 transform, plus the
    (B, 2) per-row (min, max) when ``with_minmax``.
    """
    b, c = x.shape
    assert c >= MIN_D and c & (c - 1) == 0, c
    rows = c // LANES
    tr = min(rows, TILE_ROWS)
    tiles = rows // tr
    single = tiles == 1
    ntile = b * tiles
    x2 = x.reshape(b * rows, LANES)
    ins = [x2]
    in_specs = [pl.BlockSpec((tr, LANES), lambda i: (i, 0))]
    if signs is not None:
        ins.append(signs.reshape(b * rows, LANES).astype(jnp.float32))
        in_specs.append(pl.BlockSpec((tr, LANES), lambda i: (i, 0)))
    out_shape = [jax.ShapeDtypeStruct((b * rows, LANES), jnp.float32)]
    out_specs = [pl.BlockSpec((tr, LANES), lambda i: (i, 0))]
    mm_tile = single and with_minmax
    if mm_tile:
        out_shape.append(jax.ShapeDtypeStruct((ntile, 2, LANES), jnp.float32))
        out_specs.append(pl.BlockSpec((1, 2, LANES), lambda i: (i, 0, 0)))
    outs = pl.pallas_call(
        functools.partial(_tile_kernel, signed=signs is not None,
                          last=single, inv_scale=inv_scale,
                          with_mm=mm_tile),
        grid=(ntile,),
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        name="hadamard",
        interpret=interpret,
    )(*ins)
    y, mm = outs[0], (outs[1] if mm_tile else None)
    if not single:
        cr = min(tr, CROSS_ROWS)
        ncol = tr // cr
        y4 = y.reshape(b, tiles, tr, LANES)
        spec = pl.BlockSpec((1, tiles, cr, LANES), lambda i, j: (i, 0, j, 0))
        out_shape = [jax.ShapeDtypeStruct(y4.shape, jnp.float32)]
        out_specs = [spec]
        if with_minmax:
            out_shape.append(
                jax.ShapeDtypeStruct((b * ncol, 2, LANES), jnp.float32))
            out_specs.append(pl.BlockSpec(
                (1, 2, LANES), lambda i, j: (i * ncol + j, 0, 0)))
        outs = pl.pallas_call(
            functools.partial(_cross_kernel, inv_scale=inv_scale,
                              with_mm=with_minmax),
            grid=(b, ncol),
            in_specs=[spec],
            out_specs=out_specs,
            out_shape=out_shape,
            name="hadamard_cross",
            interpret=interpret,
        )(y4)
        y = outs[0]
        mm = outs[1] if with_minmax else None
    y = y.reshape(b, c)
    if not with_minmax:
        return y
    part = mm[:, :, 0].reshape(b, -1, 2)
    return y, jnp.stack([part[:, :, 0].min(axis=1),
                         part[:, :, 1].max(axis=1)], axis=1)
