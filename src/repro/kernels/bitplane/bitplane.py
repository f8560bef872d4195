"""Pallas TPU kernels: bit-plane pack / unpack (uint32 word planes).

The quantized wire paths (binary 1-bit, ternary 2-bit — §4.5 / §7.1) ship
their per-coordinate symbols as packed uint32 words.  These kernels fuse
the w-bit field packing so HBM traffic is read d·4 bytes, write d·w/8
bytes (pack) and the reverse (unpack) — the packed plane is exactly what
travels on the wire.

Layout matches the :mod:`repro.kernels.bitplane.ref` oracle bit-for-bit:
32/w symbols per word, little-endian fields, row-major over the (BM, 128)
tile — so flattening the 2D output reproduces the 1D word stream.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

LANES = 128
BM_PACK = 256    # (256, 128) u32 in -> (256*w/32, 128) u32 out
BM_UNPACK = 8    # (8, 128) u32 words in -> (8, 128*32/w) u32 out
BM_ACCUM = 8     # (n, 8, 128) u32 words -> (8, 128*32) f32 accumulator


def pack_fields(v, width: int):
    """(R, 128) symbols (< 2**width, any int dtype) -> (R·w/32, 128) uint32
    words: the ref.py word stream of the block, row-major and lane-dense.

    Word c of source row r holds lanes [c·per, (c+1)·per) of that row at
    bit offsets w·(lane % per), per = 32/w.  The fields are disjoint, so a
    word is the plain sum of ``symbol · 2**offset``, split into a low
    (offsets < 16) and a high half so every partial sum is an integer
    below 2**16 — exact in f32 on the MXU:

    * ``v @ F`` (F: 128 × 128, column j packs the fields of word j % n_w,
      n_w = 128/per words per row) puts word j % n_w of row r at lane j;
    * a select keeps lane j only in row r with r % per == j // n_w, and
      ``G @ ·`` (G: rows/per × rows, G[q, r] = [r // per == q]) folds each
      group of ``per`` rows into one 128-lane output row.

    Every output element is one nonzero product, so the result is exact.
    The output is lane-dense: flattening it gives the word stream with no
    relayout (a (R, 128/per) output made XLA spend minutes compiling the
    reshape at 2^24 coordinates).  (Mosaic cannot split the lane axis or
    reduce uint32, which the direct formulation needs.)
    """
    per = 32 // width
    n_w = LANES // per
    rows = v.shape[0]
    lane = jax.lax.broadcasted_iota(jnp.int32, (LANES, LANES), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (LANES, LANES), 1)
    shift = width * (lane % per)
    mine = lane // per == col % n_w
    f_lo = jnp.where(mine & (shift < 16), jnp.left_shift(1, shift), 0)
    f_hi = jnp.where(mine & (shift >= 16), jnp.left_shift(1, shift - 16), 0)
    r = jax.lax.broadcasted_iota(jnp.int32, (rows, LANES), 0)
    j = jax.lax.broadcasted_iota(jnp.int32, (rows, LANES), 1)
    keep = r % per == j // n_w
    q = jax.lax.broadcasted_iota(jnp.int32, (rows // per, rows), 0)
    rr = jax.lax.broadcasted_iota(jnp.int32, (rows // per, rows), 1)
    fold = (rr // per == q).astype(jnp.float32)
    vf = v.astype(jnp.int32).astype(jnp.float32)

    def half(f):
        spread = jax.lax.dot(vf, f.astype(jnp.float32),
                             precision=jax.lax.Precision.HIGHEST)
        spread = jnp.where(keep, spread, 0.0)
        return jax.lax.dot(fold, spread,
                           precision=jax.lax.Precision.HIGHEST
                           ).astype(jnp.int32)

    words = jnp.left_shift(half(f_hi), 16) | half(f_lo)
    return jax.lax.bitcast_convert_type(words, jnp.uint32)


def _pack_kernel(width, v_ref, o_ref):
    v = v_ref[...].astype(jnp.uint32) & jnp.uint32((1 << width) - 1)
    o_ref[...] = pack_fields(v, width)


def _unpack_kernel(width, w_ref, o_ref):
    per = 32 // width
    w = w_ref[...].astype(jnp.uint32)
    bm, bn = w.shape
    shifts = (jax.lax.broadcasted_iota(jnp.uint32, (1, 1, per), 2)
              * jnp.uint32(width))
    vals = (w[:, :, None] >> shifts) & jnp.uint32((1 << width) - 1)
    o_ref[...] = vals.reshape(bm, bn * per)


@functools.partial(jax.jit, static_argnames=("width", "interpret"))
def pack_bits_2d(vals, width: int, *, interpret: bool = False):
    """vals: (R, 128) uint32 symbols, R % BM_PACK == 0 -> (R·w/32, 128)
    uint32 words, row-major in the ref.py word order."""
    r, c = vals.shape
    assert c == LANES and r % BM_PACK == 0, (r, c)
    out_rows = BM_PACK * width // 32
    return pl.pallas_call(
        functools.partial(_pack_kernel, width),
        grid=(r // BM_PACK,),
        in_specs=[pl.BlockSpec((BM_PACK, LANES), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((out_rows, LANES), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((r * width // 32, LANES), jnp.uint32),
        name="bitplane_pack",
        interpret=interpret,
    )(vals)


def _accum_kernel(n, w_ref, c_ref, o_ref):
    per = 32
    bm = o_ref.shape[0]
    shifts = jax.lax.broadcasted_iota(jnp.uint32, (1, 1, per), 2)

    def body(i, acc):
        w = w_ref[i]                                     # (BM, LANES) u32
        bits = (w[:, :, None] >> shifts) & jnp.uint32(1)
        sel = bits.reshape(bm, -1) > 0
        return acc + jnp.where(sel, c_ref[i, 1], c_ref[i, 0])

    o_ref[...] = jax.lax.fori_loop(0, n, body,
                                   jnp.zeros(o_ref.shape, jnp.float32))


@functools.partial(jax.jit, static_argnames=("interpret",))
def binary_accum_2d(words, centers, *, interpret: bool = False):
    """Fused unpack + center-select + peer accumulate (scatter decode §13).

    words: (n, R, 128) uint32 1-bit plane windows, R % BM_ACCUM == 0;
    centers: (n, 128) f32 with lane 0 = c_lo, lane 1 = c_hi per peer.
    One pass over the n×window word range folds every peer into a single
    (R, 128*32) f32 accumulator — peers added in ascending order, so the
    result matches the ref.binary_accum oracle (and the sequential flat
    decode) bit-for-bit.
    """
    n, r, c = words.shape
    assert c == LANES and r % BM_ACCUM == 0, (n, r, c)
    out_lanes = LANES * 32
    return pl.pallas_call(
        functools.partial(_accum_kernel, n),
        grid=(r // BM_ACCUM,),
        in_specs=[pl.BlockSpec((n, BM_ACCUM, LANES), lambda i: (0, i, 0)),
                  pl.BlockSpec((n, LANES), lambda i: (0, 0))],
        out_specs=pl.BlockSpec((BM_ACCUM, out_lanes), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((r, out_lanes), jnp.float32),
        name="binary_accum",
        interpret=interpret,
    )(words, centers)


@functools.partial(jax.jit, static_argnames=("width", "interpret"))
def unpack_bits_2d(words, width: int, *, interpret: bool = False):
    """words: (R, 128) uint32, R % BM_UNPACK == 0 -> (R, 128*32/w) symbols."""
    r, c = words.shape
    assert c == LANES and r % BM_UNPACK == 0, (r, c)
    out_lanes = LANES * (32 // width)
    return pl.pallas_call(
        functools.partial(_unpack_kernel, width),
        grid=(r // BM_UNPACK,),
        in_specs=[pl.BlockSpec((BM_UNPACK, LANES), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((BM_UNPACK, out_lanes), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((r, out_lanes), jnp.uint32),
        name="bitplane_unpack",
        interpret=interpret,
    )(words)
