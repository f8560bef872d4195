"""Fused Pallas TPU kernels for the §4.4 seed-trick Bernoulli wire.

Encode (``encode_pallas``) fuses sample → select → rank-compact in ONE pass
over ``flat``: the Threefry support draw runs in-register
(repro.kernels.threefry.ref inlined into the kernel body), the support rank
comes from a running SMEM carry plus an in-block flat-order cumsum, and
kept values land directly in the (cap,) wire buffer — no d-wide uniform
tensor, no d-wide cumsum, no d-wide ``.at[].set`` scatter in HBM.

Decode (``decode_sum_pallas``) fuses regenerate → unpack → accumulate for
all n peer buffers: grid (n, nblocks) with peers on the slow axis, so each
peer's (cap,) buffer is fetched once and folded straight into the shared
(d,) f32 accumulator — per-peer dense reconstructions are never
materialized (the old path built n full (d,) vectors in HBM).

Hardware mapping notes (see /opt/skills/guides/pallas_guide.md):

* grids are sequential on TPU, which is what makes the SMEM rank carry and
  the read-modify-write accumulator correct;
* flat-order cumsum inside a (BM_ROWS, 128) block is two triangular-matrix
  matmuls (lane-inclusive within rows + row-exclusive prefix) — MXU work
  instead of a serial scan, exact in f32 below 2²⁴;
* rank-compaction is a one-hot matmul into a 128-aligned window of the
  output: kept ranks of one block provably span < BM + 128 slots starting
  at ``min(carry, cap)`` rounded down to a lane multiple, so a
  (WIN_ROWS, 128) dynamic-sliced RMW covers them.  One-hot matmuls touch
  each slot through exactly one nonzero product, so the result is
  bit-identical to the gather/scatter formulation in ref.py.

Bit-identity: both kernels reproduce the jnp oracles in
repro.kernels.bernoulli_wire.ref exactly — the Threefry stream is
bit-exact, supports/ranks are integer-exact, and one-hot matmuls and the
peer-major accumulate match the oracle op-for-op — with ONE carve-out: the
Eq. (1) affine rescale ``x/p − (1−p)/p·μ``.  XLA reserves the right to
contract that multiply-subtract into an FMA depending on surrounding
fusion, so for general p the kernel and oracle may disagree by 1 ulp on
*values* (never on which slots are filled).  When 1/p is a power of two —
every shipped preset uses fraction 1/16 — ``x·(1/p)`` is exact and the
contraction freedom collapses: kernel and oracle are equal bit-for-bit.
Pinned by tests/test_bernoulli_wire_kernels.py in interpret mode.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.threefry import ref as tref

LANES = 128
BM_ROWS = 8                  # sublane rows per grid step
BM = BM_ROWS * LANES         # 1024 coordinates per step
WIN_ROWS = BM_ROWS + 1       # rank window: BM slots + 128 for alignment slack
WIN = WIN_ROWS * LANES

_HIGHEST = jax.lax.Precision.HIGHEST


def num_coord_rows(d: int) -> int:
    """Sublane rows needed to hold d coordinates, padded to full blocks."""
    return -(-d // BM) * BM_ROWS


def num_buffer_rows(cap: int) -> int:
    """Sublane rows of a wire buffer padded so any RMW window fits."""
    return -(-cap // LANES) + WIN_ROWS


def _block_coords(step, d: int, rows: int = BM_ROWS):
    """Global flat coordinate of each (row, lane) slot + validity mask.

    ``rows`` lets other wire kernels (repro.kernels.rotated_encode) reuse
    the same row-major coordinate layout at their own block height.
    """
    r = jax.lax.broadcasted_iota(jnp.int32, (rows, LANES), 0)
    c = jax.lax.broadcasted_iota(jnp.int32, (rows, LANES), 1)
    idx = (step * rows + r) * LANES + c
    return idx, idx < d


def _uniform_block(k0, k1, idx, d: int):
    """Threefry U[0,1) draw for scattered coordinates ``idx`` of a (d,)
    stream — bit-exact lanes of ``jax.random.uniform(key, (d,))``."""
    pair, c1, lo = tref.counter_words(idx.astype(jnp.uint32), d)
    o0, o1 = tref.threefry2x32(k0, k1, pair, c1)
    return tref.bits_to_uniform(jnp.where(lo, o0, o1))


def _flat_cumsum(sent):
    """Inclusive cumsum of a (BM_ROWS, LANES) bool block in flat row-major
    order, as int32.  Two triangular matmuls; block sums ≤ BM ⇒ exact."""
    s = sent.astype(jnp.float32)
    lane_le = (jax.lax.broadcasted_iota(jnp.int32, (LANES, LANES), 0)
               <= jax.lax.broadcasted_iota(jnp.int32, (LANES, LANES), 1)
               ).astype(jnp.float32)
    within = jax.lax.dot(s, lane_le, precision=_HIGHEST)
    row_lt = (jax.lax.broadcasted_iota(jnp.int32, (BM_ROWS, BM_ROWS), 1)
              < jax.lax.broadcasted_iota(jnp.int32, (BM_ROWS, BM_ROWS), 0)
              ).astype(jnp.float32)
    prefix = jax.lax.dot(row_lt, within[:, LANES - 1:LANES],
                         precision=_HIGHEST)
    return (within + prefix).astype(jnp.int32)


def _rank_window(carry, incl, sent, cap: int):
    """Shared rank bookkeeping: global ranks, keep mask, window row start
    and in-window slot index for this block's coordinates."""
    rank = carry + incl - 1
    keep = sent & (rank < cap)
    row_start = jnp.minimum(carry, cap) // LANES
    local = jnp.clip(rank - row_start * LANES, 0, WIN - 1)
    return keep, row_start, local


def _row_selectors(local, mask):
    """Split each kept coordinate's window slot into (window row, lane).

    For source row r the kernels need ``sel_row[r]`` — (WIN_ROWS, LANES)
    masks [slot row == wr] — and ``sel_lane[r]`` — (LANES, LANES) masks
    [slot lane == wl] laid out with the source lane on the minor axis.
    Both are built by broadcasting one (1, LANES) source row against an
    iota, so no lane<->sublane reshape is needed.
    """
    wrow = local // LANES
    wlane = local % LANES
    row_iota = jax.lax.broadcasted_iota(jnp.int32, (WIN_ROWS, LANES), 0)
    lane_iota = jax.lax.broadcasted_iota(jnp.int32, (LANES, LANES), 0)
    sel_row, sel_lane = [], []
    for r in range(BM_ROWS):
        keep_r = mask[r:r + 1, :]
        sel_row.append((row_iota == wrow[r:r + 1, :]) & keep_r)
        sel_lane.append((lane_iota == wlane[r:r + 1, :]).astype(jnp.float32))
    return sel_row, sel_lane


def _scatter_window(vals, local, mask):
    """(WIN_ROWS, LANES) window with vals[r, l] at slot local[r, l] where
    mask.  Per source row: a (WIN_ROWS, LANES) row-select times the
    transposed (LANES, LANES) lane one-hot on the MXU.  Every slot gets
    at most one nonzero product, so the f32 result is exact."""
    sel_row, sel_lane = _row_selectors(local, mask)
    out = jnp.zeros((WIN_ROWS, LANES), jnp.float32)
    for r in range(BM_ROWS):
        a = jnp.where(sel_row[r], vals[r:r + 1, :], 0.0)
        out = out + jax.lax.dot_general(
            a, sel_lane[r], (((1,), (1,)), ((), ())), precision=_HIGHEST)
    return out


def _gather_window(window, local, mask):
    """(BM_ROWS, LANES) with window[slot local[r, l]] where mask, else 0 —
    the inverse of :func:`_scatter_window`: one MXU lane-gather per source
    row, then a masked sublane sum that has a single nonzero term."""
    sel_row, sel_lane = _row_selectors(local, mask)
    row_of = jax.lax.broadcasted_iota(jnp.int32, (BM_ROWS, LANES), 0)
    out = jnp.zeros((BM_ROWS, LANES), jnp.float32)
    for r in range(BM_ROWS):
        picked = jax.lax.dot(window, sel_lane[r], precision=_HIGHEST)
        v = jnp.sum(jnp.where(sel_row[r], picked, 0.0), axis=0,
                    keepdims=True)
        out = jnp.where(row_of == r, v, out)
    return out


def _encode_kernel(key_ref, par_ref, x_ref, o_ref, carry_ref, *,
                   d: int, cap: int, scaled: bool):
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        carry_ref[0] = 0
        o_ref[...] = jnp.zeros_like(o_ref)

    idx, mask = _block_coords(i, d)
    p = par_ref[0]
    sent = mask & (_uniform_block(key_ref[0], key_ref[1], idx, d) < p)

    x = x_ref[...]
    # Bit-matches ref.encode's ``x / p − (1−p)/p · μ`` with p a Python
    # constant: XLA folds the division into multiply-by-f32-reciprocal and
    # binds the weak Python coefficient at f32, so the kernel multiplies by
    # the same host-rounded scalars (par_ref[3] = 1/p, par_ref[2] = (1−p)/p).
    vals = x * par_ref[3] - par_ref[2] * par_ref[1] if scaled else x

    carry = carry_ref[0]
    incl = _flat_cumsum(sent)
    keep, row_start, local = _rank_window(carry, incl, sent, cap)

    contrib = _scatter_window(vals, local, keep)
    win = o_ref[pl.ds(row_start, WIN_ROWS), :]
    o_ref[pl.ds(row_start, WIN_ROWS), :] = win + contrib
    carry_ref[0] = carry + incl[BM_ROWS - 1, LANES - 1]


@functools.partial(jax.jit,
                   static_argnames=("p", "cap", "scaled", "interpret"))
def encode_pallas(flat, key, mu, *, p: float, cap: int,
                  scaled: bool = True, interpret: bool = False):
    """flat: (d,) f32; key: (2,) uint32 (rank-folded); mu: f32 scalar.
    Returns the (cap,) f32 wire value buffer of ref.encode."""
    d = flat.shape[0]
    rows_d = num_coord_rows(d)
    rows_cap = num_buffer_rows(cap)
    x2 = jnp.pad(flat.astype(jnp.float32),
                 (0, rows_d * LANES - d)).reshape(rows_d, LANES)
    key = jnp.asarray(key).reshape(2).astype(jnp.uint32)
    params = jnp.stack([
        jnp.float32(p),
        jnp.asarray(mu, jnp.float32),
        jnp.float32((1.0 - p) / p),
        jnp.asarray(np.float32(1.0) / np.float32(p)),
    ])
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(rows_d // BM_ROWS,),
        in_specs=[pl.BlockSpec((BM_ROWS, LANES), lambda i, *_: (i, 0))],
        out_specs=pl.BlockSpec((rows_cap, LANES), lambda i, *_: (0, 0)),
        scratch_shapes=[pltpu.SMEM((1,), jnp.int32)],
    )
    out = pl.pallas_call(
        functools.partial(_encode_kernel, d=d, cap=cap, scaled=scaled),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((rows_cap, LANES), jnp.float32),
        name="bernoulli_wire_encode",
        interpret=interpret,
    )(key, params, x2)
    return out.reshape(-1)[:cap]


def _decode_kernel(keys_ref, mus_ref, par_ref, buf_ref, o_ref, carry_ref, *,
                   d: int, cap: int):
    i = pl.program_id(0)   # peer (slow axis: buffer stays resident)
    j = pl.program_id(1)   # coordinate block

    @pl.when(j == 0)
    def _reset():
        carry_ref[0] = 0

    idx, mask = _block_coords(j, d)
    p = par_ref[0]
    sent = mask & (_uniform_block(keys_ref[i, 0], keys_ref[i, 1], idx, d)
                   < p)

    carry = carry_ref[0]
    incl = _flat_cumsum(sent)
    valid, row_start, local = _rank_window(carry, incl, sent, cap)

    window = buf_ref[0, pl.ds(row_start, WIN_ROWS), :]
    vals = _gather_window(window, local, valid)
    mu = mus_ref[i]
    recon = jnp.where(mask, jnp.where(valid, vals, mu), 0.0)

    @pl.when(i == 0)
    def _zero():
        o_ref[...] = jnp.zeros_like(o_ref)

    o_ref[...] += recon
    carry_ref[0] = carry + incl[BM_ROWS - 1, LANES - 1]


@functools.partial(jax.jit, static_argnames=("p", "cap", "d", "interpret"))
def decode_sum_pallas(bufs, mus, keys, *, p: float, cap: int, d: int,
                      interpret: bool = False):
    """bufs: (n, cap) f32; mus: (n,) f32; keys: (n, 2) uint32.
    Returns Σ_i reconstruction_i as (d,) f32 — the peer-major accumulation
    of ref.decode_sum_sequential; caller divides by n."""
    n = bufs.shape[0]
    rows_d = num_coord_rows(d)
    rows_cap = num_buffer_rows(cap)
    bufs3 = jnp.pad(bufs.astype(jnp.float32),
                    ((0, 0), (0, rows_cap * LANES - cap))
                    ).reshape(n, rows_cap, LANES)
    keys = jnp.asarray(keys).reshape(n, 2).astype(jnp.uint32)
    mus = jnp.asarray(mus, jnp.float32)
    params = jnp.stack([jnp.float32(p)])
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(n, rows_d // BM_ROWS),
        in_specs=[pl.BlockSpec((1, rows_cap, LANES),
                               lambda i, j, *_: (i, 0, 0))],
        out_specs=pl.BlockSpec((BM_ROWS, LANES), lambda i, j, *_: (j, 0)),
        scratch_shapes=[pltpu.SMEM((1,), jnp.int32)],
    )
    out = pl.pallas_call(
        functools.partial(_decode_kernel, d=d, cap=cap),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((rows_d, LANES), jnp.float32),
        name="bernoulli_wire_decode_sum",
        interpret=interpret,
    )(keys, mus, params, bufs3)
    return out.reshape(-1)[:d]


def _decode_shard_kernel(keys_ref, mus_ref, par_ref, prior_ref, off_ref,
                         buf_ref, o_ref, carry_ref, *,
                         d: int, cap: int, ds: int):
    """Shard view of :func:`_decode_kernel`: decode coordinates
    [off, off+ds) of every peer's (d,) stream.

    Identical rank bookkeeping — the SMEM carry just starts at the peer's
    ``prior`` count (supports strictly before the shard, all_gathered by
    the caller) instead of 0, and the Threefry lanes draw at the global
    coordinate ``off + local``.  Shard-window lanes past d decode to μ
    (matching ref.decode_sum_shard; the caller truncates), block-padding
    lanes past ds contribute 0.
    """
    i = pl.program_id(0)   # peer (slow axis: buffer stays resident)
    j = pl.program_id(1)   # coordinate block within the shard

    @pl.when(j == 0)
    def _reset():
        carry_ref[0] = prior_ref[i]

    lidx, inblock = _block_coords(j, ds)
    gidx = off_ref[0] + lidx
    real = inblock & (gidx < d)
    p = par_ref[0]
    u = _uniform_block(keys_ref[i, 0], keys_ref[i, 1],
                       jnp.where(real, gidx, 0), d)
    sent = real & (u < p)

    carry = carry_ref[0]
    incl = _flat_cumsum(sent)
    valid, row_start, local = _rank_window(carry, incl, sent, cap)

    window = buf_ref[0, pl.ds(row_start, WIN_ROWS), :]
    vals = _gather_window(window, local, valid)
    mu = mus_ref[i]
    recon = jnp.where(inblock, jnp.where(valid, vals, mu), 0.0)

    @pl.when(i == 0)
    def _zero():
        o_ref[...] = jnp.zeros_like(o_ref)

    o_ref[...] += recon
    carry_ref[0] = carry + incl[BM_ROWS - 1, LANES - 1]


@functools.partial(jax.jit,
                   static_argnames=("p", "cap", "d", "ds", "interpret"))
def decode_sum_shard_pallas(bufs, mus, keys, prior, start, *, p: float,
                            cap: int, d: int, ds: int,
                            interpret: bool = False):
    """bufs: (n, cap) f32; mus: (n,) f32; keys: (n, 2) uint32; prior: (n,)
    int32 support counts strictly before the shard; start: int32 global
    offset (may be traced — the shard index inside shard_map).

    Returns the [start, start+ds) slice of Σ_i reconstruction_i as (ds,)
    f32, regenerating the shard supports in-kernel (fused regenerate +
    select + accumulate) — bit-exact vs ref.support_shard +
    ref.decode_sum_shard.  Caller divides by n.
    """
    n = bufs.shape[0]
    rows_ds = num_coord_rows(ds)
    rows_cap = num_buffer_rows(cap)
    bufs3 = jnp.pad(bufs.astype(jnp.float32),
                    ((0, 0), (0, rows_cap * LANES - cap))
                    ).reshape(n, rows_cap, LANES)
    keys = jnp.asarray(keys).reshape(n, 2).astype(jnp.uint32)
    mus = jnp.asarray(mus, jnp.float32)
    params = jnp.stack([jnp.float32(p)])
    prior = jnp.asarray(prior, jnp.int32).reshape(n)
    off = jnp.asarray(start, jnp.int32).reshape(1)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=5,
        grid=(n, rows_ds // BM_ROWS),
        in_specs=[pl.BlockSpec((1, rows_cap, LANES),
                               lambda i, j, *_: (i, 0, 0))],
        out_specs=pl.BlockSpec((BM_ROWS, LANES), lambda i, j, *_: (j, 0)),
        scratch_shapes=[pltpu.SMEM((1,), jnp.int32)],
    )
    out = pl.pallas_call(
        functools.partial(_decode_shard_kernel, d=d, cap=cap, ds=ds),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((rows_ds, LANES), jnp.float32),
        name="bernoulli_wire_decode_sum_shard",
        interpret=interpret,
    )(keys, mus, params, prior, off, bufs3)
    return out.reshape(-1)[:ds]
