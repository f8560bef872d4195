"""Fused Pallas TPU kernels for the §4.4 seed-trick Bernoulli wire.

Encode (``encode_pallas``) fuses sample → select → rank-compact in ONE pass
over ``flat``: the Threefry support draw runs in-register
(repro.kernels.threefry.ref inlined into the kernel body), the support rank
comes from a running carry plus an in-step flat-order cumsum, and kept
values land directly in the (cap,) wire buffer — no d-wide uniform tensor,
no d-wide cumsum, no d-wide ``.at[].set`` scatter in HBM.

Decode (``decode_sum_pallas``) fuses regenerate → unpack → accumulate for
all n peer buffers: grid (n, nblocks) with peers on the slow axis, so each
peer's (cap,) buffer is fetched once and folded straight into the shared
(d,) f32 accumulator — per-peer dense reconstructions are never
materialized.  ``decode_sum_shard_pallas`` is the same walk over one
coordinate shard.

Hardware mapping notes:

* a grid step covers ``sub`` sub-blocks of (BM_ROWS, 128) = 1,024
  coordinates; ``sub`` is a power of two up to MAX_SUB, fixed by the
  stream length (:func:`_sub_blocks`), so a 100M-coordinate bucket walks
  32K coordinates a step and a test-sized stream one or a few sub-blocks.
  Grids are sequential on TPU, which is what makes the SMEM rank carry
  between steps and the read-modify-write buffers correct;
* each step first draws the Threefry support and the flat-order support
  count of ALL its coordinates at once (:func:`_step_ranks`), so the draw
  runs over ``sub`` independent vregs instead of one sub-block's dependent
  chain.  The cumsum is two triangular matmuls (lane-inclusive within rows
  + row-exclusive prefix) in bf16 with f32 accumulation: operands are 0/1
  and row totals ≤ 128, all exact in bf16, and sums stay ≤ MAX_SUB·BM, far
  below 2²⁴, so one MXU pass gives exact integers.  The counts before each
  coordinate go to VMEM scratch; a ``fori_loop`` over the sub-blocks then
  reads its sub-block's counts, whose first entry is that sub-block's rank
  carry, so no sub-block waits on the one before it;
* rank compaction works per sub-block and moves each kept value's 32 bits
  as four byte planes.  Kept ranks of one sub-block provably span < BM +
  128 slots starting at its carry (capped at ``cap``) rounded down to a
  lane multiple, so a window of WIN_ROWS = 9 buffer rows holds them.  Per
  source row r, the planes are stacked along the streamed (M) axis, each
  plane PLANE_ROWS = 16 rows tall (the window's 9 rows padded to one bf16
  sublane tile), and one (4·16, 128) @ (128, 128) bf16 matmul against the
  row's lane one-hot moves all four at once: one weight-tile load per
  source row, one MXU pass.  Encode ORs the reassembled uint32 bits into
  its window of the (uint32) wire buffer; decode gathers window bytes with
  the transposed one-hot and picks each lane's window row with a masked
  sublane sum.

Bit-identity: all three kernels reproduce the jnp oracles in
repro.kernels.bernoulli_wire.ref exactly.  The Threefry stream is
bit-exact and supports/ranks are integer-exact.  The compaction is exact
for every f32 bit pattern: a byte is an integer in [0, 255], exact in bf16;
each output slot of a one-hot matmul receives exactly one nonzero product
(byte × 1) and otherwise +0.0 terms, so its f32 sum is that byte; the
reassembly is shifts and ORs on uint32, and the encode window takes the new
bits by OR into slots that hold zero bits.  No value is ever multiplied or
added, so ``-0.0``, subnormals, ±inf and NaN payloads pass unchanged.  The
decode accumulate writes peer 0's reconstruction and adds peers 1..n−1 in
order — ref's ``_peer_sum`` / ``decode_sum_sequential`` order.  ONE
carve-out: the Eq. (1) affine rescale ``x/p − (1−p)/p·μ``.  XLA reserves
the right to contract that multiply-subtract into an FMA depending on
surrounding fusion, so for general p the kernel and oracle may disagree by
1 ulp on *values* (never on which slots are filled).  When 1/p is a power
of two — every shipped preset uses fraction 1/16 — ``x·(1/p)`` is exact
and the contraction freedom collapses: kernel and oracle are equal
bit-for-bit.  Pinned by tests/test_bernoulli_wire_kernels.py in interpret
mode.
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
BM_ROWS = 8                  # sublane rows per sub-block
BM = BM_ROWS * LANES         # 1024 coordinates per sub-block
WIN_ROWS = 9                 # rank window: BM slots + 128 for alignment slack
WIN = WIN_ROWS * LANES
PLANE_ROWS = 16              # one byte plane of the window, bf16-tile tall
N_PLANES = 4                 # bytes of an f32
MAX_SUB = 32                 # sub-blocks per grid step, at most


def _sub_blocks(n: int) -> int:
    """Sub-blocks per grid step for an n-coordinate stream: the largest
    power of two ≤ MAX_SUB that the stream's sub-blocks fill."""
    nsub = -(-n // BM)
    sub = 1
    while sub * 2 <= min(MAX_SUB, nsub):
        sub *= 2
    return sub


def num_coord_rows(d: int) -> int:
    """Sublane rows needed to hold d coordinates, padded to full grid
    steps."""
    step = _sub_blocks(d) * BM
    return -(-d // step) * step // LANES


def num_buffer_rows(cap: int) -> int:
    """Sublane rows of a wire buffer padded so any RMW window fits."""
    return -(-cap // LANES) + PLANE_ROWS


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
    """Inclusive cumsum of an (R, LANES) bool block in flat row-major
    order, as int32.  Two triangular bf16 matmuls with f32 accumulation:
    operands are 0/1 and row totals ≤ 128 (exact in bf16), sums ≤ R·128."""
    rows = sent.shape[0]
    s = sent.astype(jnp.bfloat16)
    lane_le = (jax.lax.broadcasted_iota(jnp.int32, (LANES, LANES), 0)
               <= jax.lax.broadcasted_iota(jnp.int32, (LANES, LANES), 1)
               ).astype(jnp.bfloat16)
    within = jnp.dot(s, lane_le, preferred_element_type=jnp.float32)
    row_lt = (jax.lax.broadcasted_iota(jnp.int32, (rows, rows), 1)
              < jax.lax.broadcasted_iota(jnp.int32, (rows, rows), 0)
              ).astype(jnp.bfloat16)
    prefix = jnp.dot(row_lt, within[:, LANES - 1:LANES].astype(jnp.bfloat16),
                     preferred_element_type=jnp.float32)
    return (within + prefix).astype(jnp.int32)


def _plane_layout():
    """(window row, byte shift) of each row of the (N_PLANES·PLANE_ROWS,
    LANES) plane stack: row m is window row m % PLANE_ROWS of byte plane
    m // PLANE_ROWS."""
    m = jax.lax.broadcasted_iota(jnp.int32, (N_PLANES * PLANE_ROWS, LANES), 0)
    return m % PLANE_ROWS, (8 * (m // PLANE_ROWS)).astype(jnp.uint32)


def _bf16(byte):
    """uint32 bytes (0..255) as bf16, exactly."""
    return byte.astype(jnp.int32).astype(jnp.float32).astype(jnp.bfloat16)


def _lane_onehot(wlane_r):
    """(LANES, LANES) bf16 [k == wlane_r[l]] for one (1, LANES) source row
    of window lanes: slot lane k on the sublane axis, source lane l on the
    lane axis (built by broadcast, no lane<->sublane reshape)."""
    k = jax.lax.broadcasted_iota(jnp.int32, (LANES, LANES), 0)
    return (k == wlane_r).astype(jnp.bfloat16)


def _join_planes(stack):
    """(N_PLANES·PLANE_ROWS, LANES) f32 bytes → (PLANE_ROWS, LANES) uint32."""
    out = jnp.zeros((PLANE_ROWS, LANES), jnp.uint32)
    for b in range(N_PLANES):
        plane = stack[b * PLANE_ROWS:(b + 1) * PLANE_ROWS]
        out = out | (plane.astype(jnp.int32).astype(jnp.uint32) << (8 * b))
    return out


def _scatter_window(bits, local, mask):
    """(PLANE_ROWS, LANES) uint32 window with bits[r, l] at slot
    local[r, l] where mask, zero bits elsewhere.

    Per source row: the row's four byte planes, each masked to the window
    row its lanes land in, stacked along M, times the transposed lane
    one-hot on the MXU.  Every slot gets at most one nonzero byte product,
    so the f32 sums are exact bytes."""
    wrow = local // LANES
    wlane = local % LANES
    m_row, m_shift = _plane_layout()
    acc = jnp.zeros((N_PLANES * PLANE_ROWS, LANES), jnp.float32)
    for r in range(BM_ROWS):
        hit = (m_row == wrow[r:r + 1, :]) & mask[r:r + 1, :]
        byte = (bits[r:r + 1, :] >> m_shift) & 0xFF
        a = _bf16(jnp.where(hit, byte, 0))
        acc = acc + jax.lax.dot_general(
            a, _lane_onehot(wlane[r:r + 1, :]), (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
    return _join_planes(acc)


def _gather_window(window, local, mask):
    """(BM_ROWS, LANES) uint32 with the window's bits at slot local[r, l]
    where mask, else 0 — the inverse of :func:`_scatter_window`: one MXU
    lane-gather of the window's stacked byte planes per source row, then a
    masked sublane sum per plane that has a single nonzero term."""
    wrow = local // LANES
    wlane = local % LANES
    m_row, m_shift = _plane_layout()
    wbits = jnp.concatenate([window] * N_PLANES, axis=0)
    stack = _bf16((wbits >> m_shift) & 0xFF)
    row_of = jax.lax.broadcasted_iota(jnp.int32, (BM_ROWS, LANES), 0)
    out = jnp.zeros((BM_ROWS, LANES), jnp.uint32)
    for r in range(BM_ROWS):
        picked = jnp.dot(stack, _lane_onehot(wlane[r:r + 1, :]),
                         preferred_element_type=jnp.float32)
        hit = (m_row == wrow[r:r + 1, :]) & mask[r:r + 1, :]
        picked = jnp.where(hit, picked, 0.0)
        v = jnp.zeros((1, LANES), jnp.uint32)
        for b in range(N_PLANES):
            byte = jnp.sum(picked[b * PLANE_ROWS:(b + 1) * PLANE_ROWS],
                           axis=0, keepdims=True)
            v = v | (byte.astype(jnp.int32).astype(jnp.uint32) << (8 * b))
        out = jnp.where(row_of == r, v, out)
    return out


def _accumulate(o_ref, rows, recon, peer):
    """Peer 0 writes its reconstruction, later peers add theirs: the
    peer-major order of ref._peer_sum, with no 0.0 + x first add."""
    @pl.when(peer == 0)
    def _first():
        o_ref[rows, :] = recon

    @pl.when(peer > 0)
    def _rest():
        o_ref[rows, :] += recon


def _sub_rows(g):
    return pl.ds(pl.multiple_of(g * BM_ROWS, BM_ROWS), BM_ROWS)


def _step_ranks(sent, carry, excl_ref, sent_ref):
    """Store, for every coordinate of the step, the support count before
    it (global: ``carry`` plus the in-step count) and its sent bit; return
    the carry after the step."""
    incl = _flat_cumsum(sent)
    excl_ref[...] = carry + incl - sent.astype(jnp.int32)
    sent_ref[...] = sent.astype(jnp.int32)
    return carry + incl[incl.shape[0] - 1, LANES - 1]


def _sub_ranks(excl_ref, sent_ref, g, cap: int):
    """Keep mask, window row start and in-window slot index of sub-block
    ``g``: a sent coordinate's rank is the count before it; the count
    before the sub-block's first coordinate is the sub-block's carry."""
    rows = _sub_rows(g)
    excl = excl_ref[rows, :]
    keep = (sent_ref[rows, :] != 0) & (excl < cap)
    row_start = jnp.minimum(excl[0, 0], cap) // LANES
    local = jnp.clip(excl - row_start * LANES, 0, WIN - 1)
    return keep, row_start, local


def _scratch(sub: int):
    """The grid-step carry, and the step's counts and sent bits."""
    return [pltpu.SMEM((1,), jnp.int32),
            pltpu.VMEM((sub * BM_ROWS, LANES), jnp.int32),
            pltpu.VMEM((sub * BM_ROWS, LANES), jnp.int32)]


def _encode_kernel(key_ref, par_ref, x_ref, o_ref, carry_ref, excl_ref,
                   sent_ref, *, d: int, cap: int, scaled: bool, sub: int):
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        carry_ref[0] = 0
        o_ref[...] = jnp.zeros_like(o_ref)

    idx, mask = _block_coords(i, d, rows=sub * BM_ROWS)
    sent = mask & (_uniform_block(key_ref[0], key_ref[1], idx, d)
                   < par_ref[0])
    carry_ref[0] = _step_ranks(sent, carry_ref[0], excl_ref, sent_ref)

    def body(g, carry):
        x = x_ref[_sub_rows(g), :]
        # Bit-matches ref.encode's ``x / p − (1−p)/p · μ`` with p a Python
        # constant: XLA folds the division into multiply-by-f32-reciprocal
        # and binds the weak Python coefficient at f32, so the kernel
        # multiplies by the same host-rounded scalars (par_ref[3] = 1/p,
        # par_ref[2] = (1−p)/p).
        vals = x * par_ref[3] - par_ref[2] * par_ref[1] if scaled else x
        keep, row_start, local = _sub_ranks(excl_ref, sent_ref, g, cap)
        bits = jax.lax.bitcast_convert_type(vals, jnp.uint32)
        win = pl.ds(row_start, PLANE_ROWS)
        o_ref[win, :] = o_ref[win, :] | _scatter_window(bits, local, keep)
        return carry

    jax.lax.fori_loop(0, sub, body, 0)


@functools.partial(jax.jit,
                   static_argnames=("p", "cap", "scaled", "interpret"))
def encode_pallas(flat, key, mu, *, p: float, cap: int,
                  scaled: bool = True, interpret: bool = False):
    """flat: (d,) f32; key: (2,) uint32 (rank-folded); mu: f32 scalar.
    Returns the (cap,) f32 wire value buffer of ref.encode."""
    d = flat.shape[0]
    sub = _sub_blocks(d)
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
        grid=(rows_d // (sub * BM_ROWS),),
        in_specs=[pl.BlockSpec((sub * BM_ROWS, LANES), lambda i, *_: (i, 0))],
        out_specs=pl.BlockSpec((rows_cap, LANES), lambda i, *_: (0, 0)),
        scratch_shapes=_scratch(sub),
    )
    out = pl.pallas_call(
        functools.partial(_encode_kernel, d=d, cap=cap, scaled=scaled,
                          sub=sub),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((rows_cap, LANES), jnp.uint32),
        name="bernoulli_wire_encode",
        interpret=interpret,
    )(key, params, x2)
    return jax.lax.bitcast_convert_type(out.reshape(-1)[:cap], jnp.float32)


def _decode_sub(g, buf_ref, excl_ref, sent_ref, inblock, mu, cap: int):
    """Sub-block ``g``'s reconstruction: its kept values gathered from the
    peer's buffer, μ on the other lanes of ``inblock``, 0 off it."""
    valid, row_start, local = _sub_ranks(excl_ref, sent_ref, g, cap)
    window = jax.lax.bitcast_convert_type(
        buf_ref[0, pl.ds(row_start, PLANE_ROWS), :], jnp.uint32)
    vals = jax.lax.bitcast_convert_type(
        _gather_window(window, local, valid), jnp.float32)
    return jnp.where(inblock, jnp.where(valid, vals, mu), 0.0)


def _decode_kernel(keys_ref, mus_ref, par_ref, buf_ref, o_ref, carry_ref,
                   excl_ref, sent_ref, *, d: int, cap: int, sub: int):
    i = pl.program_id(0)   # peer (slow axis: buffer stays resident)
    j = pl.program_id(1)   # coordinate block

    @pl.when(j == 0)
    def _reset():
        carry_ref[0] = 0

    idx, mask = _block_coords(j, d, rows=sub * BM_ROWS)
    sent = mask & (_uniform_block(keys_ref[i, 0], keys_ref[i, 1], idx, d)
                   < par_ref[0])
    carry_ref[0] = _step_ranks(sent, carry_ref[0], excl_ref, sent_ref)

    def body(g, carry):
        inblock = _block_coords(j * sub + g, d)[1]
        recon = _decode_sub(g, buf_ref, excl_ref, sent_ref, inblock,
                            mus_ref[i], cap)
        _accumulate(o_ref, _sub_rows(g), recon, i)
        return carry

    jax.lax.fori_loop(0, sub, body, 0)


def _wire_buffers(bufs, cap: int):
    """(n, cap) f32 → (n, num_buffer_rows(cap), LANES) f32, zero padded."""
    n = bufs.shape[0]
    rows_cap = num_buffer_rows(cap)
    return jnp.pad(bufs.astype(jnp.float32),
                   ((0, 0), (0, rows_cap * LANES - cap))
                   ).reshape(n, rows_cap, LANES)


@functools.partial(jax.jit, static_argnames=("p", "cap", "d", "interpret"))
def decode_sum_pallas(bufs, mus, keys, *, p: float, cap: int, d: int,
                      interpret: bool = False):
    """bufs: (n, cap) f32; mus: (n,) f32; keys: (n, 2) uint32.
    Returns Σ_i reconstruction_i as (d,) f32 — the peer-major accumulation
    of ref.decode_sum_sequential; caller divides by n."""
    n = bufs.shape[0]
    sub = _sub_blocks(d)
    rows_d = num_coord_rows(d)
    bufs3 = _wire_buffers(bufs, cap)
    keys = jnp.asarray(keys).reshape(n, 2).astype(jnp.uint32)
    mus = jnp.asarray(mus, jnp.float32)
    params = jnp.stack([jnp.float32(p)])
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(n, rows_d // (sub * BM_ROWS)),
        in_specs=[pl.BlockSpec((1,) + bufs3.shape[1:],
                               lambda i, j, *_: (i, 0, 0))],
        out_specs=pl.BlockSpec((sub * BM_ROWS, LANES),
                               lambda i, j, *_: (j, 0)),
        scratch_shapes=_scratch(sub),
    )
    out = pl.pallas_call(
        functools.partial(_decode_kernel, d=d, cap=cap, sub=sub),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((rows_d, LANES), jnp.float32),
        name="bernoulli_wire_decode_sum",
        interpret=interpret,
    )(keys, mus, params, bufs3)
    return out.reshape(-1)[:d]


def _decode_shard_kernel(keys_ref, mus_ref, par_ref, prior_ref, off_ref,
                         buf_ref, o_ref, carry_ref, excl_ref, sent_ref, *,
                         d: int, cap: int, ds: int, sub: int):
    """Shard view of :func:`_decode_kernel`: decode coordinates
    [off, off+ds) of every peer's (d,) stream.

    Identical rank bookkeeping — the carry just starts at the peer's
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

    lidx, inblock = _block_coords(j, ds, rows=sub * BM_ROWS)
    gidx = off_ref[0] + lidx
    real = inblock & (gidx < d)
    u = _uniform_block(keys_ref[i, 0], keys_ref[i, 1],
                       jnp.where(real, gidx, 0), d)
    carry_ref[0] = _step_ranks(real & (u < par_ref[0]), carry_ref[0],
                               excl_ref, sent_ref)

    def body(g, carry):
        inblock = _block_coords(j * sub + g, ds)[1]
        recon = _decode_sub(g, buf_ref, excl_ref, sent_ref, inblock,
                            mus_ref[i], cap)
        _accumulate(o_ref, _sub_rows(g), recon, i)
        return carry

    jax.lax.fori_loop(0, sub, body, 0)


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
    sub = _sub_blocks(ds)
    rows_ds = num_coord_rows(ds)
    bufs3 = _wire_buffers(bufs, cap)
    keys = jnp.asarray(keys).reshape(n, 2).astype(jnp.uint32)
    mus = jnp.asarray(mus, jnp.float32)
    params = jnp.stack([jnp.float32(p)])
    prior = jnp.asarray(prior, jnp.int32).reshape(n)
    off = jnp.asarray(start, jnp.int32).reshape(1)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=5,
        grid=(n, rows_ds // (sub * BM_ROWS)),
        in_specs=[pl.BlockSpec((1,) + bufs3.shape[1:],
                               lambda i, j, *_: (i, 0, 0))],
        out_specs=pl.BlockSpec((sub * BM_ROWS, LANES),
                               lambda i, j, *_: (j, 0)),
        scratch_shapes=_scratch(sub),
    )
    out = pl.pallas_call(
        functools.partial(_decode_shard_kernel, d=d, cap=cap, ds=ds,
                          sub=sub),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((rows_ds, LANES), jnp.float32),
        name="bernoulli_wire_decode_sum_shard",
        interpret=interpret,
    )(keys, mus, params, prior, off, bufs3)
    return out.reshape(-1)[:ds]
