"""Packed bit-plane wire formats for binary / ternary quantization.

This module realizes the paper's extreme operating point — ~1–2 bits per
coordinate (§4.5 Eq. (11), §7.1 Eq. (21)) — as honest SPMD wire buffers
instead of the dense f32 simulation.  Every buffer is a single flat uint32
vector so one bucket still costs one collective launch
(:mod:`repro.core.collectives` all_gathers it as-is).

Wire format (all segments uint32 words, concatenated)
-----------------------------------------------------

``binary`` (Example 4 / Suresh et al. [10]; 1 bit/coordinate):

  ============  =======================  =====================================
  words         count                    content
  ============  =======================  =====================================
  plane         PW = ceil(d/32)          sign plane: bit j of word j//32 at
                                         offset j%32 is 1 iff Y(j) = X^max
  tail centers  CW = ceil(2*r/32)        (vmin, vmax) at wire precision r
  ============  =======================  =====================================

``ternary`` (Eq. (21) with p1 = p2 = (1 − p_pass)/2, c1 = X^min,
c2 = X^max; 2 bits/coordinate + p_pass full-precision values):

  ============  =======================  =====================================
  words         count                    content
  ============  =======================  =====================================
  plane         PW = ceil(2d/32)         2-bit branch index per coordinate:
                                         0 → c1 ("down"), 1 → c2 ("up"),
                                         2 → pass-through (3 unused)
  values        VW = ceil(cap*r/32)      capacity-padded pass-through values
                                         Y(j) in support-rank order
  tail centers  CW = ceil(2*r/32)        (c1, c2) at wire precision r
  ============  =======================  =====================================

Tail-slot centers: the per-node scalars ride the same uint32 buffer
(bitcast f32, or two bf16 packed per word at r = 16), mirroring how μ rides
the value buffer in the fixed-k / Bernoulli paths — no second launch.

Pass-through handling: the pass-through count |{j : sym_j = 2}| is
Binomial(d, p_pass), not SPMD-static, so like the Bernoulli §4.4 path the
value segment is capacity-padded (:func:`repro.core.comm_cost
.bernoulli_capacity` with p = p_pass).  Coordinates whose support rank
overflows ``cap`` are dropped by the encoder and replaced by (c1 + c2)/2 by
the decoder — a P ≈ 1e-9 (6σ) event; both sides agree on the rank order so
the substitution is symmetric.  Unlike §4.4 there is NO seed term: the
plane itself travels (binary/ternary branch choices are data-dependent and
cannot regenerate peer-side).

Sampling is bit-identical to :mod:`repro.core.encoders` (same key, same
``jax.random.uniform`` draws), so at f32 wire precision
pack → unpack reproduces ``encode_binary(key, x).y`` /
``encode_ternary(key, x, …).y`` exactly — the gather collectives built on
these buffers agree with ``dense_sim_mean`` to float tolerance (verified in
tests/distributed_checks/quantized_wire_check.py).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core import encoders
from repro.core import types as t
from repro.kernels.bernoulli_wire import ref as bw_ref
from repro.kernels.numerics import count_prefix
from repro.kernels.bitplane import ops as bp_ops

WORD = 32


def wire_bits(wire_dtype) -> int:
    """Bits per wire float (r): 32 for float32, 16 for bfloat16/float16."""
    r = int(jnp.dtype(wire_dtype).itemsize) * 8
    if r not in (16, 32):
        raise ValueError(f"unsupported wire dtype {wire_dtype!r} (r={r})")
    return r


def float_words(count: int, wire_dtype) -> int:
    """uint32 words carrying ``count`` floats at wire precision."""
    return -(-count * wire_bits(wire_dtype) // WORD)


def floats_to_words(v, wire_dtype):
    """(m,) f32 -> (float_words(m),) uint32 at wire precision.

    f32 wire: bitcast.  16-bit wire: round to the wire dtype and pack two
    halves per word, little-endian (element 2i in the low half).
    """
    v = v.reshape(-1).astype(jnp.float32)
    if wire_bits(wire_dtype) == 32:
        return jax.lax.bitcast_convert_type(v, jnp.uint32)
    h = jax.lax.bitcast_convert_type(
        v.astype(wire_dtype), jnp.uint16).astype(jnp.uint32)
    h = jnp.pad(h, (0, (-h.shape[0]) % 2))
    # strided halves, not a (m, 2) view: XLA:TPU compiles the relayout of
    # a 2-wide minor dimension slowly at wire-buffer sizes.
    return h[0::2] | (h[1::2] << jnp.uint32(16))


def words_to_floats(w, count: int, wire_dtype):
    """Inverse of :func:`floats_to_words`; returns (count,) f32."""
    w = w.reshape(-1)
    if wire_bits(wire_dtype) == 32:
        return jax.lax.bitcast_convert_type(w, jnp.float32)[:count]
    # interleave by strided writes (see floats_to_words)
    halves = (jnp.zeros((2 * w.shape[0],), jnp.uint16)
              .at[0::2].set((w & jnp.uint32(0xFFFF)).astype(jnp.uint16))
              .at[1::2].set((w >> jnp.uint32(16)).astype(jnp.uint16)))
    return jax.lax.bitcast_convert_type(
        halves[:count], jnp.dtype(wire_dtype)).astype(jnp.float32)


def rank_scatter(values, sent, cap: int):
    """Place ``values[j]`` of each sent coordinate at its support-rank slot.

    The capacity-padded value-segment layout shared by the Bernoulli §4.4
    buffer, the ternary pass-through segment and the error-feedback twins:
    ranks ≥ ``cap`` are dropped (the decoder regenerates the same ranks and
    drops them symmetrically).  Returns a (cap,) f32 buffer.

    Despite the name this is implemented as a rank-*select* gather
    (repro.kernels.bernoulli_wire.ref.rank_select): byte-identical slots to
    the historical d-wide ``.at[idx].set`` scatter, but ~10× faster on the
    CPU backend, where XLA lowers large scatters serially.
    """
    return bw_ref.rank_select(values.astype(jnp.float32), sent, cap)


def topcap_mask(scores, cap: int):
    """Boolean membership of the ``cap`` largest ``scores`` (ties → lowest
    index), without ``jax.lax.top_k``.

    ``scores`` must be non-negative f32 (|deviations|), so its uint32 bit
    pattern is order-isomorphic to its value: the cap-th largest score is
    found by a 32-step MSB-first bisection on the bit pattern — 32 fused
    compare+reduce passes instead of the O(d log d) sort XLA lowers
    ``top_k`` to on CPU (~5× faster at d = 2^20).  Ties at the threshold
    are resolved to the lowest indices, matching ``top_k``'s documented
    order, so the selected SET is identical for any input.
    """
    bits = scores.astype(jnp.float32).view(jnp.uint32)

    def body(k, thr):
        cand = thr | (jnp.uint32(1) << (31 - k))
        n_ge = jnp.sum((bits >= cand).astype(jnp.int32))
        return jnp.where(n_ge >= cap, cand, thr)

    # largest T with count(bits >= T) >= cap == the cap-th largest pattern
    thr = jax.lax.fori_loop(0, 32, body, jnp.uint32(0))
    need_ties = cap - jnp.sum((bits > thr).astype(jnp.int32))
    is_tie = bits == thr
    tie_rank = count_prefix(is_tie)
    return (bits > thr) | (is_tie & (tie_rank <= need_ties))


# --------------------------------------------------------------------------- #
# Binary: 1-bit sign plane + (vmin, vmax) tail.
# --------------------------------------------------------------------------- #

def binary_wire_words(d: int, wire_dtype) -> int:
    """Total uint32 words of one node's binary wire buffer."""
    return bp_ops.num_words(d, 1) + float_words(2, wire_dtype)


def binary_words(bits, c_lo, c_hi, wire_dtype):
    """Assemble one binary wire buffer: [packed 1-bit plane ‖ (c_lo, c_hi)].

    THE binary buffer layout — both the stochastic encoder
    (:func:`binary_pack`) and the error-feedback twin
    (repro.core.wire.ef) emit through here, so
    :func:`binary_unpack` decodes either.
    """
    plane = bp_ops.pack_bits(bits.astype(jnp.uint32), 1)
    tail = floats_to_words(jnp.stack([c_lo, c_hi]), wire_dtype)
    return jnp.concatenate([plane, tail])


def binary_pack(flat, key, wire_dtype):
    """Encode (d,) f32 -> (binary_wire_words(d),) uint32 wire buffer.

    Uses encoders.encode_binary for the stochastic rounding (same PRNG
    stream as the dense simulation).
    """
    enc = encoders.encode_binary(key, flat)
    return binary_words(enc.support, enc.extras["vmin"], enc.extras["vmax"],
                        wire_dtype)


def binary_unpack(buf, d: int, wire_dtype):
    """Reconstruct the dense Y_i (f32) from one node's wire buffer."""
    pw = bp_ops.num_words(d, 1)
    bits = bp_ops.unpack_bits(buf[:pw], 1, d)
    c = words_to_floats(buf[pw:], 2, wire_dtype)
    return jnp.where(bits > 0, c[1], c[0])


# --------------------------------------------------------------------------- #
# Ternary: 2-bit branch plane + capacity-padded values + (c1, c2) tail.
# --------------------------------------------------------------------------- #

def ternary_wire_words(d: int, cap: int, wire_dtype) -> int:
    """Total uint32 words of one node's ternary wire buffer."""
    return (bp_ops.num_words(d, 2) + float_words(cap, wire_dtype)
            + float_words(2, wire_dtype))


def ternary_words(sym, vbuf, c1, c2, wire_dtype):
    """Assemble one ternary wire buffer: [2-bit plane ‖ values ‖ (c1, c2)].

    THE ternary buffer layout — the Eq. (21) encoders
    (:func:`ternary_pack`, uniform or §6-optimal split) and the
    error-feedback twin (repro.core.wire.ef) all emit through here, so
    :func:`ternary_unpack` decodes any of them.
    """
    plane = bp_ops.pack_bits(sym, 2)
    return jnp.concatenate([
        plane,
        floats_to_words(vbuf, wire_dtype),
        floats_to_words(jnp.stack([c1, c2]), wire_dtype),
    ])


def ternary_pack(flat, key, p_pass: float, cap: int, wire_dtype,
                 probs: str = "uniform"):
    """Encode (d,) f32 -> (ternary_wire_words(d, cap),) uint32 wire buffer.

    Delegates the sampling to encoders.encode (kind="ternary": c1 = min(x),
    c2 = max(x); ``probs`` picks the mid-split p1 = p2 = (1 − p_pass)/2 or
    the §6 per-coordinate optimal split) and packs its branch indices — so
    the decoded Y_i is bit-equal to the dense encoder's by construction
    (modulo the ~1e-9 capacity overflow and wire-precision rounding).  The
    buffer layout is independent of ``probs``: branch choices ride the
    plane, so the decoder needs no probabilities.
    """
    enc = encoders.encode(
        key, flat.astype(jnp.float32),
        t.EncoderSpec(kind="ternary", fraction=p_pass, probs=probs))
    sym = enc.extras["branch"]
    sent = sym == 2  # enc.y holds the pass-through value exactly there
    vbuf = rank_scatter(enc.y, sent, cap)
    return ternary_words(sym, vbuf, enc.extras["c1"], enc.extras["c2"],
                         wire_dtype)


def ternary_unpack(buf, d: int, cap: int, wire_dtype):
    """Reconstruct the dense Y_i (f32) from one node's ternary buffer."""
    pw = bp_ops.num_words(d, 2)
    vw = float_words(cap, wire_dtype)
    sym = bp_ops.unpack_bits(buf[:pw], 2, d)
    vals = words_to_floats(buf[pw:pw + vw], cap, wire_dtype)
    c = words_to_floats(buf[pw + vw:], 2, wire_dtype)
    pos = count_prefix(sym == 2) - 1
    valid = (sym == 2) & (pos < cap)
    v = vals[jnp.clip(pos, 0, cap - 1)]
    fallback = 0.5 * (c[0] + c[1])  # symmetric 6σ-overflow substitute
    return jnp.where(sym == 0, c[0],
                     jnp.where(sym == 1, c[1],
                               jnp.where(valid, v, fallback)))


# --------------------------------------------------------------------------- #
# Word-aligned shard decode (reduce-scatter decode, DESIGN.md §13).
#
# Shard boundaries snap to uint32 word boundaries (wire.scatter_shard_len
# with the alignments below), so each node touches only a contiguous word
# range of every peer's packed plane — never splitting a word across nodes.
# All helpers fold peers in ascending order, reproducing the sequential
# flat decode's per-coordinate f32 add chain bit-for-bit.
# --------------------------------------------------------------------------- #

BINARY_ALIGN = WORD           # 1-bit plane: 32 coordinates per uint32 word
TERNARY_ALIGN = WORD // 2     # 2-bit plane: 16 coordinates per uint32 word


def _plane_window(plane, nshards: int, ws: int, w0):
    """(n, pw) plane words -> the (n, ws) word window starting at word w0.

    Pads to the full nshards*ws aligned extent first, so the traced-offset
    dynamic_slice never clamps; pad words are zero (== symbol 0), matching
    the zero padding pack_bits applies inside the last real word.
    """
    n, pw = plane.shape
    plane = jnp.pad(plane, ((0, 0), (0, nshards * ws - pw)))
    return jax.lax.dynamic_slice(plane, (0, w0), (n, ws))


def binary_decode_shard(rows, d: int, wire_dtype, start, ds: int,
                        nshards: int, *, force_pallas: bool = False):
    """Sum of all peers' binary Y_i over coordinates [start, start+ds).

    The collective-free per-node work of the §13 scatter decode: one pass
    over the n×(ds/32) word window folding every peer into a single (ds,)
    f32 accumulator (fused kernel: repro.kernels.bitplane.ops.binary_accum)
    — bit-for-bit the [start:start+ds) slice of Σ_i binary_unpack(rows[i]),
    zeroed past d.  ``ds`` must be 32-aligned
    (wire.scatter_shard_len(d, nshards, BINARY_ALIGN)).
    """
    pw = bp_ops.num_words(d, 1)
    ws = ds // WORD
    win = _plane_window(rows[:, :pw], nshards, ws, start // WORD)
    c = jax.vmap(lambda tail: words_to_floats(tail, 2, wire_dtype))(
        rows[:, pw:])
    total = bp_ops.binary_accum(win, c[:, 0], c[:, 1], ds,
                                force_pallas=force_pallas)
    return jnp.where(jnp.arange(ds) + start < d, total, 0.0)


def ternary_shard_syms(rows, d: int, start, ds: int, nshards: int):
    """Every peer's 2-bit symbols over coordinates [start, start+ds).

    Returns (n, ds) uint32; symbols past d are 0 (plane zero padding), so
    per-shard pass-through counts need no extra masking.  ``ds`` must be
    16-aligned (wire.scatter_shard_len(d, nshards, TERNARY_ALIGN)).
    """
    pw = bp_ops.num_words(d, 2)
    per = TERNARY_ALIGN
    ws = ds // per
    win = _plane_window(rows[:, :pw], nshards, ws, start // per)
    shifts = jnp.arange(per, dtype=jnp.uint32) * jnp.uint32(2)
    sym = (win[:, :, None] >> shifts[None, None, :]) & jnp.uint32(3)
    return sym.reshape(rows.shape[0], ds)


def ternary_decode_shard(rows, syms, prior, d: int, cap: int, wire_dtype,
                         start):
    """Sum of all peers' ternary Y_i over this shard's coordinate window.

    ``syms`` is the (n, ds) window from :func:`ternary_shard_syms`;
    ``prior`` is (n,) int32 — each peer's pass-through count over all
    coordinates BEFORE ``start`` (from the per-shard counts all_gather +
    exclusive cumsum in TernaryCodec.decode_gathered_shard), which offsets
    the within-window ranks to the global support-rank positions of the
    flat decode.  Peers fold in ascending order; result is bit-for-bit the
    window slice of Σ_i ternary_unpack(rows[i]), zeroed past d.
    """
    n, ds = syms.shape
    pw = bp_ops.num_words(d, 2)
    vw = float_words(cap, wire_dtype)
    vals = jax.vmap(lambda r: words_to_floats(r[pw:pw + vw], cap,
                                              wire_dtype))(rows)
    c = jax.vmap(lambda r: words_to_floats(r[pw + vw:], 2, wire_dtype))(rows)

    def body(i, acc):
        sym = syms[i]
        sent = sym == 2
        pos = prior[i] + count_prefix(sent) - 1
        valid = sent & (pos < cap)
        v = vals[i][jnp.clip(pos, 0, cap - 1)]
        fallback = 0.5 * (c[i, 0] + c[i, 1])
        y = jnp.where(sym == 0, c[i, 0],
                      jnp.where(sym == 1, c[i, 1],
                                jnp.where(valid, v, fallback)))
        return acc + y

    total = jax.lax.fori_loop(0, n, body, jnp.zeros((ds,), jnp.float32))
    return jnp.where(jnp.arange(ds) + start < d, total, 0.0)
