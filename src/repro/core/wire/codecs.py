"""The five production wire codecs (docs/DESIGN.md §3).

Each class below is the codec-registry form of a wire path that previously
lived as a hand-rolled function in :mod:`repro.core.collectives`; the PRNG
fold_in chains and op sequences are preserved exactly, so the refactor is
bit-identical (same estimates, same lowered HLO — verified by
tests/distributed_checks/quantized_wire_check.py and bucketing_check.py):

  * ``fixed_k``        — §4.4 Eq. (9) gather path: block-structured fixed-k
    values + μ tail; supports regenerate from fold_in(key, peer).
  * ``fixed_k_shared`` — TPU-native shared-support variant: one psum of the
    k-length value buffer (reduce kind "psum").
  * ``bernoulli``      — §4.4 Eq. (10) seed trick with capacity-padded
    value buffers (comm_cost.bernoulli_capacity).
  * ``binary``         — §4.5 Eq. (11) packed 1-bit sign plane
    (repro.core.bitplane), no seed term: the plane travels.
  * ``ternary``        — §7.1 Eq. (21) packed 2-bit plane + capacity-padded
    pass-through values.
  * ``ternary_opt``    — the §6 optimal per-coordinate (p1, p2) split on the
    same 2-bit plane and capacity rule (repro.core.optimal
    .ternary_optimal_probs).
  * ``dense``          — dense simulation: encode per node, exact pmean of
    the dense encodings (any encoder incl. the §6 optimal policies; charged
    naive f32 bits — the wire it actually rides).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core import bitplane
from repro.core import comm_cost
from repro.core import encoders
from repro.core import types as t
from repro.core.wire import base
from repro.kernels.bernoulli_wire import ops as bw_ops
from repro.kernels.bernoulli_wire import ref as bw_ref
from repro.kernels import numerics
from repro.kernels.fixed_k_encode import ops as fk
from repro.kernels.threefry import ref as threefry


def _wire_r(cfg: t.CompressionConfig) -> int:
    """r: bits per wire float (16 for bf16, 32 for f32)."""
    return bitplane.wire_bits(cfg.wire_dtype)


def _seed_spec(cfg: t.CompressionConfig) -> t.CommSpec:
    """CommSpec of the §4.4 seed-trick paths at the configured wire dtype:
    the μ tail slot travels at wire precision (r̄ = r)."""
    r = _wire_r(cfg)
    return t.CommSpec(protocol="sparse_seed", r_bits=r, rbar_bits=r,
                      rseed_bits=t.DEFAULT_RSEED_BITS)


# --------------------------------------------------------------------------- #
# fixed-k (block-structured) — gather + shared-support variants.
# --------------------------------------------------------------------------- #

def fixed_k_blocks(d: int, fraction: float) -> int:
    """kb: number of sampled blocks for a d-vector at the given fraction."""
    nb = fk.num_blocks(d)
    return max(1, min(nb, int(round(fraction * nb))))


def fixed_k_wire_slots(d: int, fraction: float) -> int:
    """Wire-dtype elements of one fixed-k gather buffer: kb·BLOCK values + μ."""
    return fixed_k_blocks(d, fraction) * fk.BLOCK + 1


def fixed_k_pack(flat, key, cfg, *, scale=None):
    """THE fixed-k wire buffer: [kb·BLOCK values ‖ μ] at the wire dtype.

    ``key`` is the support seed exactly as sampled (the gather codec folds
    the rank in, the shared codec does not).  ``scale=None`` is the
    unbiased Eq. (4) rescale; ``scale=1.0`` the contractive scale-1 values
    of the error-feedback twin (repro.core.wire.ef) — same layout either
    way, so the codecs' unpack/decode hooks decode both.
    """
    d = flat.shape[0]
    nb = fk.num_blocks(d)
    kb = fixed_k_blocks(d, cfg.encoder.fraction)
    ids = fk.sample_blocks(key, nb, kb)
    mu = base.center(flat, cfg.encoder.center)
    vals = fk.fixed_k_encode(flat, ids, mu, scale=scale)
    return jnp.concatenate([vals.reshape(-1), mu[None]]).astype(
        cfg.wire_dtype)


class FixedKGatherCodec(base.WireCodec):
    """gather_decode fixed-k: independent supports, [values ‖ μ] per node.

    Wire per node: kb·BLOCK + 1 wire-dtype elements — the star protocol
    §4.4 with implicit seeds.  Decode regenerates every peer's support
    locally and averages the dense reconstructions:
    Y = mean μ_i + (1/n) Σ_i scatter(ids_i, vals_i).
    """

    name = "fixed_k"
    scatter_supported = True

    def wire_slots(self, d, cfg):
        return fixed_k_wire_slots(d, cfg.encoder.fraction)

    def wire_bits(self, n, d, cfg):
        return float(n * self.wire_slots(d, cfg) * _wire_r(cfg))

    def seed_bits(self, n, cfg):
        return float(n * t.DEFAULT_RSEED_BITS)

    def cost_spec(self, d, cfg):
        k = fixed_k_blocks(d, cfg.encoder.fraction) * fk.BLOCK
        return _seed_spec(cfg), {"k": k}

    def pack(self, flat, key, rank, cfg):
        return fixed_k_pack(flat, jax.random.fold_in(key, rank), cfg)

    def unpack(self, row, peer, key, cfg, d):
        row = row.astype(jnp.float32)
        nb = fk.num_blocks(d)
        kb = fixed_k_blocks(d, cfg.encoder.fraction)
        ids = fk.sample_blocks(jax.random.fold_in(key, peer), nb, kb)
        vals = row[:-1].reshape(kb, fk.BLOCK)
        dense = jnp.zeros((nb, fk.BLOCK), jnp.float32).at[ids].add(vals)
        return dense.reshape(-1)[:d] + row[-1]

    def decode_gathered(self, rows, key, cfg, d, n):
        # fused scatter-accumulate decode (one (nb, BLOCK) accumulator
        # instead of n dense intermediates) — the original op sequence.
        rows = rows.astype(jnp.float32)
        nb = fk.num_blocks(d)
        kb = fixed_k_blocks(d, cfg.encoder.fraction)
        all_vals = rows[:, :-1].reshape(n, kb, fk.BLOCK)
        all_mu = rows[:, -1]

        def body(i, acc):
            ids_i = fk.sample_blocks(jax.random.fold_in(key, i), nb, kb)
            return acc.at[ids_i].add(all_vals[i])

        acc = jax.lax.fori_loop(0, n, body,
                                jnp.zeros((nb, fk.BLOCK), jnp.float32))
        return (acc / n + numerics.ordered_mean(all_mu)).reshape(-1)[:d]

    def decode_gathered_shard(self, rows, key, cfg, d, n, shard, nshards):
        # reduce-scatter decomposition: accumulate only the blocks in this
        # node's contiguous ⌈nb/nshards⌉-block window.  Out-of-window ids
        # land in a dump row that is sliced off, so every in-window block
        # receives exactly the flat decode's adds in the same peer order —
        # the concatenated shards equal decode_gathered bit-for-bit.
        rows = rows.astype(jnp.float32)
        nb = fk.num_blocks(d)
        kb = fixed_k_blocks(d, cfg.encoder.fraction)
        nb_s = -(-nb // nshards)
        all_vals = rows[:, :-1].reshape(n, kb, fk.BLOCK)
        all_mu = rows[:, -1]
        lo = shard * nb_s

        def body(i, acc):
            ids_i = fk.sample_blocks(jax.random.fold_in(key, i), nb, kb)
            loc = ids_i - lo
            loc = jnp.where((loc >= 0) & (loc < nb_s), loc, nb_s)
            return acc.at[loc].add(all_vals[i])

        acc = jax.lax.fori_loop(0, n, body,
                                jnp.zeros((nb_s + 1, fk.BLOCK), jnp.float32))
        return (acc[:nb_s] / n + numerics.ordered_mean(all_mu)).reshape(-1)

    def scatter_bits(self, n, d, cfg):
        # flat scatter (DESIGN.md §12) adds ONE collective on the main
        # axes: the decoded f32 shard all_gather (the dump-row window is
        # analytic — no count exchange).  Hierarchical scatter rides the
        # inner axes and is billed free (§11 convention).
        if not cfg.scatter_decode or cfg.inner_axes:
            return 0.0
        nb_s = -(-fk.num_blocks(d) // n)
        return float(n * nb_s * fk.BLOCK * 32)


class FixedKSharedCodec(base.WireCodec):
    """shared_support fixed-k: one psum of [k wire values ‖ μ] + scatter.

    All nodes draw the *same* support (shared seed: ``key`` is not
    rank-folded), so the averaged wire values ride a plain psum —
    ring-bandwidth optimal.  MSE closed form:
    :func:`repro.core.mse.mse_fixed_k_shared`.
    """

    name = "fixed_k_shared"
    reduce = "psum"

    def wire_slots(self, d, cfg):
        return fixed_k_wire_slots(d, cfg.encoder.fraction)

    def wire_bits(self, n, d, cfg):
        # star-payload convention: n × the reduced buffer (what each node
        # contributes), matching the all-reduce payload accounting in
        # benchmarks/bench_collectives.py.
        return float(n * self.wire_slots(d, cfg) * _wire_r(cfg))

    def seed_bits(self, n, cfg):
        # Eq. (9) charges r̄_s per node; our SPMD realization shares one
        # seed (the per-step key), so this is the faithful-protocol bound.
        return float(n * t.DEFAULT_RSEED_BITS)

    def cost_spec(self, d, cfg):
        k = fixed_k_blocks(d, cfg.encoder.fraction) * fk.BLOCK
        return _seed_spec(cfg), {"k": k}

    def pack(self, flat, key, rank, cfg):
        # shared support: ``key`` is deliberately NOT rank-folded — every
        # node draws the same subset, so the wire values average under a
        # plain psum.  The psum runs at the wire dtype (r = 16
        # bits/coordinate, matching the paper's r and the bf16-native TPU
        # all-reduce); μ rides the tail slot so the bucket still costs one
        # launch.
        return fixed_k_pack(flat, key, cfg)

    def decode_reduced(self, wire, key, cfg, d):
        wire = wire.astype(jnp.float32)
        nb = fk.num_blocks(d)
        kb = fixed_k_blocks(d, cfg.encoder.fraction)
        ids = fk.sample_blocks(key, nb, kb)
        gvals = wire[:-1].reshape(-1, fk.BLOCK)
        return fk.fixed_k_decode(gvals, ids, wire[-1], (d,))

    def unpack(self, row, peer, key, cfg, d):
        # shared support ⇒ decoding one node's un-reduced buffer is peer-
        # independent: it reconstructs that node's own dense message.
        return self.decode_reduced(row, key, cfg, d)


# --------------------------------------------------------------------------- #
# Bernoulli (variable-size-support) — the §4.4 seed trick.
# --------------------------------------------------------------------------- #

def bernoulli_wire_slots(d: int, fraction: float) -> int:
    """Wire-dtype elements of one §4.4 Bernoulli buffer: cap values + μ."""
    return comm_cost.bernoulli_capacity(d, float(fraction)) + 1


def _bernoulli_support(key, d: int, p):
    """The S_i of Eq. (1) under uniform probs: data-independent, so any peer
    regenerates it from the shared per-step key + node index alone."""
    return threefry.uniform(key, d) < p


def bernoulli_pack(flat, key, p: float, cap: int, mu, *, scaled=True):
    """Compact the Eq. (1) encoding into a (cap,) value buffer.

    Sent coordinates land at their support-rank position; coordinates whose
    rank overflows ``cap`` (≈6σ tail, see comm_cost.bernoulli_capacity) are
    dropped — the decoder regenerates the same ranks and drops them too, so
    encode/decode stay consistent (cost: a ~1e-9-probability bias toward μ
    on the dropped coordinates).  ``scaled=False`` ships the raw values
    instead of the unbiased 1/p rescale — the error-feedback twin
    (repro.core.wire.ef); the layout is identical, so
    :func:`bernoulli_unpack` decodes both.

    Dispatches through :mod:`repro.kernels.bernoulli_wire` — the fused
    sample+select+rank-compact Pallas kernel on TPU, the byte-identical jnp
    reference elsewhere (golden wire matrix pins the bytes).
    """
    return bw_ops.encode(flat, key, p, cap, mu, scaled=scaled)


def bernoulli_unpack(buf, key, p: float, cap: int, mu, d: int):
    """Regenerate node ``key``'s support and reconstruct its dense Y_i."""
    sent = _bernoulli_support(key, d, p)
    pos = numerics.count_prefix(sent) - 1
    valid = sent & (pos < cap)
    vals = buf[jnp.clip(pos, 0, cap - 1)]
    return jnp.where(valid, vals, mu)


def bernoulli_buffer(flat, key, rank, cfg, *, scaled=True):
    """THE §4.4 Bernoulli wire buffer: [cap value slots ‖ μ] at wire dtype
    (support from fold_in(key, rank); ``scaled`` as in bernoulli_pack)."""
    d = flat.shape[0]
    p = float(cfg.encoder.fraction)
    cap = comm_cost.bernoulli_capacity(d, p)
    kenc = jax.random.fold_in(key, rank)
    mu = base.center(flat, cfg.encoder.center)
    buf = bernoulli_pack(flat, kenc, p, cap, mu, scaled=scaled)
    return jnp.concatenate([buf, mu[None]]).astype(cfg.wire_dtype)


class BernoulliCodec(base.WireCodec):
    """gather_decode for the uniform-p Bernoulli encoder, real §4.4 wire.

    Each node all_gathers one [cap value slots ‖ μ] buffer; peers
    regenerate the supports from fold_in(key, peer).  Bit accounting:
    comm_cost.cost_sparse_seed_capacity — the static-shape realization of
    Eq. (10).
    """

    name = "bernoulli"
    scatter_supported = True

    def wire_slots(self, d, cfg):
        return bernoulli_wire_slots(d, cfg.encoder.fraction)

    def wire_bits(self, n, d, cfg):
        return float(n * self.wire_slots(d, cfg) * _wire_r(cfg))

    def seed_bits(self, n, cfg):
        return float(n * t.DEFAULT_RSEED_BITS)

    def cost_spec(self, d, cfg):
        cap = comm_cost.bernoulli_capacity(d, float(cfg.encoder.fraction))
        return _seed_spec(cfg), {"cap": cap}

    def pack(self, flat, key, rank, cfg):
        return bernoulli_buffer(flat, key, rank, cfg)

    def unpack(self, row, peer, key, cfg, d):
        p = float(cfg.encoder.fraction)
        cap = comm_cost.bernoulli_capacity(d, p)
        row = row.astype(jnp.float32)
        return bernoulli_unpack(row[:-1], jax.random.fold_in(key, peer),
                                p, cap, row[-1], d)

    def decode_gathered(self, rows, key, cfg, d, n):
        # fused regenerate+unpack+accumulate: all peer supports in one
        # batched Threefry dispatch (CPU) or one Pallas kernel folding the
        # n buffers straight into a single (d,) accumulator (TPU) — never
        # n dense per-peer reconstructions.  Same estimate as the default
        # sequential fori decode up to summation order.
        p = float(cfg.encoder.fraction)
        cap = comm_cost.bernoulli_capacity(d, p)
        rows = rows.astype(jnp.float32)
        keys = jnp.stack([jax.random.fold_in(key, i) for i in range(n)])
        total = bw_ops.decode_sum(rows[:, :-1], rows[:, -1], keys,
                                  p, cap, d)
        return total / n

    def decode_gathered_shard(self, rows, key, cfg, d, n, shard, nshards):
        # reduce-scatter decomposition.  Support ranks are global (a sent
        # coordinate's value slot is its rank in the FULL support), so each
        # shard needs every peer's support count strictly before its
        # window: per-shard counts are all_gathered over the scatter axes
        # (inner when hierarchical, the main axes on the flat mesh) and
        # exclusive-cumsummed.  Shard supports regenerate via scattered
        # Threefry lanes (threefry.ref.uniform_at): only d/nshards draws
        # per peer instead of d, which is where the O(n·d) → O(n·d/m)
        # decode win comes from.
        p = float(cfg.encoder.fraction)
        cap = comm_cost.bernoulli_capacity(d, p)
        rows = rows.astype(jnp.float32)
        keys = jnp.stack([jax.random.fold_in(key, i) for i in range(n)])
        ds = -(-d // nshards)
        start = shard * ds
        sent = bw_ops.support_shard(keys, p, d, start, ds)
        counts = jnp.sum(sent.astype(jnp.int32), axis=1)
        allc = base.gather_nested(
            counts, base.scatter_axes(cfg)).reshape(nshards, n)
        prior = jnp.cumsum(allc, axis=0) - allc
        prior_here = jnp.take(prior, shard, axis=0)
        total = bw_ops.decode_sum_shard(rows[:, :-1], rows[:, -1], keys,
                                        sent, prior_here, start,
                                        p=p, cap=cap, d=d)
        return total / n

    def scatter_bits(self, n, d, cfg):
        # flat scatter (DESIGN.md §12) adds TWO collectives on the main
        # axes: the per-shard support counts (n i32 per node — the global
        # rank offsets) and the decoded f32 shard all_gather.
        # Hierarchical scatter rides the inner axes and is billed free
        # (§11 convention).
        if not cfg.scatter_decode or cfg.inner_axes:
            return 0.0
        ds = -(-d // n)
        return float(n * n * 32 + n * ds * 32)


# --------------------------------------------------------------------------- #
# Binary / ternary packed bit-plane codecs (§4.5 / §7.1).
# --------------------------------------------------------------------------- #

class BinaryCodec(base.WireCodec):
    """gather_decode for binary quantization with the packed 1-bit plane.

    Each node all_gathers one uint32 buffer of [sign plane ‖ vmin, vmax]
    (:mod:`repro.core.bitplane`).  No seed term: the branch choices are
    data-dependent, so the plane travels explicitly.
    """

    name = "binary"
    scatter_supported = True

    def wire_slots(self, d, cfg):
        return bitplane.binary_wire_words(d, cfg.wire_dtype)

    def wire_bits(self, n, d, cfg):
        return float(n * 32 * self.wire_slots(d, cfg))

    def cost_spec(self, d, cfg):
        return (t.CommSpec(protocol="binary", r_bits=_wire_r(cfg)),
                {"packed": True})

    def pack(self, flat, key, rank, cfg):
        return bitplane.binary_pack(flat, jax.random.fold_in(key, rank),
                                    cfg.wire_dtype)

    def unpack(self, row, peer, key, cfg, d):
        return bitplane.binary_unpack(row, d, cfg.wire_dtype)

    def scatter_align(self, cfg):
        return bitplane.BINARY_ALIGN

    def decode_gathered_shard(self, rows, key, cfg, d, n, shard, nshards):
        # reduce-scatter decomposition (DESIGN.md §13): shard boundaries
        # snap to uint32 word boundaries of the 1-bit plane (32
        # coords/word), so each node reads only its contiguous word window
        # of every peer's plane — one fused unpack+center-select+accumulate
        # pass (kernels/bitplane binary_accum) over the n×(ds/32) window.
        ds = base.scatter_shard_len(d, nshards, bitplane.BINARY_ALIGN)
        total = bitplane.binary_decode_shard(rows, d, cfg.wire_dtype,
                                             shard * ds, ds, nshards)
        return total / n

    def scatter_bits(self, n, d, cfg):
        # flat scatter adds ONE collective on the main axes: the decoded
        # f32 shard all_gather (no bookkeeping exchange — the plane itself
        # travels, so peers need no rank offsets).  Hierarchical scatter
        # rides the inner axes and is billed free (§11 convention).
        if not cfg.scatter_decode or cfg.inner_axes:
            return 0.0
        ds = base.scatter_shard_len(d, n, bitplane.BINARY_ALIGN)
        return float(n * ds * 32)


class TernaryCodec(base.WireCodec):
    """gather_decode for the ternary encoder (Eq. (21)) with a 2-bit plane.

    Wire per node: [2-bit branch plane ‖ cap pass-through value slots ‖
    c1, c2] in one uint32 buffer; the value segment is capacity-padded
    exactly like the Bernoulli §4.4 path.
    """

    name = "ternary"
    scatter_supported = True

    def _cap(self, d, cfg):
        return comm_cost.bernoulli_capacity(d, float(cfg.encoder.fraction))

    def wire_slots(self, d, cfg):
        return bitplane.ternary_wire_words(d, self._cap(d, cfg),
                                           cfg.wire_dtype)

    def wire_bits(self, n, d, cfg):
        return float(n * 32 * self.wire_slots(d, cfg))

    def cost_spec(self, d, cfg):
        return (t.CommSpec(protocol="ternary", r_bits=_wire_r(cfg)),
                {"packed": True, "cap": self._cap(d, cfg)})

    def pack(self, flat, key, rank, cfg):
        d = flat.shape[0]
        return bitplane.ternary_pack(flat, jax.random.fold_in(key, rank),
                                     float(cfg.encoder.fraction),
                                     self._cap(d, cfg), cfg.wire_dtype)

    def unpack(self, row, peer, key, cfg, d):
        return bitplane.ternary_unpack(row, d, self._cap(d, cfg),
                                       cfg.wire_dtype)

    def scatter_align(self, cfg):
        return bitplane.TERNARY_ALIGN

    def decode_gathered_shard(self, rows, key, cfg, d, n, shard, nshards):
        # reduce-scatter decomposition (DESIGN.md §13).  Shard boundaries
        # snap to 2-bit-plane word boundaries (16 coords/word).  Pass-
        # through value slots are addressed by GLOBAL support rank, so —
        # exactly like BernoulliCodec — each shard needs every peer's
        # pass-through count strictly before its window: per-shard counts
        # are all_gathered over the scatter axes and exclusive-cumsummed
        # into rank offsets.  Unlike Bernoulli there is no support to
        # regenerate: the counts come straight from the shard's own symbol
        # window.
        ds = base.scatter_shard_len(d, nshards, bitplane.TERNARY_ALIGN)
        start = shard * ds
        cap = self._cap(d, cfg)
        syms = bitplane.ternary_shard_syms(rows, d, start, ds, nshards)
        counts = jnp.sum((syms == 2).astype(jnp.int32), axis=1)
        allc = base.gather_nested(
            counts, base.scatter_axes(cfg)).reshape(nshards, n)
        prior = jnp.cumsum(allc, axis=0) - allc
        prior_here = jnp.take(prior, shard, axis=0)
        total = bitplane.ternary_decode_shard(rows, syms, prior_here, d,
                                              cap, cfg.wire_dtype, start)
        return total / n

    def scatter_bits(self, n, d, cfg):
        # flat scatter adds TWO collectives on the main axes: the
        # per-shard pass-through counts (n i32 per node — the global rank
        # offsets) and the decoded f32 shard all_gather.
        if not cfg.scatter_decode or cfg.inner_axes:
            return 0.0
        ds = base.scatter_shard_len(d, n, bitplane.TERNARY_ALIGN)
        return float(n * n * 32 + n * ds * 32)


class TernaryOptCodec(TernaryCodec):
    """gather_decode for the §6-optimal ternary encoder (probs="optimal").

    Per-coordinate optimal (p1, p2) — :func:`repro.core.optimal
    .ternary_optimal_probs`, the §6 "optimal parameters" move applied to
    the Eq. (21) plane — on the *same* wire format as ``ternary``: the
    branch probabilities are data-dependent, but the realized branch
    choices ride the 2-bit plane (which travels anyway), so the decoder
    never needs them.  The pass-through mass stays exactly
    Bernoulli(fraction) per coordinate under the optimal split, so the 6σ
    capacity rule, wire_slots/wire_bits and cost_spec are all inherited
    from :class:`TernaryCodec` unchanged — this codec is honestly
    wire-modelled, unlike the §6 Bernoulli optimal-probability policies
    (whose supports are implicit and still fall back to ``dense``).
    """

    name = "ternary_opt"

    def pack(self, flat, key, rank, cfg):
        d = flat.shape[0]
        return bitplane.ternary_pack(flat, jax.random.fold_in(key, rank),
                                     float(cfg.encoder.fraction),
                                     self._cap(d, cfg), cfg.wire_dtype,
                                     probs="optimal")


# --------------------------------------------------------------------------- #
# Dense simulation (any encoder) — the accounting-honest fallback.
# --------------------------------------------------------------------------- #

class DenseSimCodec(base.WireCodec):
    """Encode locally (independent), exact pmean of the dense encodings.

    Estimate-distribution-identical to gather_decode; supports every
    encoder (incl. the §6 optimal-probability policies, whose message
    sizes are data-dependent and not wire-modelled yet).  Charged naive
    dense f32 bits — the wire it actually rides.

    The wire is PINNED to float32: ``pack`` casts to f32 regardless of
    ``cfg.wire_dtype`` (a narrower psum buffer would change the reduce
    arithmetic and silently break estimate-distribution equality with
    gather_decode), and ``wire_bits`` charges the matching 32 bits/slot.
    ``cfg.wire_dtype`` therefore deliberately does NOT apply here; the
    contract is pinned by tests/test_dense_codec_contract.py.
    """

    name = "dense"
    reduce = "psum"

    #: the psum wire's element width in bits — always f32, see class doc.
    WIRE_BITS_PER_SLOT = 32

    def wire_slots(self, d, cfg):
        return d

    def wire_bits(self, n, d, cfg):
        # intentionally ignores cfg.wire_dtype: the buffer pack() emits is
        # f32 whatever the config says, and accounting follows the bytes.
        return float(n * d * self.WIRE_BITS_PER_SLOT)

    def cost_spec(self, d, cfg):
        return t.CommSpec(protocol="naive", r_bits=32), {}

    def pack(self, flat, key, rank, cfg):
        kenc = jax.random.fold_in(key, rank)
        return encoders.encode(kenc, flat, cfg.encoder).y.astype(jnp.float32)

    def decode_reduced(self, wire, key, cfg, d):
        return wire

    def unpack(self, row, peer, key, cfg, d):
        return row.astype(jnp.float32)
