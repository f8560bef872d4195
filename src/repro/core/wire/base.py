"""WireCodec: the unit of the wire layer (docs/DESIGN.md §3).

The paper's protocols differ only in *what one node puts on the wire* and
*how peers decode it*; everything else — the star-gather scaffold, bucket
planning, bit accounting, benchmark sweeps — is protocol-independent.  A
:class:`WireCodec` captures exactly that per-protocol surface:

  * ``pack(flat, key, rank, cfg)``    — one node's wire buffer (any dtype);
  * ``unpack(row, peer, key, cfg, d)``— reconstruct peer ``peer``'s dense
    Y_i from its gathered row (regenerating seed-trick supports from
    ``fold_in(key, peer)`` where the protocol allows);
  * ``wire_slots(d, cfg)``            — static buffer length in elements;
  * ``wire_bits(n, d, cfg)``          — exact gathered payload bits for an
    n-node round: what the lowered HLO's collective result shape shows
    (the star-protocol convention the paper's C sums use);
  * ``reduce``                        — "all_gather" (star protocol) or
    "psum" (shared-support / dense-simulation paths whose wire is a plain
    all-reduce).

``mean_flat`` is the collective itself: gather codecs run the star gather
(pack → all_gather over cfg.axes → per-peer decode → average); "psum"
codecs run pack → pmean → ``decode_reduced`` (their wire is the reduced
buffer itself).  ``decode_gathered`` exists as a separate hook so codecs
with a fused decode (fixed-k's scatter-accumulate) keep their exact op
sequence — the refactor from the hand-rolled paths in
repro.core.collectives is bit-identical by construction: same PRNG
fold_in chain, same op order, same HLO.

Stateful codecs (docs/DESIGN.md §8): a codec may thread per-bucket state
through the round — error feedback's residual is the production case
(:mod:`repro.core.wire.ef`).  ``state_shape`` declares the state (None for
the stateless majority), ``init_state`` zeros it, and
``mean_flat_stateful`` is the (estimate, new_state) entry point every
caller that owns state uses (``repro.core.collectives
.compressed_mean_stateful``, ``repro.train.bucketing``).  The default
implementation makes every stateless codec trivially drivable through the
stateful API (state passes through untouched), so the train step has ONE
code path regardless of codec.  State is local by contract: it never
appears in the wire buffer, so the payload accounting below is unchanged
by statefulness (HLO-verified in tests/distributed_checks/ef_wire_check).

Accounting contract (verified by tests/test_wire_registry.py for every
registered codec):  ``comm_cost_bits == wire_bits + seed_bits`` — the
analytic §4 cost splits into bits that physically travel (the gathered
buffer, HLO-measurable) plus bits that ride the implicit PRNG (the §4.4
seed trick: supports/rotations regenerate peer-side from the shared key).

Device scopes (``jax.named_scope``, metadata only: they reach each compiled
instruction's ``op_name`` and change no instruction).  Every operation of
one round sits under a stage: ``encode`` (pack, the EF twin), ``exchange``
(the wire all_gather, the psum codecs' pmean, the decoded-shard
all_gather, the inner-axes pre-reduce), ``decode`` (with ``trim`` around
the robust reductions), and from the wrappers ``rotate`` / ``unrotate``
(:class:`~repro.core.wire.rotated.RotatedCodec`, the EF twin's rotation)
and ``ef`` (the residual add and update).  The hooks below set them, not
the codecs; where stages nest (the EF twin's rotation under ``encode``)
the innermost names the operation.
"""
from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp

from repro.core import types as t
from repro.core.wire import robust
from repro.kernels import numerics

Axes = Tuple[str, ...]


def axis_rank_size(axes: Axes):
    """Linear rank of this shard within the compression axes + node count."""
    rank = jnp.zeros((), jnp.int32)
    n = 1
    for ax in axes:
        rank = rank * jax.lax.axis_size(ax) + jax.lax.axis_index(ax)
        n *= jax.lax.axis_size(ax)
    return rank, n


def gather_nested(v, axes: Axes):
    """all_gather over possibly-multiple axes, flattening the node dim."""
    out = v[None]
    for ax in reversed(axes):
        out = jax.lax.all_gather(out, ax, axis=0, tiled=True)
    return out


def scatter_axes(cfg: t.CompressionConfig) -> Axes:
    """The mesh axes a scatter decode shards over (DESIGN.md §11/§12).

    Hierarchical configs shard over the inner (fast, intra-host) axes —
    the decoded-shard all_gather rides a link the accounting treats as
    free.  Flat configs shard over the compression axes themselves: every
    node decodes its own ⌈d/n⌉ coordinate slice of all n peer rows, and
    the shard gather crosses the main mesh (billed by
    :meth:`WireCodec.scatter_bits`).
    """
    return cfg.inner_axes if cfg.inner_axes else cfg.axes


def scatter_shard_len(d: int, nshards: int, align: int = 1) -> int:
    """Length of one scatter-decode shard: ⌈d/nshards⌉ rounded up to ``align``.

    Word-aligned sharding (DESIGN.md §13): packed bit-plane codecs store
    ``align`` coordinates per uint32 word (32 for the 1-bit plane, 16 for
    the ternary 2-bit plane), so shard boundaries snap to word boundaries
    and each node touches only a contiguous word range of every peer's
    plane.  Every shard emits exactly this many coordinates (the tail
    shard zero-padded past d), so the reassembling all_gather concatenates
    fixed-size parts and truncates to d.
    """
    ds = -(-d // nshards)
    return -(-ds // align) * align


def scatter_word_align(cfg: t.CompressionConfig) -> int:
    """Shard alignment (coordinates per indivisible wire word) for cfg.

    1 for the linear codecs (any split works), 32 for the binary 1-bit
    plane, 16 for the ternary 2-bit plane; wrappers delegate to their
    inner codec.  ``scatter_shard_len(d, nshards, scatter_word_align(cfg))``
    is THE shard split every scatter consumer (decode, accounting,
    benchmarks, checks) must agree on.
    """
    from repro.core.wire import registry
    return registry.resolve(cfg).scatter_align(cfg)


def effective_nodes(cfg: t.CompressionConfig, n: int,
                    mesh_sizes=None) -> int:
    """The codec's effective node count: the cross-host group size.

    Flat configs (no ``inner_axes``) compress over all ``n`` nodes and are
    billed for n messages.  Hierarchical configs pre-reduce exactly over
    the inner axes, so only ``n / prod(inner sizes)`` compressed messages
    exist per round — THE node count every accounting consumer
    (:func:`repro.core.comm_cost.cost_config`,
    :func:`repro.train.bucketing.bucket_wire_bits`) must charge, or
    hierarchical presets get billed payload that never crosses the slow
    link.  ``mesh_sizes`` maps axis name → size and is required whenever
    ``cfg.inner_axes`` is non-empty (the flat world size alone cannot
    determine the split).
    """
    if not cfg.inner_axes:
        return int(n)
    if mesh_sizes is None:
        raise ValueError(
            f"config has inner_axes={cfg.inner_axes}: accounting needs "
            "mesh_sizes to derive the cross-host group size")
    m = 1
    for ax in cfg.inner_axes:
        if ax not in mesh_sizes:
            raise ValueError(
                f"inner axis {ax!r} missing from mesh_sizes {mesh_sizes}")
        m *= int(mesh_sizes[ax])
    if m <= 0 or n % m:
        raise ValueError(
            f"world size {n} not divisible by inner-group size {m} "
            f"(inner_axes={cfg.inner_axes}, mesh_sizes={mesh_sizes})")
    return int(n) // m


def center(x, policy: str):
    """The node center μ_i used on the wire (data-independent policies only)."""
    if policy == "zero":
        return jnp.zeros((), jnp.float32)
    if policy == "mean":
        return numerics.ordered_mean(x)
    if policy == "min":
        return jnp.min(x).astype(jnp.float32)
    raise ValueError(f"center policy {policy!r} not supported on the wire "
                     "(optimal centers need the §6 solver — reference path only)")


class WireCodec:
    """One registered wire format; see the module docstring for the contract.

    Subclasses set ``name`` and ``reduce`` and implement the geometry,
    accounting and pack/unpack hooks.  Codecs are stateless: all parameters
    come from the :class:`repro.core.types.CompressionConfig` threaded into
    every call, so a single registered instance serves every bucket/config.
    """

    name: str = "?"
    reduce: str = "all_gather"          # "all_gather" | "psum"
    stateful: bool = False              # True iff state_shape is not None
    # True iff the codec implements decode_gathered_shard — the linear
    # gather decoders whose averaging decode partitions coordinate-wise
    # (fixed_k, bernoulli, and wrappers that delegate to them).
    scatter_supported: bool = False

    # ---- wire geometry & accounting -------------------------------------- #

    def wire_slots(self, d: int, cfg: t.CompressionConfig) -> int:
        """Static length of one node's wire buffer, in buffer elements."""
        raise NotImplementedError

    def wire_bits(self, n: int, d: int, cfg: t.CompressionConfig) -> float:
        """Exact gathered payload bits of one n-node round (HLO-verified)."""
        raise NotImplementedError

    def seed_bits(self, n: int, cfg: t.CompressionConfig) -> float:
        """Bits riding the implicit PRNG instead of the wire (§4.4 seeds)."""
        return 0.0

    def scatter_bits(self, n: int, d: int, cfg: t.CompressionConfig) -> float:
        """Extra collective payload bits a FLAT scatter decode adds.

        Flat-mesh scatter (``cfg.scatter_decode`` with empty
        ``inner_axes``) runs its auxiliary collectives — the decoded-shard
        all_gather and any codec bookkeeping like Bernoulli's per-shard
        support counts — over the main compression axes, so their bytes
        cross the same link as the wire and must be billed
        (:func:`repro.core.comm_cost.cost_config` adds this term).
        Hierarchical scatter shards over the inner (fast) axes and stays
        billed at zero here, matching the §11 convention that intra-host
        traffic is free.  Zero for codecs/configs without flat scatter.
        """
        return 0.0

    def scatter_align(self, cfg: t.CompressionConfig) -> int:
        """Coordinates per indivisible wire word (shard-split alignment).

        Packed-plane codecs override this (32 for 1-bit, 16 for 2-bit
        symbols) so :func:`scatter_shard_len` snaps shard boundaries to
        uint32 word boundaries; wrappers delegate to their inner codec.
        """
        return 1

    def cost_spec(self, d: int, cfg: t.CompressionConfig):
        """(CommSpec, kwargs) mapping this codec onto comm_cost.cost."""
        raise NotImplementedError

    def comm_cost_bits(self, n: int, d: int, cfg: t.CompressionConfig) -> float:
        """Analytic §4 cost via comm_cost.cost — == wire_bits + seed_bits."""
        from repro.core import comm_cost
        spec, kw = self.cost_spec(d, cfg)
        return comm_cost.cost(spec, n=n, d=d, **kw)

    # ---- per-node wire format -------------------------------------------- #

    def pack(self, flat, key, rank, cfg: t.CompressionConfig):
        """Encode the local (d,) f32 vector into one flat wire buffer.

        ``key`` is the shared per-bucket key; protocols with per-node
        randomness fold ``rank`` in themselves (so peers can regenerate
        node i's draws from ``fold_in(key, i)`` alone).
        """
        raise NotImplementedError

    def unpack(self, row, peer, key, cfg: t.CompressionConfig, d: int):
        """Reconstruct peer ``peer``'s dense (d,) f32 Y_i from its row."""
        raise NotImplementedError

    def decode_gathered(self, rows, key, cfg: t.CompressionConfig,
                        d: int, n: int):
        """Averaging decoder over the gathered (n, slots) wire rows.

        Default: Y = (1/n) Σ_i unpack(row_i) — codecs with a fused decode
        (fixed-k scatter-accumulate) override this.
        """
        def body(i, acc):
            return acc + self.unpack(rows[i], i, key, cfg, d)

        acc = jax.lax.fori_loop(0, n, body, jnp.zeros((d,), jnp.float32))
        return acc / n

    def decode_rows(self, rows, key, cfg: t.CompressionConfig,
                    d: int, n: int):
        """The (n, d) stack of per-peer dense reconstructions Y_i.

        The materialized-stack companion of :meth:`decode_gathered`: row i
        is exactly ``unpack(rows[i], i, ...)``.  This is the input of the
        robust decode reductions (DESIGN.md §14, :mod:`repro.core.wire
        .robust`) — order statistics need every peer's value per
        coordinate, so the fused sum-only decoders cannot serve them.
        """
        peers = jnp.arange(n, dtype=jnp.int32)
        return jax.vmap(
            lambda row, i: self.unpack(row, i, key, cfg, d))(rows, peers)

    def decode_rows_shard(self, rows, key, cfg: t.CompressionConfig,
                          d: int, n: int, start, ds: int, nshards: int):
        """One contiguous ``ds``-coordinate window of :meth:`decode_rows`.

        Returns the (n, ds) slice ``decode_rows(...)[:, start:start+ds]``
        with coordinates past d zero-padded (``nshards·ds ≥ d`` by
        :func:`scatter_shard_len`), so robust reductions compose with the
        §12/§13 reduce-scatter decode per coordinate-shard: the shard
        window sees exactly the flat stack's values, and the word-aligned
        shard splits of the bit-plane codecs are honored by the caller
        passing an aligned ``ds``.  ``start`` may be traced (shard·ds).
        """
        pad = nshards * ds - d
        peers = jnp.arange(n, dtype=jnp.int32)

        def one(row, i):
            y = jnp.pad(self.unpack(row, i, key, cfg, d), (0, pad))
            return jax.lax.dynamic_slice(y, (start,), (ds,))
        return jax.vmap(one)(rows, peers)

    def decode_rows_reduce(self, rows, key, cfg: t.CompressionConfig,
                           d: int, n: int, drop_mask=None):
        """Policy-dispatched flat decode over the gathered wire rows.

        THE decode-reduction hook (DESIGN.md §14): ``cfg.decode_policy``
        == "mean" with no ``drop_mask`` takes the codec's fused
        :meth:`decode_gathered` verbatim (bit-identical to the historical
        decode — the golden wire matrix and HLO pins never see this
        branch); any robust policy or a drop mask materializes the
        per-peer stack and runs :func:`repro.core.wire.robust
        .reduce_rows`.  ``drop_mask`` is a traced (n,) 0/1 operand — mask
        changes never recompile — and the masked mean renormalizes by the
        kept count per the ``partial_mean`` contract (NaN on all-dead).
        """
        kind, f = robust.parse_policy(cfg.decode_policy)
        if kind == "mean" and drop_mask is None:
            return self.decode_gathered(rows, key, cfg, d, n)
        stack = self.decode_rows(rows, key, cfg, d, n)
        with jax.named_scope("trim"):
            return robust.reduce_rows(stack, kind, f, drop_mask)

    def decode_gathered_shard(self, rows, key, cfg: t.CompressionConfig,
                              d: int, n: int, shard, nshards: int):
        """One shard of the averaging decode (reduce-scatter decomposition).

        Returns this node's contiguous ``⌈d/nshards⌉``-slice of what
        :meth:`decode_gathered` would return (shard ``shard`` of
        ``nshards``; the last shard is zero-padded past d) — so that
        concatenating the shards in order and truncating to d reproduces
        the flat decode bit-for-bit.  Only codecs whose decode is a
        coordinate-wise sum over peer reconstructions can implement this
        (``scatter_supported``).
        """
        raise NotImplementedError(
            f"codec {self.name!r} does not support scatter_decode")

    def decode_reduced(self, wire, key, cfg: t.CompressionConfig, d: int):
        """Decode the *reduced* wire buffer of a "psum" codec.

        Only "psum" codecs implement this: their collective is a plain
        pmean of the packed buffer, and decoding the reduced buffer IS
        decoding the averaged messages (the decode is linear in the wire
        values).  Applied to one node's un-reduced buffer it reconstructs
        that node's own dense message — which is how the error-feedback
        wrapper obtains local contributions uniformly across reduce kinds.
        """
        raise NotImplementedError

    # ---- codec state (stateless by default; see wire/ef.py) -------------- #

    def state_shape(self, d: int, cfg: t.CompressionConfig):
        """Shape of the per-bucket local state threaded through one round,
        or None for stateless codecs.  State never travels on the wire."""
        return None

    def init_state(self, d: int, cfg: t.CompressionConfig):
        """Zero state for a d-vector bucket (None for stateless codecs)."""
        shp = self.state_shape(d, cfg)
        return None if shp is None else jnp.zeros(shp, jnp.float32)

    def mean_flat_stateful(self, flat, state, key, cfg: t.CompressionConfig,
                           drop_mask=None):
        """One stateful round: returns (mean_estimate, new_state).

        Default: stateless codecs ignore and pass the state through, so
        every codec is drivable through this one entry point.  Like
        :meth:`mean_flat`, the exact inner-axes pre-reduce of the
        hierarchical schedule happens here, before any codec layer runs.
        ``drop_mask`` as in :meth:`mean_flat`.
        """
        if cfg.inner_axes:
            with jax.named_scope("exchange"):
                flat = jax.lax.pmean(flat, cfg.inner_axes)
        return self._round_stateful(flat, state, key, cfg, drop_mask)

    # ---- the collective --------------------------------------------------- #

    def mean_flat(self, flat, key, cfg: t.CompressionConfig,
                  drop_mask=None):
        """Estimate mean(flat) over cfg.inner_axes + cfg.axes; must run
        inside shard_map.

        Two-level schedule (docs/DESIGN.md §11): the mean over the inner
        (fast) axes is exact — one pmean before the codec — and the codec
        round runs only across ``cfg.axes``, the slow link.  With empty
        ``inner_axes`` this is the historical flat round, op-for-op.

        ``drop_mask`` (DESIGN.md §14): optional traced (n,) 0/1 alive mask
        over the codec ranks of ``cfg.axes`` (1 = keep).  Dropped peers
        are excluded at decode time — their wire rows still travel (the
        collective shape is static), but the decode renormalizes over the
        kept rows per the ``partial_mean`` contract (NaN on all-dead).
        The mask is a traced operand: changing it never recompiles.  For
        hierarchical configs the drop unit is the cross-host peer — the
        inner (intra-host) pre-reduce is assumed healthy.
        """
        if cfg.inner_axes:
            with jax.named_scope("exchange"):
                flat = jax.lax.pmean(flat, cfg.inner_axes)
        return self._round(flat, key, cfg, drop_mask)

    def _round(self, flat, key, cfg: t.CompressionConfig, drop_mask=None):
        """One codec round across cfg.axes (input already inner-reduced).

        Gather codecs run the star protocol (§2/§4.4) — one all_gather of
        the packed buffer per call, decode locally.  "psum" codecs pmean
        the packed buffer and decode the reduced wire; with a drop mask
        the pmean becomes the mask-weighted partial mean of the packed
        buffers (their decode is affine in the wire values, so excluding
        a peer's buffer excludes its message).  Wrapper codecs (rotation,
        error feedback) override THIS hook, not the public entry points,
        so the inner-axes pre-reduce happens exactly once at the
        outermost layer.
        """
        d = flat.shape[0]
        with jax.named_scope("encode"):
            rank, n = axis_rank_size(cfg.axes)
            buf = self.pack(flat, key, rank, cfg)
        if self.reduce == "psum":
            with jax.named_scope("exchange"):
                if drop_mask is None:
                    wire = jax.lax.pmean(buf, cfg.axes)
                else:
                    keep = drop_mask[rank].astype(jnp.float32)
                    num = jax.lax.psum(buf.astype(jnp.float32) * keep,
                                       cfg.axes)
                    den = jax.lax.psum(keep, cfg.axes)
                    wire = (num / den).astype(buf.dtype)
            with jax.named_scope("decode"):
                return self.decode_reduced(wire, key, cfg, d)
        return self.gather_decode(buf, key, cfg, d, n, drop_mask)

    def _round_stateful(self, flat, state, key, cfg: t.CompressionConfig,
                        drop_mask=None):
        """Stateful companion of :meth:`_round` (input inner-reduced)."""
        return self._round(flat, key, cfg, drop_mask), state

    def gather_decode(self, buf, key, cfg: t.CompressionConfig,
                      d: int, n: int, drop_mask=None):
        """all_gather the packed buffer over cfg.axes and decode.

        With ``cfg.scatter_decode`` the decode is reduce-scattered over
        :func:`scatter_axes` — the inner axes when present (hierarchical,
        1/m shard each, shard gather rides the fast inner link) or the
        compression axes themselves (flat mesh, ⌈d/n⌉ shard each, shard
        gather billed by :meth:`scatter_bits`).  Each node decodes only
        its contiguous shard and one all_gather of decoded shards
        reassembles the estimate.  Shards concatenate in shard-rank order
        and pads sit past d, so the result equals the flat decode
        bit-for-bit.

        Decode policy (DESIGN.md §14): the plain averaging decode with no
        ``drop_mask`` keeps the codec's fused paths verbatim; a robust
        ``cfg.decode_policy`` or a mask routes through the per-peer row
        stack (:meth:`decode_rows` / :meth:`decode_rows_shard`) and
        :func:`repro.core.wire.robust.reduce_rows`.  The robust scatter
        branch applies the reduction per coordinate-shard — coordinate-
        wise order statistics partition exactly like the averaging
        decode, so the §12/§13 word-aligned shard windows survive and the
        composed result equals the flat robust decode bit-for-bit.
        """
        with jax.named_scope("exchange"):
            rows = gather_nested(buf, cfg.axes).reshape(n, buf.shape[0])
        kind, f = robust.parse_policy(cfg.decode_policy)
        if cfg.scatter_decode:
            saxes = scatter_axes(cfg)
            with jax.named_scope("decode"):
                shard, nshards = axis_rank_size(saxes)
                if kind == "mean" and drop_mask is None:
                    part = self.decode_gathered_shard(rows, key, cfg, d, n,
                                                      shard, nshards)
                else:
                    ds = scatter_shard_len(d, nshards,
                                           self.scatter_align(cfg))
                    stack = self.decode_rows_shard(rows, key, cfg, d, n,
                                                   shard * ds, ds, nshards)
                    with jax.named_scope("trim"):
                        part = robust.reduce_rows(stack, kind, f, drop_mask)
            with jax.named_scope("exchange"):
                full = gather_nested(part, saxes).reshape(-1)
                return full[:d]
        with jax.named_scope("decode"):
            return self.decode_rows_reduce(rows, key, cfg, d, n, drop_mask)

    def mean(self, x, key, cfg: t.CompressionConfig, drop_mask=None):
        """Shape/dtype-preserving wrapper around :meth:`mean_flat`."""
        shape, dtype = x.shape, x.dtype
        flat = x.reshape(-1).astype(jnp.float32)
        y = self.mean_flat(flat, key, cfg, drop_mask)
        return y.reshape(shape).astype(dtype)

    def __repr__(self):
        return f"<WireCodec {self.name} reduce={self.reduce}>"
