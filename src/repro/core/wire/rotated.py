"""Composable §7.2 pre-transform: seeded per-bucket Hadamard rotation.

:class:`RotatedCodec` wraps *any* registered :class:`~repro.core.wire.base
.WireCodec`: the bucket vector is rotated once by Q = (1/√d)HD before the
inner codec encodes, and the averaging decode is unrotated once at the end
— valid because averaging commutes with the (linear, orthogonal) Q, so

    E‖Qᵀ z̄ − X̄‖² = E‖z̄ − Q X̄‖²,

i.e. conditional on the rotation seed the composed protocol's MSE is the
inner codec's closed form evaluated at the rotated data (the §7.2
composition rule; see repro.core.mse.mse_rotated).  Rotation spreads the
information of spiky/anisotropic vectors evenly across coordinates, which
is exactly the regime where the min/max-bracketed quantizers (binary,
ternary) and uniform-support sparsifiers are at their worst — this is the
backbone of Suresh et al.'s rotated one-bit estimator and of DRIVE.

Wire overhead is **seed-only**: Q is identified by one shared seed derived
from the per-bucket key (rotation.rotation_key), which every peer already
holds — the SPMD analogue of the §4.4 seed trick.  The gathered payload is
therefore exactly the inner codec's buffer at the rotated length
``rotation.padded_dim(d)`` (== d's next power of two; equal to d whenever
d is already a power of two), which tests verify against the lowered HLO.
The analytic §4 cost adds one r̄_s seed term per node, mirroring how
Eq. (9)/(10) charge the support seeds that likewise never travel here.

Reduce kind is inherited: the rotation composes with gather codecs
(rotate → pack → all_gather → decode → unrotate) and with psum codecs
(rotate → psum wire → decode → unrotate) alike.
"""
from __future__ import annotations

import jax

from repro.core import rotation
from repro.core import types as t
from repro.core.wire import base
from repro.kernels.rotated_encode import ops as ro_ops


class RotatedCodec(base.WireCodec):
    """The inner codec applied in the rotated basis z = Qx (§7.2)."""

    def __init__(self, inner: base.WireCodec):
        if isinstance(inner, RotatedCodec):
            raise ValueError("rotation pre-transform does not nest")
        self.inner = inner
        self.name = "rotated_" + inner.name
        self.reduce = inner.reduce
        # codec state (e.g. a wrapped EFCodec's residual) is forwarded, so
        # rotation∘EF compositions thread their state through the rotation.
        self.stateful = inner.stateful
        # the rotated decode partitions iff the inner one does (the
        # unrotate happens on the reassembled estimate, outside the shards).
        self.scatter_supported = inner.scatter_supported

    # ---- geometry & accounting: the inner codec at padded_dim(d) ---------- #

    def wire_slots(self, d, cfg):
        return self.inner.wire_slots(rotation.padded_dim(d), cfg)

    def wire_bits(self, n, d, cfg):
        # HLO-exact: the gathered payload IS the inner buffer at dp — the
        # rotation itself ships nothing (seed-only overhead).
        return self.inner.wire_bits(n, rotation.padded_dim(d), cfg)

    def seed_bits(self, n, cfg):
        return (self.inner.seed_bits(n, cfg)
                + float(n * t.DEFAULT_RSEED_BITS))

    def cost_spec(self, d, cfg):
        return self.inner.cost_spec(rotation.padded_dim(d), cfg)

    def scatter_bits(self, n, d, cfg):
        # a flat scatter decode shards the ROTATED estimate, so the shard
        # gather bytes are the inner codec's at the padded length.
        return self.inner.scatter_bits(n, rotation.padded_dim(d), cfg)

    def comm_cost_bits(self, n, d, cfg):
        # inner analytic cost at the rotated length + the rotation seed
        # (r̄_s per node in the faithful star protocol; regenerated from
        # the shared key on SPMD hardware, like the §4.4 support seeds).
        return (self.inner.comm_cost_bits(n, rotation.padded_dim(d), cfg)
                + float(n * t.DEFAULT_RSEED_BITS))

    # ---- wire format: rotate before pack, unrotate after decode ----------- #

    def pack(self, flat, key, rank, cfg):
        if self.inner.name == "binary":
            # fused rotate+encode: one kernel pair instead of
            # FWHT / min-max / threshold / pack round trips on TPU; the
            # dispatcher falls back to exactly the chain below off-TPU
            # (repro.kernels.rotated_encode).
            return ro_ops.pack_binary(flat, key, rank, cfg.wire_dtype)
        with jax.named_scope("rotate"):
            z = rotation.rotate(rotation.rotation_key(key), flat)
        return self.inner.pack(z, key, rank, cfg)

    def unpack(self, row, peer, key, cfg, d):
        dp = rotation.padded_dim(d)
        z = self.inner.unpack(row, peer, key, cfg, dp)
        return _unrotate(key, z, d)

    def decode_gathered(self, rows, key, cfg, d, n):
        # unrotate once, after the averaging decode (linearity of Q).
        dp = rotation.padded_dim(d)
        zbar = self.inner.decode_gathered(rows, key, cfg, dp, n)
        return _unrotate(key, zbar, d)

    def scatter_align(self, cfg):
        return self.inner.scatter_align(cfg)

    def gather_decode(self, buf, key, cfg, d, n, drop_mask=None):
        # Rotated decodes scatter in ROTATED space (DESIGN.md §13): the
        # unrotated estimate is not coordinate-partitionable (every output
        # coordinate mixes all of z̄), so the shard decomposition — shard
        # decode, reassembling all_gather, truncation — runs entirely
        # inside the inner codec at the padded length, and the single
        # inverse rotation is applied to the reassembled z̄.  Flat-decode
        # configs take the exact historical op sequence through the same
        # delegation.  Robust decode policies and drop masks (§14) ride
        # the same delegation: the coordinate-wise reduction happens in
        # ROTATED space — trimming per rotated coordinate, where the §7.2
        # rotation has spread any coordinate-aligned outlier energy — and
        # the single inverse rotation maps the robust estimate back.
        dp = rotation.padded_dim(d)
        zbar = self.inner.gather_decode(buf, key, cfg, dp, n, drop_mask)
        return _unrotate(key, zbar, d)

    def decode_rows_reduce(self, rows, key, cfg, d, n, drop_mask=None):
        # collective-free policy decode: the reduction runs in rotated
        # space at the padded length (same convention as gather_decode).
        dp = rotation.padded_dim(d)
        zbar = self.inner.decode_rows_reduce(rows, key, cfg, dp, n,
                                             drop_mask)
        return _unrotate(key, zbar, d)

    def decode_reduced(self, wire, key, cfg, d):
        dp = rotation.padded_dim(d)
        zbar = self.inner.decode_reduced(wire, key, cfg, dp)
        return _unrotate(key, zbar, d)

    # ---- codec state: forwarded in the rotated basis ---------------------- #

    def state_shape(self, d, cfg):
        return self.inner.state_shape(rotation.padded_dim(d), cfg)

    def _round_stateful(self, flat, state, key, cfg, drop_mask=None):
        # The state lives in the (per-step-reseeded) rotated basis — see
        # docs/DESIGN.md §8 for why EF∘rotation (EF outermost, as built by
        # registry.resolve) is the production order.  Overriding the
        # _round hooks (not mean_flat*) keeps the hierarchical inner-axes
        # pre-reduce at the one public entry point; delegating to the
        # inner codec's _round at the padded length dp means the
        # scatter-decode decomposition, when on, shards the ROTATED
        # estimate and reassembles all dp coordinates before unrotating.
        d = flat.shape[0]
        with jax.named_scope("rotate"):
            krot = rotation.rotation_key(key)
            z = rotation.rotate(krot, flat)
        zbar, new_state = self.inner._round_stateful(z, state, key, cfg,
                                                     drop_mask)
        with jax.named_scope("unrotate"):
            return rotation.unrotate(krot, zbar, d), new_state

    def _round(self, flat, key, cfg, drop_mask=None):
        d = flat.shape[0]
        with jax.named_scope("rotate"):
            krot = rotation.rotation_key(key)
            z = rotation.rotate(krot, flat)
        zbar = self.inner._round(z, key, cfg, drop_mask)
        with jax.named_scope("unrotate"):
            return rotation.unrotate(krot, zbar, d)


def _unrotate(key, z, d: int):
    """The inverse rotation of an estimate, under the ``unrotate`` scope
    (the rotation key's derivation included)."""
    with jax.named_scope("unrotate"):
        return rotation.unrotate(rotation.rotation_key(key), z, d)
