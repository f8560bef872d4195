"""Error feedback as a composable wire layer (docs/DESIGN.md §8).

:class:`EFCodec` wraps *any* registered wire codec the way
:class:`~repro.core.wire.rotated.RotatedCodec` wraps the §7.2 rotation:

    v_t   = x_t + e_t                       (residual-corrected input)
    wire  = twin_pack(v_t)                  (the inner codec's EXACT format)
    est_t = inner.decode(collective(wire))  (= mean_i m_i over the nodes)
    e_t+1 = v_t − inner.unpack(own wire)    (local; never transmitted)

so the estimate telescopes —  (1/T) Σ_t est_t = x̄ + (ē_0 − ē_T)/T  — and
constant inputs are recovered at rate 1/T with zero asymptotic bias, while
the wire payload is byte-identical to the un-wrapped codec (verified
against lowered HLO by tests/distributed_checks/ef_wire_check.py).

**Why a twin pack instead of delegating ``pack`` verbatim.**  EF is only
stable when the per-node message is *contractive*: ‖v − m(v)‖ must shrink
the centred energy.  The paper's encoders are unbiased *expansions* at
aggressive budgets (Lemma 3.2's (1/p − 1) factor): feeding their d/k- or
1/p-rescaled messages into the EF recursion provably diverges (the
residual picks up the (1/p − 1)-inflated noise each round —
tests/distributed_checks/collectives_check.py's ``ef.converges`` guards
exactly this).  Every inner codec therefore gets a *contractive twin*: a
message in the SAME wire format (same buffer layout, same slots, decoded
by the inner codec's unchanged ``unpack``) whose values are damped:

  * ``fixed_k`` / ``fixed_k_shared`` / ``bernoulli`` — the scale-1
    sparsifier: raw values on the sampled support, μ elsewhere.  This is
    the induced contraction of the unbiased encoder (damping the centred
    message by η = 1/(1 + ω) with ω = 1/p − 1 gives exactly scale 1):
    ‖v − m‖² = Σ_{j∉S} (v_j − μ)² ≤ ‖v − μ1‖², deterministically.
  * ``binary`` — Seide et al.'s 1-bit compressor: deterministic threshold
    at mean(v), cluster means in the two tail slots.  Within-cluster SS ≤
    SS around the mean, so ‖v − m‖ ≤ ‖v − v̄1‖ deterministically (the
    *stochastic* binary quantizer's variance exceeds the centred energy by
    ~2·log d on Gaussian-ish data — divergent under EF).
  * ``ternary`` / ``ternary_opt`` — deterministic hybrid: the ``cap``
    largest-|v − v̄| coordinates pass through exactly (the value segment is
    filled to capacity, never overflows), the rest 2-means like binary.
  * ``dense`` — the same rules applied densely, dispatched on the encoder
    kind.
  * ``rotated_*`` — rotate first, then the twin of the rotated codec's
    inner: EF∘rotation composes with the residual kept in model space.

Residuals absorb *all* local reconstruction error — wire-dtype rounding
and capacity-overflow drops included — because e' is computed from the
inner codec's own ``unpack`` of the bytes actually shipped.

Accounting delegates verbatim (wire_slots/wire_bits/seed_bits/cost_spec),
so ``comm_cost_bits == wire_bits + seed_bits`` holds by construction for
every wrapped codec, and ``bucket_wire_bits`` needs no EF special case.

Composition order: ``registry.resolve`` builds EF *outermost*
(EF∘rotation), which keeps the residual in model coordinates where the
telescoping identity is exact.  The reverse order
RotatedCodec(EFCodec(...)) also composes mechanically (RotatedCodec
forwards codec state), but its residual lives in the per-step-reseeded
rotated basis, where the telescoping holds only in expectation over the
rotations — see docs/DESIGN.md §8.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core import bitplane
from repro.core import encoders
from repro.core import rotation
from repro.core.wire import base, codecs, rotated
from repro.kernels import numerics


# --------------------------------------------------------------------------- #
# Contractive twin messages, one per inner wire format.  Every helper emits
# a buffer in the inner codec's exact layout; the inner ``unpack`` decodes it.
# --------------------------------------------------------------------------- #

def _two_means(v, select=None):
    """One deterministic 2-means step: threshold at the (selected) mean.

    Returns (c_lo, c_hi, hi_mask).  ``select`` restricts the clustering to a
    subset (the ternary twin's non-pass coordinates); excluded coordinates
    get an arbitrary side of the threshold and must be overwritten by the
    caller.  Cluster means minimize the within-cluster SS, so the decoded
    message m = hi ? c_hi : c_lo satisfies ‖v − m‖ ≤ ‖v − v̄1‖ (restricted
    to ``select``) — the deterministic contraction EF needs.
    """
    if select is None:
        select = jnp.ones(v.shape, bool)
    cnt = jnp.maximum(jnp.sum(select.astype(jnp.float32)), 1.0)
    thr = numerics.div_rn(numerics.ordered_sum(jnp.where(select, v, 0.0)),
                          cnt)
    hi = v >= thr
    n_hi = jnp.sum((select & hi).astype(jnp.float32))
    n_lo = jnp.sum((select & ~hi).astype(jnp.float32))
    c_hi = jnp.where(n_hi > 0,
                     numerics.div_rn(numerics.ordered_sum(
                         jnp.where(select & hi, v, 0.0)),
                         jnp.maximum(n_hi, 1.0)), thr)
    c_lo = jnp.where(n_lo > 0,
                     numerics.div_rn(numerics.ordered_sum(
                         jnp.where(select & ~hi, v, 0.0)),
                         jnp.maximum(n_lo, 1.0)), thr)
    return c_lo, c_hi, hi


def _fixed_k_twin(flat, key, rank, cfg, shared: bool):
    """Scale-1 fixed-k: [v − μ on support ‖ μ] — unpack gives v / μ."""
    kids = key if shared else jax.random.fold_in(key, rank)
    return codecs.fixed_k_pack(flat, kids, cfg, scale=1.0)


def _bernoulli_twin(flat, key, rank, cfg):
    """Scale-1 Bernoulli: raw values at their support-rank slots + μ tail."""
    return codecs.bernoulli_buffer(flat, key, rank, cfg, scaled=False)


def _wire_round(x, wire_dtype):
    """The exact value a float takes after the floats_to_words →
    words_to_floats wire round trip: identity at r = 32, round-through-
    the-wire-dtype at r = 16 (bit-equal to the bitcast pack/unpack pair
    by construction — both are ``astype(wire_dtype)`` then widen)."""
    x = jnp.asarray(x, jnp.float32)
    if bitplane.wire_bits(wire_dtype) == 32:
        return x
    return x.astype(wire_dtype).astype(jnp.float32)


def _binary_twin(flat, cfg):
    """Seide 1-bit: mean-threshold plane + the two cluster means as tail.

    Returns (buf, recon) with recon bit-for-bit ``binary_unpack(buf)`` —
    derived from the twin's own mask + centers through the wire-rounding
    identity, so the EF residual skips the plane unpack round trip
    (DESIGN.md §13).
    """
    c_lo, c_hi, hi = _two_means(flat)
    buf = bitplane.binary_words(hi, c_lo, c_hi, cfg.wire_dtype)
    recon = jnp.where(hi, _wire_round(c_hi, cfg.wire_dtype),
                      _wire_round(c_lo, cfg.wire_dtype))
    return buf, recon


def _ternary_twin(flat, cap, cfg):
    """Deterministic ternary: top-cap |v − v̄| pass through exactly, the
    rest 2-means.  Fills the value segment to capacity — no overflow.

    Returns (buf, recon) with recon bit-for-bit ``ternary_unpack(buf)``:
    the value segment is filled to capacity in support-rank order, so
    every pass-through slot is valid (the overflow fallback is
    unreachable) and the reconstruction is just the pass/branch select
    through the wire rounding — no plane unpack, no rank cumsum.
    """
    d = flat.shape[0]
    cap = min(cap, d)
    dev = jnp.abs(flat - numerics.ordered_mean(flat))
    # same membership as top_k(dev, cap) (ties → lowest index) but via the
    # O(d)-per-pass bit bisection — top_k was the ef_ternary pack hot spot.
    passm = bitplane.topcap_mask(dev, cap)
    c_lo, c_hi, hi = _two_means(flat, select=~passm)
    sym = jnp.where(passm, 2, jnp.where(hi, 1, 0)).astype(jnp.uint32)
    vbuf = bitplane.rank_scatter(flat, passm, cap)
    buf = bitplane.ternary_words(sym, vbuf, c_lo, c_hi, cfg.wire_dtype)
    wd = cfg.wire_dtype
    recon = jnp.where(passm, _wire_round(flat, wd),
                      jnp.where(hi, _wire_round(c_hi, wd),
                                _wire_round(c_lo, wd)))
    return buf, recon


def _dense_twin(flat, key, rank, cfg):
    """Dense contractive message, dispatched on the encoder kind."""
    kind = cfg.encoder.kind
    if kind == "identity":
        return flat.astype(jnp.float32)
    if kind == "binary":
        c_lo, c_hi, hi = _two_means(flat)
        return jnp.where(hi, c_hi, c_lo).astype(jnp.float32)
    if kind == "ternary":
        d = flat.shape[0]
        k = max(1, min(d, int(round(float(cfg.encoder.fraction) * d))))
        dev = jnp.abs(flat - numerics.ordered_mean(flat))
        _, top = jax.lax.top_k(dev, k)
        passm = jnp.zeros((d,), bool).at[top].set(True)
        c_lo, c_hi, hi = _two_means(flat, select=~passm)
        return jnp.where(passm, flat,
                         jnp.where(hi, c_hi, c_lo)).astype(jnp.float32)
    # Eq. (1) family (bernoulli / fixed_k, any probs policy): raw values on
    # the sampled support, center elsewhere — the per-coordinate induced
    # contraction (1 − p_j per coordinate).
    enc = encoders.encode(jax.random.fold_in(key, rank), flat, cfg.encoder)
    return jnp.where(enc.support, flat, enc.mu).astype(jnp.float32)


def _twin_pack(codec, flat, key, rank, cfg):
    """The contractive message for ``codec``, in its exact wire format.

    Extension point: a codec outside this module may define
    ``ef_twin_pack(flat, key, rank, cfg)`` (and ``ef_residual_bound``) to
    declare its own contractive twin — checked first, so new protocols
    compose with EF without this dispatch learning about them.
    """
    hook = getattr(codec, "ef_twin_pack", None)
    if hook is not None:
        return hook(flat, key, rank, cfg)
    if isinstance(codec, rotated.RotatedCodec):
        z = rotation.rotate(rotation.rotation_key(key), flat)
        return _twin_pack(codec.inner, z, key, rank, cfg)
    if isinstance(codec, codecs.FixedKGatherCodec):
        return _fixed_k_twin(flat, key, rank, cfg, shared=False)
    if isinstance(codec, codecs.FixedKSharedCodec):
        return _fixed_k_twin(flat, key, rank, cfg, shared=True)
    if isinstance(codec, codecs.BernoulliCodec):
        return _bernoulli_twin(flat, key, rank, cfg)
    if isinstance(codec, codecs.TernaryCodec):  # incl. TernaryOptCodec
        return _ternary_twin(flat, codec._cap(flat.shape[0], cfg), cfg)[0]
    if isinstance(codec, codecs.BinaryCodec):
        return _binary_twin(flat, cfg)[0]
    if isinstance(codec, codecs.DenseSimCodec):
        return _dense_twin(flat, key, rank, cfg)
    raise ValueError(
        f"error feedback has no contractive twin for codec {codec.name!r}; "
        "define ef_twin_pack/ef_residual_bound on the codec or leave "
        "error_feedback off for it")


def _twin_pack_recon(codec, flat, key, rank, cfg):
    """(wire buffer, local reconstruction) for the contractive twin.

    ``recon`` is bit-for-bit ``codec.unpack(buf, rank, key, cfg, d)``.
    For the plane codecs it is derived from the twin's own intermediates
    (mask + centers + pass values through :func:`_wire_round`) — skipping
    the plane unpack round trip that was the ef_rotated_binary hot spot —
    and the rotated wrapper recurses in rotated space with ONE inverse
    FWHT at the end.  Codecs without a fused twin recon fall back to
    pack + unpack, the historical op sequence.  Residual semantics are
    unchanged either way (golden wire bytes depend on the round-t residual
    and stay pinned).
    """
    hook = getattr(codec, "ef_twin_pack", None)
    if hook is not None:
        buf = hook(flat, key, rank, cfg)
        return buf, codec.unpack(buf, rank, key, cfg, flat.shape[0])
    if isinstance(codec, rotated.RotatedCodec):
        with jax.named_scope("rotate"):
            krot = rotation.rotation_key(key)
            z = rotation.rotate(krot, flat)
        buf, rz = _twin_pack_recon(codec.inner, z, key, rank, cfg)
        with jax.named_scope("unrotate"):
            return buf, rotation.unrotate(krot, rz, flat.shape[0])
    if isinstance(codec, codecs.TernaryCodec):  # incl. TernaryOptCodec
        return _ternary_twin(flat, codec._cap(flat.shape[0], cfg), cfg)
    if isinstance(codec, codecs.BinaryCodec):
        return _binary_twin(flat, cfg)
    buf = _twin_pack(codec, flat, key, rank, cfg)
    return buf, codec.unpack(buf, rank, key, cfg, flat.shape[0])


def twin_recon_fused(codec) -> bool:
    """True iff the EF twin for inner ``codec`` derives its reconstruction
    from encode-side intermediates (no plane unpack round trip)."""
    if isinstance(codec, rotated.RotatedCodec):
        return twin_recon_fused(codec.inner)
    return isinstance(codec, (codecs.BinaryCodec, codecs.TernaryCodec))


def twin_recon(codec, flat, key, rank, cfg):
    """The EF residual reconstruction m(v) for inner ``codec``.

    Bench/test entry point for the production residual path: bit-equal to
    ``codec.unpack`` of the shipped twin buffer (pinned by
    tests/test_wire_registry.py), collective-free.
    """
    return _twin_pack_recon(codec, flat, key, rank, cfg)[1]


def _twin_bound(codec, flat, key, cfg):
    """Deterministic bound on ‖v − m(v)‖ for the twin message of ``codec``
    (tests/test_wire_registry.py's hypothesis property; f32 wire)."""
    hook = getattr(codec, "ef_residual_bound", None)
    if hook is not None:
        return hook(flat, key, cfg)
    if isinstance(codec, rotated.RotatedCodec):
        z = rotation.rotate(rotation.rotation_key(key), flat)
        return _twin_bound(codec.inner, z, key, cfg)
    if isinstance(codec, (codecs.FixedKGatherCodec, codecs.FixedKSharedCodec,
                          codecs.BernoulliCodec)):
        mu = base.center(flat, cfg.encoder.center)
        return jnp.linalg.norm(flat - mu)
    if isinstance(codec, codecs.DenseSimCodec) and \
            cfg.encoder.kind in ("bernoulli", "fixed_k"):
        enc = encoders.encode(jax.random.fold_in(key, 0), flat, cfg.encoder)
        return jnp.linalg.norm(flat - enc.mu)
    if isinstance(codec, codecs.DenseSimCodec) and \
            cfg.encoder.kind == "identity":
        return jnp.zeros(())
    # binary / ternary twins: within-cluster SS ≤ SS around the mean.
    return jnp.linalg.norm(flat - jnp.mean(flat))


# --------------------------------------------------------------------------- #
# The wrapper codec.
# --------------------------------------------------------------------------- #

class EFCodec(base.WireCodec):
    """Error feedback composed over any inner codec (residual state local)."""

    stateful = True

    def __init__(self, inner: base.WireCodec):
        if inner.stateful:
            raise ValueError("error feedback does not nest over a stateful "
                             f"codec ({inner.name})")
        self.inner = inner
        self.name = "ef_" + inner.name
        self.reduce = inner.reduce
        self.scatter_supported = inner.scatter_supported

    # ---- geometry & accounting: delegated verbatim ------------------------ #
    # The residual never touches the wire, so the payload IS the inner
    # codec's payload and the §4 accounting identity holds by construction.

    def wire_slots(self, d, cfg):
        return self.inner.wire_slots(d, cfg)

    def wire_bits(self, n, d, cfg):
        return self.inner.wire_bits(n, d, cfg)

    def seed_bits(self, n, cfg):
        return self.inner.seed_bits(n, cfg)

    def cost_spec(self, d, cfg):
        return self.inner.cost_spec(d, cfg)

    def comm_cost_bits(self, n, d, cfg):
        return self.inner.comm_cost_bits(n, d, cfg)

    def scatter_bits(self, n, d, cfg):
        return self.inner.scatter_bits(n, d, cfg)

    # ---- wire format: twin pack, inner decode ----------------------------- #

    def pack(self, flat, key, rank, cfg):
        """The contractive twin of the inner codec's message for ``flat``.

        ``flat`` is the residual-corrected vector v = x + e; the residual
        addition itself happens in :meth:`mean_flat_stateful`.
        """
        return _twin_pack(self.inner, flat, key, rank, cfg)

    def unpack(self, row, peer, key, cfg, d):
        return self.inner.unpack(row, peer, key, cfg, d)

    def decode_gathered(self, rows, key, cfg, d, n):
        return self.inner.decode_gathered(rows, key, cfg, d, n)

    def decode_gathered_shard(self, rows, key, cfg, d, n, shard, nshards):
        return self.inner.decode_gathered_shard(rows, key, cfg, d, n,
                                                shard, nshards)

    def decode_reduced(self, wire, key, cfg, d):
        return self.inner.decode_reduced(wire, key, cfg, d)

    def scatter_align(self, cfg):
        return self.inner.scatter_align(cfg)

    def gather_decode(self, buf, key, cfg, d, n, drop_mask=None):
        # full delegation (not just the decode hooks): RotatedCodec owns
        # its scatter decomposition inside gather_decode — shards live in
        # rotated space at the padded length — so EF hands the whole
        # gather+decode to the inner codec instead of re-running base's
        # scatter branch at the model d.  For non-rotated inners this is
        # op-for-op the base implementation.  Robust decode policies and
        # drop masks (§14) delegate the same way — the reduction runs over
        # the inner codec's reconstructions of the twin rows; a dropped
        # peer's residual stays local to that peer and re-enters through
        # its own future messages, so exclusion at decode time loses no
        # mass permanently.
        return self.inner.gather_decode(buf, key, cfg, d, n, drop_mask)

    def decode_rows_reduce(self, rows, key, cfg, d, n, drop_mask=None):
        return self.inner.decode_rows_reduce(rows, key, cfg, d, n,
                                             drop_mask)

    # ---- the stateful round ----------------------------------------------- #

    def state_shape(self, d, cfg):
        return (d,)

    def residual_bound(self, flat, key, cfg):
        """Deterministic bound on one zero-residual EF step's new residual:
        ‖e'‖ = ‖flat − m(flat)‖ ≤ the inner twin's worst-case per-step
        error (f32 wire; wire-dtype rounding adds its quantization noise).
        """
        return _twin_bound(self.inner, flat, key, cfg)

    def _round_stateful(self, flat, state, key, cfg, drop_mask=None):
        """One EF round: (estimate, new_residual); must run in shard_map.

        The new residual is v minus the reconstruction of the bytes this
        node actually shipped (bit-equal to the inner codec's ``unpack``
        of them, but derived from the twin's own intermediates where the
        format allows — :func:`_twin_pack_recon`), so wire-dtype rounding
        and capacity-overflow drops are recycled too, not just
        sparsification.
        Under the hierarchical schedule ``flat`` arrives already
        inner-reduced (base.mean_flat*), so the residual tracks the
        cross-host message — the only lossy step.
        """
        d = flat.shape[0]
        with jax.named_scope("encode"):
            rank, n = base.axis_rank_size(cfg.axes)
        with jax.named_scope("ef"):
            v = flat + state
        with jax.named_scope("encode"):
            buf, recon = _twin_pack_recon(self.inner, v, key, rank, cfg)
        if self.reduce == "psum":
            with jax.named_scope("exchange"):
                if drop_mask is None:
                    wire = jax.lax.pmean(buf, cfg.axes)
                else:
                    # masked weighted psum, mirroring base._round: dropped
                    # peers contribute zero to both numerator and count.
                    keep = drop_mask[rank].astype(jnp.float32)
                    num = jax.lax.psum(buf.astype(jnp.float32) * keep,
                                       cfg.axes)
                    den = jax.lax.psum(keep, cfg.axes)
                    wire = (num / den).astype(buf.dtype)
            with jax.named_scope("decode"):
                est = self.inner.decode_reduced(wire, key, cfg, d)
        else:
            est = self.gather_decode(buf, key, cfg, d, n, drop_mask)
        with jax.named_scope("ef"):
            return est, v - recon

    def _round(self, flat, key, cfg, drop_mask=None):
        """Stateless round: zero residual, state discarded.

        Keeps EF configs usable by payload/HLO measurements and benchmarks
        that lower ``compressed_mean``; training threads real residuals via
        ``compressed_mean_stateful``.
        """
        y, _ = self._round_stateful(flat, jnp.zeros_like(flat), key, cfg,
                                    drop_mask)
        return y
