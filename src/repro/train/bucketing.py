"""Gradient bucketing: few flat collectives instead of one per pytree leaf.

The per-leaf gradient-sync rule (train_step.sync_grads) issues one
collective per pytree leaf — hundreds of tiny launches per step whose fixed
cost dwarfs the wire time for small leaves, while the paper's cost model
(§4–§6) only charges per communicated coordinate.  Suresh et al.
(arXiv:1611.00429) and DRIVE (arXiv:2105.08339) both operate on flat,
bucketized vectors for exactly this reason.

This module plans and executes that bucketing:

  * :func:`build_plan` — a *static* (host-side) partition of the grad tree
    into fixed-capacity f32 buckets.  Leaves are grouped by their sync
    signature — the mesh axes absent from their sharding spec, split into
    compressed axes (∩ cfg.axes, for leaves ≥ min_compress_size when a
    compression mode is on) and exact axes — and greedily packed in sorted
    name order.  Small leaves ride "exact" buckets (one plain psum-mean per
    bucket); a leaf larger than the capacity gets a dedicated oversize
    bucket (leaves are never split, so scatter is bit-exact).  The plan is
    a pure function of (abstract shapes, specs, mesh, config): identical
    across processes and across steps, which is what lets error-feedback
    state be keyed by bucket id.

  * :func:`pack_bucket` / :func:`unpack_bucket` — flatten leaves into the
    bucket's f32 vector and scatter results back to the original
    shapes/dtypes (bit-exact round trip for f32/bf16 grads: f32 holds
    every bf16 exactly).

  * :func:`sync_grads_bucketed` — the bucketed replacement for
    train_step.sync_grads: per bucket, pmean over the exact axes and one
    stateful codec round (encode → single fused collective → decode) over
    the compressed axes.  Error feedback is just the stateful codec case:
    the registry resolves an EF-wrapped codec
    (repro.core.wire.ef.EFCodec) and the per-bucket residuals come from
    :func:`init_ef_state`, whose shapes the resolved codec declares
    (``WireCodec.state_shape``).

  * :func:`overlap_params` — the *overlapped* issue schedule
    (``BucketSpec.overlap``, docs/DESIGN.md §9): instead of syncing the
    finished grad tree after backward, each bucket's leaves are wrapped in
    an identity sync point whose ``custom_vjp`` backward rule runs that
    bucket's :func:`_bucket_round`.  Differentiating the tagged params
    therefore emits every pack→collective→unpack *inside* the gradient
    computation, anchored only on its own leaves' cotangents — the bucket's
    collective becomes issuable the moment its last grad leaf exists
    (``Bucket.ready``) rather than after the whole loss graph.  The codec
    rounds and the ``fold_in`` chain are shared with
    :func:`sync_grads_bucketed` via :func:`_bucket_round`, so the two
    schedules agree bit-for-bit (enforced by tests/distributed_checks/
    overlap_check.py for stateless and stateful codecs alike).

Numerics vs the per-leaf path: identical for exact buckets (pmean is
elementwise, and mean-over-eaxes∘mean-over-caxes == mean over both); for
compressed buckets the estimate is the same protocol applied to the
concatenated vector — per-coordinate unbiasedness is unchanged (Lemmas
3.1/3.3 are coordinate-wise), only the node-center μ and the fixed-k
support are now drawn per bucket instead of per leaf.  Which wire format a
compressed bucket rides is decided by the codec registry
(repro.core.wire.registry.resolve — binary/ternary buckets land on packed
uint32 bit-plane buffers, §7.2-rotated configs on the composed rotated
codec): the per-bucket scalars (μ resp. vmin/vmax, c1/c2) are likewise
drawn per bucket, and :func:`bucket_wire_bits` charges each bucket the
resolved codec's exact gathered bits.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, Mapping, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import collectives as coll
from repro.core import types as t
from repro.core import wire


# --------------------------------------------------------------------------- #
# Plan data model.
# --------------------------------------------------------------------------- #

@dataclasses.dataclass(frozen=True)
class LeafSlot:
    """One leaf's placement inside a bucket (local, per-shard extents)."""

    name: str
    offset: int
    size: int
    shape: Tuple[int, ...]


@dataclasses.dataclass(frozen=True)
class Bucket:
    """A flat f32 aggregation unit: one collective per step.

    kind "exact": a single pmean over ``eaxes`` (``caxes`` is empty).
    kind "compressed": pmean over ``eaxes`` (if any), then compressed_mean
    over ``caxes``.

    ``ready`` is the bucket's slot in the readiness schedule: the
    backward-order index of its last-produced leaf.  Leaves are produced in
    backward in the reverse of their (canonical, sorted-name) forward
    order, so a bucket's grads are all available once the leaf with the
    largest backward index has been produced — that index is when the
    overlapped schedule (:func:`overlap_params`) can issue the bucket's
    collective.  Purely static metadata: it never enters the numerics (the
    PRNG chain folds the bucket's *plan position*, not its readiness).
    """

    bid: str
    kind: str                      # "exact" | "compressed"
    caxes: Tuple[str, ...]
    eaxes: Tuple[str, ...]
    slots: Tuple[LeafSlot, ...]
    size: int
    ready: int = -1


@dataclasses.dataclass(frozen=True)
class BucketPlan:
    buckets: Tuple[Bucket, ...]
    passthrough: Tuple[str, ...]   # leaves whose spec covers every mesh axis

    def leaf_names(self) -> Tuple[str, ...]:
        return tuple(sorted(
            list(self.passthrough)
            + [s.name for b in self.buckets for s in b.slots]))

    def schedule(self) -> Tuple[str, ...]:
        """Bucket ids in readiness order — the order the overlapped
        backward can issue their collectives (ties broken by bid so the
        schedule is deterministic)."""
        return tuple(b.bid for b in sorted(self.buckets,
                                           key=lambda b: (b.ready, b.bid)))


# --------------------------------------------------------------------------- #
# Plan construction (host-side, static).
# --------------------------------------------------------------------------- #

def leaf_sync_axes(spec, mesh_axes: Sequence[str]) -> Tuple[str, ...]:
    """Mesh axes absent from the leaf's spec — the unreduced X_i axes."""
    present = set()
    for s in spec:
        if s is None:
            continue
        for a in ((s,) if isinstance(s, str) else s):
            present.add(a)
    return tuple(a for a in mesh_axes if a not in present)


def local_shape(shape: Sequence[int], spec,
                mesh_sizes: Mapping[str, int]) -> Tuple[int, ...]:
    """Per-shard extents of a leaf inside shard_map (global ÷ spec axes)."""
    out = []
    for j, dim in enumerate(shape):
        s = spec[j] if j < len(spec) else None
        axes = () if s is None else ((s,) if isinstance(s, str) else tuple(s))
        q = 1
        for a in axes:
            q *= mesh_sizes.get(a, 1)
        if q > 1 and dim % q:
            raise ValueError(
                f"dim {dim} not divisible by sharding {axes} (= {q})")
        out.append(dim // q if q > 1 else dim)
    return tuple(out)


def _bucket_id(kind: str, caxes, eaxes, idx: int) -> str:
    return (f"{kind}:{'+'.join(caxes) if caxes else '-'}"
            f":{'+'.join(eaxes) if eaxes else '-'}:{idx}")


def build_plan(shapes: Mapping[str, Sequence[int]], specs: Mapping[str, tuple],
               mesh_axes: Sequence[str], mesh_sizes: Mapping[str, int],
               cmp: t.CompressionConfig) -> BucketPlan:
    """Partition a grad tree (given by *global* leaf shapes + specs) into
    buckets.  Deterministic: leaves are visited in sorted-name order and
    packed first-fit into the open bucket of their signature.  The plan —
    bucket ids, slot offsets AND the readiness schedule — is a pure
    function of the *sorted* (shapes, specs, mesh, config): shuffling the
    insertion order of the input mappings cannot change it (hypothesis
    property in tests/test_plan_stability.py), which is what lets EF state
    be keyed by bucket id and the overlap schedule agree across processes.

    Readiness: leaf backward order is the reverse of the canonical
    sorted-name order (model param names sort by layer, and backward
    produces grads in reverse layer order); ``Bucket.ready`` is the largest
    backward index over the bucket's slots — the point in backward at which
    its last grad leaf exists.
    """
    cap = cmp.bucket.capacity
    names = sorted(shapes)
    # backward production index per leaf: last forward leaf is produced
    # first in backward.
    bwd_index = {name: len(names) - 1 - i for i, name in enumerate(names)}
    open_slots: Dict[tuple, list] = {}
    open_fill: Dict[tuple, int] = {}
    counts: Dict[tuple, int] = {}
    buckets = []
    passthrough = []

    def close(sig):
        slots = open_slots.pop(sig)
        fill = open_fill.pop(sig)
        idx = counts.get(sig, 0)
        counts[sig] = idx + 1
        kind = sig[0]
        caxes, eaxes = sig[1], sig[2]
        ready = max(bwd_index[s.name] for s in slots)
        buckets.append(Bucket(_bucket_id(kind, caxes, eaxes, idx), kind,
                              caxes, eaxes, tuple(slots), fill, ready))

    for name in names:
        shp = shapes[name]
        shp = tuple(shp.shape) if hasattr(shp, "shape") else tuple(shp)
        lshape = local_shape(shp, specs[name], mesh_sizes)
        size = 1
        for d in lshape:
            size *= d
        axes = leaf_sync_axes(specs[name], mesh_axes)
        if not axes:
            passthrough.append(name)
            continue
        caxes = tuple(a for a in axes if a in cmp.axes)
        eaxes = tuple(a for a in axes if a not in cmp.axes)
        compressed = (bool(caxes) and cmp.mode != "none"
                      and size >= cmp.min_compress_size)
        if compressed:
            sig = ("compressed", caxes, eaxes)
        else:
            sig = ("exact", (), axes)  # one pmean over all sync axes
        fill = open_fill.get(sig, 0)
        if fill and fill + size > cap:
            close(sig)
            fill = 0
        open_slots.setdefault(sig, []).append(
            LeafSlot(name, fill, size, lshape))
        open_fill[sig] = fill + size

    for sig in list(open_slots):
        close(sig)
    return BucketPlan(tuple(buckets), tuple(passthrough))


def plan_for_run(aparams: Mapping[str, jax.ShapeDtypeStruct],
                 specs: Mapping[str, tuple], mesh_axes: Sequence[str],
                 mesh_sizes: Mapping[str, int],
                 cmp: t.CompressionConfig) -> Optional[BucketPlan]:
    """The plan the train step uses, or None when bucketing is disabled."""
    if not cmp.bucket.enabled:
        return None
    return build_plan(aparams, specs, mesh_axes, mesh_sizes, cmp)


# --------------------------------------------------------------------------- #
# Pack / scatter.
# --------------------------------------------------------------------------- #

def pack_bucket(grads: Mapping[str, jax.Array], bucket: Bucket) -> jax.Array:
    """Concatenate the bucket's leaves into one flat f32 vector."""
    return jnp.concatenate(
        [grads[s.name].reshape(-1).astype(jnp.float32)
         for s in bucket.slots])


def unpack_bucket(vec: jax.Array, bucket: Bucket,
                  like: Mapping[str, jax.Array]) -> Dict[str, jax.Array]:
    """Scatter a bucket vector back to leaf shapes/dtypes (from ``like``)."""
    out = {}
    for s in bucket.slots:
        g = jax.lax.slice_in_dim(vec, s.offset, s.offset + s.size)
        out[s.name] = g.reshape(s.shape).astype(like[s.name].dtype)
    return out


# --------------------------------------------------------------------------- #
# Wire accounting.
# --------------------------------------------------------------------------- #

def bucket_wire_bits(plan: BucketPlan, cfg: t.CompressionConfig,
                     n: int, mesh_sizes: Optional[Mapping[str, int]] = None
                     ) -> Dict[str, float]:
    """Gathered wire bits per compressed bucket and round, keyed by bid.

    Star-protocol payload convention (the one the paper's C sums and the
    PR-1 capacity-accounting checks use): n × the per-node wire buffer
    bits — exactly what the lowered HLO's collective result shape shows.
    Only defined for gather_decode wire paths; other modes return {}.

    The per-bucket bits come straight from the codec registry
    (``wire.resolve(cfg).wire_bits``) — the same dispatch rule
    sync_grads_bucketed executes, so accounting can never drift from the
    wire (dense-sim fallbacks are charged dense f32 bits; rotated
    compositions the inner codec's payload at the rotated length;
    error-feedback wraps delegate to their inner codec — residuals are
    local, so EF costs exactly what the wrapped codec costs).

    ``n`` is the flat world size over the compression axes; hierarchical
    configs (``cfg.inner_axes``) are billed at the cross-host group size
    (:func:`repro.core.wire.effective_nodes`), which requires
    ``mesh_sizes`` — only the messages that cross the slow link exist.
    """
    if cfg.mode != "gather_decode":
        return {}
    n_eff = wire.effective_nodes(cfg, n, mesh_sizes)
    codec = wire.resolve(cfg)
    # flat-scatter buckets (§12) additionally ship the rank-offset counts
    # and the decoded-shard gather on the same axes — billed by
    # scatter_bits (0 for every non-scatter / hierarchical config, whose
    # extra collectives ride the free inner link per the §11 convention).
    return {b.bid: float(codec.wire_bits(n_eff, b.size, cfg)
                         + codec.scatter_bits(n_eff, b.size, cfg))
            for b in plan.buckets if b.kind == "compressed"}


# --------------------------------------------------------------------------- #
# The bucketed gradient-sync rule.
# --------------------------------------------------------------------------- #

def ef_state_shapes(plan: BucketPlan,
                    cfg: t.CompressionConfig) -> Dict[str, Tuple[int, ...]]:
    """Codec state shapes per compressed bucket, keyed by bucket id.

    THE source of truth for the error-feedback residual pytree: the
    resolved codec declares its state (``WireCodec.state_shape``), so the
    train step, the dry-run lowering and the initializer can never drift
    from what ``sync_grads_bucketed`` actually threads.  Empty for
    stateless configurations.
    """
    out = {}
    for b in plan.buckets:
        if b.kind != "compressed":
            continue
        lcfg = _bucket_cfg(b, cfg, error_feedback=True)
        shp = wire.resolve(lcfg).state_shape(b.size, lcfg)
        if shp is not None:
            out[b.bid] = shp
    return out


def init_ef_state(plan: BucketPlan,
                  cfg: t.CompressionConfig) -> Dict[str, jax.Array]:
    """Zero codec state (EF residuals), one f32 buffer per compressed
    bucket — shapes derived from the resolved codec via
    :func:`ef_state_shapes` (this replaced the two hand-rolled residual
    initializers that used to live here and in core.error_feedback)."""
    return {bid: jnp.zeros(shp, jnp.float32)
            for bid, shp in ef_state_shapes(plan, cfg).items()}


def _bucket_cfg(b: Bucket, cmp: t.CompressionConfig, *,
                error_feedback: bool) -> t.CompressionConfig:
    """The per-bucket codec config: compression axes narrowed to the
    bucket's caxes and the hierarchical inner axes narrowed to the ones
    the bucket actually syncs over (its eaxes) — a leaf already sharded
    over an inner axis has no inner group to pre-reduce, and hierarchical
    scatter_decode degrades with it (nothing to scatter over).  A flat
    config (no inner axes to begin with) keeps its scatter_decode: the
    flat-mesh scatter (DESIGN.md §12) shards over the bucket's caxes."""
    inner = tuple(a for a in b.eaxes if a in cmp.inner_axes)
    return dataclasses.replace(
        cmp, axes=b.caxes, inner_axes=inner,
        scatter_decode=cmp.scatter_decode
        and (bool(inner) == bool(cmp.inner_axes)),
        error_feedback=error_feedback)


def _bucket_round(grads: Mapping[str, jax.Array], b: Bucket, j: int,
                  cmp: t.CompressionConfig, key, ef):
    """ONE bucket's sync: pack → (pmean / codec round) → unpack.

    THE shared body of both issue schedules — :func:`sync_grads_bucketed`
    runs it per bucket after backward, :func:`overlap_params` runs it
    inside each sync point's backward rule — so the two cannot drift: same
    ops, same ``fold_in(key, j)`` chain (j = the bucket's *plan position*,
    never its readiness), hence bit-identical estimates.  ``ef`` is the
    bucket's residual (engages the stateful EF-wrapped codec) or None.
    Returns (synced leaf dict, new residual or None).

    Hierarchical configs: the bucket's exact axes that are codec inner
    axes ride the codec round (the codec pre-reduces them and, with
    scatter_decode, all_gathers decoded shards over them); only the
    remaining exact axes get the standalone pmean here.  Flat configs take
    the historical path op-for-op.

    Device scopes (metadata only): ``grad_sync/b<j>`` around the bucket,
    and under it one per step: ``pack``, ``exact`` (the pmeans), ``codec``
    (the codec round, whose stages :mod:`repro.core.wire.base` names) and
    ``unpack``.
    """
    with jax.named_scope("grad_sync"), jax.named_scope(f"b{j}"):
        with jax.named_scope("pack"):
            v = pack_bucket(grads, b)
        if b.kind == "exact":
            with jax.named_scope("exact"):
                v = jax.lax.pmean(v, b.eaxes)
            with jax.named_scope("unpack"):
                return unpack_bucket(v, b, grads), ef
        lcfg = _bucket_cfg(b, cmp, error_feedback=ef is not None)
        pre = tuple(a for a in b.eaxes if a not in lcfg.inner_axes)
        if pre:
            with jax.named_scope("exact"):
                v = jax.lax.pmean(v, pre)
        kb = jax.random.fold_in(key, j)
        with jax.named_scope("codec"):
            if ef is not None:
                v, e = coll.compressed_mean_stateful(v, ef, kb, lcfg)
            else:
                v, e = coll.compressed_mean(v, kb, lcfg), None
        with jax.named_scope("unpack"):
            return unpack_bucket(v, b, grads), e


def sync_grads_bucketed(grads: Mapping[str, jax.Array], plan: BucketPlan,
                        cmp: t.CompressionConfig, key,
                        ef_state: Optional[Mapping[str, jax.Array]] = None):
    """Bucketed replacement for train_step.sync_grads (post-backward
    schedule; the overlapped schedule is :func:`overlap_params`).

    Must run inside shard_map with every mesh axis manual.  Returns
    (synced_grads, new_ef_state); new_ef_state is None iff ef_state is.
    Passing ``ef_state`` engages the error-feedback codec wrap (the
    registry resolves ``ef_*``); without it the plain codec runs.
    """
    out = {name: grads[name] for name in plan.passthrough}
    new_ef = {} if ef_state is not None else None
    for j, b in enumerate(plan.buckets):
        ef = (ef_state[b.bid]
              if ef_state is not None and b.kind == "compressed" else None)
        synced, e = _bucket_round(grads, b, j, cmp, key, ef)
        if ef is not None:
            new_ef[b.bid] = e
        out.update(synced)
    return out, new_ef


# --------------------------------------------------------------------------- #
# The overlapped issue schedule (BucketSpec.overlap; docs/DESIGN.md §9).
# --------------------------------------------------------------------------- #

def _sync_point(b: Bucket, j: int, cmp: t.CompressionConfig, stateful: bool):
    """A per-bucket identity whose backward rule IS the bucket's sync.

    Forward passes the bucket's leaves through untouched; the custom_vjp
    backward receives exactly those leaves' cotangents — available at the
    bucket's readiness point (``b.ready``), not after the full loss graph —
    and returns :func:`_bucket_round` of them.  The residual rides the
    ``ef`` argument: its "cotangent" is defined to be the bucket's new
    residual, so ``jax.grad`` w.r.t. the EF pytree returns the updated
    state (out-of-order bucket completion is safe by construction — each
    bucket's residual chain touches only its own slot; DESIGN.md §9).  The
    PRNG key's cotangent is the conventional float0 zero.
    """

    @jax.custom_vjp
    def tag(leaves, ef, key):
        return {n: leaves[n] for n in leaves}

    def fwd(leaves, ef, key):
        return {n: leaves[n] for n in leaves}, (ef, key)

    def bwd(res, g):
        ef, key = res
        synced, new_ef = _bucket_round(g, b, j, cmp, key,
                                       ef if stateful else None)
        if not stateful:
            new_ef = ef
        key_ct = np.zeros(jnp.shape(key), jax.dtypes.float0)
        return synced, new_ef, key_ct

    tag.defvjp(fwd, bwd)
    return tag


def overlap_params(params: Mapping[str, jax.Array], plan: BucketPlan,
                   cmp: t.CompressionConfig, key,
                   ef_state: Optional[Mapping[str, jax.Array]] = None):
    """Wrap the param tree with per-bucket sync points (overlap schedule).

    ``loss(overlap_params(p, ...))`` differentiates to the SAME synced
    grads :func:`sync_grads_bucketed` returns — bit-for-bit, every codec,
    stateful EF included — but each bucket's pack→collective→unpack is
    emitted *inside* the gradient computation, anchored only on that
    bucket's leaf cotangents, so it is issuable as soon as its last grad
    leaf exists instead of trailing the loss graph (HLO-verified by
    tests/distributed_checks/overlap_check.py).

    Usage (the train step's overlapped path)::

        def loss2(p, ef):
            return loss_fn(bucketing.overlap_params(p, plan, cmp, key, ef))
        (loss, aux), (grads, new_ef) = jax.value_and_grad(
            loss2, argnums=(0, 1), has_aux=True)(params, ef_state)

    With ``ef_state=None`` pass any pytree (e.g. ``{}``) as the second
    argument; its gradient is returned unchanged.  Passthrough leaves are
    left untagged — their grads flow through exactly as in the
    post-backward schedule.
    """
    tagged = dict(params)
    for j, b in enumerate(plan.buckets):
        stateful = (ef_state is not None and b.kind == "compressed"
                    and b.bid in ef_state)
        ef_b = ef_state[b.bid] if stateful else jnp.zeros((0,), jnp.float32)
        tag = _sync_point(b, j, cmp, stateful)
        sub = {s.name: params[s.name] for s in b.slots}
        tagged.update(tag(sub, ef_b, key))
    return tagged
