"""Distributed train step: shard_map (all axes manual) + microbatch
accumulation + per-leaf gradient synchronization with the paper's
compressed mean estimation.

Gradient-sync rule (DESIGN.md §4): after backward, a leaf's gradient is
already correct across every mesh axis that appears in its sharding spec
(TP/EP collectives transpose to the right reductions; FSDP all_gathers
transpose to exact in-data reduce_scatters).  The axes *absent* from the
spec still hold unreduced per-replica contributions — exactly the paper's
X_i.  Those axes are synchronized by:

  * compressed_mean (encode → collective → decode) on axes ∩ cfg.axes for
    leaves ≥ min_compress_size — the paper's technique on the wire, with
    the wire format resolved by the codec registry (repro.core.wire:
    fixed-k / Bernoulli seed-trick / packed bit-planes / §7.2-rotated
    compositions, per the config's encoder);
  * exact psum-mean on the remainder (small leaves, non-selected axes).

By default the rule executes *bucketed* (repro.train.bucketing, enabled by
cmp.bucket): leaves sharing a sync signature are packed into a few flat
f32 buckets and the step issues one collective per bucket instead of one
per leaf; sync_grads below is the per-leaf reference path (bucket.enabled
= False), kept for A/B tests and as executable documentation of the rule.

Issue schedule (docs/DESIGN.md §9): with ``cmp.bucket.overlap`` (default
ON) and no microbatch accumulation, the bucketed sync is *pipelined into
backward* — the step differentiates the loss of
``bucketing.overlap_params(params, ...)``, whose per-bucket sync points
emit each pack→collective→unpack inside the gradient computation at the
bucket's readiness point (``Bucket.ready``).  Overlapped and
post-backward schedules are bit-identical by construction (same codec
rounds, same fold_in chain); ``microbatches > 1`` always syncs the
accumulated grads after the scan (compressed codecs are nonlinear — one
codec round per step is the contract, not one per microbatch).
"""
from __future__ import annotations

import dataclasses
import functools
import logging
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs.base import SHAPES, ArchConfig, RunConfig, ShapeSpec
from repro.core import collectives as coll
from repro.core import types as core_types
from repro.models import model as model_lib
from repro.models.common import ShardCtx
from repro.optim import optimizers as opt_lib
from repro.train import bucketing

log = logging.getLogger("repro.train_step")


# --------------------------------------------------------------------------- #
# Spec plumbing.
# --------------------------------------------------------------------------- #

def spec_to_pspec(spec) -> P:
    return P(*spec)


def mesh_sizes_of(mesh) -> Dict[str, int]:
    return dict(zip(mesh.axis_names, mesh.devices.shape))


def abstract_specs(key, cfg: ArchConfig, ctx: ShardCtx, mesh_sizes, run):
    """Param spec tree (+ global ShapeDtypeStructs) without device state."""
    return model_lib.init(key, cfg, ctx, mesh_sizes, run, abstract=True)


def grad_sync_plan(mesh, run: RunConfig, aparams, specs):
    """The BucketPlan the train step will sync with (None = per-leaf path).

    Single source of truth for the plan derivation: build_train_step and
    launch/dryrun (which must mirror the step's ef_state pytree when
    lowering) both call this with the same abstract tree.
    """
    return bucketing.plan_for_run(aparams, specs, tuple(mesh.axis_names),
                                  mesh_sizes_of(mesh), run.compression)


def overlap_enabled(plan, run: RunConfig) -> bool:
    """THE eligibility rule for the backward-pipelined issue schedule.

    One predicate shared by the step builder, the Trainer and the dry-run
    record so they can never disagree about which schedule the lowered
    step executes: bucketed sync + the overlap knob + a single backward
    (grad accumulation must run its one codec round on the accumulated
    grads after the scan — DESIGN.md §9).
    """
    return (plan is not None and run.compression.bucket.overlap
            and run.microbatches == 1)


# --------------------------------------------------------------------------- #
# Batch sharding.
# --------------------------------------------------------------------------- #

def batch_axes_for(cfg: ArchConfig, run: RunConfig, shape: ShapeSpec,
                   mesh_sizes: Dict[str, int]) -> Tuple[str, ...]:
    """Largest prefix of candidate axes whose product divides global_batch."""
    if run.model_parallel:
        cands = [a for a in ("pod", "data") if a in mesh_sizes]
    else:
        cands = [a for a in ("data", "model") if a in mesh_sizes]
    chosen = []
    prod = 1
    for a in cands:
        if shape.global_batch % (prod * mesh_sizes[a]) == 0:
            chosen.append(a)
            prod *= mesh_sizes[a]
    return tuple(chosen)


def batch_pspec(cfg: ArchConfig, baxes) -> Dict[str, P]:
    tok = P(baxes if baxes else None)
    out = {"tokens": tok, "labels": tok, "mask": tok}
    if cfg.family == "vlm":
        out["patches"] = P(baxes if baxes else None, None, None)
    if cfg.family == "encdec":
        out["frames"] = P(baxes if baxes else None, None, None)
    return out


# --------------------------------------------------------------------------- #
# Gradient synchronization (the paper's technique lives here).
# --------------------------------------------------------------------------- #

def sync_grads(grads, specs, mesh_axes, cmp: core_types.CompressionConfig,
               key, batch_axes, ef_state=None):
    """Per-leaf: mean over spec-absent axes; compressed where configured.

    Axes that neither carry the batch nor appear in the leaf spec hold
    *identical* replicas (e.g. pod when batch doesn't span it) — a plain
    pmean there is a no-op numerically but keeps VMA/replication lint
    honest, so we just include them in the exact set.
    Returns (synced_grads, new_ef_state).
    """
    flat_specs = specs
    new_ef = {} if ef_state is not None else None

    out = {}
    for i, (name, g) in enumerate(sorted(grads.items())):
        spec = flat_specs[name]
        axes = bucketing.leaf_sync_axes(spec, mesh_axes)
        if not axes:
            out[name] = g
            continue
        caxes = tuple(a for a in axes if a in cmp.axes)
        eaxes = tuple(a for a in axes if a not in cmp.axes)
        if eaxes:
            g = jax.lax.pmean(g, eaxes)
        if caxes and cmp.mode != "none" and g.size >= cmp.min_compress_size:
            kleaf = jax.random.fold_in(key, i)
            if ef_state is not None:
                # error feedback == the stateful codec round (the registry
                # resolves the EF-wrapped codec; repro.core.wire.ef).
                lcfg = dataclasses.replace(cmp, axes=caxes,
                                           error_feedback=True)
                g, e = coll.compressed_mean_stateful(
                    g, ef_state[name], kleaf, lcfg)
                new_ef[name] = e
            else:
                lcfg = dataclasses.replace(cmp, axes=caxes,
                                           error_feedback=False)
                g = coll.compressed_mean(g, kleaf, lcfg)
        elif caxes:
            g = jax.lax.pmean(g, caxes)
            if ef_state is not None:
                new_ef[name] = ef_state[name]
        elif ef_state is not None:
            new_ef[name] = ef_state[name]
        out[name] = g
    return out, new_ef


# --------------------------------------------------------------------------- #
# The step builder.
# --------------------------------------------------------------------------- #

def build_train_step(mesh, cfg: ArchConfig, run: RunConfig, shape: ShapeSpec,
                     opt_cfg: Optional[opt_lib.AdamWConfig] = None,
                     base_seed: int = 0):
    """Returns (step_fn, init_fn, specs, batch_specs, sync_plan).

    step_fn(params, opt_state, ef_state, batch, step) -> (params, opt_state,
    ef_state, metrics); everything jit+shard_map'd over `mesh`.  sync_plan
    is the BucketPlan the step syncs with (None = per-leaf path) — returned
    so callers introspect/log THE plan the step executes instead of
    re-deriving it.
    """
    opt_cfg = opt_cfg or opt_lib.AdamWConfig()
    msizes = mesh_sizes_of(mesh)
    mesh_axes = tuple(mesh.axis_names)
    ctx = model_lib.make_ctx(cfg, run, msizes)
    key0 = jax.random.PRNGKey(base_seed)
    aparams, specs = abstract_specs(key0, cfg, ctx, msizes, run)
    baxes = batch_axes_for(cfg, run, shape, msizes)
    dp = 1
    for a in baxes:
        dp *= msizes[a]
    global_tokens = float(shape.global_batch * shape.seq_len)
    use_ef = run.compression.error_feedback
    # Bucketed sync (repro.train.bucketing): static plan over the abstract
    # grad tree; one collective per bucket instead of one per leaf.
    plan = grad_sync_plan(mesh, run, aparams, specs)
    # Overlapped issue schedule: pipeline the per-bucket collectives into
    # backward (eligibility: the shared overlap_enabled predicate).
    use_overlap = overlap_enabled(plan, run)
    if plan is not None:
        n_cmp = sum(1 for b in plan.buckets if b.kind == "compressed")
        log.info(
            "grad sync: %d buckets (%d compressed), schedule=%s, overlap=%s",
            len(plan.buckets), n_cmp, plan.schedule(),
            "backward-pipelined" if use_overlap else "post-backward")

    param_ps = {k: spec_to_pspec(v) for k, v in specs.items()}
    bspecs = batch_pspec(cfg, baxes)

    def _local_batch(batch, mb, n_mb):
        def slc(x):
            b_loc = x.shape[0] // n_mb
            return jax.lax.dynamic_slice_in_dim(x, mb * b_loc, b_loc, axis=0)
        return {k: slc(v) for k, v in batch.items()}

    def sharded_step(params, opt_state, ef_state, batch, step):
        key = jax.random.fold_in(key0, step)

        def loss_fn(p, mb_batch):
            loss, metrics = model_lib.train_loss(
                ctx, p, specs, cfg, run, mb_batch, global_tokens)
            return loss, metrics

        n_mb = run.microbatches
        if use_overlap:
            # Overlapped schedule: differentiate the loss of the *tagged*
            # params — grads come back already synced (each sync point's
            # backward rule ran its bucket's collective inside the grad
            # computation), and the grad w.r.t. the EF pytree IS the new
            # residual state (bucketing.overlap_params).
            def loss_tagged(p, ef, mb_batch):
                tagged = bucketing.overlap_params(
                    p, plan, run.compression, key, ef if use_ef else None)
                return loss_fn(tagged, mb_batch)

            (loss, metrics), (grads, new_ef) = jax.value_and_grad(
                loss_tagged, argnums=(0, 1), has_aux=True)(
                    params, ef_state if use_ef else {}, batch)
            if not use_ef:
                new_ef = None
        elif n_mb == 1:
            (loss, metrics), grads = jax.value_and_grad(
                loss_fn, has_aux=True)(params, batch)
        else:
            def mb_body(carry, mb):
                gacc, lacc = carry
                (l, _), g = jax.value_and_grad(loss_fn, has_aux=True)(
                    params, _local_batch(batch, mb, n_mb))
                gacc = jax.tree.map(lambda a, b: a + b.astype(jnp.float32),
                                    gacc, g)
                return (gacc, lacc + l), None

            g0 = jax.tree.map(lambda s: jnp.zeros(s.shape, jnp.float32), params)
            (grads, loss), _ = jax.lax.scan(
                mb_body, (g0, jnp.zeros(())), jnp.arange(n_mb))
            metrics = {}

        if not use_overlap:
            if plan is not None:
                grads, new_ef = bucketing.sync_grads_bucketed(
                    grads, plan, run.compression, key,
                    ef_state if use_ef else None)
            else:
                grads, new_ef = sync_grads(
                    grads, specs, mesh_axes, run.compression, key, baxes,
                    ef_state if use_ef else None)
        if use_ef:
            ef_state = new_ef
        # sharding-aware grad norm: per leaf, psum the sum-of-squares over
        # axes that hold disjoint slices (those in its spec); other axes are
        # replicated after sync.
        gss = jnp.zeros((), jnp.float32)
        for name, g in sorted(grads.items()):
            ss = jnp.sum(jnp.square(g.astype(jnp.float32)))
            ax = tuple(a for s in specs[name] if s is not None
                       for a in ((s,) if isinstance(s, str) else s))
            if ax:
                ss = jax.lax.psum(ss, tuple(dict.fromkeys(ax)))
            gss = gss + ss
        gnorm = jnp.sqrt(gss)
        params, opt_state = opt_lib.adamw_update(
            opt_cfg, grads, opt_state, params, grad_norm=gnorm)
        # loss: local token-loss sums are disjoint across batch axes and
        # (with sequence parallelism) the model axis; replicated elsewhere.
        sum_axes = tuple(dict.fromkeys(
            baxes + (("model",) if ctx.seq_shard else ())))
        loss_all = jax.lax.psum(loss, sum_axes) if sum_axes else loss
        mean_axes = tuple(a for a in mesh_axes if a not in sum_axes)
        if mean_axes:
            loss_all = jax.lax.pmean(loss_all, mean_axes)
        out_metrics = {"loss": loss_all, "grad_norm": gnorm,
                       "lr": opt_lib.lr_at(opt_cfg, opt_state.step - 1)}
        return params, opt_state, ef_state, out_metrics

    def sharded_init(key):
        params, _ = model_lib.init(key, cfg, ctx, msizes, run)
        opt_state = opt_lib.adamw_init(params)
        if use_ef and plan is not None:
            ef_state = bucketing.init_ef_state(plan, run.compression)
        elif use_ef:
            ef_state = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32),
                                    params)
        else:
            ef_state = jax.tree.map(lambda p: jnp.zeros((), jnp.float32),
                                    params)
        return params, opt_state, ef_state

    opt_ps = opt_lib.AdamWState(step=P(), m=param_ps, v=param_ps)
    if use_ef and plan is not None:
        # per-bucket residuals (codec-declared state shapes): per-device
        # state; replication is claimed (P()) but not checked, same as the
        # per-leaf EF specs below.
        ef_ps = {bid: P()
                 for bid in bucketing.ef_state_shapes(plan, run.compression)}
    elif use_ef:
        ef_ps = param_ps
    else:
        ef_ps = jax.tree.map(lambda _: P(), param_ps)
    metrics_ps = {"loss": P(), "grad_norm": P(), "lr": P()}

    step_fn = jax.jit(jax.shard_map(
        sharded_step, mesh=mesh,
        in_specs=(param_ps, opt_ps, ef_ps, bspecs, P()),
        out_specs=(param_ps, opt_ps, ef_ps, metrics_ps),
        check_vma=False))
    init_fn = jax.jit(jax.shard_map(
        sharded_init, mesh=mesh, in_specs=(P(),),
        out_specs=(param_ps, opt_ps, ef_ps), check_vma=False))
    return step_fn, init_fn, specs, bspecs, plan
