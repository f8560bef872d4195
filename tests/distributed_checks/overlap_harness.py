"""Shared harness for the overlapped-vs-post-backward sync schedules.

One construction of the MLP-chain grad tree and the two shard_map step
bodies, imported by BOTH subprocess entry points so the validator and the
benchmark can never measure different configurations:

  * tests/distributed_checks/overlap_check.py — bit-equality + HLO checks;
  * benchmarks/bench_bucketing.py (_OVERLAP_INNER) — ms/step + launch
    parity for BENCH_collectives.json's ``overlap`` section.

Importers MUST set XLA_FLAGS (device count) before importing this module —
it imports jax, and jax locks the device count at first init.
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs.registry import compression_preset
from repro.core import types
from repro.train import bucketing


def build_tree(n_layers: int, width: int):
    """(shapes, specs) of the L-layer MLP chain: w_[i] (M,M) + b_[i] (M,).

    All leaves unsharded → every mesh axis is a sync axis; the weights land
    in compressed buckets, the biases in one exact bucket.
    """
    shapes = {}
    for i in range(n_layers):
        shapes[f"w_{i:02d}"] = (width, width)
        shapes[f"b_{i:02d}"] = (width,)
    specs = {n: (None,) * len(s) for n, s in shapes.items()}
    return shapes, specs


def init_params(shapes, scale: float = 0.2):
    return {n: scale * jax.random.normal(
        jax.random.fold_in(jax.random.PRNGKey(0), i), shapes[n])
        for i, n in enumerate(sorted(shapes))}


def make_loss(n_layers: int):
    def loss_fn(params, x):
        h = x
        for i in range(n_layers):
            h = jnp.tanh(h @ params[f"w_{i:02d}"] + params[f"b_{i:02d}"])
        return jnp.mean(h * h)

    return loss_fn


def mkcfg(preset: str, width: int) -> types.CompressionConfig:
    """The preset at f32 wire (the CPU backend legalizes bf16 collectives
    at f32 — same normalization as the other distributed checks), bucket
    capacity sized so the weight leaves split into multiple buckets."""
    cfg = (types.CompressionConfig(mode="none") if preset == "none"
           else compression_preset(preset, axes=("data",)))
    return dataclasses.replace(
        cfg, min_compress_size=1024, wire_dtype="float32",
        bucket=types.BucketSpec(capacity=2 * width * width))


def make_sync_steps(mesh, n_layers: int, cfg, plan):
    """(post_fn, ovl_fn), jitted: (params, ef, x, key) -> (grads, new_ef).

    ``post_fn`` is the reference schedule (grad, then sync_grads_bucketed);
    ``ovl_fn`` differentiates through bucketing.overlap_params — the
    overlapped schedule.  Both take the EF pytree positionally ({} when the
    config is EF-free) so callers drive every preset uniformly.
    """
    loss_fn = make_loss(n_layers)
    use_ef = cfg.error_feedback
    pspec = {s.name: P() for b in plan.buckets for s in b.slots}
    efspec = {b.bid: P() for b in plan.buckets
              if use_ef and b.kind == "compressed"}

    @functools.partial(jax.shard_map, mesh=mesh,
                       in_specs=(pspec, efspec, P("data"), P()),
                       out_specs=(pspec, efspec), check_vma=False)
    def post(params, ef, x, key):
        grads = jax.grad(loss_fn)(params, x)
        g, new_ef = bucketing.sync_grads_bucketed(
            grads, plan, cfg, key, ef if use_ef else None)
        return g, (new_ef if use_ef else {})

    @functools.partial(jax.shard_map, mesh=mesh,
                       in_specs=(pspec, efspec, P("data"), P()),
                       out_specs=(pspec, efspec), check_vma=False)
    def ovl(params, ef, x, key):
        def loss2(p, e):
            tagged = bucketing.overlap_params(
                p, plan, cfg, key, e if use_ef else None)
            return loss_fn(tagged, x)

        g, gef = jax.grad(loss2, argnums=(0, 1))(params, ef if use_ef else {})
        return g, (gef if use_ef else {})

    return jax.jit(post), jax.jit(ovl)
