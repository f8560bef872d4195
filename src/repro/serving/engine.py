"""Serving: prefill + decode step builders (shard_map'd), cache shardings,
and a batched greedy-generation driver.

Cache layout note: when kv_heads < tp the kv dimension of the cache is
declared with *global* extent kv_keep·tp and P("model") — each model shard
stores the single kv head its q-block attends to (heads are duplicated
across shards in the global view; decode only ever reads the local slice).
"""
from __future__ import annotations

import functools
from typing import Dict, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs.base import ArchConfig, RunConfig, ShapeSpec
from repro.models import attention as attn_lib
from repro.models import encdec as encdec_lib
from repro.models import model as model_lib
from repro.train import train_step as ts


def serve_batch_axes(cfg, run, shape, msizes):
    return ts.batch_axes_for(cfg, run, shape, msizes)


def cache_pspecs(cfg: ArchConfig, ctx, baxes) -> Dict:
    b = baxes if baxes else None

    def attn_spec():
        return {"k": P(None, b, None, "model", None),
                "v": P(None, b, None, "model", None)}

    def ssm_spec():
        m = "model" if ctx.tp > 1 else None
        return {"conv_x": P(None, b, None, m),
                "conv_B": P(None, b, None, None),
                "conv_C": P(None, b, None, None),
                "state": P(None, b, m, None, None)}

    if cfg.family in ("dense", "vlm", "moe"):
        return attn_spec()
    if cfg.family == "ssm":
        return ssm_spec()
    if cfg.family == "hybrid":
        return {"attn": attn_spec(), "ssm": ssm_spec()}
    if cfg.family == "encdec":
        sp = attn_spec()
        sp.update({"xk": P(None, b, None, "model", None),
                   "xv": P(None, b, None, "model", None)})
        return sp
    raise ValueError(cfg.family)


def global_cache_shapes(cfg: ArchConfig, ctx, shape: ShapeSpec,
                        msizes) -> Dict:
    """ShapeDtypeStructs of the GLOBAL decode cache for dry-run lowering."""
    dims = attn_lib.attn_dims(cfg.num_heads, cfg.num_kv_heads, cfg.hd, ctx.tp)
    kv_keep = 1 if (dims.kv_replicated and ctx.tp > 1) else dims.kv_local
    kv_glob = kv_keep * (ctx.tp if ctx.tp > 1 else 1)
    b = shape.global_batch
    s_max = shape.seq_len if cfg.window is None else min(shape.seq_len,
                                                         cfg.window)
    L = cfg.num_layers
    f = jnp.bfloat16

    def attn_shape(n, s):
        return {"k": jax.ShapeDtypeStruct((n, b, s, kv_glob, cfg.hd), f),
                "v": jax.ShapeDtypeStruct((n, b, s, kv_glob, cfg.hd), f)}

    def ssm_shape(n):
        s = cfg.ssm
        tpx = ctx.tp if ctx.tp > 1 else 1
        return {
            "conv_x": jax.ShapeDtypeStruct(
                (n, b, s.conv_width - 1, s.d_inner(cfg.d_model)), f),
            "conv_B": jax.ShapeDtypeStruct(
                (n, b, s.conv_width - 1, s.n_groups * s.d_state), f),
            "conv_C": jax.ShapeDtypeStruct(
                (n, b, s.conv_width - 1, s.n_groups * s.d_state), f),
            "state": jax.ShapeDtypeStruct(
                (n, b, s.nheads(cfg.d_model), s.head_dim, s.d_state),
                jnp.float32),
        }

    if cfg.family in ("dense", "vlm", "moe"):
        return attn_shape(L, s_max)
    if cfg.family == "ssm":
        return ssm_shape(L)
    if cfg.family == "hybrid":
        per = cfg.attn_every
        return {"attn": attn_shape(L // per, s_max),
                "ssm": ssm_shape((L // per) * (per - 1))}
    if cfg.family == "encdec":
        out = attn_shape(L, s_max)
        enc_s = encdec_lib.enc_seq_padded(cfg, ctx.tp)
        xc = attn_shape(L, enc_s)
        out["xk"] = xc["k"]
        out["xv"] = xc["v"]
        return out
    raise ValueError(cfg.family)


def build_serve_fns(mesh, cfg: ArchConfig, run: RunConfig, shape: ShapeSpec,
                    base_seed: int = 0):
    """Returns (prefill_fn, decode_fn, specs, input pspec info).

    prefill_fn(params, batch) -> (cache, logits_local)
    decode_fn(params, cache, tok, pos) -> (next_tok, cache)
    """
    msizes = ts.mesh_sizes_of(mesh)
    ctx = model_lib.make_ctx(cfg, run, msizes)
    key0 = jax.random.PRNGKey(base_seed)
    _, specs = ts.abstract_specs(key0, cfg, ctx, msizes, run)
    baxes = ts.batch_axes_for(cfg, run, shape, msizes)
    param_ps = {k: ts.spec_to_pspec(v) for k, v in specs.items()}
    cache_ps = cache_pspecs(cfg, ctx, baxes)
    b = baxes if baxes else None
    tok_ps = P(b, None)
    s_max = shape.seq_len if cfg.window is None else min(shape.seq_len,
                                                         cfg.window)

    def sharded_prefill(params, batch):
        cache, logits = model_lib.prefill(ctx, params, specs, cfg, run, batch,
                                          s_max=s_max)
        return cache, logits

    def sharded_decode(params, cache, tok, pos):
        nxt, _, cache = model_lib.decode_step(ctx, params, specs, cfg, run,
                                              cache, tok, pos)
        return nxt, cache

    bspec = ts.batch_pspec(cfg, baxes)
    del bspec["labels"], bspec["mask"]

    vax = "model" if ctx.tp > 1 else None
    prefill_fn = jax.jit(jax.shard_map(
        sharded_prefill, mesh=mesh, in_specs=(param_ps, bspec),
        out_specs=(cache_ps, P(b, None, vax)), check_vma=False))
    decode_fn = jax.jit(jax.shard_map(
        sharded_decode, mesh=mesh,
        in_specs=(param_ps, cache_ps, tok_ps, P()),
        out_specs=(tok_ps, cache_ps), check_vma=False),
        donate_argnums=(1,))
    return prefill_fn, decode_fn, specs, {"batch": bspec, "cache": cache_ps,
                                          "tok": tok_ps, "baxes": baxes}


def generate(prefill_fn, decode_fn, params, batch, steps: int):
    """Greedy generation driver (host loop; decode_fn donates the cache)."""
    cache, logits = prefill_fn(params, batch)
    prompt_len = batch["tokens"].shape[1]
    # greedy pick from the replicated last-position logits is done on host
    # via the decode_fn's internal sampling; seed decode with the prompt's
    # last token prediction:
    toks = []
    tok = jnp.argmax(logits[:, :, :], axis=-1).astype(jnp.int32)  # local slice
    for i in range(steps):
        tok, cache = decode_fn(params, cache, tok, jnp.int32(prompt_len + i))
        toks.append(tok)
    return jnp.concatenate(toks, axis=1)
