"""Pallas TPU flash-attention (forward) kernel.

Why it exists here: the dry-run roofline shows every *_4k/32k attention
cell is MEMORY-bound, dominated by materialized (chunk_q × chunk_k) score
tensors — the XLA online-softmax path streams O(S²) bytes through HBM.
This kernel keeps scores/probabilities in VMEM: HBM traffic collapses to
q + k + v + o (O(S·d)), which is the §Perf headline for the qwen3 cell.

Schedule: grid = (B·Hq, nQ, nK) with the KV dimension innermost (TPU grids
execute sequentially over the trailing dim, so the (m, l, acc) online-
softmax state lives in VMEM scratch across the nK steps).  Causal/SWA
blocks that are fully masked are skipped with pl.when — no MXU work and no
HBM reads for the skipped K/V blocks beyond the pipelined prefetch.

GQA: the kv-head index map folds q-head → kv-head (h // group).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _block_mask(bq, bk, q_start, k_start, causal, window):
    q_pos = q_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
    k_pos = k_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    mask = jnp.ones((bq, bk), bool)
    if causal:
        mask &= q_pos >= k_pos
    if window is not None:
        mask &= k_pos > q_pos - window
    return mask


def _block_live(q_start, k_start, bq, bk, causal, window):
    run = True
    if causal:
        run = jnp.logical_and(True, q_start + bq - 1 >= k_start)
    if window is not None:
        run = jnp.logical_and(run, k_start + bk - 1 > q_start - window)
    return run


def _kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, m_scr, l_scr, acc_scr, *,
            causal: bool, window: Optional[int], bq: int, bk: int,
            nk: int, scale: float, q_offset: int):
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q_start = q_offset + qi * bq
    k_start = ki * bk
    run = _block_live(q_start, k_start, bq, bk, causal, window)

    @pl.when(run)
    def _compute():
        q = q_ref[0].astype(jnp.float32)          # (bq, hd)
        k = k_ref[0].astype(jnp.float32)          # (bk, hd)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        mask = _block_mask(bq, bk, q_start, k_start, causal, window)
        s = jnp.where(mask, s, NEG_INF)
        m_prev = m_scr[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1))
        p = jnp.exp(s - m_new[:, None])
        corr = jnp.exp(m_prev - m_new)
        l_scr[...] = l_scr[...] * corr + jnp.sum(p, axis=1)
        m_scr[...] = m_new
        pv = jax.lax.dot_general(p.astype(v_ref.dtype), v_ref[0],
                                 (((1,), (0,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        acc_scr[...] = acc_scr[...] * corr[:, None] + pv

    @pl.when(ki == nk - 1)
    def _finalize():
        l = jnp.maximum(l_scr[...], 1e-30)
        o_ref[0] = (acc_scr[...] / l[:, None]).astype(o_ref.dtype)
        lse_ref[0] = m_scr[...] + jnp.log(l)


@functools.partial(jax.jit, static_argnames=(
    "causal", "window", "q_offset", "block_q", "block_k", "interpret"))
def flash_attention_fwd(q, k, v, *, causal: bool = True,
                        window: Optional[int] = None, q_offset: int = 0,
                        block_q: int = 512, block_k: int = 512,
                        interpret: bool = False):
    """q: (B, Hq, Sq, hd); k, v: (B, Hkv, Sk, hd).  Returns (B, Hq, Sq, hd).

    Sq % block_q == 0, Sk % block_k == 0; hd a multiple of 128 preferred.
    """
    b, hq, sq, hd = q.shape
    _, hkv, sk, _ = k.shape
    g = hq // hkv
    bq = min(block_q, sq)
    bk = min(block_k, sk)
    nq, nk = sq // bq, sk // bk
    bh = b * hq

    q4 = q.reshape(bh, sq, hd)
    # kv indexed by (bh → b, kv head): fold b and h into one grid dim
    k4 = k.reshape(b * hkv, sk, hd)
    v4 = v.reshape(b * hkv, sk, hd)

    def q_map(h, i, j):
        return (h, i, 0)

    def kv_map(h, i, j):
        return ((h // hq) * hkv + (h % hq) // g, j, 0)

    out, lse = pl.pallas_call(
        functools.partial(_kernel, causal=causal, window=window, bq=bq,
                          bk=bk, nk=nk, scale=hd ** -0.5, q_offset=q_offset),
        grid=(bh, nq, nk),
        in_specs=[
            pl.BlockSpec((1, bq, hd), q_map),
            pl.BlockSpec((1, bk, hd), kv_map),
            pl.BlockSpec((1, bk, hd), kv_map),
        ],
        out_specs=[
            pl.BlockSpec((1, bq, hd), q_map),
            pl.BlockSpec((1, bq), lambda h, i, j: (h, i)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, sq, hd), q.dtype),
            jax.ShapeDtypeStruct((bh, sq), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((bq,), jnp.float32),
            pltpu.VMEM((bq,), jnp.float32),
            pltpu.VMEM((bq, hd), jnp.float32),
        ],
        name="flash_attention_fwd",
        interpret=interpret,
    )(q4, k4, v4)
    return out.reshape(b, hq, sq, hd), lse.reshape(b, hq, sq)


# --------------------------------------------------------------------------- #
# Backward kernels (FlashAttention-2 style, two sweeps).
#
# Residuals: q, k, v, o, lse.  delta = rowsum(do ⊙ o) per q position.
#   p  = exp(q·kᵀ·scale − lse)
#   dv = pᵀ·do          dp = do·vᵀ          ds = p ⊙ (dp − delta)
#   dk = dsᵀ·q·scale    (sweep 1: grid over kv blocks, scan q-blocks×group)
#   dq = ds·k·scale     (sweep 2: grid over q blocks, scan kv blocks)
# All block masks/skips derive from program ids + static block sizes, as in
# the forward kernel.  GQA: sweep 1 folds the g q-heads of a kv head into
# the innermost (sequential) grid dim, accumulating dk/dv in VMEM scratch.
# --------------------------------------------------------------------------- #


def _bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                    dk_ref, dv_ref, dk_scr, dv_scr, *,
                    causal, window, bq, bk, nq, nqg, scale, q_offset):
    ki = pl.program_id(1)
    jq = pl.program_id(2)           # jq = g_idx * nq + q_block
    qi = jq % nq

    @pl.when(jq == 0)
    def _init():
        dk_scr[...] = jnp.zeros_like(dk_scr)
        dv_scr[...] = jnp.zeros_like(dv_scr)

    q_start = q_offset + qi * bq
    k_start = ki * bk
    run = _block_live(q_start, k_start, bq, bk, causal, window)

    @pl.when(run)
    def _compute():
        q = q_ref[0].astype(jnp.float32)        # (bq, hd)
        k = k_ref[0].astype(jnp.float32)        # (bk, hd)
        v = v_ref[0].astype(jnp.float32)
        do = do_ref[0].astype(jnp.float32)
        lse = lse_ref[0]                        # (bq,)
        delta = delta_ref[0]                    # (bq,)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        mask = _block_mask(bq, bk, q_start, k_start, causal, window)
        s = jnp.where(mask, s, NEG_INF)
        p = jnp.exp(s - lse[:, None])           # (bq, bk)
        dv_scr[...] += jax.lax.dot_general(
            p, do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - delta[:, None])
        dk_scr[...] += jax.lax.dot_general(
            ds, q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32) * scale

    @pl.when(jq == nqg - 1)
    def _fin():
        dk_ref[0] = dk_scr[...].astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[...].astype(dv_ref.dtype)


def _bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                   dq_ref, dq_scr, *,
                   causal, window, bq, bk, nk, scale, q_offset):
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        dq_scr[...] = jnp.zeros_like(dq_scr)

    q_start = q_offset + qi * bq
    k_start = ki * bk
    run = _block_live(q_start, k_start, bq, bk, causal, window)

    @pl.when(run)
    def _compute():
        q = q_ref[0].astype(jnp.float32)
        k = k_ref[0].astype(jnp.float32)
        v = v_ref[0].astype(jnp.float32)
        do = do_ref[0].astype(jnp.float32)
        lse = lse_ref[0]
        delta = delta_ref[0]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        mask = _block_mask(bq, bk, q_start, k_start, causal, window)
        s = jnp.where(mask, s, NEG_INF)
        p = jnp.exp(s - lse[:, None])
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - delta[:, None])
        dq_scr[...] += jax.lax.dot_general(
            ds, k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32) * scale

    @pl.when(ki == nk - 1)
    def _fin():
        dq_ref[0] = dq_scr[...].astype(dq_ref.dtype)


@functools.partial(jax.jit, static_argnames=(
    "causal", "window", "q_offset", "block_q", "block_k", "interpret"))
def flash_attention_bwd(q, k, v, do, lse, delta, *, causal=True, window=None,
                        q_offset=0, block_q=512, block_k=512,
                        interpret=False):
    """Backward pass.  Layouts as flash_attention_fwd; lse/delta (B,Hq,Sq).

    Returns (dq (B,Hq,Sq,hd), dk, dv (B,Hkv,Sk,hd) in f32).
    """
    b, hq, sq, hd = q.shape
    _, hkv, sk, _ = k.shape
    g = hq // hkv
    bq = min(block_q, sq)
    bk = min(block_k, sk)
    nq, nk = sq // bq, sk // bk

    q4 = q.reshape(b * hq, sq, hd)
    do4 = do.reshape(b * hq, sq, hd)
    lse2 = lse.reshape(b * hq, sq)
    delta2 = delta.reshape(b * hq, sq)
    k4 = k.reshape(b * hkv, sk, hd)
    v4 = v.reshape(b * hkv, sk, hd)

    # ---- sweep 1: dk, dv — grid (b·hkv, nk, g·nq)
    def qh_map(c, ki, jq):
        # q-head row for (batch, kv-head) = c and group index jq // nq
        return ((c // hkv) * hq + (c % hkv) * g + jq // nq, jq % nq, 0)

    def qh_vec_map(c, ki, jq):
        return ((c // hkv) * hq + (c % hkv) * g + jq // nq, jq % nq)

    def kv_map1(c, ki, jq):
        return (c, ki, 0)

    dk, dv = pl.pallas_call(
        functools.partial(_bwd_dkv_kernel, causal=causal, window=window,
                          bq=bq, bk=bk, nq=nq, nqg=g * nq, scale=hd ** -0.5,
                          q_offset=q_offset),
        grid=(b * hkv, nk, g * nq),
        in_specs=[
            pl.BlockSpec((1, bq, hd), qh_map),     # q
            pl.BlockSpec((1, bk, hd), kv_map1),    # k
            pl.BlockSpec((1, bk, hd), kv_map1),    # v
            pl.BlockSpec((1, bq, hd), qh_map),     # do
            pl.BlockSpec((1, bq), qh_vec_map),     # lse
            pl.BlockSpec((1, bq), qh_vec_map),     # delta
        ],
        out_specs=[
            pl.BlockSpec((1, bk, hd), kv_map1),
            pl.BlockSpec((1, bk, hd), kv_map1),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b * hkv, sk, hd), jnp.float32),
            jax.ShapeDtypeStruct((b * hkv, sk, hd), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((bk, hd), jnp.float32),
            pltpu.VMEM((bk, hd), jnp.float32),
        ],
        name="flash_attention_bwd_dkv",
        interpret=interpret,
    )(q4, k4, v4, do4, lse2, delta2)

    # ---- sweep 2: dq — grid (b·hq, nq, nk)
    def q_map(h, i, j):
        return (h, i, 0)

    def q_vec_map(h, i, j):
        return (h, i)

    def kv_map2(h, i, j):
        return ((h // hq) * hkv + (h % hq) // g, j, 0)

    dq = pl.pallas_call(
        functools.partial(_bwd_dq_kernel, causal=causal, window=window,
                          bq=bq, bk=bk, nk=nk, scale=hd ** -0.5,
                          q_offset=q_offset),
        grid=(b * hq, nq, nk),
        in_specs=[
            pl.BlockSpec((1, bq, hd), q_map),
            pl.BlockSpec((1, bk, hd), kv_map2),
            pl.BlockSpec((1, bk, hd), kv_map2),
            pl.BlockSpec((1, bq, hd), q_map),
            pl.BlockSpec((1, bq), q_vec_map),
            pl.BlockSpec((1, bq), q_vec_map),
        ],
        out_specs=pl.BlockSpec((1, bq, hd), q_map),
        out_shape=jax.ShapeDtypeStruct((b * hq, sq, hd), jnp.float32),
        scratch_shapes=[pltpu.VMEM((bq, hd), jnp.float32)],
        name="flash_attention_bwd_dq",
        interpret=interpret,
    )(q4, k4, v4, do4, lse2, delta2)

    return (dq.reshape(b, hq, sq, hd), dk.reshape(b, hkv, sk, hd),
            dv.reshape(b, hkv, sk, hd))
