"""Plain float32 Mamba-2: the summed token loss of one row.

Layer equations (per row; ``x`` is the residual stream, f32):

    h  = rmsnorm(x) * norm1
    z, u, B, C, dt_raw = h W_z, h W_x, h W_B, h W_C, h W_dt
    u, B, C = silu(causal_conv(u)), silu(causal_conv(B)), silu(causal_conv(C))
    dt = softplus(dt_raw + dt_bias);  log_a = -dt * exp(A_log)
    y_t = sum_{s<=t} exp(sum_{s<v<=t} log_a_v) dt_s (C_t . B_s) u_s + D u_t
    x  = x + rmsnorm(y * silu(z)) * norm  W_out

then ``rmsnorm(x) * final_norm``, logits against the tied embedding, and the
token cross-entropy summed. The scan is evaluated chunk by chunk
(``REF_CHUNK`` positions, not the program's chunk) with the carried state
between chunks. Every dense projection and the head go through
``nn.dense(policy)``.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmarks.chip.arch.mamba2.weights import dims
from benchmarks.chip.nn import dense, ein, rmsnorm

REF_CHUNK = 64
CE_CHUNK = 512


def conv_silu(x, w):
    """Depthwise causal convolution over positions, then silu. x: (s, c)."""
    s, width = x.shape[0], w.shape[0]
    xp = jnp.concatenate([jnp.zeros((width - 1, x.shape[1]), x.dtype), x])
    y = sum(xp[i:i + s] * w[i] for i in range(width))
    return jax.nn.silu(y)


def ssd(u, B, C, dt, log_a, q):
    """u: (s, h, p); B, C: (s, n); dt, log_a: (s, h) -> (s, h, p)."""
    s, h, p = u.shape
    q = min(q, s)
    nc = s // q
    uc = u.reshape(nc, q, h, p)
    Bc, Cc = B.reshape(nc, q, -1), C.reshape(nc, q, -1)
    dtc, cum = dt.reshape(nc, q, h), jnp.cumsum(log_a.reshape(nc, q, h), 1)
    tril = jnp.tril(jnp.ones((q, q), bool))[None, :, :, None]
    seg = jnp.where(tril, cum[:, :, None, :] - cum[:, None, :, :], -jnp.inf)
    G = ein("ctn,cun->ctu", Cc, Bc)
    y_in = ein("ctuh,cuhp->cthp", G[..., None] * jnp.exp(seg) * dtc[:, None],
               uc)
    to_end = jnp.exp(cum[:, -1:, :] - cum) * dtc                 # (nc, q, h)
    s_loc = ein("cuh,cuhp,cun->chpn", to_end, uc, Bc)
    decay = jnp.exp(cum[:, -1, :])                               # (nc, h)

    def carry(state, inp):
        dc, sl = inp
        return dc[:, None, None] * state + sl, state

    _, s_in = jax.lax.scan(carry, jnp.zeros(s_loc.shape[1:], u.dtype),
                           (decay, s_loc))
    y_out = ein("ctn,cth,chpn->cthp", Cc, jnp.exp(cum), s_in)
    return (y_in + y_out).reshape(s, h, p)


def _layer(cfg, mm, x, p):
    k = dims(cfg)
    s = x.shape[0]
    h = rmsnorm(x, p["norm1"])
    z = mm("sd,de->se", h, p["ssm.w_z"])
    u = conv_silu(mm("sd,de->se", h, p["ssm.w_x"]), p["ssm.conv_x"])
    B = conv_silu(mm("sd,dn->sn", h, p["ssm.w_B"]), p["ssm.conv_B"])
    C = conv_silu(mm("sd,dn->sn", h, p["ssm.w_C"]), p["ssm.conv_C"])
    dt = jax.nn.softplus(mm("sd,dh->sh", h, p["ssm.w_dt"]) + p["ssm.dt_bias"])
    log_a = -dt * jnp.exp(p["ssm.A_log"])
    u = u.reshape(s, k["H"], k["P"])
    y = ssd(u, B, C, dt, log_a, REF_CHUNK) + u * p["ssm.D"][None, :, None]
    y = rmsnorm(y.reshape(s, k["din"]) * jax.nn.silu(z), p["ssm.norm"])
    return x + mm("se,ed->sd", y, p["ssm.w_out"])


def row_loss(params, tokens, labels, mask, cfg, policy):
    """Summed token cross-entropy of one row."""
    mm = dense(policy)
    layers = {k[len("layers."):]: v for k, v in params.items()
              if k.startswith("layers.")}
    x = params["embed"][tokens]
    body = jax.checkpoint(lambda c, p: (_layer(cfg, mm, c, p), None))
    x, _ = jax.lax.scan(body, x, layers)
    h = rmsnorm(x, params["final_norm"])
    s = h.shape[0]
    ch = min(CE_CHUNK, s)

    @jax.checkpoint
    def ce(args):
        hc, yc, mc = args
        logits = mm("sd,vd->sv", hc, params["embed"])
        lse = jax.nn.logsumexp(logits, axis=-1)
        tgt = jnp.take_along_axis(logits, yc[:, None], axis=-1)[:, 0]
        return jnp.sum((lse - tgt) * mc)

    parts = jax.lax.map(ce, (h.reshape(s // ch, ch, -1),
                             labels.reshape(s // ch, ch),
                             mask.reshape(s // ch, ch)))
    return jnp.sum(parts)
