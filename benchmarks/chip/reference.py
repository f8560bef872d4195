"""Plain float32 reference for a training cell: the model's loss and
gradients, the gradient sync the cell names, gradient clipping and AdamW.

It imports nothing of the program and takes nothing the program made: the
weights come from :mod:`benchmarks.chip.weights`, the rows from
:mod:`benchmarks.chip.traffic`, and every codec draw is regenerated here
from the seed with ``jax.random`` in its legacy (non-partitionable) Threefry
layout, which is the stream the program's codecs are defined by.

The model is the architecture's (``arch/<architecture>/model.py``): the
summed token loss of a row, divided here by the global token count. Under
the ``int8`` policy (the control, :mod:`benchmarks.chip.nn`) its dense
projections and head run as int8 training matmuls.

The sync follows the configuration's compression settings: leaves sorted by
name and packed first-fit into buckets (leaves under ``min_compress_size``
in one exact bucket), the bucket key ``fold_in(fold_in(PRNGKey(seed),
step), j)`` for plan position ``j``, and per compressed bucket either

* error feedback over the rotated 1-bit twin: ``v = g + e``; ``z = H(D v) /
  sqrt(c)`` in blocks of at most 2**20 (signs D from the bucket key folded
  with the rotation tag); threshold at mean(z), cluster means rounded to
  the wire dtype; the decoded mean over nodes rotated back; ``e' = v -
  own reconstruction``; or
* the section 4.4 Bernoulli message: support ``U(fold_in(key, rank)) < p``,
  values ``v / p - (1 - p) / p * mu`` and ``mu`` rounded to the wire dtype,
  ranks past the capacity dropped.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

from benchmarks.chip import arch, weights
from benchmarks.chip.nn import ein

ROTATION_TAG = 0x524F54
FWHT_BLOCK = 1 << 20


# --------------------------------------------------------------------------- #
# Loss and gradients.
# --------------------------------------------------------------------------- #

def node_loss_grad(params, batch, cfg, policy, global_tokens):
    """(loss, grads) of one node's rows, accumulated row by row."""
    vg = jax.value_and_grad(arch.part(cfg, "model").row_loss)

    def body(acc, row):
        l, g = vg(params, row["tokens"], row["labels"], row["mask"], cfg,
                  policy)
        return (acc[0] + l, jax.tree.map(jnp.add, acc[1], g)), None

    zero = (jnp.zeros((), jnp.float32),
            jax.tree.map(jnp.zeros_like, params))
    (loss, grads), _ = jax.lax.scan(body, zero, batch)
    return (loss / global_tokens,
            jax.tree.map(lambda g: g / global_tokens, grads))


# --------------------------------------------------------------------------- #
# Gradient sync.
# --------------------------------------------------------------------------- #

def plan(shapes: dict, comp: dict) -> list:
    """[(kind, [leaf names])] in plan order: sorted names, first-fit by
    signature, a bucket closed when the next leaf would pass the capacity,
    the open ones closed at the end in the order they were opened."""
    cap = comp["bucket"]["capacity"]
    open_slots, open_fill, out = {}, {}, []
    for name in sorted(shapes):
        size = math.prod(shapes[name])
        kind = ("compressed" if comp["mode"] != "none"
                and size >= comp["min_compress_size"] else "exact")
        fill = open_fill.get(kind, 0)
        if fill and fill + size > cap:
            out.append((kind, open_slots.pop(kind)))
            open_fill.pop(kind)
            fill = 0
        open_slots.setdefault(kind, []).append(name)
        open_fill[kind] = fill + size
    out.extend((kind, open_slots[kind]) for kind in list(open_slots))
    return out


def codec_of(comp: dict) -> str:
    enc = comp["encoder"]
    if comp["mode"] == "none":
        return "exact"
    if (enc["kind"] == "binary" and enc["rotation"] and comp["error_feedback"]
            and comp["mode"] == "gather_decode"):
        return "ef_rotated_binary"
    if (enc["kind"] == "bernoulli" and not enc["rotation"]
            and not comp["error_feedback"] and enc["probs"] == "uniform"
            and enc["center"] == "mean" and comp["mode"] == "gather_decode"):
        return "bernoulli"
    raise ValueError(f"the reference has no codec for {comp}")


def _hadamard(k: int) -> np.ndarray:
    """Sylvester-ordered H of size 2**k: H[i, j] = (-1)**popcount(i & j)."""
    i = np.arange(1 << k)
    bits = np.bitwise_and(i[:, None], i[None, :])
    par = np.zeros_like(bits)
    while bits.any():
        par ^= bits & 1
        bits >>= 1
    return (1 - 2 * par).astype(np.float32)


def fwht(x):
    """Sylvester-ordered Walsh-Hadamard transform over the last axis (a
    power of two), as H_a (x) H_b with the index split in two halves."""
    k = x.shape[-1].bit_length() - 1
    ka, kb = k // 2, k - k // 2
    xs = x.reshape(x.shape[:-1] + (1 << ka, 1 << kb))
    y = ein("ij,...jk,kl->...il", _hadamard(ka), xs, _hadamard(kb))
    return y.reshape(x.shape)


def padded_dim(d: int) -> int:
    dp = 1 << max(0, (d - 1).bit_length())
    return dp if dp <= FWHT_BLOCK else -(-d // FWHT_BLOCK) * FWHT_BLOCK


def _block_fwht(x):
    dp = x.shape[-1]
    c = min(dp, FWHT_BLOCK)
    y = fwht(x.reshape(x.shape[:-1] + (dp // c, c))).reshape(x.shape)
    return y * float(np.float32(1) / np.sqrt(np.float32(c)))


def rotate(key, v):
    """(n, d) -> (n, dp): z = H D v / sqrt(c)."""
    dp = padded_dim(v.shape[-1])
    signs = jax.random.rademacher(key, (dp,), jnp.float32)
    return _block_fwht(jnp.pad(v, ((0, 0), (0, dp - v.shape[-1]))) * signs)


def unrotate(key, z, d: int):
    signs = jax.random.rademacher(key, (z.shape[-1],), jnp.float32)
    return (_block_fwht(z) * signs)[..., :d]


def _wire(x, comp):
    return x.astype(comp["wire_dtype"]).astype(jnp.float32)


def _ef_rotated_binary(v, e, key, comp, exchange):
    d = v.shape[-1]
    v = v + e
    krot = jax.random.fold_in(key, ROTATION_TAG)
    z = rotate(krot, v)
    thr = jnp.mean(z, axis=-1, keepdims=True)
    hi = z >= thr

    def cmean(sel):
        cnt = jnp.sum(sel, axis=-1, keepdims=True)
        tot = jnp.sum(jnp.where(sel, z, 0.0), axis=-1, keepdims=True)
        return jnp.where(cnt > 0, tot / jnp.maximum(cnt, 1), thr)

    rec = jnp.where(hi, _wire(cmean(hi), comp), _wire(cmean(~hi), comp))
    zbar = jnp.mean(rec, axis=0, keepdims=True) if exchange else rec
    est = unrotate(krot, zbar, d)
    return est, v - unrotate(krot, rec, d)


def _bernoulli(v, key, comp, exchange):
    n, d = v.shape
    p = float(comp["encoder"]["fraction"])
    cap = max(1, min(d, math.ceil(p * d + 6.0 * math.sqrt(d * p * (1 - p)))))
    mu = jnp.mean(v, axis=-1, keepdims=True)
    u = jnp.stack([jax.random.uniform(jax.random.fold_in(key, i), (d,))
                   for i in range(n)])
    sent = u < p
    valid = sent & (jnp.cumsum(sent, axis=-1) <= cap)
    rec = jnp.where(valid, _wire(v / p - (1.0 - p) / p * mu, comp),
                    _wire(mu, comp))
    return jnp.mean(rec, axis=0, keepdims=True) if exchange else rec


def sync(grads, ef, comp, step_key, exchange=True):
    """Per-node grads {name: (n, *shape)} -> (synced grads, new EF state).

    Synced grads keep the node axis: one row when the nodes exchange, each
    node's own estimate when they do not (a planted fault).
    """
    codec = codec_of(comp)
    shapes = {k: v.shape[1:] for k, v in grads.items()}
    out, new_ef = {}, {}
    for j, (kind, names) in enumerate(plan(shapes, comp)):
        v = jnp.concatenate([grads[k].reshape(grads[k].shape[0], -1)
                             for k in names], axis=1)
        key = jax.random.fold_in(step_key, j)
        if kind == "exact" or codec == "exact":
            est = jnp.mean(v, axis=0, keepdims=True) if exchange else v
        elif codec == "ef_rotated_binary":
            est, new_ef[j] = _ef_rotated_binary(v, ef[j], key, comp, exchange)
        else:
            est = _bernoulli(v, key, comp, exchange)
        off = 0
        for k in names:
            size = math.prod(shapes[k])
            out[k] = est[:, off:off + size].reshape((est.shape[0],) + shapes[k])
            off += size
    return out, new_ef


def ef_init(shapes: dict, comp: dict, n: int) -> dict:
    if codec_of(comp) != "ef_rotated_binary":
        return {}
    return {j: jnp.zeros((n, sum(math.prod(shapes[k]) for k in names)))
            for j, (kind, names) in enumerate(plan(shapes, comp))
            if kind == "compressed"}


# --------------------------------------------------------------------------- #
# Optimizer.
# --------------------------------------------------------------------------- #

def lr_at(opt: dict, step):
    """Warm-up then cosine decay to ``min_lr_ratio``; ``step`` counts from 0."""
    step = jnp.asarray(step, jnp.float32)
    warm = jnp.minimum(1.0, (step + 1) / max(1, opt["warmup_steps"]))
    prog = jnp.clip((step - opt["warmup_steps"])
                    / max(1, opt["total_steps"] - opt["warmup_steps"]), 0, 1)
    cos = 0.5 * (1 + jnp.cos(jnp.pi * prog))
    return opt["lr"] * warm * (opt["min_lr_ratio"]
                               + (1 - opt["min_lr_ratio"]) * cos)


def adamw(opt: dict, step: int, params, grads, m, v):
    """One AdamW step with global-norm clipping; step counts from 0."""
    gnorm = jnp.sqrt(sum(jnp.sum(g * g) for g in jax.tree.leaves(grads)))
    scale = jnp.minimum(1.0, opt["grad_clip"] / (gnorm + 1e-9))
    t = jnp.asarray(step + 1, jnp.float32)
    b1c, b2c = 1 - opt["b1"] ** t, 1 - opt["b2"] ** t
    lr = lr_at(opt, step)
    new_p, new_m, new_v = {}, {}, {}
    for k, p in params.items():
        g = grads[k] * scale
        new_m[k] = opt["b1"] * m[k] + (1 - opt["b1"]) * g
        new_v[k] = opt["b2"] * v[k] + (1 - opt["b2"]) * g * g
        delta = (new_m[k] / b1c) / (jnp.sqrt(new_v[k] / b2c) + opt["eps"])
        if p.ndim >= 2:
            delta = delta + opt["weight_decay"] * p
        new_p[k] = p - lr * delta
    return new_p, new_m, new_v


# --------------------------------------------------------------------------- #
# The reference's three steps.
# --------------------------------------------------------------------------- #

def leaf_norms(tree) -> dict:
    return {k: jnp.sqrt(jnp.sum(jnp.square(v))) for k, v in tree.items()}


def programs(cfg: dict, traffic: dict, prog_seed: int, global_tokens: float,
             mesh: Mesh, policy: str = "f32", exchange: bool = True):
    """(grad_fn, update): per-node loss and gradients over the ``nodes``
    mesh axis, and the sync, clipping and AdamW step (its state donated)."""
    comp = traffic["compression"]
    opt = traffic["optimizer"]

    grad_fn = jax.jit(jax.shard_map(
        lambda p, bt: jax.tree.map(
            lambda a: a[None],
            node_loss_grad(p, jax.tree.map(lambda a: a[0], bt), cfg, policy,
                           global_tokens)),
        mesh=mesh, in_specs=(P(), P("nodes")), out_specs=P("nodes"),
        check_vma=False))

    @functools.partial(jax.jit, donate_argnums=(0, 2, 3, 4))
    def update(params, grads, ef, m, v, step):
        step_key = jax.random.fold_in(jax.random.PRNGKey(prog_seed), step)
        synced, ef = sync(grads, ef, comp, step_key, exchange)
        mine = {k: g[0] for k, g in synced.items()}
        params, m, v = adamw(opt, step, params, mine, m, v)
        return params, ef, m, v

    return grad_fn, update


def run(cfg: dict, traffic: dict, seed: int, prog_seed: int, batches: list,
        n_nodes: int, devices, policy: str = "f32", exchange: bool = True,
        probe=None):
    """The reference's first ``len(batches)`` steps.

    ``batches`` are the global host batches the program was fed. Returns a
    dict of host floats: ``losses`` per step, ``grad`` (per-leaf norm of the
    first synced, clipped gradient), ``raw_grad`` (per-leaf norm of the
    first unsynced mean gradient) and ``update`` (per-leaf norm of the
    parameters' change over all the steps), each read from node 0.
    ``probe(step, params, first_moment)``, where given, sees the state
    after each step.
    """
    comp = traffic["compression"]
    opt = traffic["optimizer"]
    b, s = batches[0]["tokens"].shape
    mesh = Mesh(np.asarray(devices[:n_nodes]), ("nodes",))
    rep = NamedSharding(mesh, P())
    shapes = weights.param_shapes(cfg)
    grad_fn, update = programs(cfg, traffic, prog_seed, float(b * s), mesh,
                               policy, exchange)
    norms = jax.jit(leaf_norms)
    diff_norms = jax.jit(lambda a, b: leaf_norms(jax.tree.map(jnp.subtract,
                                                              a, b)))
    params = weights.make(cfg, seed, {k: rep for k in shapes})
    m = jax.tree.map(jnp.zeros_like, params)
    v = jax.tree.map(jnp.zeros_like, params)
    ef = ef_init(shapes, comp, n_nodes)
    losses, out = [], {}
    for step, hb in enumerate(batches):
        nb = {k: a.reshape((n_nodes, b // n_nodes) + a.shape[1:])
              for k, a in hb.items()}
        nb = jax.device_put(nb, NamedSharding(mesh, P("nodes")))
        # The codec draws are defined in the legacy Threefry layout; the
        # weights are drawn, as the program's are, in the default one.
        with jax.threefry_partitionable(False), \
                jax.default_matmul_precision("highest"):
            loss, grads = grad_fn(params, nb)
            losses.append(float(jnp.sum(loss)))
            if step == 0:
                out["raw_grad"] = _host(norms(
                    {k: jnp.sum(g, axis=0) for k, g in grads.items()}))
            params, ef, m, v = update(params, grads, ef, m, v,
                                      np.int32(step))
            del grads
        if probe is not None:
            probe(step, params, m)
        if step == 0:
            out["grad"] = _host(norms(
                {k: a / (1 - opt["b1"]) for k, a in m.items()}))
    del m, v, ef
    out["update"] = _host(diff_norms(
        params, weights.make(cfg, seed, {k: rep for k in shapes})))
    out["losses"] = losses
    return out


def _host(d: dict) -> dict:
    return {k: float(v) for k, v in jax.device_get(d).items()}
