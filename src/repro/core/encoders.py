"""The paper's family of randomized, unbiased encoding protocols (§3, §5, §7.1).

Every encoder maps one vector ``x`` in R^d to a (random) vector ``y`` in R^d
("Y_i" in the paper) together with an auxiliary structure describing what
would actually travel on the wire (support size / indices / centers), which
the communication-cost models in :mod:`repro.core.comm_cost` consume.

All encoders are *unbiased*: E[y] = x (Lemmas 3.1, 3.3, 7.1).  Tests verify
this property empirically and via the closed forms in
:mod:`repro.core.mse`.

Shapes: encoders operate on a single (d,) vector; use ``encode_batch`` (vmap
with per-node key folding) for a stack of n node vectors.
"""
from __future__ import annotations

from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.core import centers as centers_lib
from repro.core import types as t
from repro.kernels import numerics
from repro.kernels.threefry import ref as threefry


class Encoded(NamedTuple):
    """Result of encoding a single vector.

    y:       (d,) the dense decoded-view of the message (what the server
             reconstructs for this node before averaging).
    mu:      () node center actually used.
    support: (d,) bool — True where y(j) != mu (the set S_i of §3).  For
             bit-accounting; the sparse protocols transmit exactly these.
    nsent:   () int32 — |S_i|.
    extras:  dict of protocol-specific wire payloads (e.g. binary encoder's
             vmin/vmax scalars).
    """

    y: jax.Array
    mu: jax.Array
    support: jax.Array
    nsent: jax.Array
    extras: dict


# ---------------------------------------------------------------------------
# Eq. (1): variable-size-support encoder.
# ---------------------------------------------------------------------------

def encode_bernoulli(key, x, probs, mu) -> Encoded:
    """Variable-size-support protocol, Eq. (1).

    Y(j) = X(j)/p_j − (1−p_j)/p_j · mu   with prob p_j,
           mu                            otherwise.

    ``probs`` may be scalar or (d,).  p_j = 0 is honoured in the Remark-1
    sense: the coordinate is never sent and the decoder assumes mu (this is
    only unbiased when X(j) = mu, which is exactly when the optimal solution
    of §6.1 assigns p = 0).
    """
    x = jnp.asarray(x)
    probs = jnp.broadcast_to(jnp.asarray(probs, x.dtype), x.shape)
    u = threefry.uniform(key, x.shape)
    sent = u < probs  # P(sent) = p_j; p_j == 0 -> never sent.
    psafe = jnp.where(probs > 0, probs, 1.0)
    scaled = x / psafe - (1.0 - psafe) / psafe * mu
    y = jnp.where(sent, scaled, mu)
    return Encoded(y=y, mu=jnp.asarray(mu, x.dtype), support=sent,
                   nsent=jnp.sum(sent.astype(jnp.int32)), extras={})


# ---------------------------------------------------------------------------
# Eq. (4): fixed-size-support encoder.
# ---------------------------------------------------------------------------

def sample_support(key, d: int, k: int) -> jax.Array:
    """Uniformly sample a k-subset of {0..d-1} (the D_i of Eq. (4)).

    Returns sorted indices, shape (k,).  Gumbel-top-k == uniform sampling
    without replacement, O(d) work — this is the 'random seed' payload of
    §4.4: on SPMD hardware every peer can regenerate the subset from the
    shared key, so indices never travel on the wire.
    """
    g = threefry.gumbel(key, (d,))
    _, idx = jax.lax.top_k(g, k)
    return jnp.sort(idx)


def encode_fixed_k(key, x, k: int, mu) -> Encoded:
    """Fixed-size-support protocol, Eq. (4).

    Y(j) = d·X(j)/k − (d−k)/k · mu  if j ∈ D_i (|D_i| = k, uniform), else mu.
    Communication cost is deterministic (§4.4) — the straggler-friendly
    member of the family.
    """
    x = jnp.asarray(x)
    d = x.shape[-1]
    idx = sample_support(key, d, k)
    support = jnp.zeros((d,), bool).at[idx].set(True)
    scaled = (d / k) * x - ((d - k) / k) * mu
    y = jnp.where(support, scaled, mu)
    return Encoded(y=y, mu=jnp.asarray(mu, x.dtype), support=support,
                   nsent=jnp.asarray(k, jnp.int32), extras={"indices": idx})


# ---------------------------------------------------------------------------
# Example 4: binary quantization (recovers Suresh et al. [10]).
# ---------------------------------------------------------------------------

def encode_binary(key, x) -> Encoded:
    """Stochastic binary quantization, Example 4 / Eq. (12).

    Special case of Eq. (1) with mu_i = X^min and p_j = (X(j)−X^min)/Δ:
    Y(j) = X^max w.p. (X(j)−X^min)/Δ else X^min.  1 bit/coordinate on the
    wire plus the two scalars (§4.5).
    """
    x = jnp.asarray(x)
    vmin = jnp.min(x)
    vmax = jnp.max(x)
    delta = vmax - vmin
    # div_rn: the IEEE quotient on every backend, so the branch bits (the
    # wire plane) do not depend on the device that packs them.
    p = jnp.where(delta > 0,
                  numerics.div_rn(x - vmin, jnp.where(delta > 0, delta, 1.0)
                                  ).astype(x.dtype),
                  0.0)
    u = threefry.uniform(key, x.shape)
    take_max = u < p
    y = jnp.where(take_max, vmax, vmin)
    return Encoded(y=y, mu=vmin, support=take_max,
                   nsent=jnp.asarray(x.shape[-1], jnp.int32),
                   extras={"vmin": vmin, "vmax": vmax})


# ---------------------------------------------------------------------------
# Eq. (21): ternary (k-ary with k=3) encoder, §7.1.
# ---------------------------------------------------------------------------

def encode_ternary(key, x, p1, p2, c1, c2) -> Encoded:
    """Ternary protocol, Eq. (21).

    Y(j) = c1 w.p. p1_j; c2 w.p. p2_j;
           (X(j) − p1_j·c1 − p2_j·c2) / (1 − p1_j − p2_j) otherwise.
    Unbiased for any centers c1, c2 (Lemma 7.1).
    """
    x = jnp.asarray(x)
    p1 = jnp.broadcast_to(jnp.asarray(p1, x.dtype), x.shape)
    p2 = jnp.broadcast_to(jnp.asarray(p2, x.dtype), x.shape)
    u = threefry.uniform(key, x.shape)
    rest = 1.0 - p1 - p2
    restsafe = jnp.where(rest > 0, rest, 1.0)
    # each product fused into its subtraction with one rounding, as
    # XLA:CPU contracts them; fma_rn gives these bits on every backend.
    num = numerics.fma_rn(-p2, c2, numerics.fma_rn(-p1, c1, x))
    y_rest = numerics.div_rn(num, restsafe).astype(x.dtype)
    y = jnp.where(u < p1, c1, jnp.where(u < p1 + p2, c2, y_rest))
    sent = u >= p1 + p2  # the full-precision branch
    # branch index (0 → c1, 1 → c2, 2 → pass-through): the symbol the
    # packed 2-bit wire plane ships (repro.core.bitplane).
    branch = jnp.where(u < p1, 0, jnp.where(u < p1 + p2, 1, 2))
    return Encoded(y=y, mu=jnp.asarray(c1, x.dtype), support=sent,
                   nsent=jnp.sum(sent.astype(jnp.int32)),
                   extras={"c1": jnp.asarray(c1), "c2": jnp.asarray(c2),
                           "branch": branch.astype(jnp.uint32)})


def encode_identity(x) -> Encoded:
    """Example 1: lossless identity encoder (p = 1, Example 5)."""
    x = jnp.asarray(x)
    return Encoded(y=x, mu=jnp.zeros((), x.dtype),
                   support=jnp.ones(x.shape, bool),
                   nsent=jnp.asarray(x.shape[-1], jnp.int32), extras={})


# ---------------------------------------------------------------------------
# Spec-driven dispatch + batched (n, d) API.
# ---------------------------------------------------------------------------

def encode(key, x, spec: t.EncoderSpec, probs=None, mu=None) -> Encoded:
    """Encode one vector according to an :class:`EncoderSpec`.

    ``probs``/``mu`` override the spec's policies when given (used by the
    §6 optimizers, which precompute them).
    """
    d = x.shape[-1]
    if spec.kind == "identity":
        return encode_identity(x)
    if spec.kind == "binary":
        return encode_binary(key, x)
    if mu is None:
        if spec.center == "optimal" and probs is None and spec.probs == "uniform":
            p0 = jnp.full(x.shape, spec.fraction, x.dtype)
            mu = centers_lib.compute_centers(x, "optimal", p0)
        elif spec.center == "optimal" and probs is not None:
            mu = centers_lib.compute_centers(x, "optimal", probs)
        else:
            policy = spec.center if spec.center != "optimal" else "mean"
            mu = centers_lib.compute_centers(x, policy)
    if spec.kind == "fixed_k":
        k = t.fixed_k_from_fraction(d, spec.fraction)
        return encode_fixed_k(key, x, k, mu)
    if spec.kind == "bernoulli":
        if probs is None:
            probs = spec.fraction
        return encode_bernoulli(key, x, probs, mu)
    if spec.kind == "ternary":
        # c1/c2 bracket the data like the binary encoder, with the
        # pass-through mass set by `fraction`.  probs="uniform" splits the
        # branch mass evenly; probs="optimal" uses the §6 per-coordinate
        # optimal split (optimal.ternary_optimal_probs) — the pass
        # probability stays `fraction` either way.
        c1 = jnp.min(x)
        c2 = jnp.max(x)
        if spec.probs == "optimal":
            from repro.core import optimal as optimal_lib
            p1, p2 = optimal_lib.ternary_optimal_probs(x, spec.fraction,
                                                       c1, c2)
            return encode_ternary(key, x, p1, p2, c1, c2)
        half = (1.0 - spec.fraction) / 2.0
        return encode_ternary(key, x, half, half, c1, c2)
    raise ValueError(f"unhandled encoder kind {spec.kind!r}")


def encode_batch(key, xs, spec: t.EncoderSpec, probs=None, mus=None) -> Encoded:
    """Independently encode a stack of node vectors (Def. 2.1 independence).

    Args:
      key: base PRNG key; node i uses fold_in(key, i).
      xs: (n, d) node vectors.
      probs: optional (n, d) probabilities.
      mus: optional (n,) centers.
    Returns an :class:`Encoded` with leading node axis n.
    """
    n = xs.shape[0]
    keys = jax.vmap(lambda i: jax.random.fold_in(key, i))(jnp.arange(n))
    if probs is None and mus is None:
        return jax.vmap(lambda k, x: encode(k, x, spec))(keys, xs)
    if probs is None:
        return jax.vmap(lambda k, x, m: encode(k, x, spec, mu=m))(keys, xs, mus)
    if mus is None:
        return jax.vmap(lambda k, x, p: encode(k, x, spec, probs=p))(keys, xs, probs)
    return jax.vmap(lambda k, x, p, m: encode(k, x, spec, probs=p, mu=m))(
        keys, xs, probs, mus)
