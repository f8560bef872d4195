"""Optimal protocol parameters (§6).

Problem (14): minimize the MSE numerator Σ_ij (1/p_ij − 1)(X_i(j) − μ_i)²
subject to a communication budget Σ_ij p_ij ≤ B and 0 < p_ij ≤ 1, jointly
over probabilities and node centers.  The objective is biconvex; the paper
prescribes alternating minimization:

  step 1 (centers, closed form, Eq. 16):  μ_i = Σ_j w_ij X_ij / Σ_j w_ij,
          w_ij = 1/p_ij − 1;
  step 2 (probabilities, §6.1): water-filling — at optimum
          p_ij = min(1, a_ij/θ) with a_ij = |X_i(j) − μ_i| and θ set so the
          budget is tight.  (The paper derives the uncapped stationary point
          a_ij/p_ij = θ and notes the capped case has no closed form; the
          standard water-filling extension below solves the capped problem
          *exactly* — the objective is convex and separable, so KKT gives
          p = min(1, a/θ) with θ the unique root of Σ min(1, a/θ) = B.)
"""
from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp

from repro.core import centers as centers_lib
from repro.core import mse as mse_lib
from repro.kernels import numerics


def optimal_probs(xs, mus, B: float, iters: int = 64):
    """Water-filled optimal probabilities for fixed centers (§6.1).

    Args:
      xs: (n, d) node vectors.
      mus: (n,) centers.
      B: communication budget — bound on Σ_ij p_ij  (0 < B ≤ n·d).
      iters: bisection iterations for θ (each halves the bracket; 64 reaches
        float64 resolution).

    Returns (n, d) probabilities with Σ p_ij ≤ B (tight unless capped at the
    |S| ceiling, in which case p = 1 on all of S — the zero-MSE regime).

    Coordinates with a_ij = 0 receive p = 0 (Remark-1 semantics: never sent,
    zero MSE contribution — see mse.mse_bernoulli).
    """
    a = jnp.abs(xs - mus[:, None]).astype(jnp.float64 if jax.config.x64_enabled else jnp.float32)
    S = jnp.sum(a > 0)
    B = jnp.minimum(jnp.asarray(B, a.dtype), S.astype(a.dtype))

    amax = jnp.max(a)
    # θ bracket: at θ→0+, Σ min(1, a/θ) → |S| ≥ B; at θ = Σa/B (uncapped
    # solution's θ), Σ min(1, a/θ) ≤ Σ a/θ = B.  Bisect within.
    lo = jnp.asarray(1e-30, a.dtype)
    hi = jnp.maximum(jnp.sum(a) / jnp.maximum(B, 1e-30), lo * 2)

    def body(_, loh):
        lo, hi = loh
        mid = 0.5 * (lo + hi)
        sent = jnp.sum(jnp.minimum(1.0, a / mid))
        # sent decreasing in θ: if sent > B we need larger θ.
        return jnp.where(sent > B, mid, lo), jnp.where(sent > B, hi, mid)

    lo, hi = jax.lax.fori_loop(0, iters, body, (lo, hi))
    theta = 0.5 * (lo + hi)
    p = jnp.minimum(1.0, a / theta)
    p = jnp.where(a > 0, p, 0.0)
    return p.astype(xs.dtype)


def optimal_probs_per_node(xs, mus, budgets):
    """Remark 5: per-node budgets B_1..B_n; each node solves its own §6.1
    problem independently (the practical federated deployment — no global
    coordination needed).  For B = Σ B_i the resulting MSE is lower-bounded
    by the jointly-optimal MSE of problem (14) (verified by property test).

    One ``vmap`` over nodes — a single trace regardless of n, and the
    budgets stay traced (jit-compatible; tests/test_optimal.py asserts a
    jit of this function compiles and matches the per-row solver).

    budgets: (n,) per-node bounds on Σ_j p_ij.
    """
    budgets = jnp.asarray(budgets)
    return jax.vmap(
        lambda x, m, b: optimal_probs(x[None, :], m[None], b)[0]
    )(xs, mus, budgets)


def ternary_optimal_probs(x, q, c1=None, c2=None):
    """§6-optimal per-coordinate (p1, p2) for the ternary encoder (§7.1).

    The Eq. (21) protocol leaves the split between the c1/c2 branches free:
    any (p1_j, p2_j) with p1_j + p2_j = 1 − q is unbiased (Lemma 7.1).
    At fixed pass mass q and centers c1 = min x, c2 = max x, the exact
    per-coordinate variance (corrected Lemma 7.2, see mse.mse_ternary) as a
    function of the mixture mean s_j = p1_j·c1 + p2_j·c2 is

        Var_j(s) = s·(c1 + c2) − (1 − q)·c1·c2 + (x_j − s)²/q − x_j²,

    convex in s with unconstrained minimizer s*_j = x_j − q·(c1 + c2)/2,
    clamped to the feasible [(1 − q)c1, (1 − q)c2].  The default mid-split
    p1 = p2 = (1 − q)/2 corresponds to s = (1 − q)(c1 + c2)/2 and is
    recovered iff x_j sits at the midpoint — so the optimal split never
    loses (tests/test_optimal.py asserts the dominance via mse_ternary).

    The pass branch keeps probability exactly q per coordinate regardless
    of the split, so the 6σ capacity sizing of the realized pass-through
    mass (comm_cost.bernoulli_capacity at p = q) is unchanged — which is
    what lets this ride the existing 2-bit-plane wire format as a plain
    codec (repro.core.wire.codecs.TernaryOptCodec).

    Returns (p1, p2) arrays shaped like ``x``.  Pass the caller's centers
    via ``c1``/``c2`` when already computed (encoders.encode does) so the
    split is optimized for exactly the centers shipped on the wire.
    """
    x = jnp.asarray(x, jnp.float32)
    q = jnp.asarray(q, jnp.float32)
    if c1 is None:
        c1 = jnp.min(x)
    if c2 is None:
        c2 = jnp.max(x)
    s = jnp.clip(x - q * (c1 + c2) / 2, (1.0 - q) * c1, (1.0 - q) * c2)
    span = c2 - c1
    p1 = jnp.where(span > 0,
                   numerics.div_rn((1.0 - q) * c2 - s,
                                   jnp.where(span > 0, span, 1.0)),
                   1.0 - q)  # degenerate constant vector: all mass on c1
    p1 = jnp.broadcast_to(p1, x.shape)
    return p1, (1.0 - q) - p1


def alternating_minimization(xs, B: float, iters: int = 20,
                             init_center: str = "mean") -> Tuple[jax.Array, jax.Array, jax.Array]:
    """§6 alternating scheme for the joint (p, μ) problem (14).

    Returns (probs (n,d), mus (n,), mse_trace (iters,)).  The trace is
    non-increasing (each step solves its subproblem exactly), which
    tests/test_optimal.py asserts.
    """
    mus = centers_lib.compute_centers(xs, init_center)

    def step(carry, _):
        mus, _ = carry
        p = optimal_probs(xs, mus, B)
        mus_new = centers_lib.optimal_centers(xs, p)
        m = mse_lib.mse_bernoulli(xs, p, mus_new)
        return (mus_new, p), m

    (mus, probs), trace = jax.lax.scan(
        step, (mus, jnp.zeros_like(xs)), None, length=iters)
    return probs, mus, trace
