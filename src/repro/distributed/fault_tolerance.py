"""Fault tolerance & straggler mitigation (DESIGN.md §5, §14).

The aggregation-level pieces live where they execute:
  * unbiased partial aggregation — :func:`repro.core.collectives.partial_mean`
    (mask-weighted mean over live nodes; the averaging decoder is
    n-agnostic, so dropping a straggling pod for a step stays unbiased);
  * robust decode reductions — ``cfg.decode_policy`` dispatched through the
    wire-codec registry (:mod:`repro.core.wire.robust`): coordinate-wise
    f-of-n trimming / median over the gathered per-peer reconstructions;
  * decode-time peer exclusion — the ``drop_mask`` operand of
    :func:`repro.core.collectives.compressed_mean`: a traced (n,) 0/1 mask
    that excludes peers at decode with zero recompiles;
  * deterministic per-step wire cost — the fixed-k encoder (§4.4), the
    production default (no long-tail packets);
  * checkpoint/restart + elastic resharding — :mod:`repro.checkpoint`.

This module adds the simulation/forensics half:

  * :class:`FailurePlan` — the deterministic failure injector tests drive,
    now also the producer of decode-time drop masks
    (:meth:`FailurePlan.drop_mask`);
  * :func:`robust_compressed_mean` — one compressed round with the plan's
    mask threaded in (the elastic-decode entry point);
  * :func:`replay_support` — reconstruct a dropped node's seed-trick
    support from its fold_in chain alone, for post-mortem reconstruction
    of what the lost wire rows *would* have carried;
  * :func:`corrupt_wire_row` — the adversarial wire-row injector of the
    Byzantine test matrix (tests/distributed_checks/robust_decode_check).
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from repro.core import comm_cost, rotation
from repro.core import types as core_t
from repro.core.collectives import compressed_mean, partial_mean  # noqa: F401
from repro.core.wire import base as wire_base
from repro.core.wire import codecs as wire_codecs
from repro.core.wire import ef as wire_ef
from repro.core.wire import resolve as wire_resolve
from repro.core.wire import rotated as wire_rotated
from repro.kernels.numerics import count_prefix
from repro.kernels.fixed_k_encode import ops as fk


def survivor_index(u) -> jax.Array:
    """THE never-kill-everyone survivor: first index attaining max(u).

    The guaranteed survivor of a failure draw ``u`` (the per-node uniforms
    a :class:`FailurePlan` thresholds) is pinned to one explicit, testable
    rule: the smallest index among the maxima.  ``jnp.argmax`` alone
    already breaks ties this way, but only as an unstated implementation
    detail — spelling the rule out keeps the draw bit-compatible while
    making the tie semantics a contract (property-tested on crafted tied
    arrays by tests/test_fault_tolerance.py).  The max-u node is also the
    node the threshold rule kills *last*: alive = (u >= rate), so the
    designated survivor is a node every rate < 1 would have spared anyway,
    and forcing it alive changes nothing until the draw kills everyone.
    """
    u = jnp.asarray(u)
    return jnp.argmax(u == jnp.max(u))


@dataclasses.dataclass(frozen=True)
class FailurePlan:
    """Deterministic failure schedule: node i is down at step t iff
    hash(seed, t, i) < rate."""
    rate: float = 0.0
    seed: int = 0

    def _draw(self, step: int, n: int) -> jax.Array:
        """THE survivor rule: one (n,) boolean draw both views derive from.

        ``alive_mask`` (host view) and ``local_alive`` (in-shard view) used
        to duplicate this draw in two hand-kept copies — they now agree by
        construction (property-tested across steps and rates by
        tests/distributed_checks/fault_tolerance_check.py).  The
        never-kill-everyone clamp goes through :func:`survivor_index`.
        """
        key = jax.random.fold_in(jax.random.PRNGKey(self.seed), step)
        u = jax.random.uniform(key, (n,))
        alive = u >= self.rate
        return alive.at[survivor_index(u)].set(True)

    def alive_mask(self, step: int, n: int) -> jax.Array:
        return self._draw(step, n)

    def local_alive(self, step: int, axes) -> jax.Array:
        """Per-shard 0/1 scalar, callable inside shard_map."""
        rank, n = wire_base.axis_rank_size(axes)
        return self._draw(step, n)[rank].astype(jnp.float32)

    def drop_mask(self, step: int, n: int) -> jax.Array:
        """The (n,) f32 0/1 alive mask in ``compressed_mean`` drop_mask
        form (1 = keep the peer's decoded row).  Same draw as
        :meth:`alive_mask`; pass it as a traced operand so mask changes
        across steps never recompile (DESIGN.md §14)."""
        return self._draw(step, n).astype(jnp.float32)


def robust_mean(x, step: int, axes, plan: FailurePlan):
    """Exact mean over the nodes the failure plan left alive this step."""
    alive = plan.local_alive(step, axes)
    return partial_mean(x * alive, alive, axes)


def robust_compressed_mean(x, key, cfg: core_t.CompressionConfig,
                           step: int, plan: FailurePlan):
    """One compressed round with the plan's drop mask threaded to decode.

    The elastic-decode analogue of :func:`robust_mean`: the wire round runs
    at full strength (collective shapes are static), but peers the plan
    killed this step are excluded from the decode reduction and the
    estimate renormalizes over the survivors — composing with whatever
    ``cfg.decode_policy`` is set (trimming applies to the kept rows).
    Must run inside shard_map like ``compressed_mean`` itself.
    """
    _, n = wire_base.axis_rank_size(tuple(cfg.axes))
    return compressed_mean(x, key, cfg, drop_mask=plan.drop_mask(step, n))


# --------------------------------------------------------------------------- #
# Seed-trick support replay (post-mortem forensics for dropped peers).
# --------------------------------------------------------------------------- #

@dataclasses.dataclass(frozen=True)
class ReplaySupport:
    """A dropped node's reconstructed wire support (all in the WIRE basis).

    ``dim``     — dimension of the basis the support lives in: the model d
                  for plain codecs, ``rotation.padded_dim(d)`` for rotated
                  compositions (the support is drawn on rotated coords).
    ``support`` — (dim,) bool: the coordinates the encoder *sampled* (the
                  S_i of Eq. (1) / the fixed-k block subset).
    ``kept``    — (dim,) bool: the sampled coordinates whose values
                  actually made the wire buffer — ``support`` minus the
                  capacity-overflow drops of the Bernoulli wire (equal to
                  ``support`` for fixed-k, whose buffer never overflows).
    ``slot``    — (dim,) int32: wire-buffer value-slot index per kept
                  coordinate, −1 elsewhere — enough to lift a captured
                  buffer back to the dense message.
    """
    dim: int
    support: jax.Array
    kept: jax.Array
    slot: jax.Array


def _bernoulli_replay(cfg, kenc, dim: int) -> ReplaySupport:
    p = float(cfg.encoder.fraction)
    cap = comm_cost.bernoulli_capacity(dim, p)
    sent = wire_codecs._bernoulli_support(kenc, dim, p)
    pos = count_prefix(sent) - 1
    kept = sent & (pos < cap)
    slot = jnp.where(kept, pos, -1)
    return ReplaySupport(dim=dim, support=sent, kept=kept, slot=slot)


def _fixed_k_replay(cfg, kenc, dim: int) -> ReplaySupport:
    nb = fk.num_blocks(dim)
    kb = wire_codecs.fixed_k_blocks(dim, cfg.encoder.fraction)
    ids = fk.sample_blocks(kenc, nb, kb)
    hit = jnp.zeros((nb,), bool).at[ids].set(True)
    # value-slot of block b = its rank among the sampled ids (sorted), so
    # slot(j) = rank(block(j))·BLOCK + (j mod BLOCK) for sampled blocks.
    rank_of = jnp.full((nb,), -1, jnp.int32).at[ids].set(
        jnp.arange(kb, dtype=jnp.int32))
    support = jnp.repeat(hit, fk.BLOCK)[:dim]
    off = jnp.arange(dim, dtype=jnp.int32) % fk.BLOCK
    slot = jnp.where(
        support,
        jnp.repeat(rank_of, fk.BLOCK)[:dim] * fk.BLOCK + off, -1)
    return ReplaySupport(dim=dim, support=support, kept=support, slot=slot)


def replay_support(cfg: core_t.CompressionConfig, key, peer: int,
                   d: int) -> ReplaySupport:
    """Reconstruct node ``peer``'s seed-trick support from the key chain.

    The §4.4 seed trick is what makes this possible at all: the sampled
    support is a pure function of ``fold_in(key, peer)`` (the exact chain
    ``pack`` uses — the same regeneration every surviving peer's ``unpack``
    already performs), so a node that died mid-round leaves enough behind
    to reconstruct *where* its lost values lived — including the
    capacity-overflow drop pattern of the Bernoulli wire, bit-exactly
    (tests/test_replay_support.py cross-checks against the threefry
    reference ``uniform_at`` and the shipped buffers).

    Dispatch mirrors ``registry.resolve``: EF delegates wholesale (the
    contractive twin rides the inner codec's exact format and fold_in
    chain); rotated compositions replay the inner support in ROTATED
    space at ``rotation.padded_dim(d)`` (see :class:`ReplaySupport.dim`);
    ``fixed_k_shared`` replays the shared (un-folded) key.  Codecs whose
    occupancy is data-dependent (binary/ternary planes, dense simulation)
    have no seed-derivable support and raise ValueError.
    """
    codec = wire_resolve(cfg)
    dim = d
    while True:
        if isinstance(codec, wire_ef.EFCodec):
            codec = codec.inner
        elif isinstance(codec, wire_rotated.RotatedCodec):
            dim = rotation.padded_dim(dim)
            codec = codec.inner
        else:
            break
    if isinstance(codec, wire_codecs.BernoulliCodec):
        return _bernoulli_replay(cfg, jax.random.fold_in(key, peer), dim)
    if isinstance(codec, wire_codecs.FixedKSharedCodec):
        return _fixed_k_replay(cfg, key, dim)
    if isinstance(codec, wire_codecs.FixedKGatherCodec):
        return _fixed_k_replay(cfg, jax.random.fold_in(key, peer), dim)
    raise ValueError(
        f"codec {codec.name!r} has no seed-derivable support to replay "
        "(data-dependent occupancy: bit-plane and dense wires)")


# --------------------------------------------------------------------------- #
# Adversarial wire-row injection (the Byzantine test matrix).
# --------------------------------------------------------------------------- #

CORRUPTION_MODES = ("nan", "inf", "sign_flip", "boost")


def corrupt_wire_row(row, mode: str):
    """One Byzantine peer's wire buffer: ``row`` corrupted in-place-shape.

    Operates on the REAL wire representation — integer plane buffers are
    bitcast to f32, corrupted, and bitcast back — so the corruption
    travels through the unmodified gather + unpack exactly like honest
    bytes (tests/distributed_checks/robust_decode_check.py injects it
    after ``pack`` inside shard_map).  Modes: "nan"/"inf" flood the
    buffer with non-finite values, "sign_flip" negates it, "boost"
    scales it by 1000.
    """
    if mode not in CORRUPTION_MODES:
        raise ValueError(f"unknown corruption mode {mode!r}; "
                         f"have {CORRUPTION_MODES}")
    as_words = jnp.issubdtype(row.dtype, jnp.integer)
    x = jax.lax.bitcast_convert_type(row, jnp.float32) if as_words \
        else row.astype(jnp.float32)
    if mode == "nan":
        x = jnp.full_like(x, jnp.nan)
    elif mode == "inf":
        x = jnp.full_like(x, jnp.inf)
    elif mode == "sign_flip":
        x = -x
    else:
        x = 1000.0 * x
    if as_words:
        return jax.lax.bitcast_convert_type(x, row.dtype)
    return x.astype(row.dtype)
