"""jnp oracles for the fused §4.4 Bernoulli wire kernels.

Two jobs in one module:

* the **fast CPU production path** the wire codecs actually execute off-TPU
  (:func:`encode`, :func:`decode_sum`) — byte-identical to the historical
  ``uniform → cumsum → scatter`` chain in repro.core.wire.codecs /
  repro.core.bitplane but without its d-wide ``.at[].set`` scatter, which
  dominated encode wall-clock (~50 ms at d = 2²⁰ on one core: the XLA CPU
  scatter is serial).  ``rank_select`` replaces it with a
  searchsorted-driven *gather* of the identical values, so the (cap,)
  buffer — and therefore the golden wire bytes — is unchanged bit-for-bit
  (pinned by tests/test_golden_wire.py and the equivalence property in
  tests/test_bernoulli_wire_kernels.py);

* the **oracles** the Pallas kernels (repro.kernels.bernoulli_wire.kernel)
  are tested against in interpret mode (:func:`encode`,
  :func:`decode_sum_sequential`).  The kernels inline the bit-exact
  Threefry stream (repro.kernels.threefry.ref), so oracle equivalence is
  exact equality, not allclose.

Support semantics (must never drift — peers regenerate them from seeds):
``sent = uniform(key, (d,)) < p``; the j-th sent coordinate (support rank
j) occupies value slot j; ranks ≥ cap are dropped by both sides
symmetrically (≈6σ tail, repro.core.comm_cost.bernoulli_capacity).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels.numerics import PREFIX_GROUP, count_prefix
from repro.kernels.threefry import ref as tf_ref


def rank_select(values, sent, cap: int):
    """(cap,) f32 with values[j] of each sent coordinate at its support
    rank; ranks ≥ cap dropped, unfilled slots 0.0.

    Equivalent to the historical scatter
    ``zeros(cap).at[where(sent & (pos < cap), pos, cap)].set(values,
    mode="drop")`` — slot k holds the value at the first coordinate whose
    inclusive support count reaches k+1 — but expressed as a gather:
    searchsorted over the inclusive cumsum finds that coordinate directly.
    Same values, same slots, same zeros ⇒ identical bytes, ~10× faster on
    the CPU backend (gathers vectorize; d-wide scatters do not).
    """
    d = values.shape[0]
    cum = count_prefix(sent)
    src = jnp.searchsorted(cum, jnp.arange(1, cap + 1, dtype=jnp.int32),
                           side="left")
    filled = jnp.arange(cap, dtype=jnp.int32) < cum[-1]
    return jnp.where(filled, values[jnp.clip(src, 0, d - 1)], 0.0)


def encode(flat, key, p: float, cap: int, mu, *, scaled: bool = True):
    """One node's (cap,) Bernoulli value buffer (no μ tail, f32).

    The oracle for the fused encode kernel AND the CPU production path of
    repro.core.wire.codecs.bernoulli_pack: support from the node key,
    Eq. (1) unbiased rescale (or raw values for the EF twin), rank-ordered
    capacity-padded compaction.
    """
    d = flat.shape[0]
    sent = tf_ref.uniform(key, d) < p
    vals = flat / p - (1.0 - p) / p * mu if scaled else flat
    return rank_select(vals, sent, cap)


def decode_one(buf, key, p: float, cap: int, mu, d: int):
    """Reconstruct one peer's dense (d,) Y_i from its (cap,) value buffer.

    Exactly repro.core.wire.codecs.bernoulli_unpack's op chain.
    """
    sent = tf_ref.uniform(key, d) < p
    pos = count_prefix(sent) - 1
    valid = sent & (pos < cap)
    vals = buf[jnp.clip(pos, 0, cap - 1)]
    return jnp.where(valid, vals, mu)


# Coordinates per tile of the streamed decode accumulation.  Large enough
# that the per-tile Threefry dispatch amortizes, small enough that the
# (n, TILE) working set stays cache-resident instead of materializing the
# full (n, d) uniform matrix the historical vmap decode built.
DECODE_TILE = 8192


def _peer_sum(recon):
    """Peer-linear f32 sum over axis 0: ((r_0 + r_1) + r_2) + … .

    NOT ``jnp.sum(recon, axis=0)`` — XLA's reduce is free to tree-combine
    the peer axis, which reassociates the f32 adds and drifts from the
    peer-major accumulation order of :func:`decode_sum_sequential` and of
    the Pallas decode kernel (one grid step per peer).  Unrolling the
    (static, small) peer count pins the order, making the batched decodes
    bit-exact vs the sequential oracle.
    """
    acc = recon[0]
    for i in range(1, recon.shape[0]):
        acc = acc + recon[i]
    return acc


def decode_sum(bufs, mus, keys, p: float, cap: int, d: int):
    """Σ_i reconstruction_i, streamed tile-by-tile over the coordinates.

    bufs: (n, cap) f32 value buffers;  mus: (n,) f32;  keys: (n, 2) uint32
    (already rank-folded).  Caller divides by n.

    Each :data:`DECODE_TILE`-wide tile runs a fused regenerate → select →
    accumulate body: the peers' support slice regenerates via the
    random-access Threefry lanes (:func:`repro.kernels.threefry.ref
    .uniform_at` — bit-exact vs the ``jax.random.uniform(key, (d,)) < p``
    rule peers encode with), support ranks come from the carried per-peer
    prior count plus a within-tile matmul cumsum, and the tile's
    peer-linear sum lands in the accumulator.  Identical integers as the
    historical one-shot vmap decode (which materialized the full (n, d)
    uniform matrix) and the sequential oracle's per-coordinate f32 add
    order (:func:`_peer_sum`), so the result equals
    :func:`decode_sum_sequential` bit-for-bit — with an (n, TILE)
    working set instead of (n, d).
    """
    n = bufs.shape[0]
    tile = min(DECODE_TILE, -(-d // PREFIX_GROUP) * PREFIX_GROUP)
    nt = -(-d // tile)

    def body(ti, carry):
        acc, prior = carry
        start = ti * tile
        idx = start + jnp.arange(tile, dtype=jnp.int32)
        real = idx < d
        idxc = jnp.where(real, idx, 0)
        u = jax.vmap(lambda k: tf_ref.uniform_at(k, idxc, d))(keys)
        sent = (u < p) & real[None, :]
        incl = jax.vmap(count_prefix)(sent)
        pos = prior[:, None] + incl - 1
        valid = sent & (pos < cap)
        vals = jnp.take_along_axis(bufs, jnp.clip(pos, 0, cap - 1), axis=1)
        recon = jnp.where(valid, vals, mus[:, None])
        acc = jax.lax.dynamic_update_slice(
            acc, _peer_sum(recon), (start,))
        return acc, prior + incl[:, -1]

    acc, _ = jax.lax.fori_loop(
        0, nt, body, (jnp.zeros((nt * tile,), jnp.float32),
                      jnp.zeros((n,), jnp.int32)))
    return acc[:d]


def support_shard(keys, p: float, d: int, start, ds: int):
    """(n, ds) support slice [start, start+ds) of every peer's (d,) draw.

    ``start`` may be traced (the shard offset inside shard_map); lanes past
    d are padding and come back False — the reduce-scatter decode's shards
    therefore concatenate to exactly the full supports.  Draws go through
    :func:`repro.kernels.threefry.ref.uniform_at`, bit-exact vs the
    ``jax.random.uniform(key, (d,)) < p`` rule peers encode with.
    """
    idx = start + jnp.arange(ds, dtype=jnp.int32)
    real = idx < d
    idxc = jnp.where(real, idx, 0)
    u = jax.vmap(lambda k: tf_ref.uniform_at(k, idxc, d))(keys)
    return (u < p) & real[None, :]


def decode_sum_shard(bufs, mus, sent, prior, cap: int):
    """Σ_i reconstruction_i restricted to one coordinate shard.

    ``sent``: (n, ds) support slice (from :func:`support_shard`);
    ``prior``: (n,) support counts of each peer strictly before the shard
    (the rank offset — a per-peer exclusive cumsum of per-shard counts,
    computed by the caller).  Same per-coordinate arithmetic as
    :func:`decode_sum`: rank = prior + within-shard cumsum − 1 (the
    cumsum via the vectorized matmul form, identical integers), ranks ≥
    cap fall back to μ.  Padding lanes (sent False) also decode to μ and
    must be truncated by the caller.
    """
    pos = prior[:, None] + jax.vmap(count_prefix)(sent) - 1
    valid = sent & (pos < cap)
    vals = jnp.take_along_axis(bufs, jnp.clip(pos, 0, cap - 1), axis=1)
    recon = jnp.where(valid, vals, mus[:, None])
    return _peer_sum(recon)


def decode_sum_sequential(bufs, mus, keys, p: float, cap: int, d: int):
    """Peer-sequential Σ_i reconstruction_i — the fused decode kernel's
    exact accumulation order (peer 0's reconstruction, then peer-major
    adds), used as its oracle."""
    def body(i, acc):
        recon = decode_one(bufs[i], keys[i], p, cap, mus[i], d)
        return jnp.where(i == 0, recon, acc + recon)

    return jax.lax.fori_loop(0, bufs.shape[0], body,
                             jnp.zeros((d,), jnp.float32))
