"""Seed-replay forensics: replay_support reconstructs a dropped node's
seed-trick support bit-exactly from the fold_in chain (DESIGN.md §14).

Cross-checked three ways: against the per-coordinate Threefry reference
(:func:`repro.kernels.threefry.ref.uniform_at` — the same scattered-lane
primitive the reduce-scatter decode uses), against the codec's own
``unpack`` of a real packed buffer (the slot map must lift the buffer back
to the dense message), and against a forced-small-capacity encode (the
overflow-drop path, which the natural ≈6σ capacity makes a ~1e-9 event).
Runs in the CI kernel-interpret job too (REPRO_KERNEL_BACKEND=
pallas_interpret), where bernoulli encode goes through the fused Pallas
kernel in interpret mode — replay must agree with those bytes as well.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import comm_cost, rotation
from repro.core import wire
from repro.core.wire import codecs as wire_codecs
from repro.configs.registry import robust_preset
from repro.distributed.fault_tolerance import ReplaySupport, replay_support
from repro.kernels.bernoulli_wire import ops as bw_ops
from repro.kernels.threefry import ref as tf_ref

D = 5000
KEY = jax.random.PRNGKey(11)


def _cfg(name):
    return robust_preset(name, "mean", axes=("data",))


# --------------------------------------------------------------------------- #
# Bernoulli: support, overflow drops, slot map.
# --------------------------------------------------------------------------- #

@pytest.mark.parametrize("peer", [0, 3, 7])
@pytest.mark.parametrize("d", [D, 4999, 257])
def test_bernoulli_replay_bit_exact_vs_threefry_ref(peer, d):
    cfg = _cfg("bernoulli_seed_1bit")
    rs = replay_support(cfg, KEY, peer, d)
    assert rs.dim == d
    p = float(cfg.encoder.fraction)
    kenc = jax.random.fold_in(KEY, peer)
    # the scattered-lane Threefry reference regenerates the identical
    # uniforms the encoder thresholded — support equality is bit-exact.
    u = tf_ref.uniform_at(kenc, jnp.arange(d), d)
    assert (np.asarray(rs.support) == np.asarray(u < p)).all()
    # natural capacity (≈6σ slack): nothing overflows, kept == support.
    assert (np.asarray(rs.kept) == np.asarray(rs.support)).all()


def test_bernoulli_replay_slots_lift_the_real_buffer():
    cfg = _cfg("bernoulli_seed_1bit")
    codec = wire.resolve(cfg)
    peer = 5
    x = jax.random.normal(jax.random.PRNGKey(1), (D,), jnp.float32)
    row = codec.pack(x, KEY, peer, cfg)
    dense = np.asarray(codec.unpack(row, peer, KEY, cfg, D))
    rs = replay_support(cfg, KEY, peer, D)
    kept = np.asarray(rs.kept)
    slot = np.asarray(rs.slot)
    buf = np.asarray(row.astype(jnp.float32))
    mu = buf[-1]
    lifted = np.where(kept, buf[np.clip(slot, 0, len(buf) - 1)], mu)
    assert (lifted == dense).all()
    assert (slot[~kept] == -1).all()
    # slots are a bijection onto the occupied buffer prefix.
    used = np.sort(slot[kept])
    assert (used == np.arange(kept.sum())).all()


def test_bernoulli_cap_overflow_drop_path():
    # the natural capacity makes overflow a ~1e-9 event, so force a tiny
    # cap through the encode entry point and check replay's kept/slot
    # logic reproduces the encoder's drop rule exactly: support ranks
    # ≥ cap are dropped, the rest keep their rank slots.
    d, p, cap = 1024, 0.25, 16
    kenc = jax.random.fold_in(KEY, 2)
    x = jax.random.normal(jax.random.PRNGKey(2), (d,), jnp.float32)
    mu = jnp.mean(x)
    buf = bw_ops.encode(x, kenc, p, cap, mu)
    dense = np.asarray(wire_codecs.bernoulli_unpack(
        buf, kenc, p, cap, mu, d))
    sent = np.asarray(tf_ref.uniform(kenc, d) < p)
    pos = np.cumsum(sent) - 1
    kept = sent & (pos < cap)
    assert sent.sum() > cap  # the drop path is actually exercised
    lifted = np.where(kept, np.asarray(buf)[np.clip(pos, 0, cap - 1)],
                      float(mu))
    assert (lifted == dense).all()


# --------------------------------------------------------------------------- #
# fixed-k (gather + shared) and the rotated/EF compositions.
# --------------------------------------------------------------------------- #

@pytest.mark.parametrize("name,folded", [("rotated_fixed_k", True),
                                         ("ef_fixed_k", True),
                                         ("fixed_k_1bit", False)])
def test_fixed_k_replay_matches_unpack_support(name, folded):
    cfg = _cfg(name)
    rs = replay_support(cfg, KEY, 4, D)
    rot = bool(cfg.encoder.rotation)
    dim = rotation.padded_dim(D) if rot else D
    assert rs.dim == dim
    # fixed-k never overflows: kept == support, block-structured.
    assert (np.asarray(rs.kept) == np.asarray(rs.support)).all()
    # cross-check against the inner codec's unpack: unpack a buffer of
    # slot indices and confirm every supported coordinate reads its slot.
    inner = wire_codecs.FixedKGatherCodec() if folded \
        else wire_codecs.FixedKSharedCodec()
    slots = inner.wire_slots(dim, cfg)
    probe = jnp.concatenate([jnp.arange(slots - 1, dtype=jnp.float32),
                             jnp.zeros((1,), jnp.float32)])  # μ = 0
    dense = np.asarray(inner.unpack(probe, 4, KEY, cfg, dim))
    sup = np.asarray(rs.support)
    slot = np.asarray(rs.slot)
    assert (dense[sup] == slot[sup]).all()
    assert (slot[~sup] == -1).all()


def test_replay_deterministic_sweep():
    # same inputs, same bits — across peers and dims, twice each.
    cfg = _cfg("bernoulli_seed_1bit")
    for d in (257, 1000):
        for peer in range(4):
            a = replay_support(cfg, KEY, peer, d)
            b = replay_support(cfg, KEY, peer, d)
            assert (np.asarray(a.support) == np.asarray(b.support)).all()
            assert (np.asarray(a.slot) == np.asarray(b.slot)).all()


def test_replay_rejects_data_dependent_wires():
    for name in ("binary_packed", "ternary_packed", "ef_rotated_binary"):
        with pytest.raises(ValueError, match="no seed-derivable support"):
            replay_support(_cfg(name), KEY, 0, D)


def test_replay_support_is_frozen_record():
    rs = replay_support(_cfg("bernoulli_seed_1bit"), KEY, 0, 257)
    assert isinstance(rs, ReplaySupport)
    with pytest.raises(dataclasses.FrozenInstanceError):
        rs.dim = 1
