"""Hierarchical two-level + reduce-scatter wire path (docs/DESIGN.md §11).

The multi-device half (bit-exactness vs the flat reference across node
counts, cross-host HLO accounting, bucketed sync) runs in a subprocess
with 16 fake CPU devices — tests/distributed_checks/hierarchical_check.py.
The units below cover the meshless pieces: effective-node accounting,
config/registry validation, and the reduce-scatter decode kernels
(stitched shards == the flat decode, bit for bit).
"""
import dataclasses
import os
import pathlib
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import comm_cost, types, wire
from repro.kernels.bernoulli_wire import ref as bw_ref
from repro.kernels.threefry import ref as tf_ref

ROOT = pathlib.Path(__file__).resolve().parent.parent


@pytest.mark.distributed
def test_hierarchical_check():
    env = dict(os.environ)
    env["PYTHONPATH"] = str(ROOT / "src")
    env.pop("XLA_FLAGS", None)
    res = subprocess.run(
        [sys.executable, str(ROOT / "tests" / "distributed_checks" /
                             "hierarchical_check.py")],
        env=env, capture_output=True, text=True, timeout=600)
    assert res.returncode == 0, f"\nSTDOUT:\n{res.stdout}\nSTDERR:\n{res.stderr}"
    assert "ALL HIERARCHICAL CHECKS PASSED" in res.stdout


def _cfg(kind, **kw):
    return types.CompressionConfig(
        encoder=types.EncoderSpec(kind=kind, fraction=1.0 / 16,
                                  center="mean"),
        mode="gather_decode", axes=("pod",), inner_axes=("data",),
        scatter_decode=True, wire_dtype="float32", min_compress_size=0,
        **kw)


# --------------------------------------------------------------------------- #
# effective-node accounting (the flat-world-size bugfix).
# --------------------------------------------------------------------------- #

def test_effective_nodes_flat_is_identity():
    flat = dataclasses.replace(_cfg("fixed_k"), inner_axes=(),
                               scatter_decode=False)
    assert wire.effective_nodes(flat, 8) == 8
    # flat configs ignore mesh_sizes entirely
    assert wire.effective_nodes(flat, 8, {"bogus": 3}) == 8


def test_effective_nodes_divides_by_inner_group():
    cfg = _cfg("fixed_k")
    assert wire.effective_nodes(cfg, 8, {"pod": 4, "data": 2}) == 4
    assert wire.effective_nodes(cfg, 16, {"pod": 2, "data": 8}) == 2


def test_effective_nodes_requires_mesh_sizes():
    cfg = _cfg("fixed_k")
    with pytest.raises(ValueError, match="mesh_sizes"):
        wire.effective_nodes(cfg, 8)
    with pytest.raises(ValueError, match="missing from mesh_sizes"):
        wire.effective_nodes(cfg, 8, {"pod": 4})
    with pytest.raises(ValueError, match="not divisible"):
        wire.effective_nodes(cfg, 8, {"pod": 4, "data": 3})


def test_cost_config_threads_mesh_sizes():
    cfg = _cfg("bernoulli")
    codec = wire.resolve(cfg)
    got = comm_cost.cost_config(cfg, n=8, d=4096,
                                mesh_sizes={"pod": 4, "data": 2})
    assert got == codec.wire_bits(4, 4096, cfg) + codec.seed_bits(4, cfg)
    with pytest.raises(ValueError, match="mesh_sizes"):
        comm_cost.cost_config(cfg, n=8, d=4096)


# --------------------------------------------------------------------------- #
# config / registry validation.
# --------------------------------------------------------------------------- #

def test_inner_axes_must_be_disjoint_from_axes():
    with pytest.raises(ValueError, match="disjoint"):
        dataclasses.replace(_cfg("fixed_k"), inner_axes=("pod", "data"))


def test_scatter_decode_flat_resolves_for_linear_codecs():
    # §12: flat (single-axis) scatter is legal for coordinate-partitionable
    # codecs — the decode shards over cfg.axes itself.
    for kind in ("fixed_k", "bernoulli"):
        flat = dataclasses.replace(_cfg(kind), inner_axes=())
        codec = wire.resolve(flat)
        assert codec.scatter_supported
        assert wire.scatter_axes(flat) == ("pod",)
    # hier configs still shard over the inner axes
    assert wire.scatter_axes(_cfg("fixed_k")) == ("data",)


def test_scatter_decode_resolves_for_bitplane_codecs():
    # §13: the packed plane decodes partition too, on word-aligned shards.
    cfg = types.CompressionConfig(
        encoder=types.EncoderSpec(kind="binary", center="min"),
        mode="gather_decode", axes=("pod",), inner_axes=("data",),
        scatter_decode=True)
    codec = wire.resolve(cfg)
    assert codec.scatter_supported
    assert wire.scatter_word_align(cfg) == 32
    tern = types.CompressionConfig(
        encoder=types.EncoderSpec(kind="ternary", fraction=1.0 / 16,
                                  center="min"),
        mode="gather_decode", axes=("pod",), scatter_decode=True)
    assert wire.scatter_word_align(tern) == 16


def test_resolve_rejects_scatter_for_psum_codec():
    # psum codecs decode a reduced wire — there are no per-peer rows to
    # shard, so scatter_decode cannot compose with them.
    cfg = types.CompressionConfig(
        encoder=types.EncoderSpec(kind="fixed_k", fraction=1.0 / 16,
                                  center="mean"),
        mode="shared_support", axes=("pod",), scatter_decode=True)
    with pytest.raises(ValueError, match="scatter_decode"):
        wire.resolve(cfg)
    # the same schedule WITHOUT scatter is fine
    wire.resolve(dataclasses.replace(cfg, scatter_decode=False))


# --------------------------------------------------------------------------- #
# reduce-scatter decode kernels, meshless: stitched shards == flat decode.
# --------------------------------------------------------------------------- #

def test_fixed_k_shard_concat_matches_flat_decode():
    d, n = 5000, 4
    cfg = _cfg("fixed_k")
    codec = wire.resolve(cfg)
    key = jax.random.PRNGKey(3)
    xs = jax.random.normal(jax.random.PRNGKey(4), (n, d))
    rows = jnp.stack([codec.pack(xs[i], key, i, cfg) for i in range(n)])
    want = np.asarray(codec.decode_gathered(rows, key, cfg, d, n))
    for nshards in (2, 4):
        parts = [codec.decode_gathered_shard(rows, key, cfg, d, n,
                                             s, nshards)
                 for s in range(nshards)]
        got = np.asarray(jnp.concatenate(parts))[:d]
        assert np.array_equal(got, want), nshards


def test_bernoulli_support_shards_stitch_to_full_draw():
    d, n, p = 1000, 3, 1.0 / 16
    keys = jnp.stack([jax.random.fold_in(jax.random.PRNGKey(5), i)
                      for i in range(n)])
    full = np.asarray(jax.vmap(lambda k: tf_ref.uniform(k, d))(keys) < p)
    for nshards in (2, 3):
        ds = -(-d // nshards)
        parts = [bw_ref.support_shard(keys, p, d, s * ds, ds)
                 for s in range(nshards)]
        got = np.asarray(jnp.concatenate(parts, axis=1))
        assert not got[:, d:].any()      # padding lanes decode dead
        assert np.array_equal(got[:, :d], full), nshards


def test_bernoulli_shard_decode_matches_flat_decode():
    d, n, p = 1000, 3, 1.0 / 16
    cap = comm_cost.bernoulli_capacity(d, p)
    k0 = jax.random.PRNGKey(6)
    keys = jnp.stack([jax.random.fold_in(k0, i) for i in range(n)])
    bufs = jax.random.normal(jax.random.fold_in(k0, 100), (n, cap))
    mus = jax.random.normal(jax.random.fold_in(k0, 101), (n,))
    want = np.asarray(bw_ref.decode_sum(bufs, mus, keys, p, cap, d))
    for nshards in (2, 3):
        ds = -(-d // nshards)
        sent = [bw_ref.support_shard(keys, p, d, s * ds, ds)
                for s in range(nshards)]
        # the rank offset the scatter path derives from its one inner
        # all_gather: each peer's support count strictly before the shard
        counts = jnp.stack([jnp.sum(s.astype(jnp.int32), axis=1)
                            for s in sent])
        prior = jnp.cumsum(counts, axis=0) - counts
        parts = [bw_ref.decode_sum_shard(bufs, mus, sent[s], prior[s], cap)
                 for s in range(nshards)]
        got = np.asarray(jnp.concatenate(parts))[:d]
        assert np.array_equal(got, want), nshards


def test_uniform_at_matches_batch_uniform():
    # the random-access Threefry draw the sharded support regenerates from
    # must be bit-exact vs the batch draw peers encode with
    key = jax.random.PRNGKey(7)
    for d in (1, 2, 255, 256, 257, 1000):
        want = np.asarray(tf_ref.uniform(key, d))
        got = np.asarray(tf_ref.uniform_at(
            key, jnp.arange(d, dtype=jnp.int32), d))
        assert np.array_equal(got, want), d
