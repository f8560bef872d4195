"""Every main-path wire kernel compiles for a TPU v5e.

Interpret mode runs the kernel bodies on the CPU but cannot see what the
TPU compiler refuses: reshapes that split the lane axis, unsigned
reductions, a block that overruns VMEM.  These tests compile each entry
point at real size — d = 2^20, n = 8 peers for the decodes, p = 1/16 — for
a described v5e (no chip needed) and assert that the Pallas kernel is in
the compiled program (``tpu_custom_call``).  The Bernoulli kernels also
compile at the length of the benchmark's largest Bernoulli bucket, where
their resident wire buffer and widest grid step are largest.

The topology and everything built from it live in module-scoped fixtures:
the TPU library may be loaded by one process at a time, so nothing here
touches it at import or collection.
"""
import functools
import os

import jax
import jax.numpy as jnp
import pytest

D = 1 << 20
N = 8
P_SEND = 1.0 / 16
# the largest compressed bucket of the benchmark cell
# mamba2-370m.train.bernoulli_seed_1bit: one 48-layer (1024 × 2048) leaf
# (w_out, w_x, w_z), synced on one chip (n = 1, one shard of length D_CELL)
D_CELL = 48 * 2048 * 1024


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    # needs the TPU library (jax[tpu]); without it this fails, never skips
    return topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_cache():
    """A described chip cannot read back what it writes to the persistent
    compilation cache, so keep the cache out of these compiles."""
    from jax.experimental.compilation_cache import compilation_cache
    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", before)


def _entry(name):
    """(function, [(shape, dtype)]) for one kernel entry point."""
    from repro.core import comm_cost
    from repro.kernels.bernoulli_wire import kernel as bwk
    from repro.kernels.bitplane import bitplane as bpk
    from repro.kernels.fixed_k_encode import fixed_k_encode as fkk
    from repro.kernels.hadamard import hadamard as hk
    from repro.kernels.rotated_encode import kernel as rek

    cap = comm_cost.bernoulli_capacity(D, P_SEND)
    cap_cell = comm_cost.bernoulli_capacity(D_CELL, P_SEND)
    f32, u32, i32 = jnp.float32, jnp.uint32, jnp.int32
    table = {
        "bernoulli_encode": (
            lambda x, k, m: bwk.encode_pallas(x, k, m, p=P_SEND, cap=cap),
            [((D,), f32), ((2,), u32), ((), f32)]),
        "bernoulli_decode_sum": (
            lambda b, m, k: bwk.decode_sum_pallas(b, m, k, p=P_SEND,
                                                  cap=cap, d=D),
            [((N, cap), f32), ((N,), f32), ((N, 2), u32)]),
        "bernoulli_decode_sum_shard": (
            lambda b, m, k, pr, s: bwk.decode_sum_shard_pallas(
                b, m, k, pr, s, p=P_SEND, cap=cap, d=D, ds=D // N),
            [((N, cap), f32), ((N,), f32), ((N, 2), u32), ((N,), i32),
             ((), i32)]),
        "bernoulli_encode_cell": (
            lambda x, k, m: bwk.encode_pallas(x, k, m, p=P_SEND,
                                              cap=cap_cell),
            [((D_CELL,), f32), ((2,), u32), ((), f32)]),
        "bernoulli_decode_sum_cell": (
            lambda b, m, k: bwk.decode_sum_pallas(b, m, k, p=P_SEND,
                                                  cap=cap_cell, d=D_CELL),
            [((1, cap_cell), f32), ((1,), f32), ((1, 2), u32)]),
        "bernoulli_decode_sum_shard_cell": (
            lambda b, m, k, pr, s: bwk.decode_sum_shard_pallas(
                b, m, k, pr, s, p=P_SEND, cap=cap_cell, d=D_CELL,
                ds=D_CELL),
            [((1, cap_cell), f32), ((1,), f32), ((1, 2), u32), ((1,), i32),
             ((), i32)]),
        "pack_bits_w1": (functools.partial(bpk.pack_bits_2d, width=1),
                         [((D // 128, 128), u32)]),
        "pack_bits_w2": (functools.partial(bpk.pack_bits_2d, width=2),
                         [((D // 128, 128), u32)]),
        "unpack_bits_w1": (functools.partial(bpk.unpack_bits_2d, width=1),
                           [((D // 32 // 128, 128), u32)]),
        "unpack_bits_w2": (functools.partial(bpk.unpack_bits_2d, width=2),
                           [((D // 16 // 128, 128), u32)]),
        "binary_accum": (bpk.binary_accum_2d,
                         [((N, D // N // 32 // 128, 128), u32),
                          ((N, 128), f32)]),
        "rotated_encode_pack": (
            lambda z, k, a, b: rek.encode_pack_pallas(z, k, a, b, dp=D),
            [((D,), f32), ((2,), u32), ((), f32), ((), f32)]),
        "rotate_minmax": (
            lambda x, s: hk.fwht_pallas(x, s, inv_scale=1.0 / 1024,
                                        with_minmax=True),
            [((1, D), f32), ((1, D), f32)]),
        "fwht": (hk.fwht_pallas, [((1, D), f32)]),
        "fixed_k_gather": (fkk.fixed_k_gather_2d,
                           [((D // 1024, 8, 128), f32), ((64,), i32),
                            ((1, 2), f32)]),
    }
    return table[name]


ENTRIES = ("bernoulli_encode", "bernoulli_decode_sum",
           "bernoulli_decode_sum_shard", "pack_bits_w1", "pack_bits_w2",
           "unpack_bits_w1", "unpack_bits_w2", "binary_accum",
           "rotated_encode_pack", "rotate_minmax", "fwht", "fixed_k_gather",
           "bernoulli_encode_cell", "bernoulli_decode_sum_cell",
           "bernoulli_decode_sum_shard_cell")


@pytest.mark.parametrize("name", ENTRIES)
def test_kernel_compiles_for_v5e(name, one_chip, no_cache):
    fn, shapes = _entry(name)
    args = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip) for s, dt in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text(), name
