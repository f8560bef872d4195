"""Fused Pallas TPU kernels for RotatedCodec(inner=binary) packing.

The pre-fusion TPU path made four HBM round trips per bucket: the FWHT
kernel wrote z, XLA re-read z for min/max, re-read it again for the
stochastic threshold (with a separate d-wide uniform tensor), and the
bit-plane pack kernel re-read the dense bits.  The fused path makes two:

* the rotation — :func:`repro.kernels.hadamard.hadamard.fwht_pallas` with
  the Rademacher signs multiplied in before the butterfly, the 1/√c scale
  after it, and (min, max) partials emitted alongside z — so the bracket
  scalars cost no extra pass;

* ``encode_pack_pallas`` — one kernel turns z into wire words: the
  take-max probabilities (the correctly rounded quotient of
  repro.kernels.numerics), the Threefry branch draw
  (repro.kernels.threefry.ref inlined), and the 1-bit plane packing all
  happen in-register per (256, 128) block, writing only the packed words.

Global (vmin, vmax) needs all chunks' partials, so the two kernels cannot
merge for multi-chunk buckets (dp > MAX_D, block-diagonal Q) — the partial
reduce between them is a (nchunks, 2) jnp min/max, order-free and exact.

Oracle contract: the butterfly FWHT and the correctly rounded threshold
make every step bit-identical to the CPU production chain
(rotation.rotate → bitplane.binary_pack), so the fused path emits the
golden wire bytes (tests/test_rotated_encode_kernel.py, interpret mode;
chip_smoke.py on the chip).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.bernoulli_wire import kernel as bw_kernel
from repro.kernels.bitplane.bitplane import pack_fields
from repro.kernels.numerics import div_rn

LANES = 128
PACK_ROWS = 256            # (256, 128) coords -> (8, 128) u32 words per step


def _encode_pack_kernel(key_ref, par_ref, z_ref, o_ref, *, dp: int):
    i = pl.program_id(0)
    idx, mask = bw_kernel._block_coords(i, dp, rows=PACK_ROWS)
    u = bw_kernel._uniform_block(key_ref[0], key_ref[1], idx, dp)
    vmin = par_ref[0]
    delta = par_ref[1] - vmin
    z = z_ref[...]
    # encode_binary's guarded threshold, elementwise, with the same
    # correctly rounded quotient (div_rn) as the CPU path.
    p = jnp.where(delta > 0,
                  div_rn(z - vmin, jnp.where(delta > 0, delta, 1.0)), 0.0)
    o_ref[...] = pack_fields((mask & (u < p)).astype(jnp.int32), 1)


@functools.partial(jax.jit, static_argnames=("dp", "interpret"))
def encode_pack_pallas(z, key, vmin, vmax, *, dp: int,
                       interpret: bool = False):
    """z: (dp,) f32 rotated vector; key: (2,) uint32 (rank-folded);
    vmin/vmax: f32 scalars.  Returns the (ceil(dp/32),) uint32 plane."""
    rows = -(-dp // LANES)
    rows = -(-rows // PACK_ROWS) * PACK_ROWS
    z2 = jnp.pad(z.astype(jnp.float32),
                 (0, rows * LANES - dp)).reshape(rows, LANES)
    key = jnp.asarray(key).reshape(2).astype(jnp.uint32)
    params = jnp.stack([jnp.asarray(vmin, jnp.float32),
                        jnp.asarray(vmax, jnp.float32)])
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(rows // PACK_ROWS,),
        in_specs=[pl.BlockSpec((PACK_ROWS, LANES), lambda i, *_: (i, 0))],
        out_specs=pl.BlockSpec((PACK_ROWS // 32, LANES),
                               lambda i, *_: (i, 0)),
        scratch_shapes=[],
    )
    words = pl.pallas_call(
        functools.partial(_encode_pack_kernel, dp=dp),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((rows // 32, LANES), jnp.uint32),
        name="rotated_encode",
        interpret=interpret,
    )(key, params, z2)
    return words.reshape(-1)[:-(-dp // 32)]
