"""Dispatch for the fused rotate+encode path of RotatedCodec(binary).

Off-TPU this is EXACTLY the historical two-stage chain
(rotation.rotate → bitplane.binary_pack) — same butterfly FWHT, same
encoder draws, same bytes (golden matrix).  On TPU (or when forced) the
fused Pallas pair replaces it — the butterfly FWHT kernel with the signs,
scale and (min, max) partials folded in, then
repro.kernels.rotated_encode.kernel's threshold+draw+pack kernel — with
the chunk partials reduced between them.  Both paths run the same f32
op chains, so they emit the same bytes.  Backend policy:
repro.kernels.backend (module-level, never trace-time).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core import bitplane, rotation
from repro.kernels import backend
from repro.kernels.hadamard import hadamard as hk
from repro.kernels.hadamard import ops as hops
from repro.kernels.rotated_encode import kernel


def pack_binary(flat, key, rank, wire_dtype, *, force_pallas: bool = False):
    """RotatedCodec(inner=binary).pack: (d,) f32 -> uint32 wire buffer
    [1-bit plane of dp = padded_dim(d) coords ‖ (vmin, vmax)]."""
    use_pallas, interpret = backend.choose(force_pallas)
    krot = rotation.rotation_key(key)
    kenc = jax.random.fold_in(key, rank)
    d = flat.shape[0]
    dp = rotation.padded_dim(d)
    if not use_pallas or dp < hk.MIN_D:
        # below one (8, 128) tile: not a kernel target (real buckets sit
        # far above min_compress_size anyway).
        z = rotation.rotate(krot, flat)
        return bitplane.binary_pack(z, kenc, wire_dtype)
    c = min(dp, hops.MAX_D)
    signs = rotation.rademacher_diag(krot, dp, jnp.float32)
    xp = jnp.pad(flat.astype(jnp.float32), (0, dp - d))
    z2, mm = hk.fwht_pallas(xp.reshape(-1, c), signs.reshape(-1, c),
                            inv_scale=rotation.inv_scale(c),
                            with_minmax=True, interpret=interpret)
    vmin = jnp.min(mm[:, 0])
    vmax = jnp.max(mm[:, 1])
    plane = kernel.encode_pack_pallas(z2.reshape(-1), kenc, vmin, vmax,
                                      dp=dp, interpret=interpret)
    tail = bitplane.floats_to_words(jnp.stack([vmin, vmax]), wire_dtype)
    return jnp.concatenate([plane, tail])
