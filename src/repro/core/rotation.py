"""Random-rotation pre-processing (§7.2 / Remark 3, after Suresh et al. [10]).

Q = (1/√d)·H·D with H the Walsh–Hadamard matrix and D = diag(±1) random.
Q is orthogonal (QQᵀ = I), identified by a single seed (the paper's point:
negligible communication overhead), and computable in O(d log d).

The FWHT itself lives in :mod:`repro.kernels.hadamard` (Pallas kernel with
pure-jnp oracle); this module provides the seeded rotate / unrotate pair
used by the reference protocol stack (repro.core.protocol) and by the
composable wire-layer pre-transform (repro.core.wire.rotated).

Shape handling:
* non-power-of-two d is zero-padded to the next power of two (standard
  practice; :func:`unrotate` truncates), and
* d beyond the kernel's MAX_D (2^20) is processed in independent MAX_D
  chunks — a block-diagonal orthogonal Q, still seed-identified, so
  bucket-sized vectors (default bucket capacity 4M) rotate in one call.

:func:`padded_dim` is the single source of truth for the rotated length:
wire codecs wrapping a rotation size their buffers at ``padded_dim(d)``
(repro.core.wire.rotated.RotatedCodec.wire_slots).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.hadamard import ops as hadamard_ops
from repro.kernels.threefry import ref as threefry

# Domain tag for deriving the shared per-bucket rotation seed from the
# per-step key: distinct from the node ranks (0..n-1) and bucket indices
# folded elsewhere, so rotation draws never collide with encoder draws.
_ROTATION_TAG = 0x524F54  # "ROT"


def rotation_key(key):
    """The shared rotation seed: same on every node of the bucket's axes."""
    return jax.random.fold_in(key, _ROTATION_TAG)


def padded_dim(d: int) -> int:
    """Length after rotation: next power of two, or — beyond the FWHT
    kernel's MAX_D — the next multiple of MAX_D (block-diagonal Q)."""
    dp = 1 << max(0, (d - 1).bit_length())
    if dp <= hadamard_ops.MAX_D:
        return dp
    return -(-d // hadamard_ops.MAX_D) * hadamard_ops.MAX_D


def _pad(x, dp: int):
    d = x.shape[-1]
    if dp == d:
        return x
    pad = [(0, 0)] * (x.ndim - 1) + [(0, dp - d)]
    return jnp.pad(x, pad)


def rademacher_diag(key, d: int, dtype=jnp.float32):
    """The D of Q = (1/√d)HD: iid ±1 signs from a shared seed."""
    return threefry.rademacher(key, (d,), dtype)


def inv_scale(c: int) -> float:
    """1/√c as the f32 the normalization multiplies by.

    Computed on the host: XLA folds ``x / √c`` with a constant c into this
    multiply anyway, and a host constant keeps the rounding of √c off the
    device (the TPU's sqrt is not correctly rounded)."""
    return float(np.float32(1.0) / np.sqrt(np.float32(c)))


def _chunked_fwht(x):
    """FWHT over the last axis, block-diagonal in MAX_D chunks beyond it."""
    dp = x.shape[-1]
    c = min(dp, hadamard_ops.MAX_D)
    if dp == c:
        return hadamard_ops.fwht(x), c
    z = hadamard_ops.fwht(x.reshape(x.shape[:-1] + (dp // c, c)))
    return z.reshape(x.shape[:-1] + (dp,)), c


def rotate(key, x):
    """z = Qx.  x: (..., d) -> (..., padded_dim(d))."""
    xp = _pad(x, padded_dim(x.shape[-1]))
    dp = xp.shape[-1]
    signs = rademacher_diag(key, dp, xp.dtype)
    z, c = _chunked_fwht(xp * signs)
    return z * jnp.asarray(inv_scale(c), xp.dtype)


def unrotate(key, z, d: int):
    """x = Q⁻¹z = Qᵀz = (1/√d)·D·H·z, truncated back to the original d."""
    dp = z.shape[-1]
    signs = rademacher_diag(key, dp, z.dtype)
    h, c = _chunked_fwht(z)
    x = signs * h * jnp.asarray(inv_scale(c), z.dtype)
    return x[..., :d]
