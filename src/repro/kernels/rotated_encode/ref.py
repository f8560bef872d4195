"""jnp oracle for the fused §7.2 1-bit encode kernel.

:func:`binary_plane` — the §4.5 stochastic 1-bit plane for a rotated
vector given the global (vmin, vmax): exactly encode_binary's branch draw
(same Threefry stream via repro.kernels.threefry.ref, same guarded,
correctly rounded threshold) packed into uint32 words by the
kernels/bitplane reference layout.  The rotation itself needs no separate
oracle: the FWHT kernel runs the CPU butterfly's exact add chain
(repro.kernels.hadamard.hadamard).

Kernel↔oracle equivalence is exact (interpret mode, CPU), pinned by
tests/test_rotated_encode_kernel.py.
"""
from __future__ import annotations

import jax.numpy as jnp

from repro.kernels.bitplane import ref as bp_ref
from repro.kernels.numerics import div_rn
from repro.kernels.threefry import ref as tref


def binary_plane(z, key, vmin, vmax, dp: int):
    """(dp,) rotated z + global (vmin, vmax) -> packed 1-bit plane words.

    The op chain of encoders.encode_binary with the min/max already
    reduced: p = (z − vmin)/Δ (guarded for Δ = 0), one Threefry uniform
    draw per coordinate, take-max bits packed 32/word little-endian.
    """
    delta = vmax - vmin
    p = jnp.where(delta > 0,
                  div_rn(z - vmin, jnp.where(delta > 0, delta, 1.0)), 0.0)
    u = tref.uniform(key, dp)
    bits = u < p
    return bp_ref.pack_bits(bits.astype(jnp.uint32), 1)
