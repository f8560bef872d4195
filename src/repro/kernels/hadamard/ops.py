"""Public jit'd wrapper for the Walsh–Hadamard transform.

Dispatch (repro.kernels.backend, resolved once at import):
* TPU backend        → Pallas butterfly kernel (hadamard.py);
* CPU / other        → the pure-jnp butterfly (ref.py).
Both run the same f32 add chain, so the results are bit-identical
(tests/test_kernels.py; chip_smoke.py on the chip).  Rows shorter than one
(8, 128) tile (hadamard.MIN_D) always take the jnp path.

Vectors longer than MAX_D are processed in independent MAX_D chunks (a
block-diagonal rotation; standard bucketing — see DESIGN.md §2).
"""
from __future__ import annotations

from repro.kernels import backend
from repro.kernels.hadamard import hadamard as _kernel
from repro.kernels.hadamard import ref as _ref

MAX_D = 1 << 20


def fwht(x, *, force_pallas: bool = False):
    """Unnormalized Walsh–Hadamard transform along the last axis.

    x: (..., d) with d a power of two, d ≤ 2**20.
    """
    d = x.shape[-1]
    if d & (d - 1):
        raise ValueError(f"fwht needs power-of-two length, got {d}")
    if d > MAX_D:
        raise ValueError(f"fwht supports d ≤ {MAX_D}; chunk the input "
                         "(repro.core.rotation handles this)")
    use_pallas, interpret = backend.choose(force_pallas)
    if not use_pallas or d < _kernel.MIN_D:
        return _ref.fwht(x)
    y = _kernel.fwht_pallas(x.reshape(-1, d), interpret=interpret)
    return y.reshape(x.shape).astype(x.dtype)
