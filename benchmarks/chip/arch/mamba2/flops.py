"""Model FLOPs of a Mamba-2 forward pass, from the configuration's shapes.

Counted per token, with no recomputation: the dense projections (z, x, B,
C, dt in; out), the tied head, and the SSD chunk terms (the chunk's C.B
scores, their product with x, the chunk-state build and its read-out). The
intra-chunk products are counted over the whole chunk block, as the chunked
algorithm computes them. Norms, the depthwise convolution, gates and the
loss's elementwise work are left out.
"""
from __future__ import annotations

from benchmarks.chip.arch.mamba2.weights import dims


def forward_per_token(cfg: dict, seq_len: int) -> float:
    k = dims(cfg)
    d, din, n, h, p = k["d"], k["din"], k["N"], k["H"], k["P"]
    q = min(k["Q"], seq_len)
    proj = 2 * d * (2 * din + 2 * n + h) + 2 * din * d
    ssd = 2 * q * n + 2 * q * h * p + 4 * h * p * n
    head = 2 * d * k["V"]
    return float(k["L"] * (proj + ssd) + head)
