"""Regenerate the golden wire-format matrix (tests/test_golden_wire.py).

For every preset in ``repro.configs.registry.COMPRESSION_PRESETS`` this
packs a fixed-seed input through the *resolved* codec and records the raw
wire-buffer bytes.  The committed ``golden_wire.npz`` pins the bit-level
wire format of every shipped preset: any change to buffer layout, PRNG
fold_in chains, capacity rules, packing order or wire dtype flips the
bytes and fails the conformance test — drift that MSE/accounting tests
cannot see (an estimator can stay unbiased while the wire format silently
changes under peers' feet).

Regen (ONLY when a wire-format change is intentional):

    PYTHONPATH=src python tests/golden/regen_golden_wire.py

and commit the refreshed .npz together with the change that caused it.
"""
from __future__ import annotations

import pathlib

import numpy as np

D = 4096          # power of two: the rotated presets pad to 2^k anyway
N_RANKS = 2       # two rows exercise the per-rank fold_in chains
X_SEED = 1234
KEY_SEED = 99

GOLDEN = pathlib.Path(__file__).resolve().parent / "golden_wire.npz"


def golden_inputs():
    """The (N_RANKS, D) f32 input rows: 0.5 · N(0, 1) from PRNGKey(X_SEED).

    Drawn from the repo's own Threefry stream (repro.kernels.threefry.ref)
    with jax's normal formula — √2 · erfinv(u), u uniform on
    (nextafter(−1, 0), 1) — so the rows do not depend on jax's
    partitionable-Threefry default, which flipped in jax 0.5 and changed
    what ``jax.random.normal`` returns for the same key.  These are the rows
    the committed .npz was generated from.
    """
    import jax
    import jax.numpy as jnp

    from repro.kernels.threefry import ref as threefry

    bits = threefry.random_bits(jax.random.PRNGKey(X_SEED), N_RANKS * D)
    lo = np.nextafter(np.float32(-1.0), np.float32(0.0))
    floats = jax.lax.bitcast_convert_type(
        (bits >> jnp.uint32(9)) | jnp.uint32(0x3F800000),
        jnp.float32) - jnp.float32(1.0)
    u = jnp.maximum(lo, floats * (jnp.float32(1.0) - lo) + lo)
    normal = np.float32(np.sqrt(2)) * jax.lax.erf_inv(u)
    return np.asarray(normal.reshape(N_RANKS, D) * 0.5)


def build_matrix():
    """{preset: (bytes uint8 [N_RANKS, nbytes], dtype str, slots int)}."""
    import jax
    import jax.numpy as jnp

    from repro.configs.registry import COMPRESSION_PRESETS, compression_preset
    from repro.core import wire

    xs = golden_inputs()
    key = jax.random.PRNGKey(KEY_SEED)  # key words: layout-independent
    out = {}
    for name in sorted(COMPRESSION_PRESETS):
        cfg = compression_preset(name, axes=("data",))
        codec = wire.resolve(cfg)
        rows = []
        for r in range(N_RANKS):
            buf = np.asarray(codec.pack(jnp.asarray(xs[r]), key, r, cfg))
            rows.append(np.frombuffer(buf.tobytes(), np.uint8))
        out[name] = (np.stack(rows), str(buf.dtype),
                     int(codec.wire_slots(D, cfg)))
    return out


def main():
    mat = build_matrix()
    arrays = {}
    for name, (rows, dtype, slots) in mat.items():
        arrays[f"{name}.bytes"] = rows
        arrays[f"{name}.dtype"] = np.asarray(dtype)
        arrays[f"{name}.slots"] = np.asarray(slots)
    np.savez_compressed(GOLDEN, **arrays)
    total = sum(a.nbytes for a in arrays.values())
    print(f"wrote {GOLDEN} ({len(mat)} presets, {total} raw bytes)")


if __name__ == "__main__":
    main()
