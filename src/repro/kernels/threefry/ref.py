"""The repo's own Threefry-2x32 PRNG stream (docs/DESIGN.md §10).

Every draw that decides a wire byte goes through this module: the §4.4
seed-trick supports, the fixed-k block supports (Gumbel top-k), the
binary/ternary branch draws and the rotation's Rademacher signs.  Peers
regenerate each other's supports from seeds, and the committed golden wire
bytes (tests/golden/golden_wire.npz) pin them, so the stream must not
depend on anything outside the repo — in particular not on jax's
partitionable-Threefry switch, whose default flipped to True in jax 0.5
and changed what ``jax.random.uniform`` returns for the same key.

The stream is defined as jax's *legacy* (non-partitionable) layout, which
the golden bytes were generated with.  It is written in plain uint32
jnp/lax ops that work identically inside Pallas kernel bodies and in XLA,
so the fused wire kernels (repro.kernels.bernoulli_wire,
repro.kernels.rotated_encode) inline the same functions their oracles
call.  tests/test_threefry_ref.py pins it against ``jax.random`` with the
legacy layout switched on inside each test.

Counter layout (jax's legacy ``threefry_random_bits``): for shape (d,) the
raw counter ``arange(d)`` is zero-padded to 2·⌈d/2⌉, split in half — NOT
interleaved — so lane j < half comes from cipher output x0 of the pair
(j, half + j) and lane j ≥ half from x1 of (j − half, j).  The zero pad
means the last x1 counter is 0 when d is odd.  A multi-dimensional shape
draws ``prod(shape)`` lanes and reshapes, row-major.

Keys are either raw (2,) uint32 words (``jax.random.PRNGKey``) or typed
threefry keys (``jax.random.key``).  ``jax.random.fold_in`` does not
depend on the layout (it is one cipher call on the key), so callers keep
deriving keys with it.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

# Threefry-2x32 constants: key-schedule parity word and the 4-round
# rotation schedules (20 rounds = 5 groups of 4, alternating schedules).
_PARITY = 0x1BD11BDA
_ROTATIONS = ((13, 15, 26, 6), (17, 29, 16, 24))


def _rotl(x, r: int):
    return (x << jnp.uint32(r)) | (x >> jnp.uint32(32 - r))


def threefry2x32(k0, k1, x0, x1):
    """The 20-round Threefry-2x32 block cipher on uint32 arrays.

    ``k0, k1`` are the key words (scalars or arrays broadcastable to the
    counters), ``x0, x1`` the counter words.  Returns the two output words.
    Pure uint32 ops — usable verbatim inside a Pallas kernel body.
    """
    k0 = jnp.asarray(k0, jnp.uint32)
    k1 = jnp.asarray(k1, jnp.uint32)
    ks = (k0, k1, k0 ^ k1 ^ jnp.uint32(_PARITY))
    x0 = jnp.asarray(x0, jnp.uint32) + ks[0]
    x1 = jnp.asarray(x1, jnp.uint32) + ks[1]
    for group in range(5):
        for r in _ROTATIONS[group % 2]:
            x0 = x0 + x1
            x1 = _rotl(x1, r)
            x1 = x1 ^ x0
        x0 = x0 + ks[(group + 1) % 3]
        x1 = x1 + ks[(group + 2) % 3] + jnp.uint32(group + 1)
    return x0, x1


def counter_words(idx, d: int):
    """The (x0, x1) counter words feeding coordinate ``idx`` of a (d,) draw.

    ``idx`` is any uint32 array of flat coordinate indices < d.  Encodes the
    split-halves layout above so callers (kernels) can evaluate scattered
    coordinate blocks without materializing the full counter array.
    """
    idx = jnp.asarray(idx, jnp.uint32)
    half = (d + 1) // 2
    lo = idx < half                       # lane from x0 of pair (idx, idx+half)
    pair = jnp.where(lo, idx, idx - half)
    c1 = pair + jnp.uint32(half)
    # zero pad: counter positions ≥ d hold 0 (odd-d last x1 word).
    c1 = jnp.where(c1 < d, c1, jnp.uint32(0))
    return pair, c1, lo


def key_words(key):
    """The (2,) uint32 key words of a raw or typed threefry key."""
    if jax.dtypes.issubdtype(jnp.result_type(key), jax.dtypes.prng_key):
        key = jax.random.key_data(key)
    return jnp.asarray(key).reshape(2).astype(jnp.uint32)


def _size(shape) -> int:
    d = math.prod(shape) if isinstance(shape, tuple) else int(shape)
    if d >= 1 << 32:  # legacy layout splits the key beyond one counter span
        raise ValueError(f"draw of {d} lanes exceeds one counter span")
    return d


def random_bits(key, d: int):
    """``jax.random.bits(key, (d,), 'uint32')`` in the legacy layout."""
    key = key_words(key)
    half = (d + 1) // 2
    cnt = jnp.arange(d, dtype=jnp.uint32)
    cnt = jnp.pad(cnt, (0, 2 * half - d))
    o0, o1 = threefry2x32(key[0], key[1], cnt[:half], cnt[half:])
    return jnp.concatenate([o0, o1])[:d]


def bits_to_uniform(bits):
    """uint32 bits -> U[0, 1) float32, exactly as jax.random.uniform does:
    fill the f32 mantissa (value in [1, 2)), subtract 1, clamp at 0."""
    fbits = (bits >> jnp.uint32(9)) | jnp.uint32(0x3F800000)
    return jnp.maximum(
        jax.lax.bitcast_convert_type(fbits, jnp.float32) - jnp.float32(1.0),
        jnp.float32(0.0))


def uniform(key, shape):
    """``jax.random.uniform(key, shape, jnp.float32)``, legacy layout.

    ``shape`` is an int (flat length) or a tuple."""
    u = bits_to_uniform(random_bits(key, _size(shape)))
    return u.reshape(shape)


def gumbel(key, shape):
    """``jax.random.gumbel(key, shape, jnp.float32)``, legacy layout.

    jax draws ``uniform(minval=tiny, maxval=1)`` — mantissa-filled floats
    scaled by the f32 span ``1 − tiny`` (which rounds to 1), shifted by
    ``tiny`` and clamped below at ``tiny`` — then takes −log(−log(u)).
    Reproduced op for op.
    """
    tiny = jnp.float32(np.finfo(np.float32).tiny)
    bits = random_bits(key, _size(shape))
    fbits = (bits >> jnp.uint32(9)) | jnp.uint32(0x3F800000)
    floats = (jax.lax.bitcast_convert_type(fbits, jnp.float32)
              - jnp.float32(1.0))
    u = jnp.maximum(tiny, floats * (jnp.float32(1.0) - tiny) + tiny)
    return (-jnp.log(-jnp.log(u))).reshape(shape)


def rademacher(key, shape, dtype=jnp.float32):
    """``jax.random.rademacher(key, shape, dtype)``, legacy layout: a
    Bernoulli(0.5) draw (``uniform < 0.5``) mapped to ±1."""
    b = (uniform(key, shape) < jnp.float32(0.5)).astype(dtype)
    return (2 * b - 1).astype(dtype)


def uniform_at(key, idx, d: int):
    """``jax.random.uniform(key, (d,), f32)[idx]`` without the (d,) draw.

    ``idx``: any int array of coordinate indices < d.  Evaluates only the
    cipher pairs feeding those lanes via :func:`counter_words` — the
    scattered-coordinate primitive the reduce-scatter decode shard uses to
    regenerate just its own slice of every peer's support.  Bit-exact vs
    the full draw (tests/test_threefry_ref.py).
    """
    key = key_words(key)
    c0, c1, lo = counter_words(idx, d)
    o0, o1 = threefry2x32(key[0], key[1], c0, c1)
    return bits_to_uniform(jnp.where(lo, o0, o1))
