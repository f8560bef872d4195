"""Seeded weights, made on the device in one jitted call.

Both the program and the plain reference start from these weights, so the
reference takes nothing the program made. The leaves and how each is drawn
are the architecture's (``arch/<architecture>/weights.py``); every leaf
gets its own key, ``fold_in`` of the seed's key with the leaf's position
among the sorted names.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmarks.chip import arch, traffic


def param_shapes(cfg: dict) -> dict:
    """{leaf name: shape} of ``cfg``'s architecture."""
    return arch.part(cfg, "weights").param_shapes(cfg)


def make(cfg: dict, seed: int, shardings=None) -> dict:
    """All leaves from ``seed``; ``shardings`` maps names to placements."""
    a = arch.part(cfg, "weights")
    shapes = a.param_shapes(cfg)
    names = sorted(shapes)
    words = traffic.seed_words(seed, traffic.WEIGHTS_TAG, 2)

    def build(kw):
        base = jax.random.wrap_key_data(kw, impl="threefry2x32")
        return {n: a.init_leaf(n, shapes[n], jax.random.fold_in(base, i), cfg)
                for i, n in enumerate(names)}

    out_sh = None if shardings is None else {n: shardings[n] for n in names}
    return jax.jit(build, out_shardings=out_sh)(jnp.asarray(words, jnp.uint32))
