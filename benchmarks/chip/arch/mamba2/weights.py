"""Mamba-2's leaves and how each is drawn.

Leaf names and shapes follow the program's parameter tree (``embed``,
``layers.ssm.w_z``, ...); the harness checks that they match before it
hands the weights over.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


def dims(cfg: dict) -> dict:
    d = cfg["d_model"]
    din = cfg["expand"] * d
    return {"L": cfg["n_layer"], "d": d, "din": din,
            "N": cfg["ngroups"] * cfg["d_state"], "H": din // cfg["headdim"],
            "P": cfg["headdim"], "W": cfg["d_conv"], "V": cfg["vocab_size"],
            "Q": cfg["chunk_size"]}


def param_shapes(cfg: dict) -> dict:
    k = dims(cfg)
    L, d, din, N, H, W = k["L"], k["d"], k["din"], k["N"], k["H"], k["W"]
    return {
        "embed": (k["V"], d), "final_norm": (d,), "layers.norm1": (L, d),
        "layers.ssm.w_z": (L, d, din), "layers.ssm.w_x": (L, d, din),
        "layers.ssm.w_B": (L, d, N), "layers.ssm.w_C": (L, d, N),
        "layers.ssm.w_dt": (L, d, H), "layers.ssm.conv_x": (L, W, din),
        "layers.ssm.conv_B": (L, W, N), "layers.ssm.conv_C": (L, W, N),
        "layers.ssm.A_log": (L, H), "layers.ssm.D": (L, H),
        "layers.ssm.dt_bias": (L, H), "layers.ssm.norm": (L, din),
        "layers.ssm.w_out": (L, din, d),
    }


def init_leaf(name: str, shape, key, cfg: dict):
    k = dims(cfg)
    leaf = name.rsplit(".", 1)[-1]
    if leaf in ("final_norm", "norm1", "norm", "D"):
        return jnp.ones(shape, jnp.float32)
    if leaf == "A_log":
        return jnp.log(jax.random.uniform(key, shape, jnp.float32, 1.0, 16.0))
    if leaf == "dt_bias":
        dt = jnp.exp(jax.random.uniform(key, shape, jnp.float32,
                                        np.log(1e-3), np.log(1e-1)))
        dt = jnp.maximum(dt, 1e-4)
        return dt + jnp.log(-jnp.expm1(-dt))      # inverse softplus
    if leaf == "embed":
        std = 0.02
    elif leaf.startswith("conv_"):
        std = k["W"] ** -0.5
    elif leaf == "w_out":
        std = k["din"] ** -0.5 / np.sqrt(k["L"])
    else:                                          # input projections
        std = k["d"] ** -0.5
    return std * jax.random.normal(key, shape, jnp.float32)
