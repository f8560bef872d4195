"""Plain float32 building blocks that every architecture's reference model
(``arch/<architecture>/model.py``) and the reference's rotation share.

Matmuls run at ``highest`` precision. The ``int8`` policy is the control,
one precision step below the configurations' bfloat16 and the one the
chip's int8 peak (twice its bf16 peak) tempts: a model runs every dense
projection and its head through :func:`dense`, which under ``int8`` makes
each an int8 training matmul (operands and the gradient flowing back
rounded to int8 under per-tensor scales).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST


def ein(spec, *ops):
    return jnp.einsum(spec, *ops, precision=HIGHEST)


def _int8(x):
    """x rounded to int8 under a symmetric per-tensor scale."""
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 127.0
    return jnp.round(x / scale).astype(jnp.int8).astype(jnp.float32) * scale


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def int8_einsum(spec, a, b):
    """An int8 training matmul: both operands, and the gradient flowing
    back, rounded to int8 with per-tensor scales; products and sums exact
    in float32."""
    return ein(spec, _int8(a), _int8(b))


def _int8_fwd(spec, a, b):
    qa, qb = _int8(a), _int8(b)
    return ein(spec, qa, qb), (qa, qb)


def _int8_bwd(spec, res, g):
    _, vjp = jax.vjp(functools.partial(ein, spec), *res)
    return vjp(_int8(g))


int8_einsum.defvjp(_int8_fwd, _int8_bwd)


def dense(policy: str):
    """The einsum the dense projections and the head use under ``policy``."""
    if policy == "f32":
        return ein
    if policy == "int8":
        return int8_einsum
    raise ValueError(f"unknown precision policy {policy!r}")


def rmsnorm(x, w, eps=1e-6):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w
