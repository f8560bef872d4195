"""Device time of the program's own scopes, from the step's compiled HLO.

The program names its gradient sync and the stages of each codec round
with ``jax.named_scope``: ``grad_sync/b<j>/{pack,exact,codec,unpack}`` in
``repro.train.bucketing``, and under ``codec`` the stages ``ef``,
``rotate``, ``encode``, ``exchange``, ``decode`` and ``unrotate`` set by
the hooks of ``repro.core.wire``. A scope reaches the compiled program
only as the ``op_name`` in an instruction's ``metadata={...}``; the
profiler's device events name the instruction and carry no ``op_name``.
So :func:`scope_map` reads the compiled HLO text and gives each
instruction the scopes on its path, and the readers look each device
operation up there by name.

JAX wraps a scope opened under a transformation in the transformation's
name: a scope opened in the sync point's ``custom_vjp`` backward rule
comes out as ``transpose(jvp(grad_sync))``. Those wrappers are unwrapped.
Components that name a jitted function (``jit(fwht_pallas)``) and the
primitive at the end of the path are left out, so a path holds the named
scopes alone (a Pallas kernel's ``name=`` is one of them).

A fused instruction is attributed by its own ``op_name``, which XLA takes
from the fusion's root: an operation fused into an instruction of another
scope is counted there. Only operations count (``Device.ops``), not
asynchronous transfers in flight. A stage is the innermost stage scope on
an instruction's path below ``codec``.
"""
from __future__ import annotations

import re

from benchmarks.chip import trace_reduce

STAGES = ("ef", "rotate", "encode", "exchange", "decode", "unrotate")
SYNC = ("grad_sync",)
CODEC = ("grad_sync", "codec")

_OP_NAME = re.compile(r'metadata=\{[^}]*?\bop_name="((?:[^"\\]|\\.)*)"')
_WRAPPED = re.compile(r"([\w.\-]+)\((.*)\)", re.S)
_ESCAPED = re.compile(r"\\(.)")
_FUNCTION_WRAPPERS = ("jit", "pjit")


def _split(path: str) -> list:
    """``path`` split at the slashes that no parenthesis encloses."""
    out, depth, cur = [], 0, []
    for ch in path:
        if ch == "/" and depth == 0:
            out.append("".join(cur))
            cur = []
            continue
        depth += (ch == "(") - (ch == ")")
        cur.append(ch)
    out.append("".join(cur))
    return out


def _components(parts) -> list:
    """The named scopes among ``op_name`` path components (module doc)."""
    out = []
    for comp in parts:
        m = _WRAPPED.fullmatch(comp)
        if m is None:
            if comp:
                out.append(comp)
            continue
        while m is not None:
            wrapper, comp = m.groups()
            m = _WRAPPED.fullmatch(comp)
        if wrapper not in _FUNCTION_WRAPPERS:
            out.extend(_components(_split(comp)))
    return out


def components(op_name: str) -> list:
    """The named scopes of one ``op_name``, outermost first."""
    return _components(_split(op_name)[:-1])


def scope_map(hlo_text: str) -> dict:
    """{instruction name: [scope, ...]} for every instruction of the HLO
    text that carries an ``op_name``."""
    out = {}
    for line in hlo_text.splitlines():
        m = trace_reduce._INSTR.match(line)
        op = _OP_NAME.search(line) if m else None
        if op:
            out[m.group(1)] = components(_ESCAPED.sub(r"\1", op.group(1)))
    return out


def under(path, scopes) -> bool:
    """True iff ``scopes`` appear on ``path`` in that order."""
    it = iter(path)
    return all(s in it for s in scopes)


def scope_ns(dev: trace_reduce.Device, smap: dict, scopes) -> float:
    """Summed durations of the device's operations under ``scopes``."""
    return float(sum(e - s for s, e, name, _ in dev.ops
                     if under(smap.get(name, ()), scopes)))


def stage(path) -> str | None:
    """The codec stage of an instruction under ``grad_sync``…``codec``:
    the innermost stage scope below ``codec``; None outside a codec
    round, or where no stage encloses it."""
    if not under(path, CODEC):
        return None
    below = path[len(path) - path[::-1].index("codec"):]
    return next((c for c in reversed(below) if c in STAGES), None)


def stage_ns(dev: trace_reduce.Device, smap: dict) -> dict:
    """{stage: summed durations} of the device's codec-round operations;
    an operation under ``codec`` with no stage counts under ``None``."""
    out = {}
    for s, e, name, _ in dev.ops:
        path = smap.get(name, ())
        if under(path, CODEC):
            k = stage(path)
            out[k] = out.get(k, 0.0) + float(e - s)
    return out


def _reading_scopes(r) -> dict:
    return getattr(r, "scopes", None) or {}


def per_step_ms(r, scopes) -> float | None:
    """Device ms a step under ``scopes``, averaged over the devices; None
    when no instruction of the compiled step carries them."""
    smap = _reading_scopes(r)
    if not any(under(p, scopes) for p in smap.values()):
        return None
    tot = sum(scope_ns(d, smap, scopes) for d in r.trace.devices)
    return tot / len(r.trace.devices) * 1e-6 / r.steps


def stage_ms(r) -> dict:
    """{stage: device ms a step} of the codec round, averaged over the
    devices; empty when the compiled step has no codec round."""
    smap = _reading_scopes(r)
    tot = {}
    for d in r.trace.devices:
        for k, v in stage_ns(d, smap).items():
            tot[k] = tot.get(k, 0.0) + v
    return {k: v / len(r.trace.devices) * 1e-6 / r.steps
            for k, v in tot.items()}
