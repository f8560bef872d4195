"""Collective-launch count + step time of the bucketed gradient sync vs the
per-leaf reference path, on an 8-device CPU mesh (subprocess: the device
count is locked at first jax init).

A realistic grad pytree has hundreds of leaves; the per-leaf rule issues one
collective per leaf while the bucketed rule issues one per bucket (a few).
The launch count is read from compiled HLO (loop-aware, launch/hlo_cost);
wall time is measured on the jitted sync alone.

Second sweep (:func:`collect_overlap`): overlapped vs post-backward issue
schedule (``BucketSpec.overlap``, DESIGN.md §9) per compression preset —
one grad+sync step of an MLP chain with each schedule, ms/step + launch
counts, recorded into BENCH_collectives.json's ``overlap`` section so the
perf trajectory tracks the schedule across PRs.  (On the single-stream CPU
backend the two schedules execute the same op set, so the times bound the
schedule's overhead rather than demonstrate the hiding a multi-stream
accelerator gets; the check asserts parity, not a win.)
"""
from __future__ import annotations

import json
import os
import pathlib
import subprocess
import sys
import time

_INNER = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import functools, json, time
import jax, jax.numpy as jnp
from jax.sharding import PartitionSpec as P
from repro.core import types
from repro.launch import hlo_cost
from repro.train import bucketing
from repro.train import train_step as ts

mesh = jax.make_mesh((8,), ("data",))
MESH_AXES = ("data",)

# 96 small + 24 large leaves — the shape of a real transformer grad tree.
SHAPES = {f"s_{i:03d}": (4096,) for i in range(96)}
SHAPES.update({f"l_{i:03d}": (65536,) for i in range(24)})
SPECS = {n: (None,) for n in SHAPES}

cmp = types.CompressionConfig(
    encoder=types.EncoderSpec(kind="fixed_k", fraction=1 / 16),
    mode="shared_support", axes=("data",), min_compress_size=65536)
plan = bucketing.build_plan(SHAPES, SPECS, MESH_AXES, {"data": 8}, cmp)

key0 = jax.random.PRNGKey(0)
XS = {n: jax.random.normal(jax.random.fold_in(key0, i), (8,) + SHAPES[n])
      for i, n in enumerate(sorted(SHAPES))}
IN_SPECS = {n: P("data", None) for n in SHAPES}
OUT_SPECS = {n: P() for n in SHAPES}


def make(fn):
    @functools.partial(jax.shard_map, mesh=mesh,
                       in_specs=(IN_SPECS, P()), out_specs=OUT_SPECS,
                       check_vma=False)
    def wrapped(xs, key):
        grads = {n: xs[n].reshape(SHAPES[n]) for n in xs}
        return fn(grads, key)
    return jax.jit(wrapped)


def perleaf(grads, key):
    out, _ = ts.sync_grads(grads, SPECS, MESH_AXES, cmp, key, ())
    return out


def bucketed(grads, key):
    out, _ = bucketing.sync_grads_bucketed(grads, plan, cmp, key)
    return out


def measure(fn):
    f = make(fn)
    comp = f.lower(XS, key0).compile()
    colls = sum(hlo_cost.analyze_text(comp.as_text()).coll_exec.values())
    f(XS, key0)  # warmup via the jit cache
    reps = 20
    t0 = time.perf_counter()
    for i in range(reps):
        out = f(XS, jax.random.fold_in(key0, i))
    jax.block_until_ready(out)
    return {"colls": colls, "us": (time.perf_counter() - t0) / reps * 1e6}

res = {"perleaf": measure(perleaf), "bucketed": measure(bucketed),
       "n_leaves": len(SHAPES), "n_buckets": len(plan.buckets)}
print(json.dumps(res))
"""


# --------------------------------------------------------------------------- #
# Overlapped vs post-backward issue schedule, per preset (subprocess).
# --------------------------------------------------------------------------- #

_OVERLAP_INNER = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import json, math, time
import jax

# the SAME step construction overlap_check.py validates (PYTHONPATH
# includes tests/distributed_checks): bench and check cannot diverge.
import overlap_harness as oh
from repro.launch import hlo_cost
from repro.train import bucketing

mesh = jax.make_mesh((8,), ("data",))
L = int(os.environ.get("BENCH_OVERLAP_L", 8))
M = int(os.environ.get("BENCH_OVERLAP_M", 128))
REPS = int(os.environ.get("BENCH_OVERLAP_REPS", 30))
PRESETS = ["none", "fixed_k_1bit", "bernoulli_seed_1bit", "binary_packed",
           "ternary_opt", "ef_rotated_binary"]

SHAPES, SPECS = oh.build_tree(L, M)
PARAMS = oh.init_params(SHAPES)
X = jax.random.normal(jax.random.PRNGKey(1), (32, M))
# total grad dimension of the synced tree — recorded per entry so the
# JSON's overlap times are never read against the presets section's
# BENCH_D-sized buckets (they measure a much smaller model end to end).
D_TOTAL = sum(math.prod(s) for s in SHAPES.values())

res = {}
for preset in PRESETS:
    cfg = oh.mkcfg(preset, M)
    plan = bucketing.build_plan(SHAPES, SPECS, ("data",), {"data": 8}, cfg)
    ef0 = bucketing.init_ef_state(plan, cfg) if cfg.error_feedback else {}
    post, ovl = oh.make_sync_steps(mesh, L, cfg, plan)

    entry = {"buckets": len(plan.buckets), "schedule": list(plan.schedule()),
             "layers": L, "width": M, "d_total": D_TOTAL}
    for label, fj in (("post_us", post), ("overlap_us", ovl)):
        comp = fj.lower(PARAMS, ef0, X, jax.random.PRNGKey(2)).compile()
        launches = sum(hlo_cost.analyze_text(comp.as_text()).coll_exec.values())
        out = fj(PARAMS, ef0, X, jax.random.PRNGKey(2))
        jax.block_until_ready(out)
        # second warm call: same discipline as the presets/device_step
        # sections (compile, then allocator settle, then the timed reps).
        jax.block_until_ready(fj(PARAMS, ef0, X, jax.random.PRNGKey(2)))
        t0 = time.perf_counter()
        for i in range(REPS):
            out = fj(PARAMS, ef0, X, jax.random.fold_in(jax.random.PRNGKey(2), i))
        jax.block_until_ready(out)
        entry[label] = (time.perf_counter() - t0) / REPS * 1e6
        entry[label.replace("_us", "_launches")] = launches
    res[preset] = entry
print(json.dumps(res))
"""


def _run_inner(script, extra_env=None, timeout=900):
    root = pathlib.Path(__file__).resolve().parent.parent
    env = dict(os.environ)
    # src for repro.*; tests/distributed_checks for the shared
    # overlap_harness module (also imported by overlap_check.py).
    env["PYTHONPATH"] = os.pathsep.join(
        [str(root / "src"), str(root / "tests" / "distributed_checks")])
    env.pop("XLA_FLAGS", None)
    env.update(extra_env or {})
    return subprocess.run([sys.executable, "-c", script], env=env,
                          capture_output=True, text=True, timeout=timeout)


_OVERLAP_CACHE = {}


def collect_overlap(*, smoke: bool = False) -> dict:
    """{preset: {overlap_us, post_us, *_launches, buckets, schedule}} — the
    machine-readable record benchmarks/run.py embeds as the JSON's
    ``overlap`` section.  Raises RuntimeError on subprocess failure.
    Memoized either way, so run.py's rows() + collect() pair never pays
    (or re-fails) the subprocess twice."""
    if smoke in _OVERLAP_CACHE:
        out = _OVERLAP_CACHE[smoke]
        if isinstance(out, RuntimeError):
            raise out
        return out
    extra = {"BENCH_OVERLAP_L": "4", "BENCH_OVERLAP_M": "64",
             "BENCH_OVERLAP_REPS": "2"} if smoke else None
    proc = _run_inner(_OVERLAP_INNER, extra)
    if proc.returncode != 0:
        err = RuntimeError(f"overlap bench failed: {proc.stderr[-500:]}")
        _OVERLAP_CACHE[smoke] = err
        raise err
    _OVERLAP_CACHE[smoke] = json.loads(proc.stdout.strip().splitlines()[-1])
    return _OVERLAP_CACHE[smoke]


def rows():
    t0 = time.perf_counter()
    proc = _run_inner(_INNER, timeout=600)
    dt = (time.perf_counter() - t0) * 1e6
    if proc.returncode != 0:
        return [{"name": "bucketing.launches", "us_per_call": dt,
                 "derived": f"FAILED: {proc.stderr[-300:]}", "check": False}]
    res = json.loads(proc.stdout.strip().splitlines()[-1])
    pl, bk = res["perleaf"], res["bucketed"]
    out = [{
        "name": "bucketing.launches",
        "us_per_call": dt,
        "derived": (f"perleaf={pl['colls']:.0f} colls/{pl['us']:.0f}us "
                    f"bucketed={bk['colls']:.0f} colls/{bk['us']:.0f}us "
                    f"({res['n_leaves']} leaves -> {res['n_buckets']} buckets,"
                    f" x{pl['us'] / max(bk['us'], 1):.1f} step-time)"),
        # the tentpole claims: ≤ 1 collective launch per bucket (the wire is
        # fused: values + μ ride one buffer) — deterministic, read from HLO.
        # Step time is only bounded, not asserted as a win: on the
        # single-stream CPU backend the wire is free and devices serialize
        # on one core, so bucketing's launch savings can't show while its
        # concat/split overhead does, and the per-leaf time swings ~2×
        # run-to-run (120 tiny collectives vs scheduler noise).  The same
        # parity-not-win convention as the overlap section below; the
        # wall-clock story lives in bench_device_step's modeled gate.
        "check": (bk["colls"] <= res["n_buckets"]
                  and bk["colls"] < pl["colls"] / 10
                  and bk["us"] < 2.0 * pl["us"]),
    }]
    t0 = time.perf_counter()
    try:
        ov = collect_overlap()
    except RuntimeError as e:
        return out + [{"name": "bucketing.overlap", "us_per_call": 0.0,
                       "derived": str(e)[-300:], "check": False}]
    dt = (time.perf_counter() - t0) * 1e6
    worst = max(e["overlap_us"] / e["post_us"] for e in ov.values())
    derived = " ".join(
        f"{p}:{e['overlap_us']:.0f}us(ovl)/{e['post_us']:.0f}us(post)"
        for p, e in sorted(ov.items()))
    out.append({
        "name": "bucketing.overlap",
        "us_per_call": dt,
        "derived": derived + f" worst-ratio x{worst:.2f}",
        # schedule parity: same launch count per schedule, and the
        # overlapped schedule costs ≤ 2× post-backward even on the
        # single-stream CPU backend (identical op set; the slack absorbs
        # CPU dispatch jitter on these sub-10ms graphs).
        "check": (worst < 2.0
                  and all(e["overlap_launches"] == e["post_launches"]
                          for e in ov.values())),
    })
    return out
