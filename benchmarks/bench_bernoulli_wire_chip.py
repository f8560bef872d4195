"""Time the §4.4 Bernoulli wire kernels alone on one TPU chip.

The length is that of the largest compressed bucket of the benchmark cell
``mamba2-370m.train.bernoulli_seed_1bit``, read from the cell's
``Trainer.sync_plan``; ``p`` is the cell's fraction.  Each call is the
cell's own: ``encode_pallas`` of one (d,) f32 gradient, and
``decode_sum_shard_pallas`` of one peer's buffer over the whole stream
(n = 1, ds = d, start 0).  The XLA-compiled jnp reference is timed beside
them: ``ref.encode``, and ``ref.support_shard`` + ``ref.decode_sum_shard``.

Run from the root of the repository, on a machine with the chip:

    PYTHONPATH=src:. python3 benchmarks/bench_bernoulli_wire_chip.py \\
        [--kernel NAME=PATH ...] [--reps 5] [--out FILE]

``--kernel NAME=PATH`` times another copy of
``repro/kernels/bernoulli_wire/kernel.py`` (another commit's, say) under
NAME, beside the installed module, which runs first as ``kernel``.  Each
line printed is one JSON object: ms per call (median of ``--reps`` timed
calls after two warm ones, host clock around ``block_until_ready``), µs
per 1,024 coordinates, GB/s of the bytes the call must move (encode: d
values read and cap written; decode: cap read and d written, f32), and
whether the output equals the first module's bit for bit.  ``--out``
writes all lines as one JSON document as well.  The script is not part of
``BENCHMARK.json``; it needs a TPU and refuses any other device.
"""
from __future__ import annotations

import argparse
import importlib.util
import json
import pathlib
import statistics
import sys
import time

import jax
import jax.numpy as jnp

CELL = "mamba2-370m.train.bernoulli_seed_1bit"


def cell_bucket(cell_name: str = CELL):
    """(d, p): the largest compressed bucket of the cell's sync plan and
    the cell's Bernoulli fraction."""
    from benchmarks.chip import harness
    cell = harness.load_cell(cell_name)
    plan = harness.Program(cell, jax.devices()).trainer.sync_plan
    d = max(b.size for b in plan.buckets if b.kind == "compressed")
    return d, float(cell.traffic["compression"]["encoder"]["fraction"])


def load_kernel(name: str, path: str):
    spec = importlib.util.spec_from_file_location(f"bw_kernel_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def time_call(fn, args, reps: int):
    """(output, median seconds, all seconds) of ``reps`` timed calls after
    two warm ones."""
    out = jax.block_until_ready(fn(*args))
    jax.block_until_ready(fn(*args))
    secs = []
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        secs.append(time.perf_counter() - t0)
    return out, statistics.median(secs), secs


def bits_equal(a, b) -> bool:
    return bool(jnp.array_equal(jax.lax.bitcast_convert_type(a, jnp.uint32),
                                jax.lax.bitcast_convert_type(b, jnp.uint32)))


def calls(kernel, ref, d: int, p: float, cap: int):
    """{op: jitted fn} of one module (a kernel module, or ``ref``)."""
    if kernel is None:
        return {
            "encode": jax.jit(lambda x, k, m: ref.encode(x, k, p, cap, m)),
            "decode_sum_shard": jax.jit(
                lambda b, m, k, pr: ref.decode_sum_shard(
                    b, m, ref.support_shard(k, p, d, 0, d), pr, cap)),
        }
    return {
        "encode": jax.jit(lambda x, k, m: kernel.encode_pallas(
            x, k, m, p=p, cap=cap)),
        "decode_sum_shard": jax.jit(
            lambda b, m, k, pr: kernel.decode_sum_shard_pallas(
                b, m, k, pr, jnp.int32(0), p=p, cap=cap, d=d, ds=d)),
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--kernel", action="append", default=[],
                    metavar="NAME=PATH")
    ap.add_argument("--reps", type=int, default=5)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--out", type=pathlib.Path)
    args = ap.parse_args(argv)

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise SystemExit(f"needs a TPU, found {dev.platform}")
    from repro.core import comm_cost
    from repro.kernels.bernoulli_wire import kernel, ref

    d, p = cell_bucket()
    cap = comm_cost.bernoulli_capacity(d, p)
    modules = [("kernel", kernel)]
    for spec in args.kernel:
        name, path = spec.split("=", 1)
        modules.append((name, load_kernel(name, path)))
    modules.append(("ref", None))

    key = jax.random.PRNGKey(args.seed)
    flat = jax.random.normal(key, (d,), jnp.float32) * 0.7
    mu = jnp.mean(flat)
    keys = jax.random.key_data(jax.random.fold_in(key, 1)).reshape(1, 2)
    prior = jnp.zeros((1,), jnp.int32)
    moved = {"encode": 4 * (d + cap), "decode_sum_shard": 4 * (cap + d)}

    lines, first = [], {}
    for name, mod in modules:
        fns = calls(mod, ref, d, p, cap)
        buf, enc_s, enc_all = time_call(fns["encode"], (flat, keys[0], mu),
                                        args.reps)
        bufs = buf.reshape(1, cap)
        if "encode" in first:
            # every module decodes the first module's buffer, so the
            # decodes compare like for like
            bufs = first["encode"].reshape(1, cap)
        dec, dec_s, dec_all = time_call(fns["decode_sum_shard"],
                                        (bufs, mu.reshape(1), keys, prior),
                                        args.reps)
        for op, out, sec, secs in (("encode", buf, enc_s, enc_all),
                                   ("decode_sum_shard", dec, dec_s, dec_all)):
            first.setdefault(op, out)
            line = {"module": name, "op": op, "d": d, "cap": cap, "p": p,
                    "ms": sec * 1e3,
                    "us_per_1024": sec * 1e6 * 1024 / d,
                    "gb_per_s": moved[op] / sec / 1e9,
                    "ms_all": [s * 1e3 for s in secs],
                    "equal_to_first": bits_equal(out, first[op]),
                    "device": {"platform": dev.platform,
                               "kind": dev.device_kind,
                               "count": jax.device_count()}}
            lines.append(line)
            print(json.dumps(line), flush=True)
        del buf, dec
    if args.out:
        args.out.parent.mkdir(parents=True, exist_ok=True)
        args.out.write_text(json.dumps(lines, indent=1) + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
