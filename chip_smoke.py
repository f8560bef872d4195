#!/usr/bin/env python3
"""Smoke run of the compressed gradient sync on a TPU.

Run from the root of a checkout, on a machine whose JAX sees one TPU chip:

    python3 chip_smoke.py [--seed N]

Phase 1 — codec round, one chip.  For every preset of
``COMPRESSION_PRESETS`` at d = 2^20 and 2^24, n = 8 peer vectors drawn from
``--seed`` are packed at their ranks, stacked as the all_gather stacks them
(the ``tests/conftest.py::simulate_wire_round`` pattern) and decoded with
``decode_gathered`` — all on the chip, through the codec dispatchers in
kernel mode ``auto``.  The oracle is the same round run by a child process
pinned to the host CPU (``JAX_PLATFORMS=cpu``; it never touches the chip),
where every dispatcher takes its jnp reference path.  Checks:

* every wire row is byte-identical to the oracle's;
* the decoded mean is bit-identical to the oracle's (every sum that
  reaches a wire byte or the decoded mean runs in a fixed order,
  ``repro.kernels.numerics``);
* the compiled round contains a Pallas kernel (``tpu_custom_call``).

Phase 2 — trainer, one chip.  ``Trainer`` on mamba2-130m at its published
widths (24 layers, d_model 768, vocab 50280), sequence 4096, batch
``TRAIN_BATCH``, ``microbatches=1`` (overlapped bucket sync), once with
exact sync and once with ``ef_rotated_binary``; ``TRAIN_STEPS`` timed steps
after one warm-up step, all losses finite.

``--chips 4`` runs only phase 3 on a four-chip host: ``compressed_mean``
for every preset — the ``hier_*`` presets on a (2, 2) mesh, the others on
a flat 4-device mesh — against the exact mean of the inputs, within a
bound from the codec's analytic MSE (``expected_sq_err``), and against the
same round simulated on one device (bit-identical except for the psum
codec, whose cross-chip sum order is the interconnect's); then the
trainer with data = 4, compressed against exact sync (losses within
``TRAIN_LOSS_BAND``), reporting the collective launches per bucket of the
compiled step.

Any failed check or phase makes the script exit non-zero.  Host-clock
timings (around ``block_until_ready``) are labelled smoke timings: they
are not benchmark numbers.  The last line of stdout is
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
"""
import argparse
import io
import json
import math
import os
import pathlib
import re
import struct
import subprocess
import sys
import time
import traceback

ROOT = pathlib.Path(__file__).resolve().parent
SRC = ROOT / "src"

N_PEERS = 8
DIMS = (1 << 20, 1 << 24)
TRAIN_ARCH = "mamba2-130m"
TRAIN_SEQ = 4096
TRAIN_BATCH = 8
TRAIN_STEPS = 5
# phase 3: one sample of ‖est − x̄‖² at d = 2^20 sits within a few per cent
# of its expectation; 2× the analytic MSE leaves room for the block-sampled
# fixed-k codecs (64 of 1024 blocks sent), whose spread is the widest.
MSE_SLACK = 2.0
# phase 3: compressed-sync losses within this fraction of the exact ones.
TRAIN_LOSS_BAND = 0.02


class SmokeFailure(Exception):
    pass


def log(msg):
    print(msg, flush=True)


# --------------------------------------------------------------------------- #
# Phase 1: one codec round per preset and size.
# --------------------------------------------------------------------------- #

def peer_inputs(seed, d):
    """(N_PEERS, d) f32 peer vectors: per-peer scale and offset on N(0, 1)."""
    import numpy as np
    rng = np.random.default_rng([seed, d])
    xs = rng.standard_normal((N_PEERS, d), dtype=np.float32)
    scale = rng.uniform(0.25, 2.0, (N_PEERS, 1)).astype(np.float32)
    shift = rng.uniform(-0.5, 0.5, (N_PEERS, 1)).astype(np.float32)
    return xs * scale + shift


def round_program(name, d):
    """jit'd (xs, key) -> (rows, decoded mean) for one preset at size d."""
    import jax
    import jax.numpy as jnp

    from repro.configs.registry import compression_preset
    from repro.core import wire

    cfg = compression_preset(name, axes=("data",))
    codec = wire.resolve(cfg)

    def run(xs, key):
        rows = jax.lax.map(
            lambda r: codec.pack(xs[r], key, r, cfg),
            jnp.arange(N_PEERS, dtype=jnp.int32))
        return rows, codec.decode_gathered(rows, key, cfg, d, N_PEERS)

    return jax.jit(run), codec


def cases():
    from repro.configs.registry import COMPRESSION_PRESETS
    return [(d, name) for d in DIMS for name in sorted(COMPRESSION_PRESETS)]


def oracle_child(seed):
    """CPU side: stream (rows, mean) per case to stdout, length-prefixed."""
    import jax
    import numpy as np

    out = sys.stdout.buffer
    key = jax.random.PRNGKey(seed)
    xs_d = {}
    for d, name in cases():
        if d not in xs_d:
            xs_d = {d: peer_inputs(seed, d)}
        fn, _ = round_program(name, d)
        rows, mean = fn(xs_d[d], key)
        buf = io.BytesIO()
        rows = np.asarray(rows)
        np.savez(buf, rows=_as_bytes(rows), dtype=np.asarray(str(rows.dtype)),
                 mean=np.asarray(mean))
        blob = buf.getvalue()
        out.write(struct.pack("<Q", len(blob)))
        out.write(blob)
        out.flush()
    return 0


def _read_item(pipe):
    import numpy as np
    head = pipe.read(8)
    if len(head) != 8:
        raise SmokeFailure("oracle child ended early")
    (size,) = struct.unpack("<Q", head)
    blob = pipe.read(size)
    if len(blob) != size:
        raise SmokeFailure("oracle child ended early")
    with np.load(io.BytesIO(blob)) as z:
        return z["rows"], str(z["dtype"]), z["mean"]


def _as_bytes(rows):
    """(n, slots) wire rows of any dtype as their (n, nbytes) raw bytes."""
    import numpy as np
    rows = np.ascontiguousarray(rows)
    return rows.view(np.uint8).reshape(rows.shape[0], -1)


def phase_with_oracle(seed):
    """Phase 1 with its CPU oracle child, stopped whatever happens."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    child = subprocess.Popen(
        [sys.executable, str(pathlib.Path(__file__).resolve()),
         "--oracle-child", "--seed", str(seed)],
        stdout=subprocess.PIPE, env=env, cwd=str(ROOT))
    try:
        phase_codec_round(seed, child.stdout)
        if child.wait(timeout=300) != 0:
            raise SmokeFailure(f"oracle child exited {child.returncode}")
    finally:
        if child.poll() is None:
            child.kill()
        child.wait()


def phase_codec_round(seed, oracle):
    import jax
    import numpy as np

    key = jax.random.PRNGKey(seed)
    failures = []
    xs_host = xs_dev = None
    cur_d = None
    for d, name in cases():
        if d != cur_d:
            cur_d = d
            xs_host = peer_inputs(seed, d)
            xs_dev = jax.device_put(xs_host)
        fn, _ = round_program(name, d)
        t0 = time.perf_counter()
        compiled = fn.lower(xs_dev, key).compile()
        t_compile = time.perf_counter() - t0
        has_kernel = "tpu_custom_call" in compiled.as_text()
        rows, mean = compiled(xs_dev, key)
        jax.block_until_ready((rows, mean))
        t0 = time.perf_counter()
        rows, mean = compiled(xs_dev, key)
        jax.block_until_ready((rows, mean))
        t_round = time.perf_counter() - t0
        rows, mean = np.asarray(rows), np.asarray(mean)
        o_bytes, o_dtype, o_mean = _read_item(oracle)
        r_bytes = _as_bytes(rows)
        same_layout = (str(rows.dtype) == o_dtype
                       and r_bytes.shape == o_bytes.shape)
        n_bad_rows = (int(np.sum(np.any(r_bytes != o_bytes, axis=1)))
                      if same_layout else N_PEERS)
        rows_ok = same_layout and n_bad_rows == 0
        finite = bool(np.isfinite(mean).all())
        diff = np.abs(mean.astype(np.float64) - o_mean.astype(np.float64))
        n_diff = int(np.sum(mean.view(np.uint32) != o_mean.view(np.uint32)))
        mean_ok = n_diff == 0
        ok = rows_ok and finite and mean_ok and has_kernel
        log(f"phase1 {name:20s} d=2^{int(math.log2(d))} "
            f"rows_identical={rows_ok} ({n_bad_rows}/{N_PEERS} peers differ) "
            f"mean_identical={mean_ok} "
            f"(unequal={n_diff}, max_abs_diff={float(diff.max()):.3e}) "
            f"tpu_custom_call={has_kernel} finite={finite} "
            f"smoke_timing_host_s: compile={t_compile:.2f} "
            f"round={t_round:.4f} {'OK' if ok else 'FAIL'}")
        if not ok:
            failures.append(f"{name}@{d}")
    if failures:
        raise SmokeFailure(f"phase 1 failed for {failures}")


# --------------------------------------------------------------------------- #
# Phase 2 / 3: the trainer.
# --------------------------------------------------------------------------- #

def train_passes(mesh, seed, batch, steps, presets):
    """Run Trainer once per sync mode; {label: (losses, step secs, trainer)}."""
    import dataclasses

    import jax
    import numpy as np

    from repro.configs.base import ShapeSpec
    from repro.configs.registry import get_config, get_run_config
    from repro.core import types
    from repro.optim.optimizers import AdamWConfig
    from repro.train.trainer import Trainer, TrainerConfig

    cfg = get_config(TRAIN_ARCH)
    shape = ShapeSpec("train_4k", "train", TRAIN_SEQ, batch)
    out = {}
    for label in presets:
        comp = (types.CompressionConfig(mode="none") if label == "exact"
                else label)
        run = get_run_config(TRAIN_ARCH, "train_4k", compression=comp)
        run = dataclasses.replace(run, microbatches=1)
        tcfg = TrainerConfig(steps=steps + 1, log_every=1, seed=seed)
        tr = Trainer(mesh, cfg, run, shape, tcfg,
                     AdamWConfig(lr=3e-4, total_steps=steps + 1))
        _, _, hist = tr.fit()
        losses = np.array([h["loss"] for h in hist])
        secs = np.diff([h["sec"] for h in hist])
        plan = tr.sync_plan
        nb = len(plan.buckets) if plan is not None else 0
        ncomp = (sum(1 for b in plan.buckets if b.kind == "compressed")
                 if plan is not None else 0)
        stats = jax.devices()[0].memory_stats() or {}
        peak = stats.get("peak_bytes_in_use")
        log(f"train {label:18s} arch={TRAIN_ARCH} seq={TRAIN_SEQ} "
            f"batch={batch} microbatches={run.microbatches} "
            f"overlap={tr.overlap} buckets={nb} compressed={ncomp} "
            f"losses={[float(f'{v:.5f}') for v in losses]} "
            f"smoke_timing_host_step_s={[float(f'{v:.4f}') for v in secs]} "
            f"peak_bytes_in_use={peak}")
        if not np.isfinite(losses).all():
            raise SmokeFailure(f"train {label}: non-finite loss {losses}")
        if len(secs) < steps:
            raise SmokeFailure(f"train {label}: only {len(secs)} timed steps")
        out[label] = (losses, secs, tr)
    return out


def phase_trainer(seed):
    import jax
    mesh = jax.make_mesh((1, 1), ("data", "model"))
    res = train_passes(mesh, seed, TRAIN_BATCH, TRAIN_STEPS,
                       ("exact", "ef_rotated_binary"))
    tr = res["ef_rotated_binary"][2]
    if not tr.overlap:
        raise SmokeFailure("ef_rotated_binary ran without overlapped sync")
    if not any(b.kind == "compressed" for b in tr.sync_plan.buckets):
        raise SmokeFailure("ef_rotated_binary compressed no bucket")


def expected_sq_err(cfg, codec, xs, key):
    """Bound on ‖est − x̄‖² for one round over the codec's peers ``xs``.

    Unbiased codecs: their analytic MSE (repro.core.mse), which one sample
    at d = 2^20 matches to within a few per cent.  Error-feedback codecs
    at zero residual send a deterministic contractive message m(x_i), and
    ‖x̄ − (1/n) Σ m(x_i)‖ ≤ (1/n) Σ ‖x_i − m(x_i)‖, each bounded by the
    codec's ``residual_bound`` plus the bf16 rounding of m (2^-9 relative).
    Returns (bound, "mse" | "deterministic").
    """
    import jax.numpy as jnp

    from repro.core import mse, rotation
    from repro.core.wire import codecs
    from repro.kernels.fixed_k_encode import ops as fk

    d = xs.shape[1]
    enc = cfg.encoder
    if cfg.error_feedback:
        b = jnp.stack([codec.residual_bound(x, key, cfg) for x in xs])
        r = jnp.mean(b + 2.0 ** -9 * (jnp.linalg.norm(xs, axis=1) + b))
        return float(r) ** 2, "deterministic"
    krot = rotation.rotation_key(key)
    dp = rotation.padded_dim(d) if enc.rotation else d
    if enc.kind == "fixed_k":
        k = codecs.fixed_k_blocks(dp, enc.fraction) * fk.BLOCK
        if enc.rotation:
            val = mse.mse_rotated_fixed_k(xs, k, krot)
        elif cfg.mode == "shared_support":
            val = mse.mse_fixed_k_shared(xs, k, jnp.mean(xs, axis=-1))
        else:
            val = mse.mse_fixed_k(xs, k, jnp.mean(xs, axis=-1))
    elif enc.kind == "bernoulli":
        val = mse.mse_bernoulli(xs, enc.fraction, jnp.mean(xs, axis=-1))
    elif enc.kind == "binary":
        val = (mse.mse_rotated_binary(xs, krot) if enc.rotation
               else mse.mse_binary(xs))
    elif enc.kind == "ternary":
        # mid-split MSE; the optimal split of ternary_opt never exceeds it
        half = (1.0 - enc.fraction) / 2
        val = mse.mse_ternary(xs, half, half, jnp.min(xs, axis=-1),
                              jnp.max(xs, axis=-1))
    else:
        raise SmokeFailure(f"no MSE bound for encoder {enc.kind!r}")
    return float(val), "mse"


def phase_four_chips(seed):
    """Flat and hierarchical meshes, then the data = 4 trainer."""
    import dataclasses
    import functools

    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import PartitionSpec as P

    from repro.configs.registry import COMPRESSION_PRESETS, compression_preset
    from repro.core import collectives, wire

    n = len(jax.devices())
    if n != 4:
        raise SmokeFailure(f"--chips 4 needs 4 devices, JAX sees {n}")
    d = DIMS[0]
    key = jax.random.PRNGKey(seed)
    xs = jnp.asarray(peer_inputs(seed, d)[:n])
    exact_mean = np.asarray(xs).astype(np.float64).mean(axis=0)
    failures = []

    def mesh_round(mesh, spec, cfg):
        @functools.partial(jax.shard_map, mesh=mesh, in_specs=(spec, P()),
                           out_specs=(P(), P()), check_vma=False)
        def f(x, k):
            x = x.reshape(-1)
            axes = tuple(cfg.inner_axes) + tuple(cfg.axes)
            return (collectives.compressed_mean(x, k, cfg),
                    jax.lax.pmean(x, axes))
        return jax.jit(f)

    def one_device_round(codec, cfg, peers):
        """(decoded mean, (1/m) Σ_i |m_i|) of the round on one device."""
        m = peers.shape[0]

        def run(v, k):
            rows = jnp.stack([codec.pack(v[i], k, i, cfg) for i in range(m)])
            msgs = jnp.stack([jnp.abs(codec.unpack(rows[i], i, k, cfg, d))
                              for i in range(m)])
            return codec.decode_gathered(rows, k, cfg, d, m), msgs.mean(0)

        return tuple(np.asarray(a) for a in jax.jit(run)(peers, key))

    flat = jax.make_mesh((n,), ("data",))
    hier = jax.make_mesh((2, 2), ("pod", "data"))
    for name in sorted(COMPRESSION_PRESETS):
        is_hier = bool(COMPRESSION_PRESETS[name].inner_axes)
        if is_hier:
            # rows 2j, 2j+1 form pod j: exact mean inside the pod, the
            # codec across the two pods
            cfg = compression_preset(name, axes=("pod",))
            mesh, spec = hier, P(("pod", "data"))
            peers = (xs[0::2] + xs[1::2]) / 2
            sim_cfg = dataclasses.replace(cfg, inner_axes=())
        else:
            cfg = compression_preset(name, axes=("data",))
            mesh, spec = flat, P("data")
            peers, sim_cfg = xs, cfg
        fn = mesh_round(mesh, spec, cfg)
        t0 = time.perf_counter()
        est, pm = jax.block_until_ready(fn(xs, key))
        t_first = time.perf_counter() - t0
        est, pm = np.asarray(est), np.asarray(pm)
        finite = bool(np.isfinite(est).all())
        pm_err = float(np.abs(pm - exact_mean).max())
        sq_err = float(np.sum((est - exact_mean) ** 2))
        sim_codec = wire.resolve(sim_cfg)
        bound, kind = expected_sq_err(sim_cfg, sim_codec, peers, key)
        limit = bound * (MSE_SLACK if kind == "mse" else 1.0)
        sim, abs_msg = one_device_round(sim_codec, sim_cfg, peers)
        exact = wire.resolve(cfg).reduce != "psum"
        unequal = int(np.sum(est.view(np.uint32) != sim.view(np.uint32)))
        # a psum adds the n wire rows in the interconnect's order and
        # precision: at most n roundings of a partial sum, each within
        # 2^-9 of Σ_i |m_i| at bf16
        sim_tol = n * 2.0 ** -9 * float(np.linalg.norm(abs_msg))
        sim_dist = float(np.linalg.norm(est - sim))
        sim_ok = unequal == 0 if exact else sim_dist <= sim_tol
        ok = finite and pm_err < 1e-5 and sq_err <= limit and sim_ok
        log(f"phase3 {name:20s} mesh={'(2,2)' if is_hier else f'({n},)'}"
            f" scatter_decode={cfg.scatter_decode} finite={finite} "
            f"pmean_vs_f64_max_abs={pm_err:.3e} "
            f"sq_err_vs_exact_mean={sq_err:.6e} {kind}_bound={bound:.6e} "
            f"ratio={sq_err / bound if bound else float('inf'):.4f} "
            f"rel_err={math.sqrt(sq_err / np.sum(exact_mean ** 2)):.4f} "
            f"vs_one_device_round: unequal={unequal}/{d} "
            f"l2_dist={sim_dist:.4e} "
            f"{'(bit-identical required)' if exact else f'(psum: l2 <= {sim_tol:.4e})'} "
            f"smoke_timing_host_s: first_call={t_first:.2f} "
            f"{'OK' if ok else 'FAIL'}")
        if not ok:
            failures.append(name)

    mesh4 = jax.make_mesh((n, 1), ("data", "model"))
    res = train_passes(mesh4, seed, n * 2, 3, ("exact", "ef_rotated_binary"))
    le, lc = res["exact"][0], res["ef_rotated_binary"][0]
    gap = np.abs(lc - le) / np.abs(le)
    log(f"phase3 trainer data={n} loss_exact={le.tolist()} "
        f"loss_ef_rotated_binary={lc.tolist()} "
        f"rel_gap={gap.tolist()} band={TRAIN_LOSS_BAND}")
    if not (gap <= TRAIN_LOSS_BAND).all():
        failures.append("trainer")
    tr = res["ef_rotated_binary"][2]
    params, opt, ef = tr.init_fn(jax.random.PRNGKey(seed))
    batch = tr.data.device_batch(0, tr.mesh, tr.bspecs)
    txt = tr.step_fn.lower(params, opt, ef, batch,
                           jnp.int32(0)).compile().as_text()
    ops = re.findall(r"(?m)^\s*(?:ROOT )?%?\S+ = .*? ((?:all-reduce|"
                     r"all-gather|reduce-scatter|all-to-all|"
                     r"collective-permute)(?:-start)?)\(", txt)
    counts = {op: ops.count(op) for op in sorted(set(ops))}
    nb = len(tr.sync_plan.buckets)
    log(f"phase3 compiled step: collective ops (static count)={counts} "
        f"buckets={nb} per_bucket={len(ops) / max(nb, 1):.2f}")
    if failures:
        raise SmokeFailure(f"phase 3 failed for {failures}")


# --------------------------------------------------------------------------- #

def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4))
    ap.add_argument("--oracle-child", action="store_true",
                    help=argparse.SUPPRESS)
    args = ap.parse_args()

    if not (SRC / "repro").is_dir():
        print("chip_smoke: src/repro not found next to chip_smoke.py; "
              "run it from a checkout of the repository", file=sys.stderr)
        return 2
    sys.path.insert(0, str(SRC))
    from repro.launch import compile_cache
    compile_cache.enable()

    if args.oracle_child:
        return oracle_child(args.seed)

    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        print(f"chip_smoke: no TPU (JAX sees {devs[0].platform}); "
              "nothing was run", file=sys.stderr)
        return 3
    from repro.kernels import backend
    if not backend.ON_TPU or backend.MODE != "auto":
        print(f"chip_smoke: kernel backend is ON_TPU={backend.ON_TPU} "
              f"mode={backend.MODE!r}; needs a TPU in mode 'auto'",
              file=sys.stderr)
        return 3
    log(f"device: {devs[0].platform} {devs[0].device_kind} x{len(devs)}; "
        f"jax {jax.__version__}; seed {args.seed}")

    if args.chips == 4:
        phases = [("phase3", lambda: phase_four_chips(args.seed))]
    elif len(devs) != 1:
        print(f"chip_smoke: expected one chip, JAX sees {len(devs)}",
              file=sys.stderr)
        return 1
    else:
        phases = [("phase1", lambda: phase_with_oracle(args.seed)),
                  ("phase2", lambda: phase_trainer(args.seed))]
    failed = []
    for label, run in phases:
        t0 = time.perf_counter()
        try:
            run()
        except SmokeFailure as e:
            print(f"chip_smoke: {label} FAILED: {e}", file=sys.stderr)
            failed.append(label)
        except Exception:  # noqa: BLE001 — reported as a failure, never a pass
            traceback.print_exc()
            print(f"chip_smoke: {label} FAILED with an exception",
                  file=sys.stderr)
            failed.append(label)
        log(f"{label} {'FAILED' if label in failed else 'passed'}: "
            f"smoke_timing_host_s={time.perf_counter() - t0:.1f}")
    if failed:
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
