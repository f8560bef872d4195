"""One run of one cell: set-up, the first steps checked against the plain
reference, and the timed window.

Everything a cell is comes from files found by name: the cell's entry in
``BENCHMARK.json`` names its configuration (``configs/<config>.json``, whose
``architecture`` key names ``arch/<architecture>/``: the program's
``ArchConfig``, the leaves, the reference model and the FLOP count) and its
traffic (``workloads/<traffic>.json``); each per-layer metric is read by
``metrics/<metric>.py``; the limits of the comparison are
``limits/<cell>.json``; the device peaks are ``peaks.json``.

Set-up builds the program's ``Trainer`` (its ``step_fn`` on a ``("data",
"model")`` mesh), puts the seeded weights in its state, draws the ring of
batches on the host, and drives the step through its first ``check_steps``
steps with the window's own call and feed. The window then runs the same
step on the same state for ``--seconds`` (see :func:`window`). Once the
window has closed, the program's state is freed and the reference replays
the checked steps.
"""
from __future__ import annotations

import dataclasses
import gc
import importlib
import json
import math
import pathlib
import shutil
import statistics
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from benchmarks.chip import arch, compare, flops, reference, scopes
from benchmarks.chip import trace_reduce, traffic, weights

HERE = pathlib.Path(__file__).resolve().parent
ROOT = HERE.parents[1]
TRACE_DIR = ROOT / ".bench_trace"
GIB = float(1 << 30)


@dataclasses.dataclass
class Cell:
    name: str
    config: dict
    traffic: dict
    chips: int
    per_layer: list
    limits: dict | None


def load_cell(name: str, bench: pathlib.Path = ROOT / "BENCHMARK.json"
              ) -> Cell:
    b = json.loads(bench.read_text())
    try:
        w = next(w for w in b["workloads"] if w["name"] == name)
    except StopIteration:
        raise KeyError(f"no workload {name!r} in {bench}") from None
    c = next(c for c in b["configs"] if c["name"] == w["config"])
    lim = HERE / "limits" / f"{name}.json"
    return Cell(
        name=name,
        config=json.loads((ROOT / c["file"]).read_text()),
        traffic=json.loads(
            (HERE / "workloads" / f"{w['traffic']}.json").read_text()),
        chips=w["chips"],
        per_layer=[{"name": m["name"], "unit": m["unit"]}
                   for m in b["per_layer"]
                   if name in m.get("workloads", [name])],
        limits=json.loads(lim.read_text()) if lim.exists() else None)


def peaks(kind: str) -> dict:
    table = json.loads((HERE / "peaks.json").read_text())["devices"]
    if kind not in table:
        raise KeyError(f"device kind {kind!r} is not in peaks.json")
    return table[kind]


# --------------------------------------------------------------------------- #
# The system under test.
# --------------------------------------------------------------------------- #

class Program:
    """The program's Trainer for a cell, with placements for its state."""

    def __init__(self, cell: Cell, devices):
        from repro.configs.base import RunConfig, ShapeSpec
        from repro.core import types as ct
        from repro.optim.optimizers import AdamWConfig
        from repro.train.trainer import Trainer, TrainerConfig

        c, t = cell.config, cell.traffic
        arch_cfg = arch.part(c, "program").arch_config(c)
        cc = dict(t["compression"])
        comp = ct.CompressionConfig(
            encoder=ct.EncoderSpec(**cc.pop("encoder")),
            bucket=ct.BucketSpec(**cc.pop("bucket")),
            axes=tuple(cc.pop("axes")), inner_axes=tuple(cc.pop("inner_axes")),
            **cc)
        run = RunConfig(compression=comp, **t["run"])
        mshape = (t["mesh"]["data"], t["mesh"]["model"])
        if math.prod(mshape) != cell.chips:
            raise ValueError(f"{cell.name}: mesh {mshape} != {cell.chips} chips")
        self.mesh = jax.make_mesh(mshape, ("data", "model"),
                                  devices=devices[:cell.chips])
        shape = ShapeSpec(cell.name, "train", t["seq_len"],
                          t["batch_per_chip"] * cell.chips)
        self.trainer = Trainer(self.mesh, arch_cfg, run, shape,
                               TrainerConfig(seed=traffic.PROGRAM_SEED),
                               AdamWConfig(**t["optimizer"]))
        self.cfg = c
        self.b1 = float(t["optimizer"]["b1"])
        self.param_sh = {k: NamedSharding(self.mesh, P(*s))
                         for k, s in self.trainer.specs.items()}
        self.batch_sh = {k: NamedSharding(self.mesh, s)
                         for k, s in self.trainer.bspecs.items()}

    def init(self, seed: int):
        """(params, opt_state, ef_state): the benchmark's seeded weights in
        the state the program's ``init_fn`` builds."""
        p_init, opt, ef = self.trainer.init_fn(
            jax.random.PRNGKey(traffic.PROGRAM_SEED))
        got = {k: tuple(v.shape) for k, v in p_init.items()}
        if got != weights.param_shapes(self.cfg):
            raise ValueError(f"the program's parameters {got} differ from "
                             "the configuration's")
        jax.block_until_ready((p_init, opt, ef))
        free(p_init)
        params = weights.make(self.cfg, seed, self.param_sh)
        jax.block_until_ready((params, opt, ef))
        return params, opt, ef

    def put(self, host_batch: dict) -> dict:
        return jax.device_put(host_batch, self.batch_sh)

    def step(self, params, opt, ef, batch, k: int):
        return self.trainer.step_fn(params, opt, ef, batch, np.int32(k))


def free(tree, keep=()):
    """Free now the device buffers of ``tree`` that ``keep`` does not hold;
    every computation that reads them has to have finished. A buffer merely
    dropped is freed when the runtime gets to it, at a moment that varies
    from run to run, and the peak memory with it."""
    kept = {id(x) for x in jax.tree.leaves(keep)}
    for x in jax.tree.leaves(tree):
        if id(x) not in kept:
            x.delete()


_norms = jax.jit(lambda t: {k: jnp.sqrt(jnp.sum(jnp.square(v)))
                            for k, v in t.items()})
_diff_norms = jax.jit(lambda a, b: {
    k: jnp.sqrt(jnp.sum(jnp.square(a[k] - b[k]))) for k in a})


def checked_steps(prog: Program, seed: int, ring: list, state, n: int,
                  probe=None):
    """Drive the first ``n`` steps through the window's call and feed.

    Returns (state, readings): each step's loss, the per-leaf norm of the
    first gradient as the optimizer holds it (first moment / (1 - b1)) and
    of the parameters' change over the ``n`` steps. ``probe(k, params,
    first_moment)``, where given, sees the state after each step.
    """
    losses = []
    for k in range(n):
        new = prog.step(*state, prog.put(ring[k]), k)
        jax.block_until_ready(new[:3])
        free(state, keep=new)
        state, m = new[:3], new[3]
        losses.append(m["loss"])
        if k == 0:
            g1 = jax.device_get(_norms(state[1].m))
        if probe is not None:
            probe(k, state[0], state[1].m)
    p0 = weights.make(prog.cfg, seed, prog.param_sh)
    upd = jax.device_get(_diff_norms(state[0], p0))
    free(p0)
    read = {"losses": [float(x) for x in jax.device_get(losses)],
            "grad": {k: float(v) / (1.0 - prog.b1) for k, v in g1.items()},
            "update": {k: float(v) for k, v in upd.items()}}
    return state, read


def window(prog: Program, ring: list, state, first: int, seconds: float):
    """Steps from ``first`` on for ``seconds``; returns (state, steps,
    losses, elapsed seconds).

    The next step's rows go to the device while a step runs, and the host
    waits for each step's state before it dispatches the next: the step
    donates nothing, so every step queued behind the running one would hold
    a state of its own and the peak memory would be the loop's, not the
    program's. The caller holds Python's garbage collector off meanwhile.
    """
    losses, ends = [], []
    k = first
    t0 = time.perf_counter()
    with jax.profiler.TraceAnnotation("window"):
        with jax.profiler.TraceAnnotation("batch_put"):
            batch = prog.put(ring[k % len(ring)])
        while True:
            with jax.profiler.TraceAnnotation("dispatch"):
                new = prog.step(*state, batch, k)
            losses.append(new[3]["loss"])
            k += 1
            with jax.profiler.TraceAnnotation("batch_put"):
                batch = prog.put(ring[k % len(ring)])
            with jax.profiler.TraceAnnotation("wait"):
                jax.block_until_ready(new[:3])
                free(state, keep=new)
                state = new[:3]
            ends.append(time.perf_counter() - t0)
            if ends[-1] >= seconds:
                break
    steps = np.diff(ends, prepend=0.0)
    print(f"step seconds: min {steps.min():.4f} median "
          f"{np.median(steps):.4f} max {steps.max():.4f}", file=sys.stderr)
    return state, k - first, losses, time.perf_counter() - t0


@dataclasses.dataclass
class Reading:
    """What a per-layer metric's reader may use."""
    trace: trace_reduce.Trace
    classes: dict
    steps: int
    tokens_per_s: float
    flops_per_token: float
    chips: int
    peak: dict
    scopes: dict = dataclasses.field(default_factory=dict)

    @property
    def window_s(self) -> float:
        return self.trace.window_s

    @property
    def busy_s(self) -> float:
        return statistics.mean(trace_reduce.busy_ns(d)
                               for d in self.trace.devices) * 1e-9


def start_trace():
    """The profiler on, with the host's own spans and no Python tracer."""
    shutil.rmtree(TRACE_DIR, ignore_errors=True)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    opts.enable_hlo_proto = False
    jax.profiler.start_trace(str(TRACE_DIR), profiler_options=opts)


def log(msg: str, t_start: float, devices=()):
    mem = [d.memory_stats() or {} for d in devices]
    gib = "".join(f" [device {i}: {m.get('bytes_in_use', 0) / GIB:.3f} GiB"
                  f" in use, peak {m.get('peak_bytes_in_use', 0) / GIB:.3f}]"
                  for i, m in enumerate(mem))
    print(f"[{time.perf_counter() - t_start:8.2f} s] {msg}{gib}",
          file=sys.stderr, flush=True)


def read_metric(name: str, r: Reading):
    return importlib.import_module(f"benchmarks.chip.metrics.{name}").read(r)


def _finite(x):
    return x if x is None or math.isfinite(x) else None


def run(cell: Cell, seed: int, seconds: float, trace: bool, devices,
        t_start: float) -> dict:
    """One run; returns the result line's object."""
    t = cell.traffic
    dev0 = devices[0]
    peak = peaks(dev0.device_kind)
    if cell.limits is None:
        raise FileNotFoundError(f"no limits/{cell.name}.json")
    used = devices[:cell.chips]
    log("start", t_start)
    prog = Program(cell, devices)
    log("program built", t_start)
    ring = traffic.ring(seed, t, cell.config["vocab_size"], cell.chips)
    state = prog.init(seed)
    log("weights and rows made", t_start, used)
    state, prog_read = checked_steps(prog, seed, ring, state,
                                     t["check_steps"])
    log("checked steps done", t_start, used)

    gc.collect()
    if trace:
        start_trace()
    setup_s = time.perf_counter() - t_start
    gc.disable()
    try:
        state, steps, losses, elapsed = window(prog, ring, state,
                                               t["check_steps"], seconds)
    finally:
        gc.enable()
    if trace:
        jax.profiler.stop_trace()
    tokens = steps * t["batch_per_chip"] * cell.chips * t["seq_len"]
    mem_peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                   for d in prog.mesh.devices.flat)
    window_losses = np.asarray(jax.device_get(losses))
    failed = int(np.sum(~np.isfinite(window_losses)))
    log(f"window done: {steps} steps in {elapsed:.3f} s", t_start, used)

    classes = smap = None
    if trace:
        b = prog.put(ring[0])
        hlo = prog.trainer.step_fn.lower(*state, b, np.int32(0)) \
            .compile().as_text()
        classes, smap = trace_reduce.classify_hlo(hlo), scopes.scope_map(hlo)
        del hlo
    del state, losses, prog
    gc.collect()

    ref = reference.run(cell.config, t, seed, traffic.PROGRAM_SEED,
                        ring[:t["check_steps"]], t["mesh"]["data"], devices)
    log("reference done", t_start)
    nums = compare.numbers(prog_read, ref, cell.limits["median_leaf"])
    correct, checks = compare.judge(nums, cell.limits)
    correct = correct and failed == 0

    device = {"platform": dev0.platform, "kind": dev0.device_kind,
              "count": cell.chips, "memory_peak_bytes": int(mem_peak)}
    out = {"correct": correct, "attempted": steps, "failed": failed}
    if trace:
        tr = trace_reduce.reduce(trace_reduce.xplane_file(TRACE_DIR), classes)
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        r = Reading(tr, classes, steps, tokens / elapsed,
                    flops.train_per_token(cell.config, t["seq_len"]),
                    cell.chips, peak, smap)
        metrics = {}
        for m in cell.per_layer:
            v = read_metric(m["name"], r)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        device["busy_s"] = r.busy_s
        device["window_s"] = r.window_s
        out["metrics"] = metrics
        out["device"] = device
        out["breakdown"] = {"device_ops": trace_reduce.top_ops(tr),
                            "idle_gaps": trace_reduce.idle_gaps(tr)}
    else:
        out["metrics"] = {
            "train_tokens_per_s": {"value": tokens / elapsed,
                                   "unit": "tokens/s"},
            "setup_s": {"value": setup_s, "unit": "s"}}
        out["device"] = device
    out["checks"] = {k: {"value": _finite(v["value"]), "limit": v["limit"]}
                     for k, v in checks.items()}
    print("readings " + json.dumps({
        "program": {k: prog_read[k] for k in ("losses", "grad", "update")},
        "reference": {k: ref[k] for k in ("losses", "grad", "raw_grad",
                                          "update")}}), file=sys.stderr)
    for k, (v, where) in nums.items():
        print(f"check {k} {v!r} limit {checks[k]['limit']!r} ({where})",
              file=sys.stderr)
    return out
