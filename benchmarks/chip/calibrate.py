"""Readings that the limits of ``correct`` are set from, for one cell.

    python3 benchmarks/chip/calibrate.py --workload <cell> --seeds 1 2 3 ... \
        [--faults 3]

In one process: the program's first steps on every seed against the plain
reference (the lower readings), and on the first ``--faults`` seeds the
control and the planted faults against the same reference (the upper
readings):

* ``control``: the reference computed with int8 operands and gradients in
  its dense matmuls, in the program's place;
* ``half_batch``: the reference fed batches whose second half of rows
  repeats the first, so the mean is taken over half of the batch;
* ``no_exchange`` (cells over several chips): the reference with each node
  keeping its own decoded gradient, read on node 0.

A state left unchanged reads 1 on ``update_gap`` by definition and is not
run. Each reading is one JSON line on standard output, with the per-leaf
norms the numbers are taken from, the per-leaf norm of the first step's
change, and the per-leaf median of the first moment after each step (most
coordinates of a Bernoulli-coded bucket carry its decoded mean, so the
median shows that mean's sign).
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path[0:1] = [str(ROOT), str(ROOT / "src")]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--faults", type=int, default=3)
    args = ap.parse_args(argv)

    import jax

    from repro.launch import compile_cache

    compile_cache.enable()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    from benchmarks.chip import harness

    cell = harness.load_cell(args.workload)
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell.chips:
        print("calibrate: needs a TPU with the cell's chips", file=sys.stderr)
        return 2
    readings(cell, args.seeds, args.faults, devices)
    print(json.dumps({"cell": cell.name, "kind": "done",
                      "seconds": time.perf_counter() - T_START}), flush=True)
    return 0


def readings(cell, seeds, faults, devices):
    """Print one JSON line per reading (see the module docstring)."""
    import jax
    import jax.numpy as jnp

    from benchmarks.chip import compare, harness, reference, traffic, weights

    t = cell.traffic
    nodes = t["mesh"]["data"]
    n = t["check_steps"]
    prog = harness.Program(cell, devices)
    medians = jax.jit(lambda m: {k: jnp.median(v) for k, v in m.items()})

    def prober(seed):
        """(probe, what it read): per-step first-moment medians and the
        first step's change."""
        seen = {"m_median": [], "step1": None}

        def probe(k, params, m):
            seen["m_median"].append(
                {k2: float(v) for k2, v in jax.device_get(medians(m)).items()})
            if k == 0:
                p0 = weights.make(cell.config, seed,
                                  {k2: v.sharding for k2, v in params.items()})
                seen["step1"] = {k2: float(v) for k2, v in jax.device_get(
                    harness._diff_norms(params, p0)).items()}
                harness.free(p0)
        return probe, seen

    def ref(seed, batches, **kw):
        t0 = time.perf_counter()
        probe, seen = prober(seed)
        out = reference.run(cell.config, t, seed, traffic.PROGRAM_SEED,
                            batches, nodes, devices, probe=probe, **kw)
        out["seconds"] = time.perf_counter() - t0
        out.update(seen)
        return out

    def emit(kind, seed, read, base, extra=None):
        nums = compare.numbers(read, base)
        med = compare.numbers(read, base, compare.NUMBERS)
        line = {"cell": cell.name, "kind": kind, "seed": seed,
                **{k: v[0] for k, v in nums.items()},
                "worst": {k: v[1] for k, v in nums.items()},
                "median_leaf": {k: med[k][0] for k in ("grad_gap",
                                                       "update_gap")},
                "step_loss_gaps": [abs(a - b) / abs(b) for a, b in
                                   zip(read["losses"], base["losses"])],
                "grad_norms": read["grad"], "ref_grad_norms": base["grad"],
                "update_norms": read["update"],
                "ref_update_norms": base["update"],
                "ref_raw_grad_norms": base["raw_grad"],
                "step1_update_norms": read["step1"],
                "ref_step1_update_norms": base["step1"],
                "m_median": read["m_median"],
                "ref_m_median": base["m_median"]}
        line.update(extra or {})
        print(json.dumps(line), flush=True)

    for i, seed in enumerate(seeds):
        ring = traffic.ring(seed, t, cell.config["vocab_size"], cell.chips)
        probe, seen = prober(seed)
        state, read = harness.checked_steps(prog, seed, ring,
                                            prog.init(seed), n, probe)
        read.update(seen)
        harness.free(state)
        del state
        gc.collect()
        base = ref(seed, ring[:n])
        emit("program", seed, read, base, {"ref_seconds": base["seconds"]})
        if i >= faults:
            continue
        emit("control", seed, ref(seed, ring[:n], policy="int8"), base)
        half = []
        for hb in ring[:n]:
            b = hb["tokens"].shape[0] // nodes
            dup = {}
            for k, a in hb.items():
                a = a.reshape((nodes, b) + a.shape[1:]).copy()
                a[:, b // 2:] = a[:, :b // 2]
                dup[k] = a.reshape(hb[k].shape)
            half.append(dup)
        emit("half_batch", seed, ref(seed, half), base)
        if nodes > 1:
            emit("no_exchange", seed, ref(seed, ring[:n], exchange=False),
                 base)


if __name__ == "__main__":
    sys.exit(main())
