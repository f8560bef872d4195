"""Record the small chip trace that tests/bench_chip reads.

    python3 benchmarks/chip/record_fixture.py --traffic <tiny traffic> \
        --out <dir>

Runs the tests' toy Mamba-2 (tests/bench_chip/data) through the harness's
own set-up and window for a step or two with the profiler on, and writes
to ``--out`` the trace (``<tag>.xplane.pb.gz``), the compiled step's HLO
instructions that the trace names (``<tag>.hlo.txt.gz``), and their
classification with the steps traced (``<tag>.classes.json``).
"""
import argparse
import gzip
import json
import pathlib
import shutil
import sys

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path[0:1] = [str(ROOT), str(ROOT / "src")]
DATA = ROOT / "tests" / "bench_chip" / "data"


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--traffic", required=True)
    ap.add_argument("--out", required=True)
    ap.add_argument("--seconds", type=float, default=0.0)
    args = ap.parse_args(argv)

    import jax
    import numpy as np

    from benchmarks.chip import harness, trace_reduce, traffic

    t = json.loads((DATA / f"tiny.{args.traffic}.json").read_text())
    cell = harness.Cell(
        name=f"tiny.{args.traffic}",
        config=json.loads((DATA / "mamba2-tiny.json").read_text()),
        traffic=t, chips=t["mesh"]["data"], per_layer=[], limits=None)
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell.chips:
        print("record_fixture: needs a TPU with the cell's chips",
              file=sys.stderr)
        return 2
    prog = harness.Program(cell, devices)
    ring = traffic.ring(1, t, cell.config["vocab_size"], cell.chips)
    state, _ = harness.checked_steps(prog, 1, ring, prog.init(1),
                                     t["check_steps"])
    jax.block_until_ready(state)
    harness.start_trace()
    state, steps, _, _ = harness.window(prog, ring, state, t["check_steps"],
                                        args.seconds)
    jax.profiler.stop_trace()
    hlo = prog.trainer.step_fn.lower(*state, prog.put(ring[0]),
                                     np.int32(0)).compile().as_text()
    xplane = trace_reduce.xplane_file(harness.TRACE_DIR)
    classes = trace_reduce.classify_hlo(hlo)
    from jax.profiler import ProfileData

    named = {trace_reduce.op_name(ev)
             for plane in ProfileData.from_file(str(xplane)).planes
             if plane.name.startswith("/device:TPU:")
             for line in plane.lines
             if line.name in ("XLA Ops", "Async XLA Ops")
             for ev in line.events}
    out = pathlib.Path(args.out)
    out.mkdir(parents=True, exist_ok=True)
    tag = f"tiny.{args.traffic}"
    (out / f"{tag}.xplane.pb.gz").write_bytes(
        gzip.compress(xplane.read_bytes()))
    shutil.rmtree(harness.TRACE_DIR, ignore_errors=True)
    lines = [ln for ln in hlo.splitlines()
             if (m := trace_reduce._INSTR.match(ln)) and m.group(1) in named]
    (out / f"{tag}.hlo.txt.gz").write_bytes(
        gzip.compress("\n".join(lines).encode()))
    (out / f"{tag}.classes.json").write_text(json.dumps(
        {"steps": steps,
         "classes": {n: classes[n] for n in sorted(named)}}, indent=0))
    print(json.dumps({"tag": tag, "steps": steps}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
