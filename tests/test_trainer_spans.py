"""Trainer.fit marks each step and its host phases on the profiler's clock.

Runs two steps of the toy Mamba-2 trainer of the benchmark's tests
(tests/bench_chip/data) under ``jax.profiler.trace`` and reads the host
plane of the recorded ``.xplane.pb`` back through ``ProfileData``.
"""
import json
import pathlib
import sys

import jax
from jax.profiler import ProfileData

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path[0:0] = [str(ROOT)]
DATA = ROOT / "tests" / "bench_chip" / "data"


def test_fit_spans_reach_the_trace(tmp_path):
    from benchmarks.chip import harness
    from repro.train.trainer import TrainerConfig

    t = json.loads((DATA / "tiny.train.ef_rotated_binary.json").read_text())
    cell = harness.Cell(
        name="tiny.train.ef_rotated_binary",
        config=json.loads((DATA / "mamba2-tiny.json").read_text()),
        traffic=t, chips=1, per_layer=[], limits=None)
    trainer = harness.Program(cell, jax.devices()).trainer
    trainer.tcfg = TrainerConfig(steps=2, log_every=1, seed=0)
    with jax.profiler.trace(str(tmp_path)):
        trainer.fit()
    assert len(trainer.metrics_history) == 2

    (xplane,) = tmp_path.glob("plugins/profile/*/*.xplane.pb")
    events = [ev for plane in ProfileData.from_file(str(xplane)).planes
              if plane.name.startswith("/host:")
              for line in plane.lines for ev in line.events]
    steps = [ev for ev in events if ev.name == "train"]
    assert sorted(dict(ev.stats).get("step_num") for ev in steps) == [0, 1]
    for span in ("feed", "dispatch", "metrics_sync"):
        inside = [ev for ev in events if ev.name == span]
        assert len(inside) == 2, span
        for ev in inside:
            assert any(s.start_ns <= ev.start_ns and ev.end_ns <= s.end_ns
                       for s in steps), span
