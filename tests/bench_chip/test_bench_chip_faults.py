"""A run with its timed path broken underneath comes out not correct.

Each test skips the harness's look for a chip, drives the rest of a run of
the toy cell on the CPU, and plants one fault: a step that returns its state
unchanged, a step that moves all leaves but one, half of the batch left out
with the mean taken over the rest, and (on four CPU devices) the exchange
between chips left out. The sound run of the same cell must stay correct
under the same limits (tests/bench_chip/data/tiny.limits.json), and the
int8 control must read above them (at these sizes it does so on the exact
cell only; the chip readings in PERF.md are what the limits of the
benchmark's own cells rest on).
"""
import json
import os
import pathlib
import subprocess
import sys

import jax
import numpy as np
import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path[0:0] = [str(ROOT), str(ROOT / "tests" / "bench_chip")]

from benchmarks.chip import compare, harness, reference, traffic  # noqa: E402
from test_bench_chip_harness import tiny_cell  # noqa: E402

SEED = 2 ** 35 + 11


@pytest.fixture(autouse=True)
def cpu_peaks(monkeypatch):
    monkeypatch.setattr(harness, "peaks",
                        lambda kind: {"bf16_flops_per_s": 197e12})


def run(cell):
    return harness.run(cell, SEED, 0.2, False, jax.devices(), 0.0)


@pytest.mark.parametrize("name", ["train.ef_rotated_binary",
                                  "train.bernoulli_seed_1bit", "train.exact"])
def test_sound_run_is_correct(name):
    out = run(tiny_cell(name))
    assert out["correct"], out["checks"]


def test_state_unchanged(monkeypatch):
    real = harness.Program.step

    def step(self, params, opt, ef, batch, k):
        _, _, _, m = real(self, params, opt, ef, batch, k)
        return params, opt, ef, m

    monkeypatch.setattr(harness.Program, "step", step)
    out = run(tiny_cell("train.ef_rotated_binary"))
    assert not out["correct"]
    assert out["checks"]["update_gap"]["value"] == pytest.approx(1.0)


def test_one_leaf_left_unmoved(monkeypatch):
    real = harness.Program.step

    def step(self, params, opt, ef, batch, k):
        p2, o2, e2, m = real(self, params, opt, ef, batch, k)
        return {**p2, "layers.ssm.w_x": params["layers.ssm.w_x"]}, o2, e2, m

    monkeypatch.setattr(harness.Program, "step", step)
    out = run(tiny_cell("train.ef_rotated_binary"))
    assert not out["correct"]
    assert out["checks"]["update_gap"]["value"] == pytest.approx(1.0)


def test_half_batch(monkeypatch):
    real = harness.Program.put

    def put(self, hb):
        b = hb["tokens"].shape[0]
        return real(self, {k: np.concatenate([a[:b // 2], a[:b // 2]])
                           for k, a in hb.items()})

    monkeypatch.setattr(harness.Program, "put", put)
    out = run(tiny_cell("train.ef_rotated_binary"))
    assert not out["correct"], out["checks"]


def test_control_reads_above_the_limits():
    cell = tiny_cell("train.exact")
    t = cell.traffic
    ring = traffic.ring(SEED, t, cell.config["vocab_size"], 1)
    args = (cell.config, t, SEED, traffic.PROGRAM_SEED, ring[:3], 1,
            jax.devices())
    base = reference.run(*args)
    ctl = reference.run(*args, policy="int8")
    correct, checks = compare.judge(
        compare.numbers(ctl, base, cell.limits["median_leaf"]), cell.limits)
    assert not correct, checks


NO_EXCHANGE = r"""
import json, sys
sys.path[0:0] = [{root!r}, {root!r} + "/src", {root!r} + "/tests/bench_chip"]
import jax
from benchmarks.chip import harness
from test_bench_chip_harness import tiny_cell
harness.peaks = lambda kind: {{"bf16_flops_per_s": 197e12}}
if {broken}:
    from repro.train import bucketing
    real = bucketing._bucket_round
    def local_round(grads, b, j, cmp, key, ef):
        import dataclasses
        return real(grads, dataclasses.replace(b, caxes=(), eaxes=()), j,
                    cmp, key, ef)
    bucketing._bucket_round = local_round
out = harness.run(tiny_cell("train-dp4.ef_rotated_binary"), {seed}, 0.2,
                  False, jax.devices(), 0.0)
print(json.dumps(out))
"""


@pytest.mark.parametrize("broken", [False, True])
def test_exchange_between_chips(broken):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    env["PYTHONPATH"] = str(ROOT / "src")
    p = subprocess.run(
        [sys.executable, "-c",
         NO_EXCHANGE.format(root=str(ROOT), broken=broken, seed=SEED)],
        env=env, capture_output=True, text=True, timeout=600, cwd=ROOT)
    assert p.returncode == 0, p.stderr[-3000:]
    out = json.loads(p.stdout.strip().splitlines()[-1])
    assert out["device"]["count"] == 4
    assert out["correct"] is (not broken), out["checks"]
