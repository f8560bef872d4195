"""BENCHMARK.json against the contract its harness relies on, the shape of
a run's result line, and the refusal to run without a TPU."""
import gzip
import importlib
import json
import os
import pathlib
import re
import subprocess
import sys

import jax
import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path[0:0] = [str(ROOT)]

from benchmarks.chip import compare, harness, scopes  # noqa: E402
from benchmarks.chip import trace_reduce  # noqa: E402

BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
DATA = ROOT / "tests" / "bench_chip" / "data"
NAME = re.compile(r"[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}")
UNIT = re.compile(r"[A-Za-z0-9_/%.\-]{1,16}")


def all_names():
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        for entry in BENCH[group]:
            yield entry["name"]
    for w in BENCH["workloads"]:
        yield w["config"]
        yield w["traffic"]
    for c in BENCH["configs"]:
        yield from c["reduced"]


@pytest.mark.parametrize("name", sorted(set(all_names())))
def test_names_use_allowed_letters(name):
    assert NAME.fullmatch(name), name


@pytest.mark.parametrize("metric", BENCH["end_to_end"] + BENCH["per_layer"],
                         ids=lambda m: m["name"])
def test_units_and_sources(metric):
    assert UNIT.fullmatch(metric["unit"]), metric["unit"]
    assert metric["better"] in ("lower", "higher")
    allowed = (("host_clock", "device_trace") if "bound" in metric else
               ("host_clock", "device_trace", "program_span",
                "program_counter"))
    assert metric["source"] in allowed


def test_names_are_unique():
    for group in ("configs", "workloads"):
        names = [e["name"] for e in BENCH[group]]
        assert len(names) == len(set(names))
    metrics = [m["name"] for m in BENCH["end_to_end"] + BENCH["per_layer"]]
    assert len(metrics) == len(set(metrics))


@pytest.mark.parametrize("cell", BENCH["workloads"], ids=lambda w: w["name"])
def test_cell_files_exist(cell):
    c = harness.load_cell(cell["name"])
    assert cell["name"] == f"{cell['config']}.{cell['traffic']}"
    assert c.config["name"] == cell["config"]
    assert c.limits is not None
    assert c.traffic["mesh"]["data"] * c.traffic["mesh"]["model"] == c.chips
    assert c.per_layer, "every cell reports a per-layer metric"
    for m in c.per_layer:
        mod = importlib.import_module(f"benchmarks.chip.metrics.{m['name']}")
        assert callable(mod.read)


@pytest.mark.parametrize("config", BENCH["configs"], ids=lambda c: c["name"])
def test_config_files(config):
    path = ROOT / config["file"]
    assert any(path.is_relative_to(ROOT / p) for p in BENCH["paths"])
    body = json.loads(path.read_text())
    assert body["source"] == config["source"]
    assert sorted(body["reduced_from_source"]) == sorted(config["reduced"])
    assert any(w["config"] == config["name"] for w in BENCH["workloads"])


def test_per_layer_cells_exist():
    cells = {w["name"] for w in BENCH["workloads"]}
    moves = {m["name"] for m in BENCH["end_to_end"]}
    for m in BENCH["per_layer"]:
        assert m["moves"] in moves
        assert set(m.get("workloads", cells)) <= cells


def tiny_cell(traffic_name, per_layer=()):
    t = json.loads((DATA / f"tiny.{traffic_name}.json").read_text())
    limits = json.loads((DATA / "tiny.limits.json").read_text())
    return harness.Cell(
        name=f"tiny.{traffic_name}",
        config=json.loads((DATA / "mamba2-tiny.json").read_text()),
        traffic=t, chips=t["mesh"]["data"],
        per_layer=[{"name": n, "unit": u} for n, u in per_layer],
        limits=limits[traffic_name])


@pytest.fixture
def cpu_peaks(monkeypatch):
    monkeypatch.setattr(harness, "peaks",
                        lambda kind: {"bf16_flops_per_s": 197e12})


@pytest.mark.parametrize("traced", [False, True])
def test_result_line_keys(traced, cpu_peaks, monkeypatch, tmp_path):
    per_layer = [("step_mfu", "%"), ("device_idle_share", "%"),
                 ("codec_kernel_ms", "ms/step")]
    if traced:
        # The CPU has no TPU planes to trace: read the committed chip trace.
        tag = "tiny.train.ef_rotated_binary"
        classes = json.loads(
            (DATA / f"{tag}.classes.json").read_text())["classes"]
        xplane = tmp_path / f"{tag}.xplane.pb"
        xplane.write_bytes(gzip.decompress(
            (DATA / f"{tag}.xplane.pb.gz").read_bytes()))
        tr = trace_reduce.reduce(xplane, classes)
        monkeypatch.setattr(trace_reduce, "reduce", lambda p, c: tr)
        monkeypatch.setattr(trace_reduce, "xplane_file", lambda d: None)
        monkeypatch.setattr(trace_reduce, "classify_hlo", lambda t: classes)
    out = harness.run(tiny_cell("train.ef_rotated_binary", per_layer),
                      seed=2 ** 33 + 5, seconds=0.5, trace=traced,
                      devices=jax.devices(), t_start=0.0)
    keys = ["correct", "attempted", "failed", "metrics", "device"]
    if traced:
        keys.append("breakdown")
    assert list(out) == keys + ["checks"]
    json.dumps(out, allow_nan=False)
    assert out["correct"] is True and out["failed"] == 0
    assert out["attempted"] >= 1
    assert set(out["checks"]) == {"loss_gap", "grad_gap", "update_gap"}
    if traced:
        assert set(out["metrics"]) == {n for n, _ in per_layer}
        assert 0 < out["device"]["busy_s"] <= out["device"]["window_s"]
        assert set(out["breakdown"]) == {"device_ops", "idle_gaps"}
        assert len(out["breakdown"]["device_ops"]) <= 10
    else:
        assert set(out["metrics"]) == {"train_tokens_per_s", "setup_s"}


def test_traced_run_reads_the_scopes(cpu_peaks, monkeypatch, tmp_path):
    """A traced run hands the per-layer readers the compiled step's scope
    map; the scoped chip trace stands in for the CPU's, which has no TPU
    planes."""
    scoped = DATA / "scoped"
    tag = "tiny.train.ef_rotated_binary"
    classes = json.loads(
        (scoped / f"{tag}.classes.json").read_text())["classes"]
    xplane = tmp_path / f"{tag}.xplane.pb"
    xplane.write_bytes(gzip.decompress(
        (scoped / f"{tag}.xplane.pb.gz").read_bytes()))
    tr = trace_reduce.reduce(xplane, classes)
    smap = scopes.scope_map(gzip.decompress(
        (scoped / f"{tag}.hlo.txt.gz").read_bytes()).decode())
    monkeypatch.setattr(trace_reduce, "reduce", lambda p, c: tr)
    monkeypatch.setattr(trace_reduce, "xplane_file", lambda d: None)
    monkeypatch.setattr(trace_reduce, "classify_hlo", lambda t: classes)
    monkeypatch.setattr(scopes, "scope_map", lambda t: smap)
    per_layer = [("codec_kernel_ms", "ms/step"), ("codec_round_ms", "ms/step"),
                 ("grad_sync_ms", "ms/step")]
    out = harness.run(tiny_cell("train.ef_rotated_binary", per_layer),
                      seed=2 ** 33 + 6, seconds=0.2, trace=True,
                      devices=jax.devices(), t_start=0.0)
    m = {k: v["value"] for k, v in out["metrics"].items()}
    assert set(m) == {n for n, _ in per_layer}
    assert 0 < m["codec_kernel_ms"] <= m["codec_round_ms"] <= m["grad_sync_ms"]


def test_run_cell_refuses_cpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    p = subprocess.run(
        [sys.executable, str(ROOT / "benchmarks/chip/run_cell.py"),
         "--workload", BENCH["workloads"][0]["name"], "--seed", "1",
         "--seconds", "1", "--trace", "0"],
        env=env, capture_output=True, text=True, timeout=300, cwd=ROOT)
    assert p.returncode != 0
    assert "{" not in p.stdout


@pytest.mark.parametrize("cell", BENCH["workloads"], ids=lambda w: w["name"])
def test_limits_name_every_number(cell):
    limits = harness.load_cell(cell["name"]).limits
    assert set(limits) == set(compare.NUMBERS) | {"median_leaf"}
    assert set(limits["median_leaf"]) <= {"grad_gap", "update_gap"}
    assert all(limits[k] > 0 for k in compare.NUMBERS)


def test_judge_refuses_limits_that_leave_a_number_out():
    nums = {k: (0.0, "") for k in compare.NUMBERS}
    with pytest.raises(KeyError, match="update_gap"):
        compare.judge(nums, {"loss_gap": 1.0, "grad_gap": 1.0})


@pytest.mark.parametrize("median_leaf", [(), ("grad_gap", "update_gap")])
def test_leaf_reduction(median_leaf):
    ref = {"losses": [2.0], "grad": {"a": 1.0, "b": 1.0, "c": 1.0},
           "raw_grad": {"a": 1.0, "b": 1.0, "c": 1.0},
           "update": {"a": 1.0, "b": 1.0, "c": 1.0}}
    prog = {"losses": [2.0], "grad": {"a": 1.0, "b": 1.1, "c": 1.5},
            "update": {"a": 1.0, "b": 1.1, "c": 1.5}}
    nums = compare.numbers(prog, ref, median_leaf)
    want = (0.1, "median leaf") if median_leaf else (0.5, "c")
    for k in ("grad_gap", "update_gap"):
        assert nums[k][0] == pytest.approx(want[0]) and nums[k][1] == want[1]
    assert nums["loss_gap"][0] == 0.0
