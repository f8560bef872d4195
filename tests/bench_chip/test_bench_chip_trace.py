"""The reduction from a profiler trace to the per-layer metrics, on a small
trace recorded on a TPU v5e (benchmarks/chip/record_fixture.py) and on
hand-made intervals; the peaks table."""
import gzip
import json
import os
import pathlib
import subprocess
import sys

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path[0:0] = [str(ROOT)]

from benchmarks.chip import harness, trace_reduce  # noqa: E402

DATA = ROOT / "tests" / "bench_chip" / "data"
FIXTURES = sorted(p.name[:-len(".classes.json")]
                  for p in DATA.glob("*.classes.json"))


def dev(ops, transfers=()):
    return trace_reduce.Device("/device:TPU:0", sorted(ops), sorted(transfers))


def test_interval_arithmetic():
    d = dev([(0, 10, "a", "other"), (5, 20, "b", "kernel"),
             (30, 40, "c", "collective"), (35, 50, "d", "other")],
            [(60, 80, "e", "collective"), (70, 75, "e", "collective")])
    assert trace_reduce.busy_ns(d) == 20 + 20       # transfers are not busy
    assert trace_reduce.class_ns(d, "kernel") == 15
    assert trace_reduce.class_ns(d, "collective") == 10 + 20 + 5
    # c runs alone 30..35; the transfer 60..80 overlaps no operation
    assert trace_reduce.exposed_ns(d, "collective") == 5 + 20
    assert trace_reduce.union([(3, 4), (0, 2), (1, 3)]) == [(0, 4)]
    assert trace_reduce.subtract([(0, 10)], [(2, 3), (5, 12)]) == 2 + 2


def test_idle_gaps_are_named_by_host_span():
    tr = trace_reduce.Trace(
        window=(0, 100), devices=[dev([(10, 20, "a", "other"),
                                       (60, 100, "b", "other")])],
        host=[(0, 12, "batch_put"), (20, 58, "dispatch")])
    gaps = trace_reduce.idle_gaps(tr)
    assert gaps == [["dispatch", 40e-9], ["batch_put", 10e-9]]


HLO = """\
HloModule jit_step, entry_computation_layout={()->f32[8]{0}}

ENTRY %main.1 () -> f32[8] {
  %custom-call.3 = (f32[8,128]{1,0:T(8,128)}, u32[]) custom-call(f32[8]{0} %p), custom_call_target="tpu_custom_call", backend_config="x"
  %custom-call.4 = f32[8]{0} custom-call(f32[8]{0} %p), custom_call_target="Sharding"
  %all-gather.36 = f32[4,8]{1,0} all-gather(f32[1,8]{1,0} %x), replica_groups={{0,1,2,3}}, dimensions={0}
  ROOT %all-reduce-start.2 = f32[8]{0} all-reduce-start(f32[8]{0} %y), to_apply=%add
  %async-start.7 = ((f32[8]), f32[2], s32[]) async-start(f32[8]{0} %z), calls=%reduce-scatter.9
  %while.106 = (s32[], f32[8]{0}) while((s32[], f32[8]{0}) %t), condition=%c, body=%b
  %fusion.865 = (f32[4,64]{1,0:T(4,128)S(1)}, f32[4]{0}) fusion(f32[512,64]{0,1:T(8,128)} %a), kind=kLoop, calls=%fused_computation.1
}
"""


def test_classify_hlo():
    assert trace_reduce.classify_hlo(HLO) == {
        "custom-call.3": "kernel", "custom-call.4": "other",
        "all-gather.36": "collective", "all-reduce-start.2": "collective",
        "async-start.7": "collective", "while.106": "container",
        "fusion.865": "other"}


@pytest.fixture(params=FIXTURES)
def recorded(request, tmp_path):
    tag = request.param
    xplane = tmp_path / f"{tag}.xplane.pb"
    xplane.write_bytes(gzip.decompress(
        (DATA / f"{tag}.xplane.pb.gz").read_bytes()))
    meta = json.loads((DATA / f"{tag}.classes.json").read_text())
    hlo = gzip.decompress((DATA / f"{tag}.hlo.txt.gz").read_bytes()).decode()
    return tag, xplane, meta, hlo


def test_recorded_hlo_classes(recorded):
    _, _, meta, hlo = recorded
    assert trace_reduce.classify_hlo(hlo) == meta["classes"]


def test_recorded_trace_reduction(recorded):
    tag, xplane, meta, _ = recorded
    classes = meta["classes"]
    tr = trace_reduce.reduce(xplane, classes)
    chips = 4 if "dp4" in tag else 1
    assert len(tr.devices) == chips
    assert tr.window_s > 0
    for d in tr.devices:
        assert d.ops, d.name
        assert 0 < trace_reduce.busy_ns(d) <= tr.window_s * 1e9
        # no container (while) interval is counted beside its body
        assert all(c != "container" for *_, c in d.ops)
        ends = [e for _, e, _, _ in d.ops]
        starts = [s for s, _, _, _ in d.ops]
        assert all(s >= e0 for s, e0 in zip(starts[1:], ends[:-1]))
        assert trace_reduce.class_ns(d, "kernel") > 0
    r = harness.Reading(tr, classes, meta["steps"], tokens_per_s=1e4,
                        flops_per_token=1e6, chips=chips,
                        peak={"bf16_flops_per_s": 197e12})
    assert 0 < harness.read_metric("step_mfu", r) < 100
    assert 0 <= harness.read_metric("device_idle_share", r) < 100
    assert harness.read_metric("codec_kernel_ms", r) > 0
    coll = harness.read_metric("collective_ms", r)
    if chips == 1:
        assert coll is None
    else:
        exposed = harness.read_metric("collective_exposed_ms", r)
        assert 0 < exposed <= coll
    ops = trace_reduce.top_ops(tr)
    assert 0 < len(ops) <= 10
    assert [v for _, v in ops] == sorted((v for _, v in ops), reverse=True)
    for name, secs in trace_reduce.idle_gaps(tr):
        assert name in trace_reduce.HOST_SPANS + ("none",)
        assert 0 < secs <= tr.window_s


def test_peaks_table():
    table = json.loads((ROOT / "benchmarks/chip/peaks.json").read_text())
    assert "TPU v5e" in table["source"]
    v5e = harness.peaks("TPU v5 lite")
    assert v5e["bf16_flops_per_s"] == 197e12
    assert v5e["hbm_bytes_per_s"] == 819e9
    assert v5e["hbm_bytes"] == 16e9
    assert v5e["ici_bits_per_s"] == 1600e9
    with pytest.raises(KeyError):
        harness.peaks("cpu")


def test_import_loads_no_backend():
    """Importing the benchmark's modules starts no JAX backend, so no
    worker that imports them takes the TPU library."""
    code = ("import sys; sys.path[0:0] = [%r, %r]\n"
            "import benchmarks.chip.harness, benchmarks.chip.calibrate\n"
            "import benchmarks.chip.record_fixture\n"
            "from jax._src import xla_bridge\n"
            "print(len(xla_bridge._backends))" % (str(ROOT), str(ROOT / "src")))
    p = subprocess.run([sys.executable, "-c", code], env=dict(os.environ),
                       capture_output=True, text=True, timeout=300)
    assert p.returncode == 0, p.stderr[-2000:]
    assert p.stdout.strip() == "0"
