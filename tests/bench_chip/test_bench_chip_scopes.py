"""The scope readers (benchmarks/chip/scopes.py, metrics/codec_round_ms.py,
metrics/grad_sync_ms.py): op_name paths on hand-made strings, and the
readers on a small trace recorded on a TPU v5e with the program's scopes
(data/scoped, benchmarks/chip/record_fixture.py) and on the older trace
recorded before the program had them (data/*.classes.json)."""
import gzip
import json
import pathlib
import sys

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path[0:0] = [str(ROOT)]

from benchmarks.chip import harness, scopes, trace_reduce  # noqa: E402

DATA = ROOT / "tests" / "bench_chip" / "data"
SCOPED = DATA / "scoped"
TAG = "tiny.train.ef_rotated_binary"
NEW = ("codec_round_ms", "grad_sync_ms")
OLD = ("step_mfu", "device_idle_share", "codec_kernel_ms", "collective_ms",
       "collective_exposed_ms")


@pytest.mark.parametrize("op_name, path", [
    # a scope opened in the sync point's custom_vjp backward rule
    ("jit(sharded_step)/transpose(jvp(grad_sync))/b0/encode/mul",
     ["grad_sync", "b0", "encode"]),
    ("jit(sharded_step)/transpose(jvp(grad_sync))/b3/codec/encode/rotate/"
     "jit(fwht_pallas)/hadamard/pallas_call",
     ["grad_sync", "b3", "codec", "encode", "rotate", "hadamard"]),
    ("jit(sharded_step)/grad_sync/b1/pack/concatenate",
     ["grad_sync", "b1", "pack"]),
    ("jit(sharded_step)/transpose(jvp())/while/body/closed_call/add",
     ["while", "body", "closed_call"]),
    ("jit(f)/vmap(transpose(jvp(a/b)))/c/mul", ["a", "b", "c"]),
    ("jit(sharded_step)/jvp(jit(_take))/gather", []),
    ("params['embed']", []),
])
def test_components_unwrap_transforms(op_name, path):
    assert scopes.components(op_name) == path


def test_scope_map_reads_op_name():
    hlo = (
        '  %fusion.7 = f32[8]{0} fusion(f32[8]{0} %a), kind=kLoop, '
        'calls=%fc, metadata={op_name="jit(s)/transpose(jvp(grad_sync))/'
        'b2/codec/decode/trim/sort" stack_frame_id=4}\n'
        '  %p.1 = f32[8]{0} parameter(0), '
        'metadata={op_name="params[\\\'embed\\\']"}\n'
        '  %copy.3 = f32[8]{0} copy(f32[8]{0} %p.1)\n')
    smap = scopes.scope_map(hlo)
    assert smap == {"fusion.7": ["grad_sync", "b2", "codec", "decode",
                                 "trim"], "p.1": []}
    assert scopes.stage(smap["fusion.7"]) == "decode"


@pytest.mark.parametrize("path, stage", [
    (["grad_sync", "b0", "codec", "encode", "rotate"], "rotate"),
    (["grad_sync", "b0", "codec", "encode", "unrotate", "hadamard"],
     "unrotate"),
    (["grad_sync", "b0", "codec", "ef"], "ef"),
    (["grad_sync", "b0", "codec"], None),
    (["grad_sync", "b0", "pack"], None),
    (["codec", "encode"], None),
])
def test_stage_is_innermost_below_codec(path, stage):
    assert scopes.stage(path) == stage


def test_scope_arithmetic():
    dev = trace_reduce.Device("/device:TPU:0", [
        (0, 10, "a", "other"), (10, 30, "b", "kernel"),
        (30, 35, "c", "other"), (40, 50, "d", "other")], [
        (35, 60, "e", "collective")])
    smap = {"a": ["grad_sync", "b0", "pack"],
            "b": ["grad_sync", "b0", "codec", "encode", "rotate"],
            "c": ["grad_sync", "b0", "codec", "exchange"],
            "e": ["grad_sync", "b0", "codec", "exchange"]}
    assert scopes.scope_ns(dev, smap, scopes.SYNC) == 35
    assert scopes.scope_ns(dev, smap, scopes.CODEC) == 25   # e in flight
    assert scopes.stage_ns(dev, smap) == {"rotate": 20.0, "exchange": 5.0}


def load(directory):
    xplane = gzip.decompress((directory / f"{TAG}.xplane.pb.gz").read_bytes())
    meta = json.loads((directory / f"{TAG}.classes.json").read_text())
    hlo = gzip.decompress((directory / f"{TAG}.hlo.txt.gz").read_bytes())
    return xplane, meta, hlo.decode()


def make_reading(directory, tmp_path):
    """A Reading of a recorded trace, with ``scopes`` set from its HLO."""
    xplane, meta, hlo = load(directory)
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(xplane)
    r = harness.Reading(trace_reduce.reduce(path, meta["classes"]),
                        meta["classes"], meta["steps"], tokens_per_s=1e4,
                        flops_per_token=1e6, chips=1,
                        peak={"bf16_flops_per_s": 197e12})
    r.scopes = scopes.scope_map(hlo)
    return r


@pytest.fixture(params=["scoped", "unscoped"])
def reading(request, tmp_path):
    """(kind, Reading) of the scoped trace and of the older one."""
    directory = SCOPED if request.param == "scoped" else DATA
    return request.param, make_reading(directory, tmp_path)


def test_scoped_fixture_nests(tmp_path):
    r = make_reading(SCOPED, tmp_path)
    kernel = harness.read_metric("codec_kernel_ms", r)
    codec = harness.read_metric("codec_round_ms", r)
    sync = harness.read_metric("grad_sync_ms", r)
    assert 0 < kernel <= codec <= sync


def test_codec_ops_have_one_stage_and_sum(tmp_path):
    r = make_reading(SCOPED, tmp_path)
    traced = {name for d in r.trace.devices for *_, name, _ in d.ops}
    codec = [n for n in traced
             if scopes.under(r.scopes.get(n, ()), scopes.CODEC)]
    assert codec
    for n in codec:
        assert scopes.stage(r.scopes[n]) in scopes.STAGES, n
    stages = scopes.stage_ms(r)
    assert set(stages) <= set(scopes.STAGES)
    assert sum(stages.values()) == pytest.approx(
        harness.read_metric("codec_round_ms", r), rel=1e-12)


@pytest.mark.parametrize("metric", NEW)
def test_new_readers_need_the_scopes(reading, metric):
    kind, r = reading
    got = harness.read_metric(metric, r)
    assert (got is None) == (kind == "unscoped")
    del r.scopes
    assert harness.read_metric(metric, r) is None


@pytest.mark.parametrize("metric", OLD)
def test_old_readers_ignore_the_scopes(reading, metric):
    _, r = reading
    with_scopes = harness.read_metric(metric, r)
    del r.scopes
    assert harness.read_metric(metric, r) == with_scopes
