"""The per-architecture files (benchmarks/chip/arch/<architecture>/), found by
a configuration's ``architecture`` key: a stub architecture swapped in
through the lookup supplies the program's ArchConfig, the weights, the
reference loss and the FLOP count; an unknown one is refused with the
directory to add; Mamba-2's leaves are the program's."""
import dataclasses
import hashlib
import json
import pathlib
import sys
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path[0:0] = [str(ROOT), str(ROOT / "tests" / "bench_chip")]

from benchmarks.chip import arch, flops, harness, reference  # noqa: E402
from benchmarks.chip import traffic, weights  # noqa: E402
from test_bench_chip_harness import tiny_cell  # noqa: E402

DATA = ROOT / "tests" / "bench_chip" / "data"
STUB_FLOPS = 1234.0
STUB_LEAF = 0.25


def tiny():
    return json.loads((DATA / "mamba2-tiny.json").read_text())


@pytest.fixture
def stub(monkeypatch):
    """An architecture ``stub`` that runs Mamba-2 in the program and
    records every call the benchmark makes into it."""
    m2 = {p: arch.part(tiny(), p) for p in arch.PARTS}
    calls = {p: [] for p in arch.PARTS}

    def arch_config(cfg):
        out = m2["program"].arch_config({**cfg, "architecture": "mamba2"})
        calls["program"].append(out)
        return out

    def param_shapes(cfg):
        calls["weights"].append("param_shapes")
        return m2["weights"].param_shapes(cfg)

    def init_leaf(name, shape, key, cfg):
        calls["weights"].append(name)
        return jnp.full(shape, STUB_LEAF, jnp.float32)

    def row_loss(params, tokens, labels, mask, cfg, policy):
        calls["model"].append(policy)
        return 3.0 * jnp.sum(mask) + 0.0 * params["embed"][tokens].sum()

    def forward_per_token(cfg, seq_len):
        calls["flops"].append(seq_len)
        return STUB_FLOPS

    funcs = {"program": {"arch_config": arch_config},
             "weights": {"param_shapes": param_shapes,
                         "init_leaf": init_leaf},
             "model": {"row_loss": row_loss},
             "flops": {"forward_per_token": forward_per_token}}
    pkg = "benchmarks.chip.arch.stub"
    monkeypatch.setitem(sys.modules, pkg, types.ModuleType(pkg))
    for p, fs in funcs.items():
        mod = types.ModuleType(f"{pkg}.{p}")
        mod.__dict__.update(fs)
        monkeypatch.setitem(sys.modules, f"{pkg}.{p}", mod)
    return {**tiny(), "architecture": "stub"}, calls


def test_stub_supplies_the_program_config(stub):
    cfg, calls = stub
    cell = dataclasses.replace(tiny_cell("train.exact"), config=cfg)
    prog = harness.Program(cell, jax.devices())
    assert calls["program"] and prog.trainer.cfg is calls["program"][-1]


def test_stub_supplies_the_weights(stub):
    cfg, calls = stub
    params = weights.make(cfg, 2 ** 40 + 1)
    assert set(calls["weights"]) == {"param_shapes"} | set(params)
    assert weights.param_shapes(cfg) == {k: v.shape for k, v in params.items()}
    for v in params.values():
        assert np.all(np.asarray(v) == STUB_LEAF)


def test_stub_supplies_the_reference_loss(stub):
    cfg, calls = stub
    t = tiny_cell("train.exact").traffic
    ring = traffic.ring(2 ** 40 + 2, t, cfg["vocab_size"], 1)
    out = reference.run(cfg, t, 2 ** 40 + 2, traffic.PROGRAM_SEED, ring[:1],
                        1, jax.devices(), policy="int8")
    assert calls["model"] and set(calls["model"]) == {"int8"}
    assert out["losses"] == [pytest.approx(3.0)]


def test_stub_supplies_the_flops(stub):
    cfg, calls = stub
    assert flops.train_per_token(cfg, 4096) == 3 * STUB_FLOPS
    assert calls["flops"] == [4096]


@pytest.mark.parametrize("entry", ["program", "weights", "model", "flops",
                                   "harness", "make", "train_per_token"])
def test_unknown_architecture_names_the_directory(entry):
    cfg = {**tiny(), "architecture": "nemotron_h"}
    cell = dataclasses.replace(tiny_cell("train.exact"), config=cfg)
    calls = {"harness": lambda: harness.Program(cell, jax.devices()),
             "make": lambda: weights.make(cfg, 1),
             "train_per_token": lambda: flops.train_per_token(cfg, 64)}
    call = calls.get(entry, lambda: arch.part(cfg, entry))
    with pytest.raises(ValueError, match="benchmarks/chip/arch/nemotron_h/"):
        call()


@pytest.mark.parametrize("name", [None, "../mamba2", "mamba2/model", ""])
def test_architecture_that_names_no_directory(name):
    cfg = {**tiny(), "architecture": name}
    with pytest.raises(ValueError, match="benchmarks/chip/arch/"):
        arch.part(cfg, "weights")


def test_mamba2_leaves_are_the_programs():
    from repro.configs.base import RunConfig
    from repro.models import model as model_lib
    from repro.models.common import ShardCtx

    cfg = tiny()
    arch_cfg = arch.part(cfg, "program").arch_config(cfg)
    run = RunConfig(model_parallel=False, seq_shard=False, remat=False)
    params, _ = model_lib.init(jax.random.PRNGKey(0), arch_cfg, ShardCtx(),
                               {"data": 1, "model": 1}, run, abstract=True)
    assert arch.part(cfg, "weights").param_shapes(cfg) == {
        k: tuple(v.shape) for k, v in params.items()}


@pytest.mark.parametrize("key, value", [("conv_bias", True),
                                        ("residual_in_fp32", True),
                                        ("tie_embeddings", False),
                                        ("compute_dtype", "float32")])
def test_mamba2_refuses_what_the_program_cannot_run(key, value):
    cfg = {**tiny(), key: value}
    with pytest.raises(ValueError, match=key):
        arch.part(cfg, "program").arch_config(cfg)


def test_mamba2_weights_draws_are_pinned():
    """The seeded tiny weights, byte for byte, as drawn before the leaves
    moved into arch/mamba2 (XLA:CPU)."""
    params = weights.make(tiny(), 2 ** 40 + 3)
    h = hashlib.sha256()
    for k in sorted(params):
        h.update(k.encode())
        h.update(np.asarray(params[k]).tobytes())
    assert h.hexdigest() == (
        "c9f73032b7f010c6366404cfea5f314468ca67cbb48b8d07236dfa828e7e368a")
