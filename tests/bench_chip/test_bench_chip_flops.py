"""The benchmark's Mamba-2 FLOP count, against a hand count and XLA's."""
import json
import pathlib
import sys

import jax
import jax.numpy as jnp
import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path[0:0] = [str(ROOT), str(ROOT / "tests" / "bench_chip")]

from benchmarks.chip import flops, weights  # noqa: E402
from test_bench_chip_reference import program_model  # noqa: E402


def test_hand_count_mamba2_130m():
    cfg = json.loads(
        (ROOT / "benchmarks/chip/configs/mamba2-130m.json").read_text())
    d, din, n, h, p, q, v = 768, 1536, 128, 24, 64, 256, 50280
    per_layer = (2 * d * din * 2 + 2 * d * n * 2 + 2 * d * h   # in projections
                 + 2 * din * d                                # out projection
                 + 2 * q * n + 2 * q * h * p + 4 * h * p * n)  # SSD chunks
    fwd = 24 * per_layer + 2 * d * v                           # tied head
    assert fwd == 296_742_912
    assert flops.train_per_token(cfg, 4096) == 3 * fwd


@pytest.mark.parametrize("cfg", [
    dict(architecture="mamba2", n_layer=1, d_model=512, d_state=64,
         headdim=64, expand=2, d_conv=4, ngroups=1, chunk_size=128,
         vocab_size=8192),
    dict(architecture="mamba2", n_layer=1, d_model=256, d_state=32,
         headdim=32, expand=2, d_conv=4, ngroups=1, chunk_size=64,
         vocab_size=4096),
])
def test_against_xla_cost_analysis(cfg):
    """One layer and one chunk, so that no loop hides its trip count from
    XLA's count; no remat. XLA also counts the elementwise work the model
    count leaves out, so the count may fall short of it, by a little."""
    from repro.models.common import ShardCtx

    b, s = 2, cfg["chunk_size"]
    arch, run, specs, model_lib = program_model(cfg)
    ctx = ShardCtx(tp=1, fsdp=False, compute_dtype=jnp.float32,
                   seq_shard=False)
    params = jax.eval_shape(lambda: weights.make(cfg, 0))
    batch = {"tokens": jax.ShapeDtypeStruct((b, s), jnp.int32),
             "labels": jax.ShapeDtypeStruct((b, s), jnp.int32),
             "mask": jax.ShapeDtypeStruct((b, s), jnp.float32)}
    grad = jax.jit(jax.grad(lambda p, bt: model_lib.train_loss(
        ctx, p, specs, arch, run, bt, float(b * s))[0]))
    xla = grad.lower(params, batch).cost_analysis()["flops"]
    mine = flops.train_per_token(cfg, s) * b * s
    assert 0.97 * xla <= mine <= xla
