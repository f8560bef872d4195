"""Wire bytes do not depend on whether the compiler contracts a·b + c
into an FMA.

XLA:CPU contracts a multiply into the add that consumes it (one rounding)
where the CPU has FMA instructions; the TPU backend rounds the product
first.  A child process built with ``--xla_cpu_max_isa=SSE4_2`` has no FMA
instructions, so it rounds like the TPU: every preset's wire rows and
decoded mean must come out bit-identical in both processes.  The wire is
widened to f32 here so a one-ulp difference cannot hide in the bf16 cast.

Run directly (``python tests/test_fma_independence.py OUT.npz``) it writes
the child's results.
"""
import dataclasses
import os
import pathlib
import subprocess
import sys

import numpy as np
import pytest

ROOT = pathlib.Path(__file__).resolve().parent.parent
D = 1 << 12
N_PEERS = 8


def _presets():
    from repro.configs.registry import COMPRESSION_PRESETS
    return sorted(COMPRESSION_PRESETS)


def _round(name):
    """(rows as raw bytes, decoded mean) of one f32-wire round."""
    import jax
    import jax.numpy as jnp

    from repro.configs.registry import compression_preset
    from repro.core import wire

    cfg = dataclasses.replace(compression_preset(name, axes=("data",)),
                              wire_dtype="float32")
    codec = wire.resolve(cfg)
    rng = np.random.default_rng(D)
    xs = (rng.standard_normal((N_PEERS, D), dtype=np.float32)
          * rng.uniform(0.25, 2.0, (N_PEERS, 1)).astype(np.float32)
          + rng.uniform(-0.5, 0.5, (N_PEERS, 1)).astype(np.float32))

    def run(xs, key):
        rows = jax.lax.map(lambda r: codec.pack(xs[r], key, r, cfg),
                           jnp.arange(N_PEERS, dtype=jnp.int32))
        return rows, codec.decode_gathered(rows, key, cfg, D, N_PEERS)

    rows, mean = jax.jit(run)(xs, jax.random.PRNGKey(0))
    rows = np.ascontiguousarray(np.asarray(rows))
    return rows.view(np.uint8).reshape(N_PEERS, -1), np.asarray(mean)


@pytest.fixture(scope="module")
def no_fma_results(tmp_path_factory):
    out = tmp_path_factory.mktemp("no_fma") / "rounds.npz"
    env = dict(os.environ, PYTHONPATH=str(ROOT / "src"), JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_cpu_max_isa=SSE4_2")
    res = subprocess.run([sys.executable, __file__, str(out)], env=env,
                         capture_output=True, text=True, timeout=600)
    assert res.returncode == 0, res.stderr
    with np.load(out) as z:
        return {k: z[k] for k in z.files}


@pytest.mark.parametrize("name", _presets())
def test_round_is_contraction_independent(name, no_fma_results):
    rows, mean = _round(name)
    np.testing.assert_array_equal(rows, no_fma_results[name + "/rows"])
    np.testing.assert_array_equal(mean.view(np.uint32),
                                  no_fma_results[name + "/mean"].view(np.uint32))


if __name__ == "__main__":
    results = {}
    for preset in _presets():
        results[preset + "/rows"], results[preset + "/mean"] = _round(preset)
    np.savez(sys.argv[1], **results)
