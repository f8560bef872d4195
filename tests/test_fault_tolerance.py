"""Straggler mitigation / failure-drop path (subprocess, 8 fake devices)
plus meshless units for the FailurePlan draw and partial_mean's contract."""
import functools
import os
import pathlib
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.distributed.fault_tolerance import (FailurePlan, partial_mean,
                                               survivor_index)

ROOT = pathlib.Path(__file__).resolve().parent.parent


@pytest.mark.distributed
def test_fault_tolerance():
    env = dict(os.environ)
    env["PYTHONPATH"] = str(ROOT / "src")
    env.pop("XLA_FLAGS", None)
    res = subprocess.run(
        [sys.executable, str(ROOT / "tests" / "distributed_checks" /
                             "fault_tolerance_check.py")],
        env=env, capture_output=True, text=True, timeout=600)
    assert res.returncode == 0, f"\nSTDOUT:\n{res.stdout}\nSTDERR:\n{res.stderr}"
    assert "FAULT TOLERANCE CHECK PASSED" in res.stdout


def test_survivor_index_tie_rule():
    # THE explicit contract: smallest index among the maxima.
    assert int(survivor_index(jnp.asarray([1.0, 3.0, 3.0, 2.0]))) == 1
    assert int(survivor_index(jnp.asarray([3.0, 1.0, 3.0, 3.0]))) == 0
    assert int(survivor_index(jnp.zeros((5,)))) == 0  # all tied -> first
    assert int(survivor_index(jnp.asarray([-1.0, -1.0, -2.0]))) == 0
    assert int(survivor_index(jnp.asarray([0.0, 0.0, 7.0]))) == 2


def test_survivor_index_properties():
    # bit-compatible with the historical bare argmax on tie-free draws,
    # always a maximum, stable under appending smaller values.
    for seed in range(25):
        u = jax.random.uniform(jax.random.PRNGKey(seed), (8,))
        i = int(survivor_index(u))
        assert i == int(jnp.argmax(u))
        assert float(u[i]) == float(jnp.max(u))
        longer = jnp.concatenate([u, u - 1.0])
        assert int(survivor_index(longer)) == i


def test_drop_mask_matches_alive_mask_grid():
    # drop_mask is alive_mask in traced-operand f32 form — one draw,
    # two consumers — across a rates x steps x sizes grid, survivor
    # clamp included at rate 1.0.
    for rate in (0.0, 0.25, 0.5, 0.9, 1.0):
        plan = FailurePlan(rate=rate, seed=7)
        for step in (0, 1, 5, 17):
            for n in (2, 8):
                dm = np.asarray(plan.drop_mask(step, n))
                am = np.asarray(plan.alive_mask(step, n))
                assert dm.dtype == np.float32
                assert np.array_equal(dm, am.astype(np.float32))
                assert dm.sum() >= 1  # never-kill-everyone
                if rate == 0.0:
                    assert dm.sum() == n
                if rate == 1.0:
                    # exactly the survivor_index node lives
                    key = jax.random.fold_in(
                        jax.random.PRNGKey(plan.seed), step)
                    u = jax.random.uniform(key, (n,))
                    want = np.zeros((n,), np.float32)
                    want[int(survivor_index(u))] = 1.0
                    assert np.array_equal(dm, want)


def test_failure_plan_edge_rates():
    # rate 0.0: everyone lives; rate 1.0: exactly the one argmax survivor.
    for step in range(10):
        assert np.asarray(FailurePlan(rate=0.0, seed=3)
                          .alive_mask(step, 8)).all()
        assert np.asarray(FailurePlan(rate=1.0, seed=3)
                          .alive_mask(step, 8)).sum() == 1


def test_failure_plan_views_share_one_draw():
    # local_alive indexes the SAME draw alive_mask returns — meshless
    # equivalence via the rank the (trivial) 1-device axis reports.
    plan = FailurePlan(rate=0.5, seed=9)
    mesh = jax.make_mesh((1,), ("data",))
    for step in range(6):

        @functools.partial(jax.shard_map, mesh=mesh, in_specs=(P(),),
                           out_specs=P(), check_vma=False)
        def local(x):
            del x
            return plan.local_alive(step, ("data",))

        want = float(np.asarray(plan.alive_mask(step, 1))[0])
        assert float(jax.jit(local)(jnp.zeros(()))) == want


def _pmean_1dev(x, alive):
    mesh = jax.make_mesh((1,), ("data",))

    @functools.partial(jax.shard_map, mesh=mesh, in_specs=(P(None), P()),
                       out_specs=P(), check_vma=False)
    def f(x, alive):
        return partial_mean(x * alive, alive, ("data",))

    return np.asarray(jax.jit(f)(x, alive))


def test_partial_mean_all_dead_is_nan():
    # 0/0 by contract: no clamp, no silent all-zero step.
    out = _pmean_1dev(jnp.ones((4,), jnp.float32), jnp.float32(0.0))
    assert np.isnan(out).all()


def test_partial_mean_single_survivor_is_exact():
    x = jnp.asarray([1.5, -2.0, 0.25, 3.0], jnp.float32)
    out = _pmean_1dev(x, jnp.float32(1.0))
    assert np.array_equal(out, np.asarray(x))
