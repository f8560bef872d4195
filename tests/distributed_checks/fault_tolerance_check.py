"""Straggler/failure-path check on 8 fake devices: robust_mean equals the
live-subset mean; the host-side and in-shard failure views agree at every
(step, rate) because they derive from one shared draw; the all-dead
partial_mean is NaN by contract, never a silent zero."""
import os

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"

import functools  # noqa: E402

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from jax.sharding import PartitionSpec as P  # noqa: E402

from repro.distributed.fault_tolerance import (FailurePlan, partial_mean,  # noqa: E402
                                               robust_mean, survivor_index)

mesh = jax.make_mesh((8,), ("data",))
N, D = 8, 1024
XS = jax.random.normal(jax.random.PRNGKey(0), (N, D))
plan = FailurePlan(rate=0.3, seed=5)


@functools.partial(jax.shard_map, mesh=mesh, in_specs=(P("data"),),
                   out_specs=P(), check_vma=False)
def agg(xs):
    return robust_mean(xs.reshape(D), 3, ("data",), plan)


got = np.asarray(jax.jit(agg)(XS))
alive = np.asarray(plan.alive_mask(3, N))
want = np.asarray(XS)[alive].mean(axis=0)
assert alive.sum() < N, "plan should kill someone at rate 0.3"
np.testing.assert_allclose(got, want, atol=1e-5)
print(f"[ok] robust_mean over {int(alive.sum())}/{N} live nodes")

# alive_mask (host view) and local_alive (in-shard view) derive from ONE
# shared draw — the gathered per-shard scalars equal the host mask at
# every step and rate, including the 0.0 / 1.0 edges.
for rate in (0.0, 0.3, 0.7, 1.0):
    p = FailurePlan(rate=rate, seed=11)
    for step in range(5):

        @functools.partial(jax.shard_map, mesh=mesh,
                           in_specs=(P("data"),), out_specs=P("data"),
                           check_vma=False)
        def view(xs):
            del xs
            return p.local_alive(step, ("data",)).reshape(1)

        got = np.asarray(jax.jit(view)(XS))
        want = np.asarray(p.alive_mask(step, N)).astype(np.float32)
        assert np.array_equal(got, want), (rate, step, got, want)
        assert np.array_equal(
            np.asarray(p.drop_mask(step, N)), want), (rate, step)
        if rate == 1.0:
            assert want.sum() == 1, want  # the one-survivor rule
            key = jax.random.fold_in(jax.random.PRNGKey(p.seed), step)
            surv = int(survivor_index(jax.random.uniform(key, (N,))))
            assert want[surv] == 1.0, (step, surv, want)
print("[ok] local_alive == alive_mask == drop_mask across steps x rates")

# robust_mean tracks the plan's survivor set over a denser steps grid —
# every step's aggregate equals the numpy mean over that step's live rows
# (the same jit cache entry serves all steps: step enters via closure
# rebuild here, so assert value-correctness only).
p = FailurePlan(rate=0.5, seed=23)
for step in range(8):

    @functools.partial(jax.shard_map, mesh=mesh, in_specs=(P("data"),),
                       out_specs=P(), check_vma=False)
    def agg_s(xs, _step=step):
        return robust_mean(xs.reshape(D), _step, ("data",), p)

    got = np.asarray(jax.jit(agg_s)(XS))
    alive = np.asarray(p.alive_mask(step, N))
    want = np.asarray(XS)[alive].mean(axis=0)
    np.testing.assert_allclose(got, want, atol=1e-5, err_msg=str(step))
print("[ok] robust_mean == live-subset mean across an 8-step grid")


# all-dead partial_mean is NaN by contract (0/0): an impossible state under
# FailurePlan's survivor rule must poison the step, not silently zero it.
@functools.partial(jax.shard_map, mesh=mesh, in_specs=(P("data"),),
                   out_specs=P(), check_vma=False)
def all_dead(xs):
    return partial_mean(xs.reshape(D), jnp.float32(0.0), ("data",))


assert np.isnan(np.asarray(jax.jit(all_dead)(XS))).all()
print("[ok] all-dead partial_mean is NaN by contract")
print("FAULT TOLERANCE CHECK PASSED")
