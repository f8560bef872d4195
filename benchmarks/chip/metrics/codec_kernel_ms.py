"""Device time per step of the step's Pallas kernels (``tpu_custom_call``
instructions of its compiled HLO), averaged over the cell's devices."""

from benchmarks.chip import trace_reduce


def read(r):
    if not any(c == "kernel" for c in r.classes.values()):
        return None
    tot = sum(trace_reduce.class_ns(d, "kernel") for d in r.trace.devices)
    return tot / len(r.trace.devices) * 1e-6 / r.steps
