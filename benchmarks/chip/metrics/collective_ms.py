"""Device time per step of the step's collectives (all-gather, all-reduce,
reduce-scatter and kin in its compiled HLO), averaged over devices."""

from benchmarks.chip import trace_reduce


def read(r):
    if not any(c == "collective" for c in r.classes.values()):
        return None
    tot = sum(trace_reduce.class_ns(d, "collective") for d in r.trace.devices)
    return tot / len(r.trace.devices) * 1e-6 / r.steps
