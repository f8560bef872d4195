"""The part of collective_ms in which no other operation runs on that
device, per step, averaged over devices."""

from benchmarks.chip import trace_reduce


def read(r):
    if not any(c == "collective" for c in r.classes.values()):
        return None
    tot = sum(trace_reduce.exposed_ns(d, "collective")
              for d in r.trace.devices)
    return tot / len(r.trace.devices) * 1e-6 / r.steps
