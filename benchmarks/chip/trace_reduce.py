"""From the profiler's ``.xplane.pb`` and the step's compiled HLO to the
numbers the per-layer metrics read.

Device operations are the events of each TPU device plane's ``XLA Ops``
line, plus the collectives among its ``Async XLA Ops`` (a transfer in
flight). Each is classified by its HLO instruction, looked up by name in
the compiled step's HLO text: ``custom-call`` with the ``tpu_custom_call``
target is a Pallas kernel; all-gather, all-reduce, reduce-scatter,
all-to-all and collective-permute (and their async start/done halves) are
collectives; ``while``, ``conditional`` and ``call`` contain other
operations and are left out, so that no interval is counted twice. Host
spans are the benchmark's own ``TraceAnnotation``s on the host plane; the
one named ``window`` bounds the reduction.
"""
from __future__ import annotations

import dataclasses
import pathlib
import re

COLLECTIVE_OPS = {
    "all-gather", "all-gather-start", "all-gather-done", "all-reduce",
    "all-reduce-start", "all-reduce-done", "reduce-scatter", "all-to-all",
    "collective-permute", "collective-permute-start",
    "collective-permute-done"}
COLLECTIVE_WORDS = ("all-gather", "all-reduce", "reduce-scatter",
                    "all-to-all", "collective-permute")
CONTAINER_OPS = {"while", "conditional", "call"}
HOST_SPANS = ("batch_put", "dispatch", "wait")

_INSTR = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*(.*)$")
_OPCODE = re.compile(r"\s*([\w\-]+)\(")
_TARGET = re.compile(r'custom_call_target="([^"]+)"')
_CALLS = re.compile(r"calls=%?([\w.\-]+)")


def _skip_type(rest: str) -> str:
    """Drop the leading result type (a shape or a parenthesized tuple)."""
    if rest.startswith("("):
        depth = 0
        for i, ch in enumerate(rest):
            depth += ch == "("
            depth -= ch == ")"
            if depth == 0:
                return rest[i + 1:]
        return ""
    depth = 0
    for i, ch in enumerate(rest):
        if ch in "[{(":
            depth += 1
        elif ch in "]})":
            depth -= 1
        elif ch == " " and depth == 0:
            return rest[i:]
    return ""


def classify_hlo(text: str) -> dict:
    """{instruction name: 'kernel' | 'collective' | 'container' | 'other'}."""
    out = {}
    for line in text.splitlines():
        m = _INSTR.match(line)
        if not m:
            continue
        name, rest = m.groups()
        op = _OPCODE.match(_skip_type(rest))
        if not op:
            continue
        opcode = op.group(1)
        target = _TARGET.search(rest)
        calls = _CALLS.search(rest)
        if opcode == "custom-call" and target and \
                target.group(1) == "tpu_custom_call":
            out[name] = "kernel"
        elif opcode in COLLECTIVE_OPS or (
                opcode.startswith("async-") and calls
                and any(w in calls.group(1) for w in COLLECTIVE_WORDS)):
            out[name] = "collective"
        elif opcode in CONTAINER_OPS:
            out[name] = "container"
        else:
            out[name] = "other"
    return out


@dataclasses.dataclass
class Device:
    name: str
    ops: list          # [(start_ns, end_ns, hlo name, class)] in the window
    transfers: list    # the same for asynchronous collectives in flight


@dataclasses.dataclass
class Trace:
    window: tuple      # (start_ns, end_ns) of the host span "window"
    devices: list      # [Device]
    host: list         # [(start_ns, end_ns, span name)]

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) * 1e-9


def op_name(ev) -> str:
    """The HLO instruction an event is of: its ``hlo_op`` stat, or the
    name before `` = `` in the instruction text the event is named by."""
    stats = dict(ev.stats)
    if "hlo_op" in stats:
        return stats["hlo_op"]
    return ev.name.split(" = ", 1)[0].lstrip("%")


def xplane_file(log_dir) -> pathlib.Path:
    found = sorted(pathlib.Path(log_dir).glob("plugins/profile/*/*.xplane.pb"))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return found[-1]


def reduce(xplane_path, classes: dict) -> Trace:
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(str(xplane_path))
    host, devices, window = [], [], None
    for plane in pd.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name == "window":
                        window = (ev.start_ns, ev.end_ns)
                    elif ev.name in HOST_SPANS:
                        host.append((ev.start_ns, ev.end_ns, ev.name))
    if window is None:
        raise ValueError("the trace has no host span named 'window'")
    w0, w1 = window
    for plane in pd.planes:
        if not re.fullmatch(r"/device:TPU:\d+", plane.name):
            continue
        ops, transfers = [], []
        for line in plane.lines:
            if line.name not in ("XLA Ops", "Async XLA Ops"):
                continue
            sync = line.name == "XLA Ops"
            for ev in line.events:
                name = op_name(ev)
                cls = classes.get(name, "other")
                s, e = max(ev.start_ns, w0), min(ev.end_ns, w1)
                if e <= s or cls == "container":
                    continue
                if sync:
                    ops.append((s, e, name, cls))
                elif cls == "collective":
                    transfers.append((s, e, name, cls))
        devices.append(Device(plane.name, sorted(ops), sorted(transfers)))
    devices.sort(key=lambda d: int(d.name.rsplit(":", 1)[1]))
    host.sort()
    return Trace(window, devices, host)


def union(intervals) -> list:
    """Merged, sorted [(start, end)]."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], e))
        else:
            out.append((s, e))
    return out


def length(intervals) -> float:
    return float(sum(e - s for s, e in intervals))


def subtract(intervals, cover) -> float:
    """Length of ``intervals`` (merged) not covered by ``cover`` (merged)."""
    total, j = 0.0, 0
    for s, e in intervals:
        cur = s
        while j < len(cover) and cover[j][1] <= cur:
            j += 1
        k = j
        while k < len(cover) and cover[k][0] < e:
            if cover[k][0] > cur:
                total += cover[k][0] - cur
            cur = max(cur, cover[k][1])
            k += 1
        if cur < e:
            total += e - cur
    return total


def busy_ns(dev: Device) -> float:
    """Time in which an operation runs (a transfer in flight is not one)."""
    return length(union((s, e) for s, e, _, _ in dev.ops))


def class_ns(dev: Device, cls: str) -> float:
    """Summed durations of one class's operations and transfers."""
    return float(sum(e - s for s, e, _, c in dev.ops + dev.transfers
                     if c == cls))


def exposed_ns(dev: Device, cls: str) -> float:
    """Time in which an operation or transfer of ``cls`` is under way and
    no operation of another class runs."""
    mine = union((s, e) for s, e, _, c in dev.ops + dev.transfers if c == cls)
    rest = union((s, e) for s, e, _, c in dev.ops if c != cls)
    return subtract(mine, rest)


def top_ops(trace: Trace, n: int = 10) -> list:
    """[[hlo name, seconds]] summed over devices, largest first."""
    tot = {}
    for dev in trace.devices:
        for s, e, name, _ in dev.ops:
            tot[name] = tot.get(name, 0.0) + (e - s) * 1e-9
    return [[k, v] for k, v in sorted(tot.items(), key=lambda kv: -kv[1])[:n]]


def idle_gaps(trace: Trace, n: int = 10) -> list:
    """The longest idle gaps of the first device, each named by the host
    span that covers most of it ('none' where the host was in no span)."""
    dev = trace.devices[0]
    busy = union((s, e) for s, e, _, _ in dev.ops)
    w0, w1 = trace.window
    gaps, cur = [], w0
    for s, e in busy:
        if s > cur:
            gaps.append((cur, s))
        cur = max(cur, e)
    if cur < w1:
        gaps.append((cur, w1))
    out = []
    for gs, ge in sorted(gaps, key=lambda g: g[0] - g[1])[:n]:
        best, name = 0.0, "none"
        for hs, he, hname in trace.host:
            ov = min(ge, he) - max(gs, hs)
            if ov > best:
                best, name = ov, hname
        out.append([name, (ge - gs) * 1e-9])
    return out
