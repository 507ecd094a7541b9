"""From a profiler trace to device busy time, device time per span, and
idle gaps labelled by what the host was doing.

The trace is JAX's ``.xplane.pb``, read with ``jax.profiler.ProfileData``.
Device operations are the events of the ``XLA Ops`` line of each
``/device:TPU:<n>`` plane (of all its lines, where a profiler names none
so; busy time is their union either way).  Host spans are the ``TraceAnnotation`` events
the probe opens (``bench/fedbench/probe.py``), found by name on the
``/host:CPU`` plane.  The profiler puts both on one clock.

All intervals are ``(start_ns, end_ns)`` pairs.
"""

from __future__ import annotations

import glob
import os
import re
from collections import defaultdict
from dataclasses import dataclass, field

WINDOW = "bench_window"
OPS_LINE = "XLA Ops"
DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")


def union(intervals) -> list[tuple[float, float]]:
    out: list[list[float]] = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def total(intervals) -> float:
    return sum(e - s for s, e in union(intervals))


def overlap(a, b) -> float:
    """Length of (union of a) intersected with (union of b)."""
    a, b = union(a), union(b)
    i = j = 0
    got = 0.0
    while i < len(a) and j < len(b):
        s, e = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if e > s:
            got += e - s
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return got


def clip(intervals, lo: float, hi: float) -> list[tuple[float, float]]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if min(e, hi) > max(s, lo)]


@dataclass
class Trace:
    ops: dict[int, list[tuple[str, float, float]]] = field(
        default_factory=dict)            # device id -> (name, start, end)
    spans: dict[str, list[tuple[float, float]]] = field(
        default_factory=lambda: defaultdict(list))

    @property
    def window(self) -> tuple[float, float]:
        w = self.spans.get(WINDOW)
        if not w:
            raise ValueError(f"trace holds no {WINDOW!r} span")
        return w[0][0], w[-1][1]


def op_name(name: str) -> str:
    """An operation's HLO name: a TPU trace names each op by its whole HLO
    instruction (``%fusion.146 = f32[...] fusion(...)``)."""
    return name.split(" = ", 1)[0].lstrip("%")


def load(path: str, span_names) -> Trace:
    """Read the newest ``.xplane.pb`` under ``path`` (a file or a profiler
    log directory)."""
    from jax.profiler import ProfileData
    if os.path.isdir(path):
        files = sorted(glob.glob(os.path.join(path, "**", "*.xplane.pb"),
                                 recursive=True), key=os.path.getmtime)
        if not files:
            raise FileNotFoundError(f"no .xplane.pb under {path}")
        path = files[-1]
    names = set(span_names) | {WINDOW}
    out = Trace()
    for plane in ProfileData.from_file(path).planes:
        chip = DEVICE_PLANE.match(plane.name)
        if chip:
            lines = list(plane.lines)
            ops = [line for line in lines if line.name == OPS_LINE]
            out.ops[int(chip.group(1))] = [
                (op_name(ev.name), ev.start_ns, ev.start_ns + ev.duration_ns)
                for line in (ops or lines) for ev in line.events]
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                for ev in line.events:
                    if ev.name in names:
                        out.spans[ev.name].append(
                            (ev.start_ns, ev.start_ns + ev.duration_ns))
    for v in out.spans.values():
        v.sort()
    return out


def busy_s(trace: Trace) -> float:
    """Seconds in which an operation ran, averaged over the devices."""
    lo, hi = trace.window
    if not trace.ops:
        return 0.0
    per = [total(clip([(s, e) for _, s, e in ops], lo, hi))
           for ops in trace.ops.values()]
    return sum(per) / len(per) / 1e9


def window_s(trace: Trace) -> float:
    lo, hi = trace.window
    return (hi - lo) / 1e9


def device_s_in(trace: Trace, span: str) -> float:
    """Seconds of device operations inside the named host spans, summed
    over the devices."""
    spans = trace.spans.get(span, [])
    return sum(overlap([(s, e) for _, s, e in ops], spans)
               for ops in trace.ops.values()) / 1e9


def top_ops(trace: Trace, n: int = 10) -> list[list]:
    lo, hi = trace.window
    acc: dict[str, float] = defaultdict(float)
    for ops in trace.ops.values():
        for name, s, e in clip_named(ops, lo, hi):
            acc[name] += (e - s) / 1e9
    return [[k, v] for k, v in sorted(acc.items(), key=lambda kv: -kv[1])[:n]]


def clip_named(ops, lo, hi):
    return [(name, max(s, lo), min(e, hi)) for name, s, e in ops
            if min(e, hi) > max(s, lo)]


def idle_gaps(trace: Trace, order, n: int = 10) -> list[list]:
    """Idle seconds of device 0 within the window, grouped by the host span
    open at each idle instant; where several are open, the first of
    ``order`` (innermost first) names it, and ``event_engine`` where none
    is (the round outside every layer span)."""
    lo, hi = trace.window
    dev = min(trace.ops) if trace.ops else None
    busy = union(clip([(s, e) for _, s, e in trace.ops.get(dev, [])],
                      lo, hi))
    idle, t = [], lo
    for s, e in busy:
        if s > t:
            idle.append((t, s))
        t = max(t, e)
    if hi > t:
        idle.append((t, hi))
    acc: dict[str, float] = defaultdict(float)
    claimed: list[tuple[float, float]] = []
    for name in order:
        spans = union(trace.spans.get(name, []))
        free = _minus(spans, claimed)
        acc[name] += overlap(idle, free) / 1e9
        claimed = union(claimed + spans)
    acc["event_engine"] += (total(idle) - overlap(idle, claimed)) / 1e9
    rows = [[k, v] for k, v in acc.items() if v > 0]
    return sorted(rows, key=lambda kv: -kv[1])[:n]


def _minus(a, b) -> list[tuple[float, float]]:
    """Union of a with the union of b taken out."""
    out = []
    b = union(b)
    for s, e in union(a):
        cur = s
        for bs, be in b:
            if be <= cur or bs >= e:
                continue
            if bs > cur:
                out.append((cur, bs))
            cur = max(cur, be)
        if cur < e:
            out.append((cur, e))
    return out
