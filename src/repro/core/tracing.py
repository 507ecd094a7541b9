"""Spans and counters the program records about its own work.

One process-wide tracer, like the simulator's counters: a deep layer (a
free function such as ``packetize``, a wire stage) records without an
object passed down to it.

* :class:`span` — a context manager and a decorator.  It reads
  :data:`clock` (``time.perf_counter_ns``) on entry and exit and adds to
  the name's cumulative ``count``, ``total_ns`` and ``self_ns``, where
  self time is the duration less what the spans opened inside it cover.
  While a profiler session is on it also opens
  ``jax.profiler.TraceAnnotation(name)``, so the span lands on the
  ``/host:CPU`` plane on the same clock as the device's ``XLA Ops``.
* :func:`count` — cumulative integer counters.
* XLA compiles — a ``jax.monitoring`` listener keeps every backend
  compile (a load from the persistent cache included) as the counters
  ``jax.compiles`` and ``jax.compile_ns``.
* :func:`snapshot` / :func:`delta` — a window's share of the totals.
  Totals are never reset, so nested windows (a hierarchical cell's core
  inside the root's round) each see their own interval.  A span is
  counted in the window in which it closes.

Nothing switches it off: the accounting is two clock reads and a few
integer additions per call, and with no profiler session active no
annotation is made.  Span sites are per transfer, per burst or per batch,
never per packet.  The names the program records, and the per-layer
metrics that read them, are listed in ``docs/TRACING.md``.

The tracer is single-threaded, as the simulator is: spans opened on
another thread would nest into the main thread's.
"""

from __future__ import annotations

import functools
import time
from collections import defaultdict
from typing import Callable

import jax
from jax.profiler import TraceAnnotation

#: The clock every span reads (ns): the host's ``perf_counter_ns``, the
#: clock a benchmark times its windows by.  Tests put a fake one here.
clock: Callable[[], int] = time.perf_counter_ns

#: The ``jax.monitoring`` event of one XLA backend compile.
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"

_profiling: Callable[[], bool] = TraceAnnotation.is_enabled
_spans: dict[str, list[int]] = {}        # name -> [count, total_ns, self_ns]
_counters: defaultdict[str, int] = defaultdict(int)
_open: list[list] = []                   # [totals, annotation, t0, child_ns]


class span:
    """A named span: ``with span("x"):`` or ``@span("x")``.

    The object holds no per-call state (open spans live on one stack), so
    one instance may be kept at module level and entered from anywhere,
    recursively included.
    """

    __slots__ = ("name", "_totals")

    def __init__(self, name: str):
        self.name = name
        self._totals = _spans.setdefault(name, [0, 0, 0])

    def __enter__(self) -> "span":
        # A profiler records an annotation only if it was on when the
        # annotation opened, so with none on, none is made.
        ann = TraceAnnotation(self.name) if _profiling() else None
        if ann is not None:
            ann.__enter__()
        _open.append([self._totals, ann, clock(), 0])
        return self

    def __exit__(self, *exc) -> None:
        t1 = clock()
        totals, ann, t0, child_ns = _open.pop()
        if ann is not None:
            ann.__exit__(None, None, None)
        d = t1 - t0
        totals[0] += 1
        totals[1] += d
        totals[2] += d - child_ns
        if _open:
            _open[-1][3] += d

    def __call__(self, fn: Callable) -> Callable:
        @functools.wraps(fn)
        def traced(*args, **kwargs):
            with self:
                return fn(*args, **kwargs)
        return traced


def count(name: str, n: int = 1) -> None:
    """Add ``n`` to the counter ``name``."""
    _counters[name] += n


def spans() -> dict[str, tuple[int, int, int]]:
    """Cumulative ``{name: (count, total_ns, self_ns)}`` of closed spans."""
    return {k: (v[0], v[1], v[2]) for k, v in _spans.items() if v[0]}


def counters() -> dict[str, int]:
    """Cumulative ``{name: value}`` of every counter."""
    return dict(_counters)


def snapshot() -> tuple[dict, dict]:
    """The totals now, for :func:`delta`."""
    return spans(), counters()


def delta(snap: tuple[dict, dict]) -> tuple[dict, dict]:
    """What was recorded since ``snap``: ``({name: (count, total_ns,
    self_ns)}, {name: value})``, holding only the names that moved."""
    s0, c0 = snap
    out_spans = {}
    for name, (n, total, self_ns) in spans().items():
        n0, total0, self0 = s0.get(name, (0, 0, 0))
        if n != n0:
            out_spans[name] = (n - n0, total - total0, self_ns - self0)
    out_counters = {name: v - c0.get(name, 0)
                    for name, v in _counters.items()
                    if v != c0.get(name, 0)}
    return out_spans, out_counters


def _on_duration(event: str, duration_s: float, **_kw) -> None:
    if event == COMPILE_EVENT:
        _counters["jax.compiles"] += 1
        _counters["jax.compile_ns"] += int(duration_s * 1e9)


jax.monitoring.register_event_duration_secs_listener(_on_duration)
