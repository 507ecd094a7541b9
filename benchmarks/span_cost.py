"""Benchmark — what one program span (``repro.core.tracing``) costs the host.

Times ``--n`` calls of a function decorated with a span (the form every
program site but two takes) against the same function bare, once with no
profiler session and once under ``jax.profiler`` (host tracing only).  The
difference per call is the span's cost: two clock reads, the totals, and
a ``TraceAnnotation`` that the profiler records only while it is on.
Prints one JSON line, the nanoseconds per span in each state, and the
JAX platform it ran on.

  PYTHONPATH=src python benchmarks/span_cost.py
  PYTHONPATH=src python benchmarks/span_cost.py --n 500000 --repeats 7
"""

from __future__ import annotations

import argparse
import json
import statistics
import tempfile
import time

import jax

from repro.core import tracing


def _bare(x):
    return x


_spanned = tracing.span("bench.span_cost")(_bare)


def _ns_per_call(fn, n: int) -> float:
    t0 = time.perf_counter_ns()
    for i in range(n):
        fn(i)
    return (time.perf_counter_ns() - t0) / n


def span_ns(n: int, repeats: int) -> float:
    """Median over ``repeats`` of (spanned − bare) ns per call."""
    return statistics.median(_ns_per_call(_spanned, n) - _ns_per_call(_bare, n)
                             for _ in range(repeats))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--n", type=int, default=200_000,
                    help="calls per timing (default 200000)")
    ap.add_argument("--repeats", type=int, default=5,
                    help="timings per state; the median is kept (default 5)")
    args = ap.parse_args(argv)
    off = span_ns(args.n, args.repeats)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    with tempfile.TemporaryDirectory(prefix="span_cost_") as tdir:
        jax.profiler.start_trace(tdir, profiler_options=opts)
        try:
            on = span_ns(args.n, args.repeats)
        finally:
            jax.profiler.stop_trace()
    d0 = jax.devices()[0]
    print(json.dumps({"span_ns_profiler_off": off, "span_ns_profiler_on": on,
                      "calls": args.n, "repeats": args.repeats,
                      "platform": d0.platform, "kind": d0.device_kind}))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
