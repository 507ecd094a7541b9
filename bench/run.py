#!/usr/bin/env python3
"""Benchmark of the federated round on a TPU: one run of one cell.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Run from the root of a checkout.  The cell (an entry of ``BENCHMARK.json``'s
``workloads``) names a deployment (``bench/configs/<config>.json``) and a
traffic mix (``bench/traffic/<traffic>.json``).  The run

1. puts the checkout's ``src/`` and ``bench/`` on its path;
2. names the platform, device kind and count, and fails without a TPU (or
   with fewer chips than the cell asks for) -- it never falls back to the
   CPU;
3. keeps JAX's persistent compilation cache in ``<checkout>/.jax_cache``
   and caches every compile, however short (``repro.compile_cache``);
4. builds the cell's fleet through the program's own entry
   (``build_fleet_training(...).system.run_round()``), warms up, and counts
   that as ``setup_s``;
5. calls ``run_round`` back to back for ``--seconds`` (one call is one
   aggregation), and reports ``client_updates_per_s`` with ``--trace 0``,
   or, with ``--trace 1``, the per-layer metrics read from the spans,
   counters and a profiler trace of the same window;
6. frees the fleet and compares what the window produced with a plain
   reference built from the seed (``bench/reference/``): each number beside
   its limit, as the last lines on standard error and under ``checks`` in
   the result.

The last line of standard output is one JSON object with ``correct``,
``attempted``, ``failed``, ``metrics``, ``device`` (and ``breakdown`` with
``--trace 1``) and ``checks``.

``--control`` puts the reference, computed at the configuration's control
precision, in place of the program's local training; ``--fault`` plants one
of the faults of ``fedbench.probe.FAULTS`` under the timed path.  Both are
for calibrating the check, and both must come out ``correct: false``.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)


def parse_args(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", action="store_true")
    ap.add_argument("--fault", default=None)
    ap.add_argument("--keep-trace", default=None,
                    help="copy the profiler trace of a --trace 1 run here")
    return ap.parse_args(argv)


def chips_missing(need: int):
    """Why this machine cannot measure the cell, or None when it can."""
    import jax
    try:
        devices = jax.devices()
    except RuntimeError as e:
        return f"JAX found no devices: {e}"
    d0 = devices[0]
    print(f"devices: platform={d0.platform} kind={d0.device_kind} "
          f"count={len(devices)}", file=sys.stderr, flush=True)
    if d0.platform != "tpu":
        return f"no TPU: JAX's backend is {d0.platform!r}"
    if len(devices) < need:
        return f"the cell needs {need} chips, JAX sees {len(devices)}"
    return None


def main(argv=None) -> int:
    args = parse_args(argv)
    # The cache directory must be fixed before JAX is imported: it lives
    # inside this checkout, so two checkouts share nothing.
    os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(ROOT, ".jax_cache")
    sys.path[:0] = [os.path.join(ROOT, "src"), BENCH]
    try:
        from repro.compile_cache import enable_compile_cache
        from fedbench import harness, spec
        cell = spec.cell(spec.load_benchmark(), args.workload)
    except (ImportError, FileNotFoundError, KeyError) as e:
        print(f"bench: cannot start: {e}", file=sys.stderr)
        return 2
    err = chips_missing(int(cell["chips"]))
    if err:
        print(f"bench: {err}", file=sys.stderr)
        return 1
    import jax
    print(f"compile cache: {enable_compile_cache()}", file=sys.stderr)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    out = harness.run(args.workload, args.seed, args.seconds,
                      bool(args.trace), t_start=T_START,
                      control=args.control, fault=args.fault,
                      keep_trace=args.keep_trace)
    harness.print_checks(out["checks"])
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
