#!/usr/bin/env python3
"""Readings for the limits of ``correct``: many seeds of one cell in one
process.

    python3 bench/calibrate.py --workload <cell> --seeds 11,12,13 \\
        --seconds <s> [--control | --fault <name>]

Each seed is one whole run of ``harness.run``, the path ``bench/run.py``
takes after its look for a chip: sound (the program as committed), the
control (the reference at the configuration's control precision in the
place of the program's local training) or a planted fault
(``fedbench.probe.FAULTS``).  One JSON line per run on standard output
holds the seed, the mode, ``correct``, set-up and window seconds, and every
number compared with its limit.  The lower reading of a limit is the
largest that sound runs give, the upper the smallest that the control or a
fault gives (``PERF.md`` keeps both).  Runs share the process, so only the
first compiles; set-up is still paid per seed.

It needs the chip, as ``bench/run.py`` does.  ``--root`` runs another
benchmark tree (such as ``bench/tests/data``), and ``--cpu`` lets that run
without a chip, for test-size configurations only.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True,
                    help="comma-separated seeds, one run each")
    ap.add_argument("--seconds", type=float, required=True)
    mode = ap.add_mutually_exclusive_group()
    mode.add_argument("--control", action="store_true")
    mode.add_argument("--fault", default=None)
    ap.add_argument("--root", default=None,
                    help="benchmark tree holding BENCHMARK.json, configs/ "
                         "and traffic/ (default: this checkout)")
    ap.add_argument("--cpu", action="store_true",
                    help="allow a run without a chip (test-size trees)")
    args = ap.parse_args(argv)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(ROOT, ".jax_cache")
    sys.path[:0] = [os.path.join(ROOT, "src"), BENCH]
    from fedbench import harness, spec
    from run import chips_missing
    root = Path(args.root).resolve() if args.root else spec.ROOT
    bench_dir = root if args.root else spec.BENCH_DIR
    cell = spec.cell(spec.load_benchmark(root), args.workload)
    err = chips_missing(int(cell["chips"]))
    if err and not args.cpu:
        print(f"calibrate: {err}", file=sys.stderr)
        return 1
    from repro.compile_cache import enable_compile_cache
    import jax
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    name = ("control" if args.control
            else f"fault:{args.fault}" if args.fault else "sound")
    for seed in (int(s) for s in args.seeds.split(",")):
        t0 = time.perf_counter()
        out = harness.run(args.workload, seed, args.seconds, False,
                          t_start=t0, control=args.control,
                          fault=args.fault, root=root, bench_dir=bench_dir)
        print(json.dumps({
            "workload": args.workload, "seed": seed, "mode": name,
            "correct": out["correct"],
            "setup_s": out["metrics"]["setup_s"]["value"],
            "total_s": time.perf_counter() - t0,
            "checks": {k: c["value"] for k, c in out["checks"].items()},
            "limits": {k: c["limit"] for k, c in out["checks"].items()}}),
            flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
