# One function per paper table. Print ``name,us_per_call,derived`` CSV.
"""Benchmark harness: one module per paper table/figure (+ roofline).

  PYTHONPATH=src python -m benchmarks.run              # all
  PYTHONPATH=src python -m benchmarks.run --only codecs
"""

from __future__ import annotations

import argparse
import sys
import time

from benchmarks import (adaptive_bench, aggregation, async_vs_sync, codecs,
                        fl_convergence, fleet_scale, kernels_bench, roofline,
                        simcore, topology_bench, transport_comparison,
                        transport_scenarios, vmap_train, wire_bench)
from repro.compile_cache import enable_compile_cache

SUITES = {
    "simcore": simcore,
    "transport_scenarios": transport_scenarios,
    "transport_comparison": transport_comparison,
    "fleet_scale": fleet_scale,
    "topology": topology_bench,
    "async_vs_sync": async_vs_sync,
    "adaptive": adaptive_bench,
    "fl_convergence": fl_convergence,
    "codecs": codecs,
    "wire": wire_bench,
    "aggregation": aggregation,
    "kernels": kernels_bench,
    "roofline": roofline,
    "vmap_train": vmap_train,
}


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--only", default=None, choices=list(SUITES))
    args = ap.parse_args()
    enable_compile_cache()
    suites = {args.only: SUITES[args.only]} if args.only else SUITES
    print("name,us_per_call,derived")
    for name, mod in suites.items():
        t0 = time.perf_counter()
        try:
            for row, us, derived in mod.bench():
                print(f"{row},{us:.1f},{derived}", flush=True)
        except Exception as e:  # noqa: BLE001 - a suite failure is a row
            print(f"{name}/SUITE_ERROR,0.0,{type(e).__name__}:{e}")
        print(f"{name}/suite_wall,"
              f"{(time.perf_counter()-t0)*1e6:.0f},complete", flush=True)


if __name__ == "__main__":
    main()
