"""Benchmark — vectorized client compute: vmap vs the python loop.

Two halves, both written to ``BENCH_vmap.json`` (the jax-train CI lane
runs ``--check`` and uploads the artifact):

* **Compute matrix** — one full local-training batch (the MNIST MLP at
  smoke scale) at 16 / 64 / 256 clients per round, through the ``python``
  per-client loop and the one-call ``vmap`` backend.  The smoke-scale
  model makes per-client dispatch the dominant cost — exactly the regime
  a scale simulator lives in (PeerFL's argument for batching client
  compute).  Gate: ``vmap`` >= ``--min-speedup`` (default 5x) over the
  python loop at 256 clients.
* **Learning curve** — a 16-client non-IID MNIST fleet (dirichlet
  alpha=0.5 shards) trained over ``mudp`` with every link dropping 10% of
  packets, vmap backend.  Gate: test accuracy reaches ``--target-acc``
  (default 0.95) within ``--max-rounds`` (default 20) — the paper's
  protocol claim made on a real learning workload: MUDP's NACK repair
  keeps convergence intact at loss rates that stall plain UDP.

  PYTHONPATH=src python benchmarks/vmap_train.py --check --out BENCH_vmap.json
  PYTHONPATH=src python -m benchmarks.run --only vmap_train
"""

from __future__ import annotations

import argparse
import json
import sys
import time

import numpy as np

from repro.compile_cache import enable_compile_cache
from repro.core import (CohortSpec, FleetConfig, FLConfig, TransportConfig,
                        build_fleet_training)
from repro.core.client_compute import make_model, make_train_backend
from repro.core.packetizer import flatten_to_vector

NS = 1_000_000_000

#: Smoke-scale MLP for the compute matrix: small enough that per-client
#: dispatch overhead dominates (the regime batching exists to fix), big
#: enough (~12.7k params) to exercise the real stack/gather/scan path.
MATRIX_MODEL_ARGS = {"hidden": 16, "batch_size": 16, "local_steps": 1,
                     "shard_size": 128}

#: Full-size training config for the learning-curve gate.
CURVE_MODEL_ARGS = {"hidden": 32, "batch_size": 32, "local_steps": 4,
                    "shard_size": 256, "alpha": 0.5}

#: Every client on a 10%-loss link: the paper's lossy regime, uniform so
#: the curve measures the transport, not cohort luck.
LOSSY10 = CohortSpec(
    name="lossy10",
    up_rate_bps=(20e6, 20e6),
    down_up_ratio=2.0,
    delay_ns=(5_000_000, 20_000_000),
    jitter_frac=0.3,
    loss_p=(0.10, 0.10),
    bursty=False,
    train_time_ns=(200_000_000, 800_000_000),
)


def _time_call(fn, budget_s: float = 1.0) -> tuple[float, int]:
    fn()                                   # warm (jit compile, caches)
    t0 = time.perf_counter()
    reps = 0
    while time.perf_counter() - t0 < budget_s:
        fn()
        reps += 1
    return (time.perf_counter() - t0) / reps, reps


def compute_matrix(client_counts, *, seed: int = 0,
                   budget_s: float = 1.0) -> list[dict]:
    """ms per full-batch local-training call, python loop vs vmap."""
    n_max = max(client_counts)
    model = make_model("mlp", n_max, seed=seed, **MATRIX_MODEL_ARGS)
    vec0 = flatten_to_vector(model.init_params())
    rows = []
    for k in client_counts:
        stack = np.tile(vec0, (k, 1))
        ci = np.arange(k, dtype=np.int32)
        ri = np.zeros(k, np.int32)
        timings = {}
        for name in ("python", "vmap"):
            backend = make_train_backend(name)
            s, reps = _time_call(
                lambda: backend.train(model, stack, ci, ri), budget_s)
            timings[name] = s
            rows.append({"clients": k, "backend": name,
                         "ms_per_call": s * 1e3,
                         "us_per_client": s * 1e6 / k,
                         "reps": reps})
        for row in rows[-2:]:
            row["speedup_vs_python"] = (timings["python"]
                                        / timings[row["backend"]])
    return rows


def learning_curve(*, seed: int = 0, n_clients: int = 16,
                   max_rounds: int = 20, transport: str = "mudp") -> dict:
    """Non-IID MNIST over a uniformly 10%-lossy fleet, vmap backend."""
    fleet = FleetConfig(
        n_clients=n_clients, seed=seed,
        cohorts={"lossy10": LOSSY10}, cohort_mix=(("lossy10", 1.0),),
        model="mlp", train_backend="vmap", model_args=dict(CURVE_MODEL_ARGS))
    fl_cfg = FLConfig(
        aggregation="fedavg",
        transport=TransportConfig(kind=transport, timeout_ns=2 * NS,
                                  udp_deadline_ns=3 * NS))
    build = build_fleet_training(fleet, fl_cfg)
    model, system = build.model, build.system
    curve = []
    t0 = time.perf_counter()
    for r in range(max_rounds):
        res = system.run_round()
        curve.append({"round": r + 1,
                      "accuracy": model.accuracy(system.global_params),
                      "loss": model.loss(system.global_params),
                      "arrived": len(res.arrived),
                      "bytes_sent": res.bytes_sent,
                      "retransmissions": res.retransmissions})
    return {
        "transport": transport,
        "n_clients": n_clients,
        "loss_p": 0.10,
        "alpha": CURVE_MODEL_ARGS["alpha"],
        "data_source": model.data.source,
        "init_accuracy": model.accuracy(model.init_params()),
        "final_accuracy": curve[-1]["accuracy"],
        "curve": curve,
        "batch_sizes": build.trainer.batch_sizes,
        "wall_s": time.perf_counter() - t0,
    }


def rounds_to_accuracy(curve: list[dict], target: float):
    for row in curve:
        if row["accuracy"] >= target:
            return row["round"]
    return None


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--clients", type=int, nargs="+", default=[16, 64, 256])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--budget-s", type=float, default=1.0,
                    help="timing budget per matrix cell")
    ap.add_argument("--max-rounds", type=int, default=20)
    ap.add_argument("--target-acc", type=float, default=0.95)
    ap.add_argument("--min-speedup", type=float, default=5.0)
    ap.add_argument("--skip-curve", action="store_true")
    ap.add_argument("--check", action="store_true",
                    help="fail unless both gates pass")
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)
    enable_compile_cache()

    matrix = compute_matrix(args.clients, seed=args.seed,
                            budget_s=args.budget_s)
    for row in matrix:
        print(f"clients={row['clients']:>4} {row['backend']:<7} "
              f"{row['ms_per_call']:8.2f} ms/call  "
              f"{row['us_per_client']:7.1f} us/client  "
              f"speedup={row['speedup_vs_python']:.2f}x")

    k_gate = max(args.clients)
    speedup = next(r["speedup_vs_python"] for r in matrix
                   if r["clients"] == k_gate and r["backend"] == "vmap")
    speedup_ok = speedup >= args.min_speedup
    print(f"speedup gate @ {k_gate} clients: {speedup:.2f}x "
          f"(>= {args.min_speedup}x) -> {'PASS' if speedup_ok else 'FAIL'}")

    report = {
        "model_args": MATRIX_MODEL_ARGS,
        "matrix": matrix,
        "gates": {"min_speedup": args.min_speedup,
                  "speedup_clients": k_gate,
                  "speedup": speedup,
                  "speedup_pass": speedup_ok},
    }

    curve_ok = True
    if not args.skip_curve:
        curve = learning_curve(seed=args.seed, max_rounds=args.max_rounds)
        hit = rounds_to_accuracy(curve["curve"], args.target_acc)
        curve_ok = hit is not None
        print(f"learning curve ({curve['transport']}, 10% loss, non-IID "
              f"alpha={curve['alpha']}, {curve['data_source']} data): "
              f"final acc {curve['final_accuracy']:.4f}; target "
              f"{args.target_acc} reached "
              f"{'at round ' + str(hit) if hit else 'NEVER'} "
              f"-> {'PASS' if curve_ok else 'FAIL'}")
        report["learning_curve"] = curve
        report["gates"].update({"target_acc": args.target_acc,
                                "rounds_to_target": hit,
                                "curve_pass": curve_ok})

    if args.out:
        with open(args.out, "w") as f:
            json.dump(report, f, indent=1)
        print(f"wrote {args.out}")

    if args.check and not (speedup_ok and curve_ok):
        print("GATE FAILURE", file=sys.stderr)
        return 1
    return 0


def bench():
    """benchmarks.run suite hook: the small end of the matrix."""
    for row in compute_matrix([16, 64], budget_s=0.3):
        yield (f"vmap_train/{row['backend']}_{row['clients']}c",
               row["ms_per_call"] * 1e3,
               f"speedup={row['speedup_vs_python']:.2f}x")


if __name__ == "__main__":
    sys.exit(main())
