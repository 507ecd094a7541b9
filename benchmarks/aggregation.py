"""Benchmark 5 — aggregation strategies (paper Eq. 1 vs weighted FedAvg).

Quantifies the order-dependence of the paper's sequential pairwise average
(later arrivals dominate: the k-th last client carries weight 2^-k) and
times the fused fedavg kernel against its jnp oracle.
"""

from __future__ import annotations

import itertools
import time

import numpy as np

from repro.core.aggregation import fedavg, pairwise_average
from repro.kernels.fedavg.fedavg import fedavg_pallas
from repro.kernels.fedavg.ref import fedavg_flat as ref_flat

import jax.numpy as jnp


def bench():
    rows = []
    rng = np.random.default_rng(0)
    clients = [{"w": rng.standard_normal(1000).astype(np.float32)}
               for _ in range(4)]
    g0 = {"w": np.zeros(1000, np.float32)}

    # order dependence of Eq. (1)
    outs = []
    for perm in itertools.permutations(range(4)):
        g = g0
        for i in perm:
            g = pairwise_average(g, clients[i])
        outs.append(g["w"])
    spread = float(max(np.linalg.norm(a - b)
                       for a in outs for b in outs))
    fa = fedavg(clients)["w"]
    worst_vs_fedavg = float(max(np.linalg.norm(o - fa) for o in outs))
    rows.append(("aggregation/pairwise_order_dependence", 0.0,
                 f"perm_spread_l2={spread:.3f}"
                 f";max_dev_from_fedavg={worst_vs_fedavg:.3f}"))

    # kernel vs oracle timing (N = 4M params, K = 8 clients)
    K, N = 8, 4_000_000
    stack = jnp.asarray(rng.standard_normal((K, N)), jnp.float32)
    w = jnp.asarray(rng.uniform(0.5, 1.5, K), jnp.float32)
    for name, fn in (("kernel",
                      lambda: fedavg_pallas(stack, w)),
                     ("jnp_ref", lambda: ref_flat(stack, w))):
        fn().block_until_ready()
        t0 = time.perf_counter()
        reps = 3
        for _ in range(reps):
            fn().block_until_ready()
        us = (time.perf_counter() - t0) * 1e6 / reps
        rows.append((f"aggregation/fedavg_{name}", us,
                     f"K={K};N={N}"))
    return rows


def main():
    for name, us, derived in bench():
        print(f"{name},{us:.1f},{derived}")


if __name__ == "__main__":
    main()
