"""The fleet's client profiles, stratified: one set for every seed.

The program draws each client's cohort, link and compute figures on their
own from the seed (``repro.core.fleet.sample_profiles``).  One seed's fleet
then holds 47 congested-edge clients and another's 66, and the work of a
window follows the seed.  The benchmark draws the same distribution by
strata instead, so that every seed gets the same set of profiles:

* each cohort takes its share of the clients by largest remainder;
* each range of a cohort's spec is cut into as many equal strata as the
  cohort has clients, and each client takes the middle of one stratum;
* the strata of the different ranges are paired by a fixed permutation
  per range, the same for every seed.

The seed decides which client index gets which profile, and with it the
client's data shard and the loss and jitter streams of its links (keyed by
index and seed as the program keys them).
"""

from __future__ import annotations

import numpy as np

#: The ranged fields of a cohort spec, in the order of their permutations.
RANGES = ("up_rate_bps", "delay_ns", "loss_p", "train_time_ns", "weight",
          "cadence_ns")


def counts(mix, n: int) -> dict[str, int]:
    """Clients per cohort: ``n`` split by the mix's weights, largest
    remainder first (ties to the earlier cohort)."""
    w = np.array([max(0.0, float(x)) for _, x in mix])
    share = n * w / w.sum()
    got = np.floor(share).astype(int)
    for i in sorted(range(len(mix)), key=lambda i: (got[i] - share[i], i)
                    )[:n - int(got.sum())]:
        got[i] += 1
    return {name: int(c) for (name, _), c in zip(mix, got)}


def canonical(specs: dict, mix, n: int) -> list[dict]:
    """The fleet's ``n`` profiles before the seed orders them."""
    out: list[dict] = []
    for ci, (name, k) in enumerate(counts(mix, n).items()):
        spec = specs[name]
        mids = (np.arange(k) + 0.5) / max(k, 1)
        cols = {}
        for fi, field in enumerate(RANGES):
            lo, hi = getattr(spec, field)
            perm = np.random.default_rng([0xC0407, ci, fi]).permutation(k)
            cols[field] = lo + (hi - lo) * mids[perm]
        for j in range(k):
            out.append({"cohort": name, "spec": spec,
                        **{f: float(cols[f][j]) for f in RANGES}})
    return out


def profiles(cfg) -> list:
    """``cfg.n_clients`` profiles of ``cfg``'s cohorts, stratified as the
    module says; a stand-in for ``repro.core.fleet.sample_profiles``."""
    from repro.core.fleet import ClientProfile, _client_addr
    specs = cfg.cohort_specs()
    mix = list(cfg.cohort_mix)
    n = int(cfg.n_clients)
    base = canonical(specs, mix, n)
    order = np.random.default_rng([int(cfg.seed), 0xF1EE7]).permutation(n)
    out = []
    for i in range(n):
        p = base[int(order[i])]
        spec = p["spec"]
        delay = int(p["delay_ns"])
        out.append(ClientProfile(
            addr=_client_addr(i), cohort=p["cohort"],
            up_rate_bps=p["up_rate_bps"],
            down_rate_bps=p["up_rate_bps"] * spec.down_up_ratio,
            delay_ns=delay, jitter_ns=int(spec.jitter_frac * delay),
            loss_p=p["loss_p"], bursty=spec.bursty,
            train_time_ns=int(p["train_time_ns"]), weight=p["weight"],
            seed=int(cfg.seed) * 1_000_003 + i * 4,
            cadence_ns=int(p["cadence_ns"])))
    return out
