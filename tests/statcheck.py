"""Distributional-equivalence harness for simulator engines.

The per-packet and batched engines are pinned bit-for-bit by
``test_engine_equivalence.py``.  The flow engine (``repro.core.flow``)
deliberately is not bit-exact — its contract is *statistical*: over a sweep
of seeds, its per-metric distributions (round time, bytes on wire,
retransmissions, rounds-to-target-loss, ...) must agree with the batched
engine within documented tolerances.  This module is the reusable machinery
for that claim:

* :func:`sweep` — run a ``seed -> {metric: value}`` scenario over N seeds
  and collect per-metric samples;
* :func:`summarize` — mean/variance/confidence interval of one sample set;
* :func:`compare` — the equivalence gate: mean agreement (relative band
  plus a z-score on the standard error, so tight distributions are held
  tight and noisy ones are judged by their own spread), a variance-ratio
  band, and a KS-style max-CDF-distance bound;
* :func:`ks_statistic` — two-sample Kolmogorov-Smirnov distance (exact
  O(n log n) over the pooled sample, no scipy dependency).

What "equivalent" means per metric (the documented tolerances live with
each test via :class:`Tolerance`; the methodology is docs/PERFORMANCE.md,
"What statistically equivalent means"):

* ``mean``: ``|mean_a - mean_b| <= rtol * max(|a|,|b|) + atol`` OR within
  ``z_max`` pooled standard errors — the OR matters because a near-zero
  metric (e.g. retx on a clean link) makes any relative band meaningless,
  and a wide-variance metric (round time under bursty loss) can miss a
  tight relative band while being statistically indistinguishable.
* ``variance``: ``var_a <= var_hi * var_b + atol^2`` and vice versa with
  ``var_lo``.  The flow engine replaces per-packet jitter by its mean, so
  a one-sided lower band (flow allowed less variance, never more) is the
  physically honest default.  Either band may be ``None`` to skip it:
  at very low loss rates the duration variance is dominated by a rare
  timer-wait event (a few percent per seed), and a sample-variance ratio
  over tens of seeds measures the luck of rare-event counts, not a
  difference between the engines — gate those metrics on the mean only.
* ``ks``: max CDF distance <= ``ks_max``.  Used where distribution *shape*
  matters (rounds-to-target is small-integer-valued; a mean test alone
  could hide a bimodal mismatch).

Every check returns a list of human-readable failure strings instead of
asserting, so a test can aggregate all metric failures into one report.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Callable, Optional, Sequence

NS = 1_000_000_000


# --------------------------------------------------------------------------
# Summaries
# --------------------------------------------------------------------------
@dataclasses.dataclass
class Summary:
    n: int
    mean: float
    var: float            # unbiased (n-1) sample variance
    lo: float             # 95% CI on the mean
    hi: float

    @property
    def sd(self) -> float:
        return math.sqrt(self.var)

    @property
    def sem(self) -> float:
        return math.sqrt(self.var / self.n) if self.n else 0.0


def summarize(values: Sequence[float]) -> Summary:
    vals = [float(v) for v in values]
    n = len(vals)
    if n == 0:
        return Summary(0, 0.0, 0.0, 0.0, 0.0)
    mean = sum(vals) / n
    var = (sum((v - mean) ** 2 for v in vals) / (n - 1)) if n > 1 else 0.0
    sem = math.sqrt(var / n) if n else 0.0
    return Summary(n, mean, var, mean - 1.96 * sem, mean + 1.96 * sem)


def ks_statistic(a: Sequence[float], b: Sequence[float]) -> float:
    """Two-sample KS distance: max |F_a(x) - F_b(x)| over the pooled
    sample."""
    xa, xb = sorted(float(v) for v in a), sorted(float(v) for v in b)
    na, nb = len(xa), len(xb)
    if not na or not nb:
        return 1.0
    i = j = 0
    d = 0.0
    while i < na and j < nb:
        x = min(xa[i], xb[j])
        while i < na and xa[i] <= x:
            i += 1
        while j < nb and xb[j] <= x:
            j += 1
        d = max(d, abs(i / na - j / nb))
    return max(d, abs(1.0 - j / nb) if i >= na else abs(i / na - 1.0))


# --------------------------------------------------------------------------
# The equivalence gate
# --------------------------------------------------------------------------
@dataclasses.dataclass
class Tolerance:
    """Per-metric equivalence bands (see module docstring for semantics)."""

    mean_rtol: float = 0.10      # relative band on the means
    mean_atol: float = 0.0       # absolute floor (units of the metric)
    z_max: float = 4.0           # pooled-SEM z-score alternative
    var_hi: float | None = 4.0   # var_a <= var_hi * var_b (+atol^2)
    var_lo: float | None = 16.0  # var_b <= var_lo * var_a (+atol^2) — loose:
    # the flow engine collapses jitter to its mean, so *less* variance than
    # the packet engines is expected and mostly unbounded below.
    ks_max: Optional[float] = None   # optional shape gate


def compare(name: str, a: Sequence[float], b: Sequence[float],
            tol: Tolerance) -> list[str]:
    """Gate sample sets ``a`` (reference engine) vs ``b`` (flow engine).

    Returns human-readable failure strings; empty means equivalent."""
    sa, sb = summarize(a), summarize(b)
    fails: list[str] = []
    diff = abs(sa.mean - sb.mean)
    scale = max(abs(sa.mean), abs(sb.mean))
    pooled_sem = math.sqrt(sa.sem ** 2 + sb.sem ** 2)
    mean_ok = diff <= tol.mean_rtol * scale + tol.mean_atol
    z_ok = pooled_sem > 0 and diff <= tol.z_max * pooled_sem
    if not (mean_ok or z_ok):
        fails.append(
            f"{name}: mean mismatch ref={sa.mean:.6g} flow={sb.mean:.6g} "
            f"(diff {diff:.4g} > rtol {tol.mean_rtol}*{scale:.4g}"
            f"+atol {tol.mean_atol:.4g}; z={diff / pooled_sem:.2f}"
            if pooled_sem > 0 else
            f"{name}: mean mismatch ref={sa.mean:.6g} flow={sb.mean:.6g}")
    a2 = tol.mean_atol ** 2
    if tol.var_hi is not None and sb.var > tol.var_hi * sa.var + a2:
        fails.append(f"{name}: flow variance {sb.var:.4g} exceeds "
                     f"{tol.var_hi}x reference {sa.var:.4g}")
    if tol.var_lo is not None and sa.var > tol.var_lo * sb.var + a2:
        fails.append(f"{name}: flow variance {sb.var:.4g} collapsed below "
                     f"reference/{tol.var_lo} ({sa.var:.4g})")
    if tol.ks_max is not None:
        d = ks_statistic(a, b)
        if d > tol.ks_max:
            fails.append(f"{name}: KS distance {d:.3f} > {tol.ks_max}")
    return fails


# --------------------------------------------------------------------------
# Seed sweeps
# --------------------------------------------------------------------------
def sweep(run: Callable[[int], dict], seeds: Sequence[int]
          ) -> dict[str, list[float]]:
    """Run ``run(seed) -> {metric: value}`` over the seeds; collect
    per-metric sample lists.  ``None`` values (e.g. rounds-to-target never
    reached) are recorded as ``math.inf`` so shape gates still see them."""
    out: dict[str, list[float]] = {}
    for seed in seeds:
        row = run(seed)
        for k, v in row.items():
            out.setdefault(k, []).append(
                math.inf if v is None else float(v))
    return out


def compare_sweeps(ref: dict[str, list[float]], flow: dict[str, list[float]],
                   tols: dict[str, Tolerance]) -> list[str]:
    """Apply per-metric tolerances to two sweep results; unknown metrics in
    either sweep are an error (a silently dropped metric is a silently
    skipped gate)."""
    fails: list[str] = []
    for name, tol in tols.items():
        if name not in ref or name not in flow:
            fails.append(f"{name}: metric missing from sweep "
                         f"(ref: {name in ref}, flow: {name in flow})")
            continue
        a = [v for v in ref[name] if not math.isinf(v)]
        b = [v for v in flow[name] if not math.isinf(v)]
        ninf_a = len(ref[name]) - len(a)
        ninf_b = len(flow[name]) - len(b)
        # Unreached targets must agree in *rate* before means are comparable.
        n = max(len(ref[name]), 1)
        if abs(ninf_a - ninf_b) > max(2, 0.25 * n):
            fails.append(f"{name}: unreached-target rate differs "
                         f"(ref {ninf_a}/{len(ref[name])}, "
                         f"flow {ninf_b}/{len(flow[name])})")
            continue
        if not a and not b:
            continue
        fails.extend(compare(name, a, b, tol))
    return fails


# --------------------------------------------------------------------------
# Scenario builders (shared by the flow-engine tests)
# --------------------------------------------------------------------------
def transfer_metrics(engine: str, kind: str, seed: int, *,
                     loss_p: float = 0.1, bursty: bool = False,
                     payload: int = 60_000, mtu: int = 1200,
                     rate_bps: float = 1e7, delay_ns: int = 5_000_000,
                     jitter_ns: int = 0,
                     timeout_ns: int = 2 * NS) -> dict:
    """One direct transfer over one seeded lossy link; the single-link
    microscope the per-transport distributional tests look through."""
    from repro.core import (GilbertElliott, BernoulliLoss, Link, Simulator,
                            TransportConfig, make_transport, packetize)
    from repro.core.flow import maybe_flow
    sim = Simulator(engine=engine)
    if bursty:
        loss = lambda s: GilbertElliott(  # noqa: E731
            p_good_loss=loss_p / 4, p_bad_loss=min(1.0, loss_p * 10),
            p_bad=0.075, seed=s)
    else:
        loss = lambda s: BernoulliLoss(p=loss_p, seed=s)  # noqa: E731
    mk = lambda s: Link(rate_bps, delay_ns, loss(s),  # noqa: E731
                        jitter_ns=jitter_ns, jitter_seed=s + 77)
    src, dst = "10.1.0.9", "10.0.0.1"
    sim.connect(src, dst, mk(seed), mk(seed + 1))
    tr = maybe_flow(sim, make_transport(kind))
    cfg = TransportConfig(kind=kind, mtu=mtu, timeout_ns=timeout_ns,
                          udp_deadline_ns=4 * NS)
    got = []
    tr.create_receiver(sim, sim.node(dst), cfg, got.append)
    data = bytes(range(256)) * (payload // 256)
    sender = tr.create_sender(sim, sim.node(src), sim.node(dst),
                              packetize(data, src, txn=1, mtu=mtu), cfg)
    sender.start()
    sim.run()
    st = sender.stats
    return {
        "duration_ns": st.duration_ns,
        "sim_end_ns": sim.now_ns,
        "bytes_sent": sim.stats["bytes_sent"],
        "packets_sent": sim.stats["packets_sent"],
        "packets_dropped": sim.stats["packets_dropped"],
        "retransmissions": st.retransmissions,
        "completed": 1.0 if st.completed else 0.0,
        "delivered": float(len(got)),
    }


def fleet_metrics(engine: str, transport: str, seed: int, *,
                  n_clients: int = 24, rounds: int = 3,
                  topology: str = "star", cells: int = 4,
                  participation: float = 0.5, n_params: int = 512,
                  mode: str = "sync",
                  deadline_ns: int = 60 * NS) -> dict:
    """One seeded consensus fleet over ``transport``, sized for sweeps:
    returns the metrics the fleet gates compare."""
    from repro.core import (ConsensusObjective, FLConfig, FleetConfig,
                            TransportConfig, build_fleet)
    fleet = FleetConfig(n_clients=n_clients, seed=seed,
                        participation_fraction=participation,
                        round_deadline_ns=deadline_ns, engine=engine,
                        mode=mode, topology=topology,
                        cells=min(cells, n_clients))
    objective = ConsensusObjective(n_clients, n_params, seed=seed)
    fl_cfg = FLConfig(
        aggregation="fedavg",
        transport=TransportConfig(kind=transport, timeout_ns=2 * NS,
                                  udp_deadline_ns=3 * NS))
    sim, system, _ = build_fleet(fleet, objective.init_params(),
                                 objective.train_fn, fl_cfg)
    loss0 = objective.loss(system.global_params)
    losses: list[float] = []
    durations: list[int] = []
    retx: list[int] = []

    def _on_round(r, params):
        losses.append(objective.loss(params))
        durations.append(r.duration_ns)
        retx.append(r.retransmissions)

    system.on_round_end = _on_round
    system.run_rounds(rounds)
    return {
        "round_time_ns": (sum(durations) / len(durations)) if durations
        else 0.0,
        "bytes_on_wire": sim.stats["bytes_sent"],
        "retransmissions": sum(retx),
        "rounds_to_target": next(
            (i + 1 for i, l in enumerate(losses) if l <= 0.1 * loss0), None),
        "final_loss": losses[-1] if losses else loss0,
    }
