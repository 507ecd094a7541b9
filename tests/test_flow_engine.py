"""The flow engine: analytic per-burst fast path.

Two layers of guarantees:

* **Smokes (tier-1, unmarked)** — the flow engine drives every registered
  transport end to end, is deterministic per seed, is *additive* (the
  packet engines never see a flow adapter; their pinned digests are
  untouched), runs every topology and both scheduler modes, and takes a
  10,000-client hierarchical fleet through a round.
* **Distributional gates (``-m stats``)** — multi-seed sweeps asserting
  the flow engine's metric distributions match the batched engine within
  the documented tolerances of ``tests/statcheck.py``, at the single-link
  level (per transport x loss regime) and the fleet level (per transport
  x topology).  The tolerance numbers here are the contract; the
  methodology behind them is docs/PERFORMANCE.md, "What statistically
  equivalent means".
"""

import os
import sys

import pytest

from repro.core import available_transports, make_transport
from repro.core.flow import FlowTransport, available_flow_models, maybe_flow
from repro.core.simulator import ENGINES, Simulator

sys.path.insert(0, os.path.dirname(__file__))
from statcheck import (NS, Tolerance, compare_sweeps,      # noqa: E402
                       fleet_metrics, sweep, transfer_metrics)

SEEDS_LINK = range(100, 125)      # 25 seeds per single-link scenario
SEEDS_FLEET = range(300, 320)     # 20 seeds per fleet scenario
TRANSPORTS = ("mudp", "udp", "tcp", "mudp+fec")


# --------------------------------------------------------------------------
# Tier-1 smokes
# --------------------------------------------------------------------------
def test_flow_is_a_registered_engine():
    assert "flow" in ENGINES
    assert Simulator(engine="flow").engine == "flow"
    with pytest.raises(ValueError, match="unknown engine"):
        Simulator(engine="warp")


def test_every_transport_has_a_flow_model():
    assert set(available_flow_models()) >= set(available_transports())


@pytest.mark.parametrize("kind", TRANSPORTS)
def test_flow_transfer_completes(kind):
    m = transfer_metrics("flow", kind, seed=42, loss_p=0.1, payload=24_000)
    assert m["completed"] == 1.0
    assert m["delivered"] == 1.0
    assert m["bytes_sent"] > 24_000
    # Plain udp is fire-and-forget (duration_ns stays 0 on both packet
    # engines too); simulated time always advances.
    assert m["sim_end_ns"] > 0


@pytest.mark.parametrize("kind", TRANSPORTS)
def test_flow_transfer_deterministic_per_seed(kind):
    a = transfer_metrics("flow", kind, seed=7, loss_p=0.1)
    b = transfer_metrics("flow", kind, seed=7, loss_p=0.1)
    c = transfer_metrics("flow", kind, seed=8, loss_p=0.1)
    assert a == b
    assert a != c


def test_flow_is_additive_to_packet_engines():
    """maybe_flow must hand the base transport back untouched on the
    packet engines — flow is a third tier, not a change to the first
    two."""
    base = make_transport("mudp")
    for engine in ("per_packet", "batched"):
        assert maybe_flow(Simulator(engine=engine), base) is base
    wrapped = maybe_flow(Simulator(engine="flow"), base)
    assert isinstance(wrapped, FlowTransport)
    assert wrapped is not base


@pytest.mark.parametrize("topology", ["star", "hier", "gossip"])
def test_flow_fleet_round_completes(topology):
    m = fleet_metrics("flow", "mudp", seed=5, n_clients=12, rounds=2,
                      topology=topology, n_params=128)
    assert m["round_time_ns"] > 0
    assert m["bytes_on_wire"] > 0
    assert m["final_loss"] >= 0.0


def test_flow_fleet_async_mode():
    m = fleet_metrics("flow", "mudp", seed=5, n_clients=12, rounds=2,
                      mode="async", n_params=128)
    assert m["round_time_ns"] > 0 and m["bytes_on_wire"] > 0


def test_flow_fleet_deterministic_per_seed():
    a = fleet_metrics("flow", "mudp", seed=9, n_clients=12, rounds=2,
                      n_params=128)
    b = fleet_metrics("flow", "mudp", seed=9, n_clients=12, rounds=2,
                      n_params=128)
    assert a == b


def test_flow_fleet_of_ten_thousand_clients_completes_a_round():
    """Reach, not speed: 10,000 clients behind 32 edge aggregators, 60%
    sampled, finish a round on the flow engine."""
    m = fleet_metrics("flow", "mudp", seed=0, n_clients=10_000, rounds=1,
                      topology="hier", cells=32, participation=0.6,
                      n_params=2048, deadline_ns=10 * NS)
    assert m["round_time_ns"] > 0 and m["bytes_on_wire"] > 0


# --------------------------------------------------------------------------
# Distributional gates (stats lane)
# --------------------------------------------------------------------------
# Single-link tolerances.  Variance bands are skipped (None) at low loss:
# the reference duration variance there is dominated by rare timer waits
# (see statcheck module docstring).  At 10% loss recovery dominates and a
# loose one-sided band holds.
def _link_tols(loss_p: float) -> dict:
    rare = loss_p < 0.05
    return {
        "duration_ns": Tolerance(mean_rtol=0.15,
                                 var_hi=None if rare else 4.0,
                                 var_lo=None if rare else 16.0),
        "bytes_sent": Tolerance(mean_rtol=0.05,
                                var_hi=None if rare else 8.0,
                                var_lo=None),
        "retransmissions": Tolerance(mean_rtol=0.25, mean_atol=1.0,
                                     var_hi=None if rare else 8.0,
                                     var_lo=None),
        "completed": Tolerance(mean_rtol=0.0, mean_atol=0.05),
        "delivered": Tolerance(mean_rtol=0.0, mean_atol=0.05),
    }


@pytest.mark.stats
@pytest.mark.parametrize("kind", TRANSPORTS)
@pytest.mark.parametrize("loss_p,bursty", [(0.02, False), (0.1, False),
                                           (0.1, True)])
def test_link_distributional_equivalence(kind, loss_p, bursty):
    ref = sweep(lambda s: transfer_metrics("batched", kind, s,
                                           loss_p=loss_p, bursty=bursty),
                SEEDS_LINK)
    flow = sweep(lambda s: transfer_metrics("flow", kind, s,
                                            loss_p=loss_p, bursty=bursty),
                 SEEDS_LINK)
    fails = compare_sweeps(ref, flow, _link_tols(loss_p))
    assert not fails, "\n".join(fails)


# Fleet tolerances: jitter collapse and deadline quantization make
# round-time variance one-sided; bytes/retx variance at fleet scale is
# rare-event dominated (incomplete broadcasts), so those gate on means.
FLEET_TOLS = {
    "round_time_ns": Tolerance(mean_rtol=0.20, var_lo=None),
    "bytes_on_wire": Tolerance(mean_rtol=0.05, var_hi=None, var_lo=None),
    "retransmissions": Tolerance(mean_rtol=0.30, mean_atol=2.0,
                                 var_hi=None, var_lo=None),
    "rounds_to_target": Tolerance(mean_rtol=0.25, mean_atol=1.0,
                                  var_hi=None, var_lo=None),
    "final_loss": Tolerance(mean_rtol=0.25, mean_atol=0.05,
                            var_hi=None, var_lo=None),
}


@pytest.mark.stats
@pytest.mark.parametrize("transport", TRANSPORTS)
@pytest.mark.parametrize("topology", ["star", "hier"])
def test_fleet_distributional_equivalence(transport, topology):
    ref = sweep(lambda s: fleet_metrics("batched", transport, s,
                                        topology=topology), SEEDS_FLEET)
    flow = sweep(lambda s: fleet_metrics("flow", transport, s,
                                         topology=topology), SEEDS_FLEET)
    fails = compare_sweeps(ref, flow, FLEET_TOLS)
    assert not fails, "\n".join(fails)


# --------------------------------------------------------------------------
# Telemetry under the flow engine (repro.core.telemetry)
# --------------------------------------------------------------------------
def _telemetry_metrics(engine: str, seed: int) -> dict:
    """Fleet-averaged ClientHealth EWMAs after a short lte-cohort run: the
    flow engine feeds the same telemetry plane through the same TxnStats
    shape, so the per-client estimators must agree distributionally."""
    from repro.core import (ConsensusObjective, FLConfig, FleetConfig,
                            TransportConfig, build_fleet)
    NS = 1_000_000_000
    n_clients = 16
    fleet = FleetConfig(n_clients=n_clients, seed=seed, engine=engine,
                        cohort_mix=(("lte", 1.0),),
                        round_deadline_ns=60 * NS)
    objective = ConsensusObjective(n_clients, 512, seed=seed)
    cfg = FLConfig(transport=TransportConfig(kind="mudp", timeout_ns=2 * NS,
                                             udp_deadline_ns=3 * NS))
    _, system, _ = build_fleet(fleet, objective.init_params(),
                               objective.train_fn, cfg)
    system.run_rounds(3)
    health = system.core.telemetry.snapshot_all().values()
    n = max(1, len(health))
    return {
        "txns": sum(h.txns for h in health) / n,
        "rtt_ns": sum(h.rtt_ns for h in health) / n,
        "loss_rate": sum(h.loss_rate for h in health) / n,
        "goodput_bps": sum(h.goodput_bps for h in health) / n,
    }


# rtt/goodput variance is straggler-dominated (one slow draw owns the
# fleet mean), so those gate on means; the loss-rate EWMA at lte loss
# levels is a rare-event average and needs an absolute floor.
TELEMETRY_TOLS = {
    "txns": Tolerance(mean_rtol=0.0),
    "rtt_ns": Tolerance(mean_rtol=0.20, var_hi=None, var_lo=None),
    "loss_rate": Tolerance(mean_rtol=0.5, mean_atol=0.01,
                           var_hi=None, var_lo=None),
    "goodput_bps": Tolerance(mean_rtol=0.25, var_hi=None, var_lo=None),
}


@pytest.mark.stats
def test_flow_telemetry_distributional_equivalence():
    ref = sweep(lambda s: _telemetry_metrics("batched", s), SEEDS_FLEET)
    flow = sweep(lambda s: _telemetry_metrics("flow", s), SEEDS_FLEET)
    fails = compare_sweeps(ref, flow, TELEMETRY_TOLS)
    assert not fails, "\n".join(fails)
