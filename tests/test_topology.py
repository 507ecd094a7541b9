"""Topology engine: registry, star bit-identity, hier and gossip semantics.

The load-bearing test is ``test_star_bit_identical_to_historical_wiring``:
it hand-rolls the exact pre-topology ``build_fleet`` body and asserts the
topology-engine path produces byte-identical global params and an
identical simulator stats digest — the same guarantee the 24 pinned
orchestrator-equivalence digests give at the scheduler level.
"""

import dataclasses

import numpy as np
import pytest

from repro.core.fleet import (ConsensusObjective, FleetConfig, build_fleet,
                              links_for, sample_profiles)
from repro.core.rounds import (FederatedSystem, FLClient, FLConfig,
                               TransportConfig)
from repro.core.simulator import Simulator
from repro.core.topology import (GossipTopology, HierSystem, StarTopology,
                                 Topology, available_topologies,
                                 edge_client_addr, edge_server_addr,
                                 make_topology, neighbor_graph,
                                 register_topology, topology_hops)
from repro.core.wire import WireError, parse_hop_specs

NS = 1_000_000_000


# Fleet construction and the params hash come from the shared
# ``consensus_fleet`` / ``params_digest`` fixtures in conftest.py.

# --------------------------------------------------------------------------
# Registry
# --------------------------------------------------------------------------
def test_registry_lists_builtins():
    assert available_topologies() == ["gossip", "hier", "star"]
    assert isinstance(make_topology("star"), StarTopology)


def test_registry_unknown_name():
    with pytest.raises(ValueError, match="unknown topology"):
        make_topology("mesh")


def test_registry_refuses_silent_shadowing():
    with pytest.raises(ValueError, match="already registered"):
        register_topology("star", StarTopology)


def test_topology_hops():
    assert topology_hops("star") == ("client->server", "server->client")
    assert "edge->root" in topology_hops("hier")
    assert topology_hops("gossip") == ("peer->peer",)


# --------------------------------------------------------------------------
# Per-hop wire spec parsing
# --------------------------------------------------------------------------
def test_parse_hop_specs():
    out = parse_hop_specs(
        "client->edge: topk(0.01)|int8(1024); edge->root: delta",
        known_hops=topology_hops("hier"))
    assert out == {"client->edge": "topk(0.01)|int8(1024)",
                   "edge->root": "delta"}


@pytest.mark.parametrize("spec", [
    "",                                     # empty
    "client->edge",                         # no pipeline
    "client->edge: raw; client->edge: hex",  # duplicate hop
    "client->edge: not_a_stage",            # bad pipeline
    "nope->where: raw",                     # unknown hop
])
def test_parse_hop_specs_rejects(spec):
    with pytest.raises(WireError):
        parse_hop_specs(spec, known_hops=topology_hops("hier"))


# --------------------------------------------------------------------------
# star: bit-identical to the historical wiring
# --------------------------------------------------------------------------
def test_star_bit_identical_to_historical_wiring(params_digest):
    n, rounds = 12, 3
    obj = ConsensusObjective(n, 48, seed=3)
    fleet = FleetConfig(n_clients=n, seed=7)
    base_cfg = FLConfig(transport=TransportConfig(kind="mudp"))

    # The exact pre-topology-engine build_fleet body.
    profiles = sample_profiles(fleet)
    fl_cfg = dataclasses.replace(
        base_cfg,
        participation_fraction=fleet.participation_fraction,
        min_participants=fleet.min_participants,
        participation_seed=fleet.seed,
        round_deadline_ns=fleet.round_deadline_ns,
        mode=fleet.mode,
        buffer_k=fleet.buffer_k)
    sim_old = Simulator(engine=fleet.engine)
    clients = []
    for i, p in enumerate(profiles):
        up, down = links_for(p)
        sim_old.connect(p.addr, fleet.server_addr, up, down)
        clients.append(FLClient(p.addr, obj.train_fn(i, p),
                                train_time_ns=p.train_time_ns,
                                weight=p.weight, cadence_ns=p.cadence_ns))
    old = FederatedSystem(sim_old, fleet.server_addr, clients,
                          obj.init_params(), fl_cfg)
    old_results = old.run_rounds(rounds)

    sim_new, new, _ = build_fleet(fleet, obj.init_params(),
                                  lambda i, p: obj.train_fn(i, p), base_cfg)
    new_results = new.run_rounds(rounds)

    assert params_digest(new.global_params) == \
        params_digest(old.global_params)
    assert sim_new.stats_digest() == sim_old.stats_digest()
    for a, b in zip(old_results, new_results):
        assert (a.arrived, a.failed, a.bytes_sent, a.duration_ns) == \
            (b.arrived, b.failed, b.bytes_sent, b.duration_ns)


def test_star_hop_counters_cover_all_traffic(consensus_fleet):
    _, sim, _, _ = consensus_fleet("star")
    assert set(sim.hop_bytes) == {"client->server", "server->client"}
    assert sum(sim.hop_bytes.values()) == sim.stats["bytes_sent"]
    assert sum(sim.hop_packets.values()) == sim.stats["packets_sent"]


# --------------------------------------------------------------------------
# hier: edge aggregation
# --------------------------------------------------------------------------
def test_hier_matches_star_final_model(consensus_fleet):
    obj_s, _, star, _ = consensus_fleet("star", n=16)
    obj_h, _, hier, _ = consensus_fleet("hier", n=16, cells=4)
    np.testing.assert_allclose(hier.global_params["w"],
                               star.global_params["w"],
                               rtol=1e-5, atol=1e-6)
    assert abs(obj_h.loss(hier.global_params)
               - obj_s.loss(star.global_params)) < 1e-6


def test_hier_root_link_smaller_than_star(consensus_fleet):
    _, sim_s, _, _ = consensus_fleet("star", n=16)
    _, sim_h, _, _ = consensus_fleet("hier", n=16, cells=4)
    assert sim_h.hop_bytes["edge->root"] < sim_s.hop_bytes["client->server"]
    assert set(sim_h.hop_bytes) == {"client->edge", "edge->client",
                                    "edge->root", "root->edge"}
    assert sum(sim_h.hop_bytes.values()) == sim_h.stats["bytes_sent"]


def _losses_over_rounds(consensus_fleet, topology, *, rounds=3, **fleet_kw):
    """64 clients, a 1,024-parameter objective and ``rounds`` rounds over
    mudp: (sim, initial loss, the loss after each round)."""
    cfg = FLConfig(transport=TransportConfig(
        kind="mudp", timeout_ns=2 * NS, udp_deadline_ns=3 * NS))
    obj, sim, system, _ = consensus_fleet(
        topology, n=64, rounds=0, seed=0, obj_params=1024, obj_seed=0,
        fl_cfg=cfg, **fleet_kw)
    loss0 = obj.loss(system.global_params)
    losses = []
    system.on_round_end = lambda r, params: losses.append(obj.loss(params))
    system.run_rounds(rounds)
    return sim, loss0, losses


def test_hier_root_link_linear_in_cells(consensus_fleet):
    """The root link carries one merged update per cell: its bytes per
    aggregator stay about constant from 2 to 8 cells, near the star's
    server link scaled by cells / clients, at the star's final loss."""
    star_sim, loss0, star_losses = _losses_over_rounds(consensus_fleet,
                                                       "star")
    star_link = star_sim.hop_bytes["client->server"]
    per_cell = {}
    for cells in (2, 4, 8):
        sim, _, losses = _losses_over_rounds(consensus_fleet, "hier",
                                             cells=cells)
        root = sim.hop_bytes["edge->root"]
        per_cell[cells] = root / cells
        expect = star_link * cells / 64
        assert 0.4 * expect <= root <= 2.5 * expect, (cells, root, expect)
        assert abs(losses[-1] - star_losses[-1]) <= 0.02 * loss0
    assert max(per_cell.values()) <= 1.6 * min(per_cell.values()), per_cell


def test_hier_replay_bit_identical(consensus_fleet, params_digest):
    """A 64-client fleet behind 4 edge aggregators, 60% sampled a round,
    runs the same round twice from one seed."""
    def one():
        cfg = FLConfig(aggregation="fedavg", transport=TransportConfig(
            kind="mudp", timeout_ns=2 * NS, udp_deadline_ns=3 * NS))
        _, sim, system, results = consensus_fleet(
            "hier", n=64, rounds=1, seed=0, obj_params=2048, obj_seed=0,
            fl_cfg=cfg, cells=4, participation_fraction=0.6,
            round_deadline_ns=10 * NS)
        return ([dataclasses.asdict(r) for r in results],
                dict(sim.hop_bytes), params_digest(system.global_params))
    first = one()
    assert first[0][0]["arrived"]
    assert one() == first


def test_hier_cell_assignment_round_robin(consensus_fleet):
    fleet = FleetConfig(n_clients=10, topology="hier", cells=3)
    assert [fleet.cell_of(i) for i in range(6)] == [0, 1, 2, 0, 1, 2]
    _, _, hier, _ = consensus_fleet("hier", n=10, cells=3, rounds=1)
    assert isinstance(hier, HierSystem)
    sizes = sorted(len(e.core.pool.clients) for e in hier.edges)
    assert sizes == [3, 3, 4]
    # Every client is in exactly one cell, and edge_for finds it.
    for e in hier.edges:
        for addr in e.core.pool.clients:
            assert hier.edge_for(addr) is e


def test_hier_addresses_are_dual_plane(consensus_fleet):
    _, sim, hier, _ = consensus_fleet("hier", n=8, cells=2, rounds=1)
    for m, e in enumerate(hier.edges):
        assert e.addr == edge_client_addr(m)
        assert e.server_addr == edge_server_addr(m)
        assert e.addr != e.server_addr


def test_hier_per_cell_histories_advance(consensus_fleet):
    _, _, hier, results = consensus_fleet("hier", n=16, cells=4, rounds=3)
    assert len(results) == 3
    for e in hier.edges:
        assert len(e.core.history) == 3


def test_hier_async_root(consensus_fleet):
    _, sim, hier, results = consensus_fleet(
        "hier", n=16, cells=4, rounds=3, mode="async", buffer_k=4,
        round_deadline_ns=120_000_000_000)
    assert len(results) == 3
    assert sim.hop_bytes["edge->root"] > 0


def test_hier_cell_scheduler_refuses_direct_drive(consensus_fleet):
    _, _, hier, _ = consensus_fleet("hier", n=8, cells=2, rounds=1)
    with pytest.raises(RuntimeError, match="parent tier"):
        hier.edges[0].scheduler.run_round()


def test_hier_per_hop_pipeline_specs(consensus_fleet):
    _, sim, hier, _ = consensus_fleet(
        "hier", n=16, cells=4,
        hops="client->edge: int8(48); edge->root: raw")
    plain = consensus_fleet("hier", n=16, cells=4)[1]
    # int8 quantization (block sized to the model) shrinks the cell uplink
    # vs the raw float default.
    assert sim.hop_bytes["client->edge"] < plain.hop_bytes["client->edge"]


# --------------------------------------------------------------------------
# gossip: serverless
# --------------------------------------------------------------------------
def test_neighbor_graph_connected_and_seeded():
    adj = neighbor_graph(20, 4, seed=1)
    assert adj == neighbor_graph(20, 4, seed=1)
    assert all(len(a) >= 4 for a in adj)
    assert all(i not in adj[i] for i in range(20))
    # Symmetry.
    for i in range(20):
        for j in adj[i]:
            assert i in adj[j]
    # Ring edges guarantee connectivity.
    seen, stack = {0}, [0]
    while stack:
        for j in adj[stack.pop()]:
            if j not in seen:
                seen.add(j)
                stack.append(j)
    assert len(seen) == 20


def test_gossip_has_zero_server_nodes(consensus_fleet):
    fleet_server = FleetConfig(n_clients=12, topology="gossip",
                               neighbors=3).server_addr
    _, sim, system, results = consensus_fleet("gossip", n=12, neighbors=3)
    assert fleet_server not in sim._nodes
    assert set(sim.hop_bytes) == {"peer->peer"}
    assert sim.hop_bytes["peer->peer"] == sim.stats["bytes_sent"]
    assert results[-1].metrics["neighbors_mean"] > 0


def test_gossip_converges_and_is_deterministic(consensus_fleet,
                                               params_digest):
    obj1, _, s1, _ = consensus_fleet("gossip", n=12, neighbors=3, rounds=4)
    obj2, _, s2, _ = consensus_fleet("gossip", n=12, neighbors=3, rounds=4)
    assert params_digest(s1.global_params) == params_digest(s2.global_params)
    initial = obj1.loss({"w": np.zeros(48, np.float32)})
    assert obj1.loss(s1.global_params) < 0.5 * initial


def test_gossip_reaches_a_tenth_of_initial_loss(consensus_fleet):
    _, loss0, losses = _losses_over_rounds(consensus_fleet, "gossip",
                                           neighbors=4)
    assert min(losses) <= 0.1 * loss0, (loss0, losses)


def test_gossip_rejects_delta_pipelines():
    obj = ConsensusObjective(8, 16, seed=0)
    fleet = FleetConfig(n_clients=8, topology="gossip", neighbors=2,
                        hops="peer->peer: delta|int8(1024)")
    with pytest.raises(ValueError, match="delta|weight-domain"):
        build_fleet(fleet, obj.init_params(),
                    lambda i, p: obj.train_fn(i, p),
                    FLConfig(transport=TransportConfig(kind="mudp")))


# --------------------------------------------------------------------------
# FleetConfig validation (fail at construction, not deep in build)
# --------------------------------------------------------------------------
@pytest.mark.parametrize("kw,match", [
    (dict(topology="mesh"), "unknown topology"),
    (dict(topology="hier", cells=0), "cells"),
    (dict(topology="hier", cells=17), "cannot exceed"),
    (dict(topology="hier", edge_cohort="dialup"), "edge_cohort"),
    (dict(topology="hier", cell_transport="pigeon"), "transport"),
    (dict(topology="gossip", neighbors=0), "degree"),
    (dict(topology="gossip", neighbors=16), "must be <"),
    (dict(hops="client->server: bogus_stage"), "invalid hops"),
    (dict(hops="peer->peer: raw"), "invalid hops"),   # not a star hop
    (dict(hops="client->server: raw", uplink="raw"), "two spellings"),
    (dict(n_clients=0), "n_clients"),
])
def test_fleetconfig_validation(kw, match):
    base = dict(n_clients=16)
    base.update(kw)
    with pytest.raises(ValueError, match=match):
        FleetConfig(**base)


def test_custom_topology_plugs_in():
    class NullTopology(Topology):
        name = "null"
        hops = ()

        def build(self, fleet, profiles, global_params, train_fn_factory,
                  fl_cfg):
            return Simulator(), None

    register_topology("null", NullTopology, overwrite=True)
    try:
        fleet = FleetConfig(n_clients=2, topology="null")
        sim, system, profiles = build_fleet(fleet, {"w": np.zeros(4)},
                                            lambda i, p: None)
        assert system is None and len(profiles) == 2
    finally:
        import repro.core.topology as topo_mod
        del topo_mod._REGISTRY["null"]
