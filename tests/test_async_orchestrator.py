"""Async (FedBuff-style) scheduler semantics: buffered aggregation,
staleness discounting (with the underflow clamp), per-client cadence,
overlapping sessions on every transport, deterministic replay, and the
simulated time to a target loss against the sync barrier."""

import dataclasses

import numpy as np
import pytest

from repro.core import (AsyncScheduler, ConsensusObjective, FLClient,
                        FLConfig, FederatedSystem, FleetConfig, Simulator,
                        TransportConfig, available_transports, build_fleet,
                        make_transport)
from repro.core.channel import DropList, Link, NoLoss

SERVER = "10.1.2.5"
NS = 1_000_000_000
MS = 1_000_000


def build(mode="async", n=4, cfg_kwargs=None, train_times=None,
          cadences=None, train_values=None, weights=None, loss_models=None,
          n_params=50):
    sim = Simulator()
    clients = []
    for i in range(n):
        addr = f"10.1.2.{10 + i}"
        lm = (loss_models or {}).get(addr, NoLoss())
        sim.connect(addr, SERVER, Link(1e8, 1 * MS, lm),
                    Link(1e8, 1 * MS, NoLoss()))

        def fn(params, round_idx, client, v=(train_values or {}).get(
                f"10.1.2.{10 + i}", float(i + 1))):
            return ({k: np.full_like(p, v) for k, p in params.items()}, {})
        c = FLClient(addr, fn,
                     train_time_ns=(train_times or {}).get(addr,
                                                           (i + 1) * 100 * MS),
                     cadence_ns=(cadences or {}).get(addr, 50 * MS))
        if weights and addr in weights:
            c.weight = weights[addr]
        clients.append(c)
    cfg = FLConfig(mode=mode, aggregation="fedavg",
                   transport=TransportConfig(kind="mudp", timeout_ns=NS),
                   **(cfg_kwargs or {}))
    params = {"w": np.zeros((n_params,), np.float32)}
    return sim, FederatedSystem(sim, SERVER, clients, params, cfg), clients


class TestBufferedAggregation:
    def test_aggregates_at_buffer_k(self):
        _, system, _ = build(cfg_kwargs={"buffer_k": 2})
        results = system.run_rounds(3)
        assert len(results) == 3
        for r in results:
            assert len(r.arrived) == 2           # exactly K per flush
            assert r.metrics["buffer_size"] == 2

    def test_rounds_overlap_fast_client_reenters(self):
        """The fastest client contributes to multiple aggregations while the
        slowest is still working — the barrier is gone."""
        _, system, _ = build(
            n=3, cfg_kwargs={"buffer_k": 2},
            train_times={"10.1.2.10": 50 * MS, "10.1.2.11": 60 * MS,
                         "10.1.2.12": 5 * NS})
        results = system.run_rounds(3)
        seen = [a for r in results for a in r.arrived]
        assert seen.count("10.1.2.10") >= 2      # re-entered mid-run
        assert all("10.1.2.12" in r.roster for r in results)  # still in flight

    def test_model_version_increments_per_aggregation(self):
        _, system, _ = build(cfg_kwargs={"buffer_k": 2})
        results = system.run_rounds(4)
        assert [r.metrics["model_version"] for r in results] == [1, 2, 3, 4]

    def test_partial_flush_on_drain(self):
        """Fewer clients than buffer_k with no re-entry possible: the drain
        flush folds what arrived instead of losing it."""
        _, system, _ = build(n=2, cfg_kwargs={"buffer_k": 50})
        results = system.run_rounds(1)
        assert len(results) == 1
        assert len(results[0].arrived) >= 2      # both clients (+ re-entries)

    def test_explicit_round_idx_rejected(self):
        _, system, _ = build()
        with pytest.raises(ValueError, match="sync-only"):
            system.run_round(round_idx=7)


class TestStaleness:
    def test_staleness_discount_hand_computed(self):
        """K=1: each arrival aggregates alone.  The second client's update
        was computed against version 0 but lands at version>0, so its
        weight is discount**staleness — pin the resulting model exactly."""
        _, system, _ = build(
            n=2, cfg_kwargs={"buffer_k": 1, "staleness_discount": 0.5},
            train_times={"10.1.2.10": 10 * MS, "10.1.2.11": 300 * MS},
            cadences={"10.1.2.10": 10 * NS, "10.1.2.11": 10 * NS},
            train_values={"10.1.2.10": 2.0, "10.1.2.11": 8.0})
        results = system.run_rounds(2)
        # Flush 1: client .10 alone (staleness 0) -> w = 2.0.  Flush 2:
        # client .11 alone; weight 0.5**1 but normalized over a single
        # contribution -> w = 8.0 regardless.  The *staleness accounting*
        # is what must be right:
        assert results[0].metrics["staleness_max"] == 0
        assert results[1].metrics["staleness_max"] == 1
        assert results[1].late_folded == 1
        np.testing.assert_allclose(system.global_params["w"], 8.0)

    def test_stale_update_downweighted_in_mixed_buffer(self):
        """A fresh and a stale update in one buffer: the stale one
        contributes discount/(1+discount) of the average."""
        _, system, _ = build(
            n=3, cfg_kwargs={"buffer_k": 2, "staleness_discount": 0.5},
            train_times={"10.1.2.10": 10 * MS, "10.1.2.11": 20 * MS,
                         "10.1.2.12": 500 * MS},
            cadences={"10.1.2.10": 1000 * MS, "10.1.2.11": 1200 * MS},
            train_values={"10.1.2.10": 1.0, "10.1.2.11": 1.0,
                          "10.1.2.12": 10.0})
        results = system.run_rounds(2)
        # Flush 1 (~22ms): .10 + .11, fresh, w=1 each -> w = 1.0.
        # .12 arrives (~0.5s) stale by 1 and waits in the buffer until
        # .10's re-entry (~1.03s) completes the pair:
        # w = (0.5*10 + 1*1) / 1.5 = 4.0
        assert results[1].metrics["staleness_max"] == 1
        np.testing.assert_allclose(system.global_params["w"], 4.0,
                                   atol=1e-6)

    def test_discount_underflow_clamped_not_dropped(self):
        """The bugfix: discount**age underflowing must clamp to
        staleness_floor and be reported, never silently zero the update."""
        _, system, _ = build(
            n=2, cfg_kwargs={"buffer_k": 1, "staleness_discount": 1e-200,
                             "staleness_floor": 1e-6},
            train_times={"10.1.2.10": 10 * MS, "10.1.2.11": 900 * MS},
            cadences={"10.1.2.10": 50 * MS},
            train_values={"10.1.2.10": 1.0, "10.1.2.11": 7.0})
        results = system.run_rounds(20)
        clamped = [r for r in results if r.staleness_clamped > 0]
        assert clamped, "straggler's discount**age must hit the floor"
        # The clamped contribution still aggregated (it flushed alone, so
        # normalization makes its value land despite the tiny weight).
        lone = [r for r in clamped if len(r.arrived) == 1
                and r.arrived == ["10.1.2.11"]]
        assert lone, "clamped update must still be aggregated"

    def test_max_staleness_drops_and_reports(self):
        _, system, _ = build(
            n=2, cfg_kwargs={"buffer_k": 1, "max_staleness": 0},
            train_times={"10.1.2.10": 10 * MS, "10.1.2.11": 900 * MS},
            cadences={"10.1.2.10": 50 * MS})
        results = system.run_rounds(20)
        dropped = sum(r.metrics["stale_dropped"] for r in results)
        assert dropped >= 1


class TestCadence:
    def test_cadence_throttles_reentry(self):
        """Same fleet, one client with a huge cadence: it contributes far
        fewer updates than its twin."""
        def run(cadence):
            _, system, _ = build(
                n=2, cfg_kwargs={"buffer_k": 1},
                train_times={"10.1.2.10": 10 * MS, "10.1.2.11": 10 * MS},
                cadences={"10.1.2.10": 1 * MS, "10.1.2.11": cadence})
            results = system.run_rounds(10)
            seen = [a for r in results for a in r.arrived]
            return seen.count("10.1.2.11")
        assert run(2 * NS) < run(1 * MS)


class TestTransportsAndDeterminism:
    @pytest.mark.parametrize("kind", available_transports())
    def test_async_runs_on_every_transport(self, kind):
        assert make_transport(kind).caps.concurrent_txns
        sim = Simulator()
        clients = []
        for i in range(4):
            addr = f"10.1.2.{10 + i}"
            sim.connect(addr, SERVER, Link(1e8, 1 * MS, NoLoss()),
                        Link(1e8, 1 * MS, NoLoss()))

            def fn(params, round_idx, client, v=float(i + 1)):
                return ({k: np.full_like(p, v) for k, p in params.items()},
                        {})
            clients.append(FLClient(addr, fn, train_time_ns=(i + 1) * 50 * MS,
                                    cadence_ns=20 * MS))
        cfg = FLConfig(mode="async", buffer_k=2,
                       transport=TransportConfig(kind=kind, timeout_ns=NS,
                                                 udp_deadline_ns=NS))
        system = FederatedSystem(sim, SERVER, clients,
                                 {"w": np.zeros((50,), np.float32)}, cfg)
        results = system.run_rounds(3)
        assert len(results) == 3
        assert all(len(r.arrived) >= 1 for r in results)

    def test_async_replay_bit_identical(self):
        def one():
            fleet = FleetConfig(n_clients=12, seed=5, mode="async",
                                buffer_k=3, round_deadline_ns=10 * NS)
            obj = ConsensusObjective(12, 128, seed=5)
            cfg = FLConfig(transport=TransportConfig(kind="mudp",
                                                     timeout_ns=2 * NS))
            _, system, _ = build_fleet(fleet, obj.init_params(),
                                       obj.train_fn, cfg)
            results = system.run_rounds(4)
            return results, system.global_params["w"]
        ra, wa = one()
        rb, wb = one()
        for x, y in zip(ra, rb):
            assert dataclasses.asdict(x) == dataclasses.asdict(y)
        assert np.array_equal(wa, wb)

    def test_async_engines_bit_identical(self):
        def one(engine):
            fleet = FleetConfig(n_clients=12, seed=5, mode="async",
                                buffer_k=3, engine=engine,
                                round_deadline_ns=10 * NS)
            obj = ConsensusObjective(12, 128, seed=5)
            _, system, _ = build_fleet(fleet, obj.init_params(), obj.train_fn)
            results = system.run_rounds(4)
            return ([dataclasses.asdict(r) for r in results],
                    system.global_params["w"])
        ra, wa = one("per_packet")
        rb, wb = one("batched")
        assert ra == rb
        assert np.array_equal(wa, wb)


class TestTimeToTarget:
    @staticmethod
    def sim_ns_to_target(mode, max_rounds):
        """Simulated time at which 32 congested-edge clients first bring
        the global loss to 5% of its initial value, or None."""
        fleet = FleetConfig(n_clients=32, seed=0, mode=mode, buffer_k=8,
                            cohort_mix=(("congested-edge", 1.0),),
                            round_deadline_ns=8 * NS)
        obj = ConsensusObjective(32, 2048, seed=0)
        cfg = FLConfig(aggregation="fedavg",
                       transport=TransportConfig(kind="mudp",
                                                 timeout_ns=2 * NS,
                                                 udp_deadline_ns=3 * NS))
        sim, system, _ = build_fleet(fleet, obj.init_params(), obj.train_fn,
                                     cfg)
        target = 0.05 * obj.loss(system.global_params)
        crossed = []

        def on_round(res, params):
            if not crossed and obj.loss(params) <= target:
                crossed.append(sim.now_ns)
        system.on_round_end = on_round
        system.run_rounds(max_rounds)
        return crossed[0] if crossed else None

    def test_async_beats_sync_barrier_on_stragglers(self):
        """Sync waits for each round's slowest client; async folds every
        8 updates, so it reaches the target in at most 0.8x the simulated
        time.  Async gets 8x the aggregations, each far shorter."""
        sync = self.sim_ns_to_target("sync", 20)
        async_ = self.sim_ns_to_target("async", 160)
        assert sync is not None and async_ is not None
        assert async_ <= 0.8 * sync, async_ / sync


class TestFailureHandling:
    def test_dead_client_benched_and_others_progress(self):
        """MUDP retry exhaustion on the dead uplink lands ~4 simulated
        seconds in (timeout * (1 + max_retries)); enough aggregations must
        be requested that a flush happens after it to report the failure."""
        dead = {(s, a) for s in range(1, 4000) for a in range(0, 80)}
        _, system, _ = build(
            n=3, cfg_kwargs={"buffer_k": 2, "unhealthy_after_failures": 1},
            loss_models={"10.1.2.12": DropList(dead)},
            train_times={"10.1.2.10": 20 * MS, "10.1.2.11": 30 * MS,
                         "10.1.2.12": 20 * MS})
        results = system.run_rounds(80)
        assert len(results) == 80
        failed = {a for r in results for a in r.failed}
        assert "10.1.2.12" in failed
        arrived = {a for r in results for a in r.arrived}
        assert "10.1.2.12" not in arrived

    def test_session_watchdog_recovers_stuck_udp_leg(self):
        """UDP with a fully dead uplink raises no failure callback; the
        per-session watchdog (round_deadline_ns) must re-enter the client
        instead of hanging the run."""
        dead = {(s, a) for s in range(1, 8000) for a in range(0, 200)}
        sim = Simulator()
        clients = []
        for i, (tt, lm) in enumerate(
                [(20 * MS, NoLoss()), (20 * MS, DropList(dead))]):
            addr = f"10.1.2.{10 + i}"
            sim.connect(addr, SERVER, Link(1e8, 1 * MS, lm),
                        Link(1e8, 1 * MS, NoLoss()))

            def fn(params, round_idx, client, v=float(i + 1)):
                return ({k: np.full_like(p, v) for k, p in params.items()},
                        {})
            clients.append(FLClient(addr, fn, train_time_ns=tt,
                                    cadence_ns=300 * MS))
        cfg = FLConfig(mode="async", buffer_k=2, round_deadline_ns=NS,
                       transport=TransportConfig(kind="udp",
                                                 udp_deadline_ns=20 * NS))
        system = FederatedSystem(sim, SERVER, clients,
                                 {"w": np.zeros((2000,), np.float32)}, cfg)
        results = system.run_rounds(6)
        assert len(results) == 6
        assert sum(r.metrics["session_timeouts"] for r in results) >= 1

    def test_all_dead_fleet_terminates(self):
        """Liveness: when every client's uplink is dead on a transport with
        no failure callback, repeated watchdog timeouts must bench the
        clients (timeout counts as a health failure) so the calendar
        drains and run_rounds returns instead of cycling forever."""
        dead = {(s, a) for s in range(1, 8000) for a in range(0, 200)}
        sim = Simulator()
        clients = []
        for i in range(2):
            addr = f"10.1.2.{10 + i}"
            sim.connect(addr, SERVER, Link(1e8, 1 * MS, DropList(dead)),
                        Link(1e8, 1 * MS, NoLoss()))

            def fn(params, round_idx, client):
                return (params, {})
            clients.append(FLClient(addr, fn, train_time_ns=10 * MS,
                                    cadence_ns=10 * MS))
        cfg = FLConfig(mode="async", buffer_k=2, round_deadline_ns=NS,
                       unhealthy_after_failures=2,
                       transport=TransportConfig(kind="udp",
                                                 udp_deadline_ns=30 * NS))
        system = FederatedSystem(sim, SERVER, clients,
                                 {"w": np.zeros((2000,), np.float32)}, cfg)
        results = system.run_rounds(4)      # must return, not hang
        assert len(results) <= 1            # at most the drain flush
        assert system.pool.benched(system.scheduler._agg_idx)


class TestSyncUnaffected:
    def test_sync_explicit_mode_matches_default(self):
        _, a, _ = build(mode="sync")
        _, b, _ = build(mode="sync")
        b.cfg = dataclasses.replace(b.cfg)      # mode survives replace()
        ra = [dataclasses.asdict(r) for r in a.run_rounds(2)]
        rb = [dataclasses.asdict(r) for r in b.run_rounds(2)]
        assert ra == rb

    def test_sync_scheduler_ignores_cadence(self):
        _, sys_a, _ = build(mode="sync", cadences={"10.1.2.10": 10 * NS})
        _, sys_b, _ = build(mode="sync", cadences={"10.1.2.10": 0})
        ra = sys_a.run_round()
        rb = sys_b.run_round()
        assert dataclasses.asdict(ra) == dataclasses.asdict(rb)

    def test_bad_mode_rejected(self):
        with pytest.raises(ValueError, match="mode"):
            FLConfig(mode="chaotic")

    def test_async_requires_concurrent_txns(self):
        import repro.core.server as server_mod
        import repro.core.scheduling as sched_mod

        class FakeTransport:
            name = "fake"
            caps = dataclasses.replace(
                make_transport("mudp").caps, concurrent_txns=False)

        sim = Simulator()
        sim.connect("10.1.2.10", SERVER, Link(1e8, 1 * MS, NoLoss()),
                    Link(1e8, 1 * MS, NoLoss()))
        core = object.__new__(server_mod.ServerCore)
        core.cfg = FLConfig(mode="async")
        core.transport = FakeTransport()
        with pytest.raises(ValueError, match="concurrent_txns"):
            AsyncScheduler(core)
