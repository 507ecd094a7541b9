"""Engine equivalence: the batched flight engine must be bit-for-bit
identical to the per-packet reference — same stats, same final clock, same
delivered bytes, same FL round results — across seeds, transports, and
jittered/reordering/lossy links.  Plus unit coverage for the pieces the
equivalence rests on: the keyed counter-based RNG (scalar == vectorized),
the bulk-ingestion fallback, per-kind counters, and the arithmetic
``wire_bytes``.
"""

import dataclasses
import hashlib

import numpy as np
import pytest

from repro.core import (BernoulliLoss, ConsensusObjective, DropList, FLConfig,
                        FleetConfig, GilbertElliott, Link, LossModel, NoLoss,
                        Packetizer, Simulator, TransportConfig,
                        available_transports, build_fleet, keyed_uniform,
                        keyed_uniforms, make_transport, packet_key_arrays,
                        packetize)
from repro.core.channel import JITTER_STREAM, LOSS_STREAM
from repro.core.fleet import DEFAULT_MIX, links_for, sample_profiles
from repro.core.packets import (HEADER_BYTES, PacketKind, make_data_packet,
                                make_nack)

NS = 1_000_000_000
SERVER = "10.0.0.1"


# --------------------------------------------------------------------------
# The keyed RNG: one function, two shapes
# --------------------------------------------------------------------------
class TestKeyedUniforms:
    def test_scalar_equals_vectorized(self):
        pkts = [make_data_packet(s, 64, "10.1.0.1", b"x" * s, txn=3)
                for s in range(1, 65)]
        pkts = [dataclasses.replace(p, attempt=s % 3)
                for s, p in enumerate(pkts)]
        txns, kinds, seqs, attempts = packet_key_arrays(pkts)
        for stream in (LOSS_STREAM, JITTER_STREAM, 0xABCD):
            for seed in (0, 1, -7, 2**63):
                vec = keyed_uniforms(stream, seed, txns, kinds, seqs,
                                     attempts)
                sca = [keyed_uniform(stream, seed, p) for p in pkts]
                assert vec.tolist() == sca

    def test_draws_in_unit_interval_and_vary(self):
        pkts = [make_data_packet(s, 999, "a", b"", txn=0)
                for s in range(1, 1000)]
        txns, kinds, seqs, attempts = packet_key_arrays(pkts)
        u = keyed_uniforms(LOSS_STREAM, 42, txns, kinds, seqs, attempts)
        assert float(u.min()) >= 0.0 and float(u.max()) < 1.0
        assert 0.3 < float(u.mean()) < 0.7
        assert len(set(u.tolist())) == len(pkts)

    def test_streams_decorrelated(self):
        p = make_data_packet(5, 9, "a", b"x", txn=2)
        assert keyed_uniform(LOSS_STREAM, 0, p) != \
            keyed_uniform(JITTER_STREAM, 0, p)

    def test_loss_models_scalar_vs_mask(self):
        pkts = packetize(bytes(range(256)) * 40, "10.1.0.2", txn=7, mtu=200)
        arrays = packet_key_arrays(pkts)
        for model in (BernoulliLoss(p=0.3, seed=5),
                      GilbertElliott(p_good_loss=0.05, p_bad_loss=0.6,
                                     p_bad=0.2, seed=9),
                      NoLoss(),
                      DropList({(2, 0), (5, 0)})):
            mask = model.drop_mask(pkts, *arrays)
            assert mask.tolist() == [model.drops(p) for p in pkts]

    def test_custom_loss_model_default_mask_falls_back(self):
        class OddSeqLoss(LossModel):
            def drops(self, pkt):
                return pkt.seq % 2 == 1

        pkts = packetize(b"z" * 4000, "10.1.0.3", txn=1, mtu=300)
        mask = OddSeqLoss().drop_mask(pkts, *packet_key_arrays(pkts))
        assert mask.tolist() == [p.seq % 2 == 1 for p in pkts]

    def test_jitter_scalar_vs_array(self):
        link = Link(1e8, 10_000_000, NoLoss(), jitter_ns=5_000_000,
                    jitter_seed=11)
        pkts = packetize(b"q" * 9000, "10.1.0.4", txn=4, mtu=256)
        arr = link.propagation_array(*packet_key_arrays(pkts))
        assert arr.tolist() == [link.propagation_ns(p) for p in pkts]


# --------------------------------------------------------------------------
# Direct transfers: one link, adversarial conditions
# --------------------------------------------------------------------------
def _transfer_digest(engine, kind, loss, *, jitter_ns=0, mtu=300,
                     payload=6000, timeout_ns=2 * NS):
    sim = Simulator(engine=engine)
    link = lambda seed: Link(1e7, 5_000_000, loss(),  # noqa: E731
                             jitter_ns=jitter_ns, jitter_seed=seed)
    sim.connect("10.1.0.9", SERVER, link(1), link(2))
    tr = make_transport(kind)
    cfg = TransportConfig(kind=kind, mtu=mtu, timeout_ns=timeout_ns,
                          udp_deadline_ns=4 * NS)
    got = []
    tr.create_receiver(sim, sim.node(SERVER), cfg, got.append)
    data = bytes(range(256)) * (payload // 256)
    sender = tr.create_sender(sim, sim.node("10.1.0.9"), sim.node(SERVER),
                              packetize(data, "10.1.0.9", txn=1, mtu=mtu),
                              cfg)
    sender.start()
    sim.run()
    blob = repr((sim.now_ns, sorted(sim.stats.items()),
                 [(d.sender_addr, d.txn, d.total, d.complete,
                   d.reassemble()) for d in got],
                 dataclasses.astuple(sender.stats)))
    return hashlib.sha256(blob.encode()).hexdigest()


@pytest.mark.parametrize("kind", available_transports())
class TestDirectTransferEquivalence:
    def test_clean_link(self, kind):
        assert _transfer_digest("per_packet", kind, NoLoss) == \
            _transfer_digest("batched", kind, NoLoss)

    def test_reordering_jitter(self, kind):
        # Jitter larger than the serialization gap reorders in flight.
        for seed in range(3):
            mk = lambda: BernoulliLoss(p=0.05, seed=seed)  # noqa: E731
            a = _transfer_digest("per_packet", kind, mk, jitter_ns=8_000_000)
            b = _transfer_digest("batched", kind, mk, jitter_ns=8_000_000)
            assert a == b

    def test_bursty_loss(self, kind):
        mk = lambda: GilbertElliott(p_good_loss=0.02, p_bad_loss=0.5,  # noqa: E731
                                    p_bad=0.15, seed=3)
        assert _transfer_digest("per_packet", kind, mk) == \
            _transfer_digest("batched", kind, mk)

    def test_exact_drop_pattern(self, kind):
        mk = lambda: DropList({(1, 0), (2, 0), (7, 0), (21, 1)})  # noqa: E731
        assert _transfer_digest("per_packet", kind, mk) == \
            _transfer_digest("batched", kind, mk)

    def test_timer_fires_mid_flight(self, kind):
        # Sender timeout far shorter than the burst's serialization time:
        # timer-driven resends and NACK rounds cross with the in-flight
        # data flight — the adversarial interleaving for deep ingestion.
        for timeout in (20_000_000, 60_000_000):
            mk = lambda: BernoulliLoss(p=0.15, seed=4)  # noqa: E731
            a = _transfer_digest("per_packet", kind, mk,
                                 jitter_ns=8_000_000, timeout_ns=timeout)
            b = _transfer_digest("batched", kind, mk,
                                 jitter_ns=8_000_000, timeout_ns=timeout)
            assert a == b


# --------------------------------------------------------------------------
# Fleet rounds: full FL stack, heterogeneous cohorts
# --------------------------------------------------------------------------
def _build_fleet(engine, kind, seed, *, n_clients=8, n_params=600,
                 mode="sync", participation=0.75, deadline_ns=90 * NS,
                 cohort_mix=DEFAULT_MIX, timeout_ns=4 * NS,
                 udp_deadline_ns=6 * NS):
    fleet = FleetConfig(n_clients=n_clients, seed=seed, mode=mode,
                        participation_fraction=participation,
                        round_deadline_ns=deadline_ns, engine=engine,
                        cohort_mix=cohort_mix)
    objective = ConsensusObjective(n_clients, n_params, seed=seed)
    cfg = FLConfig(aggregation="fedavg",
                   transport=TransportConfig(kind=kind, timeout_ns=timeout_ns,
                                             udp_deadline_ns=udp_deadline_ns))
    sim, system, _ = build_fleet(fleet, objective.init_params(),
                                 objective.train_fn, cfg)
    return sim, system


def _fleet_round_digest(engine, kind, seed, *, rounds=2, **shape):
    sim, system = _build_fleet(engine, kind, seed, **shape)
    if shape.get("mode", "sync") == "sync":
        results = [system.run_round() for _ in range(rounds)]
    else:
        results = system.run_rounds(rounds)
    blob = repr((sim.now_ns, sorted(sim.stats.items()),
                 [dataclasses.asdict(r) for r in results],
                 system.global_params["w"].tobytes()))
    return hashlib.sha256(blob.encode()).hexdigest()


# Many flights at once: 64 clients, mostly on lossy cohorts, so tens of
# uplink flights overlap with retransmission flights of the same
# transactions, sender timeouts and the round deadline.
_LOSSY_MIX = (("fiber", 0.2), ("lte", 0.4), ("congested-edge", 0.4))
_MANY_FLIGHTS = dict(n_clients=64, n_params=6000, participation=1.0,
                     cohort_mix=_LOSSY_MIX, timeout_ns=2 * NS,
                     udp_deadline_ns=3 * NS)


@pytest.mark.parametrize("kind", available_transports())
@pytest.mark.parametrize("seed,shape", [
    pytest.param(0, {}, id="0"),
    pytest.param(1, {}, id="1"),
    pytest.param(7, {}, id="7"),
    # One sync round cut by a deadline, and a short FedBuff run.
    pytest.param(11, dict(_MANY_FLIGHTS, rounds=1, deadline_ns=6 * NS),
                 id="fleet64-sync"),
    pytest.param(11, dict(_MANY_FLIGHTS, rounds=3, mode="async",
                          deadline_ns=None), id="fleet64-async"),
])
def test_fleet_round_bit_identical(kind, seed, shape):
    assert _fleet_round_digest("per_packet", kind, seed, **shape) == \
        _fleet_round_digest("batched", kind, seed, **shape)


def test_flight_visits_counted_per_round():
    # The pass walks only the flights due before its bound: far fewer
    # than the flights in flight when it runs.
    sim, system = _build_fleet("batched", "mudp", 11, deadline_ns=6 * NS,
                               **_MANY_FLIGHTS)
    live = []
    walk = sim._flight_pass

    def counting_pass(until_ns, first):
        live.append(1 + sum(1 for _, tie, f in sim._flightq
                            if tie == f.seated_tie))
        return walk(until_ns, first)

    sim._flight_pass = counting_pass
    result = system.run_round()
    visits = result.counters["engine.flight_visits"]
    passes = result.spans["engine.flight_pass"][0]
    assert passes == len(live) > 0
    assert passes <= visits < passes * (sum(live) / len(live)) / 2


# --------------------------------------------------------------------------
# Engine plumbing
# --------------------------------------------------------------------------
class TestEnginePlumbing:
    def test_unknown_engine_rejected(self):
        with pytest.raises(ValueError, match="engine"):
            Simulator(engine="warp")

    def test_send_burst_fallback_is_per_packet_loop(self):
        # Under the per-packet engine, send_burst == N sends, exactly.
        def run(use_burst):
            sim = Simulator(engine="per_packet")
            sim.connect("a", "b", Link(1e8, 1_000_000))
            got = []
            sim.node("b").register(lambda p: got.append(p) or True)
            pkts = packetize(b"x" * 3000, "a", txn=1, mtu=300)
            if use_burst:
                sim.node("a").send_burst(pkts, sim.node("b"))
            else:
                for p in pkts:
                    sim.node("a").send(p, sim.node("b"))
            sim.run()
            return sim.stats_digest(), [p.seq for p in got]

        assert run(True) == run(False)

    def test_per_kind_counters_sum_to_totals(self):
        sim = Simulator(engine="batched")
        profiles = sample_profiles(FleetConfig(n_clients=4, seed=3))
        for p in profiles:
            up, down = links_for(p)
            sim.connect(p.addr, SERVER, up, down)
        tr = make_transport("mudp+fec")
        cfg = TransportConfig(kind="mudp+fec", timeout_ns=4 * NS)
        tr.create_receiver(sim, sim.node(SERVER), cfg, lambda d: None)
        for p in profiles:
            tr.create_sender(sim, sim.node(p.addr), sim.node(SERVER),
                             packetize(b"y" * 20_000, p.addr, txn=1,
                                       mtu=cfg.mtu), cfg).start()
        sim.run()
        s = sim.stats
        for total, prefix in (("packets_sent", "sent_"),
                              ("packets_dropped", "dropped_"),
                              ("packets_delivered", "delivered_")):
            by_kind = sum(v for k, v in s.items() if k.startswith(prefix))
            assert by_kind == s[total]
        assert s.get("sent_parity", 0) > 0    # FEC trailer was counted

    def test_events_processed_counts_match_engines(self):
        a = Simulator(engine="per_packet")
        b = Simulator(engine="batched")
        for sim in (a, b):
            sim.connect("a", "b", Link(1e8, 1_000_000,
                                       jitter_ns=500_000, jitter_seed=5))
            tr = make_transport("mudp")
            cfg = TransportConfig(kind="mudp")
            tr.create_receiver(sim, sim.node("b"), cfg, lambda d: None)
            tr.create_sender(sim, sim.node("a"), sim.node("b"),
                             packetize(b"k" * 8000, "a", txn=1, mtu=300),
                             cfg).start()
            sim.run()
        assert a.events_processed == b.events_processed
        assert a.stats_digest() == b.stats_digest()

    def test_paused_run_resumes_identically(self):
        assert _staged("per_packet") == _staged("batched")

    @pytest.mark.parametrize("kind", ["udp", "mudp"])
    def test_paused_run_resumes_identically_many_flights(self, kind):
        # 24 senders' flights overlap, and under mudp lost packets bring
        # NACK volleys and retransmission flights across the pauses.
        assert _staged("per_packet", kind=kind, senders=24, loss=0.05) == \
            _staged("batched", kind=kind, senders=24, loss=0.05)


def _staged(engine, *, kind="udp", senders=1, loss=0.0):
    """Run transfers from ``senders`` nodes to one receiver, pausing
    mid-flight several times before draining; what each pause saw."""
    sim = Simulator(engine=engine)
    cfg = TransportConfig(kind=kind, udp_deadline_ns=4 * NS,
                          timeout_ns=20_000_000)
    tr = make_transport(kind)
    got = []
    tr.create_receiver(sim, sim.node("b"), cfg, got.append)
    for c in range(senders):
        addr = f"a{c}" if senders > 1 else "a"
        lossy = (BernoulliLoss(p=loss, seed=c) if loss else NoLoss())
        sim.connect(addr, "b", Link(1e7, 2_000_000 + 50_000 * c, lossy,
                                    jitter_ns=3_000_000, jitter_seed=2 + c))
        tr.create_sender(sim, sim.node(addr), sim.node("b"),
                         packetize(b"m" * 12_000, addr, txn=1, mtu=300),
                         cfg).start()
    mids = []
    # Pause mid-flight several times, then drain.
    for until in (2_500_000, 3_500_000, 5_000_000):
        sim.run(until_ns=until)
        mids.append((sim.now_ns, dict(sim.stats)))
    sim.run()
    return mids, sim.stats_digest(), [d.reassemble() for d in got]


# --------------------------------------------------------------------------
# The flight pass's bounds, one at a time
# --------------------------------------------------------------------------
class _Recorder:
    """A receiver with a bulk hook.  Each statically unsafe packet (non-DATA
    or a transaction's last) is logged with what its transaction has stored
    so far, or, for a transaction in ``watch_all``, with what every
    transaction has.  ``decline(pkt)`` may return 0 or -1 to decline a due
    packet; the hook also stops a run before such a packet."""

    def __init__(self, sim, node, *, watch_all=(), decline=None):
        self.sim = sim
        self.watch_all = set(watch_all)
        self.decline = decline
        self.stored: dict = {}
        self.log: list = []
        self.one_by_one: list = []      # DATA stored through the handler
        node.register(self.on_packet, bulk=self.ingest)

    def _seen(self, key):
        if key in self.watch_all:
            return sorted((k, sorted(v)) for k, v in self.stored.items())
        return sorted(self.stored.get(key, ()))

    def on_packet(self, p):
        key = (p.addr, p.txn)
        if p.kind == PacketKind.DATA:
            self.stored.setdefault(key, set()).add(p.seq)
            if p.seq != p.total:
                self.one_by_one.append(key + (p.seq,))
        if p.kind != PacketKind.DATA or p.seq == p.total:
            self.log.append((self.sim.now_ns, p.kind, key, p.seq,
                             self._seen(key)))
        return True

    def ingest(self, pkts, i, j, arrivals):
        if self.decline is not None:
            c = self.decline(pkts[i])
            if c is not None:
                return c
        k = i
        while k < j:
            p = pkts[k]
            if (p.kind != PacketKind.DATA or p.seq == p.total
                    or (self.decline is not None
                        and self.decline(p) is not None)):
                break
            self.stored.setdefault((p.addr, p.txn), set()).add(p.seq)
            k += 1
        return k - i


def _data(addr, txn, seqs, total):
    return [make_data_packet(s, total, addr, bytes([s % 251]) * 200, txn=txn)
            for s in seqs]


class TestFlightPassBounds:
    def _run(self, engine, sends, **recorder):
        """``sends``: (node, link delay ns, packets) bursts, all sent at
        time 0, each from its own node over its own link to ``SERVER``."""
        sim = Simulator(engine=engine)
        rec = _Recorder(sim, sim.node(SERVER), **recorder)
        for node, delay, _ in sends:
            sim.connect(node, SERVER, Link(1e7, delay))
        for node, _, pkts in sends:
            sim.node(node).send_burst(pkts, sim.node(SERVER))
        sim.run()
        return rec, sim

    def _both(self, sends, **recorder):
        ref, ref_sim = self._run("per_packet", sends, **recorder)
        got, sim = self._run("batched", sends, **recorder)
        assert got.log == ref.log
        assert got.stored == ref.stored
        assert sim.stats_digest() == ref_sim.stats_digest()
        return got

    def test_heterogeneous_flight_bounds_every_flight(self):
        # A burst of two transactions (key None) whose second packet, the
        # last of txn 6, arrives while txn 1's 40-packet flight is half in:
        # txn 1 may not be ingested past it.
        het = (_data("h", 5, [1], 4) + _data("h", 6, [1], 1)
               + _data("h", 5, [2, 3, 4], 4))
        got = self._both([("a", 1_000_000, _data("a", 1, range(1, 41), 60)),
                          ("h", 3_000_000, het)],
                         watch_all=[("h", 5), ("h", 6)])
        at_bound = got.log[0]
        assert at_bound[2] == ("h", 6)
        seen = dict(at_bound[4])
        assert 0 < len(seen[("a", 1)]) < 40
        assert got.one_by_one == []     # bulk resumes past the bound

    def test_second_flight_of_a_key_bounds_the_first(self):
        # Flight B of the same (addr, txn), planned after A over another
        # link, lands a NACK inside flight A's arrivals: A stops there, and
        # goes on once the NACK is processed.
        a = _data("a", 1, range(1, 41), 50) + _data("a", 1, [50], 50)
        b = (_data("a", 1, [41], 50) + [make_nack(7, 50, "a", 1)]
             + _data("a", 1, [42, 43], 50))
        got = self._both([("a", 1_000_000, a), ("a2", 4_000_000, b)])
        nack = got.log[0]
        assert nack[1] == PacketKind.NACK
        assert 1 < len(nack[4]) < 41
        assert got.log[-1][3] == 50 and len(got.log[-1][4]) == 44
        assert got.one_by_one == []     # bulk resumes past the NACK

    @pytest.mark.parametrize("code", [0, -1])
    def test_declined_hook_leaves_flight_deliverable(self, code):
        # 0: every fifth packet is declined, then bulk resumes after it;
        # -1: the flight is given up at its first packet past seq 20.
        # Either way the declined packets stay seated and arrive one by one.
        if code == 0:
            def decline(p):
                return 0 if p.seq % 5 == 0 else None
        else:
            def decline(p):
                return -1 if p.seq > 20 else None
        sends = [(f"c{c}", 1_000_000 + 300_000 * c,
                  _data(f"c{c}", 1, range(1, 31), 31)) for c in range(8)]
        got = self._both(sends, decline=decline)
        assert all(len(v) == 30 for v in got.stored.values())
        expect = sorted((node, 1, s) for node, _, _ in sends
                        for s in range(1, 31) if decline(
                            make_data_packet(s, 31, node, b"")) is not None)
        assert sorted(got.one_by_one) == expect


# --------------------------------------------------------------------------
# wire_bytes (arithmetic form == materialized packets)
# --------------------------------------------------------------------------
class TestWireBytes:
    @pytest.mark.parametrize("n_params", [0, 1, 37, 1000])
    @pytest.mark.parametrize("mtu", [60, 428, 1500])
    def test_matches_packet_sum(self, n_params, mtu):
        pz = Packetizer(mtu=mtu)
        tree = {"w": np.arange(n_params, dtype=np.float32)}
        data = pz.codec.encode(np.arange(n_params, dtype=np.float32))
        pkts = packetize(data, "0.0.0.0", 0, mtu)
        assert pz.wire_bytes(tree) == sum(p.size_bytes for p in pkts)

    def test_single_empty_packet_is_header_only(self):
        assert Packetizer().wire_bytes({"w": np.zeros(0, np.float32)}) == \
            HEADER_BYTES

    def test_mtu_too_small_raises(self):
        with pytest.raises(ValueError, match="mtu"):
            Packetizer(mtu=10).wire_bytes({"w": np.ones(4, np.float32)})
