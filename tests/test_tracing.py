"""The program's spans and counters (``repro.core.tracing``): the self-time
arithmetic, windows over cumulative totals, and what a round exports on
``RoundResult.spans`` / ``.counters``."""

import inspect
import time

import pytest

from repro.core import (FLConfig, FleetConfig, TransportConfig,
                        build_fleet_training, packetizer, server, tracing)
from repro.core.simulator import PACKET_ENGINES

NS = 1_000_000_000


@pytest.fixture
def fake_clock(monkeypatch):
    """A clock that reads 0, 10, 20, ... ns, one step per read."""
    ticks = iter(range(0, 10**9, 10))
    monkeypatch.setattr(tracing, "clock", lambda: next(ticks))


def test_self_time_of_nested_spans(fake_clock):
    snap = tracing.snapshot()
    with tracing.span("t.outer"):            # 0 .. 70
        with tracing.span("t.inner"):        # 10 .. 20
            pass
        with tracing.span("t.inner"):        # 30 .. 60
            with tracing.span("t.leaf"):     # 40 .. 50
                pass
    spans, _ = tracing.delta(snap)
    assert spans["t.leaf"] == (1, 10, 10)
    assert spans["t.inner"] == (2, 10 + 30, 10 + (30 - 10))
    assert spans["t.outer"] == (1, 70, 70 - (10 + 30))
    # Self times add up to the outermost span's wall.
    assert sum(s for _, _, s in spans.values()) == spans["t.outer"][1]


def test_a_span_that_raises_is_still_closed(fake_clock):
    snap = tracing.snapshot()
    with pytest.raises(KeyError):
        with tracing.span("t.raises"):
            raise KeyError("x")
    with tracing.span("t.after"):
        pass
    spans, _ = tracing.delta(snap)
    assert spans["t.raises"] == (1, 10, 10)
    assert spans["t.after"] == (1, 10, 10)


def test_delta_holds_only_what_moved_and_nested_windows_see_their_own():
    tracing.count("t.before", 5)
    outer = tracing.snapshot()
    tracing.count("t.counter", 2)
    inner = tracing.snapshot()
    tracing.count("t.counter", 3)
    with tracing.span("t.window"):
        pass
    inner_spans, inner_counters = tracing.delta(inner)
    tracing.count("t.counter")
    _, outer_counters = tracing.delta(outer)
    assert inner_counters == {"t.counter": 3}
    assert list(inner_spans) == ["t.window"]
    assert outer_counters == {"t.counter": 6}
    assert "t.before" not in outer_counters
    assert tracing.counters()["t.counter"] >= 6


def test_counters_add_and_default_to_one():
    snap = tracing.snapshot()
    tracing.count("t.n")
    tracing.count("t.n", 41)
    tracing.count("t.zero", 0)
    _, counters = tracing.delta(snap)
    assert counters == {"t.n": 42}


def test_decorator_keeps_name_and_signature():
    def payload_size(data: bytes, *, header: int = 28) -> int:
        """Doc."""
        return len(data) + header

    traced = tracing.span("t.decorated")(payload_size)
    assert traced.__name__ == "payload_size"
    assert traced.__doc__ == "Doc."
    assert inspect.signature(traced) == inspect.signature(payload_size)
    snap = tracing.snapshot()
    assert traced(b"abc", header=1) == 4
    assert tracing.delta(snap)[0]["t.decorated"][0] == 1
    # The program's decorated entry points keep theirs too.
    assert packetizer.packetize.__name__ == "packetize"
    assert "mtu" in inspect.signature(packetizer.packetize).parameters


def test_compiles_are_counted():
    import jax
    import jax.numpy as jnp
    snap = tracing.snapshot()
    jax.jit(lambda x: x * 3 + 1)(jnp.arange(7.0)).block_until_ready()
    _, counters = tracing.delta(snap)
    assert counters["jax.compiles"] >= 1
    assert counters["jax.compile_ns"] > 0


@pytest.mark.parametrize("engine", PACKET_ENGINES)
@pytest.mark.parametrize("mode", ["sync", "async"])
def test_rounds_export_their_spans_and_counters(monkeypatch, mode, engine):
    """A tiny consensus fleet on the vmap trainer and the cells' pipelines:
    every RoundResult carries its window's spans and counters, the spans'
    self time fits in the round's host wall, and ``packets.built`` equals
    a count of the payloads handed to ``packetize``."""
    handed: list[int] = []

    def recording(packetize):
        def rec(data, addr, txn=0, mtu=packetizer.DEFAULT_MTU):
            handed.append(-(-len(data) // (mtu - 28)))
            return packetize(data, addr, txn, mtu)
        return rec
    monkeypatch.setattr(server, "packetize", recording(server.packetize))
    monkeypatch.setattr(packetizer, "packetize",
                        recording(packetizer.packetize))

    fleet = FleetConfig(n_clients=8, seed=3, mode=mode, buffer_k=3,
                        engine=engine, round_deadline_ns=4 * NS,
                        model="consensus", model_args={"n_params": 6000},
                        train_backend="vmap",
                        uplink="delta|ef|topk(0.01)|int8(1024)",
                        downlink="int8(1024)")
    cfg = FLConfig(aggregation="fedavg",
                   transport=TransportConfig(kind="mudp", timeout_ns=2 * NS))
    fb = build_fleet_training(fleet, cfg)
    seen = []
    last = [time.perf_counter_ns()]

    def on_round_end(result, params):
        now = time.perf_counter_ns()
        seen.append((result, now - last[0], sum(handed)))
        handed.clear()
        last[0] = now
    fb.system.on_round_end = on_round_end
    fb.system.run_rounds(3)

    assert len(seen) == 3
    for result, wall_ns, built in seen:
        assert {"packet.build", "packet.reassemble", "engine.burst",
                "wire.encode", "wire.decode", "wire.decode_batch",
                "aggregate", "train.flush", "train.step"} <= set(result.spans)
        for count, total_ns, self_ns in result.spans.values():
            assert count >= 1 and 0 <= self_ns <= total_ns
        assert sum(s for _, _, s in result.spans.values()) <= wall_ns
        assert result.counters["packets.built"] == built
        assert result.counters["train.rows"] >= 1
        assert result.counters["device.h2d_bytes"] > 0
        assert result.counters["device.d2h_bytes"] > 0
