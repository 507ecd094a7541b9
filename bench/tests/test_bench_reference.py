"""The plain references of the wire encode and of the event layer, against
the program on hand-made inputs, and the event check catching a payload
that changed on its way."""

import time
from pathlib import Path

import numpy as np
import pytest

from fedbench import harness
from reference import events
from reference import wire as ref_wire

DATA = Path(__file__).resolve().parent / "data"


@pytest.mark.parametrize("spec", ["delta|ef|topk(0.01)|int8(1024)",
                                  "int8(1024)", "delta|ef|topk(0.25)",
                                  "topk(0.5)|int8(16)"])
def test_reference_encode_matches_the_program(spec):
    from repro.core import wire
    rng = np.random.default_rng(0)
    n = 5_000
    vec, ref = (rng.standard_normal(n).astype(np.float32) for _ in range(2))
    pipe = wire.parse_pipeline(spec)
    state = pipe.new_state()
    residual = None
    for _ in range(3):          # the residual carries from one to the next
        if pipe.caps.delta_domain:
            pipe.set_reference(state, ref)
        data = pipe.encode(vec, state)
        sent, residual = ref_wire.encode(vec, spec, ref=ref,
                                         residual=residual)
        np.testing.assert_array_equal(ref_wire.decode(data), sent)
        ef = [s.get("residual") for s in state.slots if "residual" in s]
        if residual is not None:
            np.testing.assert_array_equal(ef[0], residual)
        vec = vec + rng.standard_normal(n).astype(np.float32) * 0.1


def test_topk_ties_may_be_chosen_either_way():
    x = np.array([3.0, -2.0, 2.0, 1.0], np.float32)
    assert ref_wire.is_topk(x, [0, 1], 2) and ref_wire.is_topk(x, [0, 2], 2)
    assert not ref_wire.is_topk(x, [0, 3], 2)
    assert not ref_wire.is_topk(x, [0, 0], 2)
    sent, _ = ref_wire.encode(x, "topk(0.5)", kept=[0, 2])
    np.testing.assert_array_equal(sent, [3.0, 0.0, 2.0, 0.0])


def test_packets_against_the_mudp_slicing():
    from repro.core.packetizer import packetize
    data = bytes(range(256)) * 20                     # 5,120 bytes
    pk = [(p.seq, p.total, p.payload) for p in packetize(data, "a", 1, 1500)]
    assert events.packet_count(len(data), 1500) == len(pk) == 4
    assert events.packets_off(data, pk, 1500) == 0
    assert events.packets_off(data, pk[:-1], 1500) >= 1       # one lost
    bad = pk[:1] + [(2, 4, pk[1][2][:-1] + b"\0")] + pk[2:]
    assert events.packets_off(data, bad, 1500) == 1           # one altered
    assert events.packet_count(0, 1500) == 1


def test_sync_round_rules():
    roster, d = ["a", "b", "c"], 100

    def off(arrived, failed, came, duration=150, expected=roster):
        return events.sync_round_off(roster, expected, arrived, failed,
                                     came, d, duration)
    assert off(["a", "b"], [], [("a", 10), ("b", 90), ("c", 140)]) == 0
    assert off(["a", "b", "c"], [], [("a", 10), ("b", 90),
                                     ("c", 140)]) == 1    # c came late
    assert off(["a"], [], [("a", 10), ("b", 90)]) == 1    # b not folded
    assert off(["a"], ["b"], [("a", 10)]) == 0            # b failed
    assert off(["a"], ["a"], [("a", 10)]) == 1            # both
    assert off(["a"], [], [("a", 10)], expected=roster + ["d"]) == 1
    assert off(["a", "b"], [], [("a", 10), ("b", 90)], duration=50) == 1
    assert events.sync_round_off(roster, roster, ["a", "b"], [],
                                 [("a", 1), ("b", 2)], None, 9) == 1


def test_a_payload_changed_in_transit_is_not_correct(monkeypatch):
    # The transport hands the server other bytes than the client sent:
    # decode, fold and training all agree with themselves, and only the
    # event check sees it.
    from repro.core import server
    init = server._PendingWire.__init__

    def corrupted(self, data, *a, **k):
        init(self, data[:-1] + bytes([data[-1] ^ 1]), *a, **k)
    monkeypatch.setattr(server._PendingWire, "__init__", corrupted)
    out = harness.run("tiny_silo.sync_int8", 11, 1.0, False,
                      t_start=time.perf_counter(), root=DATA, bench_dir=DATA)
    assert not out["correct"]
    assert out["checks"]["events_off"]["value"] >= 1
