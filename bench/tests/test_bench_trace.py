"""The reduction from a profiler trace to busy time, device time per span
and labelled idle gaps."""

from pathlib import Path

import pytest

from fedbench import trace as tr
from fedbench.harness import SPAN_ORDER as ORDER

DATA = Path(__file__).resolve().parent / "data"


def _trace(ops, spans):
    t = tr.Trace()
    t.ops[0] = [("op", s, e) for s, e in ops]
    for name, ivs in spans.items():
        t.spans[name] = list(ivs)
    return t


def test_union_overlap_and_minus():
    assert tr.union([(5, 7), (0, 2), (1, 3), (7, 8)]) == [(0, 3), (5, 8)]
    assert tr.total([(0, 2), (1, 3)]) == 3
    assert tr.overlap([(0, 10)], [(2, 3), (5, 7)]) == 3
    assert tr._minus([(0, 10)], [(2, 3), (5, 7)]) == [(0, 2), (3, 5),
                                                      (7, 10)]


def test_op_names_are_cut_to_the_hlo_name():
    assert tr.op_name("%fusion.146 = f32[64,784,300]{1,2,0} fusion(f32[64] "
                      "%a, f32[8] %b), kind=kLoop") == "fusion.146"
    assert tr.op_name("copy-start.3") == "copy-start.3"


def test_busy_device_time_and_gaps():
    # Window 0..100; device busy 10..20 and 50..60 (20 of 100); the
    # aggregate span 40..70 holds the fedavg span 45..65.
    t = _trace([(10, 20), (50, 60), (150, 160)],
               {tr.WINDOW: [(0, 100)], "aggregate": [(40, 70)],
                "fedavg": [(45, 65)]})
    assert tr.window_s(t) == 100e-9
    assert tr.busy_s(t) == 20e-9
    assert tr.device_s_in(t, "fedavg") == 10e-9
    assert tr.device_s_in(t, "aggregate") == 10e-9
    gaps = dict(tr.idle_gaps(t, ["fedavg", "aggregate"]))
    # Idle: 0..10, 20..50, 60..100 = 80.  fedavg is open over 45..50 and
    # 60..65 (10); aggregate alone over 40..45 and 65..70 (10); the rest
    # (60) belongs to no span.
    assert gaps == {"fedavg": 10e-9, "aggregate": 10e-9,
                    "event_engine": 60e-9}
    assert tr.top_ops(t) == [["op", 20e-9]]


def test_recorded_trace_against_hand_read_values():
    # A profiler trace of `tiny_silo.sync_int8` (seed 9, 0.3 s window),
    # recorded on a CPU: it has host spans and no TPU plane, so it checks
    # the reading of a real .xplane.pb, the window and the idle attribution.
    # The expected values were read from the same file by a separate
    # segment sweep (every boundary of every span; each idle segment to the
    # first span of the order that covers it), not by this module.
    path = DATA / "traces" / "cpu_tiny_silo.xplane.pb"
    t = tr.load(str(path), ORDER)
    assert tr.window_s(t) == pytest.approx(0.304226483, rel=1e-12)
    assert t.ops == {} and tr.busy_s(t) == 0.0
    assert {n: len(t.spans[n]) for n in ORDER} == {
        "fedavg": 17, "wire_decode_batch": 17, "aggregate": 17,
        "train": 68, "wire_encode": 85, "wire_decode": 68}
    assert dict(tr.idle_gaps(t, ORDER)) == pytest.approx({
        "event_engine": 0.168101102, "fedavg": 0.045024096,
        "wire_decode_batch": 0.039226707, "wire_encode": 0.02181772,
        "train": 0.014132903, "aggregate": 0.008905003,
        "wire_decode": 0.007018952}, rel=1e-9)


def test_chip_trace_against_hand_read_values():
    # A profiler trace of `silo16.sync_int8` (seed 3700000051, 1 s window)
    # recorded on one TPU v5 lite.  The expected values were read from the
    # same file by a separate boundary sweep over the `/device:TPU:0`
    # plane's `XLA Ops` line, not by this module.
    path = DATA / "traces" / "tpu_silo16_int8.xplane.pb"
    t = tr.load(str(path), ORDER)
    assert list(t.ops) == [0] and len(t.ops[0]) == 235
    assert tr.window_s(t) == pytest.approx(1.086907064, rel=1e-12)
    assert tr.busy_s(t) == pytest.approx(0.002989787, rel=1e-9)
    assert len(t.spans["fedavg"]) == 1
    assert tr.device_s_in(t, "fedavg") == pytest.approx(0.000293626,
                                                        rel=1e-9)
    top = tr.top_ops(t, 3)
    assert [name for name, _ in top] == [
        "reduce", "constant_dynamic-slice_fusion", "copy"]
    assert [v for _, v in top] == pytest.approx(
        [0.001415099, 0.000402454, 0.000354036], rel=1e-9)
