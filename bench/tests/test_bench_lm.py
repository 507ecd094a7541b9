"""The ``lm`` cell's two readers on hand-built windows, and its reference's
operation count against the closed form at the configuration's sizes."""

import json
from pathlib import Path

import pytest

from fedbench import harness, spec
from fedbench.trace import WINDOW, Trace
from repro.core import RoundResult

CONFIG = json.loads((Path(spec.BENCH_DIR) / "configs"
                     / "mellum2_lora_silo8.json").read_text())
PEAK = spec.peaks("TPU v5 lite")


def result(counters):
    return RoundResult(0, 0, [], [], [], 0, 0, 0, 0, 0, spans={},
                       counters=counters)


def window(counters, flush_sizes=(), trace=None, peak=None):
    win = harness.Window(config=CONFIG, peak=peak or {}, seconds=1.0)
    win.rounds = [result(c) for c in counters]
    win.flush_sizes = list(flush_sizes)
    win.trace = trace
    return win


def test_expert_imbalance_reads_the_counters():
    read = spec.reader("moe_expert_imbalance")
    win = window([{"moe.rows": 64_000, "moe.rows_max": 1_500},
                  {"moe.rows": 64_000, "moe.rows_max": 1_700}])
    assert read(win) == pytest.approx(64 * 3_200 / 128_000)
    assert read(window([{"train.rows": 8}])) is None     # no lm model


def test_train_roofline_is_least_time_over_device_time_in_train():
    read = spec.reader("lm_train_roofline")
    ref = spec.reference("lm")
    flops = ref.flops_per_update(CONFIG)
    trace = Trace(ops={0: [("fusion.1", 0.0, 6e9), ("fusion.2", 7e9, 9e9)]})
    trace.spans[WINDOW].append((0.0, 10e9))
    trace.spans["train"].extend([(0.0, 6.5e9), (6.5e9, 10e9)])
    win = window([{}], flush_sizes=[8, 8], trace=trace, peak=PEAK)
    least = max(2 * 8 * flops / PEAK["bf16_flops_per_s"],
                2 * 2 * ref.base_bytes(CONFIG) / PEAK["hbm_bytes_per_s"])
    assert read(win) == pytest.approx(100 * least / 8.0)
    assert read(window([{}], flush_sizes=[8])) is None    # no trace
    win.config = dict(CONFIG, model="mlp")
    assert read(win) is None                              # no frozen base


def test_flops_per_update_is_the_closed_form():
    """Per position: the frozen products (projections, router, 8 of 64
    experts, the head) forward and once more for their input gradients;
    the attention scores and values three times (dQ, dK, dP, dV); the
    adapters three times.  8 layers, 4,096 positions, 2 local steps."""
    d, q, kv, r, T = 2304, 4096, 512, 16, 4096
    proj = 2 * d * q + 4 * d * kv + 2 * q * d
    moe = 2 * d * 64 + 8 * 3 * 2 * d * 896
    lora = 2 * (d * r + r * q) + 4 * (d * r + r * kv) + 2 * (q * r + r * d)
    window_keys = 1024 * 1025 // 2 + (T - 1024) * 1024
    full_keys = T * (T + 1) // 2
    scores = 4 * q * (6 * window_keys + 2 * full_keys)
    step = 2 * T * (8 * (proj + moe) + 2 * d * 98304) + 3 * scores \
        + 3 * T * 8 * lora
    assert spec.reference("lm").flops_per_update(CONFIG) == 2 * step
    assert 2 * step == pytest.approx(29.95e12, rel=1e-3)
