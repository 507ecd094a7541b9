"""The ``lm_mla`` cell's two readers on hand-built windows, and its
reference's operation count against the closed form at the
configuration's sizes."""

import json
from pathlib import Path

import pytest

from fedbench import harness, spec
from fedbench.trace import WINDOW, Trace
from repro.core import RoundResult

CONFIG = json.loads((Path(spec.BENCH_DIR) / "configs"
                     / "moonlight_lora_silo8.json").read_text())
PEAK = spec.peaks("TPU v5 lite")


def result(counters):
    return RoundResult(0, 0, [], [], [], 0, 0, 0, 0, 0, spans={},
                       counters=counters)


def window(counters, flush_sizes=(), trace=None, peak=None, config=CONFIG):
    win = harness.Window(config=config, peak=peak or {}, seconds=1.0)
    win.rounds = [result(c) for c in counters]
    win.flush_sizes = list(flush_sizes)
    win.trace = trace
    return win


def test_expert_imbalance_reads_the_routed_expert_count():
    read = spec.reader("lm_mla_expert_imbalance")
    win = window([{"moe.rows": 49_152, "moe.rows_max": 2_000},
                  {"moe.rows": 49_152, "moe.rows_max": 2_400}])
    assert read(win) == pytest.approx(64 * 4_400 / 98_304)
    assert read(window([{"train.rows": 8}])) is None     # no lm model
    assert "num_experts" not in CONFIG                   # not the lm's key


def test_train_roofline_is_least_time_over_device_time_in_train():
    read = spec.reader("lm_mla_train_roofline")
    ref = spec.reference("lm_mla")
    flops = ref.flops_per_update(CONFIG)
    trace = Trace(ops={0: [("fusion.1", 0.0, 6e9), ("fusion.2", 7e9, 9e9)]})
    trace.spans[WINDOW].append((0.0, 10e9))
    trace.spans["train"].extend([(0.0, 6.5e9), (6.5e9, 10e9)])
    win = window([{}], flush_sizes=[8, 8], trace=trace, peak=PEAK)
    least = max(2 * 8 * flops / PEAK["bf16_flops_per_s"],
                2 * 2 * ref.base_bytes(CONFIG) / PEAK["hbm_bytes_per_s"])
    assert flops * 16 / PEAK["bf16_flops_per_s"] > least / 2   # compute-bound
    assert read(win) == pytest.approx(100 * least / 8.0)
    assert read(window([{}], flush_sizes=[8])) is None    # no trace


def test_flops_per_update_is_the_closed_form():
    """Per position: the frozen products (MLA's four projections, the
    dense SwiGLU of layer 0, the router, 6 of 64 experts and the 2 shared
    ones of layers 1-5, the head) forward and once more for their input
    gradients; the attention scores (192 dims a head) and values (128)
    three times; the adapters three times.  4,096 positions, 2 steps."""
    d, H, T, r, V = 2048, 16, 4096, 16, 163840
    io = [(d, H * 192), (d, 512 + 64), (512, H * 256), (H * 128, d)]
    proj = sum(2 * i * o for i, o in io)
    lora = sum(2 * r * (i + o) for i, o in io)
    assert lora == 2 * 263_168
    dense = 6 * d * 11264
    moe = 2 * d * 64 + 6 * 6 * d * 1408 + 6 * d * 2816
    keys = T * (T + 1) // 2
    scores = 6 * 2 * H * (192 + 128) * keys
    forward = 6 * proj + dense + 5 * moe + 2 * d * V       # a position
    assert forward == pytest.approx(1.668e9, rel=1e-3)
    step = 2 * T * forward + 3 * scores + 3 * T * 6 * lora
    assert spec.reference("lm_mla").flops_per_update(CONFIG) == 2 * step
    assert 2 * step == pytest.approx(30.5e12, rel=1e-2)


def test_base_bytes_are_the_published_widths_at_six_layers():
    """The embedding and head 1.342 GB, the dense layer 83.0M parameters,
    each MoE layer 584.8M (64 experts held whole), in bfloat16; the
    selection bias float32."""
    ref = spec.reference("lm_mla")
    emb = 2 * 163840 * 2048
    attn = 2 * 2048 + 2048 * 3072 + 2048 * 576 + 512 + 512 * 4096 \
        + 2048 * 2048
    dense = attn + 3 * 2048 * 11264
    moe = attn + 2048 * 64 + 64 * 3 * 2048 * 1408 + 3 * 2048 * 2816
    assert dense == 82_973_184 and moe == 584_847_872
    params = emb + 2048 + dense + 5 * (moe + 64)
    assert params == CONFIG["base_params"] == 3_678_303_552
    assert ref.base_bytes(CONFIG) == 2 * params + 2 * 5 * 64
