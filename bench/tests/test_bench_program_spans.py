"""The readers of the program's own spans and counters
(``RoundResult.spans`` / ``.counters``): values on hand-built windows,
nothing where a span or counter is absent, and every one of them read
from a traced run of the harness."""

import json
import shutil
import time
from pathlib import Path
from types import SimpleNamespace

import pytest

from fedbench import harness, spec
from repro.core import RoundResult

DATA = Path(__file__).resolve().parent / "data"
MS = 1_000_000
PROGRAM = ("engine_unspanned_ms_per_agg", "flight_ms_per_agg",
           "packet_ms_per_agg", "packets_built_per_agg",
           "train_host_ms_per_agg", "train_step_ms_per_agg",
           "train_pad_share", "host_device_mb_per_agg",
           "wire_kernel_ms_per_agg")


def result(spans=None, counters=None):
    return RoundResult(0, 0, [], [], [], 0, 0, 0, 0, 0, spans=spans,
                       counters=counters)


def window(rounds, walls_ms):
    win = harness.Window(config={}, peak={}, seconds=1.0)
    t = 0
    for r, ms in zip(rounds, walls_ms):
        win.rounds.append(r)
        win.round_spans.append((t, t + ms * MS))
        t += ms * MS
    return win


#: Two aggregations of 100 and 60 ms; spans as (count, total_ns, self_ns).
VMAP = window([
    result({"engine.burst": (20, 4 * MS, 4 * MS),
            "engine.flight_pass": (90, 6 * MS, 6 * MS),
            "packet.build": (20, 8 * MS, 8 * MS),
            "packet.reassemble": (20, 2 * MS, 2 * MS),
            "train.flush": (1, 30 * MS, 10 * MS),
            "train.step": (1, 20 * MS, 20 * MS),
            "aggregate": (1, 9 * MS, 4 * MS),
            "wire.decode_batch": (1, 5 * MS, 2 * MS),
            "wire.kernel": (2, 3 * MS, 3 * MS)},
           {"packets.built": 1700, "train.rows": 48, "train.pad_rows": 16,
            "device.h2d_bytes": 30_000_000,
            "device.d2h_bytes": 20_000_000}),
    result({"engine.burst": (20, 2 * MS, 2 * MS),
            "packet.build": (20, 8 * MS, 8 * MS),
            "aggregate": (1, 9 * MS, 4 * MS),
            "wire.decode_batch": (1, 5 * MS, 2 * MS),
            "wire.kernel": (2, 3 * MS, 3 * MS)},
           {"packets.built": 1500, "device.h2d_bytes": 1_000_000,
            "device.d2h_bytes": 9_000_000}),
], [100, 60])


@pytest.mark.parametrize("metric,want", [
    # 160 ms of wall less 59 + 19 ms of self time, over two aggregations.
    ("engine_unspanned_ms_per_agg", (160 - 59 - 19) / 2),
    ("flight_ms_per_agg", (4 + 6 + 2) / 2),
    ("packet_ms_per_agg", (8 + 2 + 8) / 2),
    ("packets_built_per_agg", 1600.0),
    ("train_host_ms_per_agg", 10 / 2),
    ("train_step_ms_per_agg", 20 / 2),
    ("train_pad_share", 100.0 * 16 / 64),
    ("host_device_mb_per_agg", 60.0 / 2),
    ("wire_kernel_ms_per_agg", 6 / 2),
])
def test_reader_on_a_hand_built_window(metric, want):
    assert spec.reader(metric)(VMAP) == pytest.approx(want)


#: The ``python`` backend trains no batch: no flush, no step, no rows; the
#: numpy wire runs no kernel and copies nothing to a device.
PYTHON = window([result({"engine.burst": (3, MS, MS),
                         "packet.build": (3, MS, MS)},
                        {"packets.built": 30})], [10])


@pytest.mark.parametrize("metric", ["train_host_ms_per_agg",
                                    "train_step_ms_per_agg",
                                    "train_pad_share",
                                    "host_device_mb_per_agg",
                                    "wire_kernel_ms_per_agg"])
def test_reader_finds_nothing_where_its_span_is_absent(metric):
    assert spec.reader(metric)(PYTHON) is None


@pytest.mark.parametrize("metric", PROGRAM)
def test_reader_finds_nothing_in_a_program_without_spans(metric):
    # A program whose RoundResult has no spans or counters at all.
    win = window([SimpleNamespace(arrived=["a"])], [10])
    assert spec.reader(metric)(win) is None


def test_every_program_metric_reads_in_a_traced_run(tmp_path):
    shutil.copytree(DATA, tmp_path, dirs_exist_ok=True)
    bench = json.loads((DATA / "BENCHMARK.json").read_text())
    main = spec.load_benchmark()
    bench["per_layer"] += [dict(m, workloads=["tiny_mlp.sync_topk_int8"])
                           for m in main["per_layer"]
                           if m["name"] in PROGRAM]
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    out = harness.run("tiny_mlp.sync_topk_int8", 9, 1.0, True,
                      t_start=time.perf_counter(), root=tmp_path,
                      bench_dir=tmp_path)
    assert out["correct"], out["checks"]
    m = out["metrics"]
    assert set(PROGRAM) <= set(m)
    assert 0 < m["engine_unspanned_ms_per_agg"]["value"]
    assert m["packets_built_per_agg"]["value"] > 0
    assert 0 <= m["train_pad_share"]["value"] < 100
