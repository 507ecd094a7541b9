"""``flight_visits_per_agg``: the event engine's ``engine.flight_visits``
counter per aggregation, on hand-built windows, nothing from a program
without the counter, and read from a traced async run, where one open
``run`` holds every aggregation."""

import json
import shutil
import time
from pathlib import Path
from types import SimpleNamespace

import pytest

from fedbench import harness, spec
from repro.core import RoundResult

DATA = Path(__file__).resolve().parent / "data"
METRIC = "flight_visits_per_agg"


def window(rounds):
    win = harness.Window(config={}, peak={}, seconds=1.0)
    for k, r in enumerate(rounds):
        win.rounds.append(r)
        win.round_spans.append((k, k + 1))
    return win


def result(counters):
    return RoundResult(0, 0, [], [], [], 0, 0, 0, 0, 0, counters=counters)


@pytest.mark.parametrize("counters,want", [
    ([{"engine.flight_visits": 120}, {"engine.flight_visits": 80}], 100.0),
    # An aggregation whose window held no pass still counts as one.
    ([{"engine.flight_visits": 90}, {"packets.built": 7}], 45.0),
])
def test_reader_on_a_hand_built_window(counters, want):
    win = window([result(c) for c in counters])
    assert spec.reader(METRIC)(win) == pytest.approx(want)


@pytest.mark.parametrize("rounds", [
    [result({"packets.built": 30})],
    [SimpleNamespace(arrived=["a"])],       # a RoundResult with no counters
])
def test_reader_finds_nothing_without_the_counter(rounds):
    assert spec.reader(METRIC)(window(rounds)) is None


def test_reads_in_a_traced_async_run(tmp_path):
    shutil.copytree(DATA, tmp_path, dirs_exist_ok=True)
    bench = json.loads((DATA / "BENCHMARK.json").read_text())
    main = {m["name"]: m for m in spec.load_benchmark()["per_layer"]}
    bench["per_layer"].append(dict(main[METRIC],
                                   workloads=["tiny_mlp.async_buf3"]))
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    out = harness.run("tiny_mlp.async_buf3", 9, 1.0, True,
                      t_start=time.perf_counter(), root=tmp_path,
                      bench_dir=tmp_path)
    assert out["correct"], out["checks"]
    assert out["metrics"][METRIC]["value"] >= 1
