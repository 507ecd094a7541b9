"""Every file the benchmark names is found by its name, and
``BENCHMARK.json`` keeps to the shape the harness reads."""

import json
import re

import pytest

from fedbench import spec

BENCH = spec.load_benchmark()
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
CELLS = [w["name"] for w in BENCH["workloads"]]


def test_top_level_keys():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert BENCH["command"] == ["python3", "bench/run.py"]
    assert BENCH["paths"] == ["bench"]
    assert 1 <= BENCH["run_seconds"] <= 51


@pytest.mark.parametrize("entry", BENCH["configs"],
                         ids=lambda c: c["name"])
def test_config_file_loads_by_name(entry):
    config = spec.load_json("configs", entry["name"])
    assert entry["file"] == f"bench/configs/{entry['name']}.json"
    assert config["name"] == entry["name"]
    assert config["reduced"] == entry["reduced"]
    # Every configuration's checks, and the last step's loss where its
    # model reports one.
    assert set(config["limits"]) - {"loss_gap"} == {
        "train_gap", "encode_gap", "received_off", "decode_gap", "agg_gap",
        "rows_missing", "events_off"}
    assert ("loss_gap" in config["limits"]) == (config["model"] == "mlp")
    ref = spec.reference(config["model"])
    assert callable(ref.train)


@pytest.mark.parametrize("cell", BENCH["workloads"], ids=lambda c: c["name"])
def test_cell_files_load_by_name(cell):
    assert NAME.match(cell["name"]) and cell["chips"] in (1, 4)
    assert cell["config"] in {c["name"] for c in BENCH["configs"]}
    traffic = spec.load_json("traffic", cell["traffic"])
    assert traffic["mode"] in ("sync", "async")
    assert traffic["warmup_aggregations"] >= 1
    assert 0 < len(cell["why"]) <= 200
    e2e = {m["name"] for m in spec.end_to_end(BENCH, cell["name"])}
    assert "setup_s" in e2e and len(e2e) >= 2
    assert spec.per_layer(BENCH, cell["name"])


@pytest.mark.parametrize("metric", BENCH["per_layer"],
                         ids=lambda m: m["name"])
def test_metric_reader_loads_by_name(metric):
    assert callable(spec.reader(metric["name"]))
    assert NAME.match(metric["name"]) and UNIT.match(metric["unit"])
    assert metric["moves"] in {m["name"] for m in BENCH["end_to_end"]}
    assert set(metric.get("workloads", CELLS)) <= set(CELLS)


def test_metric_names_are_unique():
    names = [m["name"] for m in BENCH["end_to_end"] + BENCH["per_layer"]]
    assert len(names) == len(set(names))
    assert all(m["bound"] <= 0.25 for m in BENCH["end_to_end"])


def test_peak_table_is_keyed_by_device_kind():
    peak = spec.peaks("TPU v5 lite")
    assert peak["bf16_flops_per_s"] == 197e12
    assert peak["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        spec.peaks("cpu")


def test_benchmark_file_is_small():
    assert len(json.dumps(BENCH)) < 64 * 1024
