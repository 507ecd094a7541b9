"""Whole runs of the harness at test size on the CPU.

``harness.run`` is everything ``bench/run.py`` does after its look for a
chip, so these drive the real path: the program's fleet, the spans, the
window, the trace reduction and the comparison with the plain reference.
The faults are planted under the timed path, and each must turn
``correct`` false; so must the control, the reference computed one
precision lower in the place of the program's training.
"""

import json
import os
import shutil
import subprocess
import sys
import time
from pathlib import Path

import pytest

from fedbench import harness
from fedbench.probe import FAULTS

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
DATA = BENCH / "tests" / "data"
MLP, SILO = "tiny_mlp.sync_topk_int8", "tiny_silo.sync_int8"
ASYNC = "tiny_mlp.async_buf3"


def run(cell, seed=3, seconds=1.0, trace=False, root=DATA, **kw):
    return harness.run(cell, seed, seconds, trace, t_start=time.perf_counter(),
                       root=root, bench_dir=root, **kw)


def test_last_line_keys_and_a_correct_run():
    out = run(MLP)
    assert list(out) == ["correct", "attempted", "failed", "metrics",
                         "device", "checks"]
    assert out["correct"], out["checks"]
    assert set(out["metrics"]) == {"client_updates_per_s", "setup_s"}
    assert out["metrics"]["client_updates_per_s"]["value"] > 0
    assert set(out["device"]) == {"platform", "kind", "count",
                                  "memory_peak_bytes"}
    for c in out["checks"].values():
        assert set(c) == {"value", "limit"}


def test_traced_run_reports_per_layer_metrics():
    out = run(SILO, seed=4, trace=True)
    assert out["correct"], out["checks"]
    assert {"busy_s", "window_s"} <= set(out["device"])
    assert set(out["breakdown"]) == {"device_ops", "idle_gaps"}
    m = out["metrics"]
    assert {"aggregate_ms_per_agg", "wire_ms_per_agg", "train_ms_per_agg",
            "sim_self_ms_per_agg", "sim_events_per_agg",
            "compiles_in_window"} <= set(m)
    # No device on the CPU: the readers of device time find nothing.
    assert "device_idle_share" not in m and "fedavg_roofline" not in m


def test_async_window_folds_buffer_k_updates():
    # One open run_rounds call: every aggregation in the window folds what
    # its buffer held (rows_missing is 0), and its wall time feeds the tail.
    out = run(ASYNC, seed=8, seconds=2.0)
    assert out["correct"], out["checks"]
    assert set(out["metrics"]) == {"client_updates_per_s",
                                   "agg_wall_ms.p95", "setup_s"}
    assert out["metrics"]["agg_wall_ms.p95"]["value"] > 0
    assert out["attempted"] >= 3 * 2


@pytest.mark.parametrize("cell,fault", [(MLP, f) for f in FAULTS]
                         + [(SILO, "state_unchanged"), (ASYNC, "half_batch")])
def test_planted_fault_is_not_correct(cell, fault):
    out = run(cell, seed=5, fault=fault)
    assert not out["correct"], out["checks"]


#: The number each configuration's control has to fail: a trained model
#: carries the float32 rounding of every weight, which on the chip swamps
#: a lower matmul precision, and the last step's loss does not.
CONTROL_CAUGHT_BY = {MLP: "loss_gap", SILO: "train_gap"}


@pytest.mark.parametrize("cell", [MLP, SILO])
def test_control_is_not_correct(cell):
    out = run(cell, seed=6, control=True)
    assert not out["correct"], out["checks"]
    number = out["checks"][CONTROL_CAUGHT_BY[cell]]
    assert number["value"] > number["limit"]


def test_mlp_run_compares_the_reported_loss():
    out = run(MLP, seed=12)
    assert out["correct"], out["checks"]
    assert out["checks"]["loss_gap"]["value"] is not None
    # The consensus step reports no loss, and its checks ask for none.
    assert "loss_gap" not in run(SILO, seed=12)["checks"]


def test_added_traffic_file_needs_no_harness_edit(tmp_path):
    shutil.copytree(DATA, tmp_path, dirs_exist_ok=True)
    mix = json.loads((DATA / "traffic" / "sync_topk_int8.json").read_text())
    mix["round_deadline_ns"] = 3_000_000_000
    (tmp_path / "traffic" / "sync_deadline3.json").write_text(json.dumps(mix))
    bench = json.loads((DATA / "BENCHMARK.json").read_text())
    bench["workloads"].append({"name": "tiny_mlp.sync_deadline3",
                               "config": "tiny_mlp",
                               "traffic": "sync_deadline3", "chips": 1,
                               "why": "an added mix"})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    out = run("tiny_mlp.sync_deadline3", seed=7, root=tmp_path)
    assert out["correct"], out["checks"]


def _cli(cwd, *extra):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload",
         "mlp256.sync_topk_int8", "--seed", "3000000001", "--seconds", "1",
         "--trace", "0", *extra], cwd=cwd, env=env, capture_output=True,
        text=True, timeout=300)


def test_run_fails_without_a_tpu():
    res = _cli(ROOT)
    assert res.returncode != 0
    assert "no TPU" in res.stderr
    assert "{" not in res.stdout


def test_run_fails_with_only_the_benchmark_files(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    res = _cli(tmp_path)
    assert res.returncode != 0
    assert "{" not in res.stdout
