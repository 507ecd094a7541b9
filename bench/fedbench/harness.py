"""One run of one cell: build, warm up, measure, trace, check, report.

``run.py`` looks for the chip and calls :func:`run`.  Everything the run
needs is found by the cell's name: its configuration and traffic files,
the per-layer metric readers and the model's plain reference
(``fedbench.spec``).
"""

from __future__ import annotations

import gc
import shutil
import sys
import tempfile
import time
from collections import defaultdict
from dataclasses import dataclass, field
from pathlib import Path
from typing import Any, Optional

import numpy as np

from fedbench import spec
from fedbench.probe import CompileClock, Probe

NS = 1_000_000_000
#: Host spans the trace reduction attributes idle time to, innermost first.
SPAN_ORDER = ("fedavg", "wire_decode_batch", "aggregate", "train",
              "wire_encode", "wire_decode")


@dataclass
class Window:
    """What one measured window did, for the per-layer readers."""

    config: dict
    peak: dict
    seconds: float                      # first round's start to last end
    rounds: list = field(default_factory=list)       # RoundResult
    round_spans: list = field(default_factory=list)  # (t0, t1) ns
    round_starts: list = field(default_factory=list)  # simulated ns
    spans: dict = field(default_factory=dict)        # name -> [(t0, t1)]
    events: int = 0
    flush_sizes: list = field(default_factory=list)
    compiles: int = 0
    decode_work: list = field(default_factory=list)
    fedavg_work: list = field(default_factory=list)
    trace: Any = None                   # fedbench.trace.Trace

    @property
    def n_aggs(self) -> int:
        return len(self.rounds)

    @property
    def updates(self) -> int:
        return sum(len(r.arrived) for r in self.rounds)


def fleet_configs(config: dict, traffic: dict, seed: int):
    """The program's ``FleetConfig`` and ``FLConfig`` for a cell."""
    from repro.core import FLConfig, FleetConfig, TransportConfig
    from repro.core.fleet import CohortSpec
    cohorts = {name: CohortSpec(name=name, **{
        k: tuple(v) if isinstance(v, list) else v for k, v in c.items()})
        for name, c in config["cohorts"].items()}
    fleet = FleetConfig(
        n_clients=config["n_clients"], seed=seed, cohorts=cohorts,
        cohort_mix=tuple((n, w) for n, w in config["cohort_mix"]),
        mode=traffic["mode"], buffer_k=traffic.get("buffer_k") or 8,
        round_deadline_ns=traffic.get("round_deadline_ns"),
        topology=config["topology"], engine=config["engine"],
        model=config["model"], model_args=config["model_args"],
        train_backend=config["train_backend"],
        uplink=traffic["uplink"], downlink=traffic["downlink"])
    fl = FLConfig(aggregation=config["aggregation"],
                  aggregation_backend=config["aggregation_backend"],
                  transport=TransportConfig(**config["transport"]))
    return fleet, fl


def build_fleet(config: dict, traffic: dict, seed: int):
    """The program's fleet for a cell, through its own entry
    (``build_fleet_training``), with the client profiles drawn by
    ``fedbench.cohorts``: the same set for every seed, in the seed's
    order."""
    from repro.core import build_fleet_training, fleet
    from fedbench import cohorts
    drawn = fleet.sample_profiles
    fleet.sample_profiles = cohorts.profiles
    try:
        return build_fleet_training(*fleet_configs(config, traffic, seed))
    finally:
        fleet.sample_profiles = drawn


def warm_up(fb, config: dict, traffic: dict) -> None:
    """Compile what the window will run: the uplink decode at every payload
    count of the mix's ``warmup_decode_items`` range (the decode kernels
    are jitted per count, and a sync round's count follows its arrivals)
    and every padded flush size of the batched trainer.  The mix's
    ``warmup_aggregations`` run just before the window opens, in the same
    loop (:func:`_window`)."""
    from repro.core import flatten_to_vector, wire
    n = config["n_clients"]
    lo, hi = traffic.get("warmup_decode_items") or (1, 0)
    if hi >= lo:
        vec = np.random.default_rng(0).standard_normal(
            config["n_params"]).astype(np.float32)
        data = wire.parse_pipeline(traffic["uplink"]).encode(vec)
        for k in range(lo, hi + 1):
            wire.decode_payload_batch([data] * k)
    if fb.trainer is not None:
        vec = flatten_to_vector(fb.system.global_params)
        k = 1
        while True:
            fb.trainer.backend.train(
                fb.model, np.tile(vec, (k, 1)),
                np.arange(k, dtype=np.int32) % n, np.zeros(k, np.int32))
            if k >= n:
                break
            k *= 2


def expected_contribs(r, mode: str) -> int:
    """Contributions an aggregation must fold, by the event layer's own
    account of it: under sync every arrival and every straggler folded
    late; under async the buffer, less the updates too stale to fold."""
    if mode == "async":
        return int(r.metrics["buffer_size"]) - int(r.metrics["stale_dropped"])
    return len(r.arrived) + int(r.late_folded)


def run(cell_name: str, seed: int, seconds: float, trace: bool, *,
        t_start: float, control: bool = False, fault: Optional[str] = None,
        keep_trace: Optional[str] = None, root: Path = spec.ROOT,
        bench_dir: Path = spec.BENCH_DIR) -> dict:
    """One run; returns the result line's object.  The process's JAX and
    wire settings are restored, and every wrapper removed, on the way
    out."""
    import jax
    from repro.core import wire

    bench = spec.load_benchmark(root)
    cell = spec.cell(bench, cell_name)
    config = spec.load_json("configs", cell["config"], bench_dir)
    traffic = spec.load_json("traffic", cell["traffic"], bench_dir)
    Reference = spec.reference(config["model"])
    prev = (jax.config.jax_default_matmul_precision, wire.batch_backend())
    clock = CompileClock()
    try:
        if config["precision"].get("matmul"):
            jax.config.update("jax_default_matmul_precision",
                              config["precision"]["matmul"])
        wire.set_batch_backend(config["wire_backend"])
        out, samples = _measure(bench, cell_name, config, traffic, seed,
                                seconds, trace, t_start, clock, Reference,
                                control, fault, keep_trace)
    finally:
        clock.close()
        jax.config.update("jax_default_matmul_precision", prev[0])
        wire.set_batch_backend(prev[1])
    gc.collect()
    checks = compare(Reference(config, seed), config, traffic, samples)
    out["correct"] = all(c["value"] is not None
                         and c["value"] <= c["limit"]
                         for c in checks.values())
    out["checks"] = checks
    return out


def _measure(bench, cell_name, config, traffic, seed, seconds, trace,
             t_start, clock, Reference, control, fault, keep_trace):
    import jax

    dev0 = jax.devices()[0]
    peak = spec.peaks(dev0.device_kind) if dev0.platform == "tpu" else {}
    fb = build_fleet(config, traffic, seed)
    swap = None
    if control:
        ref_low = Reference(config, seed)
        kw = {"losses": True} if "loss_gap" in config["limits"] else {}

        def swap(stack, client_idx, round_idx):
            return ref_low.train(stack, client_idx, round_idx,
                                 config["precision"]["control"], **kw)
    probe = Probe(fb, seed=seed, plan=traffic["check"], swap_train=swap,
                  fault=fault)
    try:
        probe.install()
        warm_up(fb, config, traffic)
        win, tdir, t_open = _window(fb, probe, clock, config, traffic, peak,
                                    seconds, trace)
    finally:
        probe.remove()
    setup_s = t_open - t_start
    stats = dev0.memory_stats() or {}
    device = {"platform": dev0.platform, "kind": dev0.device_kind,
              "count": len(jax.devices()),
              "memory_peak_bytes": int(stats.get("peak_bytes_in_use", 0))}
    mode = traffic["mode"]
    out: dict = {"correct": False,
                 "attempted": sum(len(r.roster) if mode == "sync"
                                  else len(r.arrived) + len(r.failed)
                                  for r in win.rounds),
                 "failed": sum(len(r.failed) for r in win.rounds)}
    metrics: dict = {}
    if trace:
        from fedbench import trace as tr
        win.trace = tr.load(tdir, SPAN_ORDER)
        if keep_trace:
            shutil.copytree(tdir, keep_trace, dirs_exist_ok=True)
        shutil.rmtree(tdir, ignore_errors=True)
        if dev0.platform == "tpu" and not any(win.trace.ops.values()):
            raise RuntimeError(
                "the trace holds no device operations: no /device:TPU:<n> "
                "plane with events (fedbench/trace.py)")
        device["busy_s"] = tr.busy_s(win.trace)
        device["window_s"] = tr.window_s(win.trace)
        for m in spec.per_layer(bench, cell_name):
            value = spec.reader(m["name"])(win)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        out["breakdown"] = {"device_ops": tr.top_ops(win.trace),
                            "idle_gaps": tr.idle_gaps(win.trace, SPAN_ORDER)}
    else:
        walls_ms = [(t1 - t0) / 1e6 for t0, t1 in win.round_spans]
        values = {"client_updates_per_s": win.updates / win.seconds,
                  "agg_wall_ms.p95": float(np.percentile(walls_ms, 95)),
                  "setup_s": setup_s}
        for m in spec.end_to_end(bench, cell_name):
            metrics[m["name"]] = {"value": values[m["name"]],
                                  "unit": m["unit"]}
    out["metrics"] = metrics
    out["device"] = device
    samples = {
        "train": probe.train_samples, "aggs": probe.agg_samples,
        "broadcasts": probe.broadcasts, "down": probe.down_samples,
        "transfers": probe.transfer_samples,
        "expected": [expected_contribs(r, mode) for r in win.rounds],
        "seen": probe.agg_counts,
        "events_off": probe.events_off + _rounds_off(
            fb, probe, win, traffic)}
    return out, samples


def _rounds_off(fb, probe, win, traffic) -> int:
    """The sync barrier of every window round against
    ``reference.events``; async aggregations have no barrier."""
    from reference import events
    if traffic["mode"] != "sync":
        return 0
    clients = [p.addr for p in fb.profiles]
    came = defaultdict(list)
    for addr, rnd, t in probe.deliveries:
        came[rnd].append((addr, t))
    off = 0
    for r, t0 in zip(win.rounds, win.round_starts):
        benched = set(r.skipped_unhealthy)
        off += events.sync_round_off(
            r.roster, [a for a in clients if a not in benched], r.arrived,
            r.failed, [(a, t - t0) for a, t in came[r.round_idx]],
            traffic.get("round_deadline_ns"), r.duration_ns)
    return off


def _window(fb, probe, clock, config, traffic, peak, seconds, trace):
    """The mix's ``warmup_aggregations``, then the measured window: whole
    aggregations back to back until ``seconds`` have passed.  Returns the
    window, the trace directory and the host clock (s) at which the window
    opened."""
    import jax
    from fedbench.trace import WINDOW
    win = Window(config=config, peak=peak, seconds=0.0)
    tdir = tempfile.mkdtemp(prefix="bench_trace_") if trace else None
    base: dict = {}

    def open_window() -> int:
        if trace:
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(tdir, profiler_options=opts)
        base.update(compiles=clock.count, events=fb.sim.events_processed,
                    flushes=(len(fb.trainer.batch_sizes)
                             if fb.trainer is not None else 0))
        base["mark"] = jax.profiler.TraceAnnotation(WINDOW)
        base["mark"].__enter__()
        probe.recording = True
        t = time.perf_counter_ns()
        base.update(open=t, deadline=t + int(seconds * NS))
        return t

    def close_window() -> None:
        if base.get("open") is None or base.get("closed"):
            return
        base["closed"] = True
        probe.recording = False
        base["mark"].__exit__(None, None, None)
        if trace:
            jax.profiler.stop_trace()

    try:
        if traffic["mode"] == "async":
            _drive_async(fb, int(traffic["warmup_aggregations"]), win,
                         open_window, close_window, base)
        else:
            _drive_sync(fb, int(traffic["warmup_aggregations"]), win,
                        open_window, base)
    finally:
        close_window()
    if not win.rounds:
        raise RuntimeError("the window closed without an aggregation")
    win.seconds = (win.round_spans[-1][1] - win.round_spans[0][0]) / NS
    win.compiles = clock.count - base["compiles"]
    win.events = fb.sim.events_processed - base["events"]
    if fb.trainer is not None:
        win.flush_sizes = list(fb.trainer.batch_sizes[base["flushes"]:])
    win.spans = dict(probe.spans)
    win.decode_work, win.fedavg_work = probe.decode_work, probe.fedavg_work
    return win, tdir, base["open"] / NS


def _drive_sync(fb, n_warm, win, open_window, base) -> None:
    """Sync rounds: ``run_round`` back to back; one call is one
    aggregation, and its wall time is the call's."""
    for _ in range(n_warm):
        fb.system.run_round()
    open_window()
    while True:
        win.round_starts.append(fb.sim.now_ns)
        t0 = time.perf_counter_ns()
        res = fb.system.run_round()
        t1 = time.perf_counter_ns()
        win.rounds.append(res)
        win.round_spans.append((t0, t1))
        if t1 >= base["deadline"]:
            return


def _drive_async(fb, n_warm, win, open_window, close_window, base) -> None:
    """FedBuff: one open ``run_rounds`` call, in which the server folds its
    buffer each time ``buffer_k`` updates are in, and clients cycle at
    their own cadence.  An aggregation's wall time runs from the end of
    the one before it.  Once ``seconds`` have passed, the scheduler is
    told that the current aggregation is its last, and the updates still
    in flight drain outside the window.  (Repeated ``run_round()`` calls
    would not do: each drains every session into the buffer, so the next
    call's first fold takes hundreds of updates, not ``buffer_k``.)"""
    sched = fb.system.scheduler
    if not hasattr(sched, "_target") or not hasattr(sched, "_agg_idx"):
        raise RuntimeError("the async scheduler no longer has the "
                           "_target/_agg_idx the window stops it by")
    seen = [0]
    prev = fb.system.on_round_end

    def on_aggregation(result, params) -> None:
        t = time.perf_counter_ns()
        if prev is not None:
            prev(result, params)
        seen[0] += 1
        if base.get("open") is None:
            if seen[0] >= n_warm:
                base["last"] = open_window()
            return
        if base.get("closed"):
            return
        win.rounds.append(result)
        win.round_spans.append((base["last"], t))
        base["last"] = t
        if t >= base["deadline"]:
            close_window()
            sched._target = sched._agg_idx + 1

    fb.system.on_round_end = on_aggregation
    try:
        fb.system.run_rounds(1 << 40)
    finally:
        fb.system.on_round_end = prev


def _gap(got, want, base) -> float:
    """||got - want|| over ||base||, with an all-zero base read as 1."""
    num = float(np.linalg.norm(np.asarray(got, np.float64)
                               - np.asarray(want, np.float64)))
    den = float(np.linalg.norm(np.asarray(base, np.float64)))
    return num / den if den > 0 else num


def _gap_sized(got, want, base) -> Optional[float]:
    """:func:`_gap`, or nothing where the sizes differ."""
    return _gap(got, want, base) if np.size(got) == np.size(want) else None


def compare(ref, config: dict, traffic: dict, samples: dict) -> dict:
    """Every number the check compares, beside its limit (the limits and
    the readings they were set from are in the configuration file and in
    ``PERF.md``)."""
    from reference import aggregate, wire as ref_wire
    limits = config["limits"]
    vals: dict[str, Optional[float]] = {}

    ups = samples["train"]
    with_loss = "loss_gap" in limits
    if ups:
        want = ref.train(np.stack([s["received"] for s in ups]),
                         np.array([s["client"] for s in ups]),
                         np.array([s["round"] for s in ups]),
                         config["precision"]["reference"],
                         **({"losses": True} if with_loss else {}))
        if with_loss:
            want, losses = want
            got = [s.get("loss") for s in ups]
            vals["loss_gap"] = (None if None in got else float(max(
                abs(g - w) for g, w in zip(got, losses))))
        vals["train_gap"] = max(
            _gap(s["trained"], w, w.astype(np.float64) - s["received"])
            for s, w in zip(ups, want))
    else:
        vals["train_gap"] = None
        if with_loss:
            vals["loss_gap"] = None

    enc: list[Optional[float]] = []
    for s in ups:
        if s.get("data") is None:
            enc.append(None)
            continue
        kept = ref_wire.topk_indices(s["data"])
        sent, res = ref_wire.encode(s["trained"], traffic["uplink"],
                                    ref=s["received"],
                                    residual=s["residual"], kept=kept)
        enc.append(_gap_sized(ref_wire.decode(s["data"]), sent, sent))
        if res is not None:
            after = s["residual_after"]
            enc.append(None if after is None
                       else _gap_sized(after, res, sent + res))
    for g, data in samples["down"]:
        sent, _ = ref_wire.encode(g, traffic["downlink"])
        enc.append(_gap_sized(ref_wire.decode(data), sent, sent))
    vals["encode_gap"] = (None if not enc or None in enc
                          else float(max(enc)))

    heard = {ref_wire.decode(b).tobytes() for b in samples["broadcasts"]}
    vals["received_off"] = (float(sum(
        np.asarray(s["received"], np.float32).tobytes() not in heard
        for s in ups)) if heard and ups else None)

    dec, agg = [], []
    for a in samples["aggs"]:
        n = a["g_old"].size
        rows = []
        for data, row in zip(a["datas"], a["rows"]):
            r = ref_wire.decode(data)[:n]
            r = np.pad(r, (0, n - r.size))
            rows.append(r)
            dec.append(_gap(row, r, r))
        want = aggregate.fold(a["g_old"], rows, a["weights"],
                              a["delta_domain"], a["server_lr"])
        agg.append(_gap(a["g_new"], want, want - a["g_old"]))
    vals["decode_gap"] = max(dec) if dec else None
    vals["agg_gap"] = max(agg) if agg else None

    expected, seen = samples["expected"], samples["seen"]
    missing = abs(len(expected) - len(seen)) * max(expected + [1])
    missing += sum(abs(e - s) for e, s in zip(expected, seen))
    vals["rows_missing"] = float(missing)

    from reference import events
    off = samples["events_off"]
    for data, packets, mtu in samples["transfers"]:
        off += events.packets_off(data, packets, mtu)
    vals["events_off"] = float(off)
    return {k: {"value": v, "limit": limits[k]} for k, v in vals.items()}


def print_checks(checks: dict) -> None:
    for name, c in checks.items():
        print(f"check {name} {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr, flush=True)
