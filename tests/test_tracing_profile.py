"""The program's spans share the profiler's clock: under ``jax.profiler``
every span a round records lands on the ``/host:CPU`` plane of the trace,
as often as ``RoundResult.spans`` counts it, and inside the round."""

import sys
from pathlib import Path

import jax

from repro.core import FLConfig, FleetConfig, TransportConfig, build_fleet

BENCH = Path(__file__).resolve().parents[1] / "bench"
if str(BENCH) not in sys.path:
    sys.path.insert(0, str(BENCH))

from fedbench import trace as tr  # noqa: E402

NS = 1_000_000_000
ROUND = "test_round"


def test_round_spans_are_on_the_host_plane(tmp_path):
    from repro.core import ConsensusObjective
    obj = ConsensusObjective(6, 6000, seed=2)
    fleet = FleetConfig(n_clients=6, seed=2, mode="sync", engine="batched",
                        round_deadline_ns=4 * NS,
                        uplink="delta|ef|topk(0.01)|int8(1024)",
                        downlink="int8(1024)")
    cfg = FLConfig(aggregation="fedavg",
                   transport=TransportConfig(kind="mudp", timeout_ns=2 * NS))
    _, system, _ = build_fleet(fleet, obj.init_params(), obj.train_fn, cfg)
    system.run_round()              # the first round outside the trace

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        with jax.profiler.TraceAnnotation(ROUND):
            result = system.run_round()
    finally:
        jax.profiler.stop_trace()

    assert result.spans
    trace = tr.load(str(tmp_path), list(result.spans) + [ROUND])
    (lo, hi), = trace.spans[ROUND]
    for name, (count, _, _) in result.spans.items():
        got = trace.spans.get(name, [])
        assert len(got) == count, name
        assert all(lo <= s and e <= hi for s, e in got), name
