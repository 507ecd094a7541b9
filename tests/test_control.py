"""Adaptive transport control plane: telemetry, policies, renegotiation.

Four contracts are gated here:

1. **Pure add-on** — ``control="static"`` (set explicitly, not just
   defaulted) leaves every pinned orchestrator-equivalence digest
   byte-identical, and the always-on telemetry plane cannot move them.
2. **Engine-independence** — telemetry snapshots are bit-identical under
   the ``per_packet`` and ``batched`` engines (the flow engine's
   distributional version lives in tests/test_flow_engine.py).
3. **Renegotiation mechanics** — the crc wire stage, encoder
   state-migration rules, decision dedup, and the adaptive ladder.
4. **The ladder pays** — on a mixed cohort, adaptive control reaches the
   target loss in less simulated time than any one tier pinned
   fleet-wide.
"""

from __future__ import annotations

import os
import sys

import numpy as np
import pytest

from repro.core import (FLConfig, FleetConfig, TransportConfig,
                        build_fleet_training)
from repro.core.control import (AdaptivePolicy, ControlDecision,
                                DEFAULT_TIERS, StaticPolicy,
                                available_policies, make_policy,
                                register_policy)
from repro.core.telemetry import ClientHealth, Telemetry
from repro.core.wire import (CrcStage, WireDecodeError, WireError,
                             chunksum32, migrate_state, parse_pipeline)

sys.path.insert(0, os.path.dirname(__file__))
from test_orchestrator_equivalence import (EXPECTED,            # noqa: E402
                                           PACKET_ENGINES, run_digest)

NS = 1_000_000_000

UP_SPEC = "delta|ef|topk(0.15)|int8(1024)"


def _build(engine: str, control: str = "static", *, n_clients: int = 10,
           seed: int = 3, rounds: int = 3, mode: str = "sync",
           transport: str = "mudp+fec"):
    fl = FLConfig(transport=TransportConfig(
        kind=transport, uplink=UP_SPEC, downlink="int8(1024)",
        timeout_ns=2 * NS, udp_deadline_ns=3 * NS))
    fleet = FleetConfig(n_clients=n_clients, seed=seed, engine=engine,
                        mode=mode, model="consensus",
                        model_args={"n_params": 256}, control=control)
    build = build_fleet_training(fleet, fl)
    build.system.run_rounds(rounds)
    return build


# --------------------------------------------------------------------------
# 1. Pure add-on: explicit control="static" keeps every pinned digest
# --------------------------------------------------------------------------
@pytest.mark.parametrize("scenario,kind", sorted(EXPECTED), ids=str)
def test_static_control_keeps_pinned_digests(scenario, kind):
    for engine in PACKET_ENGINES:
        assert run_digest(scenario, kind, engine,
                          control="static") == EXPECTED[(scenario, kind)], (
            f"{scenario}/{kind}/{engine}: control='static' moved a pinned "
            f"digest — the control plane is not a pure add-on")


def test_unknown_policy_rejected_at_config_time():
    with pytest.raises(ValueError, match="unknown control policy"):
        FLConfig(control="nope")
    with pytest.raises(ValueError, match="unknown control policy"):
        FleetConfig(n_clients=2, control="nope")


def test_policy_registry_idiom():
    assert available_policies() == ["adaptive", "static"]
    with pytest.raises(ValueError, match="already registered"):
        register_policy("static", StaticPolicy)
    register_policy("static", StaticPolicy, overwrite=True)
    assert isinstance(make_policy("static"), StaticPolicy)
    with pytest.raises(ValueError, match="unknown control policy"):
        make_policy("definitely-not-registered")


# --------------------------------------------------------------------------
# 2. Telemetry: engine-independent, deterministic, always on
# --------------------------------------------------------------------------
def test_telemetry_bit_identical_per_packet_vs_batched():
    snaps = {}
    for engine in PACKET_ENGINES:
        b = _build(engine)
        snaps[engine] = b.system.core.telemetry.snapshot_all()
    assert snaps["per_packet"] == snaps["batched"]
    assert snaps["batched"], "telemetry plane observed nothing"
    for health in snaps["batched"].values():
        assert health.txns > 0
        assert health.rtt_ns > 0
        assert health.goodput_bps > 0


def test_round_result_exports_health_and_counters():
    b = _build("batched")
    last = b.system.history[-1]
    assert set(last.client_health) == {p.addr for p in b.profiles}
    assert all(isinstance(h, ClientHealth)
               for h in last.client_health.values())
    assert last.decode_errors == 0
    # Stateless downlink + unrenegotiated clients: the broadcast encode is
    # computed once and served from cache for the rest of the roster.
    assert last.bcast_cache_hits > 0


def test_telemetry_ewma_math():
    t = Telemetry(alpha=0.5)
    t.observe_txn("a", now_ns=10, duration_ns=100, data_sent=10,
                  retransmissions=2, payload_bytes=1000)
    h = t.snapshot("a")
    # First observation initializes the EWMA directly.
    assert h.loss_rate == pytest.approx(0.2)
    assert h.rtt_ns == pytest.approx(100.0)
    t.observe_txn("a", now_ns=20, duration_ns=200, data_sent=10,
                  retransmissions=0, payload_bytes=1000)
    h = t.snapshot("a")
    assert h.loss_rate == pytest.approx(0.5 * 0.0 + 0.5 * 0.2)
    assert h.rtt_ns == pytest.approx(0.5 * 200 + 0.5 * 100)
    assert h.txns == 2 and h.failures == 0
    t.observe_decode_error("a", now_ns=30)
    assert t.snapshot("a").decode_errors == 1
    assert t.snapshot("missing") is None
    t.forget("a")
    assert t.snapshot("a") is None


def test_failed_txn_counts_as_failure_with_zero_goodput():
    t = Telemetry()
    t.observe_txn("a", now_ns=5, duration_ns=100, data_sent=4,
                  retransmissions=4, payload_bytes=400, completed=False)
    h = t.snapshot("a")
    assert h.failures == 1 and h.txns == 1
    assert h.goodput_bps == 0.0
    assert h.loss_rate == pytest.approx(1.0)


# --------------------------------------------------------------------------
# 3a. The crc wire stage (repro.kernels.checksum on the wire)
# --------------------------------------------------------------------------
def test_crc_stage_roundtrip_and_corruption():
    p = parse_pipeline("int8(1024)|crc")
    arr = np.linspace(-1.0, 1.0, 257, dtype=np.float32)
    payload = p.encode(arr, p.new_state())
    out = p.decode(payload, p.new_state())
    assert np.allclose(out, arr, atol=1e-2)
    for flip in (len(payload) - 1, len(payload) // 2):
        bad = bytearray(payload)
        bad[flip] ^= 0x40
        with pytest.raises(WireDecodeError, match="crc mismatch"):
            p.decode(bytes(bad), p.new_state())


def test_crc_must_be_terminal():
    with pytest.raises(WireError, match="terminal"):
        parse_pipeline("crc|int8(1024)")
    parse_pipeline("crc")   # a lone crc is trivially terminal


def test_crc_batch_matches_scalar():
    stage = CrcStage()
    rng = np.random.default_rng(0)
    mat = rng.standard_normal((5, 33)).astype(np.float32)
    _, params = stage.encode_batch(mat, [{} for _ in range(5)])
    for row, param in zip(mat, params):
        _, scalar = stage.encode(row, {})
        assert scalar == param
    dec = stage.decode_batch(mat, list(params), [{} for _ in range(5)])
    assert np.array_equal(dec, mat)
    with pytest.raises(WireDecodeError, match="crc mismatch"):
        corrupt = mat.copy()
        corrupt[3, 0] += 1.0
        stage.decode_batch(corrupt, list(params), [{} for _ in range(5)])


def test_chunksum32_matches_reference_kernel():
    jax = pytest.importorskip("jax")          # noqa: F841  (ref.py needs it)
    from repro.kernels.checksum.ref import chunksum32_np
    rng = np.random.default_rng(7)
    for n in (0, 1, 8190, 8191, 8192, 20000):
        data = rng.integers(0, 256, size=n, dtype=np.uint8).tobytes()
        assert chunksum32(data) == int(chunksum32_np(
            np.frombuffer(data, dtype=np.uint8)))


# --------------------------------------------------------------------------
# 3b. Encoder state migration across a pipeline swap
# --------------------------------------------------------------------------
def test_migrate_state_carries_ef_residual_and_delta_ref():
    old = parse_pipeline("delta|ef|topk(0.2)|int8(1024)")
    new = parse_pipeline("delta|ef|topk(0.05)|int8(1024)")
    state = old.new_state()
    ref = np.ones(16, dtype=np.float32)
    old.set_reference(state, ref)
    old.encode(np.linspace(0, 1, 16, dtype=np.float32), state)
    old_residual = next(s["residual"] for s in state.slots if "residual" in s)
    assert old_residual is not None

    migrated = migrate_state(old, state, new)
    assert migrated is not None
    carried_ref = next(s["ref"] for s in migrated.slots if "ref" in s)
    carried_res = next(s["residual"] for s in migrated.slots
                      if "residual" in s)
    np.testing.assert_array_equal(carried_ref, ref)
    np.testing.assert_array_equal(carried_res, old_residual)


def test_migrate_state_to_stateless_pipeline_is_none():
    old = parse_pipeline("delta|int8(1024)")
    new = parse_pipeline("int8(1024)")
    state = old.new_state()
    old.set_reference(state, np.zeros(8, dtype=np.float32))
    assert migrate_state(old, state, new) is None


def test_migrate_state_without_old_state_is_fresh():
    new = parse_pipeline("delta|int8(1024)")
    migrated = migrate_state(parse_pipeline("int8(1024)"), None, new)
    assert migrated is not None and len(migrated.slots) == 2


# --------------------------------------------------------------------------
# 3c. The adaptive ladder and server-side renegotiation
# --------------------------------------------------------------------------
def _health(addr="c", loss=0.0, txns=5):
    return ClientHealth(addr=addr, txns=txns, loss_rate=loss)


def test_adaptive_policy_walks_the_ladder_with_hysteresis():
    pol = AdaptivePolicy(hi=0.03, lo=0.008, start_tier=1)
    cfg = TransportConfig(kind="mudp+fec", uplink=UP_SPEC)
    assert pol.renegotiate("c", None, cfg) is None          # no telemetry yet
    pol.renegotiate("c", _health(loss=0.10), cfg)
    assert pol.tier_of("c") == 2                            # escalate
    pol.renegotiate("c", _health(loss=0.02), cfg)
    assert pol.tier_of("c") == 2                            # hysteresis hold
    pol.renegotiate("c", _health(loss=0.001), cfg)
    assert pol.tier_of("c") == 1                            # relax
    pol.renegotiate("c", _health(loss=0.0), cfg)
    assert pol.tier_of("c") == 0                            # floor next
    pol.renegotiate("c", _health(loss=0.0), cfg)
    assert pol.tier_of("c") == 0
    d = pol.renegotiate("c", _health(loss=0.0), cfg)
    assert d.uplink == DEFAULT_TIERS[0]["uplink"]
    assert d.fec_parity == 0


def test_adaptive_policy_validates_args():
    with pytest.raises(ValueError, match="at least one tier"):
        AdaptivePolicy(tiers=())
    with pytest.raises(ValueError, match="unknown transport fields"):
        AdaptivePolicy(tiers=({"uplink": "raw", "mtu": 100},))
    with pytest.raises(ValueError, match="lo <= hi"):
        AdaptivePolicy(hi=0.01, lo=0.05)
    with pytest.raises(ValueError, match="start_tier"):
        AdaptivePolicy(start_tier=9)


def test_apply_decision_dedupes_and_counts():
    b = _build("batched", rounds=1)
    core = b.system.core
    addr = b.profiles[0].addr
    decision = ControlDecision(uplink=DEFAULT_TIERS[0]["uplink"],
                               fec_block=16, fec_parity=0)
    assert core._apply_decision(addr, decision) is True
    assert core.renegotiations[addr] == 1
    cfg = core.transport_cfg_for(addr)
    assert cfg.uplink == DEFAULT_TIERS[0]["uplink"]
    assert cfg.fec_parity == 0
    # Identical decision again: nothing changes, nothing is counted.
    assert core._apply_decision(addr, decision) is False
    assert core.renegotiations[addr] == 1
    # Other clients keep the base config.
    other = b.profiles[1].addr
    assert core.transport_cfg_for(other).uplink == UP_SPEC


def test_renegotiated_uplink_cannot_flip_aggregation_domain():
    b = _build("batched", rounds=1)
    core = b.system.core
    addr = b.profiles[0].addr
    with pytest.raises(ValueError, match="domain"):
        core._apply_decision(addr,
                             ControlDecision(uplink="topk(0.1)|int8(1024)"))


def test_adaptive_requires_self_describing_uplink():
    # FleetConfig.control is forwarded onto the FLConfig by the topology,
    # so a legacy-codec uplink must be rejected at ServerCore construction.
    fl = FLConfig(transport=TransportConfig(kind="mudp", codec="int8"))
    fleet = FleetConfig(n_clients=2, seed=0, model="consensus",
                        model_args={"n_params": 64}, control="adaptive")
    with pytest.raises(ValueError, match="self-describing"):
        build_fleet_training(fleet, fl)


def test_adaptive_fleet_renegotiates_and_converges():
    b = _build("batched", control="adaptive", n_clients=16, rounds=4)
    core = b.system.core
    assert sum(core.renegotiations.values()) > 0
    # Every client's health reaches the round result.
    last = b.system.history[-1]
    assert set(last.client_health) == {p.addr for p in b.profiles}
    assert b.model.loss(b.system.global_params) < 0.5


def _sim_ns_to_target(control, tier):
    """Simulated time at which 32 mixed-cohort clients over ``mudp+fec``
    first bring the loss to 2% of its initial value within 16 rounds, or
    None.  ``tier`` indexes ``DEFAULT_TIERS``: the static arm pins it
    fleet-wide; the adaptive arm starts every client on it."""
    t = DEFAULT_TIERS[tier]
    fl = FLConfig(aggregation="fedavg", transport=TransportConfig(
        kind="mudp+fec", uplink=t["uplink"], downlink="int8(1024)",
        fec_block=t["fec_block"], fec_parity=t["fec_parity"],
        timeout_ns=2 * NS, udp_deadline_ns=3 * NS))
    fleet = FleetConfig(n_clients=32, seed=0, model="consensus",
                        model_args={"n_params": 2048},
                        round_deadline_ns=20 * NS, control=control)
    b = build_fleet_training(fleet, fl)
    target = 0.02 * b.model.loss(b.system.global_params)
    crossed = []

    def on_round(res, params):
        if not crossed and b.model.loss(params) <= target:
            crossed.append(b.sim.now_ns)
    b.system.on_round_end = on_round
    b.system.run_rounds(16)
    return crossed[0] if crossed else None


@pytest.fixture(scope="module")
def adaptive_sim_ns():
    return _sim_ns_to_target("adaptive", 1)


@pytest.mark.parametrize("tier", [0, 1, 2], ids=["tier0", "tier1", "tier2"])
def test_adaptive_reaches_target_before_static_tier(adaptive_sim_ns, tier):
    """No single static tier fits a mixed fiber/lte/congested-edge cohort:
    adaptive control, starting from the middle rung, reaches the target
    in strictly less simulated time than each tier pinned fleet-wide (a
    tier that never reaches it loses).  Its other half, static control
    leaving the digests alone, is test_static_control_keeps_pinned_digests."""
    static = _sim_ns_to_target("static", tier)
    assert adaptive_sim_ns is not None
    assert static is None or adaptive_sim_ns < static, (adaptive_sim_ns,
                                                        static)


def test_adaptive_identical_across_packet_engines():
    results = {}
    for engine in PACKET_ENGINES:
        b = _build(engine, control="adaptive", n_clients=8, rounds=3)
        results[engine] = (
            dict(b.system.core.renegotiations),
            b.system.core.telemetry.snapshot_all(),
            {k: v.tolist() for k, v in b.system.global_params.items()},
        )
    assert results["per_packet"] == results["batched"]


# --------------------------------------------------------------------------
# 3d. FEC parity 0 (the trailer-less tier) and config validation
# --------------------------------------------------------------------------
def test_transport_config_validates_fec_geometry():
    with pytest.raises(ValueError, match="fec_block"):
        TransportConfig(kind="mudp+fec", fec_block=0)
    with pytest.raises(ValueError, match="fec_parity"):
        TransportConfig(kind="mudp+fec", fec_parity=-1)
    TransportConfig(kind="mudp+fec", fec_parity=0)   # valid: no trailer


@pytest.mark.parametrize("engine", [*PACKET_ENGINES, "flow"])
def test_fec_parity_zero_runs_every_engine(engine):
    fl = FLConfig(transport=TransportConfig(
        kind="mudp+fec", fec_parity=0, timeout_ns=2 * NS,
        udp_deadline_ns=3 * NS))
    fleet = FleetConfig(n_clients=6, seed=1, engine=engine,
                        model="consensus", model_args={"n_params": 128})
    b = build_fleet_training(fleet, fl)
    res = b.system.run_rounds(2)
    assert len(res) == 2
    assert all(r.parity_packets == 0 for r in res)


def test_fec_parity_zero_matches_plain_mudp_on_packet_engines():
    """With no trailer, mudp+fec must behave exactly like mudp."""
    out = {}
    for kind, parity in (("mudp", 1), ("mudp+fec", 0)):
        fl = FLConfig(transport=TransportConfig(
            kind=kind, fec_parity=parity, timeout_ns=2 * NS,
            udp_deadline_ns=3 * NS))
        fleet = FleetConfig(n_clients=6, seed=2, engine="batched",
                            model="consensus", model_args={"n_params": 128})
        b = build_fleet_training(fleet, fl)
        b.system.run_rounds(2)
        out[kind] = ({k: v.tolist()
                      for k, v in b.system.global_params.items()},
                     b.sim.now_ns)
    assert out["mudp"] == out["mudp+fec"]
