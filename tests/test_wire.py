"""Wire-plane tests: composable pipelines, self-describing headers,
per-direction negotiation, and the codec regression/adversarial suite.

The acceptance bar for the redesign:

* a payload encoded under ANY registered pipeline spec decodes correctly
  from its WireHeader alone — no out-of-band config (negotiation);
* legacy single-codec pipelines are byte-identical to the historical
  ``repro.core.compression`` wire formats (the orchestrator-equivalence
  digests pin the end-to-end version of this);
* malformed/truncated payloads raise :class:`WireDecodeError` with a
  reason, and the server degrades them explicitly (zeros + counter);
* a full async fleet round runs with independently configured uplink and
  downlink pipelines, error-feedback state held in pipeline state.
"""

from __future__ import annotations

import warnings

import numpy as np
import pytest

from repro.core import wire
from repro.core.compression import (CODECS, Int8Codec, TopKCodec, make_codec)
from repro.core.rounds import (FederatedSystem, FLClient, FLConfig,
                               TransportConfig)
from repro.core.simulator import Simulator
from repro.core.wire import (DeltaStage, ErrorFeedbackStage, Pipeline,
                             WireDecodeError, WireError, WireHeader,
                             available_stages, decode_payload,
                             legacy_pipeline, parse_pipeline, parse_stage,
                             register_stage)

RNG = np.random.default_rng(7)


def vec(n: int = 4096) -> np.ndarray:
    return RNG.standard_normal(n).astype(np.float32)


# Specs chosen so every registered built-in stage appears at least once,
# alone where legal and composed where interesting.
NEGOTIATION_SPECS = [
    "raw",
    "hex",
    "int8(256)",
    "int8(1024)",
    "topk(0.05)",
    "delta|raw",
    "delta|ef|int8(128)",
    "topk(0.1)|int8(64)",
    "delta|ef|topk(0.01)|int8(1024)",
    "int8(128)|hex",
    "int8(256)|crc",
    "delta|ef|topk(0.05)|int8(512)|crc",
]


def test_negotiation_specs_cover_every_registered_stage():
    covered = set()
    for spec in NEGOTIATION_SPECS:
        for s in parse_pipeline(spec).stages:
            covered.add(s.name)
    assert covered == set(available_stages())


# --------------------------------------------------------------------------
# Parsing, registry, caps
# --------------------------------------------------------------------------
class TestSpecParsing:
    @pytest.mark.parametrize("spec", NEGOTIATION_SPECS)
    def test_canonical_spec_round_trips(self, spec):
        p = parse_pipeline(spec)
        assert parse_pipeline(p.spec).spec == p.spec

    def test_whitespace_tolerant(self):
        assert (parse_pipeline(" delta | ef | int8( 128 ) ").spec
                == "delta|ef|int8(128)")

    @pytest.mark.parametrize("bad", ["", "|", "zstd9", "topk(", "topk)x(",
                                     "topk(a)", "int8(0)", "topk(0)",
                                     "topk(1.5)", "delta|ef"])
    def test_bad_specs_raise(self, bad):
        with pytest.raises(WireError):
            parse_pipeline(bad)

    def test_duplicate_registration_refused(self):
        with pytest.raises(WireError, match="already registered"):
            register_stage("raw", wire.RawStage)

    def test_caps_derivation(self):
        p = parse_pipeline("delta|ef|topk(0.01)|int8(1024)")
        assert not p.caps.lossless          # topk+int8 are lossy
        assert p.caps.stateful              # delta + ef carry state
        assert p.caps.delta_domain
        assert p.caps.est_ratio == pytest.approx(
            2 * 0.01 * (0.25 + 1 / 1024), rel=1e-6)
        q = parse_pipeline("hex")
        assert q.caps.lossless and not q.caps.stateful
        assert q.caps.est_ratio == 2.0

    def test_ef_cannot_terminate(self):
        with pytest.raises(WireError, match="terminal"):
            Pipeline([DeltaStage(), ErrorFeedbackStage()])

    @pytest.mark.parametrize("spec", ["ef|delta|raw", "ef|delta|int8(64)"])
    def test_ef_cannot_wrap_delta(self, spec):
        # residual would become comp - (comp - ref) = ref: the whole
        # reference model re-injected every message.
        with pytest.raises(WireError, match="wrap delta"):
            parse_pipeline(spec)
        with pytest.raises(ValueError, match="wrap delta"):
            TransportConfig(uplink=spec)

    @pytest.mark.parametrize("spec", ["topk(0.01)|ef|int8(64)",
                                      "int8(64)|ef|raw"])
    def test_ef_cannot_follow_a_remapping_stage(self, spec):
        # after topk/int8 the coordinates are per-message: last round's
        # residual would be added onto this round's different positions.
        with pytest.raises(WireError, match="remapping"):
            parse_pipeline(spec)

    def test_third_party_delta_stage_declares_the_capability(self):
        class MyDelta(DeltaStage):
            name = "mydelta"
        p = Pipeline([MyDelta(), wire.RawStage()])
        assert p.caps.delta_domain
        st = p.new_state()
        p.set_reference(st, vec(8))       # attribute-driven, not isinstance
        assert "ref" in st.slots[0]


# --------------------------------------------------------------------------
# The header
# --------------------------------------------------------------------------
class TestWireHeader:
    def test_pack_unpack(self):
        h = WireHeader("delta|int8(64)", [b"", b"abc"], 1)
        packed = h.pack()
        h2, off = WireHeader.unpack(packed + b"BODY")
        assert (h2.spec, h2.stage_params, h2.dtype_code) == \
            ("delta|int8(64)", [b"", b"abc"], 1)
        assert off == len(packed)

    @pytest.mark.parametrize("mutate", [
        lambda d: b"",                               # empty
        lambda d: d[:4],                             # truncated header
        lambda d: b"XX" + d[2:],                     # bad magic
        lambda d: d[:2] + b"\x63" + d[3:],           # unknown version
        lambda d: d[:2] + b"\x00" + d[3:],           # version 0
        lambda d: d[:-3],                            # truncated params
    ])
    def test_malformed_headers_raise_decode_error(self, mutate):
        data = WireHeader("int8(64)", [b"p"], 0).pack()
        with pytest.raises(WireDecodeError):
            WireHeader.unpack(mutate(data))

    def test_truncated_body_raises(self):
        p = parse_pipeline("int8(64)")
        data = p.encode(vec(300))
        with pytest.raises(WireDecodeError):
            decode_payload(data[:-17])

    @pytest.mark.parametrize("n", [2 ** 40, (1 << 28) + 1, 0xFFFFFFFF])
    def test_topk_giant_n_rejected_before_allocating(self, n):
        """A forged topk header must never size an allocation from its
        wire-supplied n (17 GiB at the u32 limit, 4 TiB at 2**40)."""
        import struct
        params = struct.pack("!Q", n)                # n huge, no indices
        h = WireHeader("topk(0.5)", [params], 0).pack()
        with pytest.raises(WireDecodeError, match="MAX_DECODE_PARAMS"):
            decode_payload(h)
        legacy = struct.pack("!Q", n) + struct.pack("!I", 0)
        with pytest.raises(ValueError, match="MAX_DECODE_PARAMS"):
            TopKCodec().decode(legacy)

    def test_negotiation_memo_is_size_capped(self):
        from repro.core.wire import _NEGOTIATED, _NEGOTIATED_CAP
        for block in range(1, _NEGOTIATED_CAP + 50):
            spec = f"int8({block})"
            q = parse_pipeline(spec)
            decode_payload(q.encode(vec(16)))
        assert len(_NEGOTIATED) <= _NEGOTIATED_CAP

    def test_unregistered_stage_in_header_raises(self):
        h = WireHeader("lzma", [b""], 0).pack() + vec(4).tobytes()
        with pytest.raises(WireDecodeError, match="unknown stage"):
            decode_payload(h)

    @pytest.mark.parametrize("spec", ["int8(inf)", "int8(nan)", "raw(1)",
                                      "topk(0.1,0.2)"])
    def test_hostile_stage_args_in_header_degrade_not_crash(self, spec):
        """A wire-controlled spec whose stage constructor blows up
        (OverflowError, TypeError, ...) must still surface as
        WireDecodeError — the server's explicit-degradation contract."""
        h = WireHeader(spec, [b""], 0).pack() + vec(4).tobytes()
        with pytest.raises(WireDecodeError):
            decode_payload(h)


# --------------------------------------------------------------------------
# Wire negotiation: decode from the header alone
# --------------------------------------------------------------------------
class TestNegotiation:
    @pytest.mark.parametrize("spec", NEGOTIATION_SPECS)
    @pytest.mark.parametrize("n", [0, 1, 255, 4096])
    def test_decodes_from_header_alone(self, spec, n):
        p = parse_pipeline(spec)
        v = vec(n)
        data = p.encode(v, p.new_state())
        out, negotiated = decode_payload(data)     # zero out-of-band config
        assert negotiated.spec == p.spec
        assert out.dtype == np.float32 and out.size == v.size
        if p.caps.lossless:
            np.testing.assert_array_equal(out, v)
        else:
            # Lossy pipelines must agree with their own out-of-band decode.
            np.testing.assert_array_equal(out, p.decode(data, p.new_state()))

    @pytest.mark.parametrize("spec", ["raw", "delta|raw", "int8(64)"])
    def test_decoded_vector_is_writable(self, spec):
        # The legacy codec contract returns writable arrays; a headered
        # raw decode must not hand back a read-only wire-buffer view.
        p = parse_pipeline(spec)
        out, _ = decode_payload(p.encode(vec(32), p.new_state()))
        assert out.flags.writeable
        out += 1.0   # must not raise

    def test_receiver_config_is_ignored(self):
        """The sender's header wins even when the receiver was configured
        with a different pipeline — that is the negotiation."""
        sender = parse_pipeline("int8(128)")
        data = sender.encode(vec(500))
        out, negotiated = decode_payload(data)
        assert negotiated.spec == "int8(128)"
        receiver = parse_pipeline("hex")
        with pytest.raises(WireDecodeError, match="names pipeline"):
            receiver.decode(data)   # strict decode refuses a foreign header

    def test_topk_int8_composition_quantizes_only_values(self):
        v = vec(2000)
        p = parse_pipeline("topk(0.05)|int8(50)")
        out, _ = decode_payload(p.encode(v))
        k = int(2000 * 0.05)
        assert np.count_nonzero(out) <= k
        kept = np.argsort(-np.abs(v))[:k]
        np.testing.assert_allclose(out[kept], v[kept], rtol=0.02, atol=1e-4)


# --------------------------------------------------------------------------
# Encode determinism: the server's broadcast cache relies on it
# --------------------------------------------------------------------------
# The four legacy codecs as single-stage pipelines plus the compositions
# the FL layer ships.
DETERMINISM_SPECS = [
    "raw",
    "hex",
    "int8(1024)",
    "topk(0.01)",
    "delta|ef|int8(1024)",
    "delta|ef|topk(0.01)|int8(1024)",
    "topk(0.01)|int8(256)",
]


@pytest.mark.parametrize("spec", DETERMINISM_SPECS)
def test_encode_identical_across_fresh_pipelines(spec):
    """Two independently constructed pipelines, each on fresh state, put
    the same bytes on the wire; and a header-only decode equals the
    out-of-band one (``test_decodes_from_header_alone`` holds that for
    the specs it shares)."""
    v = np.random.default_rng(0).standard_normal(250_000).astype(np.float32)
    datas = []
    for _ in range(2):
        p = parse_pipeline(spec)
        state = p.new_state()
        if p.caps.delta_domain:
            p.set_reference(state, np.zeros_like(v))
        datas.append(p.encode(v, state))
    assert datas[0] == datas[1]
    if spec not in NEGOTIATION_SPECS:
        negotiated, _ = decode_payload(datas[0])
        oob = p.decode(datas[0], p.new_state())
        assert negotiated.tobytes() == oob.tobytes()


# --------------------------------------------------------------------------
# Legacy bit-identity
# --------------------------------------------------------------------------
class TestLegacyMode:
    @pytest.mark.parametrize("name", sorted(CODECS))
    @pytest.mark.parametrize("n", [0, 1, 3, 1000, 1025])
    def test_headerless_bytes_identical_to_codec(self, name, n):
        codec = make_codec(name)
        p = legacy_pipeline(name)
        v = vec(n)
        assert p.encode(v, p.new_state()) == codec.encode(v)

    def test_legacy_ef_matches_historical_contract(self):
        """legacy [ef][int8]: residual == compensated - codec.decode(bytes),
        compounding across calls exactly like the old ErrorFeedback."""
        codec = Int8Codec()
        p = legacy_pipeline("int8", error_feedback=True)
        st = p.new_state()
        residual = None
        for _ in range(4):
            v = vec(3000)
            comp = v if residual is None else v + residual
            expect = codec.encode(comp)
            assert p.encode(v, st) == expect
            residual = comp - codec.decode(expect)
            np.testing.assert_array_equal(st.slots[0]["residual"], residual)

    def test_legacy_ef_skipped_for_lossless_codec(self):
        p = legacy_pipeline("raw", error_feedback=True)
        assert p.spec == "raw"      # no ef stage: nothing to feed back

    def test_legacy_conflicting_codec_args_refused(self):
        # "int8(512)" already names a block; a contradicting codec_kwargs
        # must raise, not silently win or lose.
        assert legacy_pipeline("int8(512)").stages[-1].block == 512
        with pytest.raises(WireError, match="ambiguous"):
            legacy_pipeline("int8(512)", {"block": 1024})

    def test_mid_pipeline_params_refuse_legacy(self):
        p = Pipeline([wire.TopKStage(0.1), wire.Int8Stage(64)],
                     self_describing=False)
        with pytest.raises(WireError, match="legacy"):
            p.encode(vec(100))


# --------------------------------------------------------------------------
# Stage state: delta + error feedback
# --------------------------------------------------------------------------
class TestStages:
    def test_delta_uses_primed_reference(self):
        p = parse_pipeline("delta|raw")
        st = p.new_state()
        ref, v = vec(64), vec(64)
        p.set_reference(st, ref)
        out, _ = decode_payload(p.encode(v, st))
        np.testing.assert_array_equal(out, v - ref)   # decode stays in delta domain

    def test_delta_unprimed_is_delta_against_zero(self):
        p = parse_pipeline("delta|raw")
        v = vec(32)
        out, _ = decode_payload(p.encode(v, p.new_state()))
        np.testing.assert_array_equal(out, v)

    def test_delta_reference_size_mismatch_raises(self):
        p = parse_pipeline("delta|raw")
        st = p.new_state()
        p.set_reference(st, vec(8))
        with pytest.raises(WireError, match="reference"):
            p.encode(vec(16), st)

    def test_error_feedback_reduces_accumulated_error(self):
        """EF's whole point: over repeated sends of the same signal, the
        accumulated decoded sum tracks the true sum better than without
        (the residual rotates through the coordinates top-k keeps
        dropping)."""
        rounds = 40
        v = vec(4000) * 0.1
        with_ef = parse_pipeline("ef|topk(0.05)")
        without = parse_pipeline("topk(0.05)")
        st = with_ef.new_state()
        got_ef = np.zeros_like(v)
        got_plain = np.zeros_like(v)
        for _ in range(rounds):
            got_ef += decode_payload(with_ef.encode(v, st))[0]
            got_plain += decode_payload(without.encode(v))[0]
        err_ef = np.linalg.norm(got_ef - rounds * v)
        err_plain = np.linalg.norm(got_plain - rounds * v)
        assert err_ef < 0.25 * err_plain

    def test_state_slot_count_is_checked(self):
        p = parse_pipeline("delta|raw")
        with pytest.raises(WireError, match="slots"):
            p.encode(vec(8), parse_pipeline("raw").new_state())


# --------------------------------------------------------------------------
# Satellite: TopK empty/small-vector regression, across every codec
# --------------------------------------------------------------------------
class TestSmallVectorRegression:
    @pytest.mark.parametrize("name", sorted(CODECS))
    @pytest.mark.parametrize("n", [0, 1, 2])
    def test_encode_decode_empty_and_tiny(self, name, n):
        """TopKCodec used to pack k=1 for an empty vector while writing
        zero entries, so decode read past the buffer; every codec must
        round-trip n in {0, 1, 2}."""
        codec = make_codec(name)
        v = np.linspace(-1, 1, n, dtype=np.float32)
        out = codec.decode(codec.encode(v))
        assert out.size == n
        if codec.lossless:
            np.testing.assert_array_equal(out, v)

    def test_topk_header_k_clamped_to_entries(self):
        data = TopKCodec(k_fraction=0.01).encode(np.zeros(0, np.float32))
        import struct
        n = struct.unpack_from("!Q", data, 0)[0]
        k = struct.unpack_from("!I", data, 8)[0]
        assert (n, k) == (0, 0)
        assert len(data) == 12              # header only, no phantom entry

    @pytest.mark.parametrize("n", [1, 5, 49])
    def test_topk_size_smaller_than_k(self, n):
        codec = TopKCodec(k_fraction=1.0)   # requests k = n
        v = vec(n)
        np.testing.assert_array_equal(codec.decode(codec.encode(v)), v)

    @pytest.mark.parametrize("spec", ["topk(0.5)", "topk(0.5)|int8(8)"])
    def test_topk_stage_empty_vector(self, spec):
        p = parse_pipeline(spec)
        out, _ = decode_payload(p.encode(np.zeros(0, np.float32)))
        assert out.size == 0


# --------------------------------------------------------------------------
# Satellite: adversarial codec round-trips
# --------------------------------------------------------------------------
ADVERSARIAL = {
    "nan_inf": np.array([np.nan, np.inf, -np.inf, 0.0, 1.0, -1.0],
                        dtype=np.float32),
    "denormal": np.array([1e-42, -1e-42, np.float32(1.4e-45), 0.0],
                         dtype=np.float32),
    "huge_tiny": np.array([3.4e38, -3.4e38, 1e-38, -1e-38],
                          dtype=np.float32),
    "off_block": RNG.standard_normal(1023).astype(np.float32),
    "block_plus_one": RNG.standard_normal(1025).astype(np.float32),
}


class TestAdversarialVectors:
    @pytest.mark.parametrize("name", sorted(CODECS))
    @pytest.mark.parametrize("case", sorted(ADVERSARIAL))
    def test_round_trip_shape_and_bits(self, name, case):
        codec = make_codec(name)
        v = ADVERSARIAL[case]
        with warnings.catch_warnings():
            warnings.simplefilter("ignore", RuntimeWarning)
            out = codec.decode(codec.encode(v))
        assert out.dtype == np.float32 and out.size == v.size
        if codec.lossless:
            # Bit-exact, including NaN payloads and denormals.
            assert out.tobytes() == v.tobytes()

    @pytest.mark.parametrize("case", ["off_block", "block_plus_one"])
    def test_int8_non_multiple_block_lengths(self, case):
        codec = Int8Codec(block=256)
        v = ADVERSARIAL[case]
        out = codec.decode(codec.encode(v))
        assert out.size == v.size
        np.testing.assert_allclose(out, v, atol=np.abs(v).max() / 100)

    @pytest.mark.parametrize("case", sorted(ADVERSARIAL))
    def test_hex_raw_cross_consistency(self, case):
        """hex is exactly hexlify(raw): decoding one through the other's
        lens must agree bit-for-bit."""
        import binascii
        v = ADVERSARIAL[case]
        raw, hexc = make_codec("raw"), make_codec("hex")
        assert binascii.hexlify(raw.encode(v)) == hexc.encode(v)
        assert raw.decode(binascii.unhexlify(hexc.encode(v))).tobytes() \
            == v.tobytes()
        assert hexc.decode(binascii.hexlify(raw.encode(v))).tobytes() \
            == v.tobytes()


# --------------------------------------------------------------------------
# Orchestrator integration
# --------------------------------------------------------------------------
SERVER = "10.9.0.1"


def _star(n_clients, cfg, train_value=2.0, n_params=300):
    from repro.core.channel import Link, NoLoss
    sim = Simulator()
    clients = []
    for i in range(n_clients):
        addr = f"10.9.0.{10 + i}"
        sim.connect(addr, SERVER,
                    Link(1e8, 1_000_000, NoLoss()),
                    Link(1e8, 1_000_000, NoLoss()))

        def fn(params, round_idx, client, _v=train_value * (i + 1)):
            return ({k: np.full_like(v, _v) for k, v in params.items()}, {})
        clients.append(FLClient(addr, fn, train_time_ns=1_000_000 * (i + 1)))
    params = {"w": np.linspace(-1, 1, n_params, dtype=np.float32)}
    return sim, FederatedSystem(sim, SERVER, clients, params, cfg)


class TestServerWirePlane:
    def test_config_rejects_legacy_flags_with_uplink_spec(self):
        with pytest.raises(ValueError, match="legacy spellings"):
            FLConfig(send_deltas=True,
                     transport=TransportConfig(uplink="delta|raw"))

    def test_config_rejects_delta_downlink(self):
        with pytest.raises(ValueError, match="downlink"):
            TransportConfig(downlink="delta|int8(64)")

    def test_config_rejects_unknown_stage_early(self):
        with pytest.raises(ValueError, match="uplink"):
            TransportConfig(uplink="gzip|raw")

    @pytest.mark.parametrize("spec", ["int8(64)|raw", "hex|int8(64)",
                                      "raw|topk(0.1)|hex|int8(64)"])
    def test_config_rejects_incoherent_stage_order_early(self, spec):
        """Parseable but dtype-incoherent specs must fail at config time,
        not by silently zero-degrading every payload at runtime."""
        with pytest.raises(ValueError, match="round-trip"):
            TransportConfig(uplink=spec)

    def test_malformed_uplink_degrades_explicitly(self):
        # Self-describing uplink: garbage has no valid header -> explicit
        # WireDecodeError -> zero vector + counter (never a bare except).
        _, system = _star(1, FLConfig(
            transport=TransportConfig(uplink="raw")))
        core = system.core
        before = core.decode_errors
        out = core.decode_vec(b"\x13\x37 garbage that is not a payload")
        assert out.size == core.n_params and not out.any()
        assert core.decode_errors == before + 1

    def test_delta_domain_mismatch_degrades_not_misaggregates(self):
        """A sender whose header negotiates a different delta-ness than
        the server's configured uplink must be refused (zero-fill), never
        aggregated under the wrong semantics."""
        _, system = _star(1, FLConfig(
            transport=TransportConfig(uplink="int8(128)")))
        core = system.core
        rogue = parse_pipeline("delta|int8(128)")
        data = rogue.encode(vec(core.n_params), rogue.new_state())
        out = core.decode_vec(data)
        assert not out.any() and core.decode_errors == 1
        # matching delta-ness still decodes
        ok = parse_pipeline("int8(128)")
        assert core.decode_vec(ok.encode(vec(core.n_params))).any()
        assert core.decode_errors == 1

    def test_packetizer_rejects_codec_and_pipeline_together(self):
        from repro.core.compression import Int8Codec
        from repro.core.packetizer import Packetizer
        with pytest.raises(WireError, match="not both"):
            Packetizer(codec=Int8Codec(), pipeline=parse_pipeline("raw"))

    def test_wire_bytes_measurement_does_not_advance_ef_state(self):
        from repro.core.packetizer import Packetizer
        p = parse_pipeline("ef|int8(64)")
        pz = Packetizer(pipeline=p)
        st = p.new_state()
        tree = {"w": vec(500)}
        pz.wire_bytes(tree, st)                  # measurement only
        assert "residual" not in st.slots[0]     # live state untouched
        real = p.encode(vec(500), st)            # first REAL send
        fresh = p.encode(vec(500), p.new_state())
        assert len(real) == len(fresh)

    def test_removed_client_wire_state_is_forgotten(self):
        """A client re-added at a recycled address must not inherit the
        dead client's EF residual / delta reference."""
        cfg = FLConfig(error_feedback=True,
                       transport=TransportConfig(codec="int8"))
        _, system = _star(2, cfg)
        system.run_round()
        addr = "10.9.0.10"
        assert addr in system.core._up_enc_state     # residual accrued
        system.remove_client(addr)
        assert addr not in system.core._up_enc_state

    def test_malformed_legacy_payload_degrades_explicitly(self):
        _, system = _star(1, FLConfig(
            transport=TransportConfig(codec="int8")))
        core = system.core
        out = core.decode_vec(b"\x00\x01")   # truncated int8 header
        assert out.size == core.n_params and not out.any()
        assert core.decode_errors == 1

    def test_n_params_cache_invalidated_on_assignment(self):
        _, system = _star(1, FLConfig())
        core = system.core
        assert core.n_params == 300
        core.global_params = {"w": np.zeros(5, np.float32)}
        assert core.n_params == 5

    def test_wire_state_none_for_stateless_pipeline(self):
        _, system = _star(1, FLConfig())
        assert system.core.wire_state("10.9.0.10",
                                      direction="uplink") is None

    def test_sync_round_self_describing_both_directions(self):
        cfg = FLConfig(transport=TransportConfig(
            uplink="delta|ef|int8(128)", downlink="hex"))
        _, system = _star(3, cfg)
        res = system.run_round()
        assert len(res.arrived) == 3
        core = system.core
        assert core.uplink_pipeline.caps.delta_domain
        # Wire really is self-describing: the broadcast + update payloads
        # carry headers, so bytes grow vs the raw legacy wire.
        _, legacy = _star(3, FLConfig())
        legacy_res = legacy.run_round()
        assert res.bytes_sent != legacy_res.bytes_sent

    def test_async_fleet_round_with_per_direction_pipelines(self):
        """Acceptance: a full async (FedBuff) fleet round with independent
        uplink/downlink pipelines; EF state lives in per-client pipeline
        state on the core, not in ServerCore fields or FLClient."""
        from repro.core import ConsensusObjective, FleetConfig, build_fleet
        obj = ConsensusObjective(16, n_params=256, seed=3)
        fleet = FleetConfig(
            n_clients=16, seed=3, mode="async", buffer_k=4,
            uplink="delta|ef|topk(0.2)|int8(128)", downlink="int8(128)")
        cfg = FLConfig(transport=TransportConfig(kind="mudp"), mode="async")
        _, system, _ = build_fleet(fleet, obj.init_params(), obj.train_fn,
                                   cfg)
        loss0 = obj.loss(system.global_params)
        results = system.run_rounds(3)
        assert len(results) == 3
        assert obj.loss(system.global_params) < loss0
        core = system.core
        assert core.uplink_pipeline.spec == "delta|ef|topk(0.2)|int8(128)"
        assert core.downlink_pipeline.spec == "int8(128)"
        # error-feedback residual + delta reference are pipeline state
        states = core._up_enc_state
        assert states, "stateful uplink must have per-client states"
        assert any("residual" in s for st in states.values()
                   for s in st.slots)
        assert any("ref" in s for st in states.values() for s in st.slots)
        assert not hasattr(next(iter(core.pool.clients.values())),
                           "error_feedback")

    def test_legacy_round_matches_headered_round_numerically(self):
        """Same lossless transform, different wire: a raw legacy system
        and a self-describing raw system converge to identical floats
        (only the wire framing differs)."""
        _, legacy = _star(3, FLConfig())
        _, headered = _star(3, FLConfig(transport=TransportConfig(
            uplink="raw", downlink="raw")))
        r1 = legacy.run_round()
        r2 = headered.run_round()
        assert r1.arrived == r2.arrived
        np.testing.assert_array_equal(legacy.global_params["w"],
                                      headered.global_params["w"])
