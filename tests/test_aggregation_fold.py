"""The server's delta-domain fold (``ServerCore.apply_aggregation``) over
the rows the batched uplink decode produced.

* the fold gives ``global_params`` with the same bytes as
  ``sum(w * v for ...) / ws.sum()`` over the decoded rows, sign of zero
  included, with a zero-weight contribution and a late-buffer vector
  mixed into the pending ones;
* ``decode_vec_batch`` hands on the decode's own rows (views of its
  matrix), materialising only a degraded row, and the fold only reads
  its contributions;
* ``aggregate.rows`` and ``aggregate.row_copies`` land in a round's
  ``RoundResult.counters``.
"""

from __future__ import annotations

import numpy as np
import pytest

from repro.core import aggregation as agg
from repro.core import server, unflatten_from_vector
from repro.core.rounds import FLConfig, TransportConfig
from repro.core.server import _PendingWire

from test_wire_batch import _star

N_PARAMS = 300


def _core(spec: str):
    _, system = _star(1, FLConfig(transport=TransportConfig(uplink=spec)),
                      n_params=N_PARAMS)
    core = system.core
    g = np.linspace(-1, 1, N_PARAMS, dtype=np.float32)
    g[:8] = -0.0        # where every row is a zero, the fold's sign shows
    core.global_params = {"w": g}
    return core


def _rows(k: int, seed: int) -> list[np.ndarray]:
    rng = np.random.default_rng(seed)
    rows = [rng.standard_normal(N_PARAMS).astype(np.float32)
            for _ in range(k)]
    for r in rows:
        r[:8] = -0.0
    return rows


def _payloads(core, rows: list[np.ndarray]) -> list[bytes]:
    pipeline = core.uplink_pipeline
    return [pipeline.encode(r, pipeline.new_state()) for r in rows]


def _sum_fold(core, vecs: list[np.ndarray], weights: list[float]) -> dict:
    """The fold as a Python ``sum`` of weighted rows over the weight sum."""
    keep = [(v, w) for v, w in zip(vecs, weights) if w > 0.0]
    ws = np.asarray([w for _, w in keep], dtype=np.float32)
    mean = sum(w * v for (v, _), w in zip(keep, ws)) / ws.sum()
    template = core.global_params
    return agg.apply_delta(
        template, unflatten_from_vector(mean.astype(np.float32), template),
        core.cfg.server_lr)


class TestFoldBitIdentity:
    @pytest.mark.parametrize("spec", ["delta|raw", "delta|topk(0.1)|int8(64)"])
    @pytest.mark.parametrize("k", [1, 10, 225])
    def test_fold_matches_python_sum(self, spec, k):
        core = _core(spec)
        datas = _payloads(core, _rows(k, seed=k))
        late = _rows(1, seed=1000 + k)[0]
        weights = [0.5 + (i % 7) * 0.25 for i in range(k)]
        # Pending rows, then a zero-weight one and a late-buffer vector.
        contribs = ([(_PendingWire(d), w) for d, w in zip(datas, weights)]
                    + [(_PendingWire(datas[0]), 0.0), (late, 0.375)])
        vecs = ([core.decode_vec(d) for d in datas]
                + [core.decode_vec(datas[0]), late])
        want = _sum_fold(core, vecs, weights + [0.0, 0.375])
        core.apply_aggregation(contribs)
        got = core.global_params
        assert got["w"].dtype == want["w"].dtype == np.float32
        assert got["w"].tobytes() == want["w"].tobytes()
        if spec == "delta|raw":
            # -0.0 rows over a -0.0 template: the fold starts from +0.0.
            assert not np.signbit(got["w"][:8]).any()


class TestRowsInPlace:
    def test_uniform_batch_rows_are_views_of_the_decode(self, monkeypatch):
        core = _core("delta|topk(0.1)|int8(64)")
        datas = _payloads(core, _rows(6, seed=2))
        seen = []

        def recording(datas):
            out = decode(datas)
            seen.extend(vec for vec, _, _ in out)
            return out
        decode = server.wire_decode_payload_batch
        monkeypatch.setattr(server, "wire_decode_payload_batch", recording)
        rows = core.decode_vec_batch(datas)
        mat = seen[0].base
        assert mat is not None and all(v.base is mat for v in seen)
        for row, vec in zip(rows, seen):
            assert row.shape == (N_PARAMS,) and row.dtype == np.float32
            assert np.shares_memory(row, mat)
            np.testing.assert_array_equal(row, vec[:N_PARAMS])

    def test_degraded_row_is_its_own_zero_array(self):
        core = _core("delta|raw")
        datas = _payloads(core, _rows(4, seed=3))
        datas[2] = b"\x00\x01 not a wire payload at all"
        rows = core.decode_vec_batch(datas)
        assert core.decode_errors == 1
        assert rows[2].shape == (N_PARAMS,) and not rows[2].any()
        assert rows[2].flags.writeable
        assert not any(np.shares_memory(rows[2], rows[i])
                       for i in (0, 1, 3))

    def test_fold_only_reads_its_contributions(self):
        core = _core("delta|raw")
        rows = _rows(5, seed=4)
        for r in rows:
            r.setflags(write=False)
        before = [r.copy() for r in rows]
        want = _sum_fold(core, rows, [1.0, 2.0, 0.5, 1.5, 1.0])
        core.apply_aggregation(list(zip(rows, [1.0, 2.0, 0.5, 1.5, 1.0])))
        assert core.global_params["w"].tobytes() == want["w"].tobytes()
        for r, b in zip(rows, before):
            assert r.tobytes() == b.tobytes()


class TestFoldCounters:
    N_CLIENTS = 3

    def _round(self, monkeypatch, corrupt: bool):
        _, system = _star(self.N_CLIENTS, FLConfig(
            transport=TransportConfig(uplink="delta|int8(128)")))
        if corrupt:
            decode = server.wire_decode_payload_batch

            def corrupting(datas):
                return decode([b"\x00\x01 garbage"] + list(datas[1:]))
            monkeypatch.setattr(server, "wire_decode_payload_batch",
                                corrupting)
        return system.run_round()

    @pytest.mark.parametrize("corrupt", [False, True])
    def test_round_counts_rows_and_copies(self, monkeypatch, corrupt):
        result = self._round(monkeypatch, corrupt)
        assert len(result.arrived) == self.N_CLIENTS
        assert result.counters["aggregate.rows"] == self.N_CLIENTS
        assert result.counters.get("aggregate.row_copies", 0) == int(corrupt)
        assert result.decode_errors == int(corrupt)
