"""Spans, counters and samples around the program's layer entry points.

The benchmark wraps these calls from its own files (the program has no
spans yet):

==========================  ===========================================
span                        wrapped call
==========================  ===========================================
``train``                   ``BatchTrainer.flush``, or each client's
                            ``train_fn`` on the ``python`` backend
``wire_encode``             ``wire.Pipeline.encode``
``wire_decode``             ``packetizer.Packetizer.decode_bytes``
                            (a client decoding its downlink)
``wire_decode_batch``       ``server.wire_decode_payload_batch``
``aggregate``               ``server.ServerCore.apply_aggregation``
``fedavg``                  ``aggregation.fedavg_stack``
==========================  ===========================================

Each wrapper takes the host clock and opens a
``jax.profiler.TraceAnnotation`` of the span's name.  Every wrapped call
returns numpy, so its device work ends inside its span.  A wrapper around
a function the program has renamed fails at install; a later ``benchmark``
change repoints it.

Beneath the spans sit the samples the correctness check reads, drawn from
the seed:

* client updates (``ServerCore.uplink_update``): the model the client
  trained from, the model it trained, and the payload it encoded with the
  error-feedback residual before and after;
* broadcasts (``ServerCore.broadcast_payload``): every downlink payload,
  and for some the global model it was encoded from;
* transfers (``server.packetize``): every transfer's packet count, and for
  some the packets themselves;
* deliveries (the scheduler's ``on_uplink``): when each update reached the
  server, and whether its bytes are the ones its client sent;
* whole aggregations: the global model before and after, the payloads and
  the rows they decoded to, the weights.

Beneath those, for the control and the planted faults, the timed path
itself can be replaced.
"""

from __future__ import annotations

import time
from collections import defaultdict
from typing import Any, Callable, Optional

import numpy as np

FAULTS = ("state_unchanged", "half_batch", "altered_answer",
          "altered_encode")


class CompileClock:
    """Counts XLA compiles as ``jax.monitoring`` reports them.  The event
    wraps ``compile_or_get_cached``, so a load from the persistent cache
    counts too."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self) -> None:
        import jax
        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **_kw) -> None:
        if event == self.EVENT:
            self.count += 1

    def close(self) -> None:
        import jax
        jax.monitoring.unregister_event_duration_listener(self._on)


class Probe:
    def __init__(self, fb: Any, *, seed: int, plan: dict,
                 swap_train: Optional[Callable] = None,
                 fault: Optional[str] = None):
        if fault is not None and fault not in FAULTS:
            raise ValueError(f"unknown fault {fault!r}; one of {FAULTS}")
        self.fb = fb
        self.plan = plan
        self.swap_train = swap_train
        self.fault = fault
        self.recording = False
        self.spans: dict[str, list[tuple[int, int]]] = defaultdict(list)
        self.decode_work: list[tuple[int, list]] = []
        self.fedavg_work: list[tuple[int, int]] = []
        self.train_samples: list[dict] = []
        self.agg_samples: list[dict] = []
        self.agg_counts: list[int] = []
        self.broadcasts: list[bytes] = []
        self.down_samples: list[tuple[np.ndarray, bytes]] = []
        self.transfer_samples: list[tuple[bytes, list, int]] = []
        self.deliveries: list[tuple[str, int, int]] = []
        self.events_off = 0
        self.index = {p.addr: i for i, p in enumerate(fb.profiles)}
        self._rng_train = np.random.default_rng([seed, 1])
        self._rng_agg = np.random.default_rng([seed, 2])
        self._rng_wire = np.random.default_rng([seed, 3])
        self._sent: dict[tuple[str, int], bytes] = {}
        self._capture: Optional[dict] = None
        self._in_uplink = False
        self._patches: list[tuple[Any, str, Any, bool]] = []

    # -- patching ----------------------------------------------------------
    def _patch(self, owner: Any, attr: str, new: Any) -> None:
        had = attr in vars(owner)
        self._patches.append((owner, attr, vars(owner).get(attr), had))
        setattr(owner, attr, new)

    def remove(self) -> None:
        for owner, attr, old, had in reversed(self._patches):
            if had:
                setattr(owner, attr, old)
            else:
                delattr(owner, attr)
        self._patches.clear()

    def _timed(self, name: str, fn: Callable,
               after: Optional[Callable] = None) -> Callable:
        from jax.profiler import TraceAnnotation
        spans = self.spans

        def wrapper(*a, **k):
            t0 = time.perf_counter_ns()
            with TraceAnnotation(name):
                out = fn(*a, **k)
            t1 = time.perf_counter_ns()
            if self.recording:
                spans[name].append((t0, t1))
                if after is not None:
                    after(a, out)
            return out
        return wrapper

    def install(self) -> "Probe":
        from repro.core import aggregation, packetizer, server, wire
        from repro.core.client_compute import BatchTrainer
        from reference import wire as ref_wire

        self._patch(wire.Pipeline, "encode",
                    self._timed("wire_encode",
                                self._recorded_encode(wire.Pipeline.encode)))
        self._patch(packetizer.Packetizer, "decode_bytes",
                    self._timed("wire_decode",
                                packetizer.Packetizer.decode_bytes))

        decode = server.wire_decode_payload_batch
        if self.fault == "altered_answer":
            decode = _altered(decode)

        def decode_work(a, out):
            datas = a[0]
            if datas:
                self.decode_work.append((len(datas), ref_wire.work(datas[0])))
        self._patch(server, "wire_decode_payload_batch",
                    self._timed("wire_decode_batch", decode, decode_work))

        def fedavg_work(a, out):
            self.fedavg_work.append(tuple(np.shape(a[0])))
        self._patch(aggregation, "fedavg_stack",
                    self._timed("fedavg", aggregation.fedavg_stack,
                                fedavg_work))

        apply = server.ServerCore.apply_aggregation
        if self.fault == "half_batch":
            inner = apply

            def apply(core, contribs):
                return inner(core, contribs[:max(1, len(contribs) // 2)])
        self._patch(server.ServerCore, "apply_aggregation",
                    self._timed("aggregate", self._recorded_apply(apply)))
        self._patch(server.ServerCore, "uplink_update", self._recorded_update(
            server.ServerCore.uplink_update))
        self._patch(server.ServerCore, "broadcast_payload",
                    self._recorded_broadcast(
                        server.ServerCore.broadcast_payload))
        self._patch(server, "packetize", self._recorded_packetize(
            server.packetize, packetizer.DEFAULT_MTU))
        sched = self.fb.system.core.scheduler
        self._patch(sched, "on_uplink", self._recorded_delivery(
            sched.on_uplink))

        if self.fb.trainer is not None:
            self._patch(BatchTrainer, "flush",
                        self._timed("train", BatchTrainer.flush))
            self._patch(self.fb.trainer.backend, "train",
                        self._swapped_backend(self.fb.trainer.backend.train))
        else:
            for addr, client in self.fb.system.core.pool.clients.items():
                self._patch(client, "train_fn", self._timed(
                    "train", self._swapped_train_fn(client.train_fn,
                                                    self.index[addr])))
        return self

    def _draw(self, rng, share: float, have: int, room: int) -> bool:
        return self.recording and have < room and rng.random() < share

    # -- training and the client's encode ----------------------------------
    def _swapped_backend(self, train: Callable) -> Callable:
        if self.swap_train is not None:
            swap = self.swap_train

            def train(model, stack, client_idx, round_idx):
                return _with_aux(swap(stack, client_idx, round_idx))
        elif self.fault == "state_unchanged":
            def train(model, stack, client_idx, round_idx):
                return np.array(stack, np.float32), [{}] * stack.shape[0]
        return train

    def _swapped_train_fn(self, fn: Callable, idx: int) -> Callable:
        from repro.core import flatten_to_vector, unflatten_from_vector
        if self.swap_train is not None:
            swap = self.swap_train

            def fn(params, round_idx, client):
                vec = flatten_to_vector(params)[None]
                new, aux = _with_aux(
                    swap(vec, np.array([idx]), np.array([round_idx])))
                return unflatten_from_vector(new[0], params), aux[0]
        elif self.fault == "state_unchanged":
            def fn(params, round_idx, client):
                return params, {}
        return fn

    def _recorded_update(self, update: Callable) -> Callable:
        """A client's trained model on its way into the uplink encode."""
        from repro.core import flatten_to_vector

        def recorded(core, session, received, new_params):
            take = self._draw(self._rng_train, self.plan["train_share"],
                              len(self.train_samples),
                              self.plan["train_rows"])
            take = take or (self.recording and not self.train_samples)
            self._capture = {} if take else None
            self._in_uplink = True
            try:
                out = update(core, session, received, new_params)
            finally:
                self._in_uplink = False
                cap, self._capture = self._capture, None
            if take:
                history = session.client.metrics_history
                self.train_samples.append(dict(
                    cap, client=self.index[session.addr],
                    loss=(history[-1].get("train_loss") if history
                          else None),
                    round=int(session.round_idx),
                    received=np.array(flatten_to_vector(received)),
                    trained=np.array(flatten_to_vector(new_params))))
            return out
        return recorded

    def _recorded_encode(self, encode: Callable) -> Callable:
        def ef_slot(pipe, state):
            for i, s in enumerate(pipe.stages):
                if s.name == "ef" and state is not None:
                    return state.slots[i]
            return None

        def recorded(pipe, vec, state=None):
            cap = self._capture if self._in_uplink else None
            slot = ef_slot(pipe, state)
            if cap is not None:
                cap["residual"] = None if slot is None else \
                    slot.get("residual")
            data = encode(pipe, vec, state)
            if self._in_uplink and self.fault == "altered_encode" \
                    and self.recording:
                data = _altered_payload(data)
            if cap is not None:
                cap.update(data=data, spec=pipe.spec,
                           residual_after=None if slot is None
                           else slot.get("residual"))
            return data
        return recorded

    # -- downlink, packets and deliveries ----------------------------------
    def _recorded_broadcast(self, broadcast: Callable) -> Callable:
        from repro.core import flatten_to_vector

        def recorded(core):
            data = broadcast(core)
            if data is not None and (not self.broadcasts
                                     or data is not self.broadcasts[-1]):
                self.broadcasts.append(data)
                if self._draw(self._rng_wire, self.plan["wire_share"],
                              len(self.down_samples),
                              self.plan["broadcasts"]):
                    self.down_samples.append(
                        (np.array(flatten_to_vector(core.global_params)),
                         data))
            return data
        return recorded

    def _recorded_packetize(self, packetize: Callable,
                            default_mtu: int) -> Callable:
        from reference import events as ref_events
        server_addr = self.fb.system.core.server_addr

        def recorded(data, addr, txn=0, mtu=default_mtu):
            packets = packetize(data, addr, txn, mtu)
            if addr != server_addr:
                self._sent[(addr, txn)] = data
            if self.recording:
                self.events_off += int(
                    len(packets) != ref_events.packet_count(len(data), mtu))
                if self._draw(self._rng_wire, self.plan["wire_share"],
                              len(self.transfer_samples),
                              self.plan["transfers"]):
                    self.transfer_samples.append(
                        (data, [(p.seq, p.total, p.payload)
                                for p in packets], mtu))
            return packets
        return recorded

    def _recorded_delivery(self, on_uplink: Callable) -> Callable:
        sim = self.fb.sim

        def recorded(session, addr, txn, vec):
            sent = self._sent.pop((addr, txn), None)
            if self.recording:
                got = getattr(vec, "data", None)
                self.events_off += int(sent is None or got is None
                                       or got != sent)
                if session is not None:
                    self.deliveries.append(
                        (addr, int(session.round_idx), sim.now_ns))
            return on_uplink(session, addr, txn, vec)
        return recorded

    # -- aggregation -------------------------------------------------------
    def _recorded_apply(self, apply: Callable) -> Callable:
        from repro.core import flatten_to_vector
        from repro.core.server import _PendingWire

        def recorded(core, contribs):
            if not self.recording:
                return apply(core, contribs)
            self.agg_counts.append(len(contribs))
            draw = self._rng_agg.random()
            take = ((not self.agg_samples
                     or (len(self.agg_samples) < self.plan["aggregations"]
                         and draw < self.plan["aggregation_share"]))
                    and all(isinstance(v, _PendingWire) and v.vec is None
                            for v, _ in contribs))
            if not take:
                return apply(core, contribs)
            g_old = flatten_to_vector(core.global_params)
            pend = [v for v, _ in contribs]
            datas = [p.data for p in pend]
            out = apply(core, contribs)
            self.agg_samples.append({
                "g_old": g_old,
                "g_new": flatten_to_vector(core.global_params),
                "datas": datas, "rows": [p.vec for p in pend],
                "weights": [float(w) for _, w in contribs],
                "delta_domain": bool(core.uplink_pipeline.caps.delta_domain),
                "server_lr": float(core.cfg.server_lr)})
            return out
        return recorded


def _with_aux(out) -> tuple[np.ndarray, list[dict]]:
    """A reference's trained rows, with or without their last losses, as
    the program's train backends return them: rows and one metrics dict
    per row."""
    rows, losses = out if isinstance(out, tuple) else (out, None)
    aux = ([{}] * rows.shape[0] if losses is None
           else [{"train_loss": float(v)} for v in losses])
    return rows, aux


def _altered(decode: Callable) -> Callable:
    """Planted fault: the first decoded row comes out with its largest
    element moved."""
    def altered(datas):
        out = decode(datas)
        for i, (vec, pipe, err) in enumerate(out):
            if vec is not None:
                vec = np.array(vec)
                j = int(np.argmax(np.abs(vec)))
                vec[j] += max(1.0, abs(float(vec[j])))
                out[i] = (vec, pipe, err)
                break
        return out
    return altered


def _altered_payload(data: bytes) -> bytes:
    """Planted fault: a client's payload leaves its encoder with the first
    64 values of its body moved (int8 codes by 100 steps, float32 values
    by 1), so it still parses."""
    from reference import wire as ref_wire
    _, _, body = ref_wire.parse(data)
    head = len(data) - body.nbytes
    body = body.copy()
    n = min(64, body.size)
    if body.dtype == np.int8:
        codes = body[:n].astype(np.int16)
        body[:n] = np.where(codes >= 0, codes - 100, codes + 100)
    else:
        body[:n] = body[:n] + 1
    return data[:head] + body.tobytes()
