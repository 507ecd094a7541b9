#!/usr/bin/env python3
"""Bring-up check: the federated round on a TPU, against a host reference.

Drives the main federated path through its user entry points —
``FleetConfig`` -> ``build_fleet_training`` -> ``run_round`` — with the
``mlp`` model at its own widths (784 -> 32 -> 10, P = 25,450 parameters,
synthetic MNIST from the seed), 256 clients, star topology, ``mudp`` on
the batched engine, sync rounds, uplink ``delta|ef|topk(0.01)|int8(1024)``,
downlink ``int8(1024)``, ``aggregation_backend="kernel"`` and the
``pallas`` wire batch backend.  Local training (``vmap``), the top-k and
int8 wire kernels and FedAvg all run on the device.

In the same process it then runs the same fleet from the same seed as the
reference: ``train_backend="python"``, numpy FedAvg and the numpy wire.
The run passes when

* rosters, arrivals, ``duration_ns`` and byte/packet counts are exactly
  equal round for round (the event layer must not see the device);
* the final global parameters agree within one int8 step of the downlink
  codec: per 1024-block, ``|device - reference| <= absmax(reference)/127``
  — the resolution at which the system itself ships the model to its
  clients (see ``param_tolerance``);
* test accuracy after the rounds is above its value at init.

  python3 chip_smoke.py              # one chip
  python3 chip_smoke.py --chips 4    # shard backend on 4 chips vs vmap on one

``--chips 4`` runs only the four-chip comparison: the same fleet on the
``shard`` train backend over all four chips against ``vmap`` on one of
them, both on the device kernels, held to the same event equality and
tolerance.

Everything runs in this one process (a chip belongs to one process at a
time).  With no TPU, or if any phase fails, the script exits non-zero and
prints no result; a passing run's last line is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time
import traceback

ROOT = os.path.dirname(os.path.abspath(__file__))
NS = 1_000_000_000

N_CLIENTS = 256          # the repo's vmap gate size
ROUNDS = 3
SEED = 7
UPLINK = "delta|ef|topk(0.01)|int8(1024)"
DOWNLINK = "int8(1024)"
QBLOCK = 1024            # the downlink codec's block: the tolerance's grain


def say(msg: str) -> None:
    print(msg, flush=True)


def compiles() -> tuple[int, float]:
    """XLA compiles so far and their seconds, from the program's counters
    ``jax.compiles`` and ``jax.compile_ns``, so rounds can report the
    compile time spent inside them apart."""
    from repro.core import tracing
    c = tracing.counters()
    return c.get("jax.compiles", 0), c.get("jax.compile_ns", 0) / NS


# --------------------------------------------------------------------------
# Phases
# --------------------------------------------------------------------------
def check_native_lowering(n_clients: int, n_params: int) -> None:
    """Lower every federated-path kernel at the run's widths with the
    platform-derived ``interpret`` default and require a Mosaic custom call
    (``tpu_custom_call``) in the compiled program."""
    import jax
    import jax.numpy as jnp

    from repro.kernels import resolve_interpret
    from repro.kernels.fedavg.fedavg import fedavg_pallas
    from repro.kernels.quantize.quantize import (dequantize_pallas,
                                                 quantize_pallas)
    from repro.kernels.topk.topk import (topk_gather_pallas,
                                         topk_scatter_pallas)

    if resolve_interpret(None):
        raise RuntimeError("kernels would run in the Pallas interpreter on "
                           f"backend {jax.default_backend()!r}")
    f32, i32, i8 = jnp.float32, jnp.int32, jnp.int8
    k_kept = max(1, int(n_params * 0.01))       # topk(0.01)
    n_blocks = -(-n_params // QBLOCK)

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype)

    cases = [
        ("fedavg_pallas", fedavg_pallas,
         (sds((n_clients, n_params), f32), sds((n_clients,), f32)), {}),
        ("quantize_pallas", quantize_pallas,
         (sds((n_clients * n_blocks, QBLOCK), f32),), {}),
        ("dequantize_pallas", dequantize_pallas,
         (sds((n_clients, QBLOCK), i8), sds((n_clients,), f32)), {}),
        ("topk_gather_pallas", topk_gather_pallas,
         (sds((n_clients, n_params), f32), sds((n_clients, k_kept), i32)),
         {}),
        ("topk_scatter_pallas", topk_scatter_pallas,
         (sds((n_clients, k_kept), i32), sds((n_clients, k_kept), f32)),
         {"n": n_params}),
    ]
    for name, fn, shapes, static in cases:
        t0 = time.perf_counter()
        text = fn.lower(*shapes, **static).compile().as_text()
        native = "tpu_custom_call" in text
        say(f"kernel {name}: shapes {[s.shape for s in shapes]} "
            f"native={native} compile_s={time.perf_counter() - t0:.3f}")
        if not native:
            raise RuntimeError(f"{name} did not lower to a Mosaic kernel")


def build(train_backend: str, on_device: bool):
    """The fleet under test (``on_device``: kernel FedAvg + pallas wire)
    or the host reference (numpy FedAvg + numpy wire)."""
    from repro.core import (FLConfig, FleetConfig, TransportConfig,
                            build_fleet_training)
    from repro.core import wire
    wire.set_batch_backend("pallas" if on_device else "numpy")
    fleet = FleetConfig(n_clients=N_CLIENTS, seed=SEED, mode="sync",
                        topology="star", engine="batched",
                        round_deadline_ns=4 * NS, model="mlp",
                        train_backend=train_backend,
                        uplink=UPLINK, downlink=DOWNLINK)
    cfg = FLConfig(aggregation="fedavg",
                   aggregation_backend="kernel" if on_device else "numpy",
                   transport=TransportConfig(kind="mudp", timeout_ns=2 * NS,
                                             udp_deadline_ns=3 * NS))
    return build_fleet_training(fleet, cfg)


def run_fleet(label: str, train_backend: str, on_device: bool,
              check_kernels: bool = False) -> dict:
    """Build, warm up and run ROUNDS rounds; returns what the checks read.
    ``check_kernels`` lowers every kernel at the model's width first."""
    import numpy as np

    from repro.core import flatten_to_vector, wire

    t0 = time.perf_counter()
    c0 = compiles()[1]
    fb = build(train_backend, on_device)
    model = fb.model
    acc0 = model.accuracy(fb.system.global_params)
    if fb.trainer is not None:
        # Compile the full-roster training step before the timed rounds.
        vec = flatten_to_vector(fb.system.global_params)
        fb.trainer.backend.train(
            model, np.tile(vec, (N_CLIENTS, 1)),
            np.arange(N_CLIENTS, dtype=np.int32),
            np.zeros(N_CLIENTS, np.int32))
    say(f"[{label}] setup_s={time.perf_counter() - t0:.3f} "
        f"(compile_s={compiles()[1] - c0:.3f}) n_params={model.n_params} "
        f"wire_backend={wire.batch_backend()} acc_init={acc0:.4f}")
    if check_kernels:
        check_native_lowering(N_CLIENTS, model.n_params)
    rounds = []
    for _ in range(ROUNDS):
        t1, c1 = time.perf_counter(), compiles()[1]
        res = fb.system.run_round()
        wall = time.perf_counter() - t1
        acc = model.accuracy(fb.system.global_params)
        rounds.append(res)
        say(f"[{label}] round {res.round_idx}: roster={len(res.roster)} "
            f"arrived={len(res.arrived)} failed={len(res.failed)} "
            f"bytes={res.bytes_sent} packets={res.packets_sent} "
            f"duration_ns={res.duration_ns} acc={acc:.4f} "
            f"wall_s={wall:.3f} (compile_s={compiles()[1] - c1:.3f})")
    if fb.trainer is not None:
        say(f"[{label}] training flush sizes {fb.trainer.batch_sizes}")
    return {"rounds": rounds, "acc0": acc0, "acc": acc, "build": fb,
            "params": flatten_to_vector(fb.system.global_params)}


def param_tolerance(ref):
    """Per-element bound: one int8 step (absmax/127) of the downlink
    codec's 1024-block that holds the element."""
    import numpy as np

    from repro.core.compression import quantize_int8
    _, scales = quantize_int8(ref, QBLOCK)
    return np.repeat(scales, QBLOCK)[:ref.size]


def compare(label: str, got: dict, ref: dict) -> list[str]:
    """Exact event equality and the parameter tolerance; returns failures."""
    import numpy as np

    fails = []
    for name, key in (("rosters", lambda r: r.roster),
                      ("arrivals", lambda r: r.arrived),
                      ("failures", lambda r: r.failed),
                      ("duration_ns", lambda r: r.duration_ns),
                      ("bytes", lambda r: r.bytes_sent),
                      ("packets", lambda r: r.packets_sent)):
        a = [key(r) for r in got["rounds"]]
        b = [key(r) for r in ref["rounds"]]
        same = a == b
        say(f"[{label}] {name} equal: {same}")
        if not same:
            fails.append(f"{label}: {name} differ")
    diff = np.abs(got["params"] - ref["params"])
    tol = param_tolerance(ref["params"])
    ok = bool(np.all(diff <= tol))
    say(f"[{label}] params: max|diff|={diff.max():.3e} "
        f"max diff/tol={np.max(diff / tol):.4f} "
        f"elements over tol={int(np.sum(diff > tol))} -> "
        f"{'within' if ok else 'OUTSIDE'} one downlink int8 step")
    if not ok:
        fails.append(f"{label}: parameters outside tolerance")
    return fails


def check_wire_encode(fb) -> list[str]:
    """The round decodes uplinks in batches (top-k scatter, dequantize) but
    encodes them one client at a time on the host, so the encode kernels
    (top-k gather, quantize) are driven here: one batch encode of the whole
    roster's trained updates, on the pallas backend against numpy.  Top-k
    bytes must be identical; int8 is held to the kernel parity contract
    (decoded values within one code step)."""
    import numpy as np

    from repro.core import flatten_to_vector, wire

    model = fb.model
    vec = flatten_to_vector(fb.system.global_params)
    trained, _ = fb.trainer.backend.train(
        model, np.tile(vec, (N_CLIENTS, 1)),
        np.arange(N_CLIENTS, dtype=np.int32), np.zeros(N_CLIENTS, np.int32))
    deltas = list(trained - vec)
    fails = []
    for spec in ("topk(0.01)", "topk(0.01)|int8(1024)"):
        pipe = wire.parse_pipeline(spec)
        out = {}
        for backend in ("pallas", "numpy"):
            wire.set_batch_backend(backend)
            data = pipe.encode_batch(deltas)
            out[backend] = (data, pipe.decode_batch(data))
        same = sum(a == b for a, b in zip(out["pallas"][0], out["numpy"][0]))
        err = np.abs(out["pallas"][1] - out["numpy"][1]).max()
        say(f"wire encode_batch {spec}: {same}/{N_CLIENTS} payloads "
            f"byte-identical to numpy, max|decoded diff|={err:.3e}")
        if "int8" not in spec:
            if same != N_CLIENTS:
                fails.append(f"wire {spec}: pallas bytes differ from numpy")
            continue
        step = max(np.abs(d).max() for d in deltas) / 127.0
        if err > 1.01 * step:
            fails.append(f"wire {spec}: decoded values beyond one int8 "
                         f"step ({err:.3e} > {step:.3e})")
    wire.set_batch_backend("pallas")
    return fails


def smoke_one_chip() -> list[str]:
    dev = run_fleet("device", "vmap", on_device=True, check_kernels=True)
    fails = check_wire_encode(dev["build"])
    ref = run_fleet("reference", "python", on_device=False)
    fails += compare("device vs reference", dev, ref)
    rose = dev["acc"] > dev["acc0"]
    say(f"[device] accuracy {dev['acc0']:.4f} -> {dev['acc']:.4f} "
        f"(reference {ref['acc0']:.4f} -> {ref['acc']:.4f}): "
        f"{'rose' if rose else 'DID NOT RISE'}")
    if not rose:
        fails.append("device accuracy did not rise above init")
    return fails


def smoke_four_chips() -> list[str]:
    shard = run_fleet("shard x4", "shard", on_device=True)
    vmap = run_fleet("vmap x1", "vmap", on_device=True)
    return compare("shard vs vmap", shard, vmap)


# --------------------------------------------------------------------------
def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description=__doc__, formatter_class=argparse.RawTextHelpFormatter)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: the federated round vs the host reference; "
                         "4: shard backend over 4 chips vs vmap on one")
    args = ap.parse_args(argv)

    sys.path.insert(0, os.path.join(ROOT, "src"))
    try:
        from repro.compile_cache import enable_compile_cache
    except ImportError as e:
        print(f"chip_smoke: the repo's sources are not next to this "
              f"script ({e})", file=sys.stderr)
        return 2

    import jax
    try:
        devices = jax.devices()
    except RuntimeError as e:
        print(f"chip_smoke: JAX found no devices: {e}", file=sys.stderr)
        return 1
    d0 = devices[0]
    device = {"platform": d0.platform, "kind": d0.device_kind,
              "count": len(devices)}
    say(f"devices: platform={device['platform']} kind={device['kind']} "
        f"count={device['count']}")
    if d0.platform != "tpu":
        print(f"chip_smoke: no TPU (JAX backend is {d0.platform!r})",
              file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} "
              f"devices, JAX sees {len(devices)}", file=sys.stderr)
        return 1
    say(f"compile cache: {enable_compile_cache()}")

    t0 = time.perf_counter()
    try:
        fails = smoke_four_chips() if args.chips == 4 else smoke_one_chip()
    except Exception:  # noqa: BLE001 - any phase failure fails the smoke
        traceback.print_exc()
        return 1
    n_compiles, compile_s = compiles()
    say(f"total_s={time.perf_counter() - t0:.3f} "
        f"compile_s={compile_s:.3f} compiles={n_compiles}")
    if fails:
        for f in fails:
            print(f"chip_smoke: FAIL {f}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
