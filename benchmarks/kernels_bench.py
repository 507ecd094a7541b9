"""Benchmark 6 — Pallas kernels vs jnp oracles.

The federated-path kernels (fedavg, quantize, checksum) follow the
platform: native on a TPU, the Pallas interpreter (Python emulation of the
TPU grid) on a CPU, where wall times validate the correctness path only.
Derived: max abs deviation vs the oracle.
"""

from __future__ import annotations

import time

import jax.numpy as jnp
import numpy as np

from repro.kernels.checksum.checksum import checksum_pallas
from repro.kernels.checksum.ref import chunksum32_jnp
from repro.kernels.fedavg.fedavg import fedavg_pallas
from repro.kernels.fedavg.ref import fedavg_flat
from repro.kernels.flash_attention.flash_attention import \
    flash_attention_pallas
from repro.kernels.flash_attention.ref import attention_ref
from repro.kernels.mlstm.mlstm import mlstm_pallas
from repro.kernels.mlstm.ref import mlstm_ref
from repro.kernels.quantize.quantize import quantize_pallas
from repro.kernels.quantize.ref import quantize_blockwise


def _time(fn, reps=2):
    out = fn()
    jnp.asarray(out[0] if isinstance(out, (tuple, list)) else out
                ).block_until_ready()
    t0 = time.perf_counter()
    for _ in range(reps):
        out = fn()
        jnp.asarray(out[0] if isinstance(out, (tuple, list)) else out
                    ).block_until_ready()
    return (time.perf_counter() - t0) * 1e6 / reps, out


def bench():
    rng = np.random.default_rng(0)
    rows = []

    stack = jnp.asarray(rng.standard_normal((4, 262_144)), jnp.float32)
    w = jnp.asarray([0.1, 0.2, 0.3, 0.4], jnp.float32)
    us_k, out_k = _time(lambda: fedavg_pallas(stack, w))
    us_r, out_r = _time(lambda: fedavg_flat(stack, w))
    dev = float(jnp.abs(out_k - out_r).max())
    rows.append(("kernels/fedavg_pallas", us_k, f"max_dev={dev:.2e}"))
    rows.append(("kernels/fedavg_ref", us_r, "oracle"))

    x = jnp.asarray(rng.standard_normal((64, 1024)), jnp.float32)
    us_k, (q_k, s_k) = _time(lambda: quantize_pallas(x))
    us_r, (q_r, s_r) = _time(lambda: quantize_blockwise(x))
    dev = float(jnp.abs(s_k - s_r).max())
    rows.append(("kernels/quantize_pallas", us_k, f"scale_dev={dev:.2e}"))
    rows.append(("kernels/quantize_ref", us_r, "oracle"))

    data = jnp.asarray(rng.integers(0, 256, 262_144).astype(np.int32))
    us_k, c_k = _time(lambda: checksum_pallas(data))
    us_r, c_r = _time(lambda: chunksum32_jnp(data))
    rows.append(("kernels/checksum_pallas", us_k,
                 f"match={int(c_k) == int(c_r)}"))
    rows.append(("kernels/checksum_ref", us_r, "oracle"))

    B, H, S, hd = 1, 2, 512, 64
    q = jnp.asarray(rng.standard_normal((B, H, S, hd)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((B, H, S, hd)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((B, H, S, hd)), jnp.float32)
    us_k, o_k = _time(lambda: flash_attention_pallas(q, k, v,
                                                     interpret=True), 1)
    us_r, o_r = _time(lambda: attention_ref(q, k, v), 1)
    dev = float(jnp.abs(o_k - o_r).max())
    rows.append(("kernels/flash_attention_pallas", us_k,
                 f"max_dev={dev:.2e};S={S}"))
    rows.append(("kernels/flash_attention_ref", us_r, "oracle"))

    nh, dh = 2, 64
    qm = jnp.asarray(rng.standard_normal((1, 256, nh, dh)), jnp.float32)
    km = jnp.asarray(rng.standard_normal((1, 256, nh, dh)), jnp.float32)
    vm = jnp.asarray(rng.standard_normal((1, 256, nh, dh)), jnp.float32)
    ig = jnp.asarray(rng.standard_normal((1, 256, nh)), jnp.float32)
    fg = jnp.asarray(rng.standard_normal((1, 256, nh)) + 1, jnp.float32)
    us_k, m_k = _time(lambda: mlstm_pallas(qm, km, vm, ig, fg,
                                           interpret=True), 1)
    us_r, m_r = _time(lambda: mlstm_ref(qm, km, vm, ig, fg), 1)
    dev = float(jnp.abs(m_k - m_r).max())
    rows.append(("kernels/mlstm_pallas", us_k, f"max_dev={dev:.2e}"))
    rows.append(("kernels/mlstm_ref", us_r, "oracle"))
    return rows


def main():
    for name, us, derived in bench():
        print(f"{name},{us:.1f},{derived}")


if __name__ == "__main__":
    main()
