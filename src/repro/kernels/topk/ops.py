"""Jit'd wrappers: batched top-k gather/scatter for the wire batch plane.

``repro.core.wire`` probes this module lazily (``set_batch_backend
("pallas")``); both ops are exact data movement, so the batch contract —
bit-identical to the numpy path — holds by construction and is pinned in
``tests/test_kernel_parity.py``.
"""

from __future__ import annotations

import jax.numpy as jnp

from repro.kernels.topk.topk import topk_gather_pallas, topk_scatter_pallas


def topk_gather(batch, idx, *, interpret: bool | None = None):
    """batch: (N, P) f32, idx: (N, K) -> (N, K) f32 kept values."""
    return topk_gather_pallas(jnp.asarray(batch, jnp.float32),
                              jnp.asarray(idx).astype(jnp.int32),
                              interpret=interpret)


def topk_scatter(idx, vals, n, *, interpret: bool | None = None):
    """idx/vals: (N, K) -> dense (N, n) f32 (zeros off the kept set)."""
    return topk_scatter_pallas(jnp.asarray(idx).astype(jnp.int32),
                               jnp.asarray(vals, jnp.float32),
                               n=int(n), interpret=interpret)
