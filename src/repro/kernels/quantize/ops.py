"""Jit'd wrappers: flat-vector int8 quantize/dequantize on device."""

from __future__ import annotations

import jax.numpy as jnp
import numpy as np

from repro.kernels.quantize.quantize import (QBLOCK, dequantize_pallas,
                                             quantize_pallas)


def quantize_vector(vec, *, interpret: bool | None = None):
    """Flat f32 vector -> (q int8 (padded to QBLOCK), scales, n)."""
    vec = jnp.asarray(vec, jnp.float32)
    n = vec.shape[0]
    nb = -(-n // QBLOCK)
    padded = jnp.zeros((nb * QBLOCK,), jnp.float32).at[:n].set(vec)
    q, s = quantize_pallas(padded.reshape(nb, QBLOCK), interpret=interpret)
    return q, s, n


def dequantize_vector(q, scales, n, *, interpret: bool | None = None):
    out = dequantize_pallas(q, scales, interpret=interpret)
    return out.reshape(-1)[:n]


def quantize_matrix(mat, *, interpret: bool | None = None):
    """Batched client slab: (N, P) f32 -> (q int8 (N, nb*QBLOCK),
    scales (N, nb)) — the wire ``int8`` stage's batch layout.  Rows are
    independent, so this is one kernel launch over N*nb blocks instead of
    N launches."""
    mat = jnp.asarray(mat, jnp.float32)
    n_items, n = mat.shape
    nb = -(-n // QBLOCK)
    padded = jnp.zeros((n_items, nb * QBLOCK), jnp.float32).at[:, :n].set(mat)
    q, s = quantize_pallas(padded.reshape(n_items * nb, QBLOCK),
                           interpret=interpret)
    return q.reshape(n_items, nb * QBLOCK), s.reshape(n_items, nb)


def dequantize_matrix(q, scales, n, *, interpret: bool | None = None):
    """Inverse of :func:`quantize_matrix`: -> (N, n) f32."""
    scales = jnp.asarray(scales, jnp.float32)
    n_items, nb = scales.shape
    out = dequantize_pallas(jnp.asarray(q, jnp.int8).reshape(n_items * nb,
                                                             QBLOCK),
                            scales.reshape(-1), interpret=interpret)
    return out.reshape(n_items, nb * QBLOCK)[:, :n]
