"""Jit'd public wrapper for the fedavg kernel (+ convenience pytree API)."""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.packetizer import flatten_to_vector, unflatten_from_vector
from repro.kernels.fedavg.fedavg import fedavg_pallas
from repro.kernels.fedavg.ref import fedavg_flat as ref_fedavg_flat


def fedavg_flat(stack, weights, *, interpret: bool | None = None):
    """Normalized weighted mean over K flat client vectors."""
    w = jnp.asarray(weights, jnp.float32)
    w = w / jnp.sum(w)
    return fedavg_pallas(jnp.asarray(stack, jnp.float32), w,
                         interpret=interpret)


def fedavg_trees(trees, weights, *, interpret: bool | None = None):
    """Aggregate a list of parameter pytrees (server-side fast path)."""
    stack = jnp.stack([flatten_to_vector(t) for t in trees])
    out = np.asarray(fedavg_flat(stack, weights, interpret=interpret))
    return unflatten_from_vector(out, trees[0])


def pairwise_average_flat(server_vec, client_vec, *,
                          interpret: bool | None = None):
    """Paper Eq. (1) as the K=2 equal-weight case."""
    stack = jnp.stack([jnp.asarray(server_vec, jnp.float32),
                       jnp.asarray(client_vec, jnp.float32)])
    return fedavg_flat(stack, jnp.asarray([1.0, 1.0]), interpret=interpret)
