"""Pallas TPU kernel: batched top-k gather/scatter over client slabs.

The wire-plane's ``topk`` stage moves values between a dense ``(N, P)``
client slab and its sparse ``(N, K)`` representation: *gather* on encode
(pick each row's K kept values at already-selected indices), *scatter* on
decode (place K values back into a zeroed dense row).  Selection itself
(argpartition) stays on the host — it is data-dependent and cheap — so the
kernels are pure data movement: one grid step per client row, a
``fori_loop`` over that row's K indices.

Layout: a dense row is padded to whole (8, 128) vregs and viewed as
``(P/1024, 8, 128)``, so element ``j`` lives in tile ``j // 1024`` at
sublane ``(j // 128) % 8``, lane ``j % 128``.  The row's indices (and, for
scatter, its values) sit in SMEM, where the loop reads them as scalars; the
data moves only through whole-tile loads and stores at a dynamic *leading*
index, with the one element picked or placed by an iota mask.  The masked
pick is a max over ``where(mask, tile, -inf)``, exact for every float
including ``-0.0``.

Scatter writes are sequential within a row, so duplicate indices resolve
last-wins — the same contract as numpy fancy assignment, which keeps the
batch decode bit-identical to the per-item path even on malformed
payloads.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import resolve_interpret, round_up

SUBLANES, LANES = 8, 128
TILE = SUBLANES * LANES


def _tiles(x: jax.Array, n: int) -> jax.Array:
    """(N, n) -> (N, ceil(n/TILE), 8, 128), zero padded."""
    n_items = x.shape[0]
    npad = round_up(max(n, 1), TILE)
    x = jnp.pad(x, ((0, 0), (0, npad - n)))
    return x.reshape(n_items, npad // TILE, SUBLANES, LANES)


def _mask(j):
    """(8, 128) bool: True at element ``j % TILE`` of a tile."""
    sub = jax.lax.broadcasted_iota(jnp.int32, (SUBLANES, LANES), 0)
    lane = jax.lax.broadcasted_iota(jnp.int32, (SUBLANES, LANES), 1)
    return (sub == (j // LANES) % SUBLANES) & (lane == j % LANES)


def _gather_kernel(idx_ref, x_ref, out_ref):
    out_ref[...] = jnp.zeros_like(out_ref)

    def body(k, carry):
        j = idx_ref[0, k]
        tile = x_ref[j // TILE]
        val = jnp.max(jnp.where(_mask(j), tile, -jnp.inf), keepdims=True)
        t = k // TILE
        out_ref[t] = jnp.where(_mask(k), val, out_ref[t])
        return carry

    jax.lax.fori_loop(0, idx_ref.shape[1], body, 0)


def _scatter_kernel(idx_ref, vals_ref, out_ref):
    out_ref[...] = jnp.zeros_like(out_ref)

    def body(k, carry):
        j = idx_ref[0, k]
        t = j // TILE
        out_ref[t] = jnp.where(_mask(j), vals_ref[0, k], out_ref[t])
        return carry

    jax.lax.fori_loop(0, idx_ref.shape[1], body, 0)


def _smem_row(k_kept: int) -> pl.BlockSpec:
    """One client's (1, K) row of an (N, 1, K) array, in SMEM."""
    return pl.BlockSpec((None, 1, k_kept), lambda i: (i, 0, 0),
                        memory_space=pltpu.SMEM)


@functools.partial(jax.jit, static_argnames=("interpret",))
def topk_gather_pallas(x: jax.Array, idx: jax.Array, *,
                       interpret: bool | None = None) -> jax.Array:
    """x: (N, P) f32, idx: (N, K) int32 -> (N, K) f32 values at idx."""
    n_items, p = x.shape
    k_kept = idx.shape[1]
    xt = _tiles(x.astype(jnp.float32), p)
    kt = round_up(max(k_kept, 1), TILE) // TILE
    out = pl.pallas_call(
        _gather_kernel,
        grid=(n_items,),
        in_specs=[
            _smem_row(k_kept),
            pl.BlockSpec((None,) + xt.shape[1:], lambda i: (i, 0, 0, 0)),
        ],
        out_specs=pl.BlockSpec((None, kt, SUBLANES, LANES),
                               lambda i: (i, 0, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((n_items, kt, SUBLANES, LANES),
                                       jnp.float32),
        interpret=resolve_interpret(interpret),
    )(idx.astype(jnp.int32).reshape(n_items, 1, k_kept), xt)
    return out.reshape(n_items, kt * TILE)[:, :k_kept]


@functools.partial(jax.jit, static_argnames=("n", "interpret"))
def topk_scatter_pallas(idx: jax.Array, vals: jax.Array, *, n: int,
                        interpret: bool | None = None) -> jax.Array:
    """idx/vals: (N, K) -> (N, n) f32, zeros except vals placed at idx."""
    n_items, k_kept = idx.shape
    nt = round_up(max(n, 1), TILE) // TILE
    out = pl.pallas_call(
        _scatter_kernel,
        grid=(n_items,),
        in_specs=[_smem_row(k_kept), _smem_row(k_kept)],
        out_specs=pl.BlockSpec((None, nt, SUBLANES, LANES),
                               lambda i: (i, 0, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((n_items, nt, SUBLANES, LANES),
                                       jnp.float32),
        interpret=resolve_interpret(interpret),
    )(idx.astype(jnp.int32).reshape(n_items, 1, k_kept),
      vals.astype(jnp.float32).reshape(n_items, 1, k_kept))
    return out.reshape(n_items, nt * TILE)[:, :n]
