"""Pallas TPU kernel: blockwise absmax int8 quantization (packet payload
compression / quantized gradient aggregation).

Client-side packetization quantizes the full parameter vector before the
wire; at tens of GB this is bandwidth-bound, so the kernel fuses
absmax-reduce + scale + round + cast in one VMEM pass (the jnp reference
makes three).

Layout: the flat vector is viewed as (nb, QBLOCK) rows; each grid step
processes R rows, a multiple of 32 (the int8 sublane tile) and at most
``MAX_ROWS_PER_TILE`` — (256, 1024) f32 = 1 MiB in, 256 KiB out, VPU
reductions along lanes.  Scales travel as an (nb, 1) column so their block
(R, 1) meets the TPU's tiling rule (last dim equal to the array's).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import resolve_interpret, round_up

QBLOCK = 1024              # values per quantization block (wire codec contract)
ROW_ALIGN = 32             # int8 sublane tile
MAX_ROWS_PER_TILE = 256


def _rows_per_tile(nb: int) -> int:
    return min(MAX_ROWS_PER_TILE, round_up(nb, ROW_ALIGN))


def _quant_kernel(x_ref, q_ref, s_ref):
    x = x_ref[...]                                          # (R, QBLOCK) f32
    absmax = jnp.max(jnp.abs(x), axis=1, keepdims=True)     # (R, 1)
    scale = jnp.maximum(absmax, 1e-12) / 127.0
    q = jnp.clip(jnp.rint(x / scale), -127, 127)
    q_ref[...] = q.astype(jnp.int8)
    s_ref[...] = scale


def _dequant_kernel(q_ref, s_ref, o_ref):
    o_ref[...] = q_ref[...].astype(jnp.float32) * s_ref[...]


@functools.partial(jax.jit, static_argnames=("interpret",))
def quantize_pallas(x: jax.Array, *, interpret: bool | None = None
                    ) -> tuple[jax.Array, jax.Array]:
    """x: (nb, QBLOCK) f32 -> (q (nb, QBLOCK) int8, scales (nb,) f32)."""
    nb, blk = x.shape
    if blk != QBLOCK:
        raise ValueError(f"quantize_pallas needs ({nb}, {QBLOCK}) rows, "
                         f"got block {blk}")
    r = _rows_per_tile(nb)
    rows = round_up(nb, r)
    x = jnp.pad(x.astype(jnp.float32), ((0, rows - nb), (0, 0)))
    q, s = pl.pallas_call(
        _quant_kernel,
        grid=(rows // r,),
        in_specs=[pl.BlockSpec((r, QBLOCK), lambda i: (i, 0))],
        out_specs=[
            pl.BlockSpec((r, QBLOCK), lambda i: (i, 0)),
            pl.BlockSpec((r, 1), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((rows, QBLOCK), jnp.int8),
            jax.ShapeDtypeStruct((rows, 1), jnp.float32),
        ],
        interpret=resolve_interpret(interpret),
    )(x)
    return q[:nb], s[:nb, 0]


@functools.partial(jax.jit, static_argnames=("interpret",))
def dequantize_pallas(q: jax.Array, scales: jax.Array, *,
                      interpret: bool | None = None) -> jax.Array:
    """q (nb, QBLOCK) int8, scales (nb,) f32 -> (nb, QBLOCK) f32."""
    nb, blk = q.shape
    if blk != QBLOCK:
        raise ValueError(f"dequantize_pallas needs ({nb}, {QBLOCK}) rows, "
                         f"got block {blk}")
    r = _rows_per_tile(nb)
    rows = round_up(nb, r)
    q = jnp.pad(q, ((0, rows - nb), (0, 0)))
    scales = jnp.pad(scales.astype(jnp.float32), (0, rows - nb))
    out = pl.pallas_call(
        _dequant_kernel,
        grid=(rows // r,),
        in_specs=[
            pl.BlockSpec((r, QBLOCK), lambda i: (i, 0)),
            pl.BlockSpec((r, 1), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((r, QBLOCK), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((rows, QBLOCK), jnp.float32),
        interpret=resolve_interpret(interpret),
    )(q, scales.reshape(rows, 1))
    return out[:nb]
