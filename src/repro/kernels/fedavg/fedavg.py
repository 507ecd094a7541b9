"""Pallas TPU kernel: fused weighted parameter aggregation (FedAvg / paper
Eq. 1).

The FL server's hot loop is ``out = sum_k w_k * x_k`` over K client vectors of
N params (N up to tens of billions). One pass over HBM: each grid step
streams a (BK, BN) tile into VMEM, reduces over its BK clients on the VPU and
accumulates into the (1, BN) output block — arithmetic intensity is too low
for the MXU, so the win is purely bandwidth (one fused read instead of K-1
accumulate passes).

Tiling: the grid is (N blocks, K blocks) with K innermost, so the output
block stays resident while the client blocks stream past it.  BK is at most
``BLOCK_K`` rows, and BN is chosen from BK so one input tile holds about
``TILE_BYTES`` (2048 lanes at BK=256, ``BLOCK_N`` at BK<=32): double-buffered
that is ~4 MiB of VMEM for any K, well inside the v5e's scoped limit.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import resolve_interpret, round_up

BLOCK_K = 256             # client rows per grid step
BLOCK_N = 16_384          # widest lane block (small K)
TILE_BYTES = 2 << 20      # f32 input tile per grid step


def _block_n(bk: int, n: int) -> int:
    bn = min(BLOCK_N, TILE_BYTES // (4 * bk), round_up(n, 128))
    return max(128, bn // 128 * 128)


def _fedavg_kernel(w_ref, x_ref, o_ref):
    part = jnp.sum(w_ref[...] * x_ref[...], axis=0, keepdims=True)  # (1, BN)

    @pl.when(pl.program_id(1) == 0)
    def _init():
        o_ref[...] = part

    @pl.when(pl.program_id(1) != 0)
    def _acc():
        o_ref[...] += part


@functools.partial(jax.jit, static_argnames=("interpret", "block_n"))
def fedavg_pallas(stack: jax.Array, weights: jax.Array, *,
                  block_n: int | None = None,
                  interpret: bool | None = None) -> jax.Array:
    """stack (K, N) f32, weights (K,) -> (N,) f32. K and N padded
    internally (padded clients carry weight 0)."""
    K, N = stack.shape
    bk = min(round_up(K, 8), BLOCK_K)
    bn = block_n or _block_n(bk, N)
    kpad, npad = round_up(K, bk), round_up(N, bn)
    stack = jnp.pad(stack.astype(jnp.float32),
                    ((0, kpad - K), (0, npad - N)))
    weights = jnp.pad(weights.astype(jnp.float32), (0, kpad - K))
    out = pl.pallas_call(
        _fedavg_kernel,
        grid=(npad // bn, kpad // bk),
        in_specs=[
            pl.BlockSpec((bk, 1), lambda j, k: (k, 0)),
            pl.BlockSpec((bk, bn), lambda j, k: (k, j)),
        ],
        out_specs=pl.BlockSpec((1, bn), lambda j, k: (0, j)),
        out_shape=jax.ShapeDtypeStruct((1, npad), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=resolve_interpret(interpret),
    )(weights.reshape(kpad, 1), stack)
    return out[0, :N]
