"""Pallas TPU kernel: ChunkSum-32 packet-payload checksum.

Single pass over the payload: each grid step loads a (8, 1024) int32 tile,
forms the weighted and unweighted terms on the VPU, and adds them
elementwise into a resident (2, 8, 1024) accumulator block (TPU grid steps
execute sequentially, so read-modify-write on the output ref across steps
is the standard accumulator pattern; step 0 initializes).  The wrapper
folds the accumulator to the two sums.

int32 wraparound is part of the checksum definition (see ref.py), so the
adds are exact on any backend and in any order.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import resolve_interpret
from repro.kernels.checksum.ref import WEIGHT_PERIOD

TILE_R, TILE_C = 8, 1024
TILE = TILE_R * TILE_C


def _checksum_kernel(x_ref, acc_ref):
    step = pl.program_id(0)
    x = x_ref[...]                                     # (8, 1024) int32
    idx = step * TILE + (
        jax.lax.broadcasted_iota(jnp.int32, (TILE_R, TILE_C), 0) * TILE_C
        + jax.lax.broadcasted_iota(jnp.int32, (TILE_R, TILE_C), 1))
    w = (idx % WEIGHT_PERIOD) + 1

    @pl.when(step == 0)
    def _init():
        acc_ref[0] = x
        acc_ref[1] = w * x

    @pl.when(step != 0)
    def _acc():
        acc_ref[0] += x
        acc_ref[1] += w * x


@functools.partial(jax.jit, static_argnames=("interpret",))
def checksum_pallas(x_i32: jax.Array, *, interpret: bool | None = None
                    ) -> jax.Array:
    """x_i32: (N,) int32 byte values -> uint32-style checksum as int32.

    N is padded to the tile size with zeros; zero bytes contribute nothing
    to either sum, so padding never changes the checksum.
    """
    n = x_i32.shape[0]
    pad = (-n) % TILE
    if pad:
        x_i32 = jnp.pad(x_i32, (0, pad))
    tiles = (n + pad) // TILE
    acc = pl.pallas_call(
        _checksum_kernel,
        grid=(tiles,),
        in_specs=[pl.BlockSpec((TILE_R, TILE_C), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((2, TILE_R, TILE_C), lambda i: (0, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((2, TILE_R, TILE_C), jnp.int32),
        interpret=resolve_interpret(interpret),
    )(x_i32.reshape(tiles * TILE_R, TILE_C))
    a, b = jnp.sum(acc, axis=(1, 2), dtype=jnp.int32)
    return (a & 0xFFFF) | ((b & 0xFFFF) << 16)
