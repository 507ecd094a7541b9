"""Jit'd wrapper: checksum raw bytes on device."""

from __future__ import annotations

import jax.numpy as jnp
import numpy as np

from repro.kernels.checksum.checksum import checksum_pallas
from repro.kernels.checksum.ref import chunksum32_np


def checksum_bytes(data: bytes, *, interpret: bool | None = None) -> int:
    x = jnp.asarray(np.frombuffer(data, dtype=np.uint8).astype(np.int32))
    return int(np.uint32(np.asarray(checksum_pallas(x, interpret=interpret))))


def checksum_bytes_ref(data: bytes) -> int:
    return chunksum32_np(np.frombuffer(data, dtype=np.uint8))
