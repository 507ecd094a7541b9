"""Pallas kernels for the federated path (fedavg, quantize, topk) and the
payload checksum.

Every kernel entry point takes ``interpret: bool | None = None``: ``None``
derives the mode from the platform through :func:`resolve_interpret`, so
the same call lowers natively (Mosaic) on a TPU and runs the Pallas
interpreter where no TPU backend exists, as in the CPU tests.  Passing a
bool forces the mode, which is how the compile tests lower natively for a
described chip from a CPU process.
"""

from __future__ import annotations

import jax


def resolve_interpret(interpret: bool | None) -> bool:
    """``interpret`` as given, or — for ``None`` — the interpreter exactly
    when JAX's default backend is not a TPU."""
    if interpret is None:
        return jax.default_backend() != "tpu"
    return bool(interpret)


def round_up(x: int, m: int) -> int:
    return -(-x // m) * m
