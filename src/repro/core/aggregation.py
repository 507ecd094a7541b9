"""Model-parameter aggregation strategies.

The paper's Algorithm III / Eq. (1) is the sequential pairwise average
``new_i = (Client_i + Server_i) / 2`` applied per arriving client. That is
implemented faithfully (``pairwise_average``), alongside the principled
weighted FedAvg (McMahan et al., 2017) and a trimmed mean for robustness —
both of which the framework defaults to at scale.

All strategies operate on parameter pytrees. The flat-vector fast path (used
by the benchmark harness and backed by the Pallas ``fedavg`` kernel) lives in
``repro.kernels.fedavg.ops``.
"""

from __future__ import annotations

from typing import Any, Optional, Sequence

import jax
import numpy as np

from repro.core import tracing


def pairwise_average(server_tree: Any, client_tree: Any) -> Any:
    """Paper Eq. (1): AggregatedParameters = (Client + Server) / 2.

    Order-dependent when folded over multiple clients — exactly as the paper
    applies it (per-transaction, as each client's packets complete).
    """
    return jax.tree_util.tree_map(
        lambda s, c: (np.asarray(s, dtype=np.float32)
                      + np.asarray(c, dtype=np.float32)) / 2.0,
        server_tree, client_tree)


FEDAVG_BACKENDS = ("numpy", "kernel", "auto")


def _kernel_ops():
    # Imported on first use: the kernel module imports repro.core (for the
    # flat-vector helpers), so a top-level import here would be circular.
    from repro.kernels.fedavg import ops as kernel_ops
    return kernel_ops


def fedavg(trees: Sequence[Any], weights: Optional[Sequence[float]] = None,
           backend: str = "numpy") -> Any:
    """Weighted FedAvg. Weights default to uniform; normally |D_k|/|D|.

    ``backend`` selects the implementation:

    * ``"numpy"`` (default) — the per-leaf float32 accumulation below.
      Digest-stable: every replay test pins this path bit-for-bit.
    * ``"kernel"`` — the fused Pallas kernel
      (``repro.kernels.fedavg.ops.fedavg_trees``): native on a TPU, the
      Pallas interpreter elsewhere.
    * ``"auto"`` — the kernel (JAX is a hard dependency).

    The two backends mirror each other to ~1 ULP
    (``tests/test_kernel_parity.py`` enforces the docstring claim) but are
    **not** bit-identical — the kernel reduces over clients in one fused
    pass while numpy accumulates sequentially — which is why the
    orchestrator defaults to numpy: replay digests must not depend on the
    platform.
    """
    if not trees:
        raise ValueError("fedavg of zero clients")
    if backend not in FEDAVG_BACKENDS:
        raise ValueError(f"unknown fedavg backend {backend!r}; "
                         f"one of {FEDAVG_BACKENDS}")
    if backend != "numpy":
        ws = [1.0] * len(trees) if weights is None else list(weights)
        return _kernel_ops().fedavg_trees(trees, ws)
    if weights is None:
        weights = [1.0] * len(trees)
    w = np.asarray(weights, dtype=np.float32)
    w = w / w.sum()

    def _avg(*leaves):
        acc = np.zeros_like(np.asarray(leaves[0], dtype=np.float32))
        for wi, leaf in zip(w, leaves):
            acc += wi * np.asarray(leaf, dtype=np.float32)
        return acc

    return jax.tree_util.tree_map(_avg, *trees)


@tracing.span("aggregate.fedavg")
def fedavg_stack(stack: np.ndarray,
                 weights: Optional[Sequence[float]] = None,
                 backend: str = "numpy") -> np.ndarray:
    """Weighted FedAvg over a flat update stack ``(K, P) -> (P,)``.

    The batched twin of :func:`fedavg`, used by the orchestrator now that
    contributions arrive as flat wire vectors: one accumulation over the
    stack and a single unflatten replaces K per-leaf tree folds.  The
    ``"numpy"`` path accumulates ``acc += w_i * row_i`` in the same order
    and dtype as the tree path — elementwise ops on a concatenation equal
    the ops on its slices, so it is **bit-identical** to per-leaf
    accumulation and digest-safe.  ``"kernel"``/``"auto"`` route to the
    fused Pallas kernel (``fedavg_flat``), ~1 ULP off and therefore never
    the default (``tests/test_kernel_parity.py`` pins both claims).
    """
    stack = np.asarray(stack, dtype=np.float32)
    if stack.ndim != 2 or stack.shape[0] == 0:
        raise ValueError(f"fedavg_stack needs a non-empty (K, P) stack, "
                         f"got shape {stack.shape}")
    if backend not in FEDAVG_BACKENDS:
        raise ValueError(f"unknown fedavg backend {backend!r}; "
                         f"one of {FEDAVG_BACKENDS}")
    if backend != "numpy":
        ws = ([1.0] * stack.shape[0] if weights is None
              else [float(w) for w in weights])
        out = np.asarray(_kernel_ops().fedavg_flat(stack, ws),
                         dtype=np.float32)
        tracing.count("device.h2d_bytes", stack.nbytes + 4 * len(ws))
        tracing.count("device.d2h_bytes", out.nbytes)
        return out
    if weights is None:
        weights = [1.0] * stack.shape[0]
    w = np.asarray(weights, dtype=np.float32)
    w = w / w.sum()
    acc = np.zeros(stack.shape[1], dtype=np.float32)
    for wi, row in zip(w, stack):
        acc += wi * row
    return acc


def trimmed_mean(trees: Sequence[Any], trim_fraction: float = 0.1) -> Any:
    """Coordinate-wise trimmed mean — robust to Byzantine/outlier clients."""
    k = int(len(trees) * trim_fraction)

    def _tm(*leaves):
        stack = np.stack([np.asarray(l, dtype=np.float32) for l in leaves])
        stack.sort(axis=0)
        sl = stack[k:len(trees) - k] if len(trees) - 2 * k > 0 else stack
        return sl.mean(axis=0)

    return jax.tree_util.tree_map(_tm, *trees)


def apply_delta(global_tree: Any, delta_tree: Any, server_lr: float = 1.0
                ) -> Any:
    """global + lr * delta (delta-transmission mode)."""
    return jax.tree_util.tree_map(
        lambda g, d: np.asarray(g, dtype=np.float32)
        + server_lr * np.asarray(d, dtype=np.float32),
        global_tree, delta_tree)


def tree_sub(a: Any, b: Any) -> Any:
    return jax.tree_util.tree_map(
        lambda x, y: np.asarray(x, dtype=np.float32)
        - np.asarray(y, dtype=np.float32), a, b)


AGGREGATORS = {
    "pairwise": "sequential pairwise average (paper Eq. 1)",
    "fedavg": "weighted federated averaging (McMahan et al.)",
    "trimmed_mean": "coordinate-wise trimmed mean (robust)",
}
