"""Plain reference of the server's fold of one aggregation, in float64.

It imports nothing of the system under test.  Contributions with a weight
of zero or less carry nothing and are left out.  In the delta domain (the
uplink ships ``trained - received``) the new global model is
``g + server_lr * sum(w_i * d_i) / sum(w_i)``; in the weight domain it is
the weighted mean ``sum(w_i * x_i) / sum(w_i)`` (FedAvg, McMahan et al.,
arXiv:1602.05629).
"""

from __future__ import annotations

import numpy as np


def fold(g_old: np.ndarray, rows: list, weights: list, delta_domain: bool,
         server_lr: float) -> np.ndarray:
    keep = [(r, w) for r, w in zip(rows, weights) if w > 0.0]
    if not keep:
        return np.asarray(g_old, np.float64)
    acc = np.zeros(np.asarray(keep[0][0]).size, np.float64)
    total = 0.0
    for r, w in keep:
        acc += float(w) * np.asarray(r, np.float64)
        total += float(w)
    mean = acc / total
    if delta_domain:
        return np.asarray(g_old, np.float64) + float(server_lr) * mean
    return mean
