"""Plain reference of the ``consensus`` client step, from the seed.

It imports nothing of the system under test.  Client ``k`` holds the
private target ``c_k = c + heterogeneity * e_k``, with ``c`` and the
``e_k`` standard normal draws of ``default_rng(seed)`` (``c`` first, then
the ``(n_clients, n_params)`` noise matrix), and one local step moves the
received model toward it: ``w' = w + lr * (c_k - w)`` in float32.  The
step has no matrix product, so its control is the same step computed in
bfloat16.
"""

from __future__ import annotations

import numpy as np


class Reference:
    def __init__(self, config: dict, seed: int):
        a = config["model_args"]
        n, p = int(config["n_clients"]), int(a["n_params"])
        rng = np.random.default_rng(int(seed))
        common = rng.standard_normal((1, p))
        noise = rng.standard_normal((n, p))
        self.targets = (common + float(a["heterogeneity"]) * noise
                        ).astype(np.float32)
        self.lr = float(a["lr"])

    def train(self, stack: np.ndarray, client_idx, round_idx,
              precision: str) -> np.ndarray:
        w = np.asarray(stack, np.float32)
        t = self.targets[np.asarray(client_idx)]
        if precision == "bfloat16":
            import jax.numpy as jnp
            bf = jnp.bfloat16
            wb, tb = jnp.asarray(w, bf), jnp.asarray(t, bf)
            return np.asarray(wb + bf(self.lr) * (tb - wb), np.float32)
        return w + self.lr * (t - w)
