"""The paper's MNIST MLP as a vmappable :class:`ClientModel`.

A two-layer softmax classifier (784 -> hidden -> 10) trained with K local
SGD steps per round on each client's non-IID dirichlet shard
(:func:`repro.data.mnist.dirichlet_shards`).  The whole local round —
minibatch sampling included — is one pure JAX function of
``(flat_params, client_idx, round_idx)``, so the fleet's ``vmap``/``shard``
train backends batch every client of a round into a single compiled call.

The legacy per-client path (:meth:`MnistMLPModel.train_fn`) runs the *same*
jitted function unbatched, so python-vs-vmap parity is jax-vs-jax and
bounded by a stated tolerance (pinned in ``tests/test_client_compute.py``);
the data and minibatch schedule are keyed only by ``(seed, client_idx,
round_idx)``, never by call order.
"""

from __future__ import annotations

from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.client_compute import ClientModel
from repro.core.packetizer import flatten_to_vector, unflatten_from_vector
from repro.data.mnist import dirichlet_shards, load_mnist


class MnistMLPModel(ClientModel):
    """784 -> hidden -> 10 MLP over per-client dirichlet shards.

    ``download=False`` by default: benchmarks and CI must be hermetic, so
    the seeded synthetic MNIST fallback is the default diet; pass
    ``download=True`` (or ``data_dir=``) to train on the real digits.
    """

    name = "mlp"

    def __init__(self, n_clients: int, *, seed: int = 0, hidden: int = 32,
                 local_steps: int = 4, batch_size: int = 32,
                 lr: float = 0.1, alpha: float = 0.5,
                 n_train: int = 8192, n_test: int = 1024,
                 shard_size: int = 256, download: bool = False,
                 data_dir: str | None = None):
        super().__init__(n_clients, seed=seed)
        self.hidden = int(hidden)
        self.local_steps = int(local_steps)
        self.batch_size = int(batch_size)
        self.lr = float(lr)
        self.data = load_mnist(n_train, n_test, seed=seed,
                               data_dir=data_dir, download=download)
        self.shards = dirichlet_shards(
            self.data.y_train, n_clients, alpha=alpha, seed=seed,
            shard_size=shard_size)
        # Device-resident constants closed over by the jitted step.
        self._x = jnp.asarray(self.data.x_train)
        self._y = jnp.asarray(self.data.y_train)
        self._shards = jnp.asarray(self.shards)
        # Flat-vector layout: tree_leaves order of the params template.
        template = self.init_params()
        leaves, self._treedef = jax.tree_util.tree_flatten(template)
        self._shapes = [leaf.shape for leaf in leaves]
        sizes = [int(np.prod(s)) if s else 1 for s in self._shapes]
        self._offsets = np.cumsum([0] + sizes).tolist()
        self.n_params = self._offsets[-1]
        self._single: Callable | None = None

    # -- ClientModel ------------------------------------------------------
    def init_params(self) -> Any:
        rng = np.random.default_rng(self.seed)
        h = self.hidden
        scale1 = np.sqrt(2.0 / 784.0)
        scale2 = np.sqrt(2.0 / h)
        return {
            "w1": (rng.standard_normal((784, h)) * scale1).astype(np.float32),
            "b1": np.zeros(h, np.float32),
            "w2": (rng.standard_normal((h, 10)) * scale2).astype(np.float32),
            "b2": np.zeros(10, np.float32),
        }

    def loss(self, params: Any) -> float:
        """Mean softmax cross-entropy on the held-out test split."""
        logits = self._forward_np(params, self.data.x_test)
        logits = logits - logits.max(axis=1, keepdims=True)
        logz = np.log(np.exp(logits).sum(axis=1))
        return float(np.mean(
            logz - logits[np.arange(len(logits)), self.data.y_test]))

    def accuracy(self, params: Any) -> float:
        logits = self._forward_np(params, self.data.x_test)
        return float(np.mean(logits.argmax(axis=1) == self.data.y_test))

    def eval_metrics(self, params: Any) -> dict:
        return {"loss": self.loss(params), "accuracy": self.accuracy(params),
                "data_source": self.data.source}

    def train_fn(self, i: int, profile: Any = None) -> Callable:
        if self._single is None:
            self._single = jax.jit(self.jax_train)
        single = self._single
        template = {k: np.asarray(v) for k, v in self.init_params().items()}
        idx = int(i)

        def _train(params: Any, round_idx: int, client: Any
                   ) -> tuple[Any, dict]:
            vec = jnp.asarray(flatten_to_vector(params))
            new, aux = single(vec, jnp.int32(idx), jnp.int32(round_idx))
            tree = unflatten_from_vector(np.asarray(new, np.float32),
                                         template)
            return tree, {k: float(v) for k, v in aux.items()}

        return _train

    def jax_train(self, vec, client_idx, round_idx, frozen=None):
        params = self._unflatten_jax(vec.astype(jnp.float32))
        shard = self._shards[client_idx]              # (shard_size,) indices
        key = jax.random.fold_in(
            jax.random.fold_in(jax.random.PRNGKey(self.seed), client_idx),
            round_idx)

        def step(carry, k):
            p, _ = carry
            bkey = jax.random.fold_in(key, k)
            pick = jax.random.randint(
                bkey, (self.batch_size,), 0, shard.shape[0])
            rows = shard[pick]
            x, y = self._x[rows], self._y[rows]
            loss, grads = jax.value_and_grad(self._ce)(p, x, y)
            p = jax.tree_util.tree_map(
                lambda w, g: w - jnp.float32(self.lr) * g, p, grads)
            return (p, loss), None

        (params, last_loss), _ = jax.lax.scan(
            step, (params, jnp.float32(0.0)),
            jnp.arange(self.local_steps, dtype=jnp.int32))
        return self._flatten_jax(params), {"train_loss": last_loss}

    # -- internals --------------------------------------------------------
    def _ce(self, params, x, y):
        logits = jnp.dot(jnp.tanh(jnp.dot(x, params["w1"]) + params["b1"]),
                         params["w2"]) + params["b2"]
        logp = jax.nn.log_softmax(logits)
        return -jnp.mean(jnp.take_along_axis(logp, y[:, None], axis=1))

    def _forward_np(self, params: Any, x: np.ndarray) -> np.ndarray:
        h = np.tanh(x @ np.asarray(params["w1"]) + np.asarray(params["b1"]))
        return h @ np.asarray(params["w2"]) + np.asarray(params["b2"])

    def _unflatten_jax(self, vec):
        leaves = [vec[a:b].reshape(shape) for a, b, shape in
                  zip(self._offsets, self._offsets[1:], self._shapes)]
        return jax.tree_util.tree_unflatten(self._treedef, leaves)

    def _flatten_jax(self, params):
        return jnp.concatenate(
            [leaf.reshape(-1) for leaf in jax.tree_util.tree_leaves(params)])
