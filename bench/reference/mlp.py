"""Plain reference of the ``mlp`` client's local training, from the seed.

It imports nothing of the system under test.  The data is the seeded
synthetic MNIST set and its Dirichlet(alpha) client shards, built here by
the same recipe (three Gaussian blobs per class template plus pixel noise;
one class mixture per client, drawn with replacement), so the same seed
gives the same pixels and the same shards.

One client's local round: ``local_steps`` SGD steps of ``lr`` on the mean
softmax cross-entropy of ``tanh(x @ w1 + b1) @ w2 + b2``, each on a
minibatch of ``batch_size`` rows of its shard picked by
``randint(fold_in(fold_in(fold_in(PRNGKey(seed), client), round), step))``.
The flat parameter vector is ``b1 | b2 | w1 | w2`` (sorted leaf names).
Beside the trained rows it gives each row's loss at its last step, the
number the program reports as ``train_loss``.
Every matrix product runs at the precision it is given: ``"highest"`` is
float32 (``jnp.dot(..., precision="highest")``), and ``"high"`` is the
control, three bfloat16 passes with float32 accumulation
(``hi*hi + hi*lo + lo*hi`` of each operand split into a bfloat16 head and
tail), written out so that it reads the same on a TPU and on a CPU, whose
backend computes every float32 product exactly.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

CHUNK = 64          # rows per reference call (one compiled shape)


def synthetic_mnist(n: int, seed: int, client: int, step: int = 0,
                    side: int = 28, noise: float = 0.25):
    rng = np.random.default_rng(seed)
    templates = np.zeros((10, side, side), np.float32)
    yy, xx = np.mgrid[0:side, 0:side]
    for c in range(10):
        for _ in range(3):
            cy, cx = rng.uniform(4, side - 4, size=2)
            sig = rng.uniform(2.0, 4.0)
            templates[c] += np.exp(
                -((yy - cy) ** 2 + (xx - cx) ** 2) / (2 * sig ** 2))
    templates /= templates.max(axis=(1, 2), keepdims=True)
    rng = np.random.default_rng(seed * 1_000_003 + client * 7919 + step)
    labels = rng.integers(0, 10, size=n)
    x = templates[labels] + rng.normal(
        0, noise, size=(n, side, side)).astype(np.float32)
    return x.reshape(n, -1).astype(np.float32), labels.astype(np.int32)


def dirichlet_shards(labels: np.ndarray, n_clients: int, alpha: float,
                     seed: int, shard_size: int) -> np.ndarray:
    classes = np.unique(labels)
    by_class = {int(c): np.flatnonzero(labels == c) for c in classes}
    rng = np.random.default_rng(seed)
    out = np.empty((n_clients, shard_size), np.int32)
    for i in range(n_clients):
        mix = rng.dirichlet(np.full(len(classes), alpha))
        drawn = rng.choice(len(classes), size=shard_size, p=mix)
        for j, ci in enumerate(drawn):
            pool = by_class[int(classes[ci])]
            out[i, j] = pool[int(rng.integers(len(pool)))]
    return out


@jax.custom_vjp
def _dot_high(a, b):
    bf, f32 = jnp.bfloat16, jnp.float32
    a_hi, b_hi = a.astype(bf), b.astype(bf)
    a_lo = (a - a_hi.astype(f32)).astype(bf)
    b_lo = (b - b_hi.astype(f32)).astype(bf)

    def d(x, y):
        return jnp.dot(x, y, preferred_element_type=f32)
    return d(a_hi, b_hi) + d(a_hi, b_lo) + d(a_lo, b_hi)


def _dot_high_fwd(a, b):
    return _dot_high(a, b), (a, b)


def _dot_high_bwd(res, g):
    a, b = res
    return _dot_high(g, b.T), _dot_high(a.T, g)


_dot_high.defvjp(_dot_high_fwd, _dot_high_bwd)


def dot(a, b, precision: str):
    if precision == "high":
        return _dot_high(a, b)
    return jnp.dot(a, b, precision=precision)


class Reference:
    def __init__(self, config: dict, seed: int):
        a = config["model_args"]
        self.seed = int(seed)
        self.hidden = int(a["hidden"])
        self.steps = int(a["local_steps"])
        self.batch = int(a["batch_size"])
        self.lr = float(a["lr"])
        x, y = synthetic_mnist(int(a["n_train"]), self.seed, client=0)
        shards = dirichlet_shards(y, int(config["n_clients"]),
                                  float(a["alpha"]), self.seed,
                                  int(a["shard_size"]))
        self.x, self.y = jnp.asarray(x), jnp.asarray(y)
        self.shards = jnp.asarray(shards)
        h = self.hidden
        self.layout = [("b1", (h,)), ("b2", (10,)), ("w1", (784, h)),
                       ("w2", (h, 10))]
        self._jitted: dict = {}

    @staticmethod
    def flops_per_sample(config: dict) -> int:
        """Model operations of one sample's SGD step: the forward pass's
        two matrix products (a multiply-add counted as 2 operations), and
        the backward pass counted as twice the forward."""
        h = int(config["model_args"]["hidden"])
        return 3 * 2 * (784 * h + h * 10)

    @classmethod
    def flops_per_update(cls, config: dict) -> int:
        """Model operations of one client's local round: ``local_steps``
        minibatches of ``batch_size`` samples."""
        a = config["model_args"]
        return (cls.flops_per_sample(config) * int(a["local_steps"])
                * int(a["batch_size"]))

    def _unflatten(self, vec):
        out, off = {}, 0
        for name, shape in self.layout:
            size = int(np.prod(shape))
            out[name] = vec[off:off + size].reshape(shape)
            off += size
        return out

    def _flatten(self, p):
        return jnp.concatenate([p[name].reshape(-1)
                                for name, _ in self.layout])

    def _one(self, vec, client, rnd, data, precision):
        xs, ys, shards = data
        p = self._unflatten(vec)
        shard = shards[client]
        key = jax.random.fold_in(
            jax.random.fold_in(jax.random.PRNGKey(self.seed), client), rnd)

        def loss(p, x, y):
            h = jnp.tanh(dot(x, p["w1"], precision) + p["b1"])
            logits = dot(h, p["w2"], precision) + p["b2"]
            logp = jax.nn.log_softmax(logits)
            return -jnp.mean(jnp.take_along_axis(logp, y[:, None], axis=1))

        for k in range(self.steps):
            pick = jax.random.randint(jax.random.fold_in(key, k),
                                      (self.batch,), 0, shard.shape[0])
            rows = shard[pick]
            value, g = jax.value_and_grad(loss)(p, xs[rows], ys[rows])
            p = {n: p[n] - jnp.float32(self.lr) * g[n] for n in p}
        return self._flatten(p), value

    def _batched(self, precision: str):
        if precision not in self._jitted:
            one = functools.partial(self._one, precision=precision)
            self._jitted[precision] = jax.jit(
                jax.vmap(one, in_axes=(0, 0, 0, None)))
        return self._jitted[precision]

    def train(self, stack: np.ndarray, client_idx, round_idx,
              precision: str, losses: bool = False):
        """Rows of ``stack`` trained as clients ``client_idx`` in rounds
        ``round_idx``; returns float32 rows, and with ``losses`` also each
        row's loss at its last step (the minibatch loss before the last
        update, which the program reports as ``train_loss``)."""
        fn = self._batched(precision)
        k = stack.shape[0]
        out = np.empty_like(np.asarray(stack, np.float32))
        last = np.empty(k, np.float64)
        for o in range(0, k, CHUNK):
            n = min(CHUNK, k - o)
            sel = np.r_[o:o + n, np.full(CHUNK - n, o + n - 1)]
            res, loss = fn(jnp.asarray(stack[sel], jnp.float32),
                           jnp.asarray(np.asarray(client_idx)[sel], jnp.int32),
                           jnp.asarray(np.asarray(round_idx)[sel], jnp.int32),
                           (self.x, self.y, self.shards))
            out[o:o + n] = np.asarray(res, np.float32)[:n]
            last[o:o + n] = np.asarray(loss, np.float64)[:n]
        return (out, last) if losses else out
