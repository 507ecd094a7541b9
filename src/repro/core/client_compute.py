"""Vectorized client compute: batched local training behind a registry.

Every fleet round used to optimize its objective client-by-client in a
Python loop (``FLClient.train_fn`` called once per session timer).  This
module batches the *client dimension* instead:

* :class:`ClientModel` — a registered model family
  (``register_model`` / ``make_model`` / ``available_models``) that can
  train one client the legacy way (``train_fn(i)`` — a per-client callable,
  bit-identical to the historical path) **and** as a pure, vmappable JAX
  function over a flat parameter vector (``jax_train``).  Built-ins:
  ``"consensus"`` (the analytic quadratic objective the fleet benchmarks
  always used) and ``"mlp"`` (the paper's MNIST MLP —
  ``repro.models.mlp`` over ``repro.data.mnist`` non-IID dirichlet shards).
* :class:`TrainBackend` — how a batch of pending training steps executes
  (``register_train_backend`` / ``make_train_backend``):
  ``"python"`` loops the per-client callables (today's path), ``"vmap"``
  runs one ``jax.jit(jax.vmap(...))`` call over the stacked batch,
  ``"shard"`` additionally ``shard_map``s the batch over the local device
  mesh (``repro.distributed.fl_mesh.client_mesh``) and falls back to vmap
  on a single device.
* :class:`BatchTrainer` — the orchestrator glue.  ``ServerCore`` (and the
  hierarchical :class:`~repro.core.topology.CellScheduler` cells through
  their nested cores, and :class:`~repro.core.topology.GossipSystem`)
  *submit* a session's training input the moment its model is delivered
  and *collect* the result when the session's training timer fires.
  Because local training is deterministic and per-client independent, the
  trainer may compute any pending set in one batched call without
  changing a single event: the first timer to fire flushes everything
  submitted so far — in a typical round that is the whole roster, so K
  clients train as one vmapped batch while the simulator still observes
  per-client completion times.

The default path is untouched: with no trainer attached,
``ServerCore.schedule_training`` runs the exact historical per-client
code, pinned by the 24 orchestrator-equivalence digests.
"""

from __future__ import annotations

import abc
from typing import Any, Callable, Optional

import numpy as np
from jax import tree_util

from repro.core import tracing
from repro.core.packetizer import flatten_to_vector, unflatten_from_vector


# --------------------------------------------------------------------------
# The model contract + registry
# --------------------------------------------------------------------------
class ClientModel(abc.ABC):
    """A model family the fleet can train: per-client or batched.

    Implementations expose the *same* local training step two ways, and
    ``tests/test_client_compute.py`` pins that they agree (bit-identical
    for the python loop vs itself; tolerance-bounded python-vs-vmap):

    * :meth:`train_fn` — ``(params_tree, round_idx, client) -> (tree,
      metrics)``, the historical per-client callable handed to
      :class:`~repro.core.server.FLClient`.
    * :meth:`jax_train` — ``(flat_vec, client_idx, round_idx, frozen) ->
      (flat_vec', aux)``, pure and vmappable over the first three
      arguments (``aux`` is a dict of scalar training metrics).

    ``frozen`` is what :meth:`frozen` returns: device arrays every client's
    step reads and none trains (a frozen base model).  Every backend hands
    them to its jitted step as an argument shared by the batch
    (``in_axes=None``), never as constants, so they are not copied into the
    compiled program and one compile serves every value of the same shape.
    Models with nothing frozen return None.  A model may also give its own
    batched step (:meth:`jax_train_batch`); by default it is
    ``jax.vmap(jax_train)``.  The ``aux`` keys named in :attr:`counters`
    are counts, added to the program's counters (``repro.core.tracing``)
    over the real rows of every step.
    """

    name: str = "abstract"
    #: ``aux`` keys that are counts for ``repro.core.tracing``.
    counters: tuple[str, ...] = ()

    def __init__(self, n_clients: int, *, seed: int = 0):
        self.n_clients = int(n_clients)
        self.seed = int(seed)

    @abc.abstractmethod
    def init_params(self) -> Any:
        """The global model template (numpy pytree, float32 leaves)."""

    @abc.abstractmethod
    def loss(self, params: Any) -> float:
        """Global objective value (lower is better)."""

    def eval_metrics(self, params: Any) -> dict:
        """Benchmark-facing evaluation record (subclasses extend)."""
        return {"loss": self.loss(params)}

    @abc.abstractmethod
    def train_fn(self, i: int, profile: Any = None) -> Callable:
        """The i-th client's legacy per-client training callable."""

    @abc.abstractmethod
    def jax_train(self, vec, client_idx, round_idx, frozen=None):
        """One client's local training as a pure JAX function."""

    def frozen(self) -> Any:
        """Device arrays shared by every client's step, or None."""
        return None

    def jax_train_batch(self, stack, client_idx, round_idx, frozen):
        """The local round of a batch of clients (rows of ``stack``)."""
        import jax
        return jax.vmap(self.jax_train, in_axes=(0, 0, 0, None))(
            stack, client_idx, round_idx, frozen)


_MODELS: dict[str, Callable[..., ClientModel]] = {}


def register_model(name: str, factory: Callable[..., ClientModel], *,
                   overwrite: bool = False) -> None:
    """Register a model factory (the transport/topology registry idiom:
    silent shadowing of a built-in would invalidate benchmarks)."""
    if not overwrite and name in _MODELS:
        raise ValueError(f"model {name!r} is already registered "
                         f"(pass overwrite=True to replace it)")
    _MODELS[name] = factory


def make_model(name: str, n_clients: int, *, seed: int = 0,
               **kwargs) -> ClientModel:
    try:
        factory = _MODELS[name]
    except KeyError:
        raise ValueError(f"unknown model {name!r}; registered models: "
                         f"{available_models()}") from None
    return factory(n_clients, seed=seed, **kwargs)


def available_models() -> list[str]:
    return sorted(_MODELS)


# --------------------------------------------------------------------------
# Built-in model: the analytic consensus objective
# --------------------------------------------------------------------------
class ConsensusModel(ClientModel):
    """:class:`~repro.core.fleet.ConsensusObjective` as a registered model.

    The python path delegates to the objective's own ``train_fn`` — the
    byte-for-byte historical fleet workload — while :meth:`jax_train`
    expresses the same ``w + lr * (c_k - w)`` step over the stacked target
    matrix for the vmap/shard backends.
    """

    name = "consensus"

    def __init__(self, n_clients: int, *, seed: int = 0,
                 n_params: int = 1024, lr: float = 0.5,
                 heterogeneity: float = 0.1):
        from repro.core.fleet import ConsensusObjective
        super().__init__(n_clients, seed=seed)
        self.objective = ConsensusObjective(
            n_clients, n_params, seed=seed, lr=lr, heterogeneity=heterogeneity)

    def init_params(self) -> Any:
        return self.objective.init_params()

    def loss(self, params: Any) -> float:
        return self.objective.loss(params)

    def train_fn(self, i: int, profile: Any = None) -> Callable:
        return self.objective.train_fn(i, profile)

    def jax_train(self, vec, client_idx, round_idx, frozen=None):
        import jax.numpy as jnp
        targets = jnp.asarray(self.objective.targets)
        target = targets[client_idx]
        w = vec.astype(jnp.float32)
        new = w + jnp.float32(self.objective.lr) * (target - w)
        return new, {"local_gap": jnp.mean((w - target) ** 2)}


register_model("consensus", ConsensusModel)


def _mlp_factory(n_clients: int, *, seed: int = 0, **kwargs) -> ClientModel:
    # Lazy: repro.models.mlp imports jax at module load; keep that off the
    # critical import path of the pure-simulator layers.
    from repro.models.mlp import MnistMLPModel
    return MnistMLPModel(n_clients, seed=seed, **kwargs)


register_model("mlp", _mlp_factory)


def _lm_factory(n_clients: int, *, seed: int = 0, **kwargs) -> ClientModel:
    from repro.models.lm_client import LMClientModel
    return LMClientModel(n_clients, seed=seed, **kwargs)


register_model("lm", _lm_factory)


def _lm_mla_factory(n_clients: int, *, seed: int = 0,
                    **kwargs) -> ClientModel:
    from repro.models.lm_client import MLAClientModel
    return MLAClientModel(n_clients, seed=seed, **kwargs)


register_model("lm_mla", _lm_mla_factory)


# --------------------------------------------------------------------------
# Train backends
# --------------------------------------------------------------------------
class TrainBackend(abc.ABC):
    """Executes a batch of independent local-training steps.

    ``train(model, stack, client_idx, round_idx)`` takes the K pending
    steps as a stacked float32 matrix ``(K, n_params)`` plus int32 vectors
    of client indices and round numbers, and returns ``(new_stack,
    metrics)`` where ``metrics`` is one dict per row.

    A caller may stage its rows in the backend's own reused buffer
    instead of stacking them: ``staging(K, n_params)`` hands out the
    buffer's first K rows, with at least ``padded_rows(K)`` rows behind
    them, and ``train`` on exactly those rows runs the padded step over
    the buffer in place.  Padding rows hold whatever the buffer last held;
    rows are independent, so their contents never reach a real row.
    """

    name: str = "abstract"
    _stage: Optional[np.ndarray] = None

    @abc.abstractmethod
    def train(self, model: ClientModel, stack: np.ndarray,
              client_idx: np.ndarray, round_idx: np.ndarray
              ) -> tuple[np.ndarray, list[dict]]:
        ...

    def padded_rows(self, k: int) -> int:
        """Rows the step runs for a batch of ``k``."""
        return k

    def staging(self, k: int, n_params: int) -> np.ndarray:
        """The first ``k`` rows of the reused float32 staging buffer.

        The buffer is allocated on first use and grown by doubling to
        ``padded_rows(k)``, never shrunk; each (re)allocation counts
        ``train.stage_allocs``."""
        rows = self.padded_rows(k)
        buf = self._stage
        if buf is None or buf.shape[1] != n_params:
            cap = rows
        elif buf.shape[0] < rows:
            cap = max(rows, 2 * buf.shape[0])
        else:
            return buf[:k]
        buf = self._stage = np.zeros((cap, n_params), np.float32)
        tracing.count("train.stage_allocs")
        return buf[:k]

    def _is_staged(self, stack: np.ndarray) -> bool:
        """Whether ``stack`` is the leading rows of the staging buffer."""
        buf = self._stage
        return (buf is not None and stack.base is buf
                and stack.strides == buf.strides
                and stack.shape[1] == buf.shape[1]
                and stack.ctypes.data == buf.ctypes.data)


class PythonLoopBackend(TrainBackend):
    """Today's path: one ``train_fn`` call per client, in batch order.

    Bit-identical to the historical per-session training (it calls the
    very same callables), which is why it is the default everywhere the
    replay digests are pinned.
    """

    name = "python"

    def __init__(self) -> None:
        self._fns: dict[tuple[int, int], Callable] = {}
        self._template: dict[int, Any] = {}

    def _fn(self, model: ClientModel, i: int) -> Callable:
        key = (id(model), i)
        fn = self._fns.get(key)
        if fn is None:
            fn = self._fns[key] = model.train_fn(i)
        return fn

    def train(self, model, stack, client_idx, round_idx):
        template = self._template.get(id(model))
        if template is None:
            template = self._template[id(model)] = model.init_params()
        out = np.empty_like(stack)
        metrics: list[dict] = []
        for j in range(stack.shape[0]):
            tree = unflatten_from_vector(stack[j], template)
            new_tree, m = self._fn(model, int(client_idx[j]))(
                tree, int(round_idx[j]), None)
            out[j] = flatten_to_vector(new_tree)
            metrics.append(m)
        return out, metrics


def _aux_to_rows(aux: dict, k: int) -> list[dict]:
    """Split a dict of (K,)-arrays into K per-row metric dicts of floats."""
    cols = {key: np.asarray(val, np.float64)[:k].tolist()
            for key, val in aux.items()}
    return [{key: col[j] for key, col in cols.items()} for j in range(k)]


def _next_pow2(k: int) -> int:
    return 1 << max(0, (k - 1).bit_length())


_STEP_SPAN = tracing.span("train.step")


class VmapBackend(TrainBackend):
    """One jitted ``model.jax_train_batch`` call per flush (by default
    ``jax.vmap(model.jax_train)``), with ``model.frozen()`` passed in as an
    argument shared by the batch.

    Batches are padded to the next power of two so a fleet with varying
    roster sizes compiles O(log K) programs instead of one per distinct
    K.  The padding rows are the staging buffer's rows after the batch
    (a stack handed in from elsewhere is copied there first); the index
    vectors repeat their last entry; padded outputs are discarded.
    """

    name = "vmap"

    def __init__(self) -> None:
        self._jitted: dict[int, Callable] = {}

    def _batched(self, model: ClientModel) -> Callable:
        fn = self._jitted.get(id(model))
        if fn is None:
            import jax
            fn = self._jitted[id(model)] = jax.jit(model.jax_train_batch)
        return fn

    def padded_rows(self, k: int) -> int:
        return _next_pow2(k)

    def train(self, model, stack, client_idx, round_idx):
        k, n_params = stack.shape
        kp = self.padded_rows(k)
        if kp != k:
            if not self._is_staged(stack):
                self.staging(k, n_params)[...] = stack
            stack = self._stage[:kp]
            client_idx = np.pad(client_idx, (0, kp - k), mode="edge")
            round_idx = np.pad(round_idx, (0, kp - k), mode="edge")
        return self._step(model, stack, client_idx, round_idx, k)

    def _step(self, model, stack, client_idx, round_idx, k: int):
        """The jitted call on a padded batch whose first ``k`` rows are
        real: the ``train.step`` span, from the inputs' copy to the device
        until the trained stack is numpy, and the rows and bytes it moved
        (``train.rows``, ``train.pad_rows``, ``device.h2d_bytes``,
        ``device.d2h_bytes``), and the model's own counts over the real
        rows (``model.counters``).

        It returns only once the results are on the host, so no transfer
        still reads ``stack`` afterwards: that is what lets the staging
        buffer be written again by the next flush."""
        import jax.numpy as jnp
        with _STEP_SPAN:
            new, aux = self._batched(model)(
                jnp.asarray(stack, jnp.float32),
                jnp.asarray(client_idx, jnp.int32),
                jnp.asarray(round_idx, jnp.int32), model.frozen())
            new = np.asarray(new, np.float32)
            aux = {key: np.asarray(val) for key, val in aux.items()}
        tracing.count("train.rows", k)
        tracing.count("train.pad_rows", stack.shape[0] - k)
        tracing.count("device.h2d_bytes",
                      4 * (stack.size + client_idx.size + round_idx.size))
        tracing.count("device.d2h_bytes",
                      new.nbytes + sum(v.nbytes for v in aux.values()))
        for name in model.counters:
            tracing.count(name, int(aux[name][:k].sum()))
        return new[:k], _aux_to_rows(aux, k)


class ShardBackend(VmapBackend):
    """The batched step sharded over the local device mesh (``clients``
    axis).

    With one device (the CI case) this is exactly :class:`VmapBackend`;
    with D devices the padded batch is split D ways via ``shard_map`` so
    each device trains K/D clients, the frozen arrays replicated on each.
    """

    name = "shard"

    def __init__(self) -> None:
        super().__init__()
        self._sharded: dict[int, Callable] = {}

    def _batched(self, model: ClientModel) -> Callable:
        import jax
        if jax.device_count() <= 1:
            return super()._batched(model)
        fn = self._sharded.get(id(model))
        if fn is None:
            from jax.sharding import PartitionSpec as P
            from repro.distributed.fl_mesh import client_mesh
            mesh = client_mesh()
            spec = P("clients")
            fn = self._sharded[id(model)] = jax.jit(jax.shard_map(
                model.jax_train_batch, mesh=mesh,
                in_specs=(spec, spec, spec, P()),
                out_specs=(spec, spec),
                check_vma=False))
        return fn

    def padded_rows(self, k: int) -> int:
        # A device multiple (shard_map needs an even split) of the pow2
        # size, for jit stability.
        import jax
        d = jax.device_count()
        kp = _next_pow2(k)
        if d <= 1:
            return kp
        return -(-max(d, kp) // d) * d


_TRAIN_BACKENDS: dict[str, Callable[[], TrainBackend]] = {}


def register_train_backend(name: str, factory: Callable[[], TrainBackend],
                           *, overwrite: bool = False) -> None:
    if not overwrite and name in _TRAIN_BACKENDS:
        raise ValueError(f"train backend {name!r} is already registered "
                         f"(pass overwrite=True to replace it)")
    _TRAIN_BACKENDS[name] = factory


def make_train_backend(name: str) -> TrainBackend:
    try:
        factory = _TRAIN_BACKENDS[name]
    except KeyError:
        raise ValueError(
            f"unknown train backend {name!r}; registered backends: "
            f"{available_train_backends()}") from None
    return factory()


def available_train_backends() -> list[str]:
    return sorted(_TRAIN_BACKENDS)


register_train_backend("python", PythonLoopBackend)
register_train_backend("vmap", VmapBackend)
register_train_backend("shard", ShardBackend)


# --------------------------------------------------------------------------
# The orchestrator glue: submit at delivery, collect at the timer
# --------------------------------------------------------------------------
class BatchTrainer:
    """Opportunistic batching without touching the event calendar.

    A session's training *input* is fully known the moment its downlink
    delivers (``ServerCore.schedule_training`` runs then); only the
    *result* is deferred by ``train_time_ns``.  So the core submits the
    input immediately and collects at the timer — and because every local
    step is deterministic and independent, ``collect`` may flush all
    currently-pending submissions as one backend call without perturbing
    any event time or order.  In a sync round the whole roster's downlinks
    usually land before the fastest client finishes training, so the first
    ``collect`` trains the entire round in one vmapped batch; stragglers
    whose models arrive later simply join the next flush.
    """

    def __init__(self, model: ClientModel, backend: TrainBackend,
                 client_index: dict[str, int]):
        self.model = model
        self.backend = backend
        self.client_index = dict(client_index)
        leaves, self._treedef = tree_util.tree_flatten(model.init_params())
        self._layout: list[tuple[int, int, tuple, np.dtype]] = []
        off = 0
        for leaf in leaves:
            leaf = np.asarray(leaf)
            self._layout.append((off, off + leaf.size, leaf.shape, leaf.dtype))
            off += leaf.size
        self._n_params = off
        self._pending: list[tuple[Any, np.ndarray, int, int]] = []
        self._results: dict[Any, tuple[Any, Any, dict]] = {}
        #: Flush sizes, newest last — benchmarks read this to report how
        #: much batching the event schedule actually allowed.
        self.batch_sizes: list[int] = []

    def submit(self, key: Any, addr: str, params_tree: Any,
               round_idx: int) -> None:
        """Register one session's training input (model just delivered)."""
        if key in self._results:
            raise RuntimeError(f"duplicate submit for session key {key!r}")
        try:
            idx = self.client_index[addr]
        except KeyError:
            raise KeyError(f"no model client index for {addr!r}") from None
        self._pending.append((key, params_tree, idx, int(round_idx)))

    @tracing.span("train.flush")
    def flush(self) -> None:
        """Train every pending submission as one backend call.

        Each received tree's leaves are copied once, straight into a row
        of the backend's staging buffer; each trained tree's leaves are
        views of one row of the step's output, which is fresh per flush
        (read-only from a jitted step, so a consumer cannot write into a
        neighbour's model by accident)."""
        if not self._pending:
            return
        pending, self._pending = self._pending, []
        k = len(pending)
        rows = self.backend.staging(k, self._n_params)
        for row, (_, tree, _, _) in zip(rows, pending):
            self._stage_row(row, tree)
        client_idx = np.fromiter((i for _, _, i, _ in pending), np.int32, k)
        round_idx = np.fromiter((r for _, _, _, r in pending), np.int32, k)
        new_stack, metrics = self.backend.train(
            self.model, rows, client_idx, round_idx)
        if np.may_share_memory(new_stack, rows.base):
            # A step that hands its input back: the next flush rewrites
            # the buffer, so these rows need their own memory.
            new_stack = new_stack.copy()
        self.batch_sizes.append(k)
        for j, (key, tree, _, _) in enumerate(pending):
            self._results[key] = (tree, self._unflatten(new_stack[j]),
                                  metrics[j])

    def _stage_row(self, row: np.ndarray, tree: Any) -> None:
        """Write ``tree``'s leaves into ``row`` back to back, in
        ``tree_leaves`` order (``flatten_to_vector``'s layout)."""
        leaves = [np.asarray(leaf) for leaf in tree_util.tree_leaves(tree)]
        n = sum(leaf.size for leaf in leaves)
        if n != row.size:
            raise ValueError(f"tree has {n} params, the model needs "
                             f"{row.size}")
        off = 0
        for leaf in leaves:
            row[off:off + leaf.size] = leaf.reshape(-1)
            off += leaf.size

    def _unflatten(self, vec: np.ndarray) -> Any:
        """A pytree shaped like the model's template over ``vec``."""
        return tree_util.tree_unflatten(self._treedef, [
            vec[a:b].reshape(shape).astype(dtype, copy=False)
            for a, b, shape, dtype in self._layout])

    def collect(self, key: Any) -> tuple[Any, Any, dict]:
        """(received_tree, trained_tree, metrics) for a submitted key."""
        if key not in self._results:
            self.flush()
        try:
            return self._results.pop(key)
        except KeyError:
            raise KeyError(f"session key {key!r} was never submitted") from \
                None


def attach_trainer(system: Any, trainer: BatchTrainer) -> int:
    """Wire ``trainer`` into every training site of a built system.

    Returns the number of cores/systems wired: a star's single
    ``ServerCore``, every hierarchical edge cell's nested core (the root
    never trains — its "training" is the cell round), or the gossip
    system itself.
    """
    from repro.core.rounds import FederatedSystem
    from repro.core.topology import GossipSystem, HierSystem
    if isinstance(system, FederatedSystem):
        system.core.batch_trainer = trainer
        return 1
    if isinstance(system, HierSystem):
        for edge in system.edges:
            edge.core.batch_trainer = trainer
        return len(system.edges)
    if isinstance(system, GossipSystem):
        system.batch_trainer = trainer
        return 1
    raise TypeError(f"don't know how to attach a trainer to "
                    f"{type(system).__name__}")
