"""Backend parity for vectorized client compute.

The contract under test (``repro.core.client_compute``):

* the ``python`` train backend is the historical per-client path — with no
  trainer attached the orchestrator byte-replays every pinned digest;
* the ``vmap``/``shard`` backends produce the *same rounds* — identical
  rosters, arrivals and event ordering, parameters equal to within an
  explicit mixed bound (ULPs per element plus a floor tied to the vector's
  scale) — across seeds x transports x sync/async x topology;
* the MNIST data layer is deterministic offline (the CI bugfix), and the
  dirichlet sharder is seeded and actually non-IID.
"""

import dataclasses
import os
import sys

import numpy as np
import pytest

jax = pytest.importorskip("jax")

from repro.core import (CohortSpec, FleetConfig, FLConfig,  # noqa: E402
                        TransportConfig, build_fleet_training, tracing)
from repro.core.client_compute import (BatchTrainer,       # noqa: E402
                                       ConsensusModel, TrainBackend,
                                       available_models,
                                       available_train_backends, make_model,
                                       make_train_backend, register_model,
                                       register_train_backend)
from repro.core.fleet import ConsensusObjective            # noqa: E402
from repro.core.packetizer import (flatten_to_vector,      # noqa: E402
                                   unflatten_from_vector)
from repro.data.mnist import (SyntheticMnist,              # noqa: E402
                              dirichlet_shards, load_mnist)

sys.path.insert(0, os.path.dirname(__file__))
from test_orchestrator_equivalence import EXPECTED, run_digest  # noqa: E402

# The explicit parity bound: python-vs-vmap parameters agree elementwise
# to ULP_BOUND float32 ULPs of each element, plus an absolute floor of
# FLOOR_ULPS ULPs of the vector's largest magnitude.  Single and batched
# calls are the same arithmetic, but XLA may order a matmul's reductions
# differently under vmap, and a reordered sum moves by a few ULPs of its
# largest *terms*, not of the result: an element near zero (a sum of
# terms that cancel) can differ by far more than its own ULPs while the
# vector's scale bounds the difference.  Events (rosters, arrivals,
# durations) stay exactly equal.
ULP_BOUND = 4
FLOOR_ULPS = 4

NS = 1_000_000_000


def assert_ulp_close(a: np.ndarray, b: np.ndarray, bound: int = ULP_BOUND,
                     floor_ulps: int = FLOOR_ULPS):
    a = np.asarray(a, np.float32)
    b = np.asarray(b, np.float32)
    mag = np.maximum(np.abs(a), np.abs(b))
    floor = floor_ulps * np.spacing(np.float32(mag.max(initial=0.0)))
    tol = bound * np.spacing(mag) + floor
    diff = np.abs(a - b)
    assert np.all(diff <= tol), (
        f"parity beyond {bound} ULP + {floor_ulps} ULP of max|x|: max diff "
        f"{diff.max()} at tol {tol.flat[np.argmax(diff - tol)]}")


# --------------------------------------------------------------------------
# Registries
# --------------------------------------------------------------------------
class TestRegistries:
    def test_builtins_present(self):
        assert "consensus" in available_models()
        assert "mlp" in available_models()
        assert set(available_train_backends()) >= {"python", "vmap", "shard"}

    def test_unknown_names_raise(self):
        with pytest.raises(ValueError, match="unknown model"):
            make_model("resnet900", 4)
        with pytest.raises(ValueError, match="unknown train backend"):
            make_train_backend("cuda")

    def test_shadowing_refused(self):
        with pytest.raises(ValueError, match="already registered"):
            register_model("consensus", ConsensusModel)
        with pytest.raises(ValueError, match="already registered"):
            register_train_backend(
                "python", lambda: make_train_backend("python"))

    def test_fleet_config_validates(self):
        with pytest.raises(ValueError, match="unknown model"):
            FleetConfig(n_clients=4, model="resnet900")
        with pytest.raises(ValueError, match="unknown train backend"):
            FleetConfig(n_clients=4, train_backend="cuda")
        with pytest.raises(ValueError, match="model_args"):
            FleetConfig(n_clients=4, model_args={"hidden": 8})


# --------------------------------------------------------------------------
# ConsensusModel == ConsensusObjective, bit for bit
# --------------------------------------------------------------------------
class TestConsensusModel:
    def test_bit_identical_to_objective(self):
        model = make_model("consensus", 6, seed=3, n_params=128)
        obj = ConsensusObjective(6, 128, seed=3)
        np.testing.assert_array_equal(model.init_params()["w"],
                                      obj.init_params()["w"])
        params = {"w": np.linspace(-1, 1, 128, dtype=np.float32)}
        for i in (0, 5):
            got, gm = model.train_fn(i)(params, 0, None)
            want, wm = obj.train_fn(i)(params, 0, None)
            np.testing.assert_array_equal(got["w"], want["w"])
            assert gm == wm
        assert model.loss(params) == obj.loss(params)


# --------------------------------------------------------------------------
# Compute-level backend parity
# --------------------------------------------------------------------------
@pytest.mark.parametrize("model_name", ["consensus", "mlp"])
def test_backend_parity_compute_level(model_name):
    kwargs = ({"n_params": 96} if model_name == "consensus"
              else {"n_train": 512, "n_test": 128, "shard_size": 32,
                    "hidden": 16})
    model = make_model(model_name, 8, seed=0, **kwargs)
    vec0 = flatten_to_vector(model.init_params())
    rng = np.random.default_rng(7)
    stack = (vec0[None] + 0.01 * rng.standard_normal(
        (8, vec0.size))).astype(np.float32)
    ci = np.arange(8, dtype=np.int32)
    ri = np.asarray([0, 0, 1, 1, 2, 2, 3, 3], np.int32)
    out_py, met_py = make_train_backend("python").train(model, stack, ci, ri)
    out_vm, met_vm = make_train_backend("vmap").train(model, stack, ci, ri)
    out_sh, met_sh = make_train_backend("shard").train(model, stack, ci, ri)
    assert_ulp_close(out_py, out_vm)
    # shard falls back to vmap on one device: exactly equal there, and
    # still ULP-bounded vs python on any mesh.
    assert_ulp_close(out_py, out_sh)
    assert len(met_py) == len(met_vm) == 8
    for a, b in zip(met_py, met_vm):
        assert set(a) == set(b)
        for key in a:
            assert_ulp_close(np.float32(a[key]), np.float32(b[key]),
                             bound=64)  # scalar summaries, looser


def test_vmap_padding_is_invisible(n=5):
    # 5 rows pad to 8 under the pow2 rule; padded outputs must not leak.
    model = make_model("consensus", n, seed=1, n_params=64)
    stack = np.tile(flatten_to_vector(model.init_params()), (n, 1))
    ci = np.arange(n, dtype=np.int32)
    ri = np.zeros(n, np.int32)
    out, met = make_train_backend("vmap").train(model, stack, ci, ri)
    assert out.shape == (n, 64) and len(met) == n
    out_py, _ = make_train_backend("python").train(model, stack, ci, ri)
    assert_ulp_close(out_py, out)


# --------------------------------------------------------------------------
# Fleet-level parity: identical rounds across the scenario matrix.
# Fleet construction comes from the shared ``training_fleet`` fixture in
# conftest.py.
# --------------------------------------------------------------------------
@pytest.mark.parametrize("seed", [0, 1])
@pytest.mark.parametrize("transport", ["mudp", "udp"])
@pytest.mark.parametrize("mode", ["sync", "async"])
def test_fleet_parity_matrix(training_fleet, seed, transport, mode):
    bp, rp = training_fleet("python", seed=seed, transport=transport,
                            mode=mode)
    bv, rv = training_fleet("vmap", seed=seed, transport=transport,
                            mode=mode)
    # The event layer must be untouched by batching: same rosters, same
    # arrivals, same simulated durations, round for round.
    assert [r.roster for r in rp] == [r.roster for r in rv]
    assert [r.arrived for r in rp] == [r.arrived for r in rv]
    assert [r.duration_ns for r in rp] == [r.duration_ns for r in rv]
    assert_ulp_close(flatten_to_vector(bp.system.global_params),
                     flatten_to_vector(bv.system.global_params))
    # vmap actually batched: fewer backend calls than client-trainings.
    assert bv.trainer is not None
    assert sum(bv.trainer.batch_sizes) >= len(bv.trainer.batch_sizes)


@pytest.mark.parametrize("topology,kw", [("hier", {"cells": 3}),
                                         ("gossip", {})])
def test_fleet_parity_topologies(training_fleet, topology, kw):
    bp, rp = training_fleet("python", topology=topology, **kw)
    bv, rv = training_fleet("vmap", topology=topology, **kw)
    assert [r.arrived for r in rp] == [r.arrived for r in rv]
    assert_ulp_close(flatten_to_vector(bp.system.global_params),
                     flatten_to_vector(bv.system.global_params))


def test_fleet_parity_mlp_over_mudp(training_fleet):
    bp, rp = training_fleet("python", model="mlp", rounds=2, n_clients=8)
    bv, rv = training_fleet("vmap", model="mlp", rounds=2, n_clients=8)
    assert [r.arrived for r in rp] == [r.arrived for r in rv]
    assert_ulp_close(flatten_to_vector(bp.system.global_params),
                     flatten_to_vector(bv.system.global_params))
    # And the model actually learns on its synthetic shards.
    m = bv.model
    assert m.accuracy(bv.system.global_params) > m.accuracy(m.init_params())


def test_mnist_over_lossy_mudp_reaches_target_accuracy():
    """The paper's claim on a learning workload: 16 clients on non-IID
    Dirichlet(0.5) shards of the seeded synthetic MNIST, every link
    dropping 10% of packets, reach 95% test accuracy within 20 rounds
    because MUDP's NACK repair delivers the updates."""
    lossy10 = CohortSpec(name="lossy10", up_rate_bps=(20e6, 20e6),
                         down_up_ratio=2.0,
                         delay_ns=(5_000_000, 20_000_000), jitter_frac=0.3,
                         loss_p=(0.10, 0.10),
                         train_time_ns=(200_000_000, 800_000_000))
    fleet = FleetConfig(
        n_clients=16, seed=0, cohorts={"lossy10": lossy10},
        cohort_mix=(("lossy10", 1.0),), model="mlp", train_backend="vmap",
        model_args={"hidden": 32, "batch_size": 32, "local_steps": 4,
                    "shard_size": 256, "alpha": 0.5})
    fl = FLConfig(aggregation="fedavg", transport=TransportConfig(
        kind="mudp", timeout_ns=2 * NS, udp_deadline_ns=3 * NS))
    build = build_fleet_training(fleet, fl)
    model, system = build.model, build.system
    assert model.data.source == "synthetic"
    accuracy = []
    while len(accuracy) < 20 and (not accuracy or accuracy[-1] < 0.95):
        system.run_round()
        accuracy.append(model.accuracy(system.global_params))
    assert accuracy[-1] >= 0.95, accuracy


def test_python_backend_attaches_no_trainer(training_fleet):
    build, _ = training_fleet("python")
    assert build.trainer is None


# --------------------------------------------------------------------------
# The default path byte-replays every pinned digest
# --------------------------------------------------------------------------
def test_all_default_path_digests_unchanged():
    for (scenario, kind), want in sorted(EXPECTED.items()):
        assert run_digest(scenario, kind, "batched") == want, (
            f"default-path digest moved for {scenario}/{kind}")


# --------------------------------------------------------------------------
# BatchTrainer mechanics
# --------------------------------------------------------------------------
class TestBatchTrainer:
    def _trainer(self, n=4):
        model = make_model("consensus", n, seed=0, n_params=32)
        index = {f"10.1.0.{i + 1}": i for i in range(n)}
        return model, BatchTrainer(model, make_train_backend("vmap"), index)

    def test_lazy_flush_batches_pending(self):
        model, tr = self._trainer()
        p = model.init_params()
        for i in range(3):
            tr.submit(("s", i), f"10.1.0.{i + 1}", p, 0)
        received, trained, metrics = tr.collect(("s", 1))
        assert tr.batch_sizes == [3]          # one call for all pending
        np.testing.assert_array_equal(received["w"], p["w"])
        want, _ = model.train_fn(1)(p, 0, None)
        assert_ulp_close(trained["w"], want["w"])
        # The other two were computed in the same flush.
        tr.collect(("s", 0))
        tr.collect(("s", 2))
        assert tr.batch_sizes == [3]

    def test_duplicate_and_unknown_keys(self):
        model, tr = self._trainer()
        p = model.init_params()
        tr.submit("a", "10.1.0.1", p, 0)
        tr.flush()
        with pytest.raises(RuntimeError, match="duplicate"):
            tr.submit("a", "10.1.0.1", p, 0)
        with pytest.raises(KeyError, match="never submitted"):
            tr.collect("ghost")
        with pytest.raises(KeyError, match="client index"):
            tr.submit("b", "172.16.0.9", p, 0)

    def test_flush_empty_is_noop(self):
        _, tr = self._trainer()
        tr.flush()
        assert tr.batch_sizes == []


# --------------------------------------------------------------------------
# Staged flushes: rows written into the backend's reused buffer, trained
# trees returned as views of the step's output
# --------------------------------------------------------------------------
def _staging_model(name, n):
    kwargs = ({"n_params": 96} if name == "consensus"
              else {"n_train": 512, "n_test": 64, "shard_size": 32,
                    "hidden": 8})
    return make_model(name, n, seed=3, **kwargs)


def _submissions(model, k, salt):
    """``k`` distinct received trees, as flatten/unflatten would give."""
    vec0 = flatten_to_vector(model.init_params())
    rng = np.random.default_rng([salt, k])
    rows = (vec0 + 0.05 * rng.standard_normal((k, vec0.size))
            ).astype(np.float32)
    return [unflatten_from_vector(r, model.init_params()) for r in rows]


def _stack_and_pad(backend, model, trees, ci, ri):
    """The old path: stack flattened copies, pad by repeating the last
    row up to the backend's padded size, train, keep the real rows."""
    k = len(trees)
    kp = backend.padded_rows(k)
    stack = np.stack([flatten_to_vector(t) for t in trees])
    pad = kp - k
    out, met = backend.train(
        model, np.concatenate([stack, np.repeat(stack[-1:], pad, 0)]),
        np.concatenate([ci, np.repeat(ci[-1:], pad)]),
        np.concatenate([ri, np.repeat(ri[-1:], pad)]))
    return out[:k], met[:k]


@pytest.mark.parametrize("backend,model_name,sizes,allocs", [
    # Shrinking flushes: stale rows of a larger flush sit in the padding.
    ("vmap", "mlp", [200, 3, 60, 1], 1),
    ("vmap", "consensus", [200, 3, 60, 1], 1),
    ("python", "consensus", [200, 3, 60, 1], 1),
    # Growth past the first capacity: 4 -> 64 -> 256 rows, and 3 -> 60
    # -> 200 unpadded.
    ("vmap", "consensus", [3, 60, 200], 3),
    ("python", "consensus", [3, 60, 200], 3),
])
def test_staged_flush_matches_stack_and_pad(backend, model_name, sizes,
                                             allocs):
    n = max(sizes)
    model = _staging_model(model_name, n)
    index = {f"c{i}": i for i in range(n)}
    tr = BatchTrainer(model, make_train_backend(backend), index)
    ref = make_train_backend(backend)
    snap = tracing.snapshot()
    for f, k in enumerate(sizes):
        trees = _submissions(model, k, f)
        ci = (np.arange(k, dtype=np.int32) * 7 + f) % n
        ri = np.full(k, f, np.int32)
        for j in range(k):
            tr.submit((f, j), f"c{ci[j]}", trees[j], int(ri[j]))
        tr.flush()
        want, want_met = _stack_and_pad(ref, model, trees, ci, ri)
        for j in range(k):
            received, trained, met = tr.collect((f, j))
            assert received is trees[j]
            np.testing.assert_array_equal(flatten_to_vector(trained),
                                          want[j])
            assert met == want_met[j]
    assert tr.batch_sizes == sizes
    _, counters = tracing.delta(snap)
    assert counters.get("train.stage_allocs", 0) == allocs


def _flush(tr, trees, tag):
    for j, tree in enumerate(trees):
        tr.submit((tag, j), f"c{j}", tree, 0)
    return [tr.collect((tag, j))[1] for j in range(len(trees))]


def test_collected_trees_are_views_of_one_step_output(monkeypatch):
    model = _staging_model("mlp", 8)
    tr = BatchTrainer(model, make_train_backend("vmap"),
                      {f"c{i}": i for i in range(8)})
    outputs = []
    step = tr.backend._step

    def spy(*a):
        out = step(*a)
        outputs.append(out[0])
        return out
    monkeypatch.setattr(tr.backend, "_step", spy)
    first = _flush(tr, _submissions(model, 5, 0), "a")
    kept = [flatten_to_vector(t) for t in first]
    second = _flush(tr, _submissions(model, 7, 1), "b")
    assert len(outputs) == 2
    for trees, out, other in ((first, outputs[0], outputs[1]),
                              (second, outputs[1], outputs[0])):
        for tree in trees:
            for leaf in jax.tree_util.tree_leaves(tree):
                assert np.shares_memory(leaf, out)
                assert not np.shares_memory(leaf, other)
                assert not np.shares_memory(leaf, tr.backend._stage)
                assert not leaf.flags.writeable
    # The second flush rewrote the staging buffer; the first flush's
    # trees are untouched.
    for tree, vec in zip(first, kept):
        np.testing.assert_array_equal(flatten_to_vector(tree), vec)


class _ReturnsItsInput(TrainBackend):
    name = "identity"

    def train(self, model, stack, client_idx, round_idx):
        return stack, [{}] * stack.shape[0]


def test_a_step_returning_its_input_gets_its_own_memory():
    model = _staging_model("consensus", 4)
    tr = BatchTrainer(model, _ReturnsItsInput(),
                      {f"c{i}": i for i in range(4)})
    trees = _submissions(model, 3, 0)
    first = _flush(tr, trees, "a")
    _flush(tr, _submissions(model, 3, 1), "b")
    for tree, sent in zip(first, trees):
        assert not np.shares_memory(tree["w"], tr.backend._stage)
        np.testing.assert_array_equal(tree["w"], sent["w"])


@pytest.mark.parametrize("sizes", [[5, 3], [8, 1, 2]])
def test_staging_moves_the_same_rows_and_bytes(sizes):
    n = max(sizes)
    model = _staging_model("consensus", n)
    n_params = flatten_to_vector(model.init_params()).size
    keys = ("train.rows", "train.pad_rows", "device.h2d_bytes",
            "device.d2h_bytes")

    def moved(run):
        snap = tracing.snapshot()
        for f, k in enumerate(sizes):
            run(f, k, _submissions(model, k, f))
        _, counters = tracing.delta(snap)
        return {key: counters.get(key, 0) for key in keys}

    tr = BatchTrainer(model, make_train_backend("vmap"),
                      {f"c{i}": i for i in range(n)})
    ref = make_train_backend("vmap")

    def staged(f, k, trees):
        _flush(tr, trees, f)

    def stacked(f, k, trees):
        ref.train(model, np.stack([flatten_to_vector(t) for t in trees]),
                  np.arange(k, dtype=np.int32), np.zeros(k, np.int32))

    got, want = moved(staged), moved(stacked)
    assert got == want
    padded = [1 << (k - 1).bit_length() for k in sizes]
    assert got["train.rows"] == sum(sizes)
    assert got["train.pad_rows"] == sum(padded) - sum(sizes)
    assert got["device.h2d_bytes"] == sum(4 * kp * (n_params + 2)
                                          for kp in padded)


# --------------------------------------------------------------------------
# MNIST offline determinism (the CI bugfix) + dirichlet sharding
# --------------------------------------------------------------------------
class TestMnistOffline:
    def test_offline_fallback_is_deterministic(self):
        a = load_mnist(256, 64, seed=5, download=False)
        b = load_mnist(256, 64, seed=5, download=False)
        assert a.source == b.source == "synthetic"
        np.testing.assert_array_equal(a.x_train, b.x_train)
        np.testing.assert_array_equal(a.y_train, b.y_train)
        np.testing.assert_array_equal(a.x_test, b.x_test)
        np.testing.assert_array_equal(a.y_test, b.y_test)
        assert a.x_train.dtype == np.float32 and a.x_train.shape == (256, 784)
        assert a.n_train == 256

    def test_unreachable_download_falls_back(self, monkeypatch):
        import repro.data.mnist as mnist_mod
        monkeypatch.setattr(
            mnist_mod, "_MNIST_MIRRORS",
            ("http://127.0.0.1:9/nowhere/",))   # port 9: discard, refuses
        data = mnist_mod.load_mnist(128, 32, seed=1, timeout=0.2)
        assert data.source == "synthetic"
        ref = mnist_mod.load_mnist(128, 32, seed=1, download=False)
        np.testing.assert_array_equal(data.x_train, ref.x_train)

    def test_seed_changes_data(self):
        a = load_mnist(128, 32, seed=0, download=False)
        b = load_mnist(128, 32, seed=1, download=False)
        assert not np.array_equal(a.x_train, b.x_train)

    def test_splits_are_distinct(self):
        d = load_mnist(128, 128, seed=0, download=False)
        assert not np.array_equal(d.x_train, d.x_test)

    def test_synthetic_is_learnable_structure(self):
        syn = SyntheticMnist(seed=0)
        x, y = syn.sample(64, client=0, step=0)
        x2, y2 = syn.sample(64, client=0, step=0)
        np.testing.assert_array_equal(x, x2)
        np.testing.assert_array_equal(y, y2)


class TestDirichletShards:
    def test_deterministic_and_shaped(self):
        labels = np.repeat(np.arange(10), 50)
        a = dirichlet_shards(labels, 8, alpha=0.5, seed=3, shard_size=40)
        b = dirichlet_shards(labels, 8, alpha=0.5, seed=3, shard_size=40)
        np.testing.assert_array_equal(a, b)
        assert a.shape == (8, 40) and a.dtype == np.int32
        assert a.min() >= 0 and a.max() < len(labels)

    def test_low_alpha_concentrates_classes(self):
        labels = np.repeat(np.arange(10), 100)
        shards = dirichlet_shards(labels, 16, alpha=0.05, seed=0,
                                  shard_size=100)
        # Each client's label histogram should be dominated by few classes.
        top2 = []
        for row in shards:
            hist = np.bincount(labels[row], minlength=10)
            top2.append(np.sort(hist)[-2:].sum() / hist.sum())
        assert np.mean(top2) > 0.8

    def test_validation(self):
        labels = np.arange(10)
        with pytest.raises(ValueError, match="n_clients"):
            dirichlet_shards(labels, 0)
        with pytest.raises(ValueError, match="alpha"):
            dirichlet_shards(labels, 2, alpha=0.0)
