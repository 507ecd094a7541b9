"""The ``lm`` client (LoRA on a frozen windowed/full-attention MoE decoder)
at a tiny size on the CPU: against its plain reference, across the train
backends, through the fleet, and the pieces it forced (YaRN tables, the
windowed chunked attention, the frozen-argument contract)."""

import json
import math
import os
import subprocess
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (FLConfig, FleetConfig, TransportConfig,
                        build_fleet_training, flatten_to_vector)
from repro.core.client_compute import make_model, make_train_backend
from repro.models import layers as L
from repro.models import lm_reference as R

ROOT = Path(__file__).resolve().parents[1]
TINY = dict(num_layers=4, seq_len=64, local_steps=2, lr=0.02,
            overrides=dict(d_model=64, num_heads=4, num_kv_heads=2,
                           head_dim=16, d_ff=32, num_experts=8,
                           num_experts_per_tok=2, sliding_window=16,
                           vocab_size=512, rope_yarn_original_max=32))
K = 4


@pytest.fixture(scope="module")
def model():
    return make_model("lm", K, seed=3, **TINY)


@pytest.fixture(scope="module")
def model32():
    """The same model with its base upcast to float32: the program's
    mathematics in the reference's precision, where a near tie in the
    router cannot fall either way by bfloat16 rounding."""
    m = make_model("lm", K, seed=3, **TINY)
    frozen = m.frozen()
    m._frozen = {"base": {k: v.astype(jnp.float32)
                          for k, v in frozen["base"].items()},
                 "key": frozen["key"]}
    return m


@pytest.fixture(scope="module")
def batch(model):
    vec = flatten_to_vector(model.init_params())
    return (np.tile(vec, (K, 1)), np.arange(K, dtype=np.int32),
            np.array([0, 1, 2, 5], np.int32))


@pytest.fixture(scope="module")
def vmap_out(model, batch):
    return make_train_backend("vmap").train(model, *batch)


@pytest.fixture(scope="module")
def reference(model, batch):
    ref = R.Reference(model.reference_config(), 3)
    return ref, ref.train(*batch, "highest", losses=True)


def _gap(got, want, start):
    return np.linalg.norm(got - want) / np.linalg.norm(want - start)


def test_layout_base_and_streams_match_the_reference(model, reference):
    ref, _ = reference
    assert model.n_params == 4 * (4 * 64 * 16 + 16 * (64 + 32 + 32 + 64)) \
        == sum(int(np.prod(s)) for _, s in R.adapter_shapes(ref.s))
    base = model.frozen()["base"]
    for name, leaf in ref.base().items():      # bit for bit, leaf by leaf
        assert leaf.dtype == base[name].dtype == jnp.bfloat16
        np.testing.assert_array_equal(np.asarray(leaf, np.float32),
                                      np.asarray(base[name], np.float32))
    key = model.frozen()["key"]
    np.testing.assert_array_equal(
        np.asarray(model.step.tokens(key, 2, 7, 1)),
        np.asarray(R.tokens(ref.s, key, 2, 7, 1)))


def test_f32_step_matches_the_reference(model32, batch, reference):
    """With the base upcast to float32 the program computes the reference's
    mathematics in the same precision: what is left is summation order
    (float32 rounding, ~1e-7 relative), so 1e-5 on the update and on the
    loss."""
    _, (want, losses) = reference
    got, aux = make_train_backend("vmap").train(model32, *batch)
    for j in range(K):
        assert _gap(got[j], want[j], batch[0][j]) < 1e-5
    np.testing.assert_allclose([a["train_loss"] for a in aux], losses,
                               rtol=1e-5)


def test_bf16_step_stays_near_the_reference(batch, vmap_out, reference):
    """The program's own precision (bfloat16 activations): the loss within
    1% of the float32 reference's (bfloat16 keeps ~3 significant digits;
    measured 0.0003-0.003 relative), the update within half its size
    (measured 0.01-0.21: at width 64 with 8 experts a token whose top-2 is
    a near tie can change experts under bfloat16 rounding, and each such
    token moves the gradient by a whole expert's share)."""
    _, (want, losses) = reference
    new, aux = vmap_out
    np.testing.assert_allclose([a["train_loss"] for a in aux], losses,
                               rtol=1e-2)
    for j in range(K):
        assert _gap(new[j], want[j], batch[0][j]) < 0.5


def test_python_backend_matches_vmap(model32, batch):
    """The python backend runs the same step at a batch of one: the same
    operations on the same values, summed in another order where XLA fuses
    a batch of one otherwise (float32, so 1e-5 of the update).  In
    bfloat16 the two can part by a router's near tie (up to 12% of the
    update measured at this size), which is why the float32 base."""
    py, aux_py = make_train_backend("python").train(model32, *batch)
    new, aux = make_train_backend("vmap").train(model32, *batch)
    for j in range(K):
        assert _gap(py[j], new[j], batch[0][j]) < 1e-5
    for a, b in zip(aux_py, aux):
        assert a["moe.rows"] == b["moe.rows"]
        np.testing.assert_allclose(a["train_loss"], b["train_loss"],
                                   rtol=1e-6)


def test_counts_come_back_through_aux(vmap_out):
    _, aux = vmap_out
    for a in aux:
        steps, seq, layers, topk = 2, 64, 4, 2
        assert a["train.tokens"] == steps * seq
        assert a["moe.rows"] == steps * layers * seq * topk
        assert a["moe.dropped_rows"] == 0
        # The busiest expert takes at least an even share, at most all.
        assert a["moe.rows"] / 8 <= a["moe.rows_max"] <= a["moe.rows"] / 2


def test_one_compile_serves_every_base(model, batch):
    """The base is an argument of the jitted step, not a constant: a step
    traced with one seed's model trains another seed's base, with no
    second compile, to that model's own result."""
    other = make_model("lm", K, seed=11, **TINY)
    step = jax.jit(model.jax_train_batch)
    args = tuple(map(jnp.asarray, batch))
    step(*args, model.frozen())
    got, _ = step(*args, other.frozen())
    assert step._cache_size() == 1
    want, _ = make_train_backend("vmap").train(other, *batch)
    np.testing.assert_allclose(np.asarray(got), want, rtol=1e-6, atol=1e-7)
    text = step.lower(*args, model.frozen()).as_text()
    assert "constant" not in text or max(
        (len(line) for line in text.splitlines() if "constant" in line),
        default=0) < 2000


def test_yarn_tables_match_the_closed_form():
    """HF ``rope_type: yarn``: slots below the beta_fast dimension keep
    theta**(-2i/d), slots above the beta_slow one are divided by the
    factor, linear in between; cos and sin carry the attention factor."""
    hd, theta, factor, orig = 16, 500_000.0, 16.0, 32
    got = L.yarn_inv_freq(hd, theta, factor, orig, 32.0, 1.0)
    base = theta ** (-np.arange(0, hd, 2) / hd)

    def dim(r):
        return hd * math.log(orig / (r * 2 * math.pi)) / (2 * math.log(theta))
    lo, hi = max(math.floor(dim(32)), 0), min(math.ceil(dim(1)), hd - 1)
    for i in range(hd // 2):
        t = min(max((i - lo) / (hi - lo), 0.0), 1.0)
        want = base[i] * (1 - t) + base[i] / factor * t
        assert got[i] == pytest.approx(want, rel=1e-6)
    assert got[0] == pytest.approx(1.0) and got[-1] < base[-1]
    pos = jnp.arange(40)
    cos, sin = L.rope_cos_sin_freqs(pos, got, 1.25)
    np.testing.assert_allclose(np.asarray(cos),
                               1.25 * np.cos(np.outer(np.arange(40), got)),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(sin),
                               1.25 * np.sin(np.outer(np.arange(40), got)),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_array_equal(L.rope_inv_freq(hd, theta),
                                  base.astype(np.float32))


def test_windowed_chunks_match_the_dense_masks():
    """The band path reads only the window's keys, and gives the dense
    windowed attention; past the window it differs from full attention,
    inside it (first ``window`` positions) it is the same.  The full
    layers' causal-prefix runs give the dense causal attention."""
    rng = np.random.default_rng(0)
    S, W = 64, 16
    q = jnp.asarray(rng.standard_normal((2, S, 4, 8)), jnp.float32)
    k, v = (jnp.asarray(rng.standard_normal((2, S, 2, 8)), jnp.float32)
            for _ in range(2))
    pos = jnp.arange(S)
    dense_w = L.gqa_attention(q, k, v, q_pos=pos, kv_pos=pos, window=W)
    dense_f = L.gqa_attention(q, k, v, q_pos=pos, kv_pos=pos)
    band = L.chunked_attention(q, k, v, q_pos=pos, kv_pos=pos, chunk=16,
                               band=W)
    full = L.chunked_attention(q, k, v, q_pos=pos, kv_pos=pos, chunk=16)
    prefix = L.causal_prefix_attention(q, k, v, chunk=8, groups=4)
    np.testing.assert_allclose(band, dense_w, rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(full, dense_f, rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(prefix, dense_f, rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(band[:, :W], full[:, :W], rtol=1e-5,
                               atol=1e-6)
    assert np.abs(np.asarray(band[:, W:] - full[:, W:])).max() > 1e-2
    g1 = jax.grad(lambda q: L.chunked_attention(
        q, k, v, q_pos=pos, kv_pos=pos, chunk=16, band=W).sum())(q)
    g2 = jax.grad(lambda q: L.gqa_attention(
        q, k, v, q_pos=pos, kv_pos=pos, window=W).sum())(q)
    np.testing.assert_allclose(g1, g2, rtol=1e-4, atol=1e-5)
    g3 = jax.grad(lambda k: L.causal_prefix_attention(
        q, k, v, chunk=8, groups=4).sum())(k)
    g4 = jax.grad(lambda k: L.gqa_attention(
        q, k, v, q_pos=pos, kv_pos=pos).sum())(k)
    np.testing.assert_allclose(g3, g4, rtol=1e-4, atol=1e-5)


def _fleet(backend: str, n: int = 4):
    fleet = FleetConfig(n_clients=n, seed=5, model="lm", model_args=TINY,
                        train_backend=backend, cohort_mix=(("fiber", 1.0),),
                        uplink="delta|int8(1024)", downlink="int8(1024)")
    return build_fleet_training(fleet, FLConfig(
        transport=TransportConfig(kind="mudp")))


@pytest.mark.parametrize("backend", ["vmap", "python"])
def test_fleet_round_through_the_normal_path(backend):
    fb = _fleet(backend)
    before = flatten_to_vector(fb.system.global_params)
    res = fb.system.run_round()
    assert len(res.arrived) == 4
    assert res.counters["train.tokens"] == 4 * 2 * 64
    assert res.counters["moe.rows"] == 4 * 2 * 4 * 64 * 2
    assert res.counters.get("moe.dropped_rows", 0) == 0   # never moves
    after = flatten_to_vector(fb.system.global_params)
    assert np.isfinite(after).all() and np.abs(after - before).max() > 0


def test_fleet_round_on_the_shard_backend_over_four_devices():
    code = (
        "import numpy as np, jax\n"
        "assert jax.device_count() == 4\n"
        "from tests.test_lm_client import _fleet\n"
        "from repro.core import flatten_to_vector\n"
        "fb = _fleet('shard')\n"
        "res = fb.system.run_round()\n"
        "assert len(res.arrived) == 4 and res.counters.get('moe.dropped_rows', 0) == 0\n"
        "print('OK', fb.trainer.batch_sizes)\n")
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=f"{ROOT / 'src'}{os.pathsep}{ROOT}")
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, env=env, cwd=ROOT, timeout=600)
    assert "OK" in r.stdout, r.stderr[-3000:]


def test_bench_configuration_is_the_programs_model():
    """``bench/configs/mellum2_lora_silo8.json`` states the published
    sizes the reference reads; the program's model built from its
    ``model_args`` has the same ones."""
    config = json.loads((ROOT / "bench/configs/mellum2_lora_silo8.json")
                        .read_text())
    m = make_model("lm", config["n_clients"], seed=0,
                   **config["model_args"])
    for key, value in m.reference_config().items():
        if key == "model_args":
            for k, v in value.items():
                assert config["model_args"][k] == v, k
        else:
            assert config[key] == value, key
    assert m.n_params == config["n_params"] == 2_359_296
    assert R.Reference.base_bytes(config) == 2 * config["base_params"]
    flops = R.Reference.flops_per_update(config)
    assert 29e12 < flops < 31e12           # ~29.9 TFLOP a silo's round


def test_reference_copies_agree():
    """The benchmark's copy of the reference imports nothing of the
    program and is this one past its docstring."""
    def body(path):
        text = path.read_text()
        return text[text.index('"""', 3) + 3:]
    assert body(ROOT / "bench/reference/lm.py") == body(
        ROOT / "src/repro/models/lm_reference.py")
    assert "repro" not in body(ROOT / "bench/reference/lm.py")


def test_client_models_load_no_llm_shelf():
    """``make_model("mlp")`` and ``make_model("lm")`` import their own
    modules; the package imports nothing, so the architectures only
    ``repro.models.model`` reaches stay unloaded."""
    shelf = ["repro.models.model", "repro.models.encdec",
             "repro.models.hymba", "repro.models.xlstm", "repro.optim"]
    code = ("import sys\n"
            "import repro.models.mlp, repro.models.lm_client\n"
            f"print(sorted(set({shelf!r}) & set(sys.modules)))\n")
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=str(ROOT / "src"))
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, env=env, cwd=ROOT, timeout=300)
    assert r.returncode == 0, r.stderr[-3000:]
    assert r.stdout.strip() == "[]", r.stdout
