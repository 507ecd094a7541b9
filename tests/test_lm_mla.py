"""The ``lm_mla`` client (LoRA on a frozen DeepSeek-V3-type decoder:
latent attention, a leading dense layer, sigmoid-routed experts with a
selection bias, shared experts) at a tiny size on the CPU: against its
plain reference (``bench/reference/lm_mla.py``, loaded by path), through
the fleet, and the pieces it forced (interleaved RoPE, attention with a
value head of its own size, the sigmoid router)."""

import importlib.util
import json
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (FLConfig, FleetConfig, TransportConfig,
                        build_fleet_training, flatten_to_vector)
from repro.core.client_compute import make_model, make_train_backend
from repro.models import layers as L
from repro.models import lm_client
from repro.models import transformer as T

ROOT = Path(__file__).resolve().parents[1]


def _load(path: Path):
    spec = importlib.util.spec_from_file_location(f"ref_{path.stem}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


R = _load(ROOT / "bench/reference/lm_mla.py")
#: d 64, 2 heads of nope/rope/v 16/8/16, latent 32, one dense layer then
#: two MoE layers of 8 experts (top-2) and one shared, 32 tokens.
TINY = dict(num_layers=3, seq_len=32, local_steps=2, lr=0.05,
            overrides=dict(d_model=64, num_heads=2, num_kv_heads=2,
                           head_dim=24, qk_nope_head_dim=16,
                           qk_rope_head_dim=8, v_head_dim=16,
                           kv_lora_rank=32, d_ff=32, dense_d_ff=96,
                           num_experts=8, num_experts_per_tok=2,
                           n_shared_experts=1, vocab_size=256))
K = 2
#: The program in float32 against the float32 reference: what is left is
#: summation order (float32 rounding, ~1e-6 relative measured), so 1e-5
#: on the update and on the loss.
F32_TOL = 1e-5


def _f32(model):
    """The model with its base upcast to float32: the program's
    mathematics in the reference's precision, where a near tie in the
    router cannot fall either way by bfloat16 rounding."""
    frozen = model.frozen()
    model._frozen = {"base": {k: v.astype(jnp.float32)
                              for k, v in frozen["base"].items()},
                     "key": frozen["key"]}
    return model


@pytest.fixture(scope="module")
def model():
    return make_model("lm_mla", K, seed=3, **TINY)


@pytest.fixture(scope="module")
def model32():
    return _f32(make_model("lm_mla", K, seed=3, **TINY))


@pytest.fixture(scope="module")
def batch(model):
    vec = flatten_to_vector(model.init_params())
    return (np.tile(vec, (K, 1)), np.arange(K, dtype=np.int32),
            np.array([0, 3], np.int32))


@pytest.fixture(scope="module")
def reference(model, batch):
    ref = R.Reference(model.reference_config(), 3)
    return ref, ref.train(*batch, "highest", losses=True)


def _gaps(got, want, start):
    return [float(np.linalg.norm(g - w) / np.linalg.norm(w - s))
            for g, w, s in zip(got, want, start)]


def _planted(model32, **leaves):
    """A float32 model whose base has ``leaves`` replaced."""
    m = make_model("lm_mla", K, seed=3, **TINY)
    base = dict(model32.frozen()["base"])
    for name, fn in leaves.items():
        base[name] = fn(base[name])
    m._frozen = {"base": base, "key": model32.frozen()["key"]}
    return m


def test_layout_base_and_streams_match_the_reference(model, reference):
    ref, _ = reference
    per_layer = 16 * ((64 + 48) + (64 + 40) + (32 + 64) + (32 + 64))
    assert model.n_params == 3 * per_layer == sum(
        int(np.prod(s)) for _, s in R.adapter_shapes(ref.s))
    assert [n for n, _ in model.step.layout] == [
        n for n, _ in R.adapter_shapes(ref.s)]
    base, cols = model.frozen()["base"], lm_client.rope_columns(model.cfg)
    assert set(base) == set(ref.base())
    for name, leaf in ref.base().items():     # bit for bit, leaf by leaf
        assert leaf.dtype == base[name].dtype, name
        want = np.asarray(leaf, np.float32)
        if name in cols:                      # RoPE pairs de-interleaved
            want = want[..., cols[name]]
        np.testing.assert_array_equal(np.asarray(base[name], np.float32),
                                      want, err_msg=name)
    assert base["e_score_correction_bias"].dtype == jnp.float32
    assert float(jnp.abs(base["e_score_correction_bias"]).min()) > 0
    key = model.frozen()["key"]
    np.testing.assert_array_equal(
        np.asarray(model.step.tokens(key, 1, 7, 1)),
        np.asarray(R.tokens(ref.s, key, 1, 7, 1)))


@pytest.mark.parametrize("backend", ["vmap", "python"])
def test_f32_step_matches_the_reference(model32, batch, reference, backend):
    """The batched step (``jax_train_batch``, vmap backend) and the single
    one (``jax_train``, python backend) against the reference: the
    adapter update and the last step's loss within :data:`F32_TOL`."""
    _, (want, losses) = reference
    got, aux = make_train_backend(backend).train(model32, *batch)
    assert max(_gaps(got, want, batch[0])) < F32_TOL
    np.testing.assert_allclose([a["train_loss"] for a in aux], losses,
                               rtol=F32_TOL)


def test_bf16_step_stays_near_the_reference(model, batch, reference):
    """The program's own precision (bfloat16 activations): the loss within
    1% of the float32 reference's (bfloat16 keeps ~3 significant digits;
    measured 0.0003-0.0006 relative), the update within half its size
    (measured 0.018-0.083: at width 64 with 8 experts a token whose top-2
    is a near tie can change experts under bfloat16 rounding)."""
    _, (want, losses) = reference
    got, aux = make_train_backend("vmap").train(model, *batch)
    np.testing.assert_allclose([a["train_loss"] for a in aux], losses,
                               rtol=1e-2)
    assert max(_gaps(got, want, batch[0])) < 0.5


@pytest.mark.parametrize("plant", ["bfloat16_reference", "bias_left_out",
                                   "shared_experts_left_out"])
def test_planted_faults_fail_the_comparison(plant, model32, batch,
                                            reference):
    """Each plant moves the update by far more than :data:`F32_TOL`: the
    reference computed in bfloat16 (the configuration's control), the
    selection bias left out of the choice of experts, and the shared
    experts left out.  Measured 0.05-0.7 against 1e-5."""
    ref, (want, _) = reference
    if plant == "bfloat16_reference":
        got = ref.train(*batch, "bfloat16")
    else:
        name = {"bias_left_out": "e_score_correction_bias",
                "shared_experts_left_out": "shared_down"}[plant]
        m = _planted(model32, **{name: jnp.zeros_like})
        got, _ = make_train_backend("vmap").train(m, *batch)
    assert min(_gaps(got, want, batch[0])) > 1e3 * F32_TOL


def test_counts_come_back_through_aux(model, batch):
    _, aux = make_train_backend("vmap").train(model, *batch)
    steps, seq, moe_layers, topk = 2, 32, 2, 2
    for a in aux:
        assert a["train.tokens"] == steps * seq
        # Routed rows only: the dense layer and the shared experts add none.
        assert a["moe.rows"] == steps * moe_layers * seq * topk
        assert a["moe.dropped_rows"] == 0
        assert a["moe.rows"] / 8 <= a["moe.rows_max"] <= a["moe.rows"] / 2


def _rotate_pairs(x, angles):
    """The literal interleaved rotation: (x_2j, x_2j+1) times e^(i a_j)."""
    z = x[..., 0::2] + 1j * x[..., 1::2]
    z = z * np.exp(1j * angles)
    out = np.empty_like(x)
    out[..., 0::2], out[..., 1::2] = z.real, z.imag
    return out


def test_interleaved_rope_is_the_literal_rotation(model):
    """The half-split rotation of the de-interleaved RoPE dims is the
    literal pairwise rotation, de-interleaved; so the scores are the same."""
    cfg = model.cfg
    r, S = cfg.qk_rope_head_dim, 12
    rng = np.random.default_rng(0)
    x, y = (rng.standard_normal((1, S, 3, r)) for _ in range(2))
    pairs = lm_client.rope_columns(cfg)["kv_a_proj_with_mqa"][-r:] \
        - cfg.kv_lora_rank
    cos, sin = L.rope_cos_sin_freqs(jnp.arange(S), L.rope_inv_freq(
        r, cfg.rope_theta))
    angles = np.arange(S)[:, None] * L.rope_inv_freq(r, cfg.rope_theta)
    want_x = _rotate_pairs(x, angles[None, :, None, :])
    want_y = _rotate_pairs(y, angles[None, :, None, :])
    got_x = L.apply_rope(jnp.asarray(x[..., pairs], jnp.float32),
                         cos[None], sin[None])
    got_y = L.apply_rope(jnp.asarray(y[..., pairs], jnp.float32),
                         cos[None], sin[None])
    np.testing.assert_allclose(got_x, want_x[..., pairs], rtol=1e-5,
                               atol=1e-5)
    np.testing.assert_allclose(
        np.einsum("bshd,bthd->bhst", got_x, got_y),
        np.einsum("bshd,bthd->bhst", want_x, want_y), rtol=1e-4, atol=1e-4)


def test_attention_with_a_value_head_of_its_own():
    """Queries and keys of 12 dims, values of 8: the chunked and the
    causal-prefix attention give the dense attention, and its gradients
    (float32, so 1e-5)."""
    rng = np.random.default_rng(1)
    S = 32
    q, k = (jnp.asarray(rng.standard_normal((2, S, 3, 12)), jnp.float32)
            for _ in range(2))
    v = jnp.asarray(rng.standard_normal((2, S, 3, 8)), jnp.float32)
    pos = jnp.arange(S)
    dense = L.gqa_attention(q, k, v, q_pos=pos, kv_pos=pos)
    assert dense.shape == (2, S, 3, 8)
    chunked = L.chunked_attention(q, k, v, q_pos=pos, kv_pos=pos, chunk=8)
    prefix = L.causal_prefix_attention(q, k, v, chunk=4, groups=4)
    np.testing.assert_allclose(chunked, dense, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(prefix, dense, rtol=1e-5, atol=1e-5)
    for arg in range(3):
        g1 = jax.grad(lambda *a: L.causal_prefix_attention(
            *a, chunk=4, groups=4).sum(), argnums=arg)(q, k, v)
        g2 = jax.grad(lambda *a: L.gqa_attention(
            *a, q_pos=pos, kv_pos=pos).sum(), argnums=arg)(q, k, v)
        np.testing.assert_allclose(g1, g2, rtol=1e-4, atol=1e-5)


def test_latent_attention_matches_a_head_by_head_formula(model32):
    """One MLA block of the program (float32 base, nonzero adapters)
    against the formula written a head at a time in float64 on the
    published (interleaved) weights: the latent and its norm, the
    decompression, the shared RoPE key, causal softmax at (nope +
    rope)**-0.5.  float32 against float64: 1e-4."""
    m, cfg = model32, model32.cfg
    H, lat = cfg.num_heads, cfg.kv_lora_rank
    nope, r, vd = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    S, d = 32, cfg.d_model
    published = R.Reference(m.reference_config(), 3).base()
    p = {n: v[1] for n, v in m.frozen()["base"].items()
         if n in ("q_proj", "kv_a_proj_with_mqa", "kv_a_layernorm",
                  "kv_b_proj", "o_proj")}
    rng = np.random.default_rng(2)
    a = {f"{ab}_{t}": rng.standard_normal((1,) + s[1:]).astype(np.float32)
         * 0.1 for ab in "ab" for t in lm_client.lora_targets(cfg)
         for n, s in m.step.layout if n == f"{ab}_{t}"}
    h = rng.standard_normal((1, S, d)).astype(np.float32)
    got = m.step._latent_attention(
        jnp.asarray(h), p, {n: jnp.asarray(v) for n, v in a.items()},
        lm_client.rope_tables(cfg, S)[1])

    s_ = m.step.alpha / m.step.rank
    w = {n: np.asarray(published[n][1], np.float64) for n in p}

    def proj(x, t):
        return x @ w[t] + s_ * (x @ a[f"a_{t}"][0]) @ a[f"b_{t}"][0]

    x = h[0].astype(np.float64)
    q = proj(x, "q_proj").reshape(S, H, nope + r)
    ckv = proj(x, "kv_a_proj_with_mqa")
    c = ckv[:, :lat] / np.sqrt(np.mean(ckv[:, :lat] ** 2, -1, keepdims=True)
                             + cfg.rms_norm_eps)
    kv = proj(c, "kv_b_proj").reshape(S, H, nope + vd)
    angles = np.arange(S)[:, None] * L.rope_inv_freq(r, cfg.rope_theta)
    k_pe = _rotate_pairs(ckv[:, lat:], angles)
    o = np.empty((S, H, vd))
    for i in range(H):
        qi = np.concatenate([q[:, i, :nope],
                             _rotate_pairs(q[:, i, nope:], angles)], -1)
        ki = np.concatenate([kv[:, i, :nope], k_pe], -1)
        sc = qi @ ki.T / np.sqrt(nope + r)
        sc = np.where(np.tril(np.ones((S, S), bool)), sc, -np.inf)
        pr = np.exp(sc - sc.max(-1, keepdims=True))
        o[:, i] = (pr / pr.sum(-1, keepdims=True)) @ kv[:, i, nope:]
    want = proj(o.reshape(S, H * vd), "o_proj")
    np.testing.assert_allclose(np.asarray(got[0]), want, rtol=1e-4,
                               atol=1e-4)


def test_sigmoid_routing_chooses_on_the_bias_and_weights_without_it():
    """Experts chosen on sigmoid score + bias, weighted by the unbiased
    scores of the chosen, renormalised and times the routed scale; the
    bias reroutes some tokens and the softmax path keeps its defaults."""
    rng = np.random.default_rng(3)
    x = jnp.asarray(rng.standard_normal((256, 16)), jnp.float32)
    router = jnp.asarray(rng.standard_normal((16, 8)) * 0.25, jnp.float32)
    bias = jnp.asarray(rng.standard_normal(8) * 0.05, jnp.float32)
    w, i = T.moe_route(x, router, 2, scoring="sigmoid", bias=bias,
                       scale=2.446)
    scores = 1 / (1 + np.exp(-(np.asarray(x, np.float64)
                               @ np.asarray(router, np.float64))))
    chosen = np.argsort(-(scores + np.asarray(bias)), -1)[:, :2]
    np.testing.assert_array_equal(np.sort(np.asarray(i), -1),
                                  np.sort(chosen, -1))
    picked = np.take_along_axis(scores, np.asarray(i), -1)
    np.testing.assert_allclose(
        w, picked / picked.sum(-1, keepdims=True) * 2.446, rtol=1e-5)
    unbiased = np.sort(np.argsort(-scores, -1)[:, :2], -1)
    assert (unbiased != np.sort(chosen, -1)).any(-1).sum() >= 1
    w0, i0 = T.moe_route(x, router, 2)                      # softmax
    probs = jax.nn.softmax(x @ router, -1)
    top, idx = jax.lax.top_k(probs, 2)
    np.testing.assert_array_equal(i0, idx)
    np.testing.assert_allclose(w0, top / top.sum(-1, keepdims=True),
                               rtol=1e-6)


def test_shared_experts_are_counted_once(model32):
    """A MoE block's output is its routed experts' plus the shared
    experts' SwiGLU, once; the rows it counts are the routed ones."""
    m = model32
    p = {n: v[0] for n, v in m.frozen()["base"].items()
         if n in ("router", "e_score_correction_bias", "we_gate", "we_up",
                  "we_down", "shared_gate", "shared_up", "shared_down")}
    h = jnp.asarray(np.random.default_rng(4).standard_normal(
        (2, 32, m.cfg.d_model)), jnp.float32)
    y, counts = m.step._moe(h, p)
    y0, counts0 = m.step._moe(h, dict(p, shared_down=jnp.zeros_like(
        p["shared_down"])))
    shared = jax.vmap(lambda x: lm_client._swiglu(
        x, p["shared_gate"], p["shared_up"], p["shared_down"]))(h)
    np.testing.assert_allclose(y - y0, shared, rtol=1e-5, atol=1e-6)
    np.testing.assert_array_equal(counts, counts0)
    np.testing.assert_array_equal(counts.sum(-1), 32 * 2)


def test_fleet_round_through_the_normal_path():
    fleet = FleetConfig(n_clients=K, seed=5, model="lm_mla", model_args=TINY,
                        train_backend="vmap", cohort_mix=(("fiber", 1.0),),
                        uplink="delta|int8(1024)", downlink="int8(1024)")
    fb = build_fleet_training(fleet, FLConfig(
        transport=TransportConfig(kind="mudp")))
    before = flatten_to_vector(fb.system.global_params)
    res = fb.system.run_round()
    assert len(res.arrived) == K
    assert res.counters["train.tokens"] == K * 2 * 32
    assert res.counters["moe.rows"] == K * 2 * 2 * 32 * 2
    after = flatten_to_vector(fb.system.global_params)
    assert np.isfinite(after).all() and np.abs(after - before).max() > 0


def test_bench_configuration_is_the_programs_model():
    """``bench/configs/moonlight_lora_silo8.json`` states the published
    sizes the reference reads; the program's model built from its
    ``model_args`` has the same ones, 1,579,008 adapter parameters at six
    layers, and a 7.36 GB base."""
    config = json.loads((ROOT / "bench/configs/moonlight_lora_silo8.json")
                        .read_text())
    assert config["model"] == "lm_mla"
    m = make_model("lm_mla", config["n_clients"], seed=0,
                   **config["model_args"])
    for key, value in m.reference_config().items():
        if key == "model_args":
            for k, v in value.items():
                assert config["model_args"][k] == v, k
        else:
            assert config[key] == value, key
    assert m.n_params == config["n_params"] == 1_579_008 == 6 * 263_168
    assert config["base_params"] == 3_678_303_552
    # bfloat16, with the 5 x 64 selection-bias entries in float32.
    assert R.Reference.base_bytes(config) == 2 * config["base_params"] \
        + 2 * 5 * 64
    assert 30e12 < R.Reference.flops_per_update(config) < 31e12
