"""Parity pins between the numpy transport-layer math and the Pallas
kernels whose docstrings claim to mirror it.

* ``repro.core.aggregation.fedavg`` (numpy backend) vs
  ``repro.kernels.fedavg.ops.fedavg_trees`` — the "optional backend" the
  orchestrator can select via ``FLConfig.aggregation_backend``.  The two
  agree to ~1 ULP (the kernel reduces over clients in one fused pass, so
  exact bit-identity is NOT guaranteed — which is why numpy stays the
  digest-stable default).
* ``repro.core.compression.quantize_int8``/``dequantize_int8`` vs
  ``repro.kernels.quantize.ref`` — the "kernel's oracle" comment, now
  enforced: identical scales (bit-for-bit) and identical int8 codes on
  shared random vectors.
"""

import numpy as np
import pytest

jax = pytest.importorskip("jax")

from repro.core import aggregation as agg                    # noqa: E402
from repro.core.compression import (dequantize_int8,          # noqa: E402
                                    quantize_int8)
from repro.kernels.fedavg import ops as fedavg_ops            # noqa: E402
from repro.kernels.quantize import ref as quantize_ref        # noqa: E402


def _trees(rng, k, n):
    return [{"w": rng.standard_normal(n).astype(np.float32),
             "b": rng.standard_normal(7).astype(np.float32)}
            for _ in range(k)]


class TestFedavgBackendParity:
    @pytest.mark.parametrize("k,n", [(2, 300), (3, 1024), (8, 4096),
                                     (5, 16384 + 13)])
    def test_kernel_mirrors_numpy(self, k, n):
        rng = np.random.default_rng(k * 1000 + n)
        trees = _trees(rng, k, n)
        weights = (rng.random(k) * 2.0 + 0.1).tolist()
        a = agg.fedavg(trees, weights, backend="numpy")
        b = fedavg_ops.fedavg_trees(trees, weights)
        for key in a:
            np.testing.assert_allclose(a[key], np.asarray(b[key]),
                                       rtol=1e-6, atol=1e-6)

    def test_uniform_weights_default(self):
        rng = np.random.default_rng(0)
        trees = _trees(rng, 4, 512)
        a = agg.fedavg(trees, backend="numpy")
        b = agg.fedavg(trees, backend="kernel")
        for key in a:
            np.testing.assert_allclose(a[key], np.asarray(b[key]),
                                       rtol=1e-6, atol=1e-6)

    def test_backend_dispatch(self):
        rng = np.random.default_rng(1)
        trees = _trees(rng, 3, 256)
        # auto == kernel whenever jax imports (it does in this test).
        auto = agg.fedavg(trees, backend="auto")
        kern = agg.fedavg(trees, backend="kernel")
        for key in auto:
            np.testing.assert_array_equal(np.asarray(auto[key]),
                                          np.asarray(kern[key]))
        with pytest.raises(ValueError, match="backend"):
            agg.fedavg(trees, backend="gpu4000")

    def test_orchestrator_accepts_kernel_backend(self):
        from repro.core import FLConfig
        cfg = FLConfig(aggregation_backend="auto")
        assert cfg.aggregation_backend == "auto"
        with pytest.raises(ValueError, match="aggregation_backend"):
            FLConfig(aggregation_backend="nope")


class TestFedavgStackParity:
    """The batched path: ``fedavg_stack`` over a flat ``(K, P)`` stack.

    Two claims from its docstring, both load-bearing: the numpy stack path
    is **bit-identical** to the per-leaf tree fold (so the orchestrator's
    flat fast path cannot move a replay digest), and the kernel backend
    mirrors it to ~1 ULP (the same oracle contract as the tree path).
    """

    @pytest.mark.parametrize("k,n", [(2, 300), (3, 1024), (8, 4096),
                                     (5, 16384 + 13)])
    def test_stack_numpy_bitwise_equals_tree_numpy(self, k, n):
        from repro.core.packetizer import (flatten_to_vector,
                                           unflatten_from_vector)
        rng = np.random.default_rng(k * 31 + n)
        trees = _trees(rng, k, n)
        weights = (rng.random(k) * 2.0 + 0.1).tolist()
        tree_out = agg.fedavg(trees, weights, backend="numpy")
        stack = np.stack([flatten_to_vector(t) for t in trees])
        vec = agg.fedavg_stack(stack, weights, backend="numpy")
        rebuilt = unflatten_from_vector(vec, trees[0])
        for key in tree_out:
            np.testing.assert_array_equal(tree_out[key], rebuilt[key])

    # (300, 3000): more clients than one kernel block, so the K-tiled
    # accumulation path runs.
    @pytest.mark.parametrize("k,n", [(2, 256), (7, 4096), (16, 16384 + 5),
                                     (300, 3000)])
    def test_kernel_mirrors_numpy_stack(self, k, n):
        rng = np.random.default_rng(k * 97 + n)
        stack = rng.standard_normal((k, n)).astype(np.float32)
        weights = (rng.random(k) + 0.05).tolist()
        a = agg.fedavg_stack(stack, weights, backend="numpy")
        b = agg.fedavg_stack(stack, weights, backend="kernel")
        np.testing.assert_allclose(a, b, rtol=1e-6, atol=1e-6)

    def test_auto_routes_to_kernel_and_validates(self):
        rng = np.random.default_rng(2)
        stack = rng.standard_normal((3, 512)).astype(np.float32)
        auto = agg.fedavg_stack(stack, backend="auto")
        kern = agg.fedavg_stack(stack, backend="kernel")
        np.testing.assert_array_equal(auto, kern)
        with pytest.raises(ValueError, match="backend"):
            agg.fedavg_stack(stack, backend="gpu4000")
        with pytest.raises(ValueError, match="stack"):
            agg.fedavg_stack(np.zeros((0, 8), np.float32))
        with pytest.raises(ValueError, match="stack"):
            agg.fedavg_stack(np.zeros(8, np.float32))

    def test_kernel_flat_direct(self):
        # fedavg_flat is the raw Pallas entry the stack path routes to;
        # K=1 must be the identity up to weight normalization.
        vec = np.linspace(-1, 1, 777, dtype=np.float32)
        out = np.asarray(fedavg_ops.fedavg_flat(vec[None], [3.0]))
        np.testing.assert_allclose(out, vec, rtol=1e-6, atol=1e-7)


class TestQuantizeOracleParity:
    """The compression docstring says quantize_int8 mirrors
    repro.kernels.quantize.ref — pinned here on shared random vectors."""

    @pytest.mark.parametrize("n,block", [(1024, 256), (4096, 1024),
                                         (1000, 256), (37, 16)])
    def test_quantize_matches_ref(self, n, block):
        rng = np.random.default_rng(n * 7 + block)
        vec = (rng.standard_normal(n) * 10).astype(np.float32)
        q_np, scales_np = quantize_int8(vec, block=block)

        nb = -(-n // block)
        padded = np.zeros(nb * block, dtype=np.float32)
        padded[:n] = vec
        q_ref, scales_ref = quantize_ref.quantize_blockwise(
            padded.reshape(nb, block))

        np.testing.assert_array_equal(q_np.reshape(nb, block),
                                      np.asarray(q_ref))
        np.testing.assert_array_equal(scales_np, np.asarray(scales_ref))

    def test_dequantize_matches_ref(self):
        rng = np.random.default_rng(5)
        n, block = 2048, 512
        vec = (rng.standard_normal(n) * 3).astype(np.float32)
        q, scales = quantize_int8(vec, block=block)
        out_np = dequantize_int8(q, scales, n, block=block)
        out_ref = np.asarray(quantize_ref.dequantize_blockwise(
            np.asarray(q).reshape(-1, block), np.asarray(scales))).reshape(-1)
        np.testing.assert_array_equal(out_np, out_ref[:n])

    def test_roundtrip_error_bounded_by_scale(self):
        rng = np.random.default_rng(6)
        vec = (rng.standard_normal(513) * 4).astype(np.float32)
        q, scales = quantize_int8(vec, block=128)
        out = dequantize_int8(q, scales, vec.size, block=128)
        err = np.abs(out - vec)
        per_block_bound = np.repeat(scales, 128)[:vec.size] * 0.5 + 1e-7
        assert np.all(err <= per_block_bound)


# --------------------------------------------------------------------------
# Wire batch-plane stage kernels (PR 9): topk gather/scatter + matrix
# quantize, the Pallas fast paths behind wire.set_batch_backend("pallas")
# --------------------------------------------------------------------------
from repro.core.compression import (dequantize_int8_batch,     # noqa: E402
                                    quantize_int8_batch)
from repro.kernels.quantize import ops as quantize_ops         # noqa: E402
from repro.kernels.quantize.quantize import QBLOCK             # noqa: E402
from repro.kernels.topk import ops as topk_ops                 # noqa: E402
from repro.kernels.topk import ref as topk_ref                 # noqa: E402


def _unique_idx(rng, n_items, p, k):
    return np.stack([np.sort(rng.choice(p, size=k, replace=False))
                     for _ in range(n_items)]).astype(np.int32)


class TestTopKKernelParity:
    """Gather/scatter are pure data movement: the Pallas kernels must be
    **exact** against both the numpy wire path and the jnp oracle — this
    is what lets the pallas batch backend keep the wire's bit-identity
    contract for ``topk`` stages."""

    @pytest.mark.parametrize("n_items,p,k", [(1, 64, 4), (7, 1000, 50),
                                             (16, 4096, 41), (3, 3000, 1500)])
    def test_gather_exact(self, n_items, p, k):
        rng = np.random.default_rng(n_items * 131 + p)
        batch = rng.standard_normal((n_items, p)).astype(np.float32)
        idx = _unique_idx(rng, n_items, p, k)
        out = np.asarray(topk_ops.topk_gather(batch, idx))
        np.testing.assert_array_equal(out,
                                      np.take_along_axis(batch, idx, axis=1))
        np.testing.assert_array_equal(
            out, np.asarray(topk_ref.gather_rows(
                jax.numpy.asarray(batch), jax.numpy.asarray(idx))))

    @pytest.mark.parametrize("n_items,p,k", [(1, 64, 4), (7, 1000, 50),
                                             (16, 4096, 41), (3, 3000, 1500)])
    def test_scatter_exact(self, n_items, p, k):
        rng = np.random.default_rng(n_items * 17 + p)
        idx = _unique_idx(rng, n_items, p, k)
        vals = rng.standard_normal((n_items, k)).astype(np.float32)
        out = np.asarray(topk_ops.topk_scatter(idx, vals, p))
        dense = np.zeros((n_items, p), np.float32)
        dense[np.repeat(np.arange(n_items), k), idx.reshape(-1)] = \
            vals.reshape(-1)
        np.testing.assert_array_equal(out, dense)
        np.testing.assert_array_equal(
            out, np.asarray(topk_ref.scatter_rows(
                jax.numpy.asarray(idx), jax.numpy.asarray(vals), p)))

    def test_scatter_duplicate_indices_last_wins(self):
        """Malformed payloads can carry duplicate indices; the kernel's
        sequential row loop must resolve them exactly like numpy fancy
        assignment (last occurrence wins) so batch decode stays
        bit-identical even on garbage."""
        idx = np.array([[3, 3, 7], [0, 5, 0]], np.int32)
        vals = np.array([[1., 2., 3.], [4., 5., 6.]], np.float32)
        out = np.asarray(topk_ops.topk_scatter(idx, vals, 8))
        dense = np.zeros((2, 8), np.float32)
        dense[np.repeat(np.arange(2), 3), idx.reshape(-1)] = vals.reshape(-1)
        np.testing.assert_array_equal(out, dense)

    def test_gather_scatter_roundtrip(self):
        rng = np.random.default_rng(9)
        batch = rng.standard_normal((5, 300)).astype(np.float32)
        idx = _unique_idx(rng, 5, 300, 30)
        vals = np.asarray(topk_ops.topk_gather(batch, idx))
        dense = np.asarray(topk_ops.topk_scatter(idx, vals, 300))
        np.testing.assert_array_equal(
            np.take_along_axis(dense, idx, axis=1), vals)


class TestQuantizeMatrixKernelParity:
    """The batched (N, P) quantize behind the wire's pallas ``int8``
    path.  XLA rewrites the scale division into multiply-by-reciprocal,
    so the jit'd kernel is NOT bit-identical to numpy — the pinned
    contract is: scales within 1 ULP, codes within 1 step (a boundary
    value can round across when its scale moved 1 ULP), and dequantize
    on shared (q, scales) inputs **bitwise** identical."""

    @pytest.mark.parametrize("n_items,n", [(1, QBLOCK), (4, 3 * QBLOCK),
                                           (7, 2 * QBLOCK + 37), (3, 5)])
    def test_quantize_matrix_ulp_pinned(self, n_items, n):
        rng = np.random.default_rng(n_items * 101 + n)
        mat = (rng.standard_normal((n_items, n)) * 8).astype(np.float32)
        q_np, s_np = quantize_int8_batch(mat, block=QBLOCK)
        q_k, s_k = quantize_ops.quantize_matrix(mat)
        q_k, s_k = np.asarray(q_k), np.asarray(s_k)
        assert q_k.shape == q_np.shape and s_k.shape == s_np.shape
        np.testing.assert_array_max_ulp(s_k, s_np, maxulp=1)
        assert np.abs(q_k.astype(np.int16)
                      - q_np.astype(np.int16)).max() <= 1

    def test_dequantize_matrix_bitwise_on_shared_inputs(self):
        rng = np.random.default_rng(12)
        n_items, n = 5, 2 * QBLOCK + 11
        mat = (rng.standard_normal((n_items, n)) * 3).astype(np.float32)
        q, s = quantize_int8_batch(mat, block=QBLOCK)
        out_np = dequantize_int8_batch(q, s, n, block=QBLOCK)
        out_k = np.asarray(quantize_ops.dequantize_matrix(q, s, n))
        np.testing.assert_array_equal(out_np, out_k)

    def test_matrix_matches_vector_rows(self):
        """(N, P) kernel == N independent vector-kernel calls: batching
        must not change any row's result."""
        rng = np.random.default_rng(13)
        mat = (rng.standard_normal((3, QBLOCK + 9)) * 2).astype(np.float32)
        q_m, s_m = quantize_ops.quantize_matrix(mat)
        for i, row in enumerate(mat):
            q_v, s_v, _ = quantize_ops.quantize_vector(row)
            np.testing.assert_array_equal(np.asarray(q_m)[i],
                                          np.asarray(q_v).reshape(-1))
            np.testing.assert_array_equal(np.asarray(s_m)[i],
                                          np.asarray(s_v))


class TestPallasWireBackend:
    """Stage-level pins for wire.set_batch_backend("pallas")."""

    @pytest.fixture
    def pallas_backend(self):
        from repro.core import wire
        prev = wire.set_batch_backend("pallas")
        yield
        wire.set_batch_backend(prev)

    def test_auto_selects_pallas_when_kernels_import(self):
        from repro.core import wire
        prev = wire.set_batch_backend("auto")
        try:
            assert wire.batch_backend() == "pallas"
        finally:
            wire.set_batch_backend(prev)

    @pytest.mark.parametrize("spec", ["topk(0.05)", "topk(0.1)|hex"])
    def test_topk_stage_bytes_identical(self, spec, pallas_backend):
        """Gather/scatter are exact, so the pallas backend keeps full
        byte-identity for topk pipelines."""
        from repro.core import wire
        pipeline = wire.parse_pipeline(spec)
        rng = np.random.default_rng(21)
        batch = [rng.standard_normal(900).astype(np.float32)
                 for _ in range(6)]
        pallas_bytes = pipeline.encode_batch(batch)
        wire.set_batch_backend("numpy")
        numpy_bytes = pipeline.encode_batch(batch)
        assert pallas_bytes == numpy_bytes
        wire.set_batch_backend("pallas")
        np.testing.assert_array_equal(pipeline.decode_batch(numpy_bytes),
                                      np.stack([pipeline.decode(d)
                                                for d in numpy_bytes]))

    def test_int8_stage_within_one_code_step(self, pallas_backend):
        """int8 under pallas is ULP-pinned, not byte-pinned: decoded
        values may differ from the numpy path by at most one quantization
        step per element (the documented jit reciprocal drift)."""
        from repro.core import wire
        pipeline = wire.parse_pipeline("int8(1024)")
        rng = np.random.default_rng(22)
        batch = [(rng.standard_normal(3000) * 5).astype(np.float32)
                 for _ in range(4)]
        pallas_dec = pipeline.decode_batch(pipeline.encode_batch(batch))
        wire.set_batch_backend("numpy")
        numpy_dec = pipeline.decode_batch(pipeline.encode_batch(batch))
        wire.set_batch_backend("pallas")
        max_scale = max(np.abs(v).max() for v in batch) / 127.0
        np.testing.assert_allclose(pallas_dec, numpy_dec,
                                   atol=1.01 * max_scale, rtol=0)

    @pytest.mark.parametrize("spec,module,fn", [
        ("topk(0.05)", "topk", "topk_scatter"),
        ("int8(1024)", "quantize", "dequantize_matrix")])
    def test_kernel_fault_is_not_degraded(self, spec, module, fn,
                                          pallas_backend, monkeypatch):
        """A decode kernel that fails on valid payloads is a device fault:
        batch decode raises it instead of degrading every payload to the
        per-item host path."""
        import importlib

        from repro.core import wire
        pipeline = wire.parse_pipeline(spec)
        rng = np.random.default_rng(23)
        datas = pipeline.encode_batch(
            [rng.standard_normal(2000).astype(np.float32) for _ in range(3)])

        def broken(*args, **kwargs):
            raise RuntimeError("device lost")
        monkeypatch.setattr(
            importlib.import_module(f"repro.kernels.{module}.ops"), fn, broken)
        with pytest.raises(wire.WireKernelError, match="device lost"):
            wire.decode_payload_batch(datas)

    def test_default_backend_unaffected_by_kernel_availability(self):
        from repro.core import wire
        assert wire.batch_backend() == "numpy"
