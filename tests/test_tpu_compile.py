"""Native TPU lowering of the federated-path kernels, without a chip.

Each test compiles one Pallas kernel for a *described* TPU v5e (a
``v5e:2x2`` topology built by the installed TPU compiler, no device
attached) with ``interpret=False`` at the ``mlp`` model's width
(P = 25,450 parameters, 256 clients, top-k 1%), and requires a Mosaic
custom call (``tpu_custom_call``) in the compiled program.  This catches
what the interpreter cannot: block shapes off the (8, 128) tiling rule,
scalar stores to VMEM, and kernels that overflow VMEM (``fedavg`` at
K = 256 used to).

The topology is described inside a module fixture, never at import:
only one process at a time may load the TPU library, and every test
worker imports this file.  The persistent compilation cache is off
around these compiles (an entry written for a described chip cannot be
read back without one).
"""

import os

import jax
import jax.numpy as jnp
import pytest

from repro.kernels.checksum.checksum import checksum_pallas
from repro.kernels.fedavg.fedavg import fedavg_pallas
from repro.kernels.quantize.quantize import (QBLOCK, dequantize_pallas,
                                             quantize_pallas)
from repro.kernels.topk.topk import topk_gather_pallas, topk_scatter_pallas

MLP_PARAMS = 784 * 32 + 32 + 32 * 10 + 10        # 25,450
N_CLIENTS = 256
K_KEPT = int(MLP_PARAMS * 0.01)                  # topk(0.01)
N_BLOCKS = -(-MLP_PARAMS // QBLOCK)


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    with pytest.MonkeyPatch.context() as mp:
        if "TPU_LOG_DIR" not in os.environ:
            mp.setenv("TPU_LOG_DIR", "disabled")
        try:
            desc = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # noqa: BLE001 - any failure means "cannot"
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        prev = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        try:
            yield desc
        finally:
            jax.config.update("jax_enable_compilation_cache", prev)
            compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def sds(topo):
    """ShapeDtypeStruct factory placed on one described v5e chip."""
    from jax.sharding import SingleDeviceSharding
    one_chip = SingleDeviceSharding(topo.devices[0])

    def make(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    return make


def _assert_native(fn, *args, **static):
    compiled = jax.jit(
        lambda *a: fn(*a, interpret=False, **static)).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


# K = 1000 is well past one client block: tiling over K keeps the
# kernel's VMEM flat, so it still compiles.
@pytest.mark.parametrize("k", [8, 64, 256, 1000])
def test_fedavg_compiles(sds, k):
    _assert_native(fedavg_pallas, sds((k, MLP_PARAMS), jnp.float32),
                   sds((k,), jnp.float32))


@pytest.mark.parametrize("rows", [N_BLOCKS, N_CLIENTS * N_BLOCKS],
                         ids=["vector", "roster"])
def test_quantize_compiles(sds, rows):
    _assert_native(quantize_pallas, sds((rows, QBLOCK), jnp.float32))


@pytest.mark.parametrize("rows", [N_BLOCKS, N_CLIENTS * N_BLOCKS],
                         ids=["vector", "roster"])
def test_dequantize_compiles(sds, rows):
    _assert_native(dequantize_pallas, sds((rows, QBLOCK), jnp.int8),
                   sds((rows,), jnp.float32))


def test_topk_gather_compiles(sds):
    _assert_native(topk_gather_pallas,
                   sds((N_CLIENTS, MLP_PARAMS), jnp.float32),
                   sds((N_CLIENTS, K_KEPT), jnp.int32))


def test_topk_scatter_compiles(sds):
    _assert_native(topk_scatter_pallas,
                   sds((N_CLIENTS, K_KEPT), jnp.int32),
                   sds((N_CLIENTS, K_KEPT), jnp.float32), n=MLP_PARAMS)


def test_checksum_compiles(sds):
    _assert_native(checksum_pallas, sds((1 << 20,), jnp.int32))



def test_expert_grouped_products_compile(sds, monkeypatch):
    """The ``lm`` model's expert products at the Mellum2 widths (one silo's
    4,096 tokens x top-8 rows, 64 experts of 2304 -> 896, weights stored
    (out, in)): forward and the input gradient through the frozen weights
    lower to the megablox kernels."""
    from repro.models import transformer as T
    monkeypatch.setattr(T, "_megablox",
                        lambda *dims: all(d % 128 == 0 for d in dims))
    rows, d, f, e = 4096 * 8, 2304, 896, 64

    def dx(x, w, sizes):
        return jax.grad(lambda x: T.grouped_matmul(x, w, sizes, True)
                        .sum())(x)
    compiled = jax.jit(dx).lower(
        sds((rows, d), jnp.bfloat16), sds((e, f, d), jnp.bfloat16),
        sds((e,), jnp.int32)).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("fan_in,fan_out", [(2048, 1408), (1408, 2048)],
                         ids=["gate_up", "down"])
def test_moonlight_expert_products_compile(sds, monkeypatch, fan_in,
                                           fan_out):
    """The ``lm_mla`` model's expert products at the Moonlight widths (one
    silo's 4,096 tokens x top-6 rows, 64 experts of 2048 <-> 1408, a width
    that only the 128 tile divides): forward and the input gradient lower
    to the megablox kernels."""
    from repro.models import transformer as T
    monkeypatch.setattr(T, "_megablox",
                        lambda *dims: all(d % 128 == 0 for d in dims))
    rows, e = 4096 * 6, 64

    def dx(x, w, sizes):
        return jax.grad(lambda x: T.grouped_matmul(x, w, sizes, True)
                        .sum())(x)
    compiled = jax.jit(dx).lower(
        sds((rows, fan_in), jnp.bfloat16),
        sds((e, fan_out, fan_in), jnp.bfloat16),
        sds((e,), jnp.int32)).compile()
    assert "tpu_custom_call" in compiled.as_text()
