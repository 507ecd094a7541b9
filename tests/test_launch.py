"""Launch-layer tests: loop-aware HLO cost analysis + dry-run plumbing."""

import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import pytest

from repro.launch.hlo_cost import analyze_hlo_text
from repro.launch.lowering import xla_cost_dict

# Children compile on the CPU backend only: on a host with a chip, a child
# that loads the TPU library would contend with this process for it.
CPU_CHILD_ENV = {"PYTHONPATH": "src", "PATH": "/usr/bin:/bin",
                 "JAX_PLATFORMS": "cpu"}


class TestHloCost:
    def test_matmul_flops_match_xla(self):
        a = jax.ShapeDtypeStruct((512, 512), jnp.bfloat16)
        c = jax.jit(lambda a, b: a @ b).lower(a, a).compile()
        mine = analyze_hlo_text(c.as_text())
        assert mine.flops == pytest.approx(2 * 512 ** 3, rel=1e-6)
        # XLA's own count agrees on a loop-free graph
        assert mine.flops == pytest.approx(xla_cost_dict(c)["flops"],
                                           rel=0.01)

    def test_scan_flops_are_trip_count_multiplied(self):
        """THE reason this module exists: cost_analysis() counts a while
        body once; the analyzer multiplies by known_trip_count."""
        a = jax.ShapeDtypeStruct((512, 512), jnp.bfloat16)
        f = jax.jit(lambda a, b: jax.lax.scan(
            lambda x, _: (x @ b, None), a, None, length=7)[0])
        c = f.lower(a, a).compile()
        assert analyze_hlo_text(c.as_text()).flops == 7 * 2 * 512 ** 3
        assert xla_cost_dict(c)["flops"] < 2 * 2 * 512 ** 3  # undercounts

    def test_nested_scan_multiplies(self):
        a = jax.ShapeDtypeStruct((128, 128), jnp.float32)

        def inner(x, b):
            return jax.lax.scan(lambda y, _: (y @ b, None), x, None,
                                length=3)[0]

        f = jax.jit(lambda a, b: jax.lax.scan(
            lambda x, _: (inner(x, b), None), a, None, length=5)[0])
        c = f.lower(a, a).compile()
        assert analyze_hlo_text(c.as_text()).flops == \
            15 * 2 * 128 ** 3

    def test_collectives_counted_with_multipliers(self):
        code = textwrap.dedent("""
            import os
            os.environ['XLA_FLAGS']='--xla_force_host_platform_device_count=4'
            import jax, jax.numpy as jnp
            from jax.sharding import PartitionSpec as P
            from repro.launch.hlo_cost import analyze_hlo_text
            mesh = jax.make_mesh((4,), ('x',))
            def f(a):
                return jax.shard_map(lambda v: jax.lax.psum(v, 'x'),
                                     mesh=mesh, in_specs=P('x'),
                                     out_specs=P(), check_vma=False)(a)
            a = jax.ShapeDtypeStruct((4, 256), jnp.float32)
            c = jax.jit(f).lower(a).compile()
            cost = analyze_hlo_text(c.as_text())
            ar = [k for k in cost.collective_counts if 'all-reduce' in k]
            assert ar, cost.collective_counts
            assert cost.collective_bytes > 0
            print('OK', cost.collective_bytes)
        """)
        r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                           text=True, env=CPU_CHILD_ENV)
        assert "OK" in r.stdout, r.stderr[-1500:]


class TestDryrunPlumbing:
    def test_smoke_cell_lowers_on_debug_mesh(self):
        """The full dry-run plumbing (rules, shardings, train step, HLO
        analysis) on a (2,2) mesh with a reduced config, in a subprocess so
        the main process keeps 1 device."""
        code = textwrap.dedent("""
            import os
            os.environ['XLA_FLAGS']='--xla_force_host_platform_device_count=4'
            import dataclasses, jax
            from repro.configs import get_config, smoke_variant, SHAPES
            from repro.configs.base import ShapeConfig, TrainConfig
            from repro.distributed import sharding as sh
            from repro.launch.lowering import _build_lowerable
            from repro.launch import hlo_cost

            cfg = dataclasses.replace(smoke_variant(get_config('yi-9b')),
                                      dtype='bfloat16')
            shape = ShapeConfig('t', 64, 8, 'train')
            mesh = jax.make_mesh((2, 2), ('data', 'model'),
                                 axis_types=(jax.sharding.AxisType.Auto,) * 2)
            rules = sh.rules_for(cfg, shape, mesh)
            with sh.use_mesh(mesh, rules):
                fn, args = _build_lowerable(
                    cfg, shape, mesh, rules, attn_impl='einsum',
                    train_cfg=TrainConfig(grad_accum=2))
                compiled = fn.lower(*args).compile()
            mem = compiled.memory_analysis()
            cost = hlo_cost.analyze_hlo_text(compiled.as_text())
            assert mem.temp_size_in_bytes > 0
            assert cost.flops > 0
            assert cost.collective_bytes > 0   # grad reduce must exist
            print('OK')
        """)
        # Hang guard only, not a speed assertion: the yi-9b smoke compile
        # takes minutes on a share-throttled CPU, and 300s proved flaky.
        r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                           text=True, timeout=1200,
                           env=CPU_CHILD_ENV)
        assert "OK" in r.stdout, (r.stdout[-500:], r.stderr[-1500:])

    def test_rules_divisibility_fallbacks(self):
        """hymba's 25 heads / whisper's 6 heads cannot shard 16 ways -> the
        rules builder must drop those mappings, never crash."""
        code = textwrap.dedent("""
            import os
            os.environ['XLA_FLAGS']='--xla_force_host_platform_device_count=4'
            import jax
            from repro.configs import get_config, SHAPES
            from repro.distributed import sharding as sh
            mesh = jax.make_mesh((2, 2), ('data', 'model'),
                                 axis_types=(jax.sharding.AxisType.Auto,) * 2)
            for arch, heads_dropped in (('hymba-1.5b', False),
                                        ('whisper-tiny', True)):
                cfg = get_config(arch)
                r = sh.rules_for(cfg, SHAPES['train_4k'], mesh)
                if cfg.num_heads % 2 != 0:
                    assert r['heads'] is None
                assert r['batch'] is not None
            # on the production 16-way axis both drop heads
            mesh16 = None
            print('OK')
        """)
        r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                           text=True, env=CPU_CHILD_ENV)
        assert "OK" in r.stdout, r.stderr[-1500:]

    def test_skip_policy(self):
        from repro.launch.lowering import cell_is_skipped
        assert cell_is_skipped("granite-34b", "long_500k") is not None
        assert cell_is_skipped("xlstm-350m", "long_500k") is None
        assert cell_is_skipped("gemma3-12b", "long_500k") is None
        assert cell_is_skipped("granite-34b", "train_4k") is None

    def test_model_flops_conventions(self):
        from repro.configs import SHAPES, get_config
        from repro.launch.lowering import model_flops
        cfg = get_config("yi-9b")
        n = cfg.active_param_count()
        assert model_flops(cfg, SHAPES["train_4k"]) == \
            pytest.approx(6 * n * 256 * 4096)
        assert model_flops(cfg, SHAPES["decode_32k"]) == \
            pytest.approx(2 * n * 128)
        moe = get_config("qwen3-moe-235b-a22b")
        assert moe.active_param_count() < 0.15 * moe.param_count()
