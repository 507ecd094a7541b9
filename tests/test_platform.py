"""What the code derives from the platform, and what refuses to run
without a chip.

* Kernel mode: ``interpret=None`` resolves to native lowering exactly on
  a TPU backend; an explicit bool always wins.
* Compile cache: ``JAX_COMPILATION_CACHE_DIR`` when set, else the fixed
  in-checkout ``.jax_cache`` (git-ignored) — never a path built from a
  temp name, a pid or the time.
* ``chip_smoke.py`` exits non-zero, printing no result, on a CPU backend
  and when its directory holds no sources.

Children run with ``JAX_PLATFORMS=cpu``: on a host with a chip, a child
that loads the TPU library would contend with this process for it.
"""

import json
import os
import shutil
import subprocess
import sys
import textwrap
from pathlib import Path

import jax
import pytest

from repro import compile_cache
from repro.kernels import resolve_interpret

REPO = Path(__file__).resolve().parents[1]


def _child_env(**extra):
    env = {"PATH": os.environ.get("PATH", "/usr/bin:/bin"),
           "PYTHONPATH": str(REPO / "src"), "JAX_PLATFORMS": "cpu"}
    env.update(extra)
    return env


@pytest.mark.parametrize("backend,want", [("tpu", False), ("cpu", True),
                                          ("gpu", True)])
def test_interpret_follows_platform(monkeypatch, backend, want):
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    assert resolve_interpret(None) is want
    assert resolve_interpret(True) is True
    assert resolve_interpret(False) is False


def test_cache_dir_is_fixed_inside_checkout():
    assert compile_cache.DEFAULT_CACHE_DIR == REPO / ".jax_cache"
    ignored = subprocess.run(["git", "check-ignore", "-q", ".jax_cache/x"],
                             cwd=REPO, capture_output=True)
    if ignored.returncode == 128:
        pytest.skip("not a git checkout")
    assert ignored.returncode == 0, ".jax_cache is not git-ignored"


_CACHE_PROBE = textwrap.dedent("""
    import jax, jax.numpy as jnp
    from repro.compile_cache import enable_compile_cache
    print(enable_compile_cache())
    print(jax.config.jax_compilation_cache_dir)
    jax.jit(lambda x: x * 3 + 1)(jnp.arange(8.0)).block_until_ready()
""")


def test_cache_defaults_to_checkout_dir():
    r = subprocess.run([sys.executable, "-c", _CACHE_PROBE],
                       capture_output=True, text=True, env=_child_env(),
                       timeout=300)
    assert r.returncode == 0, r.stderr[-1500:]
    returned, configured = r.stdout.split()[:2]
    assert returned == configured == str(REPO / ".jax_cache")


def test_cache_honours_env_dir(tmp_path):
    r = subprocess.run(
        [sys.executable, "-c", _CACHE_PROBE], capture_output=True,
        text=True, timeout=300,
        env=_child_env(JAX_COMPILATION_CACHE_DIR=str(tmp_path),
                       JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="0"))
    assert r.returncode == 0, r.stderr[-1500:]
    returned, configured = r.stdout.split()[:2]
    assert returned == configured == str(tmp_path)
    assert any(tmp_path.iterdir()), "no cache entry was written"


def _assert_no_result(r):
    assert r.returncode != 0
    for line in r.stdout.splitlines():
        try:
            obj = json.loads(line)
        except ValueError:
            continue
        assert not (isinstance(obj, dict) and obj.get("ok")), line


def test_chip_smoke_refuses_cpu():
    r = subprocess.run([sys.executable, str(REPO / "chip_smoke.py")],
                       capture_output=True, text=True, env=_child_env(),
                       cwd=REPO, timeout=300)
    _assert_no_result(r)
    assert "no TPU" in r.stderr


def test_chip_smoke_refuses_without_sources(tmp_path):
    shutil.copy(REPO / "chip_smoke.py", tmp_path / "chip_smoke.py")
    env = _child_env()
    env.pop("PYTHONPATH")
    r = subprocess.run([sys.executable, "chip_smoke.py"], capture_output=True,
                       text=True, env=env, cwd=tmp_path, timeout=300)
    _assert_no_result(r)
