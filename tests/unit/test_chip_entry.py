"""The entry points that measure the chip, run where there is none: each
must exit non-zero, name the missing chip and print no result.  And the
compile-cache helper they all call before their first compile.

Everything runs in subprocesses: platform and cache are process state that
this pytest process has already latched (tests/conftest.py)."""

import os
import subprocess
import sys

import pytest

REPO = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", ".."))


def _run(argv, env_extra=None, timeout=300):
    env = {k: v for k, v in os.environ.items()
           if k not in ("JAX_COMPILATION_CACHE_DIR", "XLA_FLAGS")}
    env.update({"JAX_PLATFORMS": "cpu", "PYTHONPATH": REPO})
    env.update(env_extra or {})
    return subprocess.run([sys.executable] + argv, capture_output=True,
                          text=True, timeout=timeout, cwd=REPO, env=env)


@pytest.mark.parametrize("argv", [
    ["chip_smoke.py"], ["chip_smoke.py", "--chips", "4"],
], ids=" ".join)
def test_refuses_to_run_without_a_chip(argv):
    r = _run(argv)
    assert r.returncode != 0, r.stdout[-2000:]
    assert "no TPU" in r.stderr, r.stderr[-2000:]
    assert not [ln for ln in r.stdout.splitlines() if ln.startswith("{")], \
        f"a result was printed without a chip: {r.stdout[-2000:]}"


_REPORT = ("import jax\n"
           "print('DIR', jax.config.jax_compilation_cache_dir)\n")


def _cache_dir(code, env_extra=None):
    r = _run(["-c", code + _REPORT], env_extra)
    assert r.returncode == 0, r.stderr[-2000:]
    return [ln[4:] for ln in r.stdout.splitlines() if ln.startswith("DIR ")]


_ENABLE = ("from deepspeed_tpu.utils.platform import enable_compile_cache\n"
           "print('DIR', enable_compile_cache())\n")
_FORCE_CPU = ("from deepspeed_tpu.utils.platform import force_cpu_platform\n"
              "force_cpu_platform(2)\n")


def test_compile_cache_default_is_fixed_in_the_checkout():
    """No pid, time or temp name in the path: the path is part of the cache
    key, so two processes must agree on it."""
    want = os.path.join(REPO, ".jax_cache")
    assert _cache_dir(_ENABLE) == [want, want]
    assert _cache_dir(_ENABLE) == [want, want]   # a second process


@pytest.mark.parametrize("code", [_ENABLE, _FORCE_CPU],
                         ids=["enable_compile_cache", "force_cpu_platform"])
def test_compile_cache_leaves_the_environments_directory_alone(code, tmp_path):
    """With JAX_COMPILATION_CACHE_DIR set no code path names another
    directory, and the cache is on: a compile lands in it."""
    compile_one = ("import jax.numpy as jnp\n"
                   "jax.jit(lambda x: x * 2 + 1)(jnp.ones((8, 8)))"
                   ".block_until_ready()\n")
    dirs = _cache_dir(code + _REPORT + compile_one,
                      {"JAX_COMPILATION_CACHE_DIR": str(tmp_path)})
    assert set(dirs) == {str(tmp_path)}
    assert os.listdir(tmp_path), "nothing was written to the cache"
