"""Process-level JAX bootstrap shared by the entry points: the CPU pin
for tests and CPU harnesses, the chip requirement for measurement paths,
and the persistent compile cache.

Importing this module does not import jax; every helper must run before
the first backend touch (``force_cpu_platform``) or the first compile
(``enable_compile_cache``) of its process.
"""

from __future__ import annotations

import os
import re

_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; returns its directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, names the directory (jax
    reads the variable itself; nothing here overrides it).  Otherwise the
    cache lives at the fixed ``<checkout>/.jax_cache``: the path is part
    of the cache key, so it must be the same in every process.  Every
    program is cached, however quick its compile, so that a warm process
    compiles nothing.  Call before the first compile.
    """
    import jax

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir",
                          os.path.join(_CHECKOUT, ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return jax.config.jax_compilation_cache_dir


def require_tpu() -> list:
    """``jax.devices()`` of the attached TPU; raises where there is none.

    Measurement entry points (``chip_smoke.py``, ``benchmarks/chip/run.py``)
    call this first: a missing chip is an error, never a CPU
    number under a device metric's name.
    """
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise RuntimeError(
            f"no TPU: jax.devices() reports {len(devices)} x "
            f"{devices[0].platform!r} (JAX_PLATFORMS="
            f"{os.environ.get('JAX_PLATFORMS')!r}); this entry point "
            f"measures the chip and has no CPU path")
    return devices


def force_cpu_platform(n_devices: int = 8,
                       persistent_cache: bool = True) -> None:
    """Pin jax to the CPU platform with ``n_devices`` virtual devices.

    Must be called before the jax backend is initialized: the platform
    and the device count are latched at the first backend touch.  Raises
    if it is too late for the request to take effect.
    """
    flag = f"--xla_force_host_platform_device_count={n_devices}"
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" in flags:
        # replace a stale/smaller count rather than trusting it
        flags = re.sub(r"--xla_force_host_platform_device_count=\d+", flag, flags)
    else:
        flags = (flags + " " + flag).strip()
    os.environ["XLA_FLAGS"] = flags
    os.environ["JAX_PLATFORMS"] = "cpu"

    import jax
    from jax._src import xla_bridge

    if xla_bridge.backends_are_initialized():
        if jax.default_backend() != "cpu" or len(jax.devices()) < n_devices:
            raise RuntimeError(
                f"force_cpu_platform({n_devices}) called after jax backend "
                f"init ({len(jax.devices())} x {jax.default_backend()}); "
                f"call it before any jax device/array operation, or run in "
                f"a fresh process")
    jax.config.update("jax_platforms", "cpu")

    # The test suite and the CPU fleets pass persistent_cache=False: a
    # cold compile per process keeps them independent of whatever an
    # earlier run left in the cache directory.
    if persistent_cache:
        enable_compile_cache()
