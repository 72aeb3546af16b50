"""Accelerator abstraction (reference ``deepspeed/accelerator/
abstract_accelerator.py:5`` ``DeepSpeedAccelerator``).

The reference abstracts torch.cuda behind an interface so the runtime can
target CUDA/ROCm/CPU uniformly.  Here the abstraction sits over JAX
platforms: one interface answers device identity/count, synchronization,
memory telemetry, dtype capability, and RNG — backed by ``jax.devices()``
of the selected platform.  Runtime code (env report, timers, bench, memory
logging) asks the accelerator instead of probing ``jax`` directly, so CPU
CI, a single v5e chip, and a pod slice all look the same.
"""

from __future__ import annotations

import abc
from typing import Any, Dict, List, Optional


_FENCE = None


def _fence_fn():
    """Cached jitted no-op (jit caches by function identity — a fresh
    lambda per fence would retrace/compile every call)."""
    global _FENCE
    if _FENCE is None:
        import jax

        _FENCE = jax.jit(lambda v: v + 1.0)
    return _FENCE


class DeepSpeedAccelerator(abc.ABC):
    """Platform interface.  Concrete: TpuAccelerator / CpuAccelerator."""

    def __init__(self) -> None:
        self._name: str = "abstract"
        self._communication_backend_name: str = "xla"

    # ------------------------------------------------------------- identity
    def device_name(self, device_index: Optional[int] = None) -> str:
        if device_index is None:
            return self._name
        return f"{self._name}:{device_index}"

    def communication_backend_name(self) -> str:
        """'xla' — collectives lower to XLA ops over ICI/DCN (the
        reference answers 'nccl' here)."""
        return self._communication_backend_name

    @abc.abstractmethod
    def devices(self) -> List[Any]:
        ...

    def device_count(self) -> int:
        return len(self.devices())

    def current_device(self) -> int:
        return 0

    def current_device_name(self) -> str:
        return self.device_name(self.current_device())

    # ------------------------------------------------------- execution
    def synchronize(self, device_index: Optional[int] = None) -> None:
        """Fence: block until all dispatched work on the device finished.
        (reference: torch.cuda.synchronize)

        A jitted no-op is enqueued on the device's compute stream — TPU
        executes programs in order, so it completes only after everything
        already queued (a bare ``device_put`` rides the DMA path without
        waiting for queued compute).
        """
        import jax

        devices = self.devices()
        if not devices:
            return  # nothing dispatched anywhere: a fence is trivially done
        dev = devices[0 if device_index is None else device_index]
        jax.block_until_ready(_fence_fn()(jax.device_put(0.0, dev)))

    # ------------------------------------------------------- capabilities
    @abc.abstractmethod
    def is_bf16_supported(self) -> bool:
        ...

    @abc.abstractmethod
    def is_fp16_supported(self) -> bool:
        ...

    def is_available(self) -> bool:
        return self.device_count() > 0

    # ------------------------------------------------------------- memory
    def memory_stats(self, device_index: int = 0) -> Dict[str, int]:
        d = self.devices()[device_index]
        stats = getattr(d, "memory_stats", lambda: None)()
        return dict(stats) if stats else {}

    def memory_allocated(self, device_index: int = 0) -> int:
        return int(self.memory_stats(device_index).get("bytes_in_use", 0))

    def max_memory_allocated(self, device_index: int = 0) -> int:
        s = self.memory_stats(device_index)
        return int(s.get("peak_bytes_in_use", s.get("bytes_in_use", 0)))

    def total_memory(self, device_index: int = 0) -> int:
        return int(self.memory_stats(device_index).get("bytes_limit", 0))

    # ---------------------------------------------------------------- rng
    def manual_seed(self, seed: int):
        """Returns a fresh PRNG key (functional RNG — no global state to
        set, the key IS the seed)."""
        import jax

        return jax.random.PRNGKey(seed)

    # ------------------------------------------------------------- naming
    def on_accelerator(self, array: Any) -> bool:
        try:
            shards = array.devices() if callable(
                getattr(array, "devices", None)) else []
        except Exception:
            return False
        mine = set(self.devices())
        return bool(shards) and set(shards) <= mine
