"""Accelerator selection (reference ``accelerator/real_accelerator.py:15``
``get_accelerator``): pick the concrete accelerator once, cache the
singleton.  Selection order: explicit ``DS_ACCELERATOR`` env override →
whatever platform JAX initialized (tpu → TpuAccelerator, else CPU)."""

from __future__ import annotations

import os
from typing import Any, List, Optional

from .abstract_accelerator import DeepSpeedAccelerator


class TpuAccelerator(DeepSpeedAccelerator):
    def __init__(self) -> None:
        super().__init__()
        self._name = "tpu"

    def devices(self) -> List[Any]:
        import jax

        return [d for d in jax.devices() if d.platform == "tpu"]

    def is_bf16_supported(self) -> bool:
        return True

    def is_fp16_supported(self) -> bool:
        # fp16 works on the VPU but the MXU wants bf16; supported = yes
        return True

    def device_kind(self) -> str:
        ds = self.devices()
        return getattr(ds[0], "device_kind", "tpu") if ds else "tpu"


class CpuAccelerator(DeepSpeedAccelerator):
    def __init__(self) -> None:
        super().__init__()
        self._name = "cpu"

    def devices(self) -> List[Any]:
        import jax

        return [d for d in jax.devices() if d.platform == "cpu"]

    def is_bf16_supported(self) -> bool:
        return True          # emulated on host; numerics are correct

    def is_fp16_supported(self) -> bool:
        return True


_accelerator: Optional[DeepSpeedAccelerator] = None


def get_accelerator() -> DeepSpeedAccelerator:
    global _accelerator
    if _accelerator is not None:
        return _accelerator
    name = os.environ.get("DS_ACCELERATOR", "").strip().lower()
    if not name:
        import jax

        name = jax.devices()[0].platform
    _accelerator = TpuAccelerator() if name == "tpu" else CpuAccelerator()
    return _accelerator


def set_accelerator(accel: DeepSpeedAccelerator) -> None:
    """Test/override hook (the reference allows pre-seeding the global)."""
    global _accelerator
    _accelerator = accel
