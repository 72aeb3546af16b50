"""ZeRO configuration.

Counterpart of the reference's ``deepspeed/runtime/zero/config.py``
(``DeepSpeedZeroConfig`` pydantic model, :78) and
``zero/offload_config.py``.  All the reference's knobs are accepted (with the
same ``stage3_*`` aliases); knobs that hand-tune CUDA stream/bucket behavior
the XLA scheduler owns on TPU are recorded and surfaced as scheduling hints
rather than driving a hand-rolled bucketer — see
``deepspeed_tpu/runtime/zero/partitioner.py`` for how each stage maps to mesh
sharding.
"""

from __future__ import annotations

import dataclasses
from typing import Dict, Optional

from ..config_utils import DeepSpeedConfigModel

ZERO_OPTIMIZATION = "zero_optimization"


class OffloadDeviceEnum:
    none = "none"
    cpu = "cpu"
    nvme = "nvme"


@dataclasses.dataclass
class DeepSpeedZeroOffloadParamConfig(DeepSpeedConfigModel):
    """Where ZeRO-3 parameter shards live between uses (offload_config.py)."""

    device: str = OffloadDeviceEnum.none
    nvme_path: Optional[str] = None
    buffer_count: int = 5
    buffer_size: int = int(1e8)
    max_in_cpu: int = int(1e9)
    pin_memory: bool = False


@dataclasses.dataclass
class DeepSpeedZeroOffloadOptimizerConfig(DeepSpeedConfigModel):
    """Where optimizer states (and fp32 master weights) live."""

    device: str = OffloadDeviceEnum.none
    nvme_path: Optional[str] = None
    buffer_count: int = 4
    pin_memory: bool = False
    pipeline_read: bool = False
    pipeline_write: bool = False
    fast_init: bool = False
    #: error-feedback compression of the device->host gradient stream:
    #: "none" | "onebit" (sign + per-block L1 scale, 16x smaller than
    #: bf16 — the 1-bit Adam quantizer applied to the host link) |
    #: "int8" (per-block absmax, 2x smaller).  The quantization error is
    #: carried in a device-resident residual and re-injected next step
    #: (error feedback), preserving convergence.  The reference streams
    #: uncompressed fp16 over PCIe (ZeRO-Infinity); over slower host
    #: links (DCN-attached hosts) compression is what keeps the
    #: optimizer step off the critical path.
    grad_compression: str = "none"
    #: scale-block granularity for grad_compression (elements per scale)
    compression_block: int = 2048
    #: dtype of the error-feedback residual ("fp32" | "bf16"); bf16
    #: halves the residual's HBM at a small fidelity cost
    compression_residual_dtype: str = "fp32"
    #: overlap leaf i+1's device->host gradient stream with leaf i's host
    #: Adam step and param upload (the reference overlaps IPG buckets
    #: with CUDA copy streams).  Costs one extra in-flight 16-bit leaf of
    #: HBM; disable to restore the strict one-leaf transient.
    #: Single-process only — the multi-host step path ignores this flag.
    pipeline_transfers: bool = True

    @property
    def pipeline(self) -> bool:
        return self.pipeline_read or self.pipeline_write


@dataclasses.dataclass
class DeepSpeedZeroConfig(DeepSpeedConfigModel):
    """ZeRO section: stages 0-3 + offload (reference zero/config.py:78).

    TPU mapping of each stage (mechanism differs, semantics preserved):
      stage 0: replicated params/grads/opt-state; grad psum over dp.
      stage 1: optimizer state sharded over the dp mesh axes (weight-update
               sharding); grads all-reduced; updated shards all-gathered.
      stage 2: + gradients reduce-scattered at the accumulation boundary.
      stage 3: + parameters stored sharded (FSDP); XLA inserts the per-layer
               all-gathers the reference's coordinator issues by hand.
    """

    DEPRECATED_FIELDS = {
        "cpu_offload": "offload_optimizer",
        "cpu_offload_params": "offload_param",
        "stage3_prefetch_bucket_size": "prefetch_bucket_size",
        "stage3_param_persistence_threshold": "param_persistence_threshold",
        "stage3_model_persistence_threshold": "model_persistence_threshold",
        "stage3_max_live_parameters": "max_live_parameters",
        "stage3_max_reuse_distance": "max_reuse_distance",
        "stage3_gather_16bit_weights_on_model_save": "gather_16bit_weights_on_model_save",
        "stage3_gather_fp16_weights_on_model_save": "gather_16bit_weights_on_model_save",
    }

    stage: int = 0
    contiguous_gradients: bool = True
    reduce_scatter: bool = True
    reduce_bucket_size: int = int(5e8)
    allgather_partitions: bool = True
    allgather_bucket_size: int = int(5e8)
    overlap_comm: Optional[bool] = None
    load_from_fp32_weights: bool = True
    elastic_checkpoint: bool = False
    offload_param: Optional[Dict] = None
    offload_optimizer: Optional[Dict] = None
    sub_group_size: int = int(1e9)
    prefetch_bucket_size: int = int(5e7)
    param_persistence_threshold: int = int(1e5)
    model_persistence_threshold: int = int(1e15) // 2  # sys.maxsize analogue
    max_live_parameters: int = int(1e9)
    max_reuse_distance: int = int(1e9)
    gather_16bit_weights_on_model_save: bool = False
    ignore_unused_parameters: bool = True
    legacy_stage1: bool = False
    round_robin_gradients: bool = False
    #: route the intra-slice (ICI) gradient reduce through the explicit
    #: blockwise-quantized reduce-scatter/all-gather
    #: (``runtime/comm/quantized.py``) instead of the compiler-implicit
    #: full-precision psum: "none" | "int8" | "int4".  Gradients then
    #: accumulate as per-data-rank partials across the gas window and
    #: cross the 'data' mesh axis once per boundary step, quantized both
    #: directions with device-resident error feedback.  Costs one full
    #: (unsharded) gradient tree of accumulator per device during the gas
    #: window; see docs/performance.md "Quantized collectives".
    quantized_collectives: str = "none"
    #: elements per fp32 wire scale for quantized_collectives; multiple of 8
    quantized_block: int = 2048

    offload_param_config: DeepSpeedZeroOffloadParamConfig = dataclasses.field(
        default_factory=DeepSpeedZeroOffloadParamConfig)
    offload_optimizer_config: DeepSpeedZeroOffloadOptimizerConfig = dataclasses.field(
        default_factory=DeepSpeedZeroOffloadOptimizerConfig)

    def __post_init__(self):
        if not 0 <= self.stage <= 3:
            raise ValueError(f"zero stage must be 0-3, got {self.stage}")
        self.quantized_collectives = str(self.quantized_collectives).lower()
        if self.quantized_collectives not in ("none", "int8", "int4"):
            raise ValueError(
                f"zero_optimization.quantized_collectives="
                f"{self.quantized_collectives!r} (want 'none', 'int8' or "
                "'int4')")
        if self.quantized_block <= 0 or self.quantized_block % 8:
            raise ValueError(
                f"zero_optimization.quantized_block={self.quantized_block!r} "
                "(want a positive multiple of 8)")
        # booleans arriving through the deprecated cpu_offload path
        if isinstance(self.offload_optimizer, bool):
            self.offload_optimizer = {"device": "cpu"} if self.offload_optimizer else None
        if isinstance(self.offload_param, bool):
            self.offload_param = {"device": "cpu"} if self.offload_param else None
        if isinstance(self.offload_param, dict):
            self.offload_param_config = DeepSpeedZeroOffloadParamConfig.from_dict(
                self.offload_param)
        if isinstance(self.offload_optimizer, dict):
            self.offload_optimizer_config = DeepSpeedZeroOffloadOptimizerConfig.from_dict(
                self.offload_optimizer)
        if self.overlap_comm is None:
            # reference default: True for stage 3, False otherwise (zero/config.py)
            self.overlap_comm = self.stage == 3

    @property
    def offload_optimizer_device(self) -> str:
        return self.offload_optimizer_config.device

    @property
    def offload_param_device(self) -> str:
        return self.offload_param_config.device

    @property
    def cpu_offload(self) -> bool:
        return self.offload_optimizer_device == OffloadDeviceEnum.cpu
