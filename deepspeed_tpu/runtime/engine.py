"""The training engine.

Counterpart of the reference's ``DeepSpeedEngine`` (``runtime/engine.py:182``):
same lifecycle (``initialize() → engine``; ``forward/backward/step`` with
gradient-accumulation boundaries, loss scaling, overflow skip, clipping,
checkpoint save/load, throughput/wall-clock timers), rebuilt on JAX:

- The train step is a jitted pure function over a ``TrainState`` pytree;
  ZeRO stages are sharding annotations (``runtime/zero/partitioner.py``)
  rather than flat-buffer partitioning + hooks.
- ``forward(batch)`` computes loss AND gradients in one fused
  value_and_grad program (autograd cannot be replayed from a returned loss
  value in JAX); ``backward()`` performs the accumulation bookkeeping and
  ``step()`` applies the update at the gas boundary — call pattern and
  semantics match the reference (engine.py forward:1664, backward:1811,
  step:2018, ``is_gradient_accumulation_boundary``:1902).
- ``train_batch_fused()`` additionally offers a whole-batch path (gas
  micro-steps + update inside one jit via ``lax.scan``) that the reference
  cannot express; it is the benchmark path.
"""

from __future__ import annotations

import dataclasses
import json
import os
import time
from functools import partial
from typing import Any, Callable, Dict, Iterable, List, Optional, Tuple, Union

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax
from jax import shard_map
from jax.sharding import NamedSharding, PartitionSpec as P

from ..comm import comm as dist
from ..models.partitioning import FSDP_RULES, TP_RULES, tree_specs, validate_specs
from ..ops.optimizer import (TpuOptimizer, get_optimizer_class,
                             resolve_param_groups)
from ..parallel.mesh import (DATA_AXIS, DCN_AXIS, EXPERT_AXIS, MeshManager,
                             ParallelDims, get_mesh_manager, initialize_mesh)
from ..telemetry.metrics import (MetricName, MetricsRegistry,
                                 MetricsSampler, analytic_mfu,
                                 host_rss_bytes, live_buffer_bytes,
                                 peak_flops_per_chip)
from ..telemetry.spans import SpanName, Tracer
from ..utils.compile_watch import CompiledProgramRegistry, hot_path
from ..utils.logging import log_dist, logger
from ..utils.timer import SynchronizedWallClockTimer, ThroughputTimer
from . import loss_scaler as ls
from .config import DeepSpeedConfig, DeepSpeedConfigError
from .dataloader import DeepSpeedDataLoader
from .lr_schedules import get_lr_schedule_class
from .model import ModelSpec
from .utils import clip_grads_by_global_norm, global_grad_norm, has_overflow
from .zero.partitioner import ZeroPartitioner

PyTree = Any

MEMORY_OPT_ALLREDUCE_SIZE = 500000000


def _dtype_of(cfg: DeepSpeedConfig):
    if cfg.fp16_enabled:
        return jnp.float16
    if cfg.bfloat16_enabled:
        return jnp.bfloat16
    return jnp.float32


class DeepSpeedEngine:
    """DeepSpeed-style training engine over a jitted, sharded train step."""

    def __init__(self,
                 args=None,
                 model: Optional[ModelSpec] = None,
                 optimizer: Optional[Union[TpuOptimizer, Callable]] = None,
                 model_parameters: Optional[PyTree] = None,
                 training_data=None,
                 lr_scheduler=None,
                 mpu=None,
                 dist_init_required: Optional[bool] = None,
                 collate_fn=None,
                 config: Optional[Union[str, Dict]] = None,
                 config_class: Optional[DeepSpeedConfig] = None,
                 mesh_manager: Optional[MeshManager] = None,
                 rng: Optional[jax.Array] = None,
                 dont_change_device: bool = False):
        assert model is not None, "deepspeed_tpu.initialize requires a ModelSpec"
        dist.init_distributed(dist_init_required=dist_init_required)

        self.mesh_manager = mesh_manager or get_mesh_manager()
        self.mesh = self.mesh_manager.mesh
        self._config = config_class or DeepSpeedConfig(
            config, mesh_manager=self.mesh_manager, model=model)
        self.module = model  # name kept for reference parity
        self.training_data = training_data
        self.collate_fn = collate_fn
        self.client_lr_scheduler = lr_scheduler

        # counters (reference engine.py attribute names)
        self.micro_steps = 0
        self.global_steps = 0
        self.global_samples = 0
        self.skipped_steps = 0

        # registered resumable data iterator: its O(1) position state rides
        # in every checkpoint's client_state so any resume (elastic restart,
        # fallback chain, rollback) lands on the exact next batch
        self.data_iterator = None

        #: every jitted program the step loop drives, by name — the
        #: compile-discipline gate (utils/compile_watch.py) watches this
        #: (the serving stack's compile_counts() contract, generalized)
        self.compile_registry = CompiledProgramRegistry("engine")

        # timers kept for API parity; their device sync is opt-in per
        # timer now and routed through the registry (docs/telemetry.md)
        self.timers = SynchronizedWallClockTimer(
            sync_registry=self.compile_registry)
        self.tput_timer = ThroughputTimer(
            batch_size=self.train_batch_size(),
            steps_per_output=self._config.steps_per_print,
            sync_registry=self.compile_registry)
        self._configure_telemetry()

        self.compute_dtype = _dtype_of(self._config)
        self.grad_accum_dtype = self._resolve_grad_accum_dtype()
        self.scaler_config = ls.LossScalerConfig.from_ds_config(self._config)
        self.loss_scaler = ls.LossScaler(self.scaler_config)

        # ZeRO-Offload / Infinity: optimizer states on host (cpu) or swap
        # files (nvme); device handles fwd/bwd + grad prep, host steps Adam
        # (reference stage_1_and_2.py cpu_offload / stage3 NVMe swapping)
        _ocfg = self._config.zero_config.offload_optimizer_config
        self._offload_device = _ocfg.device if _ocfg.device != "none" else None
        self._offload_cfg = _ocfg

        # Explicit gradient-collapse modes: gradients accumulate as
        # PER-WORKER partials (leading [n] dim over the collapse axis) and
        # cross that axis only once per boundary step.
        #
        # (a) inter-slice (DCN) data parallelism: full-precision mean, or
        #     the error-feedback collectives under dcn.grad_compression —
        #     "onebit" (reference runtime/comm/nccl.py:51) or the
        #     blockwise-quantized "int8"/"int4" middle rungs
        #     (runtime/comm/quantized.py, EQuARX);
        # (b) zero_optimization.quantized_collectives: the intra-slice
        #     (ICI, 'data' axis) grad reduce as an explicit quantized
        #     reduce-scatter + all-gather instead of the compiler-implicit
        #     full-precision psum.
        self._dcn_n = int(self.mesh.shape.get(DCN_AXIS, 1))
        dcn_mode = self._dcn_n > 1
        self._dcn_compress = self._config.dcn_grad_compression
        zq = self._config.zero_config.quantized_collectives
        if self._dcn_compress != "none" and not dcn_mode:
            raise DeepSpeedConfigError(
                "dcn.grad_compression needs a multi-slice mesh "
                "(ParallelDims(dcn=...) > 1)")
        if zq != "none":
            if dcn_mode:
                raise DeepSpeedConfigError(
                    "zero_optimization.quantized_collectives does not "
                    "compose with a multi-slice (dcn>1) mesh yet — use "
                    "dcn.grad_compression for the slow-axis reduce")
            if int(self.mesh.shape.get(EXPERT_AXIS, 1)) > 1:
                raise DeepSpeedConfigError(
                    "zero_optimization.quantized_collectives does not "
                    "compose with expert parallelism (ep>1) yet")
            if int(self.mesh.shape.get(DATA_AXIS, 1)) < 2:
                raise DeepSpeedConfigError(
                    "zero_optimization.quantized_collectives needs a "
                    "data-parallel mesh axis (data > 1)")
        # unified collapse parameters: axis/world/mode/block (None axis =
        # the classic fully-implicit path)
        if dcn_mode:
            self._collapse_axis: Optional[str] = DCN_AXIS
            self._collapse_n = self._dcn_n
            self._collapse_mode = self._dcn_compress \
                if self._dcn_compress != "none" else "mean"
            self._collapse_block = self._config.dcn_compression_block
        elif zq != "none":
            self._collapse_axis = DATA_AXIS
            self._collapse_n = int(self.mesh.shape[DATA_AXIS])
            self._collapse_mode = zq
            self._collapse_block = self._config.zero_config.quantized_block
        else:
            self._collapse_axis = None
            self._collapse_n = 1
            self._collapse_mode = "mean"
            self._collapse_block = 0
        if self._collapse_axis is not None:
            if self._offload_device is not None:
                raise DeepSpeedConfigError(
                    "explicit grad collapse (dcn>1 or quantized_collectives)"
                    " does not compose with offload_optimizer yet")
            if self.module.meta.get("pipeline"):
                raise DeepSpeedConfigError(
                    "explicit grad collapse (dcn>1 or quantized_collectives)"
                    " does not compose with the pipeline engine yet")
        self._dcn_reduce = None

        self._configure_sharding()
        self._configure_optimizer(optimizer, model_parameters)
        self._configure_lr_scheduler(lr_scheduler)
        self._init_state(rng)
        self._build_steps()
        self._init_param_spill()

        # progressive layer drop + curriculum (reference engine.py:1554/1559
        # construction, :1698-1710 per-forward injection)
        self._pld = None
        if self._config.pld_enabled:
            from .progressive_layer_drop import ProgressiveLayerDrop
            p = self._config.pld_params or {}
            self._pld = ProgressiveLayerDrop(
                theta=p.get("theta", 0.5), gamma=p.get("gamma", 0.001))
        self._curriculum = None
        if self._config.curriculum_enabled:
            from .data_pipeline.curriculum_scheduler import CurriculumScheduler
            self._curriculum = CurriculumScheduler(self._config.curriculum_params)
            self._curriculum_buckets = self._seqlen_buckets(
                self._config.curriculum_params)

        # checkpoint backend (reference _configure_checkpointing, torch vs
        # nebula): async_save runs writers in the background, committing
        # before the latest marker publishes
        self._checkpoint_engine = None
        if self._config.checkpoint_config.async_save:
            from .checkpoint_engine.async_checkpoint_engine import (
                AsyncCheckpointEngine)
            self._checkpoint_engine = AsyncCheckpointEngine(
                self._config.checkpoint_config)
        # multi-host commit/consensus context: the elastic runner attaches
        # one carrying its journal + heartbeat monitor; without a runner a
        # default is built lazily from the live comm world (see
        # _commit_context)
        self._commit_ctx = None

        # compression scheduler (reference engine.py:2002 steps it at every
        # optimizer step); the in-graph gating reads the step scalar the
        # engine threads through the batch
        self._compression_scheduler = None
        if self._config.compression_config_dict:
            from ..compression import CompressionScheduler
            self._compression_scheduler = CompressionScheduler(
                {"compression_training": self._config.compression_config_dict})

        # telemetry fan-out (reference MonitorMaster, engine.py:1840/2069)
        from ..monitor import MonitorMaster, get_monitor_config
        self.monitor = MonitorMaster(
            get_monitor_config(self._config.monitor_config_dict),
            rank=self.global_rank)

        self.training_dataloader = self.deepspeed_io(training_data) if training_data is not None else None

        # caches for the forward/backward/step protocol
        self._pending: Optional[Tuple[Any, Any]] = None  # (loss, ready flag)
        self._training = True   # train()/eval() parity toggle
        self._zero_tree_jit = None
        self._last_lr_kwargs: Dict[str, float] = {}

        if self.global_rank == 0:
            log_dist(f"DeepSpeedEngine configured: {self.zero_partitioner.describe()}; "
                     f"dtype={self.compute_dtype.__name__}, "
                     f"gas={self.gradient_accumulation_steps()}, "
                     f"micro_batch={self.train_micro_batch_size_per_gpu()}, "
                     f"train_batch={self.train_batch_size()}", ranks=[0])

    # ------------------------------------------------------------------ config accessors (reference API)
    def train_batch_size(self) -> int:
        return self._config.train_batch_size

    def train_micro_batch_size_per_gpu(self) -> int:
        return self._config.train_micro_batch_size_per_gpu

    def gradient_accumulation_steps(self) -> int:
        return self._config.gradient_accumulation_steps

    def gradient_clipping(self) -> float:
        return self._config.gradient_clipping

    def zero_optimization_stage(self) -> int:
        return self._config.zero_optimization_stage

    def zero_optimization(self) -> bool:
        return self._config.zero_enabled

    def fp16_enabled(self) -> bool:
        return self._config.fp16_enabled

    def bfloat16_enabled(self) -> bool:
        return self._config.bfloat16_enabled

    def steps_per_print(self) -> int:
        return self._config.steps_per_print

    def wall_clock_breakdown(self) -> bool:
        return self._config.wall_clock_breakdown

    @property
    def global_rank(self) -> int:
        return dist.get_rank()

    @property
    def world_size(self) -> int:
        return self.mesh_manager.world_size

    @property
    def dp_world_size(self) -> int:
        return self.mesh_manager.dp_world_size

    @property
    def cur_scale(self) -> float:
        return float(self.state["scale"]["loss_scale"])

    @property
    def lr_scheduler(self):
        return self._lr_scheduler

    def get_lr(self) -> List[float]:
        return [g["lr"] for g in self.optimizer.param_groups]

    def get_global_grad_norm(self) -> Optional[float]:
        return self._last_global_norm

    def reset_loss_scale(self) -> None:
        """Reinitialize the dynamic loss-scale state (scale, good-step
        counter, hysteresis).  Used by the supervision rollback policy: the
        carried scaler trajectory belongs to the diverged run and would
        otherwise re-enter the step that overflowed at the same scale."""
        self.state["scale"] = ls.init_state(self.scaler_config)

    # ------------------------------------------------------------- telemetry
    def _configure_telemetry(self) -> None:
        """Build the tracer + metrics stream from the ``"telemetry"``
        section.  ``wall_clock_breakdown`` alone also enables spans — the
        old ``SynchronizedWallClockTimer`` log lines are now derived from
        span aggregates, so both consumers feed from one instrumentation
        point (docs/telemetry.md)."""
        tcfg = self._config.telemetry_config
        spans_on = (tcfg.enabled and tcfg.spans.enabled) or \
            self.wall_clock_breakdown()
        self.tracer = Tracer(enabled=spans_on,
                             capacity=tcfg.spans.capacity,
                             synced=tcfg.spans.synced,
                             sync_registry=self.compile_registry,
                             name="engine")
        # while the tracer is on, each compile publishes its op map
        self.compile_registry.tracer = self.tracer
        self.metrics = MetricsRegistry("engine")
        self._mem_interval_s = float(tcfg.metrics.memory_interval_s)
        self._mem_cache = (0.0, 0, 0)  # (refreshed_at, rss, hbm)
        path = tcfg.metrics.path if (tcfg.enabled and tcfg.metrics.enabled) \
            else None
        self.metrics_sampler = MetricsSampler(
            self.metrics, path, rank=self.global_rank,
            interval_steps=tcfg.metrics.interval_steps)
        if self.metrics_sampler.enabled:
            self.metrics_sampler.attach_source(self._metrics_source)
            self.metrics_sampler.start()
        # online MFU: analytic FLOPs/token from the model family when it
        # advertises one, peak from config override or the device table
        self._flops_per_token = None
        cfg = self.module.meta.get("config")
        if "flops_per_token" in self.module.meta:
            self._flops_per_token = float(self.module.meta["flops_per_token"])
        elif cfg is not None and hasattr(cfg, "d_model"):
            try:
                from ..models import gpt as _gpt
                self._flops_per_token = float(_gpt.flops_per_token(cfg))
            except Exception:  # non-GPT configs: MFU reports 0
                self._flops_per_token = None
        if tcfg.metrics.peak_tflops is not None:
            self._peak_flops = float(tcfg.metrics.peak_tflops) * 1e12
        else:
            dev = jax.devices()[0]
            self._peak_flops = peak_flops_per_chip(
                getattr(dev, "device_kind", ""))
        self._step_t_last: Optional[float] = None
        self._tokens_since_sample = 0
        self._steps_since_sample = 0
        self._wall_since_sample = 0.0
        self._breakdown_base: Dict[str, Any] = {}

    def _metrics_source(self) -> Dict[str, Any]:
        """Engine-owned gauges pulled at every sample: memory census +
        compile-discipline counters.  The census (live-buffer walk + RSS
        read) dwarfs the rest of a sample, so it refreshes at most once
        per ``metrics.memory_interval_s`` and rides cached in between."""
        t_mem, rss, hbm = self._mem_cache
        now = time.monotonic()
        if t_mem == 0.0 or now - t_mem >= self._mem_interval_s:
            rss, hbm = host_rss_bytes(), live_buffer_bytes()
            self._mem_cache = (now, rss, hbm)
        return {
            MetricName.STEPS: self.global_steps,
            MetricName.SKIPPED_STEPS: self.skipped_steps,
            MetricName.HOST_RSS_BYTES: rss,
            MetricName.HBM_LIVE_BYTES: hbm,
            MetricName.COMPILES: sum(self.compile_registry.counts().values()),
            MetricName.HOST_SYNCS: self.compile_registry.total_host_syncs(),
        }

    def _count_batch_tokens(self, batch, n_micro: int = 1) -> None:
        """Accumulate trained tokens for the throughput gauges (GPT-style
        batches: rows × (seq − 1) next-token targets; non-token batches
        count rows)."""
        if not self.metrics_sampler.enabled:
            return
        toks = batch.get("tokens") if isinstance(batch, dict) else None
        shape = np.shape(toks) if toks is not None else None
        if shape and len(shape) >= 2:
            self._tokens_since_sample += int(np.prod(shape[:-1])) \
                * max(1, shape[-1] - 1)
        elif shape:
            self._tokens_since_sample += int(shape[0])

    def _note_step_telemetry(self) -> None:
        """Boundary-step bookkeeping: step-time histogram + (at the sample
        cadence) tokens/s, online MFU, memory, compile counters streamed
        to metrics.jsonl; wall_clock_breakdown log lines from the span
        aggregates."""
        now = time.monotonic()
        if self._step_t_last is not None:
            dt = now - self._step_t_last
            self._wall_since_sample += dt
            self._steps_since_sample += 1
            if self.metrics_sampler.enabled:
                self.metrics.histogram(MetricName.STEP_TIME_S).observe(dt)
        self._step_t_last = now
        if self.metrics_sampler.enabled and \
                self.metrics_sampler.should_sample(self.global_steps):
            if self._wall_since_sample > 0:
                tok_s = self._tokens_since_sample / self._wall_since_sample
                self.metrics.gauge(MetricName.TOKENS_PER_S).set(tok_s)
                if self._flops_per_token:
                    m = analytic_mfu(tok_s, self._flops_per_token,
                                     self._peak_flops,
                                     n_chips=self.world_size)
                    self.metrics.gauge(MetricName.MFU).set(m["mfu"])
                    self.metrics.gauge(MetricName.TFLOPS).set(m["tflops"])
            self.metrics_sampler.sample(step=self.global_steps)
            self._tokens_since_sample = 0
            self._steps_since_sample = 0
            self._wall_since_sample = 0.0
        if self.wall_clock_breakdown() and \
                self.global_steps % self.steps_per_print() == 0:
            self._log_breakdown()

    def _log_breakdown(self) -> None:
        """The old timer-log line, fed from span aggregates: mean ms per
        span name since the previous breakdown line."""
        agg = self.tracer.aggregates()
        parts = []
        for name, cur in agg.items():
            base = self._breakdown_base.get(name, {"count": 0,
                                                   "total_s": 0.0})
            dc = cur["count"] - base["count"]
            if dc <= 0:
                continue
            dt_ms = (cur["total_s"] - base["total_s"]) * 1e3 / dc
            parts.append(f"{name}: {dt_ms:.2f}")
        self._breakdown_base = agg
        if parts:
            log_dist("time (ms) | " + " | ".join(parts), ranks=[0])

    # ------------------------------------------------------------------ setup
    def _configure_sharding(self) -> None:
        axes = self.module.logical_axes
        shapes = self.module.param_shapes()
        if axes is None:
            # no annotations: everything replicated at base level
            base = jax.tree_util.tree_map(lambda _: P(), shapes)
        else:
            if self.module.partition_rules is not None:
                rules = self.module.partition_rules
            else:
                rules = FSDP_RULES if self._config.zero_optimization_stage >= 3 else TP_RULES
            base = tree_specs(axes, rules)
            base = validate_specs(shapes, base, self.mesh)
        self.zero_partitioner = ZeroPartitioner(
            self._config.zero_config, self.mesh_manager, base, shapes)
        self.shardings = self.zero_partitioner.plan()
        self._param_shapes = shapes

    def _configure_optimizer(self, client_optimizer, model_parameters) -> None:
        from .fp16 import onebit  # noqa: F401 — registers 1-bit optimizers
        if client_optimizer is not None:
            self.optimizer = client_optimizer
            self.client_optimizer = client_optimizer
        else:
            name = self._config.optimizer_name or "adam"
            params = dict(self._config.optimizer_params or {})
            betas = params.pop("betas", None)
            if betas is not None:
                params["betas"] = tuple(betas)
            cls = get_optimizer_class(name)
            self.optimizer = cls(**params)
            self.client_optimizer = None
        self.basic_optimizer = self.optimizer

    def _configure_lr_scheduler(self, client_scheduler) -> None:
        if client_scheduler is not None:
            self._lr_scheduler = client_scheduler
        elif self._config.scheduler_name is not None:
            cls = get_lr_schedule_class(self._config.scheduler_name)
            self._lr_scheduler = cls(self.optimizer, **(self._config.scheduler_params or {}))
        else:
            self._lr_scheduler = None

    def _init_state(self, rng: Optional[jax.Array]) -> None:
        """Materialize params/master/opt-state/grad-acc directly sharded.

        Init happens *inside* jit with output shardings set, so a 13B model
        never materializes unsharded anywhere — this is the zero.Init
        capability (partition at construction, partition_parameters.py:537)
        without monkey-patching.
        """
        rng = rng if rng is not None else jax.random.PRNGKey(0)
        sh = self.shardings
        mixed = self.compute_dtype != jnp.float32
        stage = self._config.zero_optimization_stage
        self._separate_master = mixed or stage >= 1

        if self._offload_device is not None:
            self._init_state_offload(rng)
            return

        separate = self._separate_master

        def init_all(rng):
            if self.module.params is not None:
                master = self.module.params
            else:
                master = self.module.init_fn(rng)
            master = jax.tree_util.tree_map(lambda p: p.astype(jnp.float32), master)
            opt_state = self.optimizer.init(master)
            if self._collapse_axis is not None:
                # per-worker partial sums: leading [n] dim over the
                # collapse axis, collapsed only at the boundary step
                grad_acc = jax.tree_util.tree_map(
                    lambda p: jnp.zeros((self._collapse_n,) + p.shape,
                                        self.grad_accum_dtype), master)
            else:
                grad_acc = jax.tree_util.tree_map(
                    lambda p: jnp.zeros(p.shape, self.grad_accum_dtype), master)
            if separate:
                params = jax.tree_util.tree_map(
                    lambda p: p.astype(self.compute_dtype), master)
                return params, master, opt_state, grad_acc
            return master, opt_state, grad_acc

        grads_sh = sh.grads
        if self._collapse_axis is not None:
            grads_sh = jax.tree_util.tree_map(
                lambda ns: NamedSharding(
                    self.mesh, self._stacked_spec(ns.spec)), sh.grads)
        shapes = jax.eval_shape(init_all, rng)
        if separate:
            opt_sh = sh.opt_state_fn(shapes[2])
            out_sh = (sh.params, sh.master, opt_sh, grads_sh)
            params, master, opt_state, grad_acc = jax.jit(
                init_all, out_shardings=out_sh)(rng)
        else:
            opt_sh = sh.opt_state_fn(shapes[1])
            out_sh = (sh.params, opt_sh, grads_sh)
            params, opt_state, grad_acc = jax.jit(
                init_all, out_shardings=out_sh)(rng)
            master = params  # same tree; no duplicate memory
        scale_state = jax.device_put(
            ls.init_state(self.scaler_config), NamedSharding(self.mesh, P()))
        self.state: Dict[str, Any] = {
            "params": params,
            "master": master,
            "opt_state": opt_state,
            "grad_acc": grad_acc,
            "scale": scale_state,
        }
        self._out_shardings = {
            "params": sh.params, "master": sh.master, "opt_state": opt_sh,
            "grads": grads_sh,
            "scale": jax.tree_util.tree_map(
                lambda _: NamedSharding(self.mesh, P()), self.state["scale"]),
        }
        self._last_global_norm: Optional[float] = None
        if self._collapse_axis is not None:
            self._init_grad_collapse(grad_acc, grads_sh)

    def _stacked_spec(self, spec) -> P:
        """Spec for a stacked-partials leaf: leading dim over the
        collapse axis, inner dims keeping their spec minus that axis (a
        partial is full-size per worker, so the collapse axis cannot also
        shard the leaf body — relevant when ZeRO's grad specs claim the
        'data' axis the zero-q collapse stacks over)."""
        ax = self._collapse_axis

        def strip(entry):
            if entry is None:
                return None
            if isinstance(entry, (tuple, list)):
                kept = tuple(a for a in entry if a != ax)
                return kept if len(kept) > 1 else (
                    kept[0] if kept else None)
            return None if entry == ax else entry

        return P(ax, *(strip(e) for e in tuple(spec)))

    def _init_grad_collapse(self, grad_acc, grads_sh) -> None:
        """Boundary-step collapse of the per-worker gradient partials
        across the collapse axis (DCN, or 'data' under
        zero_optimization.quantized_collectives): full-precision mean,
        the error-feedback 1-bit collective (reference
        NcclBackend.compressed_allreduce, runtime/comm/nccl.py:51), or
        the blockwise-quantized int8/int4 collectives
        (runtime/comm/quantized.py) — worker error and worker-owned
        server-chunk error both device-resident, threaded functionally.

        Each collapse jit donates the stacked accumulator and returns its
        zeroed alias next to the collapsed grads, so the boundary never
        holds two stacked trees (the implicit path gets the same property
        from apply_core's zero_acc aliasing)."""
        mesh = self.mesh
        axis = self._collapse_axis
        mode = self._collapse_mode
        prefix = "dcn" if axis == DCN_AXIS else "zero"
        grad_specs = self.zero_partitioner.grad_specs()

        def constrain_grads(tree):
            return jax.tree_util.tree_map(
                lambda x, sp: lax.with_sharding_constraint(
                    x, NamedSharding(mesh, sp)), tree, grad_specs)

        def mean_of(stacked):
            return constrain_grads(jax.tree_util.tree_map(
                lambda a: jnp.mean(a.astype(jnp.float32), axis=0)
                .astype(a.dtype), stacked))

        def zeroed(stacked):
            return jax.tree_util.tree_map(jnp.zeros_like, stacked)

        # the fp32 mean is always built: it is the primary program in
        # "mean" mode and the overflow fallback for every compressed mode
        self._dcn_mean_jit = self.compile_registry.register(
            f"{prefix}.mean", jax.jit(
                lambda acc: (mean_of(acc), zeroed(acc)),
                donate_argnums=(0,), out_shardings=(None, grads_sh)))
        # wire accounting (telemetry): logical = fp32 payload both
        # directions; wire = what the configured mode actually moves
        from .comm.quantized import logical_bytes, wire_bytes
        total = sum(int(np.prod(l.shape[1:]))
                    for l in jax.tree_util.tree_leaves(grad_acc))
        self._collapse_logical_bytes = logical_bytes(total)
        if mode == "mean":
            self._collapse_wire_bytes = self._collapse_logical_bytes
            return
        if mode == "onebit":
            from .comm.compressed import compressed_grad_reduce_tree
            self._dcn_reduce = compressed_grad_reduce_tree(
                mesh, axis, block=self._collapse_block)
        else:
            from .comm.quantized import quantized_grad_reduce_tree
            self._dcn_reduce = quantized_grad_reduce_tree(
                mesh, axis, wire=mode, block=self._collapse_block)
        self._collapse_wire_bytes = wire_bytes(
            self._dcn_reduce.flat_size(grad_acc), self._collapse_block,
            mode)
        we_shape, se_shape = self._dcn_reduce.ef_shapes(grad_acc)
        ef_sh = NamedSharding(mesh, P(axis))
        self._dcn_we = jax.device_put(
            jnp.zeros(we_shape, jnp.float32), ef_sh)
        self._dcn_se = jax.device_put(
            jnp.zeros(se_shape, jnp.float32), ef_sh)
        #: loss scale the EF residual is denominated in (the acc is
        #: loss-scaled; a scale change rescales the residual exactly)
        self._dcn_ef_scale = float(jax.device_get(
            self.state["scale"]["loss_scale"])) \
            if "scale" in getattr(self, "state", {}) else 1.0
        reduce = self._dcn_reduce

        def compressed_collapse(acc, we, se):
            collapsed, we2, se2 = reduce(acc, we, se)
            return constrain_grads(collapsed), zeroed(acc), we2, se2

        self._dcn_compress_jit = self.compile_registry.register(
            f"{prefix}.{mode}", jax.jit(
                compressed_collapse, donate_argnums=(0, 1, 2),
                out_shardings=(None, grads_sh, ef_sh, ef_sh)))
        self._dcn_rescale_ef_jit = self.compile_registry.register(
            f"{prefix}.rescale_ef", jax.jit(
                lambda we, se, r: (we * r, se * r),
                donate_argnums=(0, 1)))

        def finite_probe(acc):
            # one flattened reduction: abs-sums are non-negative, so the
            # scalar total is finite iff every leaf is (inf and NaN both
            # propagate through the sum) — O(1) outputs and no per-leaf
            # stacked vector regardless of tree size
            total = jax.tree_util.tree_reduce(
                jnp.add, jax.tree_util.tree_map(
                    lambda l: jnp.sum(jnp.abs(l.astype(jnp.float32))),
                    acc))
            return jnp.isfinite(total)

        self._dcn_finite_jit = self.compile_registry.register(
            # the finiteness probe only READS the accumulator; the
            # dslint: disable=missing-donation — collapse owns donation
            f"{prefix}.finite", jax.jit(finite_probe))

    def _init_param_spill(self) -> None:
        """ZeRO-Infinity parameter NVMe spill: with
        ``offload_param.device="nvme"`` (stage 3), the stored params live
        in per-leaf swap files BETWEEN optimizer steps — restored with
        async read-ahead before the gas window, re-spilled after each
        boundary step (reference AsyncPartitionedParameterSwapper,
        partitioned_param_swapper.py:35).  Host-RAM peak for the swap
        path is bounded by ``buffer_count`` block buffers
        (``max_in_cpu`` enforces the cap), so params need fit neither
        HBM-between-steps nor host RAM."""
        self._param_spill = None
        pcfg = self._config.zero_config.offload_param_config
        if pcfg.device != "nvme":
            return
        if self._config.zero_optimization_stage < 3:
            # partitioner already warned (reference config semantics)
            return
        from .swap_tensor.partitioned_param_swapper import \
            PartitionedParamSwapper
        if not pcfg.nvme_path:
            raise DeepSpeedConfigError(
                "offload_param.device='nvme' requires offload_param.nvme_path")
        self._param_spill = PartitionedParamSwapper(
            os.path.join(pcfg.nvme_path, f"param_rank{self.global_rank}"),
            aio_config=self._config.aio_config,
            buffer_count=pcfg.buffer_count,
            ram_cap_bytes=int(pcfg.max_in_cpu) if pcfg.max_in_cpu else None)
        self._spill_params()
        log_dist(
            f"[offload] params spilled to NVMe at {pcfg.nvme_path} "
            f"({self._param_spill.swapped_bytes() / 1e6:.1f} MB, "
            f"buffer_count={pcfg.buffer_count})", ranks=[0])

    def _spill_params(self) -> None:
        if self._param_spill is None or self._param_spill.spilled:
            return
        flat, self._spill_treedef = jax.tree_util.tree_flatten(
            self.state["params"])
        master_is_params = self.state["master"] is self.state["params"]
        self._param_spill.spill(flat)
        del flat
        self.state["params"] = None  # device copies dropped
        if master_is_params:
            self.state["master"] = None

    def _ensure_params_resident(self) -> None:
        """Restore spilled params before any consumer touches them."""
        if self._param_spill is None or not self._param_spill.spilled:
            return
        sh_flat = jax.tree_util.tree_leaves(self._out_shardings["params"])
        flat = self._param_spill.restore(sh_flat)
        params = jax.tree_util.tree_unflatten(self._spill_treedef, flat)
        self.state["params"] = params
        if self.state["master"] is None:
            self.state["master"] = params

    def _resolve_grad_accum_dtype(self):
        """``data_types.grad_accum_dtype`` (reference engine.py:809
        get_data_types): the dtype gradients ACCUMULATE in across
        micro-steps.  Default fp32 — unlike the reference, which defaults
        fp16 models to fp16 accumulation, we keep the conservative choice
        for every model dtype (fp32 adds are ~free on the VPU and gas>1
        accumulation is exactly where 16-bit mantissas lose gradient
        signal).  An explicit 16-bit setting halves the accumulator — the
        dominant 4-bytes/param term of the ZeRO-offload footprint — which
        is what lets the 2.7B class fit one 16 GB chip."""
        v = self._config.grad_accum_dtype
        if v is None:
            return jnp.float32
        table = {"fp32": jnp.float32, "float32": jnp.float32,
                 "float": jnp.float32,
                 "fp16": jnp.float16, "float16": jnp.float16,
                 "half": jnp.float16,
                 "bf16": jnp.bfloat16, "bfloat16": jnp.bfloat16}
        key = str(v).lower().replace("torch.", "")
        if key not in table:
            raise DeepSpeedConfigError(
                f"data_types.grad_accum_dtype={v!r} (want one of "
                f"{sorted(set(table))})")
        return table[key]

    def _init_state_offload(self, rng: jax.Array) -> None:
        """Device holds compute-dtype params + grad accumulators; fp32
        master and Adam moments live with the host offload runner.

        Initialization runs on the HOST CPU backend: the fp32 master never
        touches the device.  The previous device-side init materialized
        params + fp32 master + accumulator concurrently — 10 bytes/param
        peak with a bf16 accumulator, which OOMs the 2.7B class on a 16 GB
        chip before training even starts — and then pulled the 4 N-byte
        master over the (slow) d2h direction.  Host init costs zero d2h
        traffic, uploads only the 2 N-byte compute-dtype params, and is
        bit-identical: JAX's threefry PRNG is deterministic across
        backends.  (This is also the reference's construction order — the
        fp32 master is cloned host-side from the 16-bit weights,
        stage_1_and_2.py:98.)"""
        from .zero.offload_engine import (HostOffloadOptimizer, index_key,
                                          unique_local_blocks)
        sh = self.shardings
        self._separate_master = True
        self._master_shardings_flat = jax.tree_util.tree_leaves(sh.master)
        self._reshard_params_jit = self.compile_registry.register(
            "reshard_params", jax.jit(lambda t: t, out_shardings=sh.params))
        np_compute = np.dtype(self.compute_dtype)  # ml_dtypes handles bf16
        multihost = jax.process_count() > 1

        master_dev_flat = None  # load path only (device fp32 transient)
        if self.module.params is not None:
            # load path: the provided weights may span non-addressable
            # devices, so keep them device-side — reshard to the master
            # partition in fp32 (transient, freed once the host blocks are
            # pulled below), cast compute-dtype params from it
            master_dev = jax.jit(
                lambda t: jax.tree_util.tree_map(
                    lambda p: p.astype(jnp.float32), t),
                out_shardings=sh.master)(self.module.params)
            params = jax.jit(
                lambda t: jax.tree_util.tree_map(
                    lambda p: p.astype(self.compute_dtype), t),
                out_shardings=sh.params)(master_dev)
            master_dev_flat, self._params_treedef = \
                jax.tree_util.tree_flatten(master_dev)
            del master_dev
            master_flat = None
        else:
            # scratch path: init on the host CPU backend and upload only
            # the 2 N-byte compute-dtype params
            cpu0 = jax.local_devices(backend="cpu")[0]
            with jax.default_device(cpu0):
                host_init = jax.jit(self.module.init_fn)(
                    jax.device_put(rng, cpu0))
                master_host = jax.tree_util.tree_map(
                    lambda p: np.asarray(p, np.float32), host_init)
            del host_init
            master_flat, self._params_treedef = jax.tree_util.tree_flatten(
                master_host)
            del master_host
            param_sh_flat = jax.tree_util.tree_leaves(sh.params)
            # leaf-by-leaf upload; multi-host puts per-device blocks of the
            # master partition, then one SPMD reshard to the param sharding
            params_flat = []
            if multihost:
                for m, msh in zip(master_flat, self._master_shardings_flat):
                    blk = m.astype(np_compute)
                    arrs = [jax.device_put(np.ascontiguousarray(blk[idx]), d)
                            for d, idx in
                            msh.addressable_devices_indices_map(
                                m.shape).items()]
                    params_flat.append(
                        jax.make_array_from_single_device_arrays(
                            m.shape, msh, arrs))
                params = self._reshard_params_jit(
                    jax.tree_util.tree_unflatten(self._params_treedef,
                                                 params_flat))
            else:
                for m, psh in zip(master_flat, param_sh_flat):
                    params_flat.append(
                        jax.device_put(m.astype(np_compute), psh))
                params = jax.tree_util.tree_unflatten(self._params_treedef,
                                                      params_flat)
            del params_flat

        # per-leaf param-group assignment (torch decay/no-decay groups by
        # leaf path; reference steps each group with its own hyperparams)
        opt = self.optimizer
        groups = getattr(opt, "param_groups", None) or [{}]
        leaf_paths = [jax.tree_util.keystr(p) for p, _ in
                      jax.tree_util.tree_flatten_with_path(params)[0]]
        self._leaf_group_idx = resolve_param_groups(groups, leaf_paths)

        # Pull the fp32 master to the host BEFORE allocating the grad
        # accumulator: on the load path the device fp32 transient is
        # 4 bytes/param, and holding it across the accumulator allocation
        # gives an 8-10 bytes/param init peak — the same OOM profile the
        # host-side scratch init eliminated.  Freeing each device leaf as
        # soon as its host copy lands keeps the load-path peak at
        # params + one transient fp32 leaf.
        # Multi-host: each process keeps only its unique addressable master
        # shards (the reference's per-rank cpu_offload, stage_1_and_2.py:98)
        # and steps them locally; params are rebuilt from the shards + one
        # SPMD reshard (all-gather on device).  Scratch init: every process
        # computes the identical full init (threefry is deterministic),
        # then slices its own blocks — host-RAM only, no cross-host traffic.
        self._offload_multihost = multihost
        if self._offload_multihost:
            # per leaf: [(global index, normalized key, block shape)] for
            # the process's unique shards, and the static device->key put
            # map for rebuilding the master-sharded global array each step
            self._offload_layout = []
            self._offload_putmap = []
            master_leaves, group_of = [], []
            src_flat = master_dev_flat if master_dev_flat is not None \
                else master_flat
            for li in range(len(src_flat)):
                leaf = src_flat[li]
                msh = self._master_shardings_flat[li]
                dev_map = msh.addressable_devices_indices_map(leaf.shape)
                self._offload_putmap.append(
                    [(d, index_key(i, leaf.shape))
                     for d, i in dev_map.items()])
                if master_dev_flat is not None:
                    # load path: pull only this process's addressable
                    # shards of the device master (already msh-sharded)
                    blocks = unique_local_blocks(leaf)
                    self._offload_layout.append(
                        [(idx, index_key(idx, leaf.shape), b.shape)
                         for idx, b in blocks])
                    for _, b in blocks:
                        master_leaves.append(np.asarray(b, np.float32))
                        group_of.append(self._leaf_group_idx[li])
                    src_flat[li] = None  # free the device fp32 leaf now
                else:
                    # scratch path: slice the host init (host-RAM only)
                    blocks = {}
                    for idx in dev_map.values():
                        blocks.setdefault(index_key(idx, leaf.shape), idx)
                    self._offload_layout.append(
                        [(blocks[k], k, leaf[blocks[k]].shape)
                         for k in sorted(blocks)])
                    for k in sorted(blocks):
                        master_leaves.append(
                            np.ascontiguousarray(leaf[blocks[k]]))
                        group_of.append(self._leaf_group_idx[li])
                del leaf
        elif master_dev_flat is not None:
            master_leaves = []
            for li in range(len(master_dev_flat)):
                master_leaves.append(np.asarray(
                    jax.device_get(master_dev_flat[li]), np.float32))
                master_dev_flat[li] = None  # free the device fp32 leaf now
            group_of = list(self._leaf_group_idx)
        else:
            master_leaves = master_flat
            group_of = list(self._leaf_group_idx)
        del master_flat, master_dev_flat

        leaf_shapes = [l.shape for l in jax.tree_util.tree_leaves(params)]
        grad_acc = jax.jit(
            lambda: jax.tree_util.tree_unflatten(
                self._params_treedef,
                [jnp.zeros(s, self.grad_accum_dtype) for s in leaf_shapes]),
            out_shardings=sh.grads)()

        # error-feedback residual for compressed grad streaming (device-
        # resident, sharded like the accumulators)
        comp = getattr(self._offload_cfg, "grad_compression", "none")
        if comp not in ("none", "onebit", "int8"):
            raise DeepSpeedConfigError(
                f"offload_optimizer.grad_compression={comp!r} "
                "(want 'none', 'onebit' or 'int8')")
        if comp != "none":
            if multihost:
                raise DeepSpeedConfigError(
                    "offload_optimizer.grad_compression is single-process "
                    "only (packed bit streams don't slice across hosts)")
            cblk = int(self._offload_cfg.compression_block)
            if cblk <= 0 or cblk % 8 != 0:
                raise DeepSpeedConfigError(
                    f"offload_optimizer.compression_block={cblk} must be a "
                    "positive multiple of 8 (elements are bit-packed)")
            rds = str(self._offload_cfg.compression_residual_dtype).lower()
            if rds in ("bf16", "bfloat16"):
                rdt = jnp.bfloat16
            elif rds in ("fp32", "float32", "float"):
                rdt = jnp.float32
            else:
                raise DeepSpeedConfigError(
                    "offload_optimizer.compression_residual_dtype="
                    f"{self._offload_cfg.compression_residual_dtype!r} "
                    "(want 'fp32' or 'bf16')")
            grads_sh_flat = jax.tree_util.tree_leaves(sh.grads)
            self._offload_resid_leaves = list(jax.jit(
                lambda: tuple(jnp.zeros(s, rdt) for s in leaf_shapes),
                out_shardings=tuple(grads_sh_flat))())
        self._offload_compress = comp

        # auto-disable transfer pipelining when the second in-flight leaf
        # doesn't fit the analytic HBM budget — users shouldn't need to
        # know the knob to train the biggest model that fits
        self._offload_pipeline = bool(getattr(
            self._offload_cfg, "pipeline_transfers", True))
        if self._offload_pipeline and not multihost:
            from .memory_model import device_budget, offload_peak_bytes
            sizes = [int(np.prod(shp)) for shp in leaf_shapes]
            accum_b = jnp.dtype(self.grad_accum_dtype).itemsize
            resid_b = 0 if comp == "none" else jnp.dtype(rdt).itemsize
            budget = device_budget()
            if budget is not None and offload_peak_bytes(
                    sum(sizes), max(sizes),
                    mixed_precision=self.compute_dtype != jnp.float32,
                    grad_accum_bytes=accum_b, pipeline_transfers=True,
                    compression_residual_bytes=resid_b) > budget:
                log_dist("[offload] pipeline_transfers auto-disabled: the "
                         "second in-flight leaf exceeds the HBM budget",
                         ranks=[0])
                self._offload_pipeline = False

        self._offload_opt = HostOffloadOptimizer(
            master_leaves,
            device=self._offload_device,
            nvme_path=self._offload_cfg.nvme_path,
            aio_config=self._config.aio_config,
            pipeline_read=self._offload_cfg.pipeline_read,
            pipeline_write=self._offload_cfg.pipeline_write,
            betas=getattr(opt, "betas", (0.9, 0.999)),
            eps=getattr(opt, "eps", 1e-8),
            weight_decay=float(opt.param_groups[0].get("weight_decay", 0.0))
            if getattr(opt, "param_groups", None) else 0.0,
            adamw_mode=getattr(opt, "adam_w_mode", True),
            bias_correction=getattr(opt, "bias_correction", True),
            group_of=group_of)

        scale_state = jax.device_put(
            ls.init_state(self.scaler_config), NamedSharding(self.mesh, P()))
        self.state: Dict[str, Any] = {
            "params": params,
            "master": params,      # host runner owns the real fp32 master
            "opt_state": {},
            "grad_acc": grad_acc,
            "scale": scale_state,
        }
        self._out_shardings = {
            "params": sh.params, "master": sh.params, "opt_state": {},
            "grads": sh.grads,
            "scale": jax.tree_util.tree_map(
                lambda _: NamedSharding(self.mesh, P()), self.state["scale"]),
        }
        self._last_global_norm: Optional[float] = None
        log_dist(f"[offload] optimizer states on {self._offload_device} "
                 f"({len(master_leaves)} groups)", ranks=[0])

    # ------------------------------------------------------------------ jitted programs
    def _build_steps(self) -> None:
        from ..models.gpt import tally_head
        from ..ops.pallas.flash_attention import tally_causal_tiles

        def loss_fn(p, b):
            # runs when a step program is traced, not when it runs: how far
            # the flash kernels' causal strips engage in this model's step,
            # how many of their calls read the qkv product in place, and how
            # its loss head is laid out
            with tally_causal_tiles() as tiles, tally_head() as head:
                loss = self.module.loss_fn(p, b)
            for name, n in zip((MetricName.FLASH_CAUSAL_TILES_VISITED,
                                MetricName.FLASH_CAUSAL_TILES_SQUARE,
                                MetricName.FLASH_CALLS,
                                MetricName.FLASH_CALLS_TOKEN_MAJOR_PACKED,
                                MetricName.HEAD_LOGIT_PRODUCTS,
                                MetricName.HEAD_ROW_CHUNKS), tiles + head):
                self.metrics.counter(name).inc(n)
            return loss

        model_grad_fn = self.module.grad_fn
        gas = self.gradient_accumulation_steps()
        grad_div = 1 if self.module.meta.get("pipeline") else gas
        clip = self.gradient_clipping()
        scaler_config = self.scaler_config
        optimizer = self.optimizer
        grad_specs = self.zero_partitioner.grad_specs()
        master_specs = self.zero_partitioner.master_specs()
        param_specs = self.zero_partitioner.param_specs()
        mesh = self.mesh
        separate_master = self._separate_master
        compute_dtype = self.compute_dtype
        accum_dtype = self.grad_accum_dtype

        def constrain(tree, specs):
            return jax.tree_util.tree_map(
                lambda x, s: lax.with_sharding_constraint(x, NamedSharding(mesh, s)),
                tree, specs, is_leaf=lambda x: isinstance(x, P) and False)

        def micro(params, grad_acc, scale_state, batch):
            """One micro-batch: fused forward+backward+accumulate."""
            scale = scale_state["loss_scale"]

            if model_grad_fn is not None:
                # custom in-graph schedule (1F1B pipeline): the loss scale
                # seeds the backward (fp16 underflow protection happens
                # inside the half-precision VJPs).  A pipelined model's
                # grad_fn consumes ALL microbatches of the global batch in
                # one call (gas lives inside the schedule), so no 1/gas;
                # other grad_fn models accumulate per-micro like jax.grad.
                loss, grads = model_grad_fn(params, batch, loss_scale=scale)
                if grad_div != 1:
                    grads = jax.tree_util.tree_map(
                        lambda g: g / grad_div, grads)
            else:
                def scaled_loss(p):
                    loss = loss_fn(p, batch)
                    return loss * scale / gas, loss

                grads, loss = jax.grad(scaled_loss, has_aux=True)(params)
            grads = jax.tree_util.tree_map(
                lambda g: g.astype(accum_dtype), grads)
            new_acc = jax.tree_util.tree_map(jnp.add, grad_acc, grads)
            new_acc = constrain(new_acc, grad_specs)
            return new_acc, loss

        if self._offload_device is not None:
            # Device side of the offloaded step, STREAMED per leaf (the
            # reference's fixed-size IPG-bucket discipline,
            # stage_1_and_2.py:868 reduce_independent_p_g_buckets_...: a
            # full extra gradient-sized tree never exists on device).
            #
            #   1. grad_stats: scalar-only pass over the accumulator
            #      (fp32 by default, 16-bit under data_types.
            #      grad_accum_dtype; reductions upcast to fp32 inside) —
            #      global norm, clip coefficient, overflow flag, next loss
            #      scale.  No big outputs, nothing donated.
            #   2. prep_leaf (per leaf, accumulator leaf donated): clip ×
            #      cast to the 16-bit compute dtype in one fused kernel;
            #      the zeroed accumulator aliases the donated buffer.  The
            #      caller host-pulls the 16-bit leaf and frees it before
            #      touching the next, so the transient is ONE leaf, not the
            #      2 bytes/param whole-tree copy that kept 1.3B off a 16 GB
            #      chip (docs/performance.md round-3 finding).
            #
            # Grads cross the PCIe still LOSS-SCALED (the scale keeps small
            # components inside fp16's dynamic range — the reference's
            # cpu_offload moves scaled fp16 partitions the same way); the
            # host unscales in fp32 before Adam.
            finfo_max = float(jnp.finfo(compute_dtype).max)

            def grad_stats(grad_acc, scale_state):
                scale = scale_state["loss_scale"]
                # norm of the UNSCALED grads without materializing an
                # unscaled tree: ||g/scale|| = ||g|| / scale; clipping is a
                # scalar coefficient so it folds into one multiply
                norm = global_grad_norm(grad_acc) / scale
                if clip > 0:
                    coef = jnp.minimum(1.0, clip / (norm + 1e-6))
                else:
                    coef = jnp.ones((), jnp.float32)
                if scaler_config.enabled:
                    # what has_overflow(transfer) used to see on the cast
                    # tree, computed from scalars: a non-finite norm means
                    # inf/nan grads; a finite max beyond the compute
                    # dtype's range would inf on the cast.  (An inf norm
                    # with finite leaves also lands here — the old path
                    # silently stepped with zeroed grads; skipping is the
                    # reference's CheckOverflow semantics.)
                    absmax = global_grad_norm(grad_acc, float("inf"))
                    overflow = jnp.logical_or(
                        jnp.logical_not(jnp.isfinite(norm)),
                        absmax * coef > finfo_max)
                else:
                    overflow = jnp.zeros((), bool)
                new_scale = ls.update_state(scale_state, overflow, scaler_config)
                return coef, new_scale, norm, overflow

            def prep_leaf(g, coef):
                return (g * coef).astype(compute_dtype), jnp.zeros_like(g)

            # error-feedback compressed prep: unscale+clip in fp32, add
            # the carried residual, quantize per block, keep the new
            # quantization error on device.  The transfer is the packed
            # payload + per-block scales instead of a 16-bit tree.
            blk = int(getattr(self._offload_cfg, "compression_block", 2048))

            def _blocked(g, resid, coef, inv_scale):
                c = (g.astype(jnp.float32) * (coef * inv_scale)
                     + resid.astype(jnp.float32))
                flat = c.reshape(-1)
                nb = -(-flat.shape[0] // blk)
                fp = jnp.pad(flat, (0, nb * blk - flat.shape[0]))
                return c, flat, fp.reshape(nb, blk)

            def prep_onebit(g, resid, coef, inv_scale):
                c, flat, cb = _blocked(g, resid, coef, inv_scale)
                s = jnp.mean(jnp.abs(cb), axis=1)  # L1 scale (1-bit Adam)
                deq = jnp.where(cb >= 0, 1.0, -1.0) * s[:, None]
                resid_new = (cb - deq).reshape(-1)[:flat.shape[0]] \
                    .reshape(c.shape).astype(resid.dtype)
                bits = (cb >= 0).reshape(-1, 8).astype(jnp.int32)
                w = (1 << jnp.arange(8, dtype=jnp.int32))  # little-endian
                packed = jnp.sum(bits * w, axis=1).astype(jnp.uint8)
                return packed, s, resid_new, jnp.zeros_like(g)

            def prep_int8(g, resid, coef, inv_scale):
                c, flat, cb = _blocked(g, resid, coef, inv_scale)
                s = jnp.max(jnp.abs(cb), axis=1) / 127.0
                safe = jnp.where(s > 0, s, 1.0)
                q = jnp.clip(jnp.round(cb / safe[:, None]), -127, 127)
                deq = q * s[:, None]
                resid_new = (cb - deq).reshape(-1)[:flat.shape[0]] \
                    .reshape(c.shape).astype(resid.dtype)
                return (q.astype(jnp.int8).reshape(-1), s, resid_new,
                        jnp.zeros_like(g))

            reg = self.compile_registry
            self._micro_jit = reg.register(
                "micro", jax.jit(micro, donate_argnums=(1,)))
            self._grad_stats_jit = reg.register(
                # the scalar-only stats pass READS the accumulator; the
                # dslint: disable=missing-donation — preps own donation
                "grad_stats", jax.jit(grad_stats))
            self._prep_leaf_jit = reg.register(
                "prep_leaf", jax.jit(prep_leaf, donate_argnums=(0,)))
            self._prep_onebit_jit = reg.register(
                "prep_onebit", jax.jit(prep_onebit, donate_argnums=(0, 1)))
            self._prep_int8_jit = reg.register(
                "prep_int8", jax.jit(prep_int8, donate_argnums=(0, 1)))
            self._zero_leaf_jit = reg.register(
                "zero_leaf", jax.jit(
                    lambda g: jnp.zeros_like(g), donate_argnums=(0,)))
            return

        def apply_core(params, master, opt_state, grad_acc, scale_state, hyper):
            """Gas-boundary update: unscale, overflow check, clip, step, scale.

            ``master`` may be the same tree object as ``params`` (fp32,
            stage 0); callers handle donation accordingly.
            """
            scale = scale_state["loss_scale"]
            # unscale/clip/step in fp32 regardless of the accumulation
            # dtype (a 16-bit accumulator still gets fp32 update math)
            grads = jax.tree_util.tree_map(
                lambda g: g.astype(jnp.float32) / scale, grad_acc)
            overflow = has_overflow(grads) if scaler_config.enabled else jnp.zeros((), bool)
            if clip > 0:
                grads, norm = clip_grads_by_global_norm(grads, clip)
            else:
                norm = global_grad_norm(grads)
            # compute the update on master shards (ZeRO weight-update sharding)
            grads = constrain(grads, master_specs)
            with jax.named_scope("optimizer"):
                new_master, new_opt = optimizer.update(
                    grads, opt_state, master, hyper)
            new_master = constrain(new_master, master_specs)
            # overflow → keep previous state (the reference's skipped step)
            keep = lambda new, old: jax.tree_util.tree_map(
                lambda n, o: jnp.where(overflow, o, n), new, old)
            new_master = keep(new_master, master)
            new_opt = keep(new_opt, opt_state)
            if separate_master:
                new_params = jax.tree_util.tree_map(
                    lambda m: m.astype(compute_dtype), new_master)
                new_params = constrain(new_params, param_specs)
            else:
                new_params = new_master
            zero_acc = jax.tree_util.tree_map(jnp.zeros_like, grad_acc)
            new_scale = ls.update_state(scale_state, overflow, scaler_config)
            return new_params, new_master, new_opt, zero_acc, new_scale, norm, overflow

        if self._collapse_axis is not None:
            # per-worker gradient accumulation: the micro step runs
            # manual over the collapse axis (the slow 'dcn' axis, or
            # 'data' under zero_optimization.quantized_collectives —
            # every other mesh axis stays compiler-managed), so the
            # backward's gradient psum covers only the remaining auto
            # axes — nothing crosses the collapse axis until the boundary
            # collapse in _take_model_step
            collapse_axis = self._collapse_axis
            collapse_n = self._collapse_n

            def strip(sp):
                # the inner constraint runs inside shard_map, where the
                # manual collapse axis must not appear in auto specs
                return P(None, *(tuple(self._stacked_spec(sp))[1:]))

            shifted_grad_specs = jax.tree_util.tree_map(
                strip, grad_specs, is_leaf=lambda x: isinstance(x, P))

            def micro_slice(params, acc, scale_state, b):
                scale = scale_state["loss_scale"]
                if isinstance(b, dict) and "_train_rng" in b:
                    # distinct dropout masks per worker: n=1 draws one
                    # mask over the full batch, so replicating the key
                    # across workers would correlate the gradient noise
                    b = {**b, "_train_rng": jax.random.fold_in(
                        b["_train_rng"], lax.axis_index(collapse_axis))}

                def scaled_loss(p):
                    loss = loss_fn(p, b)
                    return loss * scale / gas, loss

                grads, loss = jax.grad(scaled_loss, has_aux=True)(params)
                grads = jax.tree_util.tree_map(
                    lambda g: g.astype(accum_dtype), grads)
                new_acc = jax.tree_util.tree_map(
                    lambda a, g: a + g[None], acc, grads)
                new_acc = constrain(new_acc, shifted_grad_specs)
                return new_acc, lax.pmean(loss, collapse_axis)

            def micro_stacked(params, grad_acc, scale_state, batch):
                leaves = jax.tree_util.tree_leaves(batch)
                rows = max((x.shape[0] for x in leaves
                            if getattr(x, "ndim", 0) >= 1), default=0)
                pspec = jax.tree_util.tree_map(lambda _: P(), params)
                aspec = jax.tree_util.tree_map(lambda _: P(collapse_axis),
                                               grad_acc)
                sspec = jax.tree_util.tree_map(lambda _: P(), scale_state)
                bspec = jax.tree_util.tree_map(
                    lambda x: P(collapse_axis)
                    if getattr(x, "ndim", 0) >= 1 and x.shape[0] == rows
                    and rows % collapse_n == 0 else P(), batch)
                fn = shard_map(micro_slice, mesh=mesh,
                               in_specs=(pspec, aspec, sspec, bspec),
                               out_specs=(aspec, P()),
                               axis_names={collapse_axis}, check_vma=False)
                return fn(params, grad_acc, scale_state, batch)

            self._micro_jit = self.compile_registry.register(
                "micro", jax.jit(micro_stacked, donate_argnums=(1,)))
        else:
            self._micro_jit = self.compile_registry.register(
                "micro", jax.jit(micro, donate_argnums=(1,)))

        # Every piece of state leaves the step with the sharding it was
        # created with (norm and overflow are inferred).  Left to the
        # compiler, the outputs come back under other, if equivalent,
        # specs; the next call then misses the jit cache and the whole
        # step compiles a second time.  It is also what returns
        # offload_param's host-resident params (ZeRO-3 parameter offload)
        # to host memory after the update.
        so = self._out_shardings
        master_sh = so["master"] if separate_master else so["params"]
        out_sh = (so["params"], master_sh, so["opt_state"],
                  self.shardings.grads, so["scale"], None, None)

        if separate_master:
            self._apply_jit = self.compile_registry.register(
                "apply", jax.jit(apply_core, donate_argnums=(0, 1, 2, 3, 4),
                                 out_shardings=out_sh))

            def fused(params, master, opt_state, grad_acc, scale_state, batches, hyper):
                def body(acc, batch):
                    acc, loss = micro(params, acc, scale_state, batch)
                    return acc, loss
                grad_acc, losses = lax.scan(body, grad_acc, batches)
                out = apply_core(params, master, opt_state, grad_acc, scale_state, hyper)
                return out + (jnp.mean(losses),)

            self._fused_jit = self.compile_registry.register(
                "fused", jax.jit(fused, donate_argnums=(0, 1, 2, 3, 4),
                                 out_shardings=out_sh + (None,)))
        else:
            def apply_single(params, opt_state, grad_acc, scale_state, hyper):
                return apply_core(params, params, opt_state, grad_acc, scale_state, hyper)

            self._apply_jit_single = self.compile_registry.register(
                "apply", jax.jit(apply_single, donate_argnums=(0, 1, 2, 3),
                                 out_shardings=out_sh))

            def fused_single(params, opt_state, grad_acc, scale_state, batches, hyper):
                def body(acc, batch):
                    acc, loss = micro(params, acc, scale_state, batch)
                    return acc, loss
                grad_acc, losses = lax.scan(body, grad_acc, batches)
                out = apply_core(params, params, opt_state, grad_acc, scale_state, hyper)
                return out + (jnp.mean(losses),)

            self._fused_jit_single = self.compile_registry.register(
                "fused", jax.jit(fused_single, donate_argnums=(0, 1, 2, 3),
                                 out_shardings=out_sh + (None,)))

    # ------------------------------------------------------------------ data
    def deepspeed_io(self, dataset, batch_size=None, route=None, pin_memory=False,
                     data_sampler=None, collate_fn=None, num_local_io_workers=None):
        bs = batch_size or \
            self.train_micro_batch_size_per_gpu() * self.dp_world_size
        cf = collate_fn or self.collate_fn
        dc = self._config.data_config
        if dc.resumable:
            from .data_pipeline.resumable import ResumableDataLoader
            loader = ResumableDataLoader(
                dataset, batch_size=bs, collate_fn=cf, shuffle=dc.shuffle,
                seed=dc.seed, drop_last=dc.drop_last,
                max_epochs=dc.max_epochs,
                max_bad_records=dc.max_bad_records,
                journal_batches=dc.journal_batches,
                mesh_manager=self.mesh_manager)
            if dc.checkpoint_iterator:
                self.set_data_iterator(loader)
            return loader
        return DeepSpeedDataLoader(dataset, batch_size=bs, collate_fn=cf,
                                   mesh_manager=self.mesh_manager)

    def set_data_iterator(self, iterator) -> None:
        """Register a stateful data iterator (``state_dict``/
        ``load_state_dict``): its position is persisted in every checkpoint
        and restored on every load, making resumes land on the exact next
        batch (reference ``set_dataloader`` keeps a loader the same way)."""
        self.data_iterator = iterator

    def _shard_batch(self, batch):
        """Place a host batch as a global array sharded over dp."""
        def put(x):
            x = jnp.asarray(x) if not isinstance(x, jax.Array) else x
            spec = P((DCN_AXIS, DATA_AXIS, EXPERT_AXIS)) if x.ndim >= 1 \
                else P()
            try:
                return jax.device_put(x, NamedSharding(self.mesh, spec))
            except ValueError:
                return jax.device_put(x, NamedSharding(self.mesh, P()))
        return jax.tree_util.tree_map(put, batch)

    # ------------------------------------------------------------------ train protocol
    def _inject_compression_step(self, batch):
        """Thread the global step into the batch so the in-graph compression
        schedule (compression/transforms.py) can gate on it."""
        if self._compression_scheduler is None or not isinstance(batch, dict):
            return batch
        from ..compression.compress import STEP_KEY
        return {**batch, STEP_KEY: jnp.asarray(self.global_steps, jnp.int32)}

    def _inject_train_rng(self, batch, n: Optional[int] = None):
        """Thread per-micro-step PRNG keys into training batches for models
        that declare ``needs_rng`` (dropout) or when PLD gates layers; eval
        never injects, so stochasticity is train-only by construction."""
        if not isinstance(batch, dict) or not (
                self.module.meta.get("needs_rng") or self._pld is not None):
            return batch
        if not getattr(self, "_training", True):
            return batch  # engine.eval(): deterministic forward
        base = jax.random.fold_in(jax.random.PRNGKey(0), self.micro_steps)
        if n is None:
            return {**batch, "_train_rng": base}
        return {**batch, "_train_rng": jax.device_put(
            jax.vmap(lambda i: jax.random.fold_in(base, i))(jnp.arange(n)),
            NamedSharding(self.mesh, P(None)))}

    @staticmethod
    def _seqlen_buckets(params) -> List[int]:
        """Fixed compile-shape buckets for curriculum seqlens.

        Every distinct truncation length is a new XLA program (SURVEY §7:
        dynamic shapes under jit), so the scheduled difficulty is rounded UP
        to a bucket — compile count stays <= n_buckets across the whole
        schedule.  An explicit ``"seqlen_buckets"`` list wins; the default
        doubles from min to max difficulty."""
        hi = int(params["max_difficulty"])
        explicit = params.get("seqlen_buckets")
        if explicit:
            buckets = sorted(int(b) for b in explicit)
            if buckets[-1] < hi:
                # a capped list would silently clamp training below the
                # scheduled max difficulty for the rest of the run
                buckets.append(hi)
            return buckets
        lo = max(1, int(params["min_difficulty"]))
        buckets, b = [], lo
        while b < hi:
            buckets.append(b)
            b *= 2
        buckets.append(hi)
        return buckets

    def _apply_curriculum(self, batch):
        """Curriculum seqlen truncation (reference engine.py:1704), bucketed
        so difficulty stepping reuses compiled programs."""
        if self._curriculum is None or not isinstance(batch, dict) \
                or "tokens" not in batch:
            return batch
        seqlen = self._curriculum.update_difficulty(self.global_steps + 1)
        for b in self._curriculum_buckets:
            if b >= seqlen:
                seqlen = b
                break
        else:
            seqlen = self._curriculum_buckets[-1]
        toks = batch["tokens"]
        if seqlen + 1 < np.shape(toks)[-1]:
            batch = {**batch, "tokens": toks[..., :seqlen + 1]}
        return batch

    def _inject_pld(self, batch, n: Optional[int] = None):
        """PLD theta injection (reference engine.py:1698); shape (n,) on the
        fused path so the gas scan unstacks one scalar per micro-step."""
        if self._pld is None or not isinstance(batch, dict):
            return batch
        self._pld.update_state(self.global_steps)
        theta = jnp.asarray(self._pld.get_theta(), jnp.float32)
        if n is not None:
            theta = jax.device_put(jnp.full((n,), theta),
                                   NamedSharding(self.mesh, P(None)))
        return {**batch, "_pld_theta": theta}

    @hot_path
    def forward(self, batch, **kwargs):
        """Compute loss (and, fused, the gradients) for one micro-batch."""
        self._ensure_params_resident()
        if not getattr(self, "_training", True):
            # engine.eval(): a validation forward must not contaminate the
            # gradient accumulator (the fused micro step would add the val
            # batch's grads to the next optimizer update)
            loss = self.eval_loss(batch)
            self._pending = loss
            return loss
        self.tput_timer.start()
        self._count_batch_tokens(batch)
        with self.tracer.span(SpanName.TRAIN_FWD):
            batch = self._apply_curriculum(batch)
            batch = self._inject_pld(batch)
            batch = self._inject_compression_step(batch)
            batch = self._inject_train_rng(batch)
            batch = self._shard_batch(batch)
            new_acc, loss = self._micro_jit(
                self.state["params"], self.state["grad_acc"],
                self.state["scale"], batch)
        self.state["grad_acc"] = new_acc
        self._pending = loss
        return loss

    __call__ = forward

    def backward(self, loss=None, allreduce_gradients: bool = True, release_loss: bool = False):
        """Accumulation bookkeeping (gradients were produced in forward)."""
        assert self._pending is not None, "backward() called before forward()"
        # gradients were produced in the fused forward; the span records
        # the (host-side) bookkeeping cost and keeps the phase visible in
        # the timeline
        with self.tracer.span(SpanName.TRAIN_BWD, fused=True):
            loss = self._pending
            self._pending = None
        if self.monitor.enabled and getattr(self, "_training", True) and \
                self.is_gradient_accumulation_boundary():
            # eval-mode losses must not land in the train-loss stream
            self.monitor.write_events([
                ("Train/Samples/train_loss", float(jax.device_get(loss)),
                 self.global_samples)])
        return loss

    def is_gradient_accumulation_boundary(self) -> bool:
        """Reference engine.py:1902 semantics."""
        return (self.micro_steps + 1) % self.gradient_accumulation_steps() == 0

    def step(self, lr_kwargs=None):
        """Apply the optimizer at the gas boundary; otherwise just count."""
        boundary = self.is_gradient_accumulation_boundary()
        if boundary:
            with self.tracer.span(SpanName.TRAIN_OPTIMIZER):
                self._take_model_step(lr_kwargs)
        self.tput_timer.stop(global_step=boundary)
        self.micro_steps += 1
        self.global_samples += self.train_micro_batch_size_per_gpu() * self.dp_world_size

    def _hyper(self) -> Dict[str, jnp.ndarray]:
        return {k: jnp.asarray(v, jnp.float32)
                for k, v in self.optimizer.current_hyperparams().items()}

    def _pull_offload_master_leaves(self) -> List[np.ndarray]:
        """Current device params as host fp32 arrays in the host
        optimizer's group order (multi-host: this process's unique
        blocks only)."""
        self._ensure_params_resident()
        if self._offload_multihost:
            from .zero.offload_engine import local_block
            leaves = []
            for li, leaf in enumerate(
                    jax.tree_util.tree_leaves(self.state["params"])):
                for idx, _, _ in self._offload_layout[li]:
                    leaves.append(np.asarray(local_block(leaf, idx),
                                             np.float32))
        else:
            leaves = [np.asarray(jax.device_get(l), np.float32)
                      for l in jax.tree_util.tree_leaves(self.state["params"])]
        return leaves

    def _zero_offload_residual(self) -> None:
        """Drop the error-feedback compression residual: it carries the
        quantization error of the PREVIOUS trajectory, which is wrong to
        inject into whatever state was just loaded."""
        if getattr(self, "_offload_compress", "none") != "none":
            self._offload_resid_leaves = [jnp.zeros_like(r)
                                          for r in self._offload_resid_leaves]

    def _reseed_offload_master(self) -> None:
        """Rebuild the host fp32 master from the current device params
        with FRESH moments (used when a checkpoint has no host optimizer
        state at all — moments are unrecoverable, so restart them)."""
        leaves = self._pull_offload_master_leaves()
        self._offload_opt.load_state_dict({
            "step": 0,
            "master": [l.ravel() for l in leaves],
            "m": [np.zeros(l.size, np.float32) for l in leaves],
            "v": [np.zeros(l.size, np.float32) for l in leaves],
        })
        self._zero_offload_residual()

    def _sync_offload_master_weights(self, overrides=None) -> None:
        """Overwrite the host fp32 master, KEEPING the Adam moments and
        step count — a mid-training weight swap (EMA/sync via
        load_module_state_dict) must not restart the optimizer trajectory
        (the reference's load_module_state_dict, engine.py:2503, leaves
        optimizer state intact).

        ``overrides`` maps flat param index -> SOURCE array: those leaves
        seed the master from the source at full precision (reading them
        back from the compute-dtype device params would bake 16-bit
        rounding into the master — the same hazard the separate-master
        branch avoids by seeding from ``touched``)."""
        overrides = overrides or {}
        if self._offload_multihost:
            from .zero.offload_engine import local_block
            leaves = []
            for li, leaf in enumerate(
                    jax.tree_util.tree_leaves(self.state["params"])):
                src = overrides.get(li)
                src32 = None if src is None else np.asarray(src, np.float32)
                for idx, _, _ in self._offload_layout[li]:
                    if src32 is not None:
                        leaves.append(np.ascontiguousarray(src32[idx]))
                    else:
                        leaves.append(np.asarray(local_block(leaf, idx),
                                                 np.float32))
        else:
            leaves = []
            for li, leaf in enumerate(
                    jax.tree_util.tree_leaves(self.state["params"])):
                src = overrides.get(li)
                leaves.append(
                    np.asarray(src, np.float32) if src is not None
                    else np.asarray(jax.device_get(leaf), np.float32))
        self._offload_opt.set_masters(leaves)
        self._zero_offload_residual()

    def _group_hyper(self) -> List[Dict[str, float]]:
        """Per-group scalar hyperparams for this step (scheduler-mutated).
        Groups inherit any hyperparam they omit from group 0's current
        values (torch style: an extra group without "lr" keeps the base lr
        — never a silent 0.0)."""
        base = self.optimizer.current_hyperparams()
        return [{k: float(g.get(k, base[k])) for k in base}
                for g in self.optimizer.param_groups]

    def _apply_offload_step(self) -> bool:
        """Gas-boundary step with host-resident optimizer states: device
        preps grads STREAMED one leaf at a time (prep → host pull → free —
        the reference's IPG-bucket discipline, stage_1_and_2.py:868), host
        Adam steps the fp32 master (native SIMD kernel), bf16 params upload
        back leaf-by-leaf (fused precast in the C++ kernel).  Peak device
        overhead beyond the persistent state is one 16-bit leaf, never a
        full gradient- or parameter-sized tree.
        Returns whether the step overflowed (and was skipped)."""
        s = self.state
        # the transferred grads are still loss-scaled (fp16 range safety);
        # read the OLD scale before the state advances, unscale in fp32
        old_scale = float(jax.device_get(s["scale"]["loss_scale"]))
        coef, new_scale, norm, overflow = self._grad_stats_jit(
            s["grad_acc"], s["scale"])
        overflow_host = bool(overflow)
        acc_leaves = jax.tree_util.tree_leaves(s["grad_acc"])
        if overflow_host:
            # skipped step: no transfers — just re-zero the accumulator
            # in place (donated buffers)
            zero_leaves = [self._zero_leaf_jit(g) for g in acc_leaves]
        else:
            bf16 = self.compute_dtype == jnp.bfloat16
            group_hyper = self._group_hyper()

            def to_arr(out, dtype, shape):
                if bf16:
                    return out.view(jnp.bfloat16).reshape(shape)
                return np.asarray(out, dtype).reshape(shape)

            comp = getattr(self, "_offload_compress", "none")
            zero_leaves = []
            if self._offload_multihost:
                from .zero.offload_engine import local_block
                host_grads = []
                for li, g in enumerate(acc_leaves):
                    transfer, zeroed = self._prep_leaf_jit(g, coef)
                    zero_leaves.append(zeroed)
                    host_grads.extend(
                        np.divide(local_block(transfer, idx), old_scale,
                                  dtype=np.float32)
                        for idx, _, _ in self._offload_layout[li])
                    transfer.delete()  # free before next leaf materializes
                outs = self._offload_opt.step(host_grads, bf16_out=bf16,
                                              group_hyper=group_hyper)
                del host_grads
                param_leaves = list(jax.tree_util.tree_leaves(s["params"]))
                # rebuild global params: per-shard device_put onto the
                # master partition, then one jitted reshard (the stage-1
                # weight-update all-gather) to the param sharding
                new_leaves, pos = [], 0
                s["params"] = s["master"] = None
                for li in range(len(param_leaves)):
                    pdtype, pshape = param_leaves[li].dtype, param_leaves[li].shape
                    param_leaves[li] = None  # old leaf freed here
                    blocks = {}
                    for _, key, bshape in self._offload_layout[li]:
                        blocks[key] = to_arr(outs[pos], pdtype, bshape)
                        pos += 1
                    arrs = [jax.device_put(blocks[key], d)
                            for d, key in self._offload_putmap[li]]
                    new_leaves.append(jax.make_array_from_single_device_arrays(
                        pshape, self._master_shardings_flat[li], arrs))
                master_sharded = jax.tree_util.tree_unflatten(
                    self._params_treedef, new_leaves)
                s["params"] = self._reshard_params_jit(master_sharded)
            else:
                # single-host PIPELINED step: dispatch the prep (and async
                # host copy) of leaf i+1 BEFORE pulling leaf i, so leaf
                # i's host Adam + upload overlap leaf i+1's d2h stream —
                # the reference overlaps its IPG buckets with CUDA copy
                # streams the same way.  Window of 2 in-flight transfers
                # (one extra 16-bit leaf of HBM; pipeline_transfers=false
                # restores the strict one-leaf transient).  Old param
                # leaves are dropped from the list AND the state trees
                # (s["master"] aliases s["params"]) before each upload so
                # the upload transient stays at one leaf.
                inv_scale = np.float32(1.0 / old_scale)
                blk = int(getattr(self._offload_cfg, "compression_block",
                                  2048))
                comp_fn = None
                if comp != "none":
                    comp_fn = self._prep_onebit_jit if comp == "onebit" \
                        else self._prep_int8_jit
                param_shardings = jax.tree_util.tree_leaves(
                    self._out_shardings["params"])
                param_leaves = list(jax.tree_util.tree_leaves(s["params"]))
                param_meta = [(l.dtype, l.shape) for l in param_leaves]
                n_leaves = len(param_leaves)
                s["params"] = s["master"] = None
                self._offload_opt.step_begin()
                window = 2 if getattr(self, "_offload_pipeline", True) else 1
                inflight: List[tuple] = []

                def drain_one():
                    pi, arrs, shape, size = inflight.pop(0)
                    if comp == "none":
                        hg = np.divide(jax.device_get(arrs[0]), old_scale,
                                       dtype=np.float32)
                    else:
                        pb = np.asarray(jax.device_get(arrs[0]))
                        sb = np.asarray(jax.device_get(arrs[1]), np.float32)
                        if comp == "onebit":
                            vals = np.unpackbits(
                                pb, bitorder="little").astype(np.float32) \
                                * 2.0 - 1.0
                        else:  # int8
                            vals = pb.astype(np.float32)
                        hg = np.ascontiguousarray(
                            (vals.reshape(-1, blk) * sb[:, None])
                            .reshape(-1)[:size].reshape(shape))
                    for a in arrs:
                        a.delete()
                    out = self._offload_opt.step_one(
                        pi, hg, bf16_out=bf16, group_hyper=group_hyper)
                    pdtype, pshape = param_meta[pi]
                    param_leaves[pi] = None  # old leaf freed here
                    param_leaves[pi] = jax.device_put(
                        to_arr(out, pdtype, pshape), param_shardings[pi])

                try:
                    for li in range(n_leaves):
                        g = acc_leaves[li]
                        shape, size = g.shape, g.size
                        if comp_fn is not None:
                            payload, scales, resid_new, zeroed = comp_fn(
                                g, self._offload_resid_leaves[li], coef,
                                inv_scale)
                            self._offload_resid_leaves[li] = resid_new
                            arrs = (payload, scales)
                        else:
                            transfer, zeroed = self._prep_leaf_jit(g, coef)
                            arrs = (transfer,)
                        zero_leaves.append(zeroed)
                        for a in arrs:
                            a.copy_to_host_async()
                        inflight.append((li, arrs, shape, size))
                        if len(inflight) >= window:
                            drain_one()
                    while inflight:
                        drain_one()
                    self._offload_opt.step_end()
                except Exception:
                    # leave the engine checkpointable: the host master is
                    # the authority — rebuild any leaf lost mid-drain
                    # from it, and replace the accumulator (its prepped
                    # leaves were donated, i.e. deleted; this step's
                    # gradients are lost either way) before re-raising.
                    # Best-effort: if the master itself is unreadable,
                    # params stay None as before this pipeline existed.
                    try:
                        # independent of the master: the accumulator's
                        # prepped leaves are gone regardless
                        while len(zero_leaves) < n_leaves:
                            zero_leaves.append(self._zero_leaf_jit(
                                acc_leaves[len(zero_leaves)]))
                        s["grad_acc"] = jax.tree_util.tree_unflatten(
                            jax.tree_util.tree_structure(s["grad_acc"]),
                            zero_leaves)
                    except Exception as restore_err:
                        logger.warning(
                            "[offload] best-effort grad_acc restore after a "
                            f"failed master read also failed: {restore_err!r}")
                    try:
                        masters = None
                        for pi, leaf in enumerate(param_leaves):
                            if leaf is None:
                                if masters is None:
                                    masters = self._offload_opt.masters()
                                pdtype, pshape = param_meta[pi]
                                host = np.asarray(masters[pi], np.float32) \
                                    .reshape(pshape).astype(np.dtype(pdtype))
                                param_leaves[pi] = jax.device_put(
                                    host, param_shardings[pi])
                        s["params"] = s["master"] = \
                            jax.tree_util.tree_unflatten(
                                self._params_treedef, param_leaves)
                    except Exception as restore_err:
                        logger.warning(
                            "[offload] best-effort param restore after a "
                            f"failed master read also failed: {restore_err!r}")
                    raise
                s["params"] = jax.tree_util.tree_unflatten(
                    self._params_treedef, param_leaves)
            s["master"] = s["params"]
        s["grad_acc"] = jax.tree_util.tree_unflatten(
            jax.tree_util.tree_structure(s["grad_acc"]), zero_leaves)
        s["scale"] = new_scale
        self._last_global_norm = norm
        return overflow_host

    @hot_path
    def _take_model_step(self, lr_kwargs=None) -> None:
        if self._offload_device is not None:
            overflow_host = self._apply_offload_step()
            self._spill_params()
            self._finish_model_step(overflow_host, lr_kwargs)
            return
        s = self.state
        grad_in = s["grad_acc"]
        zeroed_stacked = None
        if self._collapse_axis is not None:
            # collapse the per-worker partials across the collapse axis:
            # one crossing per boundary step, compressed when configured.
            # Compression preflight: an overflowed accumulator must NOT
            # touch the EF state (inf - inf = NaN would poison every later
            # step; the uncompressed mean carries the inf to apply_core,
            # which skips the step and backs the scale off as usual), and
            # a loss-scale change re-denominates the carried residual —
            # EF is linear in the gradient scale, so the rescale is exact.
            with self.tracer.span(SpanName.TRAIN_GRAD_SYNC,
                                  axis=self._collapse_axis,
                                  n=self._collapse_n):
                use_compressed = self._dcn_reduce is not None
                if use_compressed and self.scaler_config.enabled:
                    self.compile_registry.note_host_sync("step.dcn_finite")
                    # dslint: disable=host-sync-in-hot-path — one scalar pull
                    use_compressed = bool(jax.device_get(
                        self._dcn_finite_jit(s["grad_acc"])))
                mode = self._collapse_mode if use_compressed else "mean"
                wire = self._collapse_wire_bytes if use_compressed \
                    else self._collapse_logical_bytes
                with self.tracer.span(
                        SpanName.COMM_REDUCE, mode=mode,
                        axis=self._collapse_axis,
                        logical_bytes=self._collapse_logical_bytes,
                        wire_bytes=wire):
                    if use_compressed:
                        self.compile_registry.note_host_sync("step.ef_scale")
                        scale_dev = s["scale"]["loss_scale"]
                        # dslint: disable=host-sync-in-hot-path — one scalar pull
                        cur_scale = float(jax.device_get(scale_dev))
                        if cur_scale != self._dcn_ef_scale:
                            ratio = cur_scale / self._dcn_ef_scale
                            self._dcn_we, self._dcn_se = \
                                self._dcn_rescale_ef_jit(
                                    self._dcn_we, self._dcn_se,
                                    jnp.float32(ratio))
                            self._dcn_ef_scale = cur_scale
                        (grad_in, zeroed_stacked, self._dcn_we,
                         self._dcn_se) = self._dcn_compress_jit(
                            s["grad_acc"], self._dcn_we, self._dcn_se)
                    else:
                        grad_in, zeroed_stacked = self._dcn_mean_jit(
                            s["grad_acc"])
                if self.metrics_sampler.enabled:
                    self.metrics.counter(
                        MetricName.COMM_LOGICAL_BYTES).inc(
                        self._collapse_logical_bytes)
                    self.metrics.counter(
                        MetricName.COMM_WIRE_BYTES).inc(wire)
        if self._separate_master:
            (new_params, new_master, new_opt, zero_acc, new_scale, norm,
             overflow) = self._apply_jit(
                s["params"], s["master"], s["opt_state"], grad_in,
                s["scale"], self._hyper())
        else:
            (new_params, new_master, new_opt, zero_acc, new_scale, norm,
             overflow) = self._apply_jit_single(
                s["params"], s["opt_state"], grad_in, s["scale"], self._hyper())
        s["params"] = new_params
        s["master"] = new_master if self._separate_master else new_params
        s["opt_state"] = new_opt
        s["grad_acc"] = zeroed_stacked if self._collapse_axis is not None \
            else zero_acc
        s["scale"] = new_scale
        self._last_global_norm = norm  # device scalar; float() lazily
        self._spill_params()
        self.compile_registry.note_host_sync("step.overflow")
        with self.tracer.span(SpanName.TRAIN_HOST_SYNC,
                              label="step.overflow"):
            # the step/skip decision is host control flow by design:
            # dslint: disable=host-sync-in-hot-path — one scalar pull per step
            overflow_host = bool(overflow)
        self._finish_model_step(overflow_host, lr_kwargs)

    def _finish_model_step(self, overflow_host: bool, lr_kwargs=None) -> None:
        """Post-step bookkeeping shared by the device and offload paths:
        counters, scheduler, periodic log, monitor events."""
        self.global_steps += 1
        if overflow_host:
            self.skipped_steps += 1
            log_dist(f"[deepspeed_tpu] OVERFLOW! skipping step, "
                     f"reducing loss scale to {self.cur_scale}", ranks=[0])
        elif self._lr_scheduler is not None:
            self._lr_scheduler.step(**(lr_kwargs or {}))
        if self.global_steps % self.steps_per_print() == 0:
            log_dist(f"step={self.global_steps}, skipped={self.skipped_steps}, "
                     f"lr={self.get_lr()}, loss_scale={self.cur_scale}", ranks=[0])
        if self.monitor.enabled:
            events = [("Train/Samples/lr", self.get_lr()[0], self.global_samples)]
            if self.fp16_enabled():
                events.append(("Train/Samples/loss_scale", self.cur_scale,
                               self.global_samples))
            self.monitor.write_events(events)
        if self._compression_scheduler is not None:
            self._compression_scheduler.step()
        self._note_step_telemetry()

    # fused whole-batch path -------------------------------------------------
    def train_batch_fused(self, batches):
        """Run a full train batch (gas stacked on dim 0) in one jit call."""
        with self.tracer.span(SpanName.TRAIN_STEP,
                              step=self.global_steps + 1):
            return self._train_batch_fused_inner(batches)

    def _train_batch_fused_inner(self, batches):
        if self._offload_device is not None or self._collapse_axis is not None:
            # host step (offload) / boundary collapse (dcn / zero-q)
            # can't live inside one jit: micro loop, step at the boundary
            gas = self.gradient_accumulation_steps()
            chunks = jax.tree_util.tree_map(
                lambda x: np.reshape(np.asarray(x),
                                     (gas, -1) + np.shape(x)[1:]), batches)
            losses = []
            for i in range(gas):
                chunk = jax.tree_util.tree_map(lambda x: x[i], chunks)
                losses.append(self.forward(chunk))
                self.backward()
                self.step()
            return jnp.mean(jnp.stack(losses))
        self._ensure_params_resident()
        self._count_batch_tokens(batches)
        fused, args = self._fused_program(batches)
        with self.tracer.span(SpanName.TRAIN_DISPATCH):
            (new_params, new_master, new_opt, zero_acc, new_scale, norm,
             overflow, mean_loss) = fused(*args)
        s = self.state
        s["params"] = new_params
        s["master"] = new_master if self._separate_master else new_params
        s["opt_state"] = new_opt
        s["grad_acc"] = zero_acc
        s["scale"] = new_scale
        self._last_global_norm = norm
        self._spill_params()
        self.micro_steps += self.gradient_accumulation_steps()
        self.global_samples += self.train_batch_size()
        self.compile_registry.note_host_sync("step.overflow")
        with self.tracer.span(SpanName.TRAIN_HOST_SYNC,
                              label="step.overflow"):
            overflow_host = bool(overflow)
        self._finish_model_step(overflow_host)
        return mean_loss

    def _fused_program(self, batches):
        """``(program, args)`` of the fused whole-batch step for
        ``batches``: the registered jit and the sharded operands
        ``train_batch_fused`` calls it with (``program.lower(*args)`` is
        the step's AOT handle — HLO text, memory analysis)."""
        s = self.state
        batches = self._apply_curriculum(batches)
        with self.tracer.span(SpanName.TRAIN_BATCH_PUT):
            batches = jax.tree_util.tree_map(
                lambda x: jnp.asarray(x).reshape(
                    (self.gradient_accumulation_steps(), -1)
                    + np.shape(x)[1:]), batches)
            batches = jax.tree_util.tree_map(
                lambda x: jax.device_put(x, NamedSharding(
                    self.mesh, P(None, (DCN_AXIS, DATA_AXIS, EXPERT_AXIS)))),
                batches)
        if self._compression_scheduler is not None and isinstance(batches, dict):
            from ..compression.compress import STEP_KEY
            # one step scalar per gas micro-step (same global step for all)
            batches = {**batches, STEP_KEY: jax.device_put(
                jnp.full((self.gradient_accumulation_steps(),),
                         self.global_steps, jnp.int32),
                NamedSharding(self.mesh, P(None)))}
        batches = self._inject_train_rng(
            batches, n=self.gradient_accumulation_steps())
        batches = self._inject_pld(
            batches, n=self.gradient_accumulation_steps())
        if self._separate_master:
            return self._fused_jit, (
                s["params"], s["master"], s["opt_state"], s["grad_acc"],
                s["scale"], batches, self._hyper())
        return self._fused_jit_single, (
            s["params"], s["opt_state"], s["grad_acc"], s["scale"],
            batches, self._hyper())

    # ------------------------------------------------------------------ eval
    def eval_loss(self, batch):
        self._ensure_params_resident()
        batch = self._inject_compression_step(batch)
        batch = self._shard_batch(batch)
        if not hasattr(self, "_eval_jit"):
            self._eval_jit = self.compile_registry.register(
                "eval", jax.jit(self.module.loss_fn))
        return self._eval_jit(self.state["params"], batch)

    # ------------------------------------------------------------------ checkpoint
    def set_commit_context(self, ctx) -> None:
        """Attach a :class:`~.checkpoint_engine.commit.CommitContext` (the
        elastic runner does, wiring in its journal and heartbeat monitor)
        so saves run the two-phase commit and loads run resume consensus."""
        if ctx is not None and getattr(ctx, "tracer", None) is None:
            ctx.tracer = self.tracer  # ckpt.commit spans land in our trace
        self._commit_ctx = ctx

    def _commit_context(self):
        """The commit context for this save/load: the attached one, else a
        default built from the live comm world.  ``None`` when the protocol
        is disabled in config."""
        cfg = self._config.checkpoint_config.commit_config
        if not cfg.enabled:
            return None
        if self._commit_ctx is not None:
            return self._commit_ctx
        from .checkpoint_engine.commit import (CollectiveConsensusChannel,
                                               CommitContext)
        world = dist.get_world_size()
        self._commit_ctx = CommitContext(
            world_size=world, rank=self.global_rank, config=cfg,
            channel=CollectiveConsensusChannel() if world > 1 else None,
            tracer=self.tracer)
        return self._commit_ctx

    def save_checkpoint(self, save_dir, tag=None, client_state=None,
                        save_latest=True) -> bool:
        tag = tag or f"global_step{self.global_steps}"
        with self.tracer.span(SpanName.CKPT_SAVE, tag=tag):
            return self._save_checkpoint_inner(save_dir, tag, client_state,
                                               save_latest)

    def _save_checkpoint_inner(self, save_dir, tag, client_state,
                               save_latest) -> bool:
        from .checkpoint_engine.native_checkpoint_engine import save_engine_checkpoint
        self._ensure_params_resident()
        client_state = dict(client_state or {})
        client_state.update({
            "micro_steps": self.micro_steps,
            "global_steps": self.global_steps,
            "global_samples": self.global_samples,
            "skipped_steps": self.skipped_steps,
        })
        if self._lr_scheduler is not None:
            client_state["lr_scheduler"] = self._lr_scheduler.state_dict()
        client_state["optimizer_param_groups"] = self.optimizer.param_groups
        if self._curriculum is not None:
            client_state["curriculum"] = self._curriculum.state_dict()
        if self.data_iterator is not None and \
                hasattr(self.data_iterator, "state_dict"):
            client_state["data_iterator"] = self.data_iterator.state_dict()
        offload = self._offload_device is not None
        if offload:
            # host-side fp32 master + moments (zero_pp_rank_* analogue) —
            # written BEFORE save_engine_checkpoint so the latest marker
            # never advertises a tag whose offload state is missing
            path = os.path.join(save_dir, tag,
                                f"offload_optimizer_rank{self.global_rank}.npz")
            os.makedirs(os.path.dirname(path), exist_ok=True)
            self._offload_opt.save(path)
            if getattr(self, "_offload_compress", "none") != "none":
                # the error-feedback residual is part of the optimizer
                # trajectory: persisting it makes resume exact (otherwise
                # the carried quantization error is silently dropped);
                # atomic like every other shard so a kill mid-save never
                # leaves a torn rank file the commit vote then hashes
                from .checkpoint_engine.storage import atomic_write_npz
                atomic_write_npz(os.path.join(
                    save_dir, tag,
                    f"offload_residual_rank{self.global_rank}.npz"),
                    {f"r_{i}": np.asarray(jax.device_get(r), np.float32)
                     for i, r in enumerate(self._offload_resid_leaves)},
                    self._config.checkpoint_config.retry)
        if self._dcn_reduce is not None:
            # DCN error-feedback state is part of the trajectory: persist
            # for exact resume (like the offload compression residual).
            # Only this process's addressable shards are pulled — the EF
            # arrays are dcn-sharded and NOT fully addressable when the
            # slices span hosts (the deployment case)
            from .checkpoint_engine.storage import atomic_write_npz
            from .zero.offload_engine import index_key, unique_local_blocks
            os.makedirs(os.path.join(save_dir, tag), exist_ok=True)
            arrays = {"ef_scale": np.asarray(self._dcn_ef_scale)}
            for name, arr in (("we", self._dcn_we), ("se", self._dcn_se)):
                for bi, (idx, blk) in enumerate(unique_local_blocks(arr)):
                    key = index_key(idx, arr.shape)
                    arrays[f"{name}_{bi}_key"] = np.asarray(key, np.int64)
                    arrays[f"{name}_{bi}_data"] = np.asarray(blk)
            atomic_write_npz(os.path.join(save_dir, tag,
                                          f"dcn_ef_rank{self.global_rank}.npz"),
                             arrays, self._config.checkpoint_config.retry)
        save_engine_checkpoint(save_dir, tag, self.state, client_state,
                               separate_master=self._separate_master and not offload,
                               save_latest=save_latest,
                               engine=self._checkpoint_engine,
                               config=self._config.checkpoint_config,
                               manifest_meta={
                                   "world_size": self.dp_world_size,
                                   "writer": {"rank": self.global_rank},
                               },
                               commit_ctx=self._commit_context())
        self._copy_recovery_script(save_dir)
        # spilled-param engines return to the between-steps memory bound
        # (nothing big resident) as soon as the checkpoint is written
        self._spill_params()
        return True

    def _copy_recovery_script(self, save_dir: str) -> None:
        """Drop a fp32-recovery shim next to the checkpoints (reference
        engine.py:3249 copies utils/zero_to_fp32.py the same way).
        Coordinator-only and atomic: on a pod every rank saves into the
        same directory, and N ranks racing a plain ``open(path, "w")`` on
        shared storage can interleave into a torn script."""
        if self.global_rank != 0:
            return
        path = os.path.join(save_dir, "zero_to_fp32.py")
        if os.path.exists(path):
            return
        tmp = path + ".tmp"
        with open(tmp, "w") as f:
            f.write(
                "#!/usr/bin/env python3\n"
                '"""Recover a consolidated fp32 state dict from this '
                'checkpoint dir.\nUsage: python zero_to_fp32.py . out.npz '
                '[tag]\n"""\n'
                "import sys\n"
                "from deepspeed_tpu.utils.zero_to_fp32 import main\n"
                "sys.exit(main())\n")
        os.replace(tmp, path)

    def load_checkpoint(self, load_dir, tag=None, load_module_strict=True,
                        load_optimizer_states=True, load_lr_scheduler_states=True,
                        load_module_only=False):
        with self.tracer.span(SpanName.CKPT_LOAD, tag=tag or ""):
            return self._load_checkpoint_inner(
                load_dir, tag, load_module_strict, load_optimizer_states,
                load_lr_scheduler_states, load_module_only)

    def _load_checkpoint_inner(self, load_dir, tag, load_module_strict,
                               load_optimizer_states,
                               load_lr_scheduler_states, load_module_only):
        from .checkpoint_engine.native_checkpoint_engine import (
            load_engine_checkpoint, resolve_tag)
        self._ensure_params_resident()  # state acts as the load template
        if self._checkpoint_engine is not None:
            # never read our own in-flight async writes (also re-raises a
            # background write failure here instead of losing it)
            self._checkpoint_engine.wait()
        cctx = self._commit_context()
        if tag is None and cctx is not None and cctx.world_size > 1:
            # resume consensus: every host proposes its newest verified
            # committed tag and the group agrees (min over proposals) —
            # elastic restarts, rollbacks, and fallback loads all route
            # through here, so no two hosts can silently resume from
            # different tags.  A failed agreement raises
            # ResumeConsensusError: split-brain is worse than a crash.
            from .checkpoint_engine.commit import agree_resume_tag
            tag = agree_resume_tag(load_dir, cctx)
            if tag is None:
                logger.warning(
                    f"[ckpt-commit] resume consensus: no committed tag "
                    f"anywhere under {load_dir}; starting fresh")
                return None, {}
        offload = self._offload_device is not None
        state, client_state = load_engine_checkpoint(
            load_dir, tag, self.state,
            shardings=self._out_shardings,
            load_optimizer_states=load_optimizer_states and not load_module_only,
            separate_master=self._separate_master and not offload,
            config=self._config.checkpoint_config)
        if state is None:
            return None, {}
        # the tag the fallback chain actually loaded (may be older than the
        # latest marker when that tag was corrupt) — the per-rank offload /
        # DCN files must come from the SAME tag as the model state
        loaded_tag = client_state.pop("_ckpt_tag", None) or \
            resolve_tag(load_dir, tag)
        self.state = state
        if offload:
            loaded = False
            if load_optimizer_states and not load_module_only:
                path = os.path.join(
                    load_dir, loaded_tag or "",
                    f"offload_optimizer_rank{self.global_rank}.npz")
                if os.path.exists(path):
                    self._offload_opt.load(path)
                    loaded = True
                    if getattr(self, "_offload_compress", "none") != "none":
                        # restore the error-feedback residual for exact
                        # resume, else zero it — the pre-load residual
                        # belongs to the trajectory being replaced
                        rpath = os.path.join(
                            os.path.dirname(path),
                            f"offload_residual_rank{self.global_rank}.npz")
                        if os.path.exists(rpath):
                            gsh = jax.tree_util.tree_leaves(
                                self._out_shardings["grads"])
                            with np.load(rpath) as z:
                                self._offload_resid_leaves = [
                                    jax.device_put(
                                        z[f"r_{i}"].astype(
                                            np.dtype(r.dtype)), s)
                                    for i, (r, s) in enumerate(zip(
                                        self._offload_resid_leaves, gsh))]
                        else:
                            self._zero_offload_residual()
                else:
                    logger.warning(
                        f"no offload optimizer state at {path}; re-seeding "
                        "host master from loaded params, moments reset")
            if not loaded:
                # the host master must always track the loaded params or the
                # first step would overwrite them with the init-time master
                self._reseed_offload_master()
        if self._dcn_reduce is not None:
            ef_path = os.path.join(load_dir, loaded_tag or "",
                                   f"dcn_ef_rank{self.global_rank}.npz")
            if os.path.exists(ef_path):
                with np.load(ef_path) as z:
                    self._dcn_ef_scale = float(z["ef_scale"])
                    for name in ("we", "se"):
                        cur = getattr(self, f"_dcn_{name}")
                        blocks = {}
                        bi = 0
                        while f"{name}_{bi}_key" in z:
                            key = tuple(map(tuple, z[f"{name}_{bi}_key"]))
                            blocks[key] = z[f"{name}_{bi}_data"]
                            bi += 1
                        from .zero.offload_engine import index_key
                        arrs = []
                        for shard in cur.addressable_shards:
                            k = index_key(shard.index, cur.shape)
                            arrs.append(jax.device_put(blocks[k],
                                                       shard.device))
                        setattr(self, f"_dcn_{name}",
                                jax.make_array_from_single_device_arrays(
                                    cur.shape, cur.sharding, arrs))
            else:
                # a checkpoint without EF state: the carried quantization
                # error belongs to the replaced trajectory
                self._dcn_we = jnp.zeros_like(self._dcn_we)
                self._dcn_se = jnp.zeros_like(self._dcn_se)
                self._dcn_ef_scale = float(jax.device_get(
                    self.state["scale"]["loss_scale"]))
        self.micro_steps = client_state.get("micro_steps", 0)
        self.global_steps = client_state.get("global_steps", 0)
        self.global_samples = client_state.get("global_samples", 0)
        self.skipped_steps = client_state.get("skipped_steps", 0)
        if load_lr_scheduler_states and self._lr_scheduler is not None and \
                "lr_scheduler" in client_state:
            self._lr_scheduler.load_state_dict(client_state["lr_scheduler"])
        if self._curriculum is not None and "curriculum" in client_state:
            self._curriculum.load_state_dict(client_state["curriculum"])
        if self.data_iterator is not None and \
                hasattr(self.data_iterator, "load_state_dict") and \
                "data_iterator" in client_state:
            try:
                self.data_iterator.load_state_dict(
                    client_state["data_iterator"])
            except ValueError as e:
                # geometry changed between save and load: the saved position
                # no longer names the same batches — keep the live position
                # and say so, rather than silently replaying a different
                # sequence under a "resumed" banner
                logger.warning(
                    f"data iterator state in checkpoint NOT restored: {e}")
        self._spill_params()  # restore the between-steps memory bound
        if "optimizer_param_groups" in client_state and load_optimizer_states:
            restored = client_state["optimizer_param_groups"]
            if len(restored) == len(self.optimizer.param_groups):
                self.optimizer.param_groups = restored
            else:
                # the leaf->group mapping (offload group_of, _group_hyper
                # indexing) derives from the CONSTRUCTED groups; a
                # checkpoint with a different group structure cannot be
                # applied positionally
                logger.warning(
                    f"checkpoint has {len(restored)} param groups but the "
                    f"optimizer was constructed with "
                    f"{len(self.optimizer.param_groups)}; keeping the "
                    "constructed groups (hyperparams from the checkpoint "
                    "are NOT restored)")
        return load_dir, client_state

    # -------------------------------------------------- module-level parity
    # (reference engine.py:1631 train / :1637 eval / :1938 zero_grad /
    #  :409 get_batch_info / :2214 get_mom / :2436 module_state_dict /
    #  :2503 load_module_state_dict)

    def train(self, mode: bool = True) -> "DeepSpeedEngine":
        """Toggle training mode: controls whether ``forward`` threads
        per-micro-step dropout PRNG keys (eval is deterministic by
        construction — no key, no stochasticity)."""
        self._training = bool(mode)
        return self

    def eval(self) -> "DeepSpeedEngine":
        self._training = False
        return self

    def zero_grad(self) -> None:
        """Clear accumulated gradients (donating re-zero of the
        accumulator tree — no new allocation survives the call)."""
        if self._zero_tree_jit is None:
            self._zero_tree_jit = self.compile_registry.register(
                "zero_tree", jax.jit(
                    lambda t: jax.tree_util.tree_map(jnp.zeros_like, t),
                    donate_argnums=(0,)))
        self.state["grad_acc"] = self._zero_tree_jit(self.state["grad_acc"])

    def compile_counts(self) -> Dict[str, int]:
        """jit-cache entries per registered step program — the
        no-recompile contract after warmup is ``all(v <= 1)`` per shape
        class (the serving stack's ``compile_counts()``, generalized; see
        ``utils/compile_watch.py`` and ``scripts/compile_report.py``)."""
        return self.compile_registry.counts()

    def get_batch_info(self):
        """(train_batch_size, train_micro_batch_size_per_gpu,
        gradient_accumulation_steps)."""
        return (self.train_batch_size(),
                self.train_micro_batch_size_per_gpu(),
                self.gradient_accumulation_steps())

    def get_mom(self):
        """Per-group momentum config: the betas tuple for the Adam
        family, the scalar momentum for SGD/RMSprop (reference get_mom
        branches on optimizer_name the same way)."""
        opt = self.optimizer
        groups = getattr(opt, "param_groups", None) or [{}]
        fallback = getattr(opt, "betas", None)
        if fallback is None:
            fallback = getattr(opt, "momentum", (0.9, 0.999))
        return [g.get("betas", g.get("momentum", fallback)) for g in groups]

    def module_state_dict(self):
        """The current parameter pytree (compute-dtype device arrays) —
        the SPMD stand-in for the reference's torch state_dict."""
        self._ensure_params_resident()
        return self.state["params"]

    def load_module_state_dict(self, state_dict, strict: bool = True):
        """Replace the parameters from a pytree of arrays (host or
        device).  ``strict`` requires an exactly matching tree structure;
        non-strict matches leaves by tree path (torch load_state_dict
        matches by name the same way) and loads those whose shapes agree,
        warning about the rest.  The fp32 master (separate-master or host
        offload) syncs to the loaded weights from the source leaves;
        offload engines keep their Adam moments and step count."""
        self._ensure_params_resident()
        cur_kv, cur_def = jax.tree_util.tree_flatten_with_path(
            self.state["params"])
        new_kv, new_def = jax.tree_util.tree_flatten_with_path(state_dict)
        if strict and cur_def != new_def:
            raise ValueError(
                f"state_dict tree mismatch: {new_def} vs {cur_def}")
        # match by tree PATH, not flattened position: two structurally
        # different trees whose leaves happen to align in order must not
        # load wrong weights into wrong slots (torch load_state_dict
        # matches by name the same way)
        new_by_path = {jax.tree_util.keystr(p): l for p, l in new_kv}
        sh_flat = jax.tree_util.tree_leaves(self._out_shardings["params"])
        out = []
        touched = []   # (flat index, source leaf)
        skipped = []
        for i, ((path, cur), psh) in enumerate(zip(cur_kv, sh_flat)):
            key = jax.tree_util.keystr(path)
            leaf = new_by_path.pop(key, None)
            if leaf is None:
                if strict:
                    raise ValueError(f"state_dict is missing leaf {key}")
                out.append(cur)
                skipped.append(f"{key} (absent)")
                continue
            if tuple(leaf.shape) != tuple(cur.shape):
                if strict:
                    raise ValueError(
                        f"leaf {key} shape {leaf.shape} != {cur.shape}")
                out.append(cur)
                skipped.append(f"{key} ({leaf.shape} != {cur.shape})")
                continue
            out.append(jax.device_put(
                jnp.asarray(leaf, dtype=cur.dtype), psh))
            touched.append((i, leaf))
        if not strict and (skipped or new_by_path):
            extra = list(new_by_path)
            logger.warning(
                f"load_module_state_dict (non-strict): loaded "
                f"{len(touched)}/{len(cur_kv)} leaves"
                + (f"; skipped {len(skipped)} ({skipped[:8]}...)"
                   if skipped else "")
                + (f"; unmatched source leaves {extra[:8]}" if extra else ""))
        params = jax.tree_util.tree_unflatten(cur_def, out)
        self.state["params"] = params
        if self._separate_master and self._offload_device is None:
            # the fp32 master seeds from the SOURCE leaves — casting
            # through a 16-bit compute dtype first would bake rounding
            # error into the master every optimizer step evolves from
            m_flat = list(jax.tree_util.tree_leaves(self.state["master"]))
            msh_flat = jax.tree_util.tree_leaves(
                self._out_shardings["master"])
            for i, leaf in touched:
                m_flat[i] = jax.device_put(
                    jnp.asarray(leaf, dtype=jnp.float32), msh_flat[i])
            self.state["master"] = jax.tree_util.tree_unflatten(
                cur_def, m_flat)
        else:
            self.state["master"] = params
        if self._offload_device is not None:
            # host master syncs to the loaded weights (from the SOURCE
            # leaves, full precision); moments and step count survive (a
            # weight swap is not a trajectory restart — reference
            # load_module_state_dict, engine.py:2503)
            self._sync_offload_master_weights(
                overrides={i: leaf for i, leaf in touched})
