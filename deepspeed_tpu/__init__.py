"""deepspeed_tpu: a TPU-native distributed training & inference framework.

Public API mirrors the reference's ``deepspeed/__init__.py`` (initialize :52,
init_inference :233, init_distributed :29, add_config_arguments :210) while
the machinery underneath is JAX/XLA/Pallas over a device mesh.
"""

from __future__ import annotations

import argparse
import json
import os
from typing import Any, Dict, Optional, Tuple, Union

__version__ = "0.1.0"
__git_branch__ = "main"

from . import comm as _comm_pkg  # noqa: F401
from .accelerator import get_accelerator  # noqa: F401 — reference parity
from .comm.comm import init_distributed
from .inference.config import DeepSpeedInferenceConfig  # noqa: F401
from .inference.engine import InferenceEngine  # noqa: F401
from .parallel.mesh import (MeshManager, ParallelDims, get_mesh_manager,
                            initialize_mesh)
from .runtime.activation_checkpointing import checkpointing
from .runtime.config import DeepSpeedConfig, DeepSpeedConfigError  # noqa: F401
from .runtime.lr_schedules import add_tuning_arguments  # noqa: F401
from .ops.transformer import (DeepSpeedTransformerConfig,
                              DeepSpeedTransformerLayer)
from .runtime.pipe.engine import PipelineEngine  # noqa: F401
from .runtime.pipe.module import LayerSpec, PipelineModule, TiedLayerSpec
from .runtime import zero  # noqa: F401 — deepspeed.zero namespace parity
from .module_inject.replace_policy import (  # noqa: F401
    replace_transformer_layer, revert_transformer_layer)
from .runtime.engine import DeepSpeedEngine
from .runtime.model import ModelSpec, from_gpt
from .utils.logging import log_dist, logger  # noqa: F401

# guards Autotuner trial engines from re-entering the autotuner
_autotuning_active = False


def _load_raw_config(config: Union[str, Dict, None],
                     config_params: Union[str, Dict, None]) -> Dict:
    cfg = config if config is not None else config_params
    if cfg is None:
        raise ValueError("DeepSpeed requires a config (path or dict)")
    if isinstance(cfg, (str, os.PathLike)):
        with open(cfg) as f:
            return json.load(f)
    return dict(cfg)


def _mesh_from_config(raw: Dict, mesh_manager: Optional[MeshManager]) -> MeshManager:
    if mesh_manager is not None:
        from .parallel.mesh import set_mesh_manager
        set_mesh_manager(mesh_manager)
        return mesh_manager
    tp = raw.get("tensor_parallel", {})
    tp_size = tp.get("size", tp.get("tp_size", 1)) if tp else 1
    sp = raw.get("sequence_parallel", {})
    sp_size = sp.get("size", 1) if sp else 1
    pipe = raw.get("pipeline", {})
    pp_size = pipe.get("stages", 1) if isinstance(pipe, dict) else 1
    moe = raw.get("moe", {})
    ep_size = moe.get("ep_size", 1) if isinstance(moe, dict) else 1
    mesh_dims = raw.get("mesh", None)
    if mesh_dims:
        dims = ParallelDims(dp=mesh_dims.get("dp", -1), tp=mesh_dims.get("tp", tp_size),
                            pp=mesh_dims.get("pp", pp_size), sp=mesh_dims.get("sp", sp_size),
                            ep=mesh_dims.get("ep", ep_size))
    else:
        dims = ParallelDims(dp=-1, tp=tp_size, pp=pp_size, sp=sp_size, ep=ep_size)
    return initialize_mesh(dims)


def initialize(args=None,
               model: Optional[ModelSpec] = None,
               optimizer=None,
               model_parameters=None,
               training_data=None,
               lr_scheduler=None,
               mpu=None,
               dist_init_required: Optional[bool] = None,
               collate_fn=None,
               config: Union[str, Dict, None] = None,
               config_params: Union[str, Dict, None] = None,
               mesh_manager: Optional[MeshManager] = None,
               rng=None) -> Tuple[DeepSpeedEngine, Any, Any, Any]:
    """Initialize the engine (reference deepspeed/__init__.py:52).

    Returns ``(engine, optimizer, training_dataloader, lr_scheduler)``.
    """
    logger.info(f"deepspeed_tpu v{__version__} initialize")
    if config is None and args is not None and hasattr(args, "deepspeed_config") \
            and args.deepspeed_config is not None:
        config = args.deepspeed_config
    raw = _load_raw_config(config, config_params)
    mm = _mesh_from_config(raw, mesh_manager)

    # autotuning handoff (reference launcher/runner.py:324 run_autotuning →
    # autotuner.tune): with {"autotuning": {"enabled": true}} (or the
    # launcher's --autotuning flag latched in DS_AUTOTUNING), search the
    # config space first.  Mode "run" (default) proceeds with the tuned
    # config; mode "tune" records results and proceeds untouched.
    # An explicit {"enabled": false} wins over the env latch, and the
    # re-entrancy guard keeps the Autotuner's own trial engines (which call
    # initialize() in this same process) from tuning recursively.
    global _autotuning_active
    at_enabled = raw.get("autotuning", {}).get("enabled")
    at_env = os.environ.get("DS_AUTOTUNING", "").strip()
    if at_env and at_env not in ("tune", "run"):
        logger.warning(f"DS_AUTOTUNING={at_env!r} is not 'tune' or 'run'; "
                       "treating it as 'run'")
    at_mode = at_env if at_env in ("tune", "run") else "run"
    should_tune = (at_enabled is True or (at_enabled is None and bool(at_env)))
    if should_tune and not _autotuning_active:
        from .autotuning import Autotuner
        _autotuning_active = True
        try:
            tuned = Autotuner(model, raw, mesh_manager=mm, rng=rng).tune()
        finally:
            _autotuning_active = False
        if tuned is not None and at_mode == "run":
            raw = tuned

    # pipelined models get the PipelineEngine (reference __init__.py:124-148
    # routes PipelineModule to PipelineEngine the same way)
    from .runtime.pipe.module import PipelineModule
    if isinstance(model, PipelineModule):
        raise TypeError(
            "initialize() needs a ModelSpec, not a raw PipelineModule — wrap "
            "it (e.g. models.gpt_pipeline.model_spec for GPT, or build a "
            "ModelSpec whose meta includes {'pipeline': True}) so the engine "
            "knows the loss/init functions to jit")
    engine_cls = DeepSpeedEngine
    if model is not None and getattr(model, "meta", {}).get("pipeline"):
        from .runtime.pipe.engine import PipelineEngine
        engine_cls = PipelineEngine

    engine = engine_cls(
        args=args,
        model=model,
        optimizer=optimizer,
        model_parameters=model_parameters,
        training_data=training_data,
        lr_scheduler=lr_scheduler,
        mpu=mpu,
        dist_init_required=dist_init_required,
        collate_fn=collate_fn,
        config=raw,
        mesh_manager=mm,
        rng=rng)
    return engine, engine.optimizer, engine.training_dataloader, engine.lr_scheduler


def init_inference(model=None, config=None, **kwargs):
    """Build an InferenceEngine (reference deepspeed/__init__.py:233).

    ``model`` may be:
      - a live HF torch module (GPT-2 family) — converted through the
        injection policies (module_inject/replace_policy.py);
      - a ``(GPTConfig, params)`` tuple of this framework's native GPT;
      - a ``ModelSpec`` with materialized ``params``.
    ``config`` is a DeepSpeedInferenceConfig dict; remaining kwargs merge
    into it (the reference's kwargs-into-config behaviour).
    """
    from .inference.config import DeepSpeedInferenceConfig
    from .inference.engine import InferenceEngine

    cfg_dict = dict(config or {})
    cfg_dict.update(kwargs)
    inf_config = DeepSpeedInferenceConfig.from_dict(cfg_dict)

    from .models import bert as bert_mod
    from .models import gpt as gpt_mod
    if isinstance(model, tuple) and len(model) == 2 \
            and isinstance(model[0], bert_mod.BertConfig):
        from .inference.engine import BertInferenceEngine
        return BertInferenceEngine(model[0], model[1], inf_config,
                                   mesh_manager=get_mesh_manager(optional=True))
    if isinstance(model, tuple) and len(model) == 2 and (
            isinstance(model[0], gpt_mod.GPTConfig)
            # a config class that names its cache family (models/__init__)
            or getattr(model[0], "cache_family", None) is not None):
        model_config, params = model
    elif isinstance(model, ModelSpec):
        assert model.params is not None, \
            "init_inference(ModelSpec) needs materialized params"
        model_config, params = model.meta["config"], model.params
        if isinstance(model_config, bert_mod.BertConfig):
            from .inference.engine import BertInferenceEngine
            return BertInferenceEngine(
                model_config, params, inf_config,
                mesh_manager=get_mesh_manager(optional=True))
    else:
        # generic (diffusers) policies first, matched on the state dict —
        # the reference's generic_policies loop (replace_module.py); a
        # UNet/VAE returns its served wrapper directly
        sd = model if isinstance(model, dict) else (
            model.state_dict() if hasattr(model, "state_dict") else None)
        if sd is not None:
            import jax.numpy as jnp

            from .module_inject.replace_policy import GENERIC_POLICIES
            dtype = inf_config.jnp_dtype
            if dtype == jnp.int8:   # weight-only int8 is LM-path-only
                dtype = jnp.bfloat16
            extra = {k: cfg_dict[k] for k in ("n_head", "groups")
                     if k in cfg_dict}
            for policy in GENERIC_POLICIES:
                if policy.match(sd):
                    return policy.apply(
                        sd, dtype=dtype,
                        enable_cuda_graph=inf_config.enable_cuda_graph,
                        **extra)
            from .module_inject.replace_policy import HFBertLayerPolicy
            # RoBERTa/ELECTRA share BERT's attention key names but not the
            # embeddings layout the converter handles — require the exact
            # BertForMaskedLM/BertModel prefix so unsupported models fall
            # through to the clear "no policy matched" error
            convertible_bert = (
                HFBertLayerPolicy.match(sd) and hasattr(model, "config") and
                ("bert.embeddings.word_embeddings.weight" in sd or
                 "embeddings.word_embeddings.weight" in sd) and
                # task heads (classification/QA) would be silently dropped
                # — only the MLM/encoder surface converts
                not any(k.startswith(("classifier.", "qa_outputs."))
                        for k in sd))
            if convertible_bert:
                from .inference.engine import BertInferenceEngine
                from .module_inject.replace_policy import convert_hf_bert
                bcfg, bparams = convert_hf_bert(model, dtype=dtype)
                return BertInferenceEngine(
                    bcfg, bparams, inf_config,
                    mesh_manager=get_mesh_manager(optional=True))
        from .module_inject import convert_hf_model
        model_config, params = convert_hf_model(
            model, dtype=inf_config.jnp_dtype)
    return InferenceEngine(model_config, params, inf_config,
                           mesh_manager=get_mesh_manager(optional=True))


class OnDevice:
    """Reference ``deepspeed.OnDevice`` parity: a context for constructing
    params with a chosen dtype/placement.  On TPU the real mechanism is
    abstract init (``ModelSpec.init_fn`` under ``jax.eval_shape`` +
    jit-with-out-shardings — no unsharded materialization, see
    ``runtime/engine.py:_init_state``); this context covers ad-hoc array
    construction with ``jax.default_device``."""

    def __init__(self, dtype=None, device: str = "meta", enabled: bool = True):
        if dtype is not None:
            logger.warning(
                "OnDevice(dtype=...) is not honored on TPU — construct "
                "arrays in the target dtype (GPTConfig.param_dtype / "
                "jnp.asarray(..., dtype)) instead")
        self.dtype = dtype
        self.device = device
        self.enabled = enabled
        self._ctx = None

    def __enter__(self):
        import jax
        if not self.enabled:
            return self
        if self.device not in ("meta", None):
            self._ctx = jax.default_device(jax.devices(self.device)[0])
            self._ctx.__enter__()
        return self

    def __exit__(self, *exc):
        if self._ctx is not None:
            self._ctx.__exit__(*exc)
        return False


def add_config_arguments(parser: argparse.ArgumentParser) -> argparse.ArgumentParser:
    """Add --deepspeed / --deepspeed_config args (reference :210)."""
    group = parser.add_argument_group("DeepSpeed", "DeepSpeed configurations")
    group.add_argument("--deepspeed", default=False, action="store_true",
                       help="Enable DeepSpeed (helper flag)")
    group.add_argument("--deepspeed_config", default=None, type=str,
                       help="Path to DeepSpeed json configuration")
    group.add_argument("--deepscale", default=False, action="store_true",
                       help=argparse.SUPPRESS)
    group.add_argument("--deepscale_config", default=None, type=str,
                       help=argparse.SUPPRESS)
    return parser
