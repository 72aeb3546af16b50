"""GPT with Mixture-of-Experts FFNs (DeepSpeed-MoE style).

Model family for the MoE benchmark config (BASELINE.md: 350M×64-expert).
Follows DeepSpeed-MoE's architecture: every other transformer layer replaces
its dense FFN with an expert layer (reference ``deepspeed/moe/layer.py`` used
this way in Megatron-DeepSpeed).  Layers are stacked in *pairs*
(dense block, MoE block) and scanned, so compile time stays O(1) in depth and
the expert dim shards over the ``expert`` mesh axis.

The gate's auxiliary load-balance loss is accumulated through the scan and
returned next to the LM loss (reference ``l_aux``, sharded_moe.py:209).
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import NamedSharding, PartitionSpec as P

from ..moe.layer import MoE
from .gpt import GPTConfig, _attn_residual, _block, _layer_norm
from .partitioning import EMBED, HEADS, KV, LAYERS, MLP, SEQ, VOCAB

PyTree = Any


@dataclasses.dataclass(frozen=True)
class GPTMoEConfig(GPTConfig):
    #: the module whose ``FAMILY`` ``models.cache_family`` serves this
    #: config through
    cache_family = "gpt_moe_inference"

    num_experts: int = 8
    moe_top_k: int = 1
    capacity_factor: float = 1.25
    eval_capacity_factor: float = 2.0
    min_capacity: int = 4
    aux_loss_coef: float = 0.01
    use_residual: bool = False
    ep_size: int = 1

    def __post_init__(self):
        super().__post_init__()
        assert self.n_layer % 2 == 0, "GPT-MoE requires an even layer count"

    @property
    def n_pairs(self) -> int:
        return self.n_layer // 2


# 350M×64e preset from BASELINE.md (DeepSpeed-MoE paper's small config)
GPT_MOE_350M_64E = GPTMoEConfig(n_layer=24, n_head=16, d_model=1024,
                                num_experts=64, moe_top_k=1)


def _moe_obj(config: GPTMoEConfig, drop_tokens: bool = True) -> MoE:
    return MoE(hidden_size=config.d_model, num_experts=config.num_experts,
               ep_size=config.ep_size, k=config.moe_top_k,
               capacity_factor=config.capacity_factor,
               eval_capacity_factor=config.eval_capacity_factor,
               min_capacity=config.min_capacity,
               use_residual=config.use_residual,
               drop_tokens=drop_tokens,
               # deterministic gating by default: rng plumbing through scan is
               # opt-in (use_rts needs a per-layer key)
               use_rts=False)


def _as_gpt_config(config: GPTMoEConfig, n_layer: int) -> GPTConfig:
    base = GPTConfig(**{f.name: getattr(config, f.name)
                        for f in dataclasses.fields(GPTConfig)})
    return dataclasses.replace(base, n_layer=n_layer)


def _dense_block_init(rng, config: GPTMoEConfig, n_stack: int):
    from .gpt import init as gpt_init
    full = gpt_init(_as_gpt_config(config, n_stack), rng)
    return full["blocks"]


def init(config: GPTMoEConfig, rng: jax.Array) -> PyTree:
    kd, km, ke, kt = jax.random.split(rng, 4)
    n_pairs = config.n_pairs
    moe = _moe_obj(config)

    dense_blocks = _dense_block_init(kd, config, n_pairs)
    moe_attn_blocks = _dense_block_init(km, config, n_pairs)
    # drop the dense FFN weights from the MoE half-block; keep attn + both LNs
    for k in ("wi", "bi", "wo_mlp", "bo_mlp"):
        moe_attn_blocks.pop(k)

    expert_keys = jax.random.split(ke, n_pairs)
    moe_stack = jax.vmap(lambda k: moe.init(k, dtype=config.param_dtype))(expert_keys)

    from .gpt import init as gpt_init
    outer = gpt_init(_as_gpt_config(config, 1), kt)
    return {
        "wte": outer["wte"],
        "wpe": outer["wpe"],
        "dense_blocks": dense_blocks,
        "moe_attn_blocks": moe_attn_blocks,
        "moe_blocks": moe_stack,
        "lnf_scale": outer["lnf_scale"],
        "lnf_bias": outer["lnf_bias"],
    }


def logical_axes(config: GPTMoEConfig) -> PyTree:
    from .gpt import logical_axes as gpt_axes
    base = gpt_axes(config)
    moe = _moe_obj(config)
    moe_axes = moe.logical_axes()

    def stack_axes(tree):
        return jax.tree_util.tree_map(
            lambda axes: (LAYERS,) + tuple(axes), tree,
            is_leaf=lambda x: isinstance(x, tuple) and all(
                a is None or isinstance(a, str) for a in x))

    attn_axes = dict(base["blocks"])
    for k in ("wi", "bi", "wo_mlp", "bo_mlp"):
        attn_axes.pop(k)
    return {
        "wte": base["wte"],
        "wpe": base["wpe"],
        "dense_blocks": base["blocks"],
        "moe_attn_blocks": attn_axes,
        "moe_blocks": stack_axes(moe_axes),
        "lnf_scale": base["lnf_scale"],
        "lnf_bias": base["lnf_bias"],
    }


def _moe_half_block(x, attn_p, moe_p, moe: MoE, config: GPTMoEConfig,
                    train: bool, constrain):
    """Transformer block whose FFN is the expert layer."""
    x = _attn_residual(x, attn_p, config)
    h2 = _layer_norm(x, attn_p["ln2_scale"], attn_p["ln2_bias"])
    moe_out, l_aux, _counts = moe.apply(moe_p, h2, train=train, constrain=constrain)
    return x + moe_out, l_aux


def apply(params: PyTree, tokens: jnp.ndarray, config: GPTMoEConfig,
          train: bool = True) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """tokens [B,S] → (logits [B,S,V] fp32, total aux loss)."""
    cdt = config.dtype
    moe = _moe_obj(config)
    B, S = tokens.shape
    pos = jnp.arange(S)
    x = params["wte"].astype(cdt)[tokens] + params["wpe"].astype(cdt)[pos][None]

    # Sharding: expert params are sharded over the expert axis, so XLA's
    # propagation already reshards dispatch/combine (the all-to-all).  Explicit
    # constraints (P(EXPERT, DATA, None)) can be threaded here for manual
    # tuning; None lets the partitioner choose.
    constrain_fn = None

    dense_fn = partial(_block, config=config)
    moe_fn = partial(_moe_half_block, moe=moe, config=config, train=train,
                     constrain=constrain_fn)
    if config.remat:
        dense_fn = jax.checkpoint(dense_fn)
        moe_fn = jax.checkpoint(moe_fn, static_argnums=())

    def pair_body(carry, pair_params):
        x, aux = carry
        dense_p, attn_p, moe_p = pair_params
        x = dense_fn(x, dense_p)
        x, l_aux = moe_fn(x, attn_p, moe_p)
        return (x, aux + l_aux), None

    (x, aux_total), _ = lax.scan(
        pair_body, (x, jnp.zeros((), jnp.float32)),
        (params["dense_blocks"], params["moe_attn_blocks"], params["moe_blocks"]))

    x = _layer_norm(x, params["lnf_scale"], params["lnf_bias"])
    # bf16 MXU inputs, fp32 accumulation (see gpt.lm_logits)
    logits = jnp.einsum("bsd,vd->bsv", x.astype(cdt),
                        params["wte"].astype(cdt),
                        preferred_element_type=jnp.float32)
    return logits, aux_total


def loss_fn(params: PyTree, batch: Dict[str, jnp.ndarray],
            config: GPTMoEConfig, train: bool = True) -> jnp.ndarray:
    tokens = batch["tokens"]
    inputs, targets = tokens[:, :-1], tokens[:, 1:]
    logits, aux = apply(params, inputs, config, train=train)
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, targets[..., None], axis=-1)[..., 0]
    nll = logz - gold
    mask = (targets >= 0).astype(jnp.float32)
    lm_loss = jnp.sum(nll * mask) / jnp.maximum(jnp.sum(mask), 1.0)
    return lm_loss + config.aux_loss_coef * aux


def model_spec(config: GPTMoEConfig):
    from ..runtime.model import ModelSpec
    return ModelSpec(
        loss_fn=lambda p, b: loss_fn(p, b, config),
        init_fn=lambda rng: init(config, rng),
        logical_axes=logical_axes(config),
        apply_fn=lambda p, t: apply(p, t, config, train=False)[0],
        name="gpt-moe",
        meta={"config": config},
    )
