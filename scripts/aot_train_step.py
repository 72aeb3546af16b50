#!/usr/bin/env python3
"""Compile the engine's own fused train step for a described v5e host.

No chip is needed or used: the TPU's compiler runs here against a
*described* ``v5e:2x2`` topology (``on-chip-measurement`` guide, section
2), so what it refuses (a program that does not fit 16 GB, a kernel Mosaic
rejects, a sharding that does not partition) shows up before any chip
time is spent.  Nothing runs; the numbers printed are the compiler's
``memory_analysis()`` per device, never a measurement.

The engine is built through ``deepspeed_tpu.initialize`` on a mesh of the
described devices.  Those hold no arrays, so its state is materialized as
shapes: for the length of ``_init_state`` the two calls that would place
arrays (``jax.jit(init, out_shardings=...)`` and ``jax.device_put``) are
replaced by shape-only stand-ins.  Everything after that — the partitioner's
specs, ``_build_steps``, the registered ``fused`` jit — is the engine's own.

Usage:
    python scripts/aot_train_step.py --micro-batch 16
    python scripts/aot_train_step.py --chips 4 --tp 2 --zero 3 --micro-batch 4
"""

from __future__ import annotations

import argparse
import contextlib
import dataclasses
import importlib
import json
import os
import sys
import time

os.environ.setdefault("TPU_LOG_DIR", "disabled")  # or libtpu logs under /tmp
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

import chip_smoke
import deepspeed_tpu
from deepspeed_tpu.models import bert, gpt
from deepspeed_tpu.parallel.mesh import (DP_GROUP, ParallelDims,
                                         initialize_mesh)
from deepspeed_tpu.runtime.engine import DeepSpeedEngine
from deepspeed_tpu.runtime.model import from_gpt


def _shapes_of(tree, shardings):
    return jax.tree_util.tree_map(
        lambda x, sh: jax.ShapeDtypeStruct(jnp.shape(x), jnp.result_type(x),
                                           sharding=sh), tree, shardings)


@contextlib.contextmanager
def _abstract_state():
    """Within the block ``DeepSpeedEngine._init_state`` produces
    ``ShapeDtypeStruct`` state instead of arrays."""
    real_init = DeepSpeedEngine._init_state

    def shape_jit(fn, out_shardings=None, **_):
        return lambda *args: _shapes_of(jax.eval_shape(fn, *args),
                                        out_shardings)

    def shape_put(tree, sharding):
        return _shapes_of(tree, jax.tree_util.tree_map(lambda _: sharding,
                                                       tree))

    def init_state(self, rng):
        real_jit, real_put = jax.jit, jax.device_put
        jax.jit, jax.device_put = shape_jit, shape_put
        try:
            real_init(self, rng)
        finally:
            jax.jit, jax.device_put = real_jit, real_put

    DeepSpeedEngine._init_state = init_state
    try:
        yield
    finally:
        DeepSpeedEngine._init_state = real_init


def compile_step(config, micro_batch: int, *, chips: int = 1, tp: int = 1,
                 zero_stage: int = 1, gas: int = 1):
    """``jax.stages.Compiled`` of the fused step of ``config`` (a GPT or a
    BERT config) on ``chips`` described v5e devices with
    ``ParallelDims(dp=chips // tp, tp=tp)``."""
    from jax.experimental import topologies
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    mm = initialize_mesh(ParallelDims(dp=-1, tp=tp),
                         devices=topo.devices[:chips])
    ds_config = {**chip_smoke.ds_config(micro_batch, zero_stage, tp=tp),
                 "gradient_accumulation_steps": gas}
    is_bert = isinstance(config, bert.BertConfig)
    with _abstract_state():
        engine, _, _, _ = deepspeed_tpu.initialize(
            model=bert.model_spec(config) if is_bert else from_gpt(config),
            config=ds_config, mesh_manager=mm, rng=jax.random.PRNGKey(0))
    s = engine.state
    rows = micro_batch * mm.dp_world_size

    def tokens(width):
        return jax.ShapeDtypeStruct(
            (gas, rows, width), jnp.int32,
            sharding=NamedSharding(mm.mesh, P(None, DP_GROUP)))

    if is_bert:   # the batches each model's train step takes
        batches = {"tokens": tokens(config.max_seq_len),
                   "mlm_labels": tokens(config.max_seq_len)}
    else:
        batches = {"tokens": tokens(config.max_seq_len + 1)}
    hyper = _shapes_of(engine._hyper(), jax.tree_util.tree_map(
        lambda _: mm.replicated(), engine._hyper()))
    if engine._separate_master:
        lowered = engine._fused_jit.lower(
            s["params"], s["master"], s["opt_state"], s["grad_acc"],
            s["scale"], batches, hyper)
    else:
        lowered = engine._fused_jit_single.lower(
            s["params"], s["opt_state"], s["grad_acc"], s["scale"], batches,
            hyper)
    return lowered.compile()


def report(compiled) -> dict:
    ma = compiled.memory_analysis()
    text = compiled.as_text()
    return {
        # the compiler's own peak for this program on one device, arguments
        # included: what it holds against the chip's 15.75 GiB of HBM
        "peak_bytes_per_device": ma.peak_memory_in_bytes,
        "argument_bytes": ma.argument_size_in_bytes,
        "output_bytes": ma.output_size_in_bytes,
        "alias_bytes": ma.alias_size_in_bytes,
        "temp_bytes": ma.temp_size_in_bytes,
        "code_bytes": ma.generated_code_size_in_bytes,
        "tpu_custom_calls": text.count("tpu_custom_call"),
        "all_gather": text.count(" all-gather("),
        "reduce_scatter": text.count(" reduce-scatter("),
        "all_reduce": text.count(" all-reduce("),
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--model", default="gpt2-350m",
                    choices=sorted(gpt.PRESETS) + ["bert-large"])
    ap.add_argument("--micro-batch", type=int, required=True)
    ap.add_argument("--chips", type=int, default=1, choices=(1, 2, 4))
    ap.add_argument("--tp", type=int, default=1)
    ap.add_argument("--zero", type=int, default=1, choices=(0, 1, 2, 3))
    ap.add_argument("--gas", type=int, default=1)
    ap.add_argument("--remat-policy", default="attn_out",
                    choices=("nothing", "dots", "attn_out"))
    args = ap.parse_args(argv)
    if jax.default_backend() != "cpu":
        raise SystemExit("run with JAX_PLATFORMS=cpu: this compiles for a "
                         "described chip and must not attach a real one")
    if args.model == "bert-large":   # BERT-large at seq 128, remat on
        config = dataclasses.replace(bert.BERT_LARGE, max_seq_len=128,
                                     dtype=jnp.bfloat16, remat=True)
    else:                            # as chip_smoke.py's train phase does
        config = dataclasses.replace(
            gpt.PRESETS[args.model], max_seq_len=1024, dtype=jnp.bfloat16,
            remat=True, remat_policy=args.remat_policy)
    # the kernel entries ask jax.default_backend(), which is the CPU here
    fa = importlib.import_module("deepspeed_tpu.ops.pallas.flash_attention")
    fa.use_pallas = lambda: True
    fa.interpret_mode = lambda: False
    t0 = time.time()
    compiled = compile_step(config, args.micro_batch, chips=args.chips,
                            tp=args.tp, zero_stage=args.zero, gas=args.gas)
    out = {"model": args.model, "micro_batch": args.micro_batch,
           "chips": args.chips, "tp": args.tp, "zero": args.zero,
           "gas": args.gas, "remat_policy": args.remat_policy,
           "compile_s": round(time.time() - t0, 1),
           "compiled_for": "described v5e:2x2 (not a chip run)",
           **report(compiled)}
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
