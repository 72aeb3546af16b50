"""Cross-process ENGINE training (VERDICT r2 items 4 & 8): two OS processes
x two CPU devices each run a real ``deepspeed_tpu.initialize`` +
forward/backward/step — once on the device optimizer path (ZeRO-2) and once
with ``offload_optimizer`` (per-rank host masters stepping only the
process's addressable shards, the reference's per-rank cpu_offload in
``stage_1_and_2.py:98``).  Losses must match a single-process run of the
same global batch to fp32 tolerance.

Mirrors the reference's DistributedTest semantics (tests/unit/common.py:66).
"""

import json
import os
import socket
import subprocess
import sys

import numpy as np
import pytest

pytestmark = pytest.mark.slow

import jax

REPO_ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__),
                                         "..", "..", ".."))

_WORKER = r"""
import json, os
from deepspeed_tpu.utils.platform import force_cpu_platform
force_cpu_platform(n_devices=2, persistent_cache=False)
import jax
import numpy as np

import deepspeed_tpu
from deepspeed_tpu.comm import comm as dist

dist.init_distributed()   # WORLD_SIZE/RANK/MASTER_* from env

import jax.numpy as jnp
from deepspeed_tpu.models import gpt
from deepspeed_tpu.parallel.mesh import (ParallelDims, initialize_mesh,
                                         reset_mesh_manager)
from deepspeed_tpu.runtime.model import from_gpt

CFG = gpt.GPTConfig(vocab_size=256, max_seq_len=64, n_layer=2, n_head=2,
                    d_model=64, dtype=jnp.float32)


def run(offload, tp=False):
    # With tp: dp=2 x tp=2 over 4 devices in 2 processes, the device order
    # arranged so every `model` (TP) group SPANS the process boundary --
    # the layout a real pod slice runs on every layer (VERDICT r3 #3).
    # XLA inserts the TP collectives across the process link inside one
    # SPMD program.
    reset_mesh_manager()
    if tp:
        by_proc = sorted(jax.devices(), key=lambda d: (d.process_index, d.id))
        # flat order is filled (data, model)-major with model fastest, so
        # interleaving processes makes each model pair cross-process
        order = [by_proc[0], by_proc[2], by_proc[1], by_proc[3]]
        mm = initialize_mesh(ParallelDims(dp=-1, tp=2), devices=order)
        for pair in mm.mesh.devices.reshape(-1, 2):  # [dp, model]
            assert {d.process_index for d in pair} == {0, 1}, (
                "model group does not cross the process boundary: %s" % pair)
    else:
        mm = initialize_mesh(ParallelDims(dp=-1))
    # micro x dp -> global batch 8 either way
    ds = {"train_micro_batch_size_per_gpu": 8 // mm.dp_world_size,
          "gradient_accumulation_steps": 1,
          "optimizer": {"type": "Adam", "params": {"lr": 1e-3}},
          "zero_optimization": {"stage": 2},
          "steps_per_print": 1 << 30}
    if tp:
        ds["tensor_parallel"] = {"enabled": True, "size": 2}
    if offload:
        ds["zero_optimization"]["offload_optimizer"] = {"device": "cpu"}
    engine, _, _, _ = deepspeed_tpu.initialize(
        model=from_gpt(CFG), config=ds, mesh_manager=mm,
        rng=jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    batch = {"tokens": rng.integers(0, 256, size=(8, 65)).astype(np.int32)}
    losses = []
    for _ in range(2):
        loss = engine.forward(batch)
        engine.backward()
        engine.step()
        losses.append(float(jax.device_get(loss)))
    return losses


def run_tp_serving():
    # TP-sharded INFERENCE with model groups spanning the processes: the
    # served logits must match a single-process engine on the same
    # weights (SPMD makes the process boundary invisible to serving too)
    reset_mesh_manager()
    by_proc = sorted(jax.devices(), key=lambda d: (d.process_index, d.id))
    order = [by_proc[0], by_proc[2], by_proc[1], by_proc[3]]
    mm = initialize_mesh(ParallelDims(dp=-1, tp=2), devices=order)
    for pair in mm.mesh.devices.reshape(-1, 2):
        assert {d.process_index for d in pair} == {0, 1}
    from deepspeed_tpu.models import gpt as gm
    params = gm.init(CFG, jax.random.PRNGKey(5))
    eng = deepspeed_tpu.init_inference(
        model=(CFG, params),
        config={"dtype": "float32", "tensor_parallel": {"tp_size": 2}},
        mesh_manager=mm)
    toks = np.random.default_rng(5).integers(0, 256, size=(2, 16))
    out = eng.forward(toks)
    # logits stay vocab-sharded over the model axis and the halves live
    # on DIFFERENT processes — report this process's half + its offset
    shard = next(s for s in out.addressable_shards)
    lg = np.asarray(shard.data, np.float32)
    v0 = shard.index[-1].start or 0
    return {"vocab_start": int(v0), "vocab_len": int(lg.shape[-1]),
            "mean": float(lg.mean()), "std": float(lg.std()),
            "slice": lg[:, :2, :8].tolist()}


out = {"rank": dist.get_rank(),
       "n_global_devices": jax.device_count(),
       "device": run(offload=False),
       "offload": run(offload=True),
       "tp_device": run(offload=False, tp=True),
       "tp_offload": run(offload=True, tp=True),
       "tp_serving": run_tp_serving()}
with open(os.environ["PROBE_OUT"], "w") as f:
    json.dump(out, f)
"""


def _free_port() -> int:
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def _single_process_reference() -> list:
    """The same global batch through the in-process engine (dp over the
    conftest's virtual devices); ZeRO math is dp-extent-invariant in fp32."""
    import deepspeed_tpu
    import jax.numpy as jnp
    from deepspeed_tpu.models import gpt
    from deepspeed_tpu.parallel.mesh import (ParallelDims, initialize_mesh,
                                             reset_mesh_manager)
    from deepspeed_tpu.runtime.model import from_gpt

    reset_mesh_manager()
    cfg = gpt.GPTConfig(vocab_size=256, max_seq_len=64, n_layer=2, n_head=2,
                        d_model=64, dtype=jnp.float32)
    ds = {"train_micro_batch_size_per_gpu": 1,   # x dp=8 -> global batch 8
          "gradient_accumulation_steps": 1,
          "optimizer": {"type": "Adam", "params": {"lr": 1e-3}},
          "zero_optimization": {"stage": 2},
          "steps_per_print": 1 << 30}
    mm = initialize_mesh(ParallelDims(dp=-1))
    engine, _, _, _ = deepspeed_tpu.initialize(
        model=from_gpt(cfg), config=ds, mesh_manager=mm,
        rng=jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    batch = {"tokens": rng.integers(0, 256, size=(8, 65)).astype(np.int32)}
    losses = []
    for _ in range(2):
        loss = engine.forward(batch)
        engine.backward()
        engine.step()
        losses.append(float(jax.device_get(loss)))
    return losses


def _serving_reference() -> np.ndarray:
    """Single-process TP-less serving of the same weights/tokens: the
    full [2, 16, padded_vocab] logits."""
    import deepspeed_tpu
    import jax.numpy as jnp
    from deepspeed_tpu.models import gpt
    from deepspeed_tpu.parallel.mesh import reset_mesh_manager

    reset_mesh_manager()
    cfg = gpt.GPTConfig(vocab_size=256, max_seq_len=64, n_layer=2, n_head=2,
                        d_model=64, dtype=jnp.float32)
    params = gpt.init(cfg, jax.random.PRNGKey(5))
    eng = deepspeed_tpu.init_inference(model=(cfg, params),
                                       config={"dtype": "float32"})
    toks = np.random.default_rng(5).integers(0, 256, size=(2, 16))
    return np.asarray(jax.device_get(eng.forward(toks)), np.float32)


def test_two_process_engine_train_step(tmp_path):
    from deepspeed_tpu.ops.op_builder import get_builder
    if not get_builder("cpu_adam").is_compatible():
        pytest.skip("no C++ toolchain for native ops")
    get_builder("cpu_adam").load()  # pre-build: workers reuse the cache

    port = _free_port()
    script = tmp_path / "worker.py"
    script.write_text(_WORKER)
    procs = []
    for rank in range(2):
        env = {**os.environ,
               "PYTHONPATH": REPO_ROOT,
               "WORLD_SIZE": "2", "RANK": str(rank), "LOCAL_RANK": "0",
               "MASTER_ADDR": "127.0.0.1", "MASTER_PORT": str(port),
               "PROBE_OUT": str(tmp_path / f"out{rank}.json")}
        procs.append(subprocess.Popen([sys.executable, str(script)],
                                      env=env, stdout=subprocess.PIPE,
                                      stderr=subprocess.PIPE, text=True))
    expect = _single_process_reference()  # compiles while workers run
    for rank, p in enumerate(procs):
        try:
            out, err = p.communicate(timeout=600)
        except subprocess.TimeoutExpired:
            for q in procs:
                q.kill()
            pytest.fail(f"rank {rank} hung")
        assert p.returncode == 0, f"rank {rank} failed:\n{err[-3000:]}"
    results = [json.load(open(tmp_path / f"out{r}.json")) for r in range(2)]
    for res in results:
        assert res["n_global_devices"] == 4
        # the device-optimizer path must match single-process bit-for-bit
        # up to fp32 reduction-order noise
        np.testing.assert_allclose(res["device"], expect, rtol=1e-5)
        # per-rank host Adam (native SIMD kernel) tracks the device Adam
        np.testing.assert_allclose(res["offload"], expect, rtol=3e-4)
        # TP groups spanning the process boundary: same math, the
        # collectives merely ride the cross-process link (VERDICT r3 #3)
        np.testing.assert_allclose(res["tp_device"], expect, rtol=1e-5)
        np.testing.assert_allclose(res["tp_offload"], expect, rtol=3e-4)
        # TP-sharded SERVING across the boundary matches single-process:
        # each process holds one vocab half of the logits — compare it
        # against the same slice of the unsharded reference
        serve_expect = _serving_reference()
        sv = res["tp_serving"]
        v0, vl = sv["vocab_start"], sv["vocab_len"]
        ref_half = serve_expect[:, :, v0:v0 + vl]
        np.testing.assert_allclose(sv["mean"], ref_half.mean(), rtol=1e-4)
        np.testing.assert_allclose(sv["std"], ref_half.std(), rtol=1e-4)
        np.testing.assert_allclose(sv["slice"], ref_half[:, :2, :8],
                                   atol=1e-4, rtol=1e-4)
    # both ranks observed identical losses (replicated scalar) on every path
    for key in ("device", "offload", "tp_device", "tp_offload"):
        np.testing.assert_allclose(results[0][key], results[1][key],
                                   rtol=1e-7, err_msg=key)
