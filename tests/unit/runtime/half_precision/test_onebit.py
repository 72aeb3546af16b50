"""1-bit optimizers + compressed allreduce.

Mirrors the reference's ``tests/unit/runtime/half_precision/onebit/``
coverage: warmup-phase equivalence with Adam, convergence in the compressed
phase, and the compressed collective against the exact mean.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import deepspeed_tpu
from deepspeed_tpu.models import gpt
from deepspeed_tpu.parallel.mesh import (ParallelDims, initialize_mesh,
                                         reset_mesh_manager)
from deepspeed_tpu.runtime.model import from_gpt


def test_pack_unpack_roundtrip():
    from deepspeed_tpu.runtime.comm.compressed import pack_signs, unpack_signs
    signs = jax.random.bernoulli(jax.random.PRNGKey(0), shape=(1024,))
    packed = pack_signs(signs)
    assert packed.dtype == jnp.uint8 and packed.shape == (128,)
    np.testing.assert_array_equal(np.asarray(unpack_signs(packed)),
                                  np.asarray(signs))


def test_compressed_allreduce_error_feedback_converges():
    """Error feedback's guarantee: per-round errors stay bounded and the
    running mean of outputs converges to the true value (the sum of applied
    updates telescopes to the sum of true updates ± the bounded error)."""
    from deepspeed_tpu.runtime.comm.compressed import compressed_allreduce_tree
    mm = initialize_mesh(ParallelDims(dp=-1))
    fn = compressed_allreduce_tree(mm.mesh, "data")
    x = {"a": jax.random.normal(jax.random.PRNGKey(1), (1000,)),
         "b": jax.random.normal(jax.random.PRNGKey(2), (3, 17))}
    n = fn.flat_size(x)
    we = jnp.zeros((n,), jnp.float32)
    se = jnp.zeros((n,), jnp.float32)
    acc = {k: jnp.zeros_like(v) for k, v in x.items()}
    mean_errs = {}
    for t in range(1, 41):
        # one round in flight at a time: XLA:CPU's in-process all-to-all
        # needs all eight participants on a pool thread at once, and with
        # 32 unfenced rounds queued on a busy host the eighth of some round
        # never gets one (rendezvous: "only 7 of them arrived", the worker
        # aborts after 40 s)
        out, we, se = jax.block_until_ready(fn(x, we, se))
        acc = {k: acc[k] + out[k] for k in x}
        if t in (8, 40):
            mean_errs[t] = max(float(jnp.max(jnp.abs(acc[k] / t - x[k])))
                               for k in x)
    # the running mean of applied values approaches x (error feedback's
    # telescoping); sign compression with one global scale converges slowly
    # on heavy-tailed inputs, so assert monotone improvement, not a bound
    assert mean_errs[40] < 0.75 * mean_errs[8], mean_errs


def test_onebit_adam_warmup_matches_adam():
    """Before freeze_step the trajectories of OnebitAdam and FusedAdam are
    identical (reference warmup semantics)."""
    from deepspeed_tpu.ops.adam.fused_adam import FusedAdam
    from deepspeed_tpu.runtime.fp16.onebit import OnebitAdam

    params = {"w": jax.random.normal(jax.random.PRNGKey(3), (64,)),
              "b": jnp.zeros((8,))}
    grads = [{"w": jax.random.normal(jax.random.PRNGKey(i), (64,)),
              "b": jnp.ones((8,)) * 0.1} for i in range(4)]
    hyper = {"lr": jnp.float32(1e-2), "weight_decay": jnp.float32(0.0)}

    ob = OnebitAdam(freeze_step=100)
    ad = FusedAdam(adam_w_mode=True)
    p1, s1 = dict(params), ob.init(params)
    p2, s2 = dict(params), ad.init(params)
    for g in grads:
        p1, s1 = ob.update(g, s1, p1, hyper)
        p2, s2 = ad.update(g, s2, p2, hyper)
    np.testing.assert_allclose(np.asarray(p1["w"]), np.asarray(p2["w"]),
                               atol=1e-6)


def test_onebit_adam_compressed_phase_converges():
    """Past freeze_step: 1-bit quantized momentum still minimizes a convex
    objective (error feedback keeps the updates unbiased)."""
    from deepspeed_tpu.runtime.fp16.onebit import OnebitAdam
    target = jax.random.normal(jax.random.PRNGKey(4), (128,))
    initial = float(jnp.linalg.norm(target))
    params = {"w": jnp.zeros((128,))}
    opt = OnebitAdam(freeze_step=30)
    state = opt.init(params)
    hyper = {"lr": jnp.float32(0.05), "weight_decay": jnp.float32(0.0)}

    @jax.jit
    def step(params, state):
        g = {"w": params["w"] - target}
        return opt.update(g, state, params, hyper)

    dists = []
    for _ in range(150):
        params, state = step(params, state)
        dists.append(float(jnp.linalg.norm(params["w"] - target)))
    # compressed phase drives well into the optimum's neighborhood; a
    # single "worker" then random-walks there (multi-worker averaging is
    # what tightens it), so assert descent + boundedness, not a fixed point
    assert min(dists) < 0.15 * initial, (min(dists), initial)
    assert dists[-1] < initial, (dists[-1], initial)
    assert np.isfinite(dists).all()
    assert int(state["step"]) == 150


@pytest.mark.parametrize("name", ["OnebitAdam", "OnebitLamb", "ZeroOneAdam"])
@pytest.mark.slow
def test_onebit_engine_training(name):
    """Engine-level: each 1-bit optimizer trains tiny GPT, loss decreases."""
    reset_mesh_manager()
    mm = initialize_mesh(ParallelDims(dp=-1))
    cfg = gpt.GPTConfig(vocab_size=128, max_seq_len=32, n_layer=1, n_head=2,
                        d_model=32, dtype=jnp.float32)
    extra = {"freeze_step": 2} if name != "ZeroOneAdam" else \
        {"var_freeze_step": 4, "var_update_scaler": 2}
    ds = {"train_micro_batch_size_per_gpu": 1,
          "gradient_accumulation_steps": 1,
          "optimizer": {"type": name, "params": {"lr": 1e-3, **extra}},
          "zero_optimization": {"stage": 1},
          "steps_per_print": 1 << 30}
    engine, _, _, _ = deepspeed_tpu.initialize(
        model=from_gpt(cfg), config=ds, mesh_manager=mm,
        rng=jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    batch = {"tokens": rng.integers(0, 128, size=(8, 33)).astype(np.int32)}
    losses = []
    for _ in range(6):
        loss = engine.forward(batch)
        engine.backward()
        engine.step()
        losses.append(float(jax.device_get(loss)))
    assert all(np.isfinite(losses))
    assert losses[-1] < losses[0], losses
    reset_mesh_manager()
