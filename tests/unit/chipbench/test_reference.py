"""The plain reference against the program's own forward pass at a tiny
size, for both configurations, and the tolerance that decides ``correct``."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.chip import builders, flops
from benchmarks.chip.reference import compare, gpt_reference
from deepspeed_tpu.models import gpt

from .common import ROOT, benchmark, tiny_config

CONFIGS = {c["name"]: c for c in benchmark()["configs"]}


def tiny(name):
    with open(os.path.join(ROOT, CONFIGS[name]["file"])) as f:
        file = {**json.load(f), **tiny_config(name)}
    return file, builders.resolve(file["builder"])(file)


@pytest.mark.parametrize("name", sorted(CONFIGS))
def test_reference_is_the_programs_forward_in_float32(name):
    file, cfg = tiny(name)
    cfg32 = cfg.__class__(**{**cfg.__dict__, "dtype": jnp.float32,
                             "use_flash_attention": False})
    params = gpt.init(cfg32, jax.random.PRNGKey(3))
    tokens = np.random.default_rng(0).integers(
        0, cfg.vocab_size, (2, 48)).astype(np.int32)
    with jax.default_matmul_precision("highest"):
        system = gpt.apply(params, tokens, cfg32)[:, -16:, :cfg.vocab_size]
    ref = gpt_reference.forward(file, params, tokens, 16)
    assert ref.shape == (2, 16, cfg.vocab_size) and ref.dtype == jnp.float32
    assert compare.relative_error(system, ref) < 1e-5


@pytest.mark.parametrize("name", sorted(CONFIGS))
def test_bf16_passes_and_a_left_out_term_fails(name):
    file, cfg = tiny(name)
    params = gpt.init(cfg, jax.random.PRNGKey(3))
    tokens = np.random.default_rng(0).integers(
        0, cfg.vocab_size, (2, 48)).astype(np.int32)
    ref = gpt_reference.forward(file, params, tokens, 16)
    bf16 = cfg.__class__(**{**cfg.__dict__, "dtype": jnp.bfloat16})
    system = gpt.apply(params, tokens, bf16)[:, -16:, :cfg.vocab_size]
    assert compare.agrees(system, ref)
    # the position embedding left out, or the activation swapped
    nopos = dict(params, wpe=jnp.zeros_like(params["wpe"]))
    assert not compare.agrees(
        gpt.apply(nopos, tokens, bf16)[:, -16:, :cfg.vocab_size], ref)
    other = cfg.__class__(**{**bf16.__dict__, "activation": (
        "relu" if cfg.activation == "gelu" else "gelu")})
    assert not compare.agrees(
        gpt.apply(params, tokens, other)[:, -16:, :cfg.vocab_size], ref)


def test_opt_positions_are_offset():
    file, cfg = tiny("opt-1.3b")
    assert cfg.pos_offset == 2 and cfg.activation == "relu"
    params = gpt.init(cfg, jax.random.PRNGKey(0))
    assert params["wpe"].shape[0] == cfg.max_seq_len + 2
    tokens = np.arange(24, dtype=np.int32)[None]
    ref = gpt_reference.forward(file, params, tokens, 4)
    wrong = gpt_reference.forward({**file, "position_offset": 0}, params,
                                  tokens, 4)
    assert not compare.agrees(wrong, ref)


def test_compare():
    ref = np.asarray([[1.0, -4.0], [2.0, 0.5]])
    assert compare.relative_error(ref, ref) == 0
    assert compare.relative_error(ref + 0.04, ref) == pytest.approx(0.01)
    assert not compare.agrees(ref * np.nan, ref)
    with pytest.raises(ValueError):
        compare.relative_error(ref[:1], ref)


def test_an_even_loss_of_precision_fails_on_the_rms_error():
    """Noise spread evenly over a million logits stays under the largest
    logit's tolerance long after it has passed the root-mean-square one."""
    rng = np.random.default_rng(0)
    ref = rng.standard_normal((20, 50000))
    noisy = ref + 1.2 * compare.RMS_TOLERANCE * rng.standard_normal(ref.shape)
    assert compare.relative_error(noisy, ref) < compare.TOLERANCE
    assert compare.rms_error(noisy, ref) > compare.RMS_TOLERANCE
    assert not compare.agrees(noisy, ref)
    assert compare.agrees(
        ref + 0.5 * compare.RMS_TOLERANCE * rng.standard_normal(ref.shape),
        ref)


@pytest.mark.parametrize("name", sorted(CONFIGS))
def test_required_operations_per_token_are_the_programs(name):
    with open(os.path.join(ROOT, CONFIGS[name]["file"])) as f:
        file = json.load(f)
    cfg = builders.resolve(file["builder"])(file)
    z = {**gpt_reference.sizes(file), "d_ff": cfg.ffn_dim}
    assert flops.train_flops_per_token(
        z, cfg.max_seq_len, cfg.padded_vocab) == pytest.approx(
        gpt.flops_per_token(cfg), rel=1e-12)


def test_kernel_operations_from_shapes():
    fwd = "(bf16[384,1024,64], f32[384,1,1024])"
    ops, nbytes = flops.flash_call(fwd)
    assert ops == 4 * 384 * 1024 * 1024 * 64 / 2
    assert nbytes == 4 * 384 * 1024 * 64 * 2 + 4 * 384 * 1024
    bwd = "(bf16[384,1024,64], bf16[384,1024,64], f32[384,4,1024,64])"
    assert flops.flash_call(bwd)[0] == 2.5 * ops
    assert flops.flash_call("(bf16[384,1024,64], bf16[384,1024,64])")[0] \
        == 2.5 * ops
    assert flops.flash_call("bf16[384,1024,64]") == (0.0, 0.0)   # dq alone
    assert flops.flash_call(fwd, causal=False)[0] == 2 * ops
    assert flops.decode_call(1000, 16, 64) == (4.0 * 1000 * 1024,
                                               2.0 * 1000 * 1024 * 2)
