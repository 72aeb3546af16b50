"""chip_smoke.py's control flow at a tiny size on the CPU mesh: the phases
are what the driver runs on the chip after every PR, so a change that breaks
them (an entry point renamed, a snapshot key dropped) should fail here
first.  Kernel presence is the one check left to the chip: interpret mode
compiles no Mosaic kernel."""

import jax
import jax.numpy as jnp

import chip_smoke
from deepspeed_tpu.models import gpt
from deepspeed_tpu.parallel.mesh import ParallelDims

TINY = gpt.GPTConfig(vocab_size=512, max_seq_len=128, n_layer=2, n_head=4,
                     d_model=64, dtype=jnp.bfloat16)


def test_train_phase(capsys):
    chip_smoke.train_phase(TINY, 2, 0, jax.devices()[0], kernels=False)
    assert "[train] " in capsys.readouterr().out


def test_serve_phase(capsys):
    chip_smoke.serve_phase(
        TINY, 0, kernels=False, prompt_lens=(5, 70, 13, 4, 51, 25, 9, 33),
        new_tokens=8, follow_up_len=6, slots=2, prefill_chunk=16)
    out = capsys.readouterr().out
    assert "requests=10 completed=10 readmits=2" in out, out


def test_sharded_phase_dp2_tp2(capsys):
    chip_smoke.sharded_phase(TINY, 2, 0, jax.devices()[:4],
                             ParallelDims(dp=2, tp=2), kernels=False)
    out = capsys.readouterr().out
    assert "shard_devices={'wqkv': 4, 'adam_moment': 4}" in out, out
