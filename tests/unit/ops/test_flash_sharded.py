"""The flash kernel under a multi-device mesh: the kernel entry maps the
call over the mesh itself (rows over the data-parallel axes, heads over the
model axis), because the compiler cannot partition a Mosaic kernel.  Checked
here for values and gradients, in interpret mode on virtual CPU devices."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from deepspeed_tpu.ops.pallas import flash_attention, mha_reference
from deepspeed_tpu.parallel.mesh import (DP_GROUP, MODEL_AXIS, ParallelDims,
                                         initialize_mesh)


@pytest.mark.parametrize("batch", [4, 3], ids=["rows-split", "rows-whole"])
def test_flash_matches_reference_on_dp2_tp2_mesh(monkeypatch, batch):
    monkeypatch.setenv("DS_TPU_PALLAS_INTERPRET", "1")
    mm = initialize_mesh(ParallelDims(dp=2, tp=2), devices=jax.devices()[:4])
    rng = np.random.default_rng(0)
    rows = DP_GROUP if batch % 2 == 0 else None
    q, k, v = (jax.device_put(
        jnp.asarray(rng.normal(size=(batch, 128, 4, 64)), jnp.float32),
        mm.sharding(rows, None, MODEL_AXIS, None)) for _ in range(3))
    w = jnp.asarray(rng.normal(size=q.shape), jnp.float32)

    def loss(attn):
        return lambda q, k, v: jnp.sum(attn(q, k, v) * w)

    kernel = lambda q, k, v: flash_attention(q, k, v, causal=True,
                                             block_q=128, block_k=128)
    dense = lambda q, k, v: mha_reference(q, k, v, causal=True)
    out = jax.jit(kernel)(q, k, v)
    assert "shard_map" in str(jax.make_jaxpr(kernel)(q, k, v))
    np.testing.assert_allclose(np.asarray(out), np.asarray(dense(q, k, v)),
                               atol=2e-5, rtol=2e-5)
    got = jax.jit(jax.grad(loss(kernel), argnums=(0, 1, 2)))(q, k, v)
    want = jax.grad(loss(dense), argnums=(0, 1, 2))(q, k, v)
    for g, r, name in zip(got, want, "qkv"):
        np.testing.assert_allclose(np.asarray(g), np.asarray(r), atol=5e-5,
                                   rtol=5e-5, err_msg=f"d{name}")
