"""Unit matrix for the paged-KV primitives: block allocator
(alloc/free/refcount-CoW, fragmentation, exhaustion), the park store
(LRU, disk spill, TTL, integrity rejection), config validation, and the
pool gather/scatter round trip."""

import time

import numpy as np
import pytest

from deepspeed_tpu.serving import (BlockAllocator, ParkCorruptError,
                                   ParkStore, PoolExhaustedError,
                                   PagingConfig, ServingConfig)
from deepspeed_tpu.serving.paging import (TRASH_BLOCK, blocks_for,
                                          pad_table)
from deepspeed_tpu.utils.fault_injection import corrupt_file


# ------------------------------------------------------------- allocator


def test_allocator_alloc_unique_and_exhaustion():
    a = BlockAllocator(5)          # blocks 1..4 usable, 0 is trash
    got = [a.alloc() for _ in range(4)]
    assert sorted(got) == [1, 2, 3, 4]
    assert a.free_blocks == 0 and a.used_blocks == 4
    with pytest.raises(PoolExhaustedError, match="exhausted"):
        a.alloc()


def test_allocator_free_recycles():
    a = BlockAllocator(3)
    b1, b2 = a.alloc(), a.alloc()
    a.free(b1)
    assert a.free_blocks == 1
    assert a.alloc() == b1          # stack: freed block reused first
    a.free(b1)
    a.free(b2)
    assert a.free_blocks == 2 and a.used_blocks == 0


def test_allocator_refcount_cow_release():
    """share() models copy-on-write prefix sharing: the block only
    returns to the free list when its LAST holder frees it."""
    a = BlockAllocator(2)           # exactly one usable block
    b = a.alloc()
    a.share(b)
    a.share(b)
    assert a.refs(b) == 3
    a.free(b)
    a.free(b)
    assert a.free_blocks == 0       # one holder left
    a.free(b)
    assert a.free_blocks == 1       # last free releases


def test_allocator_misuse_is_loud():
    a = BlockAllocator(3)
    b = a.alloc()
    a.free(b)
    with pytest.raises(ValueError, match="double free"):
        a.free(b)
    with pytest.raises(ValueError, match="share unallocated"):
        a.share(b)
    with pytest.raises(ValueError, match="share unallocated"):
        a.share(TRASH_BLOCK)
    a.free(TRASH_BLOCK)             # no-op, never raises
    with pytest.raises(ValueError, match=">= 2 blocks"):
        BlockAllocator(1)


def test_allocator_fragmentation_accounting():
    """Interleaved alloc/free keeps the books balanced and never hands
    out the trash block or a live block twice."""
    a = BlockAllocator(9)
    rng = np.random.default_rng(0)
    live = []
    for _ in range(200):
        if live and (rng.random() < 0.5 or a.free_blocks == 0):
            a.free(live.pop(int(rng.integers(len(live)))))
        else:
            bid = a.alloc()
            assert bid != TRASH_BLOCK and bid not in live
            live.append(bid)
        assert a.used_blocks + a.free_blocks == 8
        assert a.used_blocks == len(live)


def test_blocks_for_and_pad_table():
    assert blocks_for(0, 8) == 0
    assert blocks_for(1, 8) == 1
    assert blocks_for(8, 8) == 1
    assert blocks_for(9, 8) == 2
    t = pad_table([3, 7], 4)
    assert t.dtype == np.int32 and list(t) == [3, 7, TRASH_BLOCK,
                                               TRASH_BLOCK]
    with pytest.raises(ValueError, match="overflows"):
        pad_table([1, 2, 3], 2)


# ------------------------------------------------------------ park store


def _banks(rng, n=2, rows=16):
    return [rng.standard_normal((2, 1, rows, 2, 4)).astype(np.float32)
            for _ in range(n)]


def test_park_roundtrip_and_lru_touch():
    rng = np.random.default_rng(1)
    st = ParkStore(capacity=4, park_dir=None, ttl_s=60.0)
    a = _banks(rng)
    st.put("s1", np.arange(5, dtype=np.int32), a, 5)
    st.put("s2", np.arange(6, dtype=np.int32), _banks(rng), 6)
    got, length = st.load("s1")
    assert length == 5
    for x, y in zip(got, a):
        np.testing.assert_array_equal(x, y)
    # s1 is now MRU: filling past capacity drops s2 first
    st.put("s3", np.arange(3, dtype=np.int32), _banks(rng), 3)
    st.put("s4", np.arange(3, dtype=np.int32), _banks(rng), 3)
    displaced = st.put("s5", np.arange(3, dtype=np.int32), _banks(rng), 3)
    assert [d[0] for d in displaced] == ["s2"]
    assert displaced[0][1] == "dropped"      # no park_dir → dropped
    assert "s1" in st and "s2" not in st


def test_park_capacity_zero_spills_fresh_entry(tmp_path):
    st = ParkStore(capacity=0, park_dir=str(tmp_path), ttl_s=60.0)
    rng = np.random.default_rng(2)
    displaced = st.put("s", np.arange(4, dtype=np.int32), _banks(rng), 4)
    assert displaced == [("s", "disk", displaced[0][2])]
    got, length = st.load("s")               # disk round trip verifies sha
    assert length == 4 and len(got) == 2


def test_park_disk_corruption_rejected(tmp_path):
    st = ParkStore(capacity=0, park_dir=str(tmp_path), ttl_s=60.0)
    rng = np.random.default_rng(3)
    st.put("s", np.arange(4, dtype=np.int32), _banks(rng), 4)
    path = st.entry("s").path
    corrupt_file(path, nbytes=64, seed=0)
    with pytest.raises(ParkCorruptError):
        st.load("s")


def test_park_ram_corruption_rejected():
    st = ParkStore(capacity=4, park_dir=None, ttl_s=60.0)
    rng = np.random.default_rng(4)
    st.put("s", np.arange(4, dtype=np.int32), _banks(rng), 4)
    st.entry("s").arrays[0][0, 0, 0, 0, 0] += 1.0   # bitrot
    with pytest.raises(ParkCorruptError, match="integrity"):
        st.load("s")


def test_park_ttl_sweep_removes_disk_file(tmp_path):
    import os
    st = ParkStore(capacity=0, park_dir=str(tmp_path), ttl_s=0.05)
    rng = np.random.default_rng(5)
    st.put("s", np.arange(4, dtype=np.int32), _banks(rng), 4)
    path = st.entry("s").path
    assert os.path.exists(path)
    swept = st.sweep(time.monotonic() + 1.0)
    assert [s[0] for s in swept] == ["s"]
    assert "s" not in st and not os.path.exists(path)


# ---------------------------------------------------------------- config


@pytest.mark.parametrize("bad,msg", [
    ({"block_tokens": 12}, "power of two"),
    ({"block_tokens": 0}, "power of two"),
    ({"pool_blocks": 0}, "pool_blocks must be >= 1"),
    ({"park_capacity": -1}, "park_capacity must be >= 0"),
    ({"park_ttl_s": 0.0}, "park_ttl_s must be > 0"),
    ({"hbm_high_watermark": 0}, "hbm_high_watermark must be >= 1"),
    ({"hbm_high_watermark": -5}, "hbm_high_watermark must be >= 1"),
])
def test_paging_config_validation(bad, msg):
    with pytest.raises(ValueError, match=msg):
        PagingConfig.from_dict(bad)


def test_paging_config_watermark_roundtrip():
    assert PagingConfig.from_dict({}).hbm_high_watermark is None
    cfg = PagingConfig.from_dict({"hbm_high_watermark": 1 << 20})
    assert cfg.hbm_high_watermark == 1 << 20


def test_serving_config_nested_paging():
    cfg = ServingConfig.from_dict(
        {"slots": 2, "paging": {"enabled": True, "block_tokens": 32,
                                "park_capacity": 7}})
    p = cfg.paging_config
    assert p.enabled and p.block_tokens == 32 and p.park_capacity == 7
    assert not ServingConfig.from_dict({}).paging_config.enabled
    with pytest.raises(ValueError, match="power of two"):
        ServingConfig.from_dict({"paging": {"block_tokens": 3}})


def test_runtime_config_serving_section():
    from deepspeed_tpu.runtime.config import (DeepSpeedConfig,
                                              DeepSpeedConfigError)
    base = {"train_micro_batch_size_per_gpu": 1}
    c = DeepSpeedConfig({**base,
                         "serving": {"slots": 3,
                                     "paging": {"enabled": True}}})
    assert c.serving_config.slots == 3
    assert c.serving_config.paging_config.enabled
    with pytest.raises(DeepSpeedConfigError,
                       match="invalid 'serving' section.*power of two"):
        DeepSpeedConfig({**base,
                         "serving": {"paging": {"block_tokens": 6}}})
    with pytest.raises(DeepSpeedConfigError,
                       match="invalid 'serving' section"):
        DeepSpeedConfig({**base, "serving": {"slots": 0}})


# ----------------------------------------------- pool gather/scatter ops


def test_pool_scatter_gather_roundtrip_bitwise():
    """A prefilled batch-1 cache survives the blocks round trip bit for
    bit (the live rows; rows past the frontier are masked anyway)."""
    import jax
    import jax.numpy as jnp
    import deepspeed_tpu
    from deepspeed_tpu.models import gpt
    from deepspeed_tpu.serving import SlotBatcher, ServingConfig
    from deepspeed_tpu.serving.paging import PagedKVPool, pad_table

    cfg = gpt.GPTConfig(vocab_size=128, max_seq_len=64, n_layer=2,
                        n_head=2, d_model=32, dtype=jnp.float32,
                        vocab_round_to=128)
    params = gpt.init(cfg, jax.random.PRNGKey(0))
    eng = deepspeed_tpu.init_inference(model=(cfg, params),
                                       config={"dtype": "float32"})
    bat = SlotBatcher(eng, ServingConfig(slots=1, max_len=32,
                                         prefill_chunk=8))
    pool = PagedKVPool(bat, block_tokens=8, num_blocks=6)
    prompt = np.arange(11, dtype=np.int32) % 128
    cache, frontier = bat._chunked_prefill(prompt)
    table = [pool.allocator.alloc() for _ in range(2)]   # ceil(11/8)
    pool.scatter(cache, pad_table(table, pool.max_blocks))
    back = pool.gather(table, frontier)
    for src, dst in zip(jax.tree_util.tree_leaves(cache),
                        jax.tree_util.tree_leaves(back)):
        if getattr(src, "ndim", 0) == 4:     # a bank: [L, 1, S, H*D]
            np.testing.assert_array_equal(
                np.asarray(src)[:, :, :16], np.asarray(dst)[:, :, :16])
    assert int(back.length) == frontier
    # every paging program compiled exactly once
    counts = bat.compile_counts()
    for name in ("read_slot", "page_gather", "page_scatter"):
        assert counts[name] <= 1, counts
