"""Acceptance e2e for paged KV + session tiering: the gateway holds
strictly more concurrent conversations than it has slots, multi-turn
conversations park their KV between turns and re-admit it on the
follow-up instead of re-prefilling — with every reply BITWISE-identical
to an uninterrupted sequential ``InferenceSession``, zero recompiles
after warmup, and corrupt/faulted parked state rejected into a correct
re-prefill, never a wrong answer."""

import glob
import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import deepspeed_tpu
from deepspeed_tpu.models import gpt
from deepspeed_tpu.runtime.supervision.events import EventJournal, EventKind
from deepspeed_tpu.utils import fault_injection
from deepspeed_tpu.utils.fault_injection import FailNTimes, corrupt_file

CFG = gpt.GPTConfig(vocab_size=256, max_seq_len=128, n_layer=2, n_head=4,
                    d_model=64, dtype=jnp.float32, vocab_round_to=128)


@pytest.fixture(scope="module")
def engine():
    params = gpt.init(CFG, jax.random.PRNGKey(0))
    return deepspeed_tpu.init_inference(model=(CFG, params),
                                        config={"dtype": "float32"})


def _serve(engine, journal=None, **paging):
    cfg = {"slots": 2, "max_len": 64, "prefill_chunk": 8,
           "queue_capacity": 32,
           "paging": {"enabled": True, "block_tokens": 8, **paging}}
    return engine.serve(config=cfg, journal=journal)


def _reference_turns(engine, turns, budgets):
    """One sequential session driving the same conversation."""
    s = engine.start_session(batch=1, max_len=64)
    outs = []
    for t, n in zip(turns, budgets):
        s.append(jnp.asarray(np.asarray(t, np.int32)[None]))
        outs.append(np.asarray(s.generate(max_new_tokens=n))[0])
    return outs


def _assert_zero_recompiles(snap):
    assert snap["recompiles"] == 0
    assert all(v <= 1 for v in snap["compile_counts"].values()), \
        snap["compile_counts"]


def test_multiturn_park_readmit_bitwise_pool(engine, tmp_path):
    """The headline e2e: 5 two-turn conversations through 2 slots.
    Turn 2 re-admits the pooled KV (no re-prefill) and both turns match
    the uninterrupted sequential session bit for bit; the gateway held
    strictly more conversations than slots at zero recompiles."""
    journal = EventJournal(str(tmp_path / "events.jsonl"))
    gw = _serve(engine, journal=journal)
    rng = np.random.default_rng(0)
    convs = []
    for i in range(5):
        convs.append({
            "sid": f"conv-{i}",
            "p1": rng.integers(0, 256, (int(rng.integers(4, 12)),)).astype(
                np.int32),
            "n1": int(rng.integers(3, 7)),
            "t2": rng.integers(0, 256, (int(rng.integers(3, 8)),)).astype(
                np.int32),
            "n2": int(rng.integers(3, 6)),
        })
    for c in convs:
        c["h1"] = gw.submit(c["p1"], max_new_tokens=c["n1"],
                            session_id=c["sid"])
    for c in convs:
        c["out1"] = c["h1"].result(timeout=120)
    for c in convs:
        full = np.concatenate([c["p1"], c["out1"], c["t2"]])
        c["h2"] = gw.submit(full, max_new_tokens=c["n2"],
                            session_id=c["sid"])
    for c in convs:
        c["out2"] = c["h2"].result(timeout=120)
    snap = gw.snapshot()
    gw.shutdown()

    # every follow-up was a tier hit — no conversation re-prefilled
    assert snap["readmits"] == 5
    assert snap["readmit_misses"] == 5          # the 5 first turns
    # strictly more concurrent conversations than slots, cheaper HBM
    assert snap["peak_concurrent_conversations"] > gw.config.slots
    assert 0 < snap["hbm_bytes_per_conversation"] < \
        snap["serving_hbm_bytes"] / gw.config.slots
    _assert_zero_recompiles(snap)

    for c in convs:
        ref1, ref2 = _reference_turns(
            engine, [c["p1"], c["t2"]], [c["n1"], c["n2"]])
        np.testing.assert_array_equal(c["out1"], ref1)
        np.testing.assert_array_equal(c["out2"], ref2)

    kinds = [e["kind"] for e in journal.read()]
    assert kinds.count(EventKind.SERVE_READMIT) == 10  # 5 miss + 5 hit
    assert kinds.count(EventKind.SERVE_PAGE_ALLOC) >= 5
    hits = [e for e in journal.read()
            if e["kind"] == EventKind.SERVE_READMIT and e["hit"]]
    assert len(hits) == 5
    assert all(e["tier"] == "pool" and e["tokens_reused"] > 0
               for e in hits)


def test_tiering_ram_and_disk_readmit_bitwise(engine, tmp_path):
    """A 2-block pool forces park pressure: sessions tier out to host
    RAM and spill to disk, and follow-ups re-admit from BOTH host tiers
    bitwise-identically."""
    journal = EventJournal(str(tmp_path / "events.jsonl"))
    gw = _serve(engine, journal=journal, pool_blocks=2, park_capacity=1,
                park_dir=str(tmp_path / "park"))
    rng = np.random.default_rng(1)
    convs = []
    for i in range(4):
        convs.append({
            "sid": f"c{i}",
            "p1": rng.integers(0, 256, (int(rng.integers(6, 14)),)).astype(
                np.int32),
            "t2": rng.integers(0, 256, (5,)).astype(np.int32)})
    for c in convs:
        c["out1"] = gw.submit(c["p1"], max_new_tokens=4,
                              session_id=c["sid"]).result(timeout=120)
    assert glob.glob(str(tmp_path / "park" / "*.npz"))
    for c in convs:
        full = np.concatenate([c["p1"], c["out1"], c["t2"]])
        c["h2"] = gw.submit(full, max_new_tokens=4, session_id=c["sid"])
    for c in convs:
        c["out2"] = c["h2"].result(timeout=120)
    snap = gw.snapshot()
    gw.shutdown()
    assert snap["readmits"] == 4 and snap["park_spills"] >= 1
    _assert_zero_recompiles(snap)
    tiers = {e["tier"] for e in journal.read()
             if e["kind"] == EventKind.SERVE_READMIT and e["hit"]}
    assert "disk" in tiers and tiers <= {"pool", "ram", "disk"}
    kinds = [e["kind"] for e in journal.read()]
    assert EventKind.SERVE_PARK in kinds
    assert EventKind.SERVE_PAGE_EVICT in kinds
    for c in convs:
        ref1, ref2 = _reference_turns(engine, [c["p1"], c["t2"]], [4, 4])
        np.testing.assert_array_equal(c["out1"], ref1)
        np.testing.assert_array_equal(c["out2"], ref2)


def test_corrupt_disk_park_rejected_into_correct_reprefill(
        engine, tmp_path):
    """Bitrot in a parked file is DETECTED (sha mismatch) and the
    follow-up silently re-prefills — the reply is still bitwise right,
    never decoded from corrupt KV."""
    journal = EventJournal(str(tmp_path / "events.jsonl"))
    gw = _serve(engine, journal=journal, pool_blocks=1, park_capacity=0,
                park_dir=str(tmp_path / "park"))
    rng = np.random.default_rng(2)
    p = rng.integers(0, 256, (9,)).astype(np.int32)
    o1 = gw.submit(p, max_new_tokens=4, session_id="x").result(timeout=60)
    files = glob.glob(str(tmp_path / "park" / "*.npz"))
    assert len(files) == 1
    corrupt_file(files[0], nbytes=64, seed=3)
    t2 = rng.integers(0, 256, (4,)).astype(np.int32)
    o2 = gw.submit(np.concatenate([p, o1, t2]), max_new_tokens=4,
                   session_id="x").result(timeout=60)
    snap = gw.snapshot()
    gw.shutdown()
    assert snap["readmits"] == 0 and snap["readmit_misses"] == 2
    ref1, ref2 = _reference_turns(engine, [p, t2], [4, 4])
    np.testing.assert_array_equal(o1, ref1)
    np.testing.assert_array_equal(o2, ref2)
    followup = [e for e in journal.read()
                if e["kind"] == EventKind.SERVE_READMIT][-1]
    assert followup["hit"] is False


def test_corrupt_ram_park_rejected(engine):
    """Same contract for the RAM tier: in-memory bitrot fails the
    integrity check and costs a re-prefill, not a wrong answer."""
    gw = _serve(engine, pool_blocks=1, park_capacity=8)
    rng = np.random.default_rng(3)
    p = rng.integers(0, 256, (9,)).astype(np.int32)
    o1 = gw.submit(p, max_new_tokens=4, session_id="x").result(timeout=60)
    entry = gw._pager.park.entry("x")
    assert entry is not None and entry.arrays is not None
    entry.arrays[0][0, 0, 0, 0] += 1.0
    t2 = rng.integers(0, 256, (4,)).astype(np.int32)
    o2 = gw.submit(np.concatenate([p, o1, t2]), max_new_tokens=4,
                   session_id="x").result(timeout=60)
    snap = gw.snapshot()
    gw.shutdown()
    assert snap["readmits"] == 0
    ref1, ref2 = _reference_turns(engine, [p, t2], [4, 4])
    np.testing.assert_array_equal(o2, ref2)
    np.testing.assert_array_equal(o1, ref1)


@pytest.mark.chaos
def test_park_fault_drops_session_not_request(engine, tmp_path):
    """A failing park (disk full, host OOM — modeled by the serve.park
    fault point) loses only the retention: the reply is delivered and
    the follow-up re-prefills correctly."""
    journal = EventJournal(str(tmp_path / "events.jsonl"))
    gw = _serve(engine, journal=journal, pool_blocks=1)  # forces parking
    rng = np.random.default_rng(4)
    p = rng.integers(0, 256, (9,)).astype(np.int32)
    with fault_injection.inject("serve.park", FailNTimes(1)):
        o1 = gw.submit(p, max_new_tokens=4,
                       session_id="x").result(timeout=60)
    t2 = rng.integers(0, 256, (4,)).astype(np.int32)
    o2 = gw.submit(np.concatenate([p, o1, t2]), max_new_tokens=4,
                   session_id="x").result(timeout=60)
    snap = gw.snapshot()
    gw.shutdown()
    assert snap["readmits"] == 0 and snap["readmit_misses"] == 2
    ref1, ref2 = _reference_turns(engine, [p, t2], [4, 4])
    np.testing.assert_array_equal(o1, ref1)
    np.testing.assert_array_equal(o2, ref2)


@pytest.mark.chaos
def test_readmit_fault_falls_back_to_reprefill(engine):
    """A faulted readmit (serve.readmit fault point) re-prefills instead
    of failing the request; the answer stays bitwise right."""
    gw = _serve(engine)
    rng = np.random.default_rng(5)
    p = rng.integers(0, 256, (9,)).astype(np.int32)
    o1 = gw.submit(p, max_new_tokens=4, session_id="x").result(timeout=60)
    t2 = rng.integers(0, 256, (4,)).astype(np.int32)
    with fault_injection.inject("serve.readmit", FailNTimes(1)):
        o2 = gw.submit(np.concatenate([p, o1, t2]), max_new_tokens=4,
                       session_id="x").result(timeout=60)
    snap = gw.snapshot()
    gw.shutdown()
    assert snap["readmits"] == 0 and snap["readmit_misses"] >= 1
    ref1, ref2 = _reference_turns(engine, [p, t2], [4, 4])
    np.testing.assert_array_equal(o2, ref2)
    del o1, ref1


@pytest.mark.chaos
def test_admission_fault_on_readmit_frees_blocks(engine):
    """An admission fault AFTER the tier restore frees the re-admitted
    block table through the row ledger (no leak) and fails only that
    request; a resubmit still answers bitwise-correctly."""
    from deepspeed_tpu.serving import RequestFailed
    gw = _serve(engine)
    rng = np.random.default_rng(9)
    p = rng.integers(0, 256, (9,)).astype(np.int32)
    o1 = gw.submit(p, max_new_tokens=4, session_id="x").result(timeout=60)
    used_before = gw._pager.pool.allocator.used_blocks
    t2 = rng.integers(0, 256, (4,)).astype(np.int32)
    full = np.concatenate([p, o1, t2])
    with fault_injection.inject("serve.admit", FailNTimes(1)):
        h = gw.submit(full, max_new_tokens=4, session_id="x")
        with pytest.raises(RequestFailed):
            h.result(timeout=60)
    # the session was consumed by the failed readmit and its blocks freed
    assert gw._pager.pool.allocator.used_blocks < used_before
    o2 = gw.submit(full, max_new_tokens=4,
                   session_id="x").result(timeout=60)
    gw.shutdown()
    ref1, ref2 = _reference_turns(engine, [p, t2], [4, 4])
    np.testing.assert_array_equal(o1, ref1)
    np.testing.assert_array_equal(o2, ref2)


def test_paged_prefix_shares_blocks_cow(engine, tmp_path):
    """Three sessions over one system prompt share the prefix's FULL
    blocks (refcounted); evicting the pooled prefix keeps the shared
    blocks alive for the sessions that reference them."""
    journal = EventJournal(str(tmp_path / "events.jsonl"))
    gw = _serve(engine, journal=journal)
    rng = np.random.default_rng(6)
    system = rng.integers(0, 256, (11,)).astype(np.int32)  # 1 full block
    turns = [rng.integers(0, 256, (int(rng.integers(3, 8)),)).astype(
        np.int32) for _ in range(3)]
    hs = [gw.submit(np.concatenate([system, t]), max_new_tokens=5,
                    prefix_len=len(system), session_id=f"s{i}")
          for i, t in enumerate(turns)]
    outs = [h.result(timeout=120) for h in hs]
    snap = gw.snapshot()
    assert snap["prefix_builds"] == 1 and snap["prefix_hits"] == 2
    # the shared full block is counted once, not three times
    alloc = gw._pager.pool.allocator
    prefix_table = next(iter(gw._prefixes.values())).table
    assert prefix_table is not None
    assert alloc.refs(prefix_table[0]) == 4     # pool entry + 3 sessions
    for t, out in zip(turns, outs):
        ref, = _reference_turns(engine, [np.concatenate([system, t])], [5])
        np.testing.assert_array_equal(out, ref)
    # prefix eviction releases only the pool's reference
    with gw._cond:
        gw._evict_prefix(reason="test")
    assert alloc.refs(prefix_table[0]) == 3
    evict = [e for e in journal.read()
             if e["kind"] == EventKind.SERVE_EVICT][-1]
    assert "bytes" in evict
    snap = gw.snapshot()
    gw.shutdown()
    _assert_zero_recompiles(snap)


def test_idle_gateway_ttl_sweep_releases_memory(engine, tmp_path):
    """The TTL sweep runs from the scheduler tick path: an IDLE gateway
    (no admissions) still evicts an expired pooled prefix and an expired
    parked session, journaling the reclaimed bytes."""
    journal = EventJournal(str(tmp_path / "events.jsonl"))
    cfg = {"slots": 2, "max_len": 64, "prefill_chunk": 8,
           "prefix_ttl_s": 0.5, "idle_wait_s": 0.01,
           "paging": {"enabled": True, "block_tokens": 8,
                      "pool_blocks": 1, "park_ttl_s": 0.5}}
    gw = engine.serve(config=cfg, journal=journal)
    rng = np.random.default_rng(7)
    p = rng.integers(0, 256, (10,)).astype(np.int32)
    gw.submit(p, max_new_tokens=3, prefix_len=6,
              session_id="x").result(timeout=60)
    # both a pooled prefix and a parked session existed (journal proof —
    # the TTL may already be sweeping them while we look)
    kinds = [e["kind"] for e in journal.read()]
    assert EventKind.SERVE_PARK in kinds
    # NO further traffic: the idle loop's sweep must reclaim both
    deadline = time.monotonic() + 15.0
    while time.monotonic() < deadline:
        snap = gw.snapshot()
        if snap["cached_prefixes"] == 0 and len(gw._pager.park) == 0:
            break
        time.sleep(0.05)
    gw.shutdown()
    evicts = [e for e in journal.read()
              if e["kind"] == EventKind.SERVE_EVICT]
    assert "ttl" in {e["reason"] for e in evicts}
    assert any(e.get("bytes", 0) > 0 for e in evicts)
    assert snap["cached_prefixes"] == 0 and len(gw._pager.park) == 0


def test_int8_kv_park_readmit_bitwise():
    """int8 KV composes with tiering: code AND scale banks ride the
    page/park round trip together (forced host park via a 2-block pool)
    and the follow-up stays bitwise-parity with the int8 session."""
    params = gpt.init(CFG, jax.random.PRNGKey(0))
    eng = deepspeed_tpu.init_inference(
        model=(CFG, params),
        config={"dtype": "float32", "kv_cache_dtype": "int8"})
    gw = _serve(eng, pool_blocks=2)
    rng = np.random.default_rng(11)
    p = rng.integers(0, 256, (9,)).astype(np.int32)
    o1 = gw.submit(p, max_new_tokens=4, session_id="x").result(timeout=120)
    t2 = rng.integers(0, 256, (4,)).astype(np.int32)
    o2 = gw.submit(np.concatenate([p, o1, t2]), max_new_tokens=4,
                   session_id="x").result(timeout=120)
    snap = gw.snapshot()
    gw.shutdown()
    assert snap["readmits"] == 1 and snap["parked"] >= 1
    _assert_zero_recompiles(snap)
    ref1, ref2 = _reference_turns(eng, [p, t2], [4, 4])
    np.testing.assert_array_equal(o1, ref1)
    np.testing.assert_array_equal(o2, ref2)


def test_session_id_requires_paging(engine):
    gw = engine.serve(config={"slots": 1, "max_len": 64})
    with pytest.raises(ValueError, match="session_id.*paging"):
        gw.submit(np.zeros((4,), np.int32), session_id="x")
    gw.shutdown()


def test_pool_exhaustion_is_survivable(engine):
    """A pool too small for even one session never wedges the gateway:
    rows go unpoolable, sessions park to host, everything still answers
    (the allocator's own exhaustion error is loud — tested in
    test_paging — but the scheduler absorbs it)."""
    gw = _serve(engine, pool_blocks=1, park_capacity=8)
    rng = np.random.default_rng(8)
    outs = []
    for i in range(3):
        p = rng.integers(0, 256, (12,)).astype(np.int32)
        outs.append((p, gw.submit(p, max_new_tokens=4,
                                  session_id=f"s{i}").result(timeout=60)))
    snap = gw.snapshot()
    gw.shutdown()
    assert snap["completed"] == 3 and snap["parked"] == 3
    for p, out in outs:
        ref, = _reference_turns(engine, [p], [4])
        np.testing.assert_array_equal(out, ref)


def test_hbm_pressure_sweep_parks_pool_sessions(engine, tmp_path):
    """Telemetry-census pressure eviction: a live-buffer census above
    ``serving.paging.hbm_high_watermark`` parks pool-LRU sessions to
    host (bounded per sweep), journaling the observed pressure — and the
    parked conversation still answers its follow-up bitwise.  At or
    below the watermark (or with no watermark configured) the sweep is
    a no-op."""
    # far above any real census: the scheduler tick runs its own sweep
    # against the process's true live-buffer bytes (which a loaded test
    # process can push past a small watermark) — keep automatic sweeps
    # inert so only the explicit ``live_bytes`` overrides below evict
    wm = 1 << 60
    journal = EventJournal(str(tmp_path / "events.jsonl"))
    gw = _serve(engine, journal=journal, hbm_high_watermark=wm,
                park_capacity=8)
    rng = np.random.default_rng(4)
    convs = []
    for i in range(3):
        p = rng.integers(0, 256, (10,)).astype(np.int32)
        convs.append(
            {"sid": f"c{i}", "p": p,
             "out1": gw.submit(p, max_new_tokens=4,
                               session_id=f"c{i}").result(timeout=60)})
    pager = gw._pager
    assert pager.stats()["sessions_pool"] == 3

    # at/below the watermark: nothing moves
    assert pager.pressure_sweep(live_bytes=wm) == 0
    assert pager.stats()["sessions_pool"] == 3

    # one over: pool-LRU sessions park to host, bounded by max_evictions
    assert pager.pressure_sweep(live_bytes=wm + 1, max_evictions=2) == 2
    st = pager.stats()
    assert st["sessions_pool"] == 1
    assert st["sessions_ram"] + st["sessions_disk"] == 2
    # the next sweep under pressure drains the rest
    assert pager.pressure_sweep(live_bytes=wm + 1) == 1
    assert pager.stats()["sessions_pool"] == 0

    evs = [e for e in journal.read()
           if e["kind"] == EventKind.SERVE_PAGE_EVICT]
    assert len(evs) == 3
    assert all(e["reason"] == "hbm_pressure" and e["pressure"] == wm + 1
               and e["watermark"] == wm for e in evs)

    # a pressure-parked session re-admits from host and matches the
    # uninterrupted reference bit for bit
    c = convs[0]
    t2 = rng.integers(0, 256, (6,)).astype(np.int32)
    full = np.concatenate([c["p"], c["out1"], t2])
    out2 = gw.submit(full, max_new_tokens=4,
                     session_id=c["sid"]).result(timeout=60)
    gw.shutdown()
    ref1, ref2 = _reference_turns(engine, [c["p"], t2], [4, 4])
    np.testing.assert_array_equal(c["out1"], ref1)
    np.testing.assert_array_equal(out2, ref2)


def test_pressure_sweep_noop_without_watermark(engine):
    gw = _serve(engine, park_capacity=8)
    p = np.arange(8, dtype=np.int32)
    gw.submit(p, max_new_tokens=3, session_id="s").result(timeout=60)
    assert gw._pager.hbm_high_watermark is None
    assert gw._pager.pressure_sweep(live_bytes=1 << 40) == 0
    assert gw._pager.stats()["sessions_pool"] == 1
    gw.shutdown()
