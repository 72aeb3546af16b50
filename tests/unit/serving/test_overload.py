"""Overload control: SLO-driven admission shedding, the hysteretic
degradation ladder (pure-unit and through the live gateway), spec
pause/resume bitwise exactness, and the mid-decode deadline contract when
several slots expire inside one tick."""

import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import deepspeed_tpu
from deepspeed_tpu.models import gpt
from deepspeed_tpu.runtime.supervision.events import EventJournal, EventKind
from deepspeed_tpu.serving import (AdmissionController, DegradationLadder,
                                   OverloadConfig, RequestShed,
                                   RequestTimedOut, ServingConfig,
                                   SlotBatcher)
from deepspeed_tpu.utils import fault_injection
from deepspeed_tpu.utils.fault_injection import DelaySeconds

CFG = gpt.GPTConfig(vocab_size=256, max_seq_len=128, n_layer=2, n_head=4,
                    d_model=64, dtype=jnp.float32, vocab_round_to=128)
DCFG = gpt.GPTConfig(vocab_size=256, max_seq_len=128, n_layer=1, n_head=2,
                     d_model=32, dtype=jnp.float32, vocab_round_to=128)


@pytest.fixture(autouse=True)
def _clear_faults():
    yield
    fault_injection.clear()


@pytest.fixture(scope="module")
def engine():
    params = gpt.init(CFG, jax.random.PRNGKey(0))
    return deepspeed_tpu.init_inference(model=(CFG, params),
                                        config={"dtype": "float32"})


# ------------------------------------------------- admission (pure unit)

def test_admission_classify_and_queue_share_shed():
    """Default classes: priority >= 1 is interactive (full queue share),
    priority 0 is batch and sheds once the queue is half full."""
    ctl = AdmissionController(OverloadConfig(enabled=True),
                              queue_capacity=10)
    assert ctl.classify(5).name == "interactive"
    assert ctl.classify(1).name == "interactive"
    assert ctl.classify(0).name == "batch"
    assert ctl.should_shed(0, depth=4) is None
    d = ctl.should_shed(0, depth=5)           # 0.5 * 10
    assert d is not None and d.reason == "queue_share"
    assert d.cls.name == "batch"
    # interactive rides until the hard capacity bound
    assert ctl.should_shed(5, depth=9) is None
    d = ctl.should_shed(5, depth=10)
    assert d is not None and d.reason == "queue_share"
    assert ctl.shed_counts[("batch", "queue_share")] == 1
    assert ctl.shed_counts[("interactive", "queue_share")] == 1


def test_admission_slo_shed_scales_with_queue_depth():
    """The TTFT estimate scales recent queue waits by the depth ratio, so
    a deepening queue triggers the SLO shed before waits are re-measured;
    the dominant phase tracks the decomposition."""
    cfg = OverloadConfig(enabled=True, ewma_alpha=1.0, classes=[
        {"name": "interactive", "min_priority": 0,
         "ttft_slo_ms": 100.0, "queue_share": 1.0}])
    ctl = AdmissionController(cfg, queue_capacity=100)
    # no observations yet: est is 0, nothing sheds on SLO grounds
    assert ctl.should_shed(0, depth=10) is None
    ctl.note_admit(queued_ms=60.0, depth=2)
    ctl.note_prefill(10.0)
    ctl.note_first_token(20.0)
    assert ctl.est_ttft_ms(2) == pytest.approx(90.0)
    assert ctl.should_shed(0, depth=2) is None
    # depth doubled since the wait was measured -> est 60*2+30 = 150 > SLO
    assert ctl.est_ttft_ms(4) == pytest.approx(150.0)
    d = ctl.should_shed(0, depth=4)
    assert d is not None and d.reason == "slo"
    assert d.est_ttft_ms == pytest.approx(150.0)
    assert ctl.dominant_phase(4) == "queue_wait"
    ctl.note_first_token(500.0)
    assert ctl.dominant_phase(4) == "decode"


# ----------------------------------------------------- ladder (pure unit)

def test_ladder_engages_and_releases_with_hysteresis():
    cfg = OverloadConfig(enabled=True, engage_ticks=3, release_ticks=2,
                         pressure_high=0.5, pressure_low=0.1)
    lad = DegradationLadder(cfg)
    # two high ticks: below the hysteresis bar, nothing engages
    assert lad.step(0.9, "decode") == []
    assert lad.step(0.9, "decode") == []
    # a dip resets the streak
    assert lad.step(0.3, "decode") == []
    assert lad.step(0.9, "decode") == []
    assert lad.step(0.9, "decode") == []
    out = lad.step(0.9, "decode")
    assert out == [("draft_k", "engage", 1)]       # decode-tagged rung
    assert lad.bitmask() == 1 and lad.level == 1
    # release needs release_ticks consecutive calm iterations
    assert lad.step(0.05, "decode") == []
    out = lad.step(0.05, "decode")
    assert out == [("draft_k", "release", 0)]
    assert lad.level == 0 and lad.bitmask() == 0
    assert lad.engagements["draft_k"] == 1
    assert lad.releases["draft_k"] == 1
    assert lad.dwell_ticks["draft_k"] >= 1


def test_ladder_phase_preference_and_lifo_release():
    """Rung choice prefers the dominant phase's lever; releases undo the
    newest engagement first, one transition per step."""
    cfg = OverloadConfig(enabled=True, engage_ticks=1, release_ticks=1,
                         pressure_high=0.5, pressure_low=0.1)
    lad = DegradationLadder(cfg)
    assert lad.step(0.9, "prefill") == [("chunk_widen", "engage", 1)]
    assert lad.step(0.9, "queue_wait") == [("max_tokens", "engage", 2)]
    assert lad.step(0.9, "decode") == [("draft_k", "engage", 3)]
    # prefill lever taken: falls back to escalation order
    assert lad.step(0.9, "prefill") == [("spec_pause", "engage", 4)]
    assert lad.step(0.9, "prefill") == []           # ladder exhausted
    assert lad.step(0.05, "prefill") == [("spec_pause", "release", 3)]
    assert lad.step(0.05, "prefill") == [("draft_k", "release", 2)]
    assert lad.step(0.05, "prefill") == [("max_tokens", "release", 1)]
    assert lad.step(0.05, "prefill") == [("chunk_widen", "release", 0)]


def test_ladder_rejects_unknown_rungs():
    with pytest.raises(ValueError, match="unknown ladder rungs"):
        DegradationLadder(OverloadConfig(enabled=True),
                          available=["draft_k", "nope"])


# --------------------------------------------------- gateway end-to-end

def test_gateway_sheds_and_degrades_under_storm(engine, tmp_path):
    """An open-loop storm past capacity: batch-class submissions shed
    pre-admission (journaled with the triggering phase), the ladder
    engages under pressure and RELEASES after the drain, every accepted
    request completes, and nothing recompiles."""
    journal = EventJournal(str(tmp_path / "events.jsonl"))
    gw = engine.serve(config={
        "slots": 2, "max_len": 64, "prefill_chunk": 8,
        "queue_capacity": 8, "journal_every_ticks": 4,
        "overload": {"enabled": True, "engage_ticks": 2,
                     "release_ticks": 3, "pressure_high": 0.4,
                     "pressure_low": 0.1, "max_new_tokens_cap": 4},
    }, journal=journal)
    rng = np.random.default_rng(0)
    handles, shed, shed_cls = [], 0, {"batch": 0, "interactive": 0}
    for i in range(40):
        prompt = rng.integers(0, 256, (12,)).astype(np.int32)
        try:
            handles.append(gw.submit(prompt, max_new_tokens=8,
                                     priority=5 if i % 3 == 0 else 0))
        except RequestShed as e:
            shed += 1
            shed_cls[e.cls] += 1
            assert e.reason in ("queue_share", "slo")
    # batch gives way at half the queue; interactive sheds only when the
    # queue is literally full, so batch always sheds first and hardest
    assert shed_cls["batch"] > 0 and handles
    assert shed_cls["batch"] >= shed_cls["interactive"]
    outs = [h.result(timeout=120) for h in handles]
    assert all(o.shape[0] >= 1 for o in outs)
    # idle long enough for the release hysteresis to walk back down
    deadline = time.monotonic() + 30.0
    while time.monotonic() < deadline:
        if gw.snapshot()["degrade_rungs"] == 0:
            break
        time.sleep(0.05)
    snap = gw.snapshot()
    gw.shutdown()
    assert snap["completed"] == len(handles)
    assert snap["shed"] == shed
    assert snap["degrade_rungs"] == 0               # everything released
    assert all(v <= 1 for v in snap["compile_counts"].values()), \
        snap["compile_counts"]
    ev = journal.read()
    sheds = [e for e in ev if e["kind"] == EventKind.SERVE_SHED]
    assert len(sheds) == shed
    assert all(e["phase"] in ("queue_wait", "prefill", "decode")
               for e in sheds)
    assert all(e["priority"] == 0 for e in sheds if e["cls"] == "batch")
    assert sum(e["cls"] == "batch" for e in sheds) == shed_cls["batch"]
    deg = [e for e in ev if e["kind"] == EventKind.SERVE_DEGRADE]
    assert any(e["action"] == "engage" for e in deg)
    assert any(e["action"] == "release" for e in deg)
    assert snap["degrade_transitions"] == len(deg)


def test_max_tokens_rung_caps_new_admissions_only(engine, tmp_path):
    """With the max_tokens rung pinned engaged (pressure held high by a
    stopped gateway), a newly admitted request's budget is capped; the
    cap never drops an accepted request."""
    journal = EventJournal(str(tmp_path / "events.jsonl"))
    gw = engine.serve(config={
        "slots": 1, "max_len": 64, "prefill_chunk": 8,
        "queue_capacity": 4, "idle_wait_s": 0.01,
        "overload": {"enabled": True, "engage_ticks": 1,
                     "release_ticks": 10000, "pressure_high": 0.25,
                     "pressure_low": 0.0, "max_new_tokens_cap": 3},
    }, journal=journal, autostart=False)
    hs = [gw.submit(np.arange(4, dtype=np.int32), max_new_tokens=20,
                    priority=5) for _ in range(3)]
    gw.start()
    outs = [h.result(timeout=120) for h in hs]
    gw.shutdown()
    # the queue was deep when the later admissions happened: at least one
    # got its reply budget degraded to the cap, none were lost
    assert sorted(o.shape[0] for o in outs)[0] == 3
    assert all(o.shape[0] in (3, 20) for o in outs)
    deg = [e for e in journal.read()
           if e["kind"] == EventKind.SERVE_DEGRADE]
    assert deg and deg[0]["rung"] == "max_tokens"


# ------------------------------------------- spec pause/resume exactness

def test_spec_pause_resume_bitwise_greedy():
    """Ladder levels 0 (full K) -> 2 (paused) -> 1 (K/2) -> 0: greedy
    slots stay bitwise on the sequential chain through every transition,
    with zero recompiles (each level is its own pre-registered program)."""
    params = gpt.init(CFG, jax.random.PRNGKey(0))
    eng = deepspeed_tpu.init_inference(model=(CFG, params),
                                       config={"dtype": "float32"})
    dparams = gpt.init(DCFG, jax.random.PRNGKey(7))
    bat = SlotBatcher(eng, ServingConfig.from_dict(
        {"slots": 2, "max_len": 96, "prefill_chunk": 8,
         "speculative": {"enabled": True, "draft_k": 4}}),
        draft=(DCFG, dparams))
    assert bat.draft_k2 == max(1, bat.draft_k // 2)
    rng = np.random.default_rng(1)
    p0 = rng.integers(0, 256, (9,)).astype(np.int32)
    p1 = rng.integers(0, 256, (12,)).astype(np.int32)
    base = jax.random.PRNGKey(0)
    bat.admit(0, p0, jax.random.fold_in(base, 11), greedy=True,
              temperature=1.0)
    bat.admit(1, p1, jax.random.fold_in(base, 22), greedy=True,
              temperature=1.0)
    outs = {0: [], 1: []}

    def drain(res):
        if isinstance(res, tuple):
            window, counts = res
            for r in (0, 1):
                outs[r].extend(int(t) for t in window[r, :int(counts[r])])
        else:
            for r in (0, 1):
                outs[r].append(int(res[r]))

    for level, ticks in ((0, 3), (2, 4), (1, 3), (0, 3)):
        bat.set_spec_level(level)
        for _ in range(ticks):
            drain(bat.tick())

    n = min(len(outs[0]), len(outs[1]), 20)
    for r, p in ((0, p0), (1, p1)):
        s = eng.start_session(batch=1, max_len=96)
        s.append(jnp.asarray(p[None]))
        ref = np.asarray(s.generate(max_new_tokens=n))[0]
        np.testing.assert_array_equal(np.asarray(outs[r][:n], np.int32),
                                      ref)
    bad = {k: v for k, v in bat.compile_counts().items() if v > 1}
    assert not bad, bad


# ------------------------------- concurrent mid-decode deadline expiry

def test_concurrent_multislot_deadline_expiry_one_tick(engine, tmp_path):
    """Three slots share one deadline under an injected slow tick: all
    three expire in the SAME decode tick, each caller gets its own
    partial tokens via RequestTimedOut, serve.timeout is journaled per
    request with tokens_out, and every slot is immediately reusable."""
    journal = EventJournal(str(tmp_path / "events.jsonl"))
    gw = engine.serve(config={"slots": 3, "max_len": 64,
                              "prefill_chunk": 8, "queue_capacity": 8,
                              "idle_wait_s": 0.01}, journal=journal)
    with fault_injection.inject("serve.decode_tick",
                                DelaySeconds(0.3, n=None)):
        hs = [gw.submit(np.arange(4 + i, dtype=np.int32),
                        max_new_tokens=50, deadline_s=0.8)
              for i in range(3)]
        errs = []
        for h in hs:
            with pytest.raises(RequestTimedOut) as ei:
                h.result(timeout=60)
            errs.append(ei.value)
    # the partial-output contract: each caller got what was decoded
    for h, e in zip(hs, errs):
        assert 0 < e.partial.shape[0] < 50
        assert h.state == "timeout"
        assert h.tokens_out == e.partial.shape[0]
    evs = [e for e in journal.read()
           if e["kind"] == EventKind.SERVE_TIMEOUT]
    assert len(evs) == 3
    assert all(e["queued"] is False and e["tokens_out"] >= 1
               and e["slot"] is not None for e in evs)
    # all three were harvested by the same tick pass: the three journal
    # stamps sit well inside one injected tick delay of each other
    spread = max(e["ts"] for e in evs) - min(e["ts"] for e in evs)
    assert spread < 0.25, spread
    # distinct slots, all recycled: a fresh trio completes normally
    assert len({e["slot"] for e in evs}) == 3
    outs = [gw.submit(np.arange(5, dtype=np.int32),
                      max_new_tokens=2).result(timeout=60)
            for _ in range(3)]
    assert all(o.shape == (2,) for o in outs)
    snap = gw.snapshot()
    gw.shutdown()
    assert snap["timeouts"] == 3 and snap["completed"] == 3


def test_multislot_deadline_expiry_releases_paged_blocks(engine, tmp_path):
    """Paged gateway: sessions timing out mid-decode in the same tick
    free their block tables through the row ledger — no retained tier
    copy, no leaked pool blocks."""
    journal = EventJournal(str(tmp_path / "events.jsonl"))
    gw = engine.serve(config={
        "slots": 2, "max_len": 64, "prefill_chunk": 8,
        "queue_capacity": 8, "idle_wait_s": 0.01,
        "paging": {"enabled": True, "block_tokens": 16}},
        journal=journal)
    with fault_injection.inject("serve.decode_tick",
                                DelaySeconds(0.3, n=None)):
        hs = [gw.submit(np.arange(6 + i, dtype=np.int32),
                        max_new_tokens=50, deadline_s=0.8,
                        session_id=f"sess-{i}") for i in range(2)]
        for h in hs:
            with pytest.raises(RequestTimedOut) as ei:
                h.result(timeout=60)
            assert ei.value.partial.shape[0] >= 1
    st = gw._pager.stats()
    # a timeout never retires the conversation into a tier, and the row
    # ledger returned every block to the pool
    assert st["decoding_sessions"] == 0 and st["sessions_pool"] == 0
    assert st["pool_blocks_used"] == 0, st
    gw.shutdown()


# ------------------------------------------------------------ warm start

def test_warm_start_precompiles_every_rung_program(engine):
    """``serving.warm_start`` compiles the whole program set at
    construction — including the chunk_widen rung's wide pair — so a
    ladder rung engaging mid-storm never stalls the tick loop behind a
    first XLA compile, and no later traffic recompiles anything."""
    gw = engine.serve(config={"slots": 2, "max_len": 64,
                              "prefill_chunk": 8, "warm_start": True,
                              "overload": {"enabled": True}})
    counts = gw._batcher.compile_counts()
    # an admission with and without a prefix, and the chunk pair that builds
    # one launch by launch, each at both widths
    assert sorted(counts) == sorted((
        "admit", "admit_prefix", "prefill", "extend", "admit_wide",
        "admit_prefix_wide", "prefill_wide", "extend_wide", "release",
        "tick"))
    for name in counts:
        assert counts.get(name) == 1, (name, counts)
    # prewarm left every slot free: real traffic runs immediately...
    outs = [gw.submit(np.arange(4 + i, dtype=np.int32), max_new_tokens=3)
            for i in range(4)]
    assert all(h.result(timeout=60).shape == (3,) for h in outs)
    # ...and through the WIDE path, without a single new compile
    gw._batcher.set_chunk_wide(True)
    wide = gw.submit(np.arange(17, dtype=np.int32), max_new_tokens=3)
    assert wide.result(timeout=60).shape == (3,)
    forked = gw.submit(np.arange(40, dtype=np.int32), max_new_tokens=3,
                       prefix_len=20)
    assert forked.result(timeout=60).shape == (3,)
    assert gw._batcher.compile_counts() == counts
    assert gw.snapshot()["recompiles"] == 0
    gw.shutdown()
