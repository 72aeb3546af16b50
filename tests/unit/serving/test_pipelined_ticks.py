"""The decode loop keeps one tick in flight: launch tick n+1, then pull and
harvest tick n.  What that order must not change (every request's tokens),
what it must drop (the token a row's late tick decoded for a tenant that
had already finished), what it must leave behind when the server goes idle
or stops (nothing un-pulled), and what its counters say.

Every loop here reads a tick's result once a pass (the gateway's own pull,
or ``tick()``), so no CPU-mesh loop runs unfenced (ROADMAP D0)."""

import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import deepspeed_tpu
from deepspeed_tpu.models import gpt
from deepspeed_tpu.serving import (RequestCancelled, RequestFailed,
                                   RequestTimedOut, ServingConfig,
                                   ServingGateway, SlotBatcher)
from deepspeed_tpu.telemetry.spans import Tracer
from deepspeed_tpu.utils import fault_injection
from deepspeed_tpu.utils.fault_injection import (DelaySeconds, Fault,
                                                 FaultError)

CFG = gpt.GPTConfig(vocab_size=256, max_seq_len=128, n_layer=2, n_head=4,
                    d_model=64, dtype=jnp.float32, vocab_round_to=128)
DCFG = gpt.GPTConfig(vocab_size=256, max_seq_len=128, n_layer=1, n_head=2,
                     d_model=32, dtype=jnp.float32, vocab_round_to=128)
SERVING = {"slots": 3, "max_len": 64, "prefill_chunk": 8,
           "queue_capacity": 32, "idle_wait_s": 0.01}


@pytest.fixture(autouse=True)
def _clear_faults():
    yield
    fault_injection.clear()


@pytest.fixture(scope="module")
def engine():
    params = gpt.init(CFG, jax.random.PRNGKey(0))
    return deepspeed_tpu.init_inference(model=(CFG, params),
                                        config={"dtype": "float32"})


@pytest.fixture(scope="module")
def draft():
    return DCFG, gpt.init(DCFG, jax.random.PRNGKey(7))


class SyncGateway(ServingGateway):
    """The order this tree had before: every tick launched, pulled and
    harvested before the loop comes round, nothing in flight."""

    def _decode_tick(self) -> None:
        tick = self._launch_tick(overlapped=False)
        self._harvest(tick, self._batcher.pull(tick.pending))


def _gateway(cls, engine, draft, mode, **cfg):
    """``mode``: ``plain``, ``spec`` (draft/verify rounds) or ``paused``
    (a speculative gateway held on the ladder's spec_pause rung)."""
    config = {**SERVING, **cfg}
    if mode != "plain":
        config["speculative"] = {"enabled": True, "draft_k": 3}
    gw = cls(engine, config, autostart=False,
             draft=draft if mode != "plain" else None)
    if mode == "paused":
        gw._batcher.set_spec_level(2)
    return gw


def _requests(n, seed=0, sampled=True):
    """A seeded multiset: prompts of 3-19 tokens, 3-12 new tokens, every
    other request sampled at a pinned seed."""
    rng = np.random.default_rng(seed)
    out = []
    for i in range(n):
        prompt = rng.integers(0, 256,
                              (int(rng.integers(3, 20)),)).astype(np.int32)
        kw = {"max_new_tokens": int(rng.integers(3, 13))}
        if sampled and i % 2:
            kw.update(do_sample=True, temperature=0.9, seed=1000 + i)
        out.append((prompt, kw))
    return out


def _serve_all(gw, requests, timeout=180):
    """Everything queued before the scheduler starts: the admission order
    is the submission order, whatever the machine's timing."""
    handles = [gw.submit(p, **kw) for p, kw in requests]
    gw.start()
    return [h.result(timeout=timeout) for h in handles]


def _idle(gw, timeout=30.0):
    """Wait for the scheduler to have nothing queued, live or in flight, on
    two polls in a row: the loop clears ``_in_flight`` BEFORE it harvests
    the last tick, so one quiet poll can fall between the two and a
    snapshot taken then lacks that tick."""
    t_end = time.monotonic() + timeout
    quiet = 0
    while time.monotonic() < t_end:
        with gw._cond:
            busy = bool(gw._queue or gw._active)
        quiet = 0 if busy or gw._in_flight is not None else quiet + 1
        if quiet == 2:
            return
        time.sleep(0.005)
    raise AssertionError("the gateway never went idle")


# ------------------------------------------------------- (a) same tokens

@pytest.mark.parametrize("mode", ["plain", "spec", "paused"])
def test_pipelined_loop_gives_every_request_the_synchronous_tokens(
        engine, draft, mode):
    """The same seeded multiset, greedy and sampled rows mixed, through
    the loop with a tick in flight and through launch-pull-harvest in one
    pass: token for token the same, and only the first overlaps."""
    requests = _requests(9)
    outs, snaps = [], []
    for cls in (ServingGateway, SyncGateway):
        gw = _gateway(cls, engine, draft, mode)
        outs.append(_serve_all(gw, requests))
        gw.shutdown()
        snaps.append(gw.snapshot())
    for (_, kw), got, want in zip(requests, *outs):
        assert got.shape == (kw["max_new_tokens"],)
        np.testing.assert_array_equal(got, want)
    piped, sync = snaps
    assert piped["completed"] == sync["completed"] == 9
    assert piped["tokens_out"] == sync["tokens_out"] == sum(
        kw["max_new_tokens"] for _, kw in requests)
    assert piped["ticks_overlapped"] > 0 and piped["late_row_ticks"] == 9
    assert sync["ticks_overlapped"] == sync["late_row_ticks"] == 0
    assert all(v <= 1 for v in piped["compile_counts"].values())
    if mode == "spec":
        assert piped["spec_rounds"] > 0
        assert piped["spec_accepted"] == sync["spec_accepted"]


def test_a_row_at_the_slots_end_runs_its_late_tick_harmlessly(engine, draft):
    """prompt + reply == max_len: the late tick's write falls past the
    slot and is dropped; the row's neighbour and the row's next tenant
    decode what they decode alone."""
    rng = np.random.default_rng(5)
    full = (rng.integers(0, 256, (52,)).astype(np.int32),
            {"max_new_tokens": 12})
    requests = [full] + _requests(4, seed=6, sampled=False)
    gw = _gateway(ServingGateway, engine, draft, "plain", slots=2)
    outs = _serve_all(gw, requests)
    gw.shutdown()
    for (prompt, kw), out in zip(requests, outs):
        s = engine.start_session(batch=1, max_len=64)
        s.append(jnp.asarray(prompt[None]))
        np.testing.assert_array_equal(
            out, np.asarray(s.generate(
                max_new_tokens=kw["max_new_tokens"]))[0])


# ------------------------------------------- (b) the re-admitted row's tick

def test_a_readmitted_row_never_receives_the_dead_tenants_token(engine,
                                                                draft):
    """One slot, A then B queued behind it.  A finishes at harvest n with
    tick n+1 already in flight for its row; B is admitted into the row
    before tick n+1 is pulled.  That token is dropped: not appended to B,
    not B's first token, not counted."""
    tracer = Tracer(name="serving")
    gw = ServingGateway(engine, {**SERVING, "slots": 1}, autostart=False,
                        tracer=tracer)
    rng = np.random.default_rng(11)
    pa, pb = (rng.integers(0, 256, (n,)).astype(np.int32) for n in (7, 9))
    ha = gw.submit(pa, max_new_tokens=3)
    hb = gw.submit(pb, max_new_tokens=4)
    gw.start()
    oa, ob = ha.result(timeout=120), hb.result(timeout=120)
    gw.shutdown()
    snap = gw.snapshot()
    for prompt, out in ((pa, oa), (pb, ob)):
        s = engine.start_session(batch=1, max_len=64)
        s.append(jnp.asarray(prompt[None]))
        np.testing.assert_array_equal(
            out, np.asarray(s.generate(max_new_tokens=len(out)))[0])
    # 3 + 4 ticks deliver, one late tick each: A's under B's admission,
    # B's pulled before the loop waits
    assert snap["tokens_out"] == 7 and snap["ticks"] == 9
    assert snap["late_row_ticks"] == 2
    assert gw.metrics.active_slot_ticks == snap["ticks"]
    spans = tracer.spans()
    admit_b = [s for s in spans if s.name == "serve.admit"
               and s.args["rid"] == hb.request_id][0]
    pulls = sorted((s for s in spans if s.name == "serve.pull"
                    and s.t0 >= admit_b.t0 + admit_b.dur),
                   key=lambda s: s.t0)
    # after B's admission: the pull of A's late tick, of B's four ticks,
    # of B's late tick
    assert len(pulls) == 6
    assert hb.t_first_token >= pulls[1].t0 + pulls[1].dur
    firsts = [s for s in spans if s.name == "serve.first_token"]
    assert [s.args["rid"] for s in firsts] == [ha.request_id, hb.request_id]
    assert firsts[1].t0 + firsts[1].dur == hb.t_first_token


# ------------------------------------------------------------- (c) drain

class _FailFromTick(Fault):
    """Raise at ``serve.decode_tick`` once ``n`` ticks were harvested: a
    tick is in flight by then."""

    def __init__(self, n):
        self.n = n

    def fire(self, point, **ctx):
        if ctx.get("tick", 0) >= self.n:
            raise FaultError(f"injected failure at {point}")


def _drain_last_request(gw):
    out = gw.submit(np.arange(5, dtype=np.int32),
                    max_new_tokens=4).result(timeout=120)
    assert out.shape == (4,)
    _idle(gw)
    snap = gw.snapshot()
    assert (snap["ticks"], snap["late_row_ticks"]) == (5, 1)


def _drain_cancel(gw):
    h = gw.submit(np.arange(5, dtype=np.int32), max_new_tokens=50)
    while h.tokens_out < 3:
        time.sleep(0.005)
    assert gw.cancel(h)
    with pytest.raises(RequestCancelled):
        h.result(timeout=120)
    _idle(gw)
    assert gw.snapshot()["cancelled"] == 1


def _drain_deadline(gw):
    with fault_injection.inject("serve.decode_tick",
                                DelaySeconds(0.1, n=None)):
        h = gw.submit(np.arange(5, dtype=np.int32), max_new_tokens=50,
                      deadline_s=0.35)
        with pytest.raises(RequestTimedOut):
            h.result(timeout=120)
        _idle(gw)
    assert gw.snapshot()["timeouts"] == 1


def _drain_shutdown(drain):
    def case(gw):
        handles = [gw.submit(np.arange(4 + i, dtype=np.int32),
                             max_new_tokens=40) for i in range(4)]
        while handles[0].tokens_out < 2:
            time.sleep(0.005)
        gw.shutdown(drain=drain, timeout=120)
        assert not gw._thread.is_alive()
        if drain:
            assert all(h.result(timeout=1).shape == (40,) for h in handles)
        else:
            for h in handles:
                with pytest.raises(RequestFailed):
                    h.result(timeout=1)
    return case


def _drain_loop_dies(gw):
    with fault_injection.inject("serve.decode_tick", _FailFromTick(3)):
        handles = [gw.submit(np.arange(4 + i, dtype=np.int32),
                             max_new_tokens=40) for i in range(3)]
        for h in handles:
            with pytest.raises(RequestFailed, match="loop died"):
                h.result(timeout=120)
        gw._thread.join(timeout=30)
    assert not gw._thread.is_alive()


@pytest.mark.filterwarnings(
    "ignore::pytest.PytestUnhandledThreadExceptionWarning")
@pytest.mark.parametrize("case", [
    _drain_last_request, _drain_cancel, _drain_deadline,
    _drain_shutdown(True), _drain_shutdown(False), _drain_loop_dies],
    ids=["last_request", "cancel", "deadline", "shutdown_drain",
         "shutdown_no_drain", "loop_dies"])
def test_no_tick_is_left_unpulled_and_no_handle_blocked(engine, draft, case):
    gw = _gateway(ServingGateway, engine, draft, "plain", slots=2)
    gw.start()
    case(gw)
    assert gw._in_flight is None
    if gw._thread.is_alive():
        # still serving after the drain, from a clean slot batch
        out = gw.submit(np.arange(6, dtype=np.int32),
                        max_new_tokens=3).result(timeout=120)
        assert out.shape == (3,)
        gw.shutdown()
        assert gw._in_flight is None
    snap = gw.snapshot()
    # every tick launched was pulled and harvested, or the loop died with
    # at most the one in flight discarded
    assert snap["ticks"] > 0 and snap["host_syncs"] >= snap["ticks"]


def test_a_session_retired_under_a_tick_in_flight_readmits_its_own_kv(
        engine, draft):
    """A session finishes turn 1 while a neighbour keeps the loop busy, so
    its row is retired with a tick in flight that has written one cell
    past its frontier; turn 2 re-admits the parked KV and decodes what an
    uninterrupted session decodes."""
    gw = ServingGateway(engine, {**SERVING, "slots": 2, "paging": {
        "enabled": True, "block_tokens": 8}})
    rng = np.random.default_rng(21)
    p1 = rng.integers(0, 256, (11,)).astype(np.int32)
    t2 = rng.integers(0, 256, (5,)).astype(np.int32)
    neighbour = gw.submit(rng.integers(0, 256, (6,)).astype(np.int32),
                          max_new_tokens=40)
    o1 = gw.submit(p1, max_new_tokens=5, session_id="s").result(timeout=120)
    assert not neighbour.done()
    o2 = gw.submit(np.concatenate([p1, o1, t2]), max_new_tokens=6,
                   session_id="s").result(timeout=120)
    neighbour.result(timeout=120)
    gw.shutdown()
    snap = gw.snapshot()
    assert snap["readmits"] == 1 and snap["readmit_misses"] == 1
    s = engine.start_session(batch=1, max_len=64)
    for turn, n, got in ((p1, 5, o1), (t2, 6, o2)):
        s.append(jnp.asarray(turn[None]))
        np.testing.assert_array_equal(
            got, np.asarray(s.generate(max_new_tokens=n))[0])


# ------------------------------------------- (d) the synchronous entries

def test_tick_is_launch_then_pull_and_returns_host_tokens(engine):
    """``tick()`` hands back numpy tokens at once, and a twin batcher
    driven through ``launch`` / ``pull`` by hand decodes the same tokens
    to the same frontier logits."""
    cfg = ServingConfig.from_dict({**SERVING, "slots": 2})
    a, b = SlotBatcher(engine, cfg), SlotBatcher(engine, cfg)
    with pytest.raises(RuntimeError, match="before any admission"):
        a.launch()
    rng = np.random.default_rng(2)
    for row in range(2):
        prompt = rng.integers(0, 256, (5 + row,)).astype(np.int32)
        for batcher in (a, b):
            batcher.admit(row, prompt, jax.random.PRNGKey(row), row == 0,
                          0.8)
    for _ in range(4):
        got = a.tick()
        pending = b.launch()
        assert isinstance(got, np.ndarray) and got.dtype == np.int32
        assert isinstance(pending, jax.Array)
        np.testing.assert_array_equal(got, b.pull(pending))
        np.testing.assert_array_equal(np.asarray(a._last),
                                      np.asarray(b._last))


def test_probe_logits_after_pipelined_traffic_gives_a_fresh_servers_logits(
        engine, draft):
    """``probe_logits`` drives ``tick()``: after a run that kept ticks in
    flight, and its drain, the stopped server's slot path gives the
    logits of a server that never served."""
    prompts = [np.arange(1, 12, dtype=np.int32),
               np.arange(3, 9, dtype=np.int32)]
    used = _gateway(ServingGateway, engine, draft, "plain")
    _serve_all(used, _requests(5, seed=3))
    with pytest.raises(RuntimeError, match="shut the gateway down"):
        used.probe_logits(prompts, 3)
    used.shutdown()
    fresh = _gateway(ServingGateway, engine, draft, "plain")
    replies, logits = used.probe_logits(prompts, 3)
    want_replies, want = fresh.probe_logits(prompts, 3)
    assert replies == want_replies
    for got, ref in zip(logits, want):
        assert got.shape == (4, CFG.padded_vocab)
        np.testing.assert_array_equal(got, ref)
    fresh.shutdown()


# ---------------------------------------------------------- (e) counters

def test_counters_of_a_backlog_that_is_never_empty(engine, draft):
    """Driven pass by pass from this thread (admit, then one decode
    pass): while requests wait, every tick is launched with every slot
    bound and, but for the first, with its predecessor un-pulled; every
    completion costs exactly one late row-tick."""
    slots = 2
    gw = _gateway(ServingGateway, engine, draft, "plain", slots=slots)
    requests = _requests(14, seed=9, sampled=False)
    handles = [gw.submit(p, **kw) for p, kw in requests]
    while gw._queue:
        gw._admit_ready()
        gw._decode_tick()
    busy = gw.snapshot()
    assert busy["ticks"] > 20
    assert gw.metrics.active_slot_ticks == busy["ticks"] * slots
    assert busy["slot_occupancy"] == 1.0
    assert busy["overlap_share"] > 0.9
    assert busy["ticks_overlapped"] == busy["ticks"] - 1
    while gw._active or gw._in_flight is not None:
        gw._decode_tick()
    done = gw.snapshot()
    assert all(h.done() for h in handles)
    assert done["completed"] == done["late_row_ticks"] == 14
    assert done["tokens_out"] == sum(kw["max_new_tokens"]
                                     for _, kw in requests)
    row_ticks = gw.metrics.active_slot_ticks
    assert done["tokens_out"] + done["late_row_ticks"] == row_ticks
    assert done["late_row_share"] == 14 / row_ticks
    assert done["overlap_share"] == done["ticks_overlapped"] / done["ticks"]
    assert done["ticks_overlapped"] == done["ticks"] - 1
    gw.shutdown()


def test_the_report_prints_both_shares_on_the_serving_line(engine, draft,
                                                           tmp_path, capsys):
    """``overlap_share`` and ``late_row_share`` ride the journal's
    ``serve.tick`` events; ``run_report.py`` prints the newest beside the
    gauges of the metrics row."""
    import importlib.util
    import json
    import os
    from deepspeed_tpu.runtime.supervision.events import (EventJournal,
                                                          EventKind)
    from deepspeed_tpu.telemetry.metrics import (MetricsRegistry,
                                                 MetricsSampler)
    journal = EventJournal(str(tmp_path / "events.jsonl"))
    gw = ServingGateway(engine, {**SERVING, "journal_every_ticks": 1},
                        journal=journal, autostart=False)
    sampler = MetricsSampler(MetricsRegistry(),
                             str(tmp_path / "metrics.jsonl"))
    gw.attach_metrics(sampler)
    _serve_all(gw, _requests(5, seed=4, sampled=False))
    gw.shutdown()
    sampler.sample(step=1)
    last = [e for e in journal.read()
            if e["kind"] == EventKind.SERVE_TICK][-1]
    snap = gw.snapshot()
    assert last["overlap_share"] == round(snap["overlap_share"], 4) > 0.5
    assert last["late_row_share"] == round(snap["late_row_share"], 4) > 0
    root = os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))))
    spec = importlib.util.spec_from_file_location(
        "run_report", os.path.join(root, "scripts", "run_report.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    assert mod.main([str(tmp_path)]) == 0
    line = [l for l in capsys.readouterr().out.splitlines()
            if "serving:" in l][0]
    assert f"live_block_share {round(snap['live_block_share'], 4)}" in line
    assert f"overlap_share {last['overlap_share']}" in line
    assert f"late_row_share {last['late_row_share']}" in line
    # ... and the launches an admission took: one each
    assert last["launches_per_admission"] == 1.0
    assert "launches_per_admission 1.0" in line
    assert mod.main([str(tmp_path), "--json"]) == 0
    serving = json.loads(capsys.readouterr().out)["metrics"][
        "metrics.jsonl"]["serving"]
    assert serving["overlap_share"] == last["overlap_share"]
    assert serving["late_row_share"] == last["late_row_share"]
