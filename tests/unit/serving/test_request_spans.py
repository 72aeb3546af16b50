"""The serving cycle seen from inside: the spans one request leaves on the
gateway's tracer, keyed by its id, its waits apart from the scheduler's
phases, and ``probe_logits``, the public way to the slot path's logits."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import deepspeed_tpu
from deepspeed_tpu.models import gpt
from deepspeed_tpu.telemetry.spans import WAIT_THREAD, Tracer

CFG = gpt.GPTConfig(vocab_size=256, max_seq_len=128, n_layer=2, n_head=4,
                    d_model=64, dtype=jnp.float32, vocab_round_to=128)
CHUNK = 8
SERVING = {"slots": 2, "max_len": 64, "prefill_chunk": CHUNK,
           "queue_capacity": 16}


@pytest.fixture(scope="module")
def engine():
    params = gpt.init(CFG, jax.random.PRNGKey(0))
    return deepspeed_tpu.init_inference(model=(CFG, params),
                                        config={"dtype": "float32"})


def _inside(child, parent):
    return (child.tid == parent.tid and child.t0 >= parent.t0
            and child.t0 + child.dur <= parent.t0 + parent.dur
            and child.depth == parent.depth + 1)


def test_one_request_leaves_its_spans_keyed_by_its_id(engine):
    tracer = Tracer(name="serving")
    gw = engine.serve(config=SERVING, tracer=tracer)
    prompt = np.arange(1, CHUNK + 2, dtype=np.int32)    # chunk + 1 tokens
    h = gw.submit(prompt, max_new_tokens=3)
    assert h.result(timeout=120).shape == (3,)
    gw.shutdown()
    snap = gw.snapshot()
    spans = tracer.spans()
    by = lambda name: [s for s in spans if s.name == name]
    one = lambda name: by(name)[0] if len(by(name)) == 1 else pytest.fail(
        f"{len(by(name))} {name} spans")

    queue, admit, first = (one("serve.queue"), one("serve.admit"),
                           one("serve.first_token"))
    assert queue.args == {"rid": h.request_id, "priority": 0, "depth": 0}
    assert admit.args["rid"] == first.args["rid"] == h.request_id
    # the request's wait for its first token, piece by piece: the three
    # spans leave out only the journal line between t_admit and the end of
    # the admission span
    assert queue.t0 == h.t_submit
    assert queue.t0 + queue.dur == pytest.approx(admit.t0, abs=1e-3)
    assert first.t0 == h.t_admit
    assert first.t0 + first.dur == h.t_first_token
    assert queue.dur + admit.dur + first.dur == pytest.approx(
        h.t_first_token - h.t_submit, abs=1e-3)

    # admission's one child, where the work happens: ``serve.prefill``
    # around the ONE launch that runs the chunks, writes the slot and binds
    # the row, with the arguments it always had and the passes the program
    # ran for them (in a slot of 64 none is wider than the chunk, and under
    # the ridge none is narrower), on the slot's own row of the dense pool
    # (``in_place``).  No
    # batch-1 cache is allocated by the host, no chunk is a launch of its
    # own and the slot write is no step of its own: their spans are not
    # entered
    prefill = one("serve.prefill")
    assert _inside(prefill, admit)
    assert prefill.args == {"tokens": CHUNK + 1, "start": 0, "chunk": CHUNK,
                            "padded": 2 * CHUNK, "chunks": 2, "passes": 2,
                            "wide": 0, "narrow": 0, "in_place": 1}
    assert [s.name for s in spans if _inside(s, admit)] == ["serve.prefill"]
    for name in ("serve.cache_alloc", "serve.prefill_chunk",
                 "serve.slot_write"):
        assert by(name) == []
    assert (snap["admitted"], snap["admit_launches"],
            snap["launches_per_admission"]) == (1, 1, 1.0)

    # every tick: the pull inside it, the harvest after it.  Three ticks
    # deliver the reply; the fourth was launched before the third's
    # harvest finished the request, ran its row for nothing and is pulled
    # before the loop waits
    ticks, pulls, harvests = (by("serve.tick"), by("serve.pull"),
                              by("serve.harvest"))
    assert len(ticks) == len(pulls) == len(harvests) == snap["ticks"] == 4
    assert (snap["tokens_out"], snap["late_row_ticks"],
            snap["ticks_overlapped"]) == (3, 1, 3)
    for tick, pull, harvest in zip(ticks, pulls, harvests):
        assert _inside(pull, tick)
        assert harvest.t0 >= tick.t0 + tick.dur and harvest.args == {
            "live": 1}
    assert first.t0 + first.dur <= harvests[0].t0 + harvests[0].dur

    # the two waits sit on the synthetic line, the phases on the scheduler's
    assert queue.wait and first.wait and not admit.wait
    assert queue.thread == first.thread == WAIT_THREAD != admit.thread


def test_requests_keep_their_own_ids_and_only_a_prefix_build_runs_chunk_by_chunk(
        engine):
    """Two requests over one pooled prefix: each admission carries its own
    ``rid``.  The first builds the prefix, which hands a batch-1 cache BACK
    and so runs launch by launch (``build_prefix``: a fresh cache, a
    ``serve.prefill_chunk`` a chunk), and then continues it in its one
    launch; the second only continues it."""
    tracer = Tracer(name="serving")
    gw = engine.serve(config=SERVING, tracer=tracer)
    rng = np.random.default_rng(3)
    prefix = rng.integers(1, 256, (2 * CHUNK,)).astype(np.int32)
    handles = []
    for n in (3, 5):
        tail = rng.integers(1, 256, (n,)).astype(np.int32)
        handles.append(gw.submit(np.concatenate([prefix, tail]),
                                 max_new_tokens=2, prefix_len=2 * CHUNK))
        handles[-1].result(timeout=120)
    gw.shutdown()
    spans = tracer.spans()
    by = lambda name: [s for s in spans if s.name == name]
    for name in ("serve.queue", "serve.admit", "serve.first_token"):
        assert [s.args["rid"] for s in by(name)] == [
            h.request_id for h in handles]
    admits = by("serve.admit")
    build, first, second = by("serve.prefill")
    assert [(p.args["tokens"], p.args["start"], p.args["padded"],
             p.args["chunks"], p.args["passes"], p.args["wide"],
             p.args["narrow"])
            for p in (build, first, second)] == [
        (2 * CHUNK, 0, 2 * CHUNK, 2, 2, 0, 0),
        (3, 2 * CHUNK, CHUNK, 1, 1, 0, 0), (5, 2 * CHUNK, CHUNK, 1, 1, 0, 0)]
    assert _inside(build, admits[0]) and _inside(first, admits[0])
    assert _inside(second, admits[1])
    # the builder's children: the batch-1 cache it allocates and a launch a
    # chunk; an admission's ``serve.prefill`` has none
    (alloc,) = by("serve.cache_alloc")
    assert _inside(alloc, build)
    assert alloc.args["bytes"] == 2 * CFG.n_layer * 64 * CFG.d_model * 4
    chunks = sorted(by("serve.prefill_chunk"), key=lambda s: s.t0)
    assert [c.args for c in chunks] == [
        {"index": 0, "pos": 0, "program": "prefill"},
        {"index": 1, "pos": CHUNK, "program": "extend"}]
    assert all(_inside(c, build) for c in chunks)
    assert by("serve.slot_write") == []
    snap = gw.snapshot()
    assert (snap["admitted"], snap["admit_launches"]) == (2, 2 + 1 + 1)


def test_the_prefill_span_counts_the_ladders_passes(engine, monkeypatch):
    """``serve.prefill`` carries ``passes`` (the chunk passes the admission
    program ran) and ``wide`` (the tokens of its passes wider than
    ``chunk``) beside the arguments it had, which keep their meaning: with
    the wide widths patched to 32 and 16 over chunks of 8, a prompt of 63
    tokens is two passes of 32 where it was 8 chunks (on the slot's own row
    of the pool the wide passes start with the prompt: no ``prefill`` takes
    its first chunk), ``in_place`` 1; one that continues a pooled prefix
    keeps the row cache (``in_place`` 0) and starts with its wide passes
    too; the prefix's builder runs a launch a chunk, none wide."""
    from deepspeed_tpu.serving import batcher
    monkeypatch.setattr(batcher, "WIDE_PASSES", (32, 16))
    tracer = Tracer(name="serving")
    gw = engine.serve(config=SERVING, tracer=tracer)
    rng = np.random.default_rng(9)
    prompts = [rng.integers(1, 256, (n,)).astype(np.int32)
               for n in (CHUNK + 1, 24, 40, 63)]
    for p in prompts:
        gw.submit(p, max_new_tokens=1).result(timeout=120)
    gw.submit(prompts[-1][:60], max_new_tokens=1,
              prefix_len=CHUNK + 3).result(timeout=120)
    gw.shutdown()
    spans = [s.args for s in tracer.spans() if s.name == "serve.prefill"]
    assert [(a["tokens"], a["start"], a["chunk"], a["padded"], a["chunks"],
             a["passes"], a["wide"], a["in_place"]) for a in spans] == [
        (9, 0, 8, 16, 2, 1, 16, 1), (24, 0, 8, 24, 3, 2, 16, 1),
        (40, 0, 8, 40, 5, 2, 32, 1), (63, 0, 8, 64, 8, 2, 64, 1),
        (11, 0, 8, 16, 2, 2, 0, 0),     # the prefix, built chunk by chunk
        (49, 11, 8, 56, 7, 3, 48, 0)]   # 32, 16 and one token in a chunk
    assert gw.snapshot()["recompiles"] == 0


def test_the_spans_carry_a_narrow_last_pass_and_the_rows_computed(
        engine, monkeypatch):
    """``serve.prefill`` and ``serve.device`` carry ``narrow``, the width of
    a last pass narrower than ``chunk`` (0: the chunk), and ``padded`` is
    the rows the passes COMPUTED: with the ridge patched to 2 under chunks
    of 8 in slots of 64 (widths 8 and 4), a prompt of 3 tokens is one pass of 4 rows, 9
    tokens a chunk and 4 rows, 13 two chunks, 27 three chunks and 4 rows,
    and after a pooled prefix the same rule holds from its end; ``chunks``
    and ``chunk`` keep their meaning (the padded prompt's); the prefix's
    builder runs its chunks whole."""
    from deepspeed_tpu.serving import batcher
    monkeypatch.setattr(batcher, "NARROW_FLOOR", 2)
    monkeypatch.setattr(batcher, "NARROW_SLOT_CHUNKS", 8)
    tracer = Tracer(name="serving")
    gw = engine.serve(config=SERVING, tracer=tracer)
    rng = np.random.default_rng(11)
    prompts = [rng.integers(1, 256, (n,)).astype(np.int32)
               for n in (3, 9, 13, 27)]
    for p in prompts:
        gw.submit(p, max_new_tokens=1).result(timeout=120)
    gw.submit(prompts[-1][:22], max_new_tokens=1,
              prefix_len=CHUNK + 3).result(timeout=120)
    gw.shutdown()
    keys = ("tokens", "start", "chunk", "padded", "chunks", "passes", "wide",
            "narrow")
    spans = [tuple(s.args[k] for k in keys) for s in tracer.spans()
             if s.name == "serve.prefill"]
    assert spans == [
        (3, 0, 8, 4, 1, 1, 0, 4), (9, 0, 8, 12, 2, 2, 0, 4),
        (13, 0, 8, 16, 2, 2, 0, 0), (27, 0, 8, 28, 4, 4, 0, 4),
        (11, 0, 8, 16, 2, 2, 0, 0),     # the prefix, built chunk by chunk
        (11, 11, 8, 12, 2, 2, 0, 4)]    # a chunk and three tokens in 4 rows
    device = [s for s in tracer.spans() if s.name == "serve.device"
              and s.args["program"].startswith("admit")]
    # the launches' device spans carry the same counts (the prefix's
    # builder is no watched launch), less the host's ``start`` and ``chunks``
    both = [i for i, k in enumerate(keys) if k not in ("start", "chunks")]
    assert [tuple(d.args[keys[i]] for i in both)
            for d in sorted(device, key=lambda s: s.t0)] == [
        tuple(s[i] for i in both) for s in spans[:4] + spans[5:]]
    assert gw.snapshot()["recompiles"] == 0


def test_a_gateway_without_a_tracer_serves_and_keeps_no_record(engine):
    gw = engine.serve(config=SERVING)
    h = gw.submit(np.arange(1, 6, dtype=np.int32), max_new_tokens=2)
    assert len(h.result(timeout=120)) == 2
    gw.shutdown()
    assert not gw.tracer.enabled
    assert gw.tracer.spans() == [] and gw.tracer.aggregates() == {}


def test_probe_logits_matches_the_full_forward_pass(engine):
    """Chunked prefill and greedy ticks through the gateway's own slot
    path, against ``gpt.apply`` on prompt + reply at the same positions."""
    gw = engine.serve(config=SERVING, autostart=False)
    rng = np.random.default_rng(5)
    prompts = [rng.integers(1, 256, (n,)).astype(np.int32)
               for n in (5, CHUNK + 1)]
    ticks = 3
    replies, logits = gw.probe_logits(prompts, ticks)
    assert [len(r) for r in replies] == [ticks, ticks]
    for p, reply, got in zip(prompts, replies, logits):
        assert got.shape == (1 + ticks, CFG.padded_vocab)
        assert got.dtype == np.float32
        full = np.concatenate([p, np.asarray(reply, np.int32)])[None]
        ref = np.asarray(gpt.apply(engine.params, jnp.asarray(full), CFG))[0]
        np.testing.assert_allclose(got, ref[len(p) - 1:len(p) + ticks],
                                   atol=2e-4, rtol=2e-4)
        # greedy: each reply token is the argmax of the logits before it
        assert reply == [int(np.argmax(l[:CFG.vocab_size]))
                         for l in got[:ticks]]
    # the slots are free again, and it asks for a stopped scheduler
    assert not np.asarray(gw._batcher.active).any()
    with pytest.raises(ValueError, match="3 prompts for 2 slots"):
        gw.probe_logits(prompts + prompts[:1], 1)
    gw.start()
    with pytest.raises(RuntimeError, match="shut the gateway down first"):
        gw.probe_logits(prompts, 1)
    gw.shutdown()
    assert gw.probe_logits(prompts[:1], 1)[0] == [replies[0][:1]]


def _device_watchers(but=()):
    import threading
    return [t for t in threading.enumerate()
            if t.name.startswith("device-spans:") and t not in but]


@pytest.mark.parametrize("on", [True, False], ids=["tracer_on", "tracer_off"])
def test_every_admission_and_every_tick_leaves_a_device_span(engine, on):
    """``serve.device``: one span a launch, the launch's time on the device
    as the registry's watcher reconstructs it, apart from every thread's
    phases; with the tracer off no watcher thread is started at all."""
    before = _device_watchers()
    tracer = Tracer(enabled=on, name="serving")
    gw = engine.serve(config=SERVING, tracer=tracer)
    rng = np.random.default_rng(3)
    handles = [gw.submit(rng.integers(1, 256, (n,)).astype(np.int32),
                         max_new_tokens=3) for n in (5, CHUNK + 1, 20, 3)]
    for h in handles:
        h.result(timeout=120)
    started = _device_watchers(before)
    gw.shutdown()               # waits for the last launches' stamps
    snap = gw.snapshot()
    if not on:
        assert started == [] and tracer.spans() == []
        assert gw._batcher.registry.device_spans_lost == 0
        return
    assert [t.name for t in started] == ["device-spans:serving"]
    spans = tracer.spans()
    device = sorted((s for s in spans if s.name == "serve.device"),
                    key=lambda s: s.t0)
    assert all(s.thread == WAIT_THREAD and s.wait and s.depth == 0
               and s.dur >= 0 and s.args["waited"] >= 0 for s in device)
    # launches of one registry never overlap: the device runs them in order
    for a, b in zip(device, device[1:]):
        assert a.t0 + a.dur <= b.t0
    admits = [s for s in device if s.args["program"] == "admit"]
    ticks = [s for s in device if s.args["program"] == "tick"]
    assert len(admits) + len(ticks) == len(device)
    assert len(admits) == snap["admitted"] == 4
    assert len(ticks) == snap["ticks"] > 0
    # an admission's span carries ``serve.prefill``'s counts and its slot
    prefills = sorted((s for s in spans if s.name == "serve.prefill"),
                      key=lambda s: s.t0)
    keys = ("tokens", "padded", "passes", "wide", "narrow", "chunk")
    assert [[a.args[k] for k in keys] for a in admits] == [
        [p.args[k] for k in keys] for p in prefills]
    assert [a.args["padded"] for a in admits] == [8, 16, 24, 8]
    assert {a.args["slot"] for a in admits} <= {0, 1}
    # it begins no earlier than the host's launch of it could have returned
    for a, p in zip(admits, prefills):
        assert p.t0 <= a.t0 + a.dur and a.t0 >= p.t0
    assert gw._batcher.registry.device_spans_lost == 0
