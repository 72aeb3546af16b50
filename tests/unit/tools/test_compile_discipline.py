"""Tier-1 e2e for the compile-discipline runtime gate: a short train loop
and a 3-slot serving session run under ``CompileWatch`` and must show ZERO
post-warmup compiles; un-caching a jitted program makes the gate fail with
the program name and arg-shape signature in the ``perf.recompile`` journal
line.  (The static half — the dslint rules — is pinned by
``test_dslint_rules.py`` / ``test_dslint_tree.py``.)"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import deepspeed_tpu
from deepspeed_tpu.models import gpt
from deepspeed_tpu.runtime.supervision.events import EventJournal, read_events
from deepspeed_tpu.utils.compile_watch import (CompiledProgramRegistry,
                                               CompileWatch, RecompileError)
from tests.unit.common import base_config, random_tokens, tiny_model

SEQ = 16


# ------------------------------------------------------------- watch unit

def test_watch_detects_shape_churn_with_name_and_shapes(tmp_path):
    """The registry wrapper sees a cache-size increase and the watch turns
    it into a perf.recompile journal line carrying program + shapes."""
    reg = CompiledProgramRegistry("unit")
    prog = reg.register("add_one", jax.jit(lambda x: x + 1))
    journal = EventJournal(str(tmp_path / "events.jsonl"))
    with CompileWatch(reg, journal=journal) as watch:
        prog(jnp.zeros((4,), jnp.float32))
        watch.mark_warm()
        prog(jnp.ones((4,), jnp.float32))     # same shape: cache hit
        assert watch.recompiles == []
        prog(jnp.zeros((8,), jnp.float32))    # shape churn: recompile
        new = watch.check()
    assert [e.program for e in new] == ["add_one"]
    assert "[8]" in new[0].shapes
    events = read_events(journal.path, kind="perf.recompile")
    assert len(events) == 1
    assert events[0]["program"] == "add_one"
    assert "[8]" in events[0]["shapes"]
    with pytest.raises(RecompileError, match="add_one"):
        watch.assert_no_recompiles()


def test_watch_counts_reregistration_as_recompile():
    """Un-caching (re-registering the same name with a fresh jit) cannot
    hide: the retired program's compiles keep counting."""
    reg = CompiledProgramRegistry("unit")
    prog = reg.register("mul", jax.jit(lambda x: x * 2))
    prog(jnp.zeros((4,)))
    assert reg.counts()["mul"] == 1
    # the bug under test: a FRESH closure per build (jit cannot share its
    # cache across distinct function objects, so this re-compiles)
    prog2 = reg.register("mul", jax.jit(lambda x: x * 2))
    prog2(jnp.zeros((4,)))
    assert reg.counts()["mul"] == 2
    assert [e.count for e in reg.events] == [1, 2]


# ------------------------------------------------------------- train loop

def test_train_loop_zero_recompiles_after_warmup(tmp_path):
    journal = EventJournal(str(tmp_path / "events.jsonl"))
    engine, *_ = deepspeed_tpu.initialize(
        model=tiny_model(), config=base_config(micro_batch=1, gas=1),
        rng=jax.random.PRNGKey(0))
    with CompileWatch(engine.compile_registry, journal=journal) as watch:
        # warmup is ONE step: the state leaves the step with the sharding
        # it came in with, so nothing is left to settle in a second one
        engine.forward(random_tokens(8, SEQ, seed=0))
        engine.backward()
        engine.step()
        watch.mark_warm()
        for i in range(4):              # steady state: nothing compiles
            engine.forward(random_tokens(8, SEQ, seed=10 + i))
            engine.backward()
            engine.step()
        watch.assert_no_recompiles("the steady-state train loop")
    assert read_events(journal.path, kind="perf.recompile") == []
    counts = engine.compile_counts()
    assert counts["micro"] == 1 and counts["apply"] == 1, counts
    # the boundary-step overflow pull is the sanctioned (counted) sync
    syncs = read_events(journal.path, kind="perf.host_sync")
    assert any(e["label"] == "step.overflow" and e["count"] == 5
               for e in syncs)


# ---------------------------------------------------------------- serving

CFG = gpt.GPTConfig(vocab_size=256, max_seq_len=128, n_layer=2, n_head=4,
                    d_model=64, dtype=jnp.float32, vocab_round_to=128)


def _inference_engine():
    params = gpt.init(CFG, jax.random.PRNGKey(0))
    return deepspeed_tpu.init_inference(model=(CFG, params),
                                        config={"dtype": "float32"})


def test_serving_session_zero_recompiles(tmp_path):
    """10 heterogeneous requests through 3 slots: steady-state compile
    counts stay <= 1 per program and the gateway metrics agree."""
    journal = EventJournal(str(tmp_path / "events.jsonl"))
    engine = _inference_engine()
    gw = engine.serve(config={"slots": 3, "max_len": 64,
                              "prefill_chunk": 8}, journal=journal)
    rng = np.random.default_rng(0)
    handles = []
    for i in range(10):
        prompt = rng.integers(1, 256,
                              (int(rng.integers(3, 24)),)).astype(np.int32)
        handles.append(gw.submit(prompt,
                                 max_new_tokens=int(rng.integers(2, 9)),
                                 do_sample=bool(i % 2), temperature=0.8,
                                 seed=i))
    for h in handles:
        h.result(timeout=300.0)
    # after the stop: the loop pulls the tick it has in flight first
    gw.shutdown()
    snap = gw.snapshot()
    assert snap["recompiles"] == 0
    assert all(v <= 1 for v in snap["compile_counts"].values()), \
        snap["compile_counts"]
    # one sanctioned d2h pull per tick, counted
    assert snap["host_syncs"] == snap["ticks"] > 0
    assert read_events(journal.path, kind="perf.recompile") == []
    # the close journals the sanctioned host-sync totals as a debug kind
    syncs = read_events(journal.path, kind="perf.host_sync")
    assert syncs and syncs[-1]["label"] == "serving.tick"


def test_uncached_program_fails_the_gate(tmp_path):
    """Re-building the batcher's programs per tick (the exact bug the
    static rule exists to prevent) must trip the runtime gate, naming the
    program and its arg shapes in the perf.recompile journal line."""
    from deepspeed_tpu.serving import ServingConfig, SlotBatcher
    journal = EventJournal(str(tmp_path / "events.jsonl"))
    engine = _inference_engine()
    cfg = ServingConfig(slots=2, max_len=32, prefill_chunk=8)
    batcher = SlotBatcher(engine, cfg)
    watch = CompileWatch(batcher.registry, journal=journal,
                         first_compile_free=True).open()
    batcher.admit(0, np.arange(1, 6, dtype=np.int32),
                  jax.random.PRNGKey(0), True, 1.0)
    batcher.tick()
    assert watch.check() == []          # first compiles are warmup
    batcher._build_programs(cfg)        # the bug: fresh jits per call
    batcher.tick()
    new = watch.check()
    assert [e.program for e in new] == ["tick"]
    assert new[0].count == 2
    events = read_events(journal.path, kind="perf.recompile")
    assert len(events) == 1
    assert events[0]["program"] == "tick"
    assert events[0]["shapes"]          # arg-shape signature present
    assert batcher.compile_counts()["tick"] == 2
    with pytest.raises(RecompileError, match="tick"):
        watch.assert_no_recompiles()
    watch.close()
