"""Multi-host checkpoint commit protocol + resume consensus: the
multi-writer chaos matrix (N concurrent writers against one tag —
kill-one-mid-write, straggler-past-deadline, coordinator death between
ready and commit), consensus over divergent local newest tags, torn-tag
sweep idempotence, and the cross-engine committed round trip.  Toy state
trees (no engine compile) keep the whole module tier-1 fast; the
real-engine acceptance path lives in ``test_commit_e2e.py``."""

import json
import os
import threading
import time

import numpy as np
import pytest

from deepspeed_tpu.runtime.checkpoint_engine import (
    CheckpointCorruptionError, DeepSpeedCheckpointConfig,
    load_engine_checkpoint, save_engine_checkpoint)
from deepspeed_tpu.runtime.checkpoint_engine import commit as cp
from deepspeed_tpu.runtime.checkpoint_engine.async_checkpoint_engine import (
    AsyncCheckpointEngine)
from deepspeed_tpu.runtime.checkpoint_engine.config import (
    CheckpointCommitConfig)
from deepspeed_tpu.runtime.checkpoint_engine.storage import atomic_write_npz
from deepspeed_tpu.runtime.supervision.events import (EventJournal, EventKind,
                                                      read_events)
from deepspeed_tpu.utils import fault_injection as fi

pytestmark = pytest.mark.chaos


def tree(v, acc=0.0):
    """A minimal engine-shaped state tree whose params encode ``v``
    (same fixture shape as test_durability.py)."""
    import jax.numpy as jnp
    a = jnp.asarray(float(v), jnp.float32)
    return {"params": {"w": a, "b": jnp.full((4,), float(v))},
            "master": {"w": a, "b": jnp.full((4,), float(v))},
            "opt_state": {"m": {"w": a * 0.1}, "v": {"w": a * 0.2}},
            "grad_acc": {"w": jnp.asarray(float(acc))},
            "scale": {"loss_scale": jnp.asarray(1024.0)}}


@pytest.fixture(autouse=True)
def _no_leaked_faults():
    yield
    fi.clear()


def fast_cfg(**kw):
    kw.setdefault("barrier_deadline_s", 0.4)
    kw.setdefault("barrier_poll_s", 0.01)
    kw.setdefault("barrier_backoff_max_s", 0.05)
    kw.setdefault("consensus_deadline_s", 2.0)
    return CheckpointCommitConfig(**kw)


def ctx(world, rank=0, journal=None, heartbeat=None, channel=None, **cfgkw):
    return cp.CommitContext(world_size=world, rank=rank, config=fast_cfg(**cfgkw),
                            journal=journal, heartbeat=heartbeat,
                            channel=channel)


def save(d, step, commit_ctx=None, tag=None, config=None):
    save_engine_checkpoint(str(d), tag or f"global_step{step}", tree(step),
                           {"global_steps": step}, separate_master=True,
                           config=config, commit_ctx=commit_ctx)


def write_shard(d, tag, rank, world=2):
    """A non-coordinator writer's contribution: shard file + ready vote."""
    atomic_write_npz(os.path.join(str(d), tag, f"shard_rank{rank}.npz"),
                     {"w": np.full((4,), float(rank))})
    cp.write_rank_manifest(str(d), tag, rank, world_size=world)


def loaded_step(d, tag=None):
    st, cs = load_engine_checkpoint(str(d), tag, tree(-1))
    return None if st is None else cs["global_steps"]


def latest(d):
    p = os.path.join(str(d), "latest")
    return open(p).read().strip() if os.path.exists(p) else None


# --------------------------------------------------------------- phase 1/2

def test_single_host_save_publishes_commit_before_latest(tmp_path):
    j = EventJournal(str(tmp_path / "events.jsonl"))
    save(tmp_path, 5, commit_ctx=ctx(1, journal=j))
    tag = "global_step5"
    assert cp.is_committed(str(tmp_path), tag)
    assert latest(tmp_path) == tag
    doc = cp.read_commit(str(tmp_path), tag)
    assert doc["world_size"] == 1 and doc["ranks"] == [0]
    # the commit pins the manifest it certified
    assert "manifest_sha256" in doc
    ready = cp.read_rank_manifest(str(tmp_path), tag, 0)
    assert ready["rank"] == 0
    kinds = [e["kind"] for e in read_events(j.path)]
    assert EventKind.CKPT_COMMITTED in kinds
    assert loaded_step(tmp_path) == 5


def test_multiwriter_all_ranks_succeed(tmp_path):
    """The happy path of the matrix: N writers, everyone votes, commit."""
    tag = "global_step9"
    world = 3

    def writer(rank):
        time.sleep(0.03 * rank)  # stagger: coordinator polls meanwhile
        write_shard(tmp_path, tag, rank)

    threads = [threading.Thread(target=writer, args=(r,))
               for r in (1, 2)]
    for t in threads:
        t.start()
    save(tmp_path, 9, commit_ctx=ctx(world))
    for t in threads:
        t.join()
    assert cp.is_committed(str(tmp_path), tag)
    assert latest(tmp_path) == tag
    st = cp.commit_status(str(tmp_path), tag)
    assert st["verdict"] == "committed"
    assert st["ready_ranks"] == [0, 1, 2]
    # each rank's vote hashes exactly its own shard
    for r in (1, 2):
        m = cp.read_rank_manifest(str(tmp_path), tag, r)
        assert list(m["files"]) == [f"shard_rank{r}.npz"]


def test_rank_killed_midsave_latest_never_advances(tmp_path):
    """THE invariant: a rank that dies before voting can not let the
    latest marker advance to the torn tag."""
    j = EventJournal(str(tmp_path / "events.jsonl"))
    save(tmp_path, 1, commit_ctx=ctx(1))          # prior committed tag
    assert latest(tmp_path) == "global_step1"
    # rank 1 never votes (killed mid-write): barrier must expire
    save(tmp_path, 2, commit_ctx=ctx(2, journal=j))
    assert latest(tmp_path) == "global_step1"      # never moved
    assert not cp.is_committed(str(tmp_path), "global_step2")
    assert cp.is_torn(str(tmp_path), "global_step2")
    evs = read_events(j.path, kind=EventKind.CKPT_COMMIT_TIMEOUT)
    assert len(evs) == 1 and evs[0]["missing_ranks"] == [1]
    # resume falls back past the torn tag without help
    assert loaded_step(tmp_path) == 1


def test_straggler_past_deadline_tag_stays_torn(tmp_path):
    """A vote that lands after the coordinator abandoned the tag joins a
    corpse: still uncommitted, swept at the next startup."""
    j = EventJournal(str(tmp_path / "events.jsonl"))
    tag = "global_step3"
    save(tmp_path, 1, commit_ctx=ctx(1))

    def straggler():
        time.sleep(0.8)  # well past the 0.4s barrier deadline
        write_shard(tmp_path, tag, 1)

    t = threading.Thread(target=straggler)
    t.start()
    save(tmp_path, 3, commit_ctx=ctx(2, journal=j))
    t.join()
    assert cp.is_torn(str(tmp_path), tag)          # vote arrived too late
    assert latest(tmp_path) == "global_step1"
    # startup quarantine
    removed = cp.sweep_torn_tags(str(tmp_path), journal=j)
    assert removed == [tag]
    assert not os.path.isdir(tmp_path / tag)
    evs = read_events(j.path, kind=EventKind.CKPT_TORN_TAG)
    assert len(evs) == 1 and evs[0]["tag"] == tag
    # idempotent: a second sweep (another host racing) finds nothing
    assert cp.sweep_torn_tags(str(tmp_path), journal=j) == []
    assert len(read_events(j.path, kind=EventKind.CKPT_TORN_TAG)) == 1


def test_coordinator_dies_between_ready_and_commit(tmp_path):
    """All votes in, coordinator killed before commit.json: no commit, no
    latest move, torn tag quarantined on restart."""
    save(tmp_path, 1, commit_ctx=ctx(1))
    with fi.inject("ckpt.publish_commit", fi.FailNTimes(None)):
        with pytest.raises(fi.FaultError):
            save(tmp_path, 4, commit_ctx=ctx(1))
    tag = "global_step4"
    assert not cp.is_committed(str(tmp_path), tag)
    assert latest(tmp_path) == "global_step1"
    assert cp.is_torn(str(tmp_path), tag)          # rank0 voted, no commit
    assert cp.sweep_torn_tags(str(tmp_path)) == [tag]
    assert loaded_step(tmp_path) == 1


def test_commit_refuses_corrupt_rank_shard(tmp_path):
    """Vote verification at commit: a shard that rotted between vote and
    barrier completion blocks the commit marker — the tag is abandoned
    (graceful degradation, same as a barrier expiry), never advertised."""
    tag = "global_step7"
    write_shard(tmp_path, tag, 1)
    fi.corrupt_file(str(tmp_path / tag / "shard_rank1.npz"))
    save(tmp_path, 7, commit_ctx=ctx(2))           # must not raise
    assert not cp.is_committed(str(tmp_path), tag)
    assert latest(tmp_path) is None
    # and publish_commit itself names the problem when called directly
    with pytest.raises(cp.CheckpointCommitError, match="sha256 mismatch"):
        cp.publish_commit(str(tmp_path), tag, 2)


def test_heartbeat_dead_rank_fails_barrier_immediately(tmp_path):
    """A rank the heartbeat monitor already classifies missing must fail
    the barrier now, not after the full deadline."""
    class DeadRank1Monitor:
        def check(self, now=None):
            return {"alive": [0], "stale": [], "missing": [1]}

    j = EventJournal(str(tmp_path / "events.jsonl"))
    t0 = time.monotonic()
    c = ctx(2, journal=j, heartbeat=DeadRank1Monitor(),
            barrier_deadline_s=30.0)
    save(tmp_path, 2, commit_ctx=c)
    assert time.monotonic() - t0 < 5.0             # nowhere near 30s
    evs = read_events(j.path, kind=EventKind.CKPT_COMMIT_TIMEOUT)
    assert len(evs) == 1
    assert evs[0]["dead_ranks"] == [1] and evs[0]["missing_ranks"] == [1]
    assert "dead" in evs[0]["reason"]
    assert latest(tmp_path) is None


def test_barrier_tolerates_broken_monitor(tmp_path):
    class BrokenMonitor:
        def check(self, now=None):
            raise RuntimeError("monitor exploded")

    save(tmp_path, 2, commit_ctx=ctx(1, heartbeat=BrokenMonitor()))
    assert cp.is_committed(str(tmp_path), "global_step2")


# ---------------------------------------------------------------- loading

def test_load_rejects_torn_tag_even_when_advertised(tmp_path):
    """Defense in depth: even if a bug (or an operator) points latest at a
    torn tag, resume walks past it; pinning it explicitly raises."""
    save(tmp_path, 1, commit_ctx=ctx(1))
    save(tmp_path, 2, commit_ctx=ctx(1))
    os.remove(cp.commit_path(str(tmp_path), "global_step2"))  # now torn
    assert latest(tmp_path) == "global_step2"
    assert loaded_step(tmp_path) == 1
    with pytest.raises(CheckpointCorruptionError, match="torn"):
        load_engine_checkpoint(str(tmp_path), "global_step2", tree(-1))


def test_precommit_tags_stay_loadable(tmp_path):
    """Back-compat: tags written before the protocol (no votes, no commit)
    load exactly as before."""
    save(tmp_path, 6)                              # no commit_ctx
    assert not cp.uses_commit_protocol(str(tmp_path), "global_step6")
    assert cp.commit_status(str(tmp_path), "global_step6")["verdict"] == \
        "pre-commit"
    assert loaded_step(tmp_path) == 6


def test_retention_sweeps_torn_tags(tmp_path):
    """keep_last retention runs the torn sweep: shard-only corpses don't
    accumulate across preemptions."""
    cfg = DeepSpeedCheckpointConfig(keep_last=2)
    write_shard(tmp_path, "global_step1", 1)       # torn corpse
    os.utime(tmp_path / "global_step1", (1.0, 1.0))
    for s in (2, 3):
        save(tmp_path, s, commit_ctx=ctx(1), config=cfg)
    assert not os.path.isdir(tmp_path / "global_step1")
    assert cp.is_committed(str(tmp_path), "global_step3")


# -------------------------------------------------------------- consensus

def test_consensus_trivial_single_host(tmp_path):
    j = EventJournal(str(tmp_path / "events.jsonl"))
    save(tmp_path, 5, commit_ctx=ctx(1))
    agreed = cp.agree_resume_tag(str(tmp_path), ctx(1, journal=j))
    assert agreed == "global_step5"
    evs = read_events(j.path, kind=EventKind.CKPT_RESUME_CONSENSUS)
    assert evs and evs[0]["tag"] == "global_step5" and evs[0]["step"] == 5


def test_consensus_skips_uncommitted_and_corrupt(tmp_path):
    save(tmp_path, 5, commit_ctx=ctx(1))
    save(tmp_path, 6, commit_ctx=ctx(1))
    os.remove(cp.commit_path(str(tmp_path), "global_step6"))
    step, tag = cp.local_commit_proposal(str(tmp_path))
    assert (step, tag) == (5, "global_step5")


def _host(load_dir, shared, rank, world, out, journal=None):
    ch = cp.FileConsensusChannel(str(shared), rank, world,
                                 deadline_s=5.0, poll_s=0.01)
    c = ctx(world, rank=rank, journal=journal, channel=ch)
    try:
        out[rank] = cp.agree_resume_tag(str(load_dir), c)
    except Exception as e:
        out[rank] = e


def test_consensus_divergent_newest_tags_agree_on_min(tmp_path):
    """Host A committed step 100 and 200; host B's disk only has 100 (its
    200 save never landed).  The group must agree on 100 — on BOTH."""
    a, b, shared = tmp_path / "a", tmp_path / "b", tmp_path / "shared"
    for d, steps in ((a, (100, 200)), (b, (100,))):
        for s in steps:
            save(d, s, commit_ctx=ctx(1))
    ja = EventJournal(str(tmp_path / "ja.jsonl"), rank=0)
    out = {}
    tb = threading.Thread(target=_host, args=(b, shared, 1, 2, out))
    tb.start()
    _host(a, shared, 0, 2, out, journal=ja)
    tb.join()
    assert out[0] == "global_step100" and out[1] == "global_step100"
    ev = read_events(ja.path, kind=EventKind.CKPT_RESUME_CONSENSUS)[0]
    assert ev["local_step"] == 200 and ev["step"] == 100


def test_consensus_peer_with_nothing_aborts_loudly(tmp_path):
    """A peer with an empty disk cannot silently make this host resume:
    the group either starts fresh together or aborts."""
    a, b, shared = tmp_path / "a", tmp_path / "b", tmp_path / "shared"
    save(a, 100, commit_ctx=ctx(1))
    os.makedirs(b)
    ja = EventJournal(str(tmp_path / "ja.jsonl"))
    out = {}
    tb = threading.Thread(target=_host, args=(b, shared, 1, 2, out))
    tb.start()
    _host(a, shared, 0, 2, out, journal=ja)
    tb.join()
    assert isinstance(out[0], cp.ResumeConsensusError)
    assert out[1] is None                          # the fresh host is fine
    evs = read_events(ja.path, kind=EventKind.CKPT_CONSENSUS_FAILURE)
    assert evs and "no resumable tag" in evs[0]["reason"]


def test_consensus_agreed_tag_missing_locally_aborts(tmp_path):
    """The agreed (min) step must exist committed+verified locally —
    otherwise loading anything else would silently diverge from the
    group."""
    a, b, shared = tmp_path / "a", tmp_path / "b", tmp_path / "shared"
    save(a, 200, commit_ctx=ctx(1))                # A only has 200
    save(b, 100, commit_ctx=ctx(1))                # B only has 100
    out = {}
    tb = threading.Thread(target=_host, args=(b, shared, 1, 2, out))
    tb.start()
    _host(a, shared, 0, 2, out)
    tb.join()
    assert isinstance(out[0], cp.ResumeConsensusError)  # A lacks step 100
    assert out[1] == "global_step100"


def test_file_channel_round_isolation_and_timeout(tmp_path):
    """Round 2 must not read round 1's proposals; a peer that never
    proposes is a loud deadline abort."""
    shared = tmp_path / "shared"
    a = cp.FileConsensusChannel(str(shared), 0, 2, deadline_s=5.0,
                                poll_s=0.01)
    b = cp.FileConsensusChannel(str(shared), 1, 2, deadline_s=5.0,
                                poll_s=0.01)
    res = {}
    t = threading.Thread(target=lambda: res.update(b=b.agree_min(7)))
    t.start()
    assert a.agree_min(3) == 3
    t.join()
    assert res["b"] == 3
    # round 2: fresh values, the old minimum (3) must not leak in
    t = threading.Thread(target=lambda: res.update(b2=b.agree_min(20)))
    t.start()
    assert a.agree_min(30) == 20
    t.join()
    assert res["b2"] == 20
    # a lone host (fresh consensus dir: no stale rounds) times out loudly
    lone = cp.FileConsensusChannel(str(tmp_path / "lone"), 0, 2,
                                   deadline_s=0.2, poll_s=0.01)
    with pytest.raises(cp.ResumeConsensusError, match="timed out"):
        lone.agree_min(1)


def test_consensus_round_sweep_clears_stale_rounds(tmp_path):
    shared = tmp_path / "shared"
    ch = cp.FileConsensusChannel(str(shared), 0, 1, deadline_s=1.0)
    assert ch.agree_min(4) == 4
    assert os.path.isdir(shared)
    ch.sweep_rounds()
    assert not os.path.isdir(shared)


def test_a_sweep_that_lands_under_a_vote_loses_it_today(tmp_path,
                                                        monkeypatch):
    """The hole ROADMAP.md D0 (v) records, held as it IS so that the repair
    has a case to turn round: the coordinator's ``sweep_rounds`` landing
    between a peer's making its round's directory and its writing its vote.
    ``atomic_write_text`` makes the parent once and retries the write alone,
    so the vote dies of ``FileNotFoundError`` where the channel's docstring
    promises a re-assert or a loud ``ResumeConsensusError``.  The repair
    (re-make the directory on every attempt) makes this ``agree_min``
    return 4: assert that then."""
    from deepspeed_tpu.runtime.checkpoint_engine import storage
    shared = tmp_path / "shared"
    ch = cp.FileConsensusChannel(str(shared), 0, 1, deadline_s=1.0)
    made = storage._ensure_parent
    swept = []

    def made_then_swept(path):
        made(path)
        if not swept:
            swept.append(path)
            ch.sweep_rounds()

    monkeypatch.setattr(storage, "_ensure_parent", made_then_swept)
    with pytest.raises(FileNotFoundError):
        ch.agree_min(4)
    assert swept and not os.path.isdir(shared)


# ------------------------------------------------------------ cross-engine

def test_cross_engine_async_commit_sync_resume(tmp_path):
    """Async save runs the whole commit chain (barrier included) in the
    writer pool; a sync engine then resumes the committed tag."""
    j = EventJournal(str(tmp_path / "events.jsonl"))
    cfg = DeepSpeedCheckpointConfig(async_save=True)
    eng = AsyncCheckpointEngine(cfg)
    save_engine_checkpoint(str(tmp_path), "global_step8", tree(8),
                           {"global_steps": 8}, separate_master=True,
                           engine=eng, config=cfg,
                           commit_ctx=ctx(1, journal=j))
    eng.wait()                                     # join the commit chain
    assert cp.is_committed(str(tmp_path), "global_step8")
    assert latest(tmp_path) == "global_step8"
    assert loaded_step(tmp_path) == 8              # sync resume
    kinds = [e["kind"] for e in read_events(j.path)]
    assert EventKind.CKPT_COMMITTED in kinds


def test_async_abandoned_tag_is_not_an_error(tmp_path):
    """Barrier expiry under the async engine is graceful degradation: no
    exception at the next wait(), latest unmoved, tag torn."""
    cfg = DeepSpeedCheckpointConfig(async_save=True)
    eng = AsyncCheckpointEngine(cfg)
    save_engine_checkpoint(str(tmp_path), "global_step9", tree(9),
                           {"global_steps": 9}, separate_master=True,
                           engine=eng, config=cfg, commit_ctx=ctx(2))
    eng.wait()                                     # must NOT raise
    assert latest(tmp_path) is None
    assert cp.is_torn(str(tmp_path), "global_step9")


# ----------------------------------------------------------------- tooling

def _load_script(name):
    import importlib.util
    path = os.path.join(os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.dirname(os.path.abspath(__file__))))), "scripts", name)
    spec = importlib.util.spec_from_file_location(name[:-3], path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_verify_checkpoint_commit_status_cli(tmp_path, capsys):
    vc = _load_script("verify_checkpoint.py")
    save(tmp_path, 0, tag="legacy_step0")          # pre-commit
    save(tmp_path, 1, commit_ctx=ctx(1))           # committed, latest
    write_shard(tmp_path, "global_step2", 1)       # torn
    assert vc.main([str(tmp_path), "--commit-status"]) == 0
    out = capsys.readouterr().out
    assert "COMMITTED  global_step1 (latest)" in out
    assert "TORN       global_step2" in out
    assert "PRE-COMMIT legacy_step0" in out


def test_verify_checkpoint_flags_torn_committed(tmp_path, capsys):
    """The serious verdict: a commit marker whose rank shards no longer
    verify exits 1."""
    vc = _load_script("verify_checkpoint.py")
    tag = "global_step4"
    write_shard(tmp_path, tag, 1)
    save(tmp_path, 4, commit_ctx=ctx(2))
    assert cp.is_committed(str(tmp_path), tag)
    os.remove(tmp_path / tag / "shard_rank1.npz")  # shard lost after commit
    assert vc.main([str(tmp_path), "--commit-status"]) == 1
    assert "TORN-COMMITTED" in capsys.readouterr().out


def test_dump_run_events_treats_commit_timeout_as_abort(tmp_path, capsys):
    dre = _load_script("dump_run_events.py")
    j = EventJournal(str(tmp_path / "events.jsonl"))
    j.emit(EventKind.CKPT_RESUME_CONSENSUS, tag="global_step5", step=5,
           local_tag="global_step5", local_step=5, world_size=2)
    assert dre.main([str(tmp_path)]) == 0
    j.emit(EventKind.CKPT_COMMIT_TIMEOUT, tag="global_step6",
           missing_ranks=[3], dead_ranks=[], deadline_s=0.4,
           reason="commit barrier deadline expired")
    assert dre.main([str(tmp_path)]) == 1          # abort-class
    out = capsys.readouterr().out
    assert "ckpt.commit_timeout" in out and "missing_ranks=[3]" in out
