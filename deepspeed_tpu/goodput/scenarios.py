"""Seeded, declarative fault scenarios for the goodput fleet.

A scenario is data, not code: which ranks get which fault plans
(``utils/fault_injection.py`` specs, delivered to subprocess ranks through
the ``DS_FAULT_PLAN`` env var), what the fleet supervisor does between
incarnations (e.g. corrupt the newest committed tag), and what the scored
run is expected to look like.  Factories draw every free choice (victim
rank, kill step) from ``random.Random(seed)``, so a scenario resolved at a
given seed is bit-identical across runs and machines — the regression gate
in ``scripts/goodput_bench.py`` depends on that.

Registry contract: ``SCENARIOS`` maps name → ``factory(seed) -> Scenario``;
``build_scenario(name, seed)`` resolves one, validating every fault spec
against the fault-point and plan-fault registries at build time (a typo'd
scenario must fail in the parent, not silently run fault-free and score a
fake-perfect goodput).  Schema + metric definitions: ``docs/goodput.md``.
"""

from __future__ import annotations

import dataclasses
import random
from typing import Any, Dict, Mapping, Optional, Tuple

from ..utils import fault_injection

#: every rank, in FaultSpec.ranks
ALL_RANKS = "*"


@dataclasses.dataclass(frozen=True)
class FaultSpec:
    """One fault to install in one or more subprocess ranks.

    ``fault``/``args`` must be :data:`~deepspeed_tpu.utils.fault_injection.
    PLAN_FAULTS`-serializable; ``ranks`` is a tuple of rank ids or
    ``("*",)`` for the whole fleet; ``incarnation`` scopes the fault to one
    incarnation (faults usually belong to the first — a respawned rank
    must not re-kill itself)."""

    point: str
    fault: str
    args: Mapping[str, Any] = dataclasses.field(default_factory=dict)
    ranks: Tuple = (ALL_RANKS,)
    incarnation: int = 0

    def applies_to(self, rank: int, incarnation: int) -> bool:
        if int(incarnation) != self.incarnation:
            return False
        return ALL_RANKS in self.ranks or int(rank) in self.ranks

    def plan_entry(self) -> Dict[str, Any]:
        return {"point": self.point, "fault": self.fault,
                "args": dict(self.args)}


@dataclasses.dataclass(frozen=True)
class CorruptTagAction:
    """Supervisor-side bitrot between incarnations: flip bytes of the first
    file matching ``file_match`` in the newest *committed* tag.  Models
    corruption that lands after the commit certified the bytes — exactly
    what the verified-fallback resume chain exists to survive."""

    after_incarnation: int = 0
    file_match: str = "model_states"
    nbytes: int = 16
    seed: int = 0


@dataclasses.dataclass(frozen=True)
class Scenario:
    """A fully-resolved fleet run: geometry, faults, knobs, expectations."""

    name: str
    description: str
    world_size: int
    target_steps: int
    save_interval: int
    seed: int
    faults: Tuple[FaultSpec, ...] = ()
    actions: Tuple[CorruptTagAction, ...] = ()
    #: which fleet runs it: ``engine`` = N data-parallel engine ranks
    #: (``goodput/fleet.py``), ``pipeline`` = N MPMD stage-group processes
    #: (``runtime/pipe/fleet.py``) — for pipeline mode ``world_size`` is
    #: the stage count and a fault's ``ranks`` name stages
    mode: str = "engine"
    #: engine mode only: respawn restarted incarnations at THIS world size
    #: (elastic resize — the dp-resharding resume path under test)
    resize_to: Optional[int] = None
    #: whole-group respawns the supervisor may spend before aborting
    max_restarts: int = 2
    #: SIGTERM-drain survivors on a bounce instead of SIGKILL (a dead rank
    #: can never vote, so drain saves during a bounce burn barrier deadline
    #: for nothing — kill scenarios keep this off)
    drain_on_bounce: bool = False
    #: consecutive non-finite losses before the runner declares divergence
    nan_abort_threshold: int = 2
    #: scored expectations (``score.py`` folds these into ``ok``):
    #: min_goodput, max_wasted_steps, max_mttr_s, expect_kinds (each must
    #: appear ≥1×), allow_abort_kinds (abort-class kinds the scenario
    #: legitimately produces, e.g. ckpt.commit_timeout after a kill)
    expect: Mapping[str, Any] = dataclasses.field(default_factory=dict)

    def plan_for(self, rank: int, incarnation: int) -> str:
        """The serialized ``DS_FAULT_PLAN`` for one spawned rank ('' when
        no fault touches it)."""
        entries = [f.plan_entry() for f in self.faults
                   if f.applies_to(rank, incarnation)]
        if not entries:
            return ""
        return fault_injection.serialize_plan(entries)

    def validate(self) -> "Scenario":
        if self.world_size < 1:
            raise ValueError(f"{self.name}: world_size must be >= 1")
        if self.mode not in ("engine", "pipeline"):
            raise ValueError(
                f"{self.name}: unknown mode {self.mode!r} "
                f"(engine | pipeline)")
        if self.resize_to is not None:
            if self.mode != "engine":
                raise ValueError(
                    f"{self.name}: resize_to is an engine-mode knob")
            if not 1 <= self.resize_to:
                raise ValueError(
                    f"{self.name}: resize_to must be >= 1")
        if self.target_steps < self.save_interval:
            raise ValueError(
                f"{self.name}: target_steps ({self.target_steps}) below "
                f"save_interval ({self.save_interval}) can never commit")
        for f in self.faults:
            # serialize_plan re-checks point + fault-type registration and
            # constructor-validates the kwargs
            fault_injection.serialize_plan([f.plan_entry()])
        return self


# ------------------------------------------------------------- factories
def _baseline_clean(seed: int) -> Scenario:
    return Scenario(
        name="baseline_clean",
        description="no faults: the goodput=1.0 anchor every other "
                    "scenario is read against",
        world_size=2, target_steps=10, save_interval=2, seed=seed,
        expect={"min_goodput": 0.999, "max_wasted_steps": 0,
                "max_incidents": 0},
    ).validate()


def _kill_one_rank(seed: int) -> Scenario:
    rng = random.Random(seed)
    victim = rng.randrange(2)
    step = rng.randint(5, 7)
    return Scenario(
        name="kill_one_rank",
        description=f"SIGKILL rank {victim} at step {step} (no notice); "
                    "the fleet must bounce, consensus-resume from the last "
                    "committed tag, and finish",
        world_size=2, target_steps=12, save_interval=2, seed=seed,
        # the ranks do not wait for each other: a victim that outruns the
        # publisher reaches its step before anything is committed, and the
        # fleet would then resume from nothing.  The scenario is "a rank
        # dies after the fleet has a checkpoint", so the kill waits, at its
        # step, for the first journaled commit
        faults=(FaultSpec("train.step", "KillAtStep",
                          {"step": step, "after_event": "ckpt.committed"},
                          ranks=(victim,)),),
        expect={"min_goodput": 0.5, "max_mttr_s": 90.0,
                "expect_kinds": ("fleet.rank_exit", "fleet.restart",
                                 "ckpt.resume_consensus"),
                "allow_abort_kinds": ("ckpt.commit_timeout",)},
    ).validate()


def _preempt_sigterm_drain(seed: int) -> Scenario:
    rng = random.Random(seed)
    step = rng.randint(5, 7)
    return Scenario(
        name="preempt_sigterm_drain",
        description=f"SIGTERM every rank at step {step} (spot reclaim "
                    "notice): all ranks drain-checkpoint the same tag "
                    "within the preempt-save deadline, then the fleet "
                    "relaunches and resumes with zero wasted steps",
        world_size=2, target_steps=12, save_interval=4, seed=seed,
        faults=(FaultSpec("train.step", "SignalAtStep", {"step": step}),),
        expect={"min_goodput": 0.9, "max_wasted_steps": 1,
                "max_mttr_s": 90.0,
                "expect_kinds": ("preempt.signal", "ckpt.preempt_save",
                                 "fleet.restart")},
    ).validate()


def _corrupt_newest_ckpt(seed: int) -> Scenario:
    rng = random.Random(seed)
    step = rng.randint(7, 8)
    return Scenario(
        name="corrupt_newest_ckpt",
        description=f"rank 0 crashes (exit 3) at step {step}; the newest "
                    "committed tag bitrots while the fleet is down; resume "
                    "must reject it via the verified-fallback chain and "
                    "retrain from the previous tag",
        world_size=1, target_steps=10, save_interval=2, seed=seed,
        faults=(FaultSpec("train.step", "ExitAtStep",
                          {"step": step, "code": 3}, ranks=(0,)),),
        actions=(CorruptTagAction(after_incarnation=0,
                                  file_match="model_states",
                                  nbytes=16, seed=seed),),
        expect={"min_goodput": 0.5, "max_mttr_s": 90.0,
                "expect_kinds": ("fleet.rank_exit", "fleet.restart")},
    ).validate()


def _straggler_slow_rank(seed: int) -> Scenario:
    rng = random.Random(seed)
    straggler = 1 + rng.randrange(1)  # never rank 0: the coordinator
    return Scenario(
        name="straggler_slow_rank",
        description=f"rank {straggler}'s heartbeats drag at 3x their "
                    "advertised interval for a window: the monitor must "
                    "classify it slow (heartbeat.slow) without declaring "
                    "it dead, and goodput must not collapse",
        world_size=2, target_steps=10, save_interval=2, seed=seed,
        faults=(FaultSpec("supervision.heartbeat", "DelaySeconds",
                          {"seconds": 0.5, "n": 8}, ranks=(straggler,)),),
        expect={"min_goodput": 0.999, "max_wasted_steps": 0,
                "max_incidents": 0,
                "expect_kinds": ("heartbeat.slow",)},
    ).validate()


def _nan_poisoned_window(seed: int) -> Scenario:
    rng = random.Random(seed)
    start = rng.randint(5, 6)
    return Scenario(
        name="nan_poisoned_window",
        description=f"steps [{start},{start + 2}) feed NaN losses: the "
                    "supervisor must roll back to the newest verified tag, "
                    "quarantine the poisoned batch window, and recover "
                    "without a restart",
        world_size=1, target_steps=12, save_interval=2, seed=seed,
        faults=(FaultSpec("train.loss", "NaNLossWindow",
                          {"from_step": start, "to_step": start + 2},
                          ranks=(0,)),),
        expect={"min_goodput": 0.5, "max_incidents": 0,
                "expect_kinds": ("rollback", "data.quarantine",
                                 "rollback.recovered")},
    ).validate()


def _preempt_during_rollback(seed: int) -> Scenario:
    rng = random.Random(seed)
    start = rng.randint(5, 6)
    return Scenario(
        name="preempt_during_rollback",
        description=f"compound fault: steps [{start},{start + 2}) feed NaN "
                    "losses AND a SIGTERM lands on the first step re-trained "
                    "inside the rollback window — the preempt drain must "
                    "checkpoint the *rolled-back* trajectory (not the "
                    "poisoned one), and the relaunched fleet must resume "
                    "from it with the quarantine still honored",
        world_size=1, target_steps=12, save_interval=2, seed=seed,
        faults=(FaultSpec("train.loss", "NaNLossWindow",
                          {"from_step": start, "to_step": start + 2},
                          ranks=(0,)),
                FaultSpec("train.step", "SignalAtStep", {"step": start + 1},
                          ranks=(0,))),
        expect={"min_goodput": 0.3, "max_mttr_s": 120.0,
                "expect_kinds": ("rollback", "data.quarantine",
                                 "preempt.signal", "fleet.restart")},
    ).validate()


def _partial_cluster_restart(seed: int) -> Scenario:
    rng = random.Random(seed)
    step = rng.randint(5, 6)
    victims = tuple(sorted(rng.sample(range(1, 3), 2)))
    return Scenario(
        name="partial_cluster_restart",
        description=f"ranks {victims} of 3 die at step {step}: a partial "
                    "cluster is not a quorum — the whole group bounces "
                    "once and consensus-resumes together",
        world_size=3, target_steps=10, save_interval=2, seed=seed,
        faults=tuple(FaultSpec("train.step", "KillAtStep", {"step": step},
                               ranks=(v,)) for v in victims),
        expect={"min_goodput": 0.4, "max_mttr_s": 120.0,
                "expect_kinds": ("fleet.rank_exit", "fleet.restart",
                                 "ckpt.resume_consensus"),
                "allow_abort_kinds": ("ckpt.commit_timeout",)},
    ).validate()


def _eight_rank_consensus_storm(seed: int) -> Scenario:
    rng = random.Random(seed)
    victim = rng.randrange(8)
    step = rng.randint(5, 6)
    return Scenario(
        name="eight_rank_consensus_storm",
        description=f"8 ranks, SIGKILL rank {victim} at step {step}: the "
                    "two-phase commit barrier and the resume consensus each "
                    "field 8 contending voters over the shared FS — the "
                    "contention case the 2-rank matrix never exercises",
        world_size=8, target_steps=8, save_interval=2, seed=seed,
        faults=(FaultSpec("train.step", "KillAtStep", {"step": step},
                          ranks=(victim,)),),
        expect={"min_goodput": 0.3, "max_mttr_s": 180.0,
                "expect_kinds": ("fleet.rank_exit", "fleet.restart",
                                 "ckpt.resume_consensus"),
                "allow_abort_kinds": ("ckpt.commit_timeout",)},
    ).validate()


def _elastic_resize_shrink(seed: int) -> Scenario:
    rng = random.Random(seed)
    victim = rng.randrange(4)
    step = rng.randint(5, 6)
    return Scenario(
        name="elastic_resize_shrink",
        description=f"4 ranks, SIGKILL rank {victim} at step {step}; the "
                    "restarted incarnation respawns at world size 2 (spot "
                    "capacity shrank) — dp-resharding resume must load the "
                    "4-rank tag, and the replayed window must be bitwise "
                    "(the fixture batches are rank-identical, so a replay "
                    "fingerprint mismatch means the reshard corrupted the "
                    "trajectory)",
        world_size=4, target_steps=10, save_interval=2, seed=seed,
        resize_to=2,
        faults=(FaultSpec("train.step", "KillAtStep", {"step": step},
                          ranks=(victim,)),),
        expect={"min_goodput": 0.3, "max_mttr_s": 180.0,
                "expect_kinds": ("fleet.rank_exit", "fleet.restart",
                                 "fleet.resize", "ckpt.resume_consensus"),
                "allow_abort_kinds": ("ckpt.commit_timeout",)},
    ).validate()


def _stage_loss_restart(seed: int) -> Scenario:
    rng = random.Random(seed)
    victim = 1 + rng.randrange(1)  # never stage 0: the journal anchor
    step = rng.randint(4, 5)
    return Scenario(
        name="stage_loss_restart",
        description=f"MPMD pipeline, SIGKILL stage {victim} at step {step} "
                    "mid-1F1B: survivors quiesce at the microbatch barrier "
                    "on the epoch bump, the victim respawns alone, the "
                    "group consensus-resumes onto the newest committed tag "
                    "and the loader replays — the continuation must be "
                    "bitwise-identical to an unfaulted run",
        world_size=2, target_steps=8, save_interval=2, seed=seed,
        mode="pipeline",
        faults=(FaultSpec("train.step", "KillAtStep", {"step": step},
                          ranks=(victim,)),),
        expect={"min_goodput": 0.5, "max_mttr_s": 60.0,
                "expect_kinds": ("pipe.stage_lost", "pipe.stage_respawn",
                                 "pipe.quiesce", "fleet.restart",
                                 "ckpt.resume_consensus")},
    ).validate()


def _dcn_stall_mid_1f1b(seed: int) -> Scenario:
    rng = random.Random(seed)
    victim = rng.randrange(2)
    return Scenario(
        name="dcn_stall_mid_1f1b",
        description=f"stage {victim}'s first activation-flow sends hit "
                    "injected DCN resets: the per-peer breaker must open "
                    "(pipe.transport_degraded), the spooled activation "
                    "bundles must carry the boundary traffic, and the run "
                    "must finish with zero restarts and zero wasted steps",
        world_size=2, target_steps=6, save_interval=2, seed=seed,
        mode="pipeline",
        # 9 = failures_to_open(3) sends × attempts-per-send(1 + retries 2):
        # enough consecutive exhausted sends to open the breaker, then the
        # injector runs dry and the probe can re-promote the channel
        faults=(FaultSpec("serve.transport.send", "FailNTimes",
                          {"n": 9, "match": "activation"},
                          ranks=(victim,)),),
        expect={"min_goodput": 0.999, "max_wasted_steps": 0,
                "max_incidents": 0,
                "expect_kinds": ("pipe.transport_degraded",)},
    ).validate()


def _fault_storm_during_pipeline_drain(seed: int) -> Scenario:
    rng = random.Random(seed)
    step = 2 * rng.randint(2, 3)  # lands exactly on a save boundary
    return Scenario(
        name="fault_storm_during_pipeline_drain",
        description=f"compound pipeline storm: stage 0's shard write for "
                    f"the step-{step} tag drags (injected delay) while "
                    f"stage 1 — already past its own vote — is killed on "
                    "its next step fire, so the death lands while the "
                    "other stage is still mid-checkpoint-vote; the commit "
                    "barrier may time out (allowed), but the bounded "
                    "restart must still converge with zero invariant "
                    "violations",
        world_size=2, target_steps=8, save_interval=2, seed=seed,
        mode="pipeline",
        faults=(FaultSpec("ckpt.rank_write", "DelaySeconds",
                          {"seconds": 1.5, "n": 1,
                           "match": f"step-{step:06d}"},
                          ranks=(0,)),
                FaultSpec("train.step", "KillAtStep", {"step": step},
                          ranks=(1,))),
        expect={"min_goodput": 0.3, "max_mttr_s": 90.0,
                "expect_kinds": ("pipe.stage_lost", "pipe.stage_respawn",
                                 "fleet.restart"),
                "allow_abort_kinds": ("ckpt.commit_timeout",)},
    ).validate()


#: name → factory(seed); iteration order is the bench matrix order
SCENARIOS = {
    "baseline_clean": _baseline_clean,
    "kill_one_rank": _kill_one_rank,
    "preempt_sigterm_drain": _preempt_sigterm_drain,
    "corrupt_newest_ckpt": _corrupt_newest_ckpt,
    "straggler_slow_rank": _straggler_slow_rank,
    "nan_poisoned_window": _nan_poisoned_window,
    "preempt_during_rollback": _preempt_during_rollback,
    "partial_cluster_restart": _partial_cluster_restart,
    "eight_rank_consensus_storm": _eight_rank_consensus_storm,
    "elastic_resize_shrink": _elastic_resize_shrink,
    "stage_loss_restart": _stage_loss_restart,
    "dcn_stall_mid_1f1b": _dcn_stall_mid_1f1b,
    "fault_storm_during_pipeline_drain": _fault_storm_during_pipeline_drain,
}


def scenario_names() -> Tuple[str, ...]:
    return tuple(SCENARIOS)


def build_scenario(name: str, seed: int = 0) -> Scenario:
    """Resolve one registered scenario at ``seed`` (deterministic)."""
    try:
        factory = SCENARIOS[name]
    except KeyError:
        raise KeyError(
            f"unknown goodput scenario {name!r} "
            f"(registered: {', '.join(SCENARIOS)})") from None
    scenario = factory(int(seed))
    if scenario.name != name:
        raise ValueError(
            f"scenario factory {name!r} built a scenario named "
            f"{scenario.name!r} — registry and dataclass must agree")
    return scenario
