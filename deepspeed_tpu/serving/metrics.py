"""Serving metrics: thread-safe counters + a snapshot the journal, the
bench harness, and operators share.

Kept deliberately dumb — monotonically increasing counters plus a TTFT
:class:`~deepspeed_tpu.telemetry.metrics.Histogram` (the ONE latency
implementation: the bounded reservoir that feeds ``BENCH_SERVE.json``
p50/p99 is the same object the telemetry ``metrics.jsonl`` stream
samples, so the two artifacts can't disagree).  Percentile math on the
raw reservoir stays in the consumer (``scripts/serve_bench.py``), not the
hot path; the snapshot's ``ttft_s`` list is that reservoir, API-stable.
"""

from __future__ import annotations

import time
from typing import Dict, Optional

from ..moe.held_experts import read_pair_counts
from ..telemetry.metrics import Histogram, MetricName
from ..utils.lock_watch import LockName, TrackedLock

#: TTFT samples kept (oldest dropped) — enough for p99 at bench scale
_TTFT_CAP = 4096


class ServingMetrics:
    def __init__(self):
        self._lock = TrackedLock(LockName.SERVE_METRICS)
        self.t_start = time.monotonic()
        self.submitted = 0
        self.admitted = 0
        #: program launches the admissions took, a prefix built on a miss
        #: included (the batcher's count; one each is the design)
        self.admit_launches = 0
        self.rejected = 0
        #: submissions shed by the admission controller (each also counts
        #: as rejected — shed is the overload-policy subset)
        self.shed = 0
        #: degradation-ladder rung engage/release transitions
        self.degrade_transitions = 0
        #: currently engaged rungs, as the RUNG_BITS bitmask gauge
        self.degrade_rungs = 0
        self.completed = 0
        self.cancelled = 0
        self.timeouts = 0
        self.failed = 0
        self.evictions = 0
        self.prefix_hits = 0
        self.prefix_builds = 0
        self.ticks = 0
        self.tokens_out = 0
        self.active_slot_ticks = 0   # sum over ticks of active slots
        self.slot_ticks = 0          # sum over ticks of total slots
        #: cache blocks the decode kernel stepped (the live blocks of the
        #: live rows, all layers) and the blocks of the whole slot grid,
        #: summed over plain ticks from the lengths the gateway holds
        self.kv_blocks_live = 0
        self.kv_blocks_grid = 0
        #: cached tokens those ticks' queries saw, and the tokens the
        #: decode kernel's copies moved for them: whole blocks, and a
        #: row's last as far as the kernel's copy goes
        self.kv_tokens_live = 0
        self.kv_tokens_streamed = 0
        #: the same by pool (``SweepPlan.by_kind``: ``"full"`` the whole
        #: rows, ``"window"`` a family's rings): ``[live, streamed, calls]``
        #: summed over plain ticks, calls the sweep kernel's (one a layer)
        self.kv_sweep: dict = {}
        #: cached rows a slot holds over what one geometry for all layers
        #: would hold (a gauge the gateway sets from the batcher's plan)
        self.kv_pool_share = 1.0
        #: ticks launched while the tick before them was un-pulled (the
        #: decode loop keeps one in flight; a busy period's first tick
        #: has no predecessor)
        self.ticks_overlapped = 0
        #: row-ticks the device ran for a request that had already
        #: finished (a row's release is one tick late); they count in
        #: ``active_slot_ticks``, their tokens nowhere
        self.late_row_ticks = 0
        #: post-warmup compiles observed by the gateway's CompileWatch —
        #: nonzero means the zero-recompile serving contract regressed
        self.recompiles = 0
        #: sanctioned device→host pulls on the tick loop (noted by the
        #: batcher's registry; ~1 per tick is the design)
        self.host_syncs = 0
        # ---- paged KV / session tiering (serving/paging.py) ----
        #: sessions parked to a host tier (RAM or disk)
        self.parked = 0
        #: follow-up turns served from a tier copy (no re-prefill)
        self.readmits = 0
        #: follow-up turns that fell back to a full re-prefill
        self.readmit_misses = 0
        #: pool-pressure evictions (warm tier → host park)
        self.pool_evictions = 0
        #: RAM-park capacity spills to the disk tier
        self.park_spills = 0
        self.pages_allocated = 0
        #: gauges pushed by the gateway after tier changes
        self.hbm_bytes_per_conversation = 0.0
        self.peak_concurrent_conversations = 0
        self.serving_hbm_bytes = 0
        self.pool_blocks_used = 0
        # ---- an expert family's device counters (KVCache.stats) ----
        #: (token, expert) pairs that landed on an expert held here, pairs
        #: routed in all, and the pairs each held expert took: cumulative,
        #: prompt passes included, as of the last harvested tick
        self.moe_pairs_held = 0
        self.moe_pairs_routed = 0
        #: (expert layer call, held expert) with at least one pair: each
        #: streams that expert's matrices once
        self.moe_expert_visits = 0
        self.moe_expert_pairs: list = []
        #: pages of held pairs that expert layer calls ran beyond their
        #: first (``held_experts.pairs_cap``): 0 unless a call held more
        #: than twice a uniform router's share
        self.moe_pages_over_cap = 0
        #: routed pairs that chose a zero-compute expert (a router wider
        #: than its matrices); 0 for every other family
        self.moe_pairs_zero = 0
        #: a per-slot-state family's device counters, by name (state rows
        #: stepped, real and padded tokens through the chunk scan):
        #: cumulative, as of the last harvested tick
        self.state_steps: dict = {}
        #: a selecting family's cumulative counts (``select_counters``)
        self.sparse_select: dict = {}
        #: time-to-first-token, seconds — the shared telemetry histogram
        #: (count/sum exact, reservoir bounded at :data:`_TTFT_CAP`)
        self.ttft = Histogram(MetricName.SERVE_TTFT_S, cap=_TTFT_CAP)
        #: re-admission wall seconds (tier read + remainder prefill) —
        #: the number the bench gates against re-prefill latency
        self.readmit = Histogram(MetricName.SERVE_READMIT_S, cap=_TTFT_CAP)
        # ---- speculative decoding (serving/batcher.py spec tick) ----
        #: speculative draft/verify rounds run
        self.spec_rounds = 0
        #: draft proposals accepted / proposed (cumulative, all slots)
        self.spec_accepted = 0
        self.spec_proposed = 0
        #: per-round acceptance rate (accepted/proposed over the round's
        #: live slots) — the draft-quality signal the bench journals
        self.spec_accept_rate = Histogram(
            MetricName.SERVE_SPEC_ACCEPT_RATE, cap=_TTFT_CAP)
        #: tokens emitted per speculative tick (all live slots)
        self.spec_tokens_per_tick = Histogram(
            MetricName.SERVE_SPEC_TOKENS_PER_TICK, cap=_TTFT_CAP)

    def count(self, field: str, n: int = 1) -> None:
        with self._lock:
            setattr(self, field, getattr(self, field) + n)

    def set_value(self, field: str, value) -> None:
        """Absolute update for gauge-style counters fed from an external
        monotonic source (the CompileWatch host-sync totals)."""
        with self._lock:
            setattr(self, field, value)

    def set_max(self, field: str, value) -> None:
        """High-water-mark update (peak concurrent conversations)."""
        with self._lock:
            setattr(self, field, max(getattr(self, field), value))

    def record_tick(self, active: int, slots: int, tokens: int,
                    kv_blocks=(0, 0), overlapped: bool = False,
                    late_rows: int = 0, kv_by_kind=None) -> None:
        """``active``: the requests bound at the tick's launch, the
        ``late_rows`` of them that had finished by its harvest included;
        ``tokens``: those delivered to a request; ``kv_blocks``: the
        tick's ``(live, grid)`` cache blocks
        (``SlotBatcher.sweep_blocks``); ``kv_by_kind``: its cached tokens
        by pool with the sweep's calls, ``pool -> (live, streamed, calls)``
        (``SlotBatcher.sweep_by_kind``; one pool, ``"full"``, for every
        family but one with rings)."""
        with self._lock:
            self.ticks += 1
            self.tokens_out += tokens
            self.active_slot_ticks += active
            self.slot_ticks += slots
            self.kv_blocks_live += kv_blocks[0]
            self.kv_blocks_grid += kv_blocks[1]
            for kind, counts in (kv_by_kind or {}).items():
                self.kv_tokens_live += counts[0]
                self.kv_tokens_streamed += counts[1]
                mine = self.kv_sweep.setdefault(kind, [0, 0, 0])
                for i, c in enumerate(counts):
                    mine[i] += c
            self.ticks_overlapped += bool(overlapped)
            self.late_row_ticks += late_rows

    def record_spec_round(self, accepted: int, proposed: int,
                          emitted: int) -> None:
        with self._lock:
            self.spec_rounds += 1
            self.spec_accepted += accepted
            self.spec_proposed += proposed
        self.spec_accept_rate.observe(accepted / max(1, proposed))
        self.spec_tokens_per_tick.observe(float(emitted))

    def kv_sweep_counters(self) -> dict:
        """The single-token sweep's cumulative counts by pool, flat, as the
        ``serve.kv_sweep`` record carries them: ``<pool>_tokens_live``,
        ``<pool>_tokens_streamed`` and ``<pool>_calls``."""
        with self._lock:
            return {f"{kind}_{name}": c[i] for kind, c
                    in self.kv_sweep.items() for i, name in enumerate(
                        ("tokens_live", "tokens_streamed", "calls"))}

    def record_moe_pairs(self, counts) -> None:
        """``counts``: the batcher's cumulative group ``moe_pairs``, ``[held,
        routed, visits, pairs of each held expert..., pages over the cap,
        pairs on zero-compute experts]``
        (``moe.held_experts.pair_counts``)."""
        read = read_pair_counts(counts)
        with self._lock:
            self.moe_pairs_held = read["held"]
            self.moe_pairs_routed = read["routed"]
            self.moe_expert_visits = read["visits"]
            self.moe_expert_pairs = read["per_expert"]
            self.moe_pages_over_cap = read["pages_over_cap"]
            self.moe_pairs_zero = read["zero"]

    def record_state_steps(self, counts: dict) -> None:
        """``counts``: name -> cumulative count, the batcher's group
        ``state_steps`` under the family's ``state_counters``."""
        with self._lock:
            self.state_steps = dict(counts)

    def record_sparse_select(self, counts: dict) -> None:
        """``counts``: name -> cumulative count, the batcher's group
        ``sparse_select`` under the family's ``select_counters``."""
        with self._lock:
            self.sparse_select = dict(counts)

    def record_ttft(self, seconds: float) -> None:
        self.ttft.observe(float(seconds))

    def record_readmit(self, seconds: float) -> None:
        self.readmit.observe(float(seconds))

    def snapshot(self, queue_depth: Optional[int] = None) -> Dict:
        """One coherent view: counters, slot occupancy, tokens/sec over
        the gateway's lifetime, and the raw TTFT reservoir."""
        with self._lock:
            elapsed = max(time.monotonic() - self.t_start, 1e-9)
            snap = {
                "submitted": self.submitted,
                "admitted": self.admitted,
                "admit_launches": self.admit_launches,
                # 1.0 when every admission was its one program launch
                "launches_per_admission": (
                    self.admit_launches / self.admitted
                    if self.admitted else 0.0),
                "rejected": self.rejected,
                "shed": self.shed,
                "degrade_transitions": self.degrade_transitions,
                "degrade_rungs": self.degrade_rungs,
                "completed": self.completed,
                "cancelled": self.cancelled,
                "timeouts": self.timeouts,
                "failed": self.failed,
                "evictions": self.evictions,
                "prefix_hits": self.prefix_hits,
                "prefix_builds": self.prefix_builds,
                "ticks": self.ticks,
                "tokens_out": self.tokens_out,
                "recompiles": self.recompiles,
                "host_syncs": self.host_syncs,
                "parked": self.parked,
                "readmits": self.readmits,
                "readmit_misses": self.readmit_misses,
                "pool_evictions": self.pool_evictions,
                "park_spills": self.park_spills,
                "pages_allocated": self.pages_allocated,
                "hbm_bytes_per_conversation":
                    self.hbm_bytes_per_conversation,
                "peak_concurrent_conversations":
                    self.peak_concurrent_conversations,
                "serving_hbm_bytes": self.serving_hbm_bytes,
                "pool_blocks_used": self.pool_blocks_used,
                "spec_rounds": self.spec_rounds,
                "spec_accepted": self.spec_accepted,
                "spec_proposed": self.spec_proposed,
                "spec_accept_rate_mean": (
                    self.spec_accepted / self.spec_proposed
                    if self.spec_proposed else 0.0),
                "elapsed_s": elapsed,
                "tokens_per_s": self.tokens_out / elapsed,
                "slot_occupancy": (self.active_slot_ticks / self.slot_ticks
                                   if self.slot_ticks else 0.0),
                "kv_blocks_live": self.kv_blocks_live,
                "kv_blocks_grid": self.kv_blocks_grid,
                # the share of the slot grid's cache blocks the decode
                # kernel stepped; one minus it is what a sweep of the
                # whole grid would have stepped for nothing
                "live_block_share": (self.kv_blocks_live
                                     / self.kv_blocks_grid
                                     if self.kv_blocks_grid else 0.0),
                "kv_tokens_live": self.kv_tokens_live,
                "kv_tokens_streamed": self.kv_tokens_streamed,
                # cached tokens the decode kernel moved for each one a
                # query saw: the dead tail of every row's last block
                "streamed_over_live": (self.kv_tokens_streamed
                                       / self.kv_tokens_live
                                       if self.kv_tokens_live else 0.0),
                # the same by pool, and the share of a one-geometry pool
                # (every layer's row ``max_len`` long) the pools take
                "kv_sweep": {k: list(v) for k, v in self.kv_sweep.items()},
                "kv_pool_share_of_one_geometry": self.kv_pool_share,
                "kv_window_streamed_share": (
                    self.kv_sweep.get("window", (0, 0))[1]
                    / self.kv_tokens_streamed
                    if self.kv_tokens_streamed else 0.0),
                "ticks_overlapped": self.ticks_overlapped,
                "late_row_ticks": self.late_row_ticks,
                # how much of the loop ran with a tick in flight, and
                # what the one-tick-late release cost the device
                "overlap_share": (self.ticks_overlapped / self.ticks
                                  if self.ticks else 0.0),
                "late_row_share": (self.late_row_ticks
                                   / self.active_slot_ticks
                                   if self.active_slot_ticks else 0.0),
                "moe_pairs_held": self.moe_pairs_held,
                "moe_pairs_routed": self.moe_pairs_routed,
                "moe_expert_visits": self.moe_expert_visits,
                "moe_expert_pairs": list(self.moe_expert_pairs),
                "moe_pages_over_cap": self.moe_pages_over_cap,
                "moe_pairs_zero": self.moe_pairs_zero,
                "state_steps": dict(self.state_steps),
                "sparse_select": dict(self.sparse_select),
                # the busiest held expert's pairs over the mean's
                "moe_expert_load_max_over_mean": (
                    max(self.moe_expert_pairs) * len(self.moe_expert_pairs)
                    / self.moe_pairs_held if self.moe_pairs_held else 0.0),
            }
        snap["ttft_s"] = self.ttft.values()
        snap["readmit_s"] = self.readmit.values()
        if queue_depth is not None:
            snap["queue_depth"] = queue_depth
        return snap
