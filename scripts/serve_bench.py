#!/usr/bin/env python3
"""Synthetic-load benchmark for the continuous-batching serving gateway.

Drives a real ``ServingGateway`` (tiny random-init GPT by default) with a
seeded Poisson arrival process and mixed prompt/reply lengths, then writes
``BENCH_SERVE.json`` — throughput tokens/s, TTFT p50/p99, slot occupancy,
reject/timeout counts — so serving behaviour is a tracked per-PR
trajectory (schema: ``docs/serving.md``).

A second phase benchmarks paged KV + session tiering on a **long-tail**
conversation-length mix with **multi-turn** traffic (follow-up after
park): the same seeded conversations run once through a paged gateway
(follow-ups re-admit parked KV) and once through a re-prefill control
(paging with no retention capacity, so every follow-up pays the full
prefill).  ``BENCH_SERVE.json`` gains and GATES:

- ``hbm_bytes_per_concurrent_conversation`` — (slot cache + block pool)
  ÷ peak concurrently-held conversations; must beat the fixed-slot
  ``cache_bytes / slots`` floor, and peak held conversations must
  strictly exceed ``slots``;
- ``readmit_p50_ms`` / ``readmit_p99_ms`` vs ``reprefill_p50_ms`` —
  re-admission must be faster than re-prefilling the conversation.

With ``--spec-ab`` a third phase A/Bs the **speculative tick**
(``docs/serving.md`` "Speculative tick") on identical seeded saturated
decode-heavy traffic: both the target (4L/d128 by default) and a
genuinely small draft (1L/d32) train briefly on an affine token rule
outside the timed windows, so acceptance is high from a draft an order
of magnitude cheaper — the regime speculation pays in.  The ``"spec"``
block GATES tokens/s uplift ≥ ``--spec-uplift``
(default 1.3×), TTFT p99 within 10%, zero failures/recompiles, and the
journaled per-round acceptance rate.  ``--config gemma_tpu_baseline``
additionally appends an informational external-baseline reference row
(the paper's Gemma-on-TPU serving baseline vs the local CPU fixture) to
``bench_log.jsonl`` in the directory of ``--out``.

Usage:
    python scripts/serve_bench.py [--slots 4] [--requests 32] [--rate 20]
                                  [--seed 0] [--out BENCH_SERVE.json]
                                  [--conversations 16] [--turns 2]
                                  [--spec-ab] [--draft-k 3]
                                  [--config gemma_tpu_baseline]
                                  [--print-json]

Exit codes: 0 bench completed + gates hold; 1 any request failed/was
rejected unexpectedly, a recompile was observed, or a tiering/spec gate
broke.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile
import time
from typing import List, Optional

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np  # noqa: E402


def build_engine(n_layer: int, d_model: int, n_head: int, max_seq_len: int):
    import jax
    import jax.numpy as jnp
    import deepspeed_tpu
    from deepspeed_tpu.models import gpt
    cfg = gpt.GPTConfig(vocab_size=256, max_seq_len=max_seq_len,
                        n_layer=n_layer, n_head=n_head, d_model=d_model,
                        dtype=jnp.float32, vocab_round_to=128)
    params = gpt.init(cfg, jax.random.PRNGKey(0))
    return deepspeed_tpu.init_inference(model=(cfg, params),
                                        config={"dtype": "float32"})


def _longtail_lengths(rng, n, lo, hi):
    """Heavy-tailed conversation lengths: most chats are short, a few
    are near the cap — the mix where per-slot ``max_len`` stranding
    hurts most."""
    raw = np.exp(rng.normal(np.log(max(lo * 2, 12)), 0.7, size=n))
    return np.clip(raw.astype(np.int64), lo, hi).astype(np.int64)


def _percentiles_ms(samples) -> dict:
    arr = np.asarray(samples if len(samples) else [0.0], np.float64)
    return {"p50": round(float(np.percentile(arr, 50)), 3),
            "p99": round(float(np.percentile(arr, 99)), 3)}


def run_tiering_phase(engine, args, retain: bool) -> dict:
    """One multi-turn long-tail pass.  ``retain=True`` runs the real
    paged/tiering config (follow-ups re-admit); ``retain=False`` is the
    re-prefill control: the same machinery with zero retention capacity,
    so every follow-up journals a ``serve.readmit`` MISS whose
    ``readmit_ms`` is the honest full-re-prefill admission cost."""
    from deepspeed_tpu.runtime.supervision.events import (EventJournal,
                                                          read_events)
    paging = {"enabled": True, "block_tokens": args.block_tokens}
    if retain:
        # size the warm tier for the working set (half the conversations'
        # full-slot worth — long-tail means most use far fewer blocks);
        # overflow still exercises the host park tiers
        paging["pool_blocks"] = (args.conversations *
                                 (args.tier_max_len // args.block_tokens)
                                 ) // 2
    else:
        paging.update(pool_blocks=1, park_capacity=0)
    jpath = os.path.join(tempfile.mkdtemp(prefix="serve_bench_"),
                         "events.jsonl")
    gw = engine.serve(config={
        "slots": args.slots, "max_len": args.tier_max_len,
        "prefill_chunk": args.prefill_chunk,
        "queue_capacity": args.queue_capacity,
    } | {"paging": paging}, journal=EventJournal(jpath))
    rng = np.random.default_rng(args.seed)   # same workload both passes
    C, T = args.conversations, args.turns
    # conversation histories long enough that re-prefilling them is the
    # real cost re-admission avoids (the fixed-slot pain case)
    plens = _longtail_lengths(rng, C, args.tier_min_prompt,
                              args.tier_max_prompt)
    convs = [{"sid": f"conv-{i}", "history": rng.integers(
        0, 256, (int(plens[i]),)).astype(np.int32)} for i in range(C)]
    # warmup conversation: pays the one-time program compiles
    # (page_gather/scatter on the paged pass) OUTSIDE the timed window
    warm = np.arange(int(plens[0]), dtype=np.int32) % 256
    for _ in range(2):
        out = gw.submit(warm, max_new_tokens=4,
                        session_id="warmup").result(timeout=args.timeout_s)
        warm = np.concatenate([warm, out,
                               np.zeros((4,), np.int32)])
    failed = 0
    t0 = time.monotonic()
    for turn in range(T):
        gaps = rng.exponential(1.0 / args.rate, size=C)
        handles = []
        for i, c in enumerate(convs):
            time.sleep(float(gaps[i]))
            n_new = int(rng.integers(args.min_new, args.max_new + 1))
            handles.append((c, n_new,
                            gw.submit(c["history"], max_new_tokens=n_new,
                                      session_id=c["sid"])))
        for c, n_new, h in handles:
            try:
                out = h.result(timeout=args.timeout_s)
                follow = rng.integers(0, 256, (int(rng.integers(
                    3, 9)),)).astype(np.int32)
                c["history"] = np.concatenate([c["history"], out, follow])
            except Exception as e:
                print(f"  tiering {c['sid']} turn {turn} failed: {e}",
                      file=sys.stderr)
                failed += 1
    wall = time.monotonic() - t0
    snap = gw.snapshot()
    gw.shutdown()
    # follow-up admission latencies from the journal: per session, every
    # serve.readmit AFTER its first is a follow-up turn (hit: tier
    # restore + remainder prefill; miss: full re-prefill)
    seen, follow_hit, follow_miss = set(), [], []
    for e in read_events(jpath, kind="serve.readmit"):
        if e["session"] == "warmup":
            continue
        if e["session"] not in seen:
            seen.add(e["session"])
            continue
        (follow_hit if e["hit"] else follow_miss).append(e["readmit_ms"])
    pool_bytes = snap["paging"]["pool_bytes"]
    slot_bytes = snap["serving_hbm_bytes"] - pool_bytes
    peak = snap["peak_concurrent_conversations"]
    return {
        "retain": retain, "wall_s": round(wall, 3), "failed": failed,
        "completed": snap["completed"], "readmits": snap["readmits"],
        "readmit_misses": snap["readmit_misses"],
        "parked": snap["parked"], "park_spills": snap["park_spills"],
        "pool_evictions": snap["pool_evictions"],
        "recompiles": snap["recompiles"],
        "peak_concurrent_conversations": peak,
        "slot_cache_bytes": slot_bytes, "pool_bytes": pool_bytes,
        "hbm_bytes_per_concurrent_conversation": round(
            (slot_bytes + pool_bytes) / max(1, peak), 1),
        "follow_up_hit_ms": follow_hit, "follow_up_miss_ms": follow_miss,
    }


def run_tiering_bench(args) -> dict:
    """Paged vs re-prefill control on the identical seeded long-tail
    multi-turn workload; returns the gated comparison block."""
    engine = build_engine(args.layers, args.d_model, args.heads,
                          max_seq_len=args.tier_max_len)
    paged = run_tiering_phase(engine, args, retain=True)
    control = run_tiering_phase(engine, args, retain=False)
    readmit = _percentiles_ms(paged["follow_up_hit_ms"])
    reprefill = _percentiles_ms(control["follow_up_miss_ms"])
    fixed_floor = round(paged["slot_cache_bytes"] / max(1, args.slots), 1)
    result = {
        "config": {"conversations": args.conversations,
                   "turns": args.turns,
                   "block_tokens": args.block_tokens,
                   "traffic": "longtail"},
        "paged": {k: v for k, v in paged.items()
                  if not k.startswith("follow_up")},
        "control": {k: v for k, v in control.items()
                    if not k.startswith("follow_up")},
        "hbm_bytes_per_concurrent_conversation":
            paged["hbm_bytes_per_concurrent_conversation"],
        "hbm_bytes_per_conversation_fixed_slots": fixed_floor,
        "readmit_p50_ms": readmit["p50"], "readmit_p99_ms": readmit["p99"],
        "reprefill_p50_ms": reprefill["p50"],
        "reprefill_p99_ms": reprefill["p99"],
    }
    gates = {
        # tiering holds strictly more conversations than the slot cap
        "more_conversations_than_slots":
            paged["peak_concurrent_conversations"] > args.slots,
        # and pays less HBM per held conversation than fixed slots
        "hbm_per_conversation_beats_fixed":
            result["hbm_bytes_per_concurrent_conversation"] < fixed_floor,
        # re-admission must beat re-prefilling the whole conversation
        "readmit_faster_than_reprefill":
            readmit["p50"] < reprefill["p50"],
        "no_failures": paged["failed"] == 0 and control["failed"] == 0,
        "no_recompiles": paged["recompiles"] == 0
            and control["recompiles"] == 0,
        # every measured follow-up re-admitted (+ the warmup session's)
        "all_followups_readmitted":
            paged["readmits"] >= args.conversations * (args.turns - 1),
    }
    result["gates"] = gates
    result["gates_ok"] = all(gates.values())
    return result


def _train_rule_params(cfg, steps: int, row_len: int, lr: float = 3e-3):
    """Train ``cfg`` on the affine rule ``t[i+1] = (3 t[i] + 7) % 256``
    (the fixture of ``tests/unit/inference/test_speculative.py``): the
    greedy continuation changes token every step, and a SMALL draft
    learns the same rule — high acceptance from a genuinely cheaper
    proposal model, which is the regime speculation pays in.  ``row_len``
    must cover the serve-time positions (learned positional embeddings:
    untrained positions emit noise and crater acceptance)."""
    import jax
    import jax.numpy as jnp
    import deepspeed_tpu
    from deepspeed_tpu.parallel.mesh import (ParallelDims, initialize_mesh,
                                             reset_mesh_manager)
    from deepspeed_tpu.runtime.model import from_gpt
    reset_mesh_manager()
    rows = []
    for s in range(8):
        t = [(s * 17 + 3) % 256]
        for _ in range(row_len - 1):
            t.append((t[-1] * 3 + 7) % 256)
        rows.append(t)
    data = np.asarray(rows, np.int32)
    mm = initialize_mesh(ParallelDims(dp=-1))
    eng, _, _, _ = deepspeed_tpu.initialize(
        model=from_gpt(cfg),
        config={"train_micro_batch_size_per_gpu": 8 // mm.dp_world_size,
                "gradient_accumulation_steps": 1,
                "optimizer": {"type": "Adam", "params": {"lr": lr}},
                "zero_optimization": {"stage": 1},
                "steps_per_print": 1 << 30},
        mesh_manager=mm, rng=jax.random.PRNGKey(0))
    for _ in range(steps):
        eng.train_batch_fused({"tokens": data})
    params = jax.tree_util.tree_map(
        lambda l: jnp.asarray(np.asarray(jax.device_get(l), np.float32)),
        eng.state["params"])
    reset_mesh_manager()
    return params


def _rule_prompt(start: int, length: int) -> np.ndarray:
    t = [int(start) % 256]
    for _ in range(length - 1):
        t.append((t[-1] * 3 + 7) % 256)
    return np.asarray(t, np.int32)


def run_spec_phase(engine, draft, args, spec: bool) -> dict:
    """One saturated closed-loop pass of the seeded rule-following
    traffic (all requests submitted up front — throughput measurement,
    not arrival modelling).  ``spec=True`` runs the speculative tick
    with the trained small draft; ``spec=False`` is the plain one-token
    tick on the identical workload."""
    from deepspeed_tpu.runtime.supervision.events import (EventJournal,
                                                          read_events)
    config = {
        "slots": args.slots, "max_len": args.max_len,
        "prefill_chunk": args.prefill_chunk,
        "queue_capacity": max(args.queue_capacity, args.spec_requests + 1),
        "journal_every_ticks": 1,
    }
    if spec:
        config["speculative"] = {"enabled": True, "draft_k": args.draft_k}
    jpath = os.path.join(tempfile.mkdtemp(prefix="serve_bench_spec_"),
                         "events.jsonl")
    gw = engine.serve(config=config, journal=EventJournal(jpath),
                      draft=draft if spec else None)
    rng = np.random.default_rng(args.seed)   # same workload both passes
    R = args.spec_requests
    margin = args.draft_k   # identical budgets whether spec is on or off
    hi_new = min(args.spec_max_new,
                 args.max_len - args.spec_max_prompt - margin)
    # rule-following greedy traffic on a decode-heavy shape (short
    # prompts, long budgets): the draft-friendly fixture — the trained
    # draft's proposals verify, so the gate measures the per-round
    # amortization, not draft quality.  Short prompts keep admission
    # (identical prefill work in both passes) from drowning the decode
    # loop the A/B is about
    prompts = [_rule_prompt(int(rng.integers(0, 256)),
                            int(rng.integers(args.min_prompt,
                                             args.spec_max_prompt + 1)))
               for _ in range(R)]
    budgets = [int(rng.integers(args.spec_min_new, hi_new + 1))
               for _ in range(R)]
    # warmup outside the timed window: pays every compile the measured
    # traffic can hit — the prompt must span MULTIPLE prefill chunks
    # (the chunked `extend` program only compiles on the second chunk;
    # in the speculative pass `draft_extend` likewise) and the budget
    # must run full speculative rounds for the draft/verify/accept set
    warm_len = min(args.prefill_chunk + 8,
                   args.max_len - args.draft_k - 8)
    gw.submit(_rule_prompt(3, warm_len),
              max_new_tokens=args.draft_k + 5).result(timeout=args.timeout_s)
    failed = 0
    ttfts = []
    t0 = time.monotonic()
    handles = [gw.submit(prompts[i], max_new_tokens=budgets[i],
                         seed=int(args.seed) + i) for i in range(R)]
    for h in handles:
        try:
            h.result(timeout=args.timeout_s)
            ttfts.append(h.ttft_s)
        except Exception as e:
            print(f"  spec-ab request {h.request_id} failed: {e}",
                  file=sys.stderr)
            failed += 1
    wall = time.monotonic() - t0
    snap = gw.snapshot()
    gw.shutdown()
    rounds = read_events(jpath, kind="serve.spec_round")
    return {
        "spec": spec, "wall_s": round(wall, 3), "failed": failed,
        "completed": len(handles) - failed,
        "tokens_out": int(sum(budgets)),
        "tokens_per_s": round(sum(budgets) / wall, 3),
        "ttft_ms": _percentiles_ms([t * 1e3 for t in ttfts
                                    if t is not None]),
        "ticks": snap["ticks"], "recompiles": snap["recompiles"],
        "spec_rounds": snap["spec_rounds"],
        "accept_rate_mean": round(snap["spec_accept_rate_mean"], 4),
        "spec_rounds_journaled": sum(
            1 for e in rounds if e.get("accept_rate") is not None),
    }


def run_spec_bench(args) -> dict:
    """Speculation off vs on over the identical seeded saturated
    workload; returns the gated A/B block.  Both models train briefly on
    the affine rule OUTSIDE the timed windows (the draft-friendly
    fixture: high acceptance from a draft ~an order of magnitude
    cheaper than the target)."""
    import jax.numpy as jnp
    import deepspeed_tpu
    from deepspeed_tpu.models import gpt
    row_len = min(args.max_len,
                  args.spec_max_prompt + args.spec_max_new
                  + args.draft_k + 8)
    # the spec phase runs its own target (bigger than the main bench
    # fixture): with a dispatch-bound toy model the per-tick cost is
    # flat and batched verify can't amortize — the uplift the gate
    # guards only exists once target steps are compute-bound
    tcfg = gpt.GPTConfig(vocab_size=256, max_seq_len=args.max_len,
                         n_layer=args.spec_layers, n_head=args.heads,
                         d_model=args.spec_d_model, dtype=jnp.float32,
                         vocab_round_to=128)
    dcfg = gpt.GPTConfig(vocab_size=256, max_seq_len=args.max_len,
                         n_layer=1, n_head=2, d_model=32,
                         dtype=jnp.float32, vocab_round_to=128)
    tparams = _train_rule_params(tcfg, args.spec_train_steps, row_len)
    dparams = _train_rule_params(dcfg, args.spec_train_steps + 40, row_len)
    engine = deepspeed_tpu.init_inference(model=(tcfg, tparams),
                                          config={"dtype": "float32"})
    draft = (dcfg, dparams)
    # best-of-N per arm: the passes are sub-second on the CPU fixture,
    # so scheduler noise dominates a single trial — any failure or
    # recompile in ANY trial still fails the gates below
    offs = [run_spec_phase(engine, draft, args, spec=False)
            for _ in range(args.spec_trials)]
    ons = [run_spec_phase(engine, draft, args, spec=True)
           for _ in range(args.spec_trials)]
    off = max(offs, key=lambda r: r["tokens_per_s"])
    on = max(ons, key=lambda r: r["tokens_per_s"])
    off["failed"] = sum(r["failed"] for r in offs)
    on["failed"] = sum(r["failed"] for r in ons)
    off["recompiles"] = max(r["recompiles"] for r in offs)
    on["recompiles"] = max(r["recompiles"] for r in ons)
    uplift = round(on["tokens_per_s"] / max(off["tokens_per_s"], 1e-9), 3)
    result = {
        "config": {"draft_k": args.draft_k,
                   "target": {"n_layer": args.spec_layers,
                              "d_model": args.spec_d_model,
                              "n_head": args.heads,
                              "trained_steps": args.spec_train_steps},
                   "draft": {"n_layer": 1, "d_model": 32, "n_head": 2,
                             "trained_steps": args.spec_train_steps + 40},
                   "requests": args.spec_requests,
                   "trials": args.spec_trials,
                   "max_prompt": args.spec_max_prompt,
                   "new_tokens": [args.spec_min_new, args.spec_max_new],
                   "traffic": "affine-rule greedy, saturated"},
        "off": off, "on": on,
        "tokens_per_s_off": off["tokens_per_s"],
        "tokens_per_s_on": on["tokens_per_s"],
        "uplift": uplift,
        "ttft_p99_off_ms": off["ttft_ms"]["p99"],
        "ttft_p99_on_ms": on["ttft_ms"]["p99"],
        "accept_rate_mean": on["accept_rate_mean"],
    }
    gates = {
        # the headline: batched draft/verify must beat one-token ticks
        "tokens_per_s_uplift": uplift >= args.spec_uplift,
        # speculation must not tax first-token latency (admission still
        # prefills the same prompts) — p99 within 10%
        "ttft_p99_within_10pct":
            on["ttft_ms"]["p99"] <= off["ttft_ms"]["p99"] * 1.1,
        "no_failures": off["failed"] == 0 and on["failed"] == 0,
        "no_recompiles": off["recompiles"] == 0 and on["recompiles"] == 0,
        # the per-round acceptance rate landed in the journal
        "acceptance_journaled": on["spec_rounds_journaled"] > 0
            and on["spec_rounds"] > 0,
    }
    result["gates"] = gates
    result["gates_ok"] = all(gates.values())
    return result


#: external serving baselines the trajectory log can carry as
#: informational reference rows (--config <name>); numbers are from the
#: cited papers, NOT comparable to the local CPU fixture — the row
#: records the reference point next to the trajectory, it gates nothing
EXTERNAL_BASELINES = {
    "gemma_tpu_baseline": {
        "paper": "Fine-Tuning and Serving Gemma 4 31B on Google Cloud "
                 "TPU: A Technical Comparison with GPU Baselines",
        "source": "https://arxiv.org/pdf/2605.25645",
        "system": "Gemma 4 31B served on Cloud TPU (paper's serving "
                  "comparison vs GPU baselines)",
        "note": "external reference row: paper-scale model on TPU vs "
                "this repo's tiny random-init CPU fixture — magnitudes "
                "are NOT comparable; tracked so the serving trajectory "
                "carries the external reference point",
    },
}


def emit_external_baseline(args, result: dict) -> str:
    """Append one informational external-baseline row to
    ``bench_log.jsonl`` beside ``--out``: the named paper baseline next to
    the local fixture numbers."""
    base = EXTERNAL_BASELINES[args.config]
    row = {
        "label": f"serve-{args.config.replace('_', '-')}",
        "external": True,
        "ts": time.strftime("%Y-%m-%dT%H:%M:%S"),
        **base,
        "local_fixture": {
            "throughput_tok_s": result["throughput_tok_s"],
            "ttft_p50_ms": result["ttft_p50_ms"],
            "ttft_p99_ms": result["ttft_p99_ms"],
            "slot_occupancy": result["slot_occupancy"],
            "model": result["config"]["model"],
            "slots": result["config"]["slots"],
            "platform": "cpu-fixture",
        },
    }
    if "spec" in result:
        row["local_fixture"]["spec_uplift"] = result["spec"]["uplift"]
        row["local_fixture"]["spec_accept_rate"] = \
            result["spec"]["accept_rate_mean"]
    path = os.path.join(os.path.dirname(os.path.abspath(args.out)),
                        "bench_log.jsonl")
    with open(path, "a") as f:
        f.write(json.dumps(row) + "\n")
    return path


def run_bench(args) -> dict:
    from deepspeed_tpu.serving import QueueFullError

    engine = build_engine(args.layers, args.d_model, args.heads,
                          max_seq_len=args.max_len)
    gw = engine.serve(config={
        "slots": args.slots, "max_len": args.max_len,
        "prefill_chunk": args.prefill_chunk,
        "queue_capacity": args.queue_capacity,
        "default_deadline_s": args.deadline_s,
    })
    rng = np.random.default_rng(args.seed)
    # Poisson arrivals: exponential inter-arrival gaps at --rate req/s
    gaps = rng.exponential(1.0 / args.rate, size=args.requests)
    prompts = [rng.integers(0, 256, (int(rng.integers(
        args.min_prompt, args.max_prompt + 1)),)).astype(np.int32)
        for _ in range(args.requests)]
    budgets = [int(rng.integers(args.min_new, args.max_new + 1))
               for _ in range(args.requests)]
    sampled = rng.random(args.requests) < args.sample_frac

    handles: List[Optional[object]] = []
    rejected = 0
    t0 = time.monotonic()
    for i in range(args.requests):
        time.sleep(float(gaps[i]))
        try:
            handles.append(gw.submit(
                prompts[i], max_new_tokens=budgets[i],
                do_sample=bool(sampled[i]), temperature=0.9,
                seed=int(args.seed) + i))
        except QueueFullError:
            rejected += 1
            handles.append(None)
    ok, failed = 0, 0
    for h in handles:
        if h is None:
            continue
        try:
            h.result(timeout=args.timeout_s)
            ok += 1
        except Exception as e:  # timeouts/cancels count against the run
            print(f"  request {h.request_id} failed: {e}", file=sys.stderr)
            failed += 1
    wall = time.monotonic() - t0
    snap = gw.snapshot()
    gw.shutdown()

    ttft = np.asarray(snap.pop("ttft_s") or [0.0])
    snap.pop("compile_counts", None)
    result = {
        "config": {
            "slots": args.slots, "max_len": args.max_len,
            "prefill_chunk": args.prefill_chunk,
            "queue_capacity": args.queue_capacity,
            "requests": args.requests, "rate": args.rate,
            "seed": args.seed,
            "prompt_len": [args.min_prompt, args.max_prompt],
            "max_new_tokens": [args.min_new, args.max_new],
            "sample_frac": args.sample_frac,
            "model": {"layers": args.layers, "d_model": args.d_model,
                      "heads": args.heads},
        },
        "wall_s": round(wall, 3),
        "completed": ok, "failed": failed, "rejected": rejected,
        "throughput_tok_s": round(snap["tokens_out"] / wall, 3),
        "ttft_p50_ms": round(float(np.percentile(ttft, 50)) * 1e3, 3),
        "ttft_p99_ms": round(float(np.percentile(ttft, 99)) * 1e3, 3),
        "slot_occupancy": round(snap["slot_occupancy"], 4),
        # compile discipline: post-warmup recompiles must stay 0; the
        # host-sync count is the tick loop's sanctioned d2h pulls
        "recompiles": snap["recompiles"],
        "host_syncs": snap["host_syncs"],
        "metrics": {k: v for k, v in snap.items()
                    if isinstance(v, (int, float))},
    }
    if args.turns > 1:
        result["tiering"] = run_tiering_bench(args)
    if args.spec_ab:
        result["spec"] = run_spec_bench(args)
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--max-len", type=int, default=128)
    ap.add_argument("--prefill-chunk", type=int, default=16)
    ap.add_argument("--queue-capacity", type=int, default=256)
    ap.add_argument("--requests", type=int, default=32)
    ap.add_argument("--rate", type=float, default=20.0,
                    help="mean arrivals per second (Poisson)")
    ap.add_argument("--min-prompt", type=int, default=4)
    ap.add_argument("--max-prompt", type=int, default=48)
    ap.add_argument("--min-new", type=int, default=4)
    ap.add_argument("--max-new", type=int, default=32)
    ap.add_argument("--sample-frac", type=float, default=0.5,
                    help="fraction of requests that sample (rest greedy)")
    ap.add_argument("--deadline-s", type=float, default=None)
    ap.add_argument("--timeout-s", type=float, default=300.0)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--layers", type=int, default=2)
    ap.add_argument("--d-model", type=int, default=64)
    ap.add_argument("--heads", type=int, default=4)
    ap.add_argument("--conversations", type=int, default=16,
                    help="long-tail multi-turn conversations in the "
                         "tiering phase")
    ap.add_argument("--turns", type=int, default=2,
                    help="turns per conversation (1 disables the "
                         "tiering phase)")
    ap.add_argument("--block-tokens", type=int, default=16)
    ap.add_argument("--tier-max-len", type=int, default=256,
                    help="slot length of the tiering phase (long "
                         "conversations are where re-prefill hurts)")
    ap.add_argument("--tier-min-prompt", type=int, default=16)
    ap.add_argument("--tier-max-prompt", type=int, default=160)
    ap.add_argument("--spec-ab", action="store_true",
                    help="run the speculative A/B phase: the same seeded "
                         "saturated traffic with speculation off vs on "
                         "(trained affine-rule target + small draft), "
                         "gating tokens/s uplift and TTFT")
    ap.add_argument("--draft-k", type=int, default=3,
                    help="draft proposals per speculative round")
    ap.add_argument("--spec-requests", type=int, default=16,
                    help="requests per speculative A/B pass")
    ap.add_argument("--spec-trials", type=int, default=2,
                    help="trials per arm; tokens/s is best-of (the "
                         "passes are sub-second, scheduler noise "
                         "dominates one trial)")
    ap.add_argument("--spec-layers", type=int, default=4,
                    help="target depth of the A/B fixture (big enough "
                         "that ticks are compute-bound, not dispatch)")
    ap.add_argument("--spec-d-model", type=int, default=128)
    ap.add_argument("--spec-max-prompt", type=int, default=24,
                    help="A/B prompts stay short: admission prefill is "
                         "identical in both passes and dilutes the "
                         "decode-loop uplift the gate measures")
    ap.add_argument("--spec-min-new", type=int, default=48)
    ap.add_argument("--spec-max-new", type=int, default=64)
    ap.add_argument("--spec-uplift", type=float, default=1.3,
                    help="minimum tokens/s uplift the A/B gate demands")
    ap.add_argument("--spec-train-steps", type=int, default=120,
                    help="affine-rule training steps for the A/B "
                         "target (draft trains 40 more)")
    ap.add_argument("--config", default=None,
                    choices=sorted(EXTERNAL_BASELINES),
                    help="also append this named external-baseline "
                         "reference row to bench_log.jsonl beside --out "
                         "(informational, gates nothing)")
    ap.add_argument("--print-json", action="store_true",
                    help="print the result as one JSON line on stdout "
                         "(one row, for a caller that collects them)")
    ap.add_argument("--out", default="BENCH_SERVE.json")
    args = ap.parse_args(argv)

    result = run_bench(args)
    tmp = args.out + ".tmp"
    with open(tmp, "w") as f:
        json.dump(result, f, indent=2, sort_keys=True)
        f.write("\n")
    os.replace(tmp, args.out)
    print(f"wrote {args.out}:")
    print(f"  throughput  {result['throughput_tok_s']} tok/s")
    print(f"  ttft        p50 {result['ttft_p50_ms']} ms   "
          f"p99 {result['ttft_p99_ms']} ms")
    print(f"  occupancy   {result['slot_occupancy']}")
    print(f"  completed {result['completed']}  failed {result['failed']}  "
          f"rejected {result['rejected']}")
    print(f"  recompiles  {result['recompiles']}   "
          f"host_syncs {result['host_syncs']}")
    tier_ok = True
    tier = result.get("tiering")
    if tier is not None:
        print(f"  tiering     conversations "
              f"{tier['paged']['peak_concurrent_conversations']} held on "
              f"{args.slots} slots")
        print(f"              hbm/conv {tier['hbm_bytes_per_concurrent_conversation']} B "
              f"(fixed-slot floor "
              f"{tier['hbm_bytes_per_conversation_fixed_slots']} B)")
        print(f"              readmit p50 {tier['readmit_p50_ms']} ms  "
              f"p99 {tier['readmit_p99_ms']} ms   vs re-prefill p50 "
              f"{tier['reprefill_p50_ms']} ms")
        if not tier["gates_ok"]:
            bad = [k for k, v in tier["gates"].items() if not v]
            print(f"  TIERING GATE FAILED: {bad}", file=sys.stderr)
            tier_ok = False
    spec_ok = True
    spec = result.get("spec")
    if spec is not None:
        print(f"  spec        {spec['tokens_per_s_off']} tok/s off  →  "
              f"{spec['tokens_per_s_on']} tok/s on   "
              f"(uplift {spec['uplift']}x, draft_k "
              f"{spec['config']['draft_k']})")
        print(f"              accept_rate {spec['accept_rate_mean']}   "
              f"ttft p99 {spec['ttft_p99_off_ms']} → "
              f"{spec['ttft_p99_on_ms']} ms")
        if not spec["gates_ok"]:
            bad = [k for k, v in spec["gates"].items() if not v]
            print(f"  SPEC GATE FAILED: {bad}", file=sys.stderr)
            spec_ok = False
    if args.config is not None:
        path = emit_external_baseline(args, result)
        print(f"  external    appended {args.config} reference row to "
              f"{os.path.relpath(path)}")
    if args.print_json:
        print(json.dumps(result))
    return 1 if result["failed"] or result["recompiles"] \
        or not tier_ok or not spec_ok else 0


if __name__ == "__main__":
    from deepspeed_tpu.utils.platform import enable_compile_cache
    enable_compile_cache()
    sys.exit(main())
