#!/usr/bin/env python3
"""Goodput regression gate: run the fault-scenario matrix → BENCH_GOODPUT.json.

Each scenario spawns a real simulated fleet (``deepspeed_tpu/goodput``:
N engine subprocesses, shared checkpoint dir, ``FileConsensusChannel``,
fault plans via ``DS_FAULT_PLAN``) and scores goodput / MTTR / wasted
steps / invariant checks from the run's ``events.jsonl``.  The committed
artifact makes robustness regressions diffable per PR, the same way
``BENCH_SERVE.json`` tracks serving throughput and ``BENCH_COMPILE.json``
tracks compile counts: a scenario whose goodput drops past tolerance, or
that starts violating an invariant, fails the gate.

Step-count metrics (goodput, useful/wasted steps, incidents) are
deterministic given a scenario seed, so the gate compares them tight;
wall-clock metrics (MTTR, goodput_wall) are reported and bounded only by
each scenario's own generous ``max_mttr_s`` expectation.

Usage:
    python scripts/goodput_bench.py [--scenarios a,b,...] [--seed 0]
                                    [--out BENCH_GOODPUT.json]
                                    [--baseline BENCH_GOODPUT.json]
                                    [--goodput-tolerance 0.1]
                                    [--keep-runs DIR]

Exit codes: 0 every scenario ok and no regression vs the baseline;
1 any scenario failed its expectations, violated an invariant, or
regressed past tolerance (the report is still written).
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import subprocess
import sys
import tempfile

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def check_rank_telemetry(run_dir: str, world_size: int) -> bool:
    """Every rank must have produced a parseable ``metrics.rank<N>.jsonl``
    — verified by running ``scripts/run_report.py`` on the scenario's run
    dir (the report CLI is the single implementation of that check)."""
    rr = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                      "run_report.py")
    proc = subprocess.run(
        [sys.executable, rr, run_dir,
         "--expect-rank-metrics", str(world_size)],
        capture_output=True, text=True)
    if proc.returncode != 0:
        print(f"[goodput-bench]   telemetry check failed:\n{proc.stderr}",
              file=sys.stderr, flush=True)
    return proc.returncode == 0


def check_mttr_decomposition(run_dir: str) -> list:
    """Every recovered incident's critical-path phases must sum to the
    journal MTTR *exactly* (the clamping contract of
    ``telemetry/critical_path.py``) — for stage-group pipelines that is
    the detect → respawn → warm → requiesce → replay decomposition
    ``docs/pipeline-mpmd.md`` promises.  A decomposition that drifts from
    the journal means the phase anchors regressed, and fails the
    scenario like any other expectation."""
    from deepspeed_tpu.runtime.supervision.events import read_events
    from deepspeed_tpu.telemetry.critical_path import (
        decompose_stage_restarts, decompose_training_restarts)
    evs = read_events(os.path.join(run_dir, "events.jsonl"))
    stage_rows = [m for m in decompose_stage_restarts(evs)
                  if m["recovered"] and m.get("stage") is not None]
    rows = stage_rows or [m for m in decompose_training_restarts(evs)
                          if m["recovered"]]
    problems = []
    for m in rows:
        total_s = sum(m["phases"].values()) / 1000.0
        if abs(total_s - m["mttr_s"]) > 2e-3:
            problems.append(
                f"MTTR decomposition drifts from the journal: phases sum "
                f"to {total_s:.3f}s but mttr_s={m['mttr_s']} "
                f"(incarnation {m.get('incarnation')})")
    return problems


def run_matrix(args) -> dict:
    from deepspeed_tpu.goodput import build_scenario, run_scenario
    from deepspeed_tpu.goodput.scenarios import scenario_names

    names = args.scenarios.split(",") if args.scenarios \
        else list(scenario_names())
    keep = args.keep_runs
    base_dir = keep or tempfile.mkdtemp(prefix="goodput_bench_")
    scores = {}
    try:
        for name in names:
            scenario = build_scenario(name, seed=args.seed)
            run_dir = os.path.join(base_dir, name)
            shutil.rmtree(run_dir, ignore_errors=True)
            print(f"[goodput-bench] {name}: world={scenario.world_size} "
                  f"target={scenario.target_steps} "
                  f"faults={len(scenario.faults)}", flush=True)
            score = run_scenario(run_dir, scenario)
            # silent telemetry breakage under restarts fails the scenario
            # like any other expectation
            score["telemetry_ok"] = check_rank_telemetry(
                run_dir, scenario.world_size)
            if not score["telemetry_ok"]:
                score["ok"] = False
                score.setdefault("failures", []).append(
                    "a rank produced no parseable metrics.jsonl "
                    "(run_report --expect-rank-metrics)")
            decomp_problems = check_mttr_decomposition(run_dir)
            score["mttr_decomposition_ok"] = not decomp_problems
            if decomp_problems:
                score["ok"] = False
                score.setdefault("failures", []).extend(decomp_problems)
            scores[name] = score
            print(f"[goodput-bench]   goodput={score['goodput']} "
                  f"wasted={score['wasted_steps']} "
                  f"incidents={score['incidents']} "
                  f"mttr_max={score['mttr_s']['max']} "
                  f"violations={score['invariant_violations']['total']} "
                  f"ok={score['ok']}", flush=True)
            if not score["ok"]:
                for f in score["failures"]:
                    print(f"[goodput-bench]   FAIL: {f}", file=sys.stderr,
                          flush=True)
    finally:
        if not keep:
            shutil.rmtree(base_dir, ignore_errors=True)
    return {
        "config": {"seed": args.seed, "scenarios": names},
        "scenarios": {
            name: {k: v for k, v in score.items() if k != "kinds"}
            for name, score in scores.items()
        },
        "summary": {
            "scenarios": len(scores),
            "ok": sum(1 for s in scores.values() if s["ok"]),
            "mean_goodput": round(
                sum(s["goodput"] for s in scores.values()) / len(scores), 4)
            if scores else 0.0,
            "total_invariant_violations": sum(
                s["invariant_violations"]["total"] for s in scores.values()),
        },
    }


def gate(result: dict, baseline: dict, tolerance: float) -> list:
    """Regressions of the new result vs the committed baseline.  Only
    deterministic step-count metrics gate hard; scenarios new to the
    matrix pass on their own expectations."""
    problems = []
    base_scen = (baseline or {}).get("scenarios", {})
    for name, score in result["scenarios"].items():
        if not score["ok"]:
            problems.append(f"{name}: failed its own expectations: "
                            + "; ".join(score.get("failures", ())))
        base = base_scen.get(name)
        if base is None:
            continue
        if score["goodput"] < base["goodput"] - tolerance:
            problems.append(
                f"{name}: goodput {score['goodput']} regressed past "
                f"baseline {base['goodput']} - {tolerance}")
        base_viol = base.get("invariant_violations", {}).get("total", 0)
        if score["invariant_violations"]["total"] > base_viol:
            problems.append(
                f"{name}: {score['invariant_violations']['total']} invariant "
                f"violation(s) vs {base_viol} in the baseline: "
                + "; ".join(score["invariant_violations"]["problems"]))
    return problems


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--scenarios", default=None,
                    help="comma-separated scenario names (default: all)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--out", default="BENCH_GOODPUT.json")
    ap.add_argument("--baseline", default=None,
                    help="baseline artifact to gate against "
                         "(default: the existing --out file)")
    ap.add_argument("--goodput-tolerance", type=float, default=0.1)
    ap.add_argument("--keep-runs", default=None,
                    help="keep per-scenario run dirs under this directory")
    ap.add_argument("--print-json", action="store_true",
                    help="emit a one-line JSON summary on stdout "
                         "(one row, for a caller that collects them)")
    args = ap.parse_args(argv)

    baseline_path = args.baseline or args.out
    baseline = None
    if os.path.exists(baseline_path):
        try:
            with open(baseline_path) as f:
                baseline = json.load(f)
        except ValueError as e:
            print(f"[goodput-bench] unreadable baseline {baseline_path}: {e}",
                  file=sys.stderr)

    result = run_matrix(args)
    problems = gate(result, baseline, args.goodput_tolerance)

    tmp = args.out + ".tmp"
    with open(tmp, "w") as f:
        json.dump(result, f, indent=1, sort_keys=True)
        f.write("\n")
    os.replace(tmp, args.out)
    if args.print_json:
        print(json.dumps({
            "bench": "goodput", "summary": result["summary"],
            "detail": {
                name: {"ok": s["ok"], "goodput": s["goodput"],
                       "mttr_max": s["mttr_s"]["max"],
                       "violations": s["invariant_violations"]["total"]}
                for name, s in result["scenarios"].items()}}))
    s = result["summary"]
    print(f"wrote {args.out}: {s['ok']}/{s['scenarios']} scenarios ok, "
          f"mean goodput {s['mean_goodput']}, "
          f"{s['total_invariant_violations']} invariant violation(s)")
    for p in problems:
        print(f"REGRESSION: {p}", file=sys.stderr)
    return 1 if problems else 0


if __name__ == "__main__":
    sys.exit(main())
