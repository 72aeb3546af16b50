#!/usr/bin/env python3
"""Find a serving cell's knee: the same cell at several fixed rates.

    python3 benchmarks/chip/sweep.py --workload <open-loop cell> \
        --rates 3.5 4.5 5.5 6.5 --seconds 20 [--seed 1]

Made once, when a cell is defined (or when an optimisation has moved the
knee); a run of the benchmark never searches.  The parent never touches JAX,
so each rate's child has the chip to itself.  For every rate it prints the
queue left at the window's end (a queue that grows says the rate is above
the knee), the time to first token (median and tail), the time per output
token, the slot occupancy and how late the generator sent.  The standing
population scales with the rate (``rate x mean output x --token-s``, the
mean taken from the traffic file's own grid of output lengths unless
``--mean-output`` gives it).
"""

import argparse
import json
import os
import subprocess
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))


def child(workload: str, traffic: dict, seed: int, seconds: float) -> int:
    t0 = time.perf_counter()
    sys.path.insert(0, ROOT)
    from benchmarks.chip import harness, stats
    cell = harness.load_cell(ROOT, workload)
    cell.traffic.update(traffic)
    ctx = harness.measure(cell, seed, seconds, False, t0)
    ttft, tpot = ctx.samples["ttft_ms"], ctx.samples["tpot_ms"]
    print("SWEEP " + json.dumps({
        "rate_hz": traffic["rate_hz"], "standing": traffic["standing"],
        "due": ctx.attempted, "failed": ctx.failed,
        "queue_at_close": ctx.scalars["queue_at_close"],
        "ttft_p50_ms": round(stats.percentile(ttft, 50), 1),
        "ttft_p90_ms": round(stats.percentile(ttft, 90), 1),
        "ttft_p95_ms": round(stats.percentile(ttft, 95), 1),
        "ttft_max_ms": round(max(ttft), 1),
        "tpot_p50_ms": round(stats.percentile(tpot, 50), 1) if tpot else None,
        "occupancy": round(ctx.scalars["slot_occupancy"], 3),
        "late_p95_ms": round(stats.percentile(ctx.samples["late_ms"], 95), 3),
        "largest_tick_gap_s": round(ctx.scalars["largest_tick_gap_s"], 3),
        "correct": all(ctx.checks.values())}), flush=True)
    return 0


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--mean-output", type=float, default=None)
    ap.add_argument("--token-s", type=float, default=0.006,
                    help="seconds a stream takes for a token near the knee")
    ap.add_argument("--child", default=None)
    args = ap.parse_args()
    if args.child:
        return child(args.workload, json.loads(args.child), args.seed,
                     args.seconds)
    mean_output = args.mean_output
    if mean_output is None:
        sys.path.insert(0, ROOT)
        from benchmarks.chip.stats import quantile_grid
        with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
            cell = next(w for w in json.load(f)["workloads"]
                        if w["name"] == args.workload)
        with open(os.path.join(HERE, "traffic",
                               cell["traffic"] + ".json")) as f:
            grid = quantile_grid(1000, json.load(f)["output_len"])
        mean_output = sum(grid) / len(grid)
    for rate in args.rates:
        traffic = {"rate_hz": rate, "standing": int(round(
            rate * mean_output * args.token_s))}
        proc = subprocess.run(
            [sys.executable, os.path.abspath(__file__), "--workload",
             args.workload, "--seconds", str(args.seconds), "--seed",
             str(args.seed), "--rates", str(rate), "--child",
             json.dumps(traffic)], capture_output=True, text=True)
        lines = [l for l in proc.stdout.splitlines() if l.startswith("SWEEP ")]
        print(lines[-1] if lines else
              f"rate {rate}: exit {proc.returncode}\n{proc.stdout[-1500:]}"
              f"\n{proc.stderr[-1500:]}", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
