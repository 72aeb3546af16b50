#!/usr/bin/env python3
"""Two sets of runs of ONE tree, as a check will make them: the test a
builder runs before submitting a cell or a bound (PERF.md 2).

    python3 benchmarks/chip/spread.py --workload <cell> [--runs 6] \
        [--from-tar <git archive>]

The tree this file lies in is copied twice, to ``.spread/A`` and
``.spread/B`` (``--from-tar`` unpacks a ``git archive`` there instead), and
each copy gets a ``HOME``, an ``XDG_CACHE_HOME`` and a ``TMPDIR`` of its
own and its own compile cache, as the driver gives the parent and the
change.  Each side first makes one short run that compiles (recorded
apart); then run ``i`` of either set takes seed ``i`` for ``run_seconds``,
untraced, the first half of the pairs A then B and the rest B then A.  The
parent never touches JAX, so every run has the chip to itself.

Printed and written to ``chiprun_out/spread/<cell>/``: every run's metrics
and set-up phases; for each metric each set's spread (the distance between
the first and the third quartile of ``statistics.quantiles(values, n=4)``
over the median), their mean with each set's run farthest from its median
left out (what a bound is too tight against) and the wider spread of all
the runs (what it is too loose against); B's median over A's; and, for
``setup_s`` and its phases, the median of the runs made second in their
pair over that of the runs made first.
"""

import argparse
import json
import os
import re
import shutil
import statistics
import subprocess
import sys
import tarfile
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
_SKIP = (".git", ".spread", ".jax_cache", ".bench_trace", "chiprun_out",
         "__pycache__", ".pytest_cache", ".archive_check", ".scratch")
_PHASE = re.compile(r"^\[setup\] done=(\S+) s=([0-9.]+)", re.M)


def spread(values) -> float:
    """Interquartile distance over the median."""
    if len(values) < 2:
        return 0.0
    q = statistics.quantiles(values, n=4)
    return (q[2] - q[0]) / abs(statistics.median(values))


def without_farthest(values):
    if len(values) < 3:
        return list(values)
    med = statistics.median(values)
    far = max(range(len(values)), key=lambda i: abs(values[i] - med))
    return [v for i, v in enumerate(values) if i != far]


def summarize(a, b) -> dict:
    """The readings of one metric's two sets."""
    return {
        "spread_a": spread(a), "spread_b": spread(b),
        "tight": (spread(without_farthest(a))
                  + spread(without_farthest(b))) / 2.0,
        "loose": max(spread(a), spread(b), spread(list(a) + list(b))),
        "median_a": statistics.median(a), "median_b": statistics.median(b),
        "b_over_a": statistics.median(b) / statistics.median(a)}


def make_trees(base: str, tar: str | None) -> dict:
    shutil.rmtree(base, ignore_errors=True)
    trees = {}
    for side in "AB":
        dest = os.path.join(base, side)
        if tar:
            os.makedirs(dest)
            with tarfile.open(tar) as t:
                t.extractall(dest)
        else:
            shutil.copytree(ROOT, dest, ignore=shutil.ignore_patterns(*_SKIP))
        trees[side] = dest
    return trees


def one_run(tree: str, side: str, base: str, workload: str, seed: int,
            seconds: float, log_path: str) -> dict:
    env = {k: v for k, v in os.environ.items() if k != "BENCH_RUN"}
    for var in ("HOME", "XDG_CACHE_HOME", "TMPDIR"):
        env[var] = os.path.join(base, f"{var.lower()}_{side}")
        os.makedirs(env[var], exist_ok=True)
    t0 = time.monotonic()
    proc = subprocess.run(
        [sys.executable, os.path.join("benchmarks", "chip", "run.py"),
         "--workload", workload, "--seed", str(seed), "--seconds",
         str(seconds), "--trace", "0"],
        cwd=tree, env=env, capture_output=True, text=True)
    wall = time.monotonic() - t0
    with open(log_path, "w") as f:
        f.write(proc.stdout + "\n--- stderr ---\n" + proc.stderr[-6000:])
    out = {"side": side, "seed": seed, "rc": proc.returncode, "wall_s": wall}
    lines = proc.stdout.strip().splitlines()
    try:
        result = json.loads(lines[-1])
    except (IndexError, ValueError):
        out["error"] = (proc.stdout[-800:] + proc.stderr[-1200:])
        return out
    out.update(correct=result["correct"], failed=result["failed"],
               attempted=result["attempted"],
               memory_peak_bytes=result["device"]["memory_peak_bytes"],
               metrics={k: v["value"] for k, v in result["metrics"].items()},
               phases={k: float(v) for k, v in _PHASE.findall(proc.stdout)},
               scalars=result.get("scalars", {}))
    return out


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--runs", type=int, default=6)
    ap.add_argument("--from-tar", default=None)
    args = ap.parse_args()
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        seconds = json.load(f)["run_seconds"]
    seeds = [2147483000 + 1009 * i for i in range(args.runs)]
    base = os.path.join(ROOT, ".spread")
    out_dir = os.path.join(ROOT, "chiprun_out", "spread", args.workload)
    os.makedirs(out_dir, exist_ok=True)
    trees = make_trees(base, args.from_tar)
    runs = []

    def go(side, seed, secs, tag, position):
        r = one_run(trees[side], side, base, args.workload, seed, secs,
                    os.path.join(out_dir, f"{tag}.log"))
        r.update(tag=tag, position=position)
        runs.append(r)
        print("RUN " + json.dumps(r), flush=True)
        return r

    for side in "AB":       # each side's first run compiles: recorded apart
        go(side, seeds[0] + 7, 3.0, f"first_{side}", None)
    for i, seed in enumerate(seeds):
        first = "A" if i < (len(seeds) + 1) // 2 else "B"
        for position, side in enumerate((first, "BA"["AB".index(first)])):
            go(side, seed, seconds, f"{side}{i}", position)

    timed = [r for r in runs if r["position"] is not None and "metrics" in r]
    summary = {"workload": args.workload, "seconds": seconds,
               "seeds": seeds, "metrics": {},
               "all_correct": all(r.get("correct") for r in runs),
               "errors": sum("error" in r for r in runs)}
    names = sorted({k for r in timed for k in r["metrics"]})
    for name in names:
        a = [r["metrics"][name] for r in timed if r["side"] == "A"]
        b = [r["metrics"][name] for r in timed if r["side"] == "B"]
        if len(a) >= 2 and len(b) >= 2:
            summary["metrics"][name] = summarize(a, b)
    # the set-up by place in the pair: what the side run second pays
    by_place = {}
    for key in ["setup_s"] + sorted({k for r in timed for k in r["phases"]}):
        get = (lambda r: r["metrics"].get("setup_s")) if key == "setup_s" \
            else (lambda r: r["phases"].get(key))
        first = [get(r) for r in timed if r["position"] == 0 and get(r)]
        second = [get(r) for r in timed if r["position"] == 1 and get(r)]
        if first and second:
            by_place[key] = {
                "first": statistics.median(first),
                "second": statistics.median(second),
                "second_over_first": statistics.median(second)
                / statistics.median(first)}
    summary["setup_by_place"] = by_place
    print("SUMMARY " + json.dumps(summary, indent=1), flush=True)
    with open(os.path.join(out_dir, "summary.json"), "w") as f:
        json.dump({"summary": summary, "runs": runs}, f, indent=1)
    shutil.rmtree(base, ignore_errors=True)
    return 0 if summary["all_correct"] and not summary["errors"] else 1


if __name__ == "__main__":
    sys.exit(main())
