#!/usr/bin/env python3
"""Telemetry report + regression gate: join a run's observability streams,
and pin tracing cost/shape in BENCH_TELEMETRY.json.

Two modes:

**Report** (default): given a run dir, join ``events.jsonl`` +
``metrics*.jsonl`` (+ a Perfetto trace via ``--trace``) into one per-run
summary.  ``--expect-rank-metrics N`` additionally requires a parseable
``metrics.rank<i>.jsonl`` for every rank — ``scripts/goodput_bench.py``
runs this per fleet scenario, so a rank that silently stops producing
telemetry under restarts fails the goodput gate.

**Bench** (``--bench``): run the tiny CPU train fixture telemetry-off vs
telemetry-on and a 3-slot serving session, then write
``BENCH_TELEMETRY.json`` pinning: the span inventory (drift vs the
committed baseline fails), span coverage of measured step wall time
(``--coverage-threshold``, default 0.95), tracing overhead
(``--overhead-threshold``, default 0.05 — the acceptance bound), trace
schema validity, metrics-stream field presence, and zero recompiles.

Usage:
    python scripts/run_report.py RUN_DIR [--expect-rank-metrics N]
                                 [--trace FILE] [--json]
    python scripts/run_report.py --fleet-dir DIR [--json]
    python scripts/run_report.py --device-trace LOGDIR
    python scripts/run_report.py --bench [--out BENCH_TELEMETRY.json]
                                 [--baseline FILE] [--steps 5] [--warmup 2]
                                 [--repeats 3]

Exit codes: 0 ok; 1 schema/overhead/coverage/inventory regression (bench)
or missing/unparseable telemetry (report); 2 usage / no run dir.
"""

from __future__ import annotations

import argparse
import glob
import json
import math
import os
import sys
import tempfile
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


# ----------------------------------------------------------------- report
#: the ``serve.*`` gauges of a metrics row that the serving line prints
_SERVING_FIELDS = ("queue_depth", "occupancy", "live_block_share",
                   "kv_streamed_over_live", "kv_pool_share",
                   "kv_window_streamed_share", "tokens_per_s")
#: and, beside them, the decode loop's pipelining shares and the launches
#: an admission took, which the gateway journals on its ``serve.tick``
#: events (the newest one is read)
_SERVING_TICK_FIELDS = ("overlap_share", "late_row_share",
                        "launches_per_admission")


def device_span_table(trace_events) -> dict:
    """``program -> {n, p50_ms, p95_ms, us_per_padded_token, in_place,
    share}`` of a trace's ``serve.device`` events (``docs/telemetry.md``
    "Device spans": each admission's and each tick's launch as the device
    ran it): launches, the median and the 95th percentile of their device
    time, of admissions the device time a computed prompt token
    (``padded``) and the share of them that worked on the slot's own row of
    the pool (``in_place``; None where no span says), and the program's
    share of the run (the first such span's start to the last one's end).
    Empty where the trace holds none (a tracer that was off, an older
    program)."""
    spans = [e for e in trace_events if isinstance(e, dict)
             and e.get("ph") == "X" and e.get("name") == "serve.device"]
    if not spans:
        return {}
    run_us = max(e["ts"] + e["dur"] for e in spans) - min(
        e["ts"] for e in spans)
    by_program = {}
    for e in spans:
        by_program.setdefault((e.get("args") or {}).get("program", "?"),
                              []).append(e)
    # nearest rank, as ``telemetry.metrics.Histogram.percentile``
    rank = lambda xs, q: xs[max(0, math.ceil(q * len(xs)) - 1)]
    table = {}
    for program, evs in sorted(by_program.items()):
        durs = sorted(e["dur"] for e in evs)
        padded = sum((e.get("args") or {}).get("padded", 0) for e in evs)
        said = [(e.get("args") or {})["in_place"] for e in evs
                if "in_place" in (e.get("args") or {})]
        table[program] = {
            "n": len(evs),
            "p50_ms": rank(durs, 0.5) / 1e3,
            "p95_ms": rank(durs, 0.95) / 1e3,
            "us_per_padded_token": round(sum(durs) / padded, 3)
            if padded else None,
            "in_place": round(sum(said) / len(said), 4) if said else None,
            "share": round(sum(durs) / max(run_us, 1), 4)}
    return table


def report(args) -> int:
    from deepspeed_tpu.runtime.supervision.events import (ABORT_KINDS,
                                                          EventKind,
                                                          read_events)
    from deepspeed_tpu.telemetry.export import validate_trace
    from deepspeed_tpu.telemetry.metrics import read_metrics

    run_dir = args.run_dir
    if not os.path.isdir(run_dir):
        print(f"error: no run dir at {run_dir}", file=sys.stderr)
        return 2
    problems = []
    out = {"run_dir": run_dir}

    # events ------------------------------------------------------------
    events = read_events(os.path.join(run_dir, "events.jsonl"))
    by_kind = {}
    for e in events:
        by_kind[e.get("kind", "?")] = by_kind.get(e.get("kind", "?"), 0) + 1
    out["events"] = {"total": len(events), "by_kind": by_kind,
                     "aborts": sum(1 for e in events
                                   if e.get("kind") in ABORT_KINDS)}

    # metrics -----------------------------------------------------------
    paths = sorted(set(glob.glob(os.path.join(run_dir, "metrics*.jsonl"))))
    if args.expect_rank_metrics is not None:
        for r in range(args.expect_rank_metrics):
            p = os.path.join(run_dir, f"metrics.rank{r}.jsonl")
            if p not in paths:
                problems.append(f"rank {r}: no metrics file at {p}")
    last_tick = next((e for e in reversed(events)
                      if e.get("kind") == EventKind.SERVE_TICK), {})
    tick_shares = {field: last_tick[field] for field in _SERVING_TICK_FIELDS
                   if field in last_tick}
    ranks = {}
    for p in paths:
        rows = read_metrics(p)
        if not rows:
            problems.append(f"{os.path.basename(p)}: no parseable "
                            "metrics.sample rows")
            continue
        # prefer the newest per-step sample (a restarted engine appends a
        # fresh start row with no step to the same file)
        stepped = [r for r in rows if "step" in r]
        last = stepped[-1] if stepped else rows[-1]
        m = last.get("m", {})
        st = m.get("train.step_time_s") or {}
        ranks[os.path.basename(p)] = {
            "samples": len(rows),
            "last_step": last.get("step"),
            "step_time_p50_s": st.get("p50") if isinstance(st, dict)
            else None,
            "step_time_p99_s": st.get("p99") if isinstance(st, dict)
            else None,
            "mfu": m.get("train.mfu"),
            "tokens_per_s": m.get("train.tokens_per_s"),
            # how far the flash kernels' causal strips engage in the
            # traced step (1.0: not at all; None: no flash kernel in it)
            "flash_causal_tiles_visited": m.get("flash.causal_tiles_visited"),
            "flash_causal_tiles_square": m.get("flash.causal_tiles_square"),
            "flash_causal_tile_ratio": round(
                m["flash.causal_tiles_visited"]
                / m["flash.causal_tiles_square"], 4)
            if m.get("flash.causal_tiles_square") else None,
            # flash kernel call sites of the traced step, and the share of
            # them that read the packed qkv product in token-major rows
            # (1.0: nothing is re-laid around any call)
            "flash_calls": m.get("flash.calls"),
            "flash_calls_token_major_packed":
                m.get("flash.calls_token_major_packed"),
            "flash_packed_call_ratio": round(
                m.get("flash.calls_token_major_packed", 0)
                / m["flash.calls"], 4) if m.get("flash.calls") else None,
            # the loss head of the traced step: vocabulary-sized products
            # it holds and the chunks of the sequence it walks (1: plain)
            "head_logit_products": m.get("head.logit_products"),
            "head_row_chunks": m.get("head.row_chunks"),
            "host_rss_bytes": m.get("mem.host_rss_bytes"),
            "rollbacks": m.get("elastic.rollbacks"),
        }
        # a gateway's gauges, where one streamed through this sampler
        serving = {field: m[f"serve.{field}"] for field in _SERVING_FIELDS
                   if f"serve.{field}" in m}
        if serving:
            serving.update(tick_shares)
            ranks[os.path.basename(p)]["serving"] = serving
    out["metrics"] = ranks

    # fleet telemetry ----------------------------------------------------
    if args.fleet_dir:
        from deepspeed_tpu.telemetry.critical_path import (
            missing_worker_telemetry, span_chain_coverage)
        out["fleet"] = {
            "chain": span_chain_coverage(events),
            "missing": missing_worker_telemetry(run_dir, events=events),
        }
        problems.extend(f"fleet: {p}" for p in out["fleet"]["missing"])

    # trace -------------------------------------------------------------
    if args.trace:
        try:
            with open(args.trace) as f:
                obj = json.load(f)
        except (OSError, ValueError) as e:
            problems.append(f"trace {args.trace} unreadable: {e}")
        else:
            schema = validate_trace(obj)
            spans = [e for e in obj.get("traceEvents", [])
                     if isinstance(e, dict) and e.get("ph") == "X"]
            names = {}
            for e in spans:
                names[e.get("name")] = names.get(e.get("name"), 0) + 1
            out["trace"] = {"spans": len(spans), "by_name": names,
                            "schema_problems": schema,
                            "device_by_program": device_span_table(spans)}
            problems.extend(f"trace: {p}" for p in schema)

    out["problems"] = problems
    if args.as_json:
        print(json.dumps(out, indent=1, sort_keys=True))
    else:
        ev = out["events"]
        print(f"run {run_dir}: {ev['total']} events "
              f"({ev['aborts']} abort-class), "
              f"{len(ranks)} metrics file(s)")
        for name, r in sorted(ranks.items()):
            p50 = r["step_time_p50_s"]
            print(f"  {name}: {r['samples']} samples, last step "
                  f"{r['last_step']}, step p50 "
                  f"{p50 if p50 is None else round(p50, 4)}s, "
                  f"mfu {r['mfu']}" + (
                      ", flash causal tiles visited / square "
                      f"{r['flash_causal_tiles_visited']} / "
                      f"{r['flash_causal_tiles_square']} = "
                      f"{r['flash_causal_tile_ratio']}"
                      if r["flash_causal_tile_ratio"] else "") + (
                      ", flash calls packed token-major / all "
                      f"{r['flash_calls_token_major_packed']} / "
                      f"{r['flash_calls']} = "
                      f"{r['flash_packed_call_ratio']}"
                      if r["flash_calls"] else "") + (
                      f", head products {r['head_logit_products']} in "
                      f"{r['head_row_chunks']} chunk(s)"
                      if r["head_row_chunks"] else ""))
            if "serving" in r:
                print("    serving: " + ", ".join(
                    f"{field} {round(value, 4)}"
                    for field, value in r["serving"].items()))
        if "fleet" in out:
            ch = out["fleet"]["chain"]
            print(f"  fleet: span-chain coverage {ch['coverage']} "
                  f"({ch['complete']}/{ch['accepted']})")
        if "trace" in out:
            print(f"  trace: {out['trace']['spans']} spans over "
                  f"{len(out['trace']['by_name'])} names")
            device = out["trace"]["device_by_program"]
            if device:
                # the serving device spans: what each admission's and each
                # tick's launch took ON THE DEVICE (serve.device)
                print("    serving, device time by program (serve.device): "
                      "launches, p50 ms, p95 ms, us a padded token, on the "
                      "slot's own row, share of the run")
                for program, r in device.items():
                    in_place = "-" if r["in_place"] is None \
                        else f"{100 * r['in_place']:.0f}%"
                    print(f"      {program}: {r['n']}, {r['p50_ms']:.3f}, "
                          f"{r['p95_ms']:.3f}, "
                          f"{r['us_per_padded_token'] or '-'}, {in_place}, "
                          f"{100 * r['share']:.2f}%")
        for p in problems:
            print(f"  PROBLEM: {p}", file=sys.stderr)
    return 1 if problems else 0


# ------------------------------------------------------------------ bench
def _train_fixture(telemetry: bool, steps: int, warmup: int,
                   metrics_path=None):
    """Tiny CPU train loop (the compile_report fixture); returns
    (engine, per-step wall seconds after warmup)."""
    import numpy as np
    import jax
    import jax.numpy as jnp
    import deepspeed_tpu
    from deepspeed_tpu.models import gpt
    from deepspeed_tpu.runtime.model import from_gpt

    cfg = gpt.GPTConfig(vocab_size=256, max_seq_len=64, n_layer=2, n_head=4,
                        d_model=64, dtype=jnp.float32, vocab_round_to=128)
    ds = {"train_micro_batch_size_per_gpu": 2,
          "gradient_accumulation_steps": 1,
          "steps_per_print": 100000,
          "optimizer": {"type": "Adam", "params": {"lr": 1e-3}},
          "zero_optimization": {"stage": 0}}
    if telemetry:
        ds["telemetry"] = {"enabled": True,
                           "metrics": {"path": metrics_path,
                                       "interval_steps": 1}}
    engine, _, _, _ = deepspeed_tpu.initialize(
        model=from_gpt(cfg), config=ds, rng=jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)

    def batch():
        return {"tokens": rng.integers(0, 256, size=(2, 17)).astype(np.int32)}

    for _ in range(warmup):
        engine.train_batch_fused(batch())
    times = []
    for _ in range(steps):
        b = batch()
        t0 = time.perf_counter()
        loss = engine.train_batch_fused(b)
        float(loss)  # fence: the step's outputs are real
        times.append(time.perf_counter() - t0)
    return engine, times


def _median(xs):
    s = sorted(xs)
    return s[len(s) // 2]


def _bench_serving(tmp_dir: str) -> dict:
    import numpy as np
    import jax
    import jax.numpy as jnp
    import deepspeed_tpu
    from deepspeed_tpu.models import gpt
    from deepspeed_tpu.telemetry import Tracer
    from deepspeed_tpu.utils.compile_watch import CompileWatch

    cfg = gpt.GPTConfig(vocab_size=256, max_seq_len=128, n_layer=2, n_head=4,
                        d_model=64, dtype=jnp.float32, vocab_round_to=128)
    params = gpt.init(cfg, jax.random.PRNGKey(0))
    engine = deepspeed_tpu.init_inference(model=(cfg, params),
                                          config={"dtype": "float32"})
    tracer = Tracer(name="serving")
    gw = engine.serve(config={"slots": 3, "max_len": 64,
                              "prefill_chunk": 8}, tracer=tracer)
    watch = CompileWatch(gw._batcher.registry, first_compile_free=True).open()
    rng = np.random.default_rng(1)
    handles = [gw.submit(
        rng.integers(1, 256, (int(rng.integers(3, 20)),)).astype(np.int32),
        max_new_tokens=int(rng.integers(2, 8)), seed=i) for i in range(6)]
    for h in handles:
        h.result(timeout=300.0)
    snap = gw.snapshot()
    gw.shutdown()
    return {
        "requests": len(handles),
        "span_inventory": tracer.span_inventory(),
        "recompiles": snap["recompiles"],
        "ttft_samples": len(snap["ttft_s"]),
        "tracer": tracer,
    }


def bench(args) -> int:
    from deepspeed_tpu.telemetry.export import validate_trace, write_trace
    from deepspeed_tpu.telemetry.metrics import read_metrics
    from deepspeed_tpu.telemetry.spans import SpanName

    problems = []
    tmp_dir = tempfile.mkdtemp(prefix="run_report_bench_")

    # overhead: alternate off/on, take the best (min) ratio over repeats —
    # robust to shared-CI noise spikes while still honest (telemetry can't
    # be systematically faster)
    ratios, on_times = [], None
    for r in range(args.repeats):
        _, t_off = _train_fixture(False, args.steps, args.warmup)
        mpath = os.path.join(tmp_dir, f"metrics_{r}.jsonl")
        eng, t_on = _train_fixture(True, args.steps, args.warmup,
                                   metrics_path=mpath)
        ratios.append(_median(t_on) / max(_median(t_off), 1e-9))
        on_times, on_engine, on_metrics = t_on, eng, mpath
    overhead = min(ratios) - 1.0
    if overhead > args.overhead_threshold:
        problems.append(
            f"tracing overhead {overhead:.3f} exceeds the "
            f"{args.overhead_threshold} bound (ratios: "
            f"{[round(x, 3) for x in ratios]})")

    # coverage: train.step spans vs measured step wall time of the last
    # telemetry run (both sides measure the same loop)
    agg = on_engine.tracer.aggregates()
    step_total = agg.get(SpanName.TRAIN_STEP, {}).get("total_s", 0.0)
    # the tracer also timed the warmup steps; charge only the measured ones
    recs = [r for r in on_engine.tracer.spans()
            if r.name == SpanName.TRAIN_STEP][-args.steps:]
    covered = sum(r.dur for r in recs)
    measured = sum(on_times)
    coverage = covered / measured if measured else 0.0
    if coverage < args.coverage_threshold:
        problems.append(
            f"span coverage {coverage:.3f} of measured step wall time is "
            f"below the {args.coverage_threshold} bound")

    # metrics stream: the acceptance fields must be present in the samples
    rows = read_metrics(on_metrics)
    stepped = [r for r in rows if "step" in r]
    if not stepped:
        problems.append("metrics.jsonl carries no per-step samples")
    else:
        m = stepped[-1]["m"]
        for field in ("train.mfu", "train.step_time_s",
                      "mem.host_rss_bytes", "mem.hbm_live_bytes",
                      "train.tokens_per_s"):
            if field not in m:
                problems.append(f"metrics.sample missing '{field}'")

    # trace export + schema
    trace_path = os.path.join(tmp_dir, "trace.json")
    serving = _bench_serving(tmp_dir)
    obj = write_trace(trace_path, [on_engine.tracer, serving.pop("tracer")])
    schema = validate_trace(obj)
    problems.extend(f"trace schema: {p}" for p in schema)
    if serving["recompiles"]:
        problems.append(
            f"serving fixture saw {serving['recompiles']} post-warmup "
            "recompile(s) with tracing enabled")

    inventory = sorted(set(on_engine.tracer.span_inventory())
                       | set(serving["span_inventory"]))
    result = {
        "config": {"steps": args.steps, "warmup": args.warmup,
                   "repeats": args.repeats,
                   "overhead_threshold": args.overhead_threshold,
                   "coverage_threshold": args.coverage_threshold},
        "overhead": round(overhead, 4),
        "overhead_ratios": [round(x, 4) for x in ratios],
        "coverage": round(coverage, 4),
        "span_inventory": inventory,
        "train": {
            "steps": args.steps,
            "step_s_median": round(_median(on_times), 5),
            "spans": {k: v["count"] for k, v in agg.items()},
            "metrics_samples": len(rows),
        },
        "serving": serving,
        "trace": {"events": len(obj["traceEvents"]),
                  "schema_problems": schema},
    }

    # inventory pin: a span appearing or vanishing is a telemetry-surface
    # change the PR must own by regenerating the artifact
    baseline_path = args.baseline or args.out
    if os.path.exists(baseline_path):
        try:
            with open(baseline_path) as f:
                base = json.load(f)
        except ValueError:
            base = None
        if base and base.get("span_inventory") and \
                base["span_inventory"] != inventory:
            gone = sorted(set(base["span_inventory"]) - set(inventory))
            new = sorted(set(inventory) - set(base["span_inventory"]))
            problems.append(
                f"span inventory drifted from the committed baseline "
                f"(missing: {gone}, new: {new}) — regenerate "
                f"{args.out} deliberately if this is intended")

    tmp = args.out + ".tmp"
    with open(tmp, "w") as f:
        json.dump(result, f, indent=1, sort_keys=True)
        f.write("\n")
    os.replace(tmp, args.out)
    print(f"wrote {args.out}: overhead {result['overhead']}, coverage "
          f"{result['coverage']}, {len(inventory)} span names, "
          f"{result['train']['metrics_samples']} metrics samples")
    for p in problems:
        print(f"REGRESSION: {p}", file=sys.stderr)
    return 1 if problems else 0


def device_trace(logdir: str) -> int:
    """Device seconds by program and scope of a capture window
    (``telemetry.profiler_trace``): its trace joined to the op maps the
    window wrote beside it (docs/telemetry.md)."""
    from deepspeed_tpu.telemetry import op_maps as dt
    try:
        maps, ops = dt.read_programs(logdir), dt.read_trace_ops(logdir)
    except (OSError, ValueError, KeyError) as e:
        print(f"error: {logdir}: {e}", file=sys.stderr)
        return 1
    print(f"{len(ops)} device ops, {len(maps)} programs "
          f"({sum(len(m['ops']) for m in maps)} rows)")
    print("\n".join(dt.format_report(ops, maps)))
    return 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("run_dir", nargs="?", default=None,
                    help="run dir holding events.jsonl + metrics*.jsonl")
    ap.add_argument("--expect-rank-metrics", type=int, default=None,
                    metavar="N",
                    help="require a parseable metrics.rank<i>.jsonl for "
                         "every rank i < N")
    ap.add_argument("--trace", default=None,
                    help="Perfetto trace JSON to validate + summarize")
    ap.add_argument("--fleet-dir", default=None, metavar="DIR",
                    help="treat DIR as a fleet run dir: report span-chain "
                         "coverage and fail on missing worker telemetry "
                         "(trace.*.json exports, per-rank metrics); "
                         "scripts/fleet_report.py does the full merge")
    ap.add_argument("--device-trace", default=None, metavar="LOGDIR",
                    help="a telemetry.profiler_trace logdir (.xplane.pb + "
                         "programs.json): print device seconds by program "
                         "and by named scope, with the unjoined share")
    ap.add_argument("--json", action="store_true", dest="as_json")
    ap.add_argument("--bench", action="store_true",
                    help="run the CPU fixtures and gate BENCH_TELEMETRY.json")
    ap.add_argument("--out", default="BENCH_TELEMETRY.json")
    ap.add_argument("--baseline", default=None,
                    help="baseline artifact (default: the existing --out)")
    ap.add_argument("--steps", type=int, default=5)
    ap.add_argument("--warmup", type=int, default=2)
    ap.add_argument("--repeats", type=int, default=3)
    ap.add_argument("--overhead-threshold", type=float, default=0.05)
    ap.add_argument("--coverage-threshold", type=float, default=0.95)
    args = ap.parse_args(argv)

    if args.bench:
        return bench(args)
    if args.device_trace:
        return device_trace(args.device_trace)
    if args.run_dir is None and args.fleet_dir is not None:
        args.run_dir = args.fleet_dir
    if args.run_dir is None:
        print("error: RUN_DIR, --fleet-dir, --device-trace or --bench "
              "required",
              file=sys.stderr)
        return 2
    return report(args)


if __name__ == "__main__":
    sys.exit(main())
