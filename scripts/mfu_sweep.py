#!/usr/bin/env python3
"""On-chip evidence sweep: MFU tuning rows + capability/inference rows.

One parameterized runner for every sweep (the ``--set`` choices below —
scripts are drift too; see ``docs/static-analysis.md``).  This parent never
imports JAX, so it never holds the chip: each row runs the named bench
config in its own subprocess, one at a time, appending one JSON line per
run to the log.  Rows are ordered most-valuable-first; each run gets a
generous timeout, is stopped with SIGTERM, and a run that does not stop
aborts the rest of the sweep (the chip is still held by it).

Usage:  python scripts/mfu_sweep.py [--set NAME] [logfile]

Sets:
  full    GPT-2 350M micro-batch / flash-block rows, BERT headline,
          ZeRO-offload capability, gpt_bench prefill/decode rows
  remat   remat-policy / attention-impl rows
  round5  remat levers first, then offload capability, inference rows,
          stall anatomy, xplane trace
  short   the four highest-value rows
"""

from __future__ import annotations

import argparse
import json
import os
import signal
import subprocess
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_GPT_BENCH = ["-m", "deepspeed_tpu.benchmarks.inference.gpt_bench",
              "--model", "gpt2-125m", "--batch", "8", "--prompt", "512",
              "--new-tokens", "32"]

#: rows are (label, env overrides, bench argv); argv None runs the default
#: driver bench (GPT-2 350M training).  Known-good first, so that a run
#: that hangs later in the list still leaves earlier numbers on the record.
_FULL = [
    ("baseline-b1024", {}, None),
    ("bq512", {"FLASH_BLOCK_Q": "512"}, None),
    ("b512", {"FLASH_BLOCK_Q": "512", "FLASH_BLOCK_K": "512"}, None),
    ("mb40", {"BENCH_MB": "40"}, None),
    ("mb48", {"BENCH_MB": "48"}, None),
    ("mb48-bq512", {"BENCH_MB": "48", "FLASH_BLOCK_Q": "512"}, None),
    # bf16 accumulator halves the grad tree: try the next micro-batch up
    ("mb64-bf16acc", {"BENCH_MB": "64",
                      "BENCH_ACCUM_DTYPE": "bf16"}, None),
    ("bert-large", {}, ["bench.py", "bert"]),
    # a bigger micro-batch is the first lever at seq 128
    ("bert-mb512", {"BENCH_MB": "512"}, ["bench.py", "bert"]),
    ("bert-mb768", {"BENCH_MB": "768"}, ["bench.py", "bert"]),
    # the 2.7B offload ladder is the most memory-aggressive run in the
    # list — keep it AFTER the headline tuning rows so a hang here
    # still leaves the MFU numbers on the record
    ("offload-capability", {}, ["bench.py", "offload"]),
    ("prefill-bf16", {}, _GPT_BENCH + ["--dtype", "bfloat16"]),
    ("prefill-int8", {}, _GPT_BENCH + ["--dtype", "int8"]),
    ("prefill-int8-compute", {}, _GPT_BENCH + ["--dtype", "int8-compute"]),
    ("decode-int8-kv", {}, _GPT_BENCH + ["--dtype", "bfloat16",
                                         "--kv-cache-dtype", "int8"]),
    # round-5 kernel rows: in-kernel alibi bias and banded decode with
    # dead-block DMA skip (long prompt so the O(window) stream shows)
    ("decode-alibi-int8-kv", {}, _GPT_BENCH + [
        "--dtype", "bfloat16", "--kv-cache-dtype", "int8",
        "--variant", "alibi"]),
    ("decode-windowed256", {}, _GPT_BENCH + [
        "--dtype", "bfloat16", "--prompt", "896",   # + 32 new < 1024 ctx
        "--variant", "windowed:256"]),
]

# phase-2 rows: remat_policy=attn_out saves each block's attention output
# (64 MB/layer at mb32) so the remat backward skips re-running the
# attention forward; =dots additionally saves matmul outputs;
# BENCH_DENSE_ATTN=1 swaps the Pallas flash kernel for XLA's dense scores
# path (MXU-friendly; the S^2 buffer is transient under remat)
_REMAT = [
    ("attn-out", {"BENCH_REMAT_POLICY": "attn_out"}, None),
    ("attn-out-mb48", {"BENCH_REMAT_POLICY": "attn_out",
                       "BENCH_MB": "48"}, None),
    ("attn-out-bf16acc-mb64", {"BENCH_REMAT_POLICY": "attn_out",
                               "BENCH_ACCUM_DTYPE": "bf16",
                               "BENCH_MB": "64"}, None),
    ("dots-mb32", {"BENCH_REMAT_POLICY": "dots",
                   "BENCH_MB": "32"}, None),
    ("dense-mb32", {"BENCH_DENSE_ATTN": "1", "BENCH_MB": "32"}, None),
    ("dense-attn-out-mb32", {"BENCH_DENSE_ATTN": "1",
                             "BENCH_REMAT_POLICY": "attn_out",
                             "BENCH_MB": "32"}, None),
]

_ROUND5 = [
    # --- MFU levers (highest value).  bench.py's default GPT config is
    # now remat_policy=attn_out (HLO-proven to drop the backward's flash
    # fwd re-run), so the first row IS the candidate best; the second is
    # the A/B against the old full-recompute policy ---
    ("attn-out", {}, None),
    ("nothing", {"BENCH_REMAT_POLICY": "nothing"}, None),
    ("dense-mb32", {"BENCH_DENSE_ATTN": "1", "BENCH_MB": "32"}, None),
    ("dense-attn-out-mb32", {"BENCH_DENSE_ATTN": "1",
                             "BENCH_REMAT_POLICY": "attn_out",
                             "BENCH_MB": "32"}, None),
    # anatomy early: ~2 min, and its per-component table decides where
    # any remaining tuning effort goes
    ("stall-anatomy", {}, ["scripts/stall_anatomy.py"]),
    ("attn-out-mb48", {"BENCH_REMAT_POLICY": "attn_out",
                       "BENCH_MB": "48"}, None),
    ("dots-mb24", {"BENCH_REMAT_POLICY": "dots",
                   "BENCH_MB": "24"}, None),
    ("attn-out-losschunk256", {"BENCH_REMAT_POLICY": "attn_out",
                               "BENCH_LOSS_CHUNK": "256"}, None),
    # no-remat rows: the extra forward is ~25% of executed flops — wins
    # if no-remat activations fit at a micro-batch that still feeds MXU
    ("gpt-noremat-mb12", {"BENCH_NO_REMAT": "1", "BENCH_MB": "12",
                          "BENCH_GAS": "3"}, None),
    ("bert-noremat-mb128", {"BENCH_NO_REMAT": "1",
                            "BENCH_MB": "128"},
     ["bench.py", "bert"]),
    # --- capability (BASELINE #3) ---
    ("offload-capability", {}, ["bench.py", "offload"]),
    # --- inference rows ---
    ("prefill-bf16", {}, _GPT_BENCH + ["--dtype", "bfloat16"]),
    ("prefill-int8", {}, _GPT_BENCH + ["--dtype", "int8"]),
    ("prefill-int8-compute", {}, _GPT_BENCH + ["--dtype", "int8-compute"]),
    ("decode-int8-kv", {}, _GPT_BENCH + ["--dtype", "bfloat16",
                                         "--kv-cache-dtype", "int8"]),
    ("decode-alibi-int8-kv", {}, _GPT_BENCH + [
        "--dtype", "bfloat16", "--kv-cache-dtype", "int8",
        "--variant", "alibi"]),
    ("decode-windowed256", {}, _GPT_BENCH + [
        "--dtype", "bfloat16", "--prompt", "896",
        "--variant", "windowed:256"]),
    # --- xplane trace of the winning-config step (timing not comparable;
    # runs last so a hang here costs nothing) ---
    ("trace-baseline", {"BENCH_TRACE": "/tmp/mfu_sweep_xplane"}, None),
]

_SHORT = [
    ("attn-out", {}, None),                       # new bench default
    ("nothing", {"BENCH_REMAT_POLICY": "nothing"}, None),  # A/B
    ("stall-anatomy", {}, ["scripts/stall_anatomy.py"]),
    ("dense-mb32", {"BENCH_DENSE_ATTN": "1", "BENCH_MB": "32"}, None),
]

# quantized-collective rows (CPU fixture — comm_bench forces 2 virtual
# CPU devices itself, so these rows run anywhere; --no-gate because the
# sweep records the trajectory, scripts/comm_bench.py owns the gate):
# one row per collapse mode so regressions bisect per mode in the log,
# plus the all-modes row that refreshes the full BENCH_COMM picture
_COMM_BENCH = ["scripts/comm_bench.py", "--no-gate",
               "--out", "/tmp/BENCH_COMM_sweep.json"]
_COMM = [
    ("comm-mean", {}, _COMM_BENCH + ["--modes", "none"]),
    ("comm-int8", {}, _COMM_BENCH + ["--modes", "none,int8"]),
    ("comm-int4", {}, _COMM_BENCH + ["--modes", "none,int4"]),
    ("comm-onebit", {}, _COMM_BENCH + ["--modes", "none,onebit"]),
    ("comm-zero-int8", {}, _COMM_BENCH + ["--modes", "none,zero_int8"]),
    ("comm-all", {}, _COMM_BENCH),
]

# serving rows (CPU fixture — serve_bench drives a tiny random-init GPT,
# so these run anywhere): the fixed-slot single-turn baseline, the paged
# long-tail + multi-turn tiering gate run, a wider-slot variant, the
# speculative-tick A/B gate run, and the informational external-baseline
# reference row.  serve_bench owns the gates; the sweep records the
# trajectory.
_SERVE_BENCH = ["scripts/serve_bench.py", "--print-json",
                "--out", "/tmp/BENCH_SERVE_sweep.json"]
_SERVE = [
    ("serve-fixed-slots", {"JAX_PLATFORMS": "cpu"},
     _SERVE_BENCH + ["--turns", "1"]),
    ("serve-paged-longtail", {"JAX_PLATFORMS": "cpu"}, _SERVE_BENCH),
    ("serve-paged-8slots", {"JAX_PLATFORMS": "cpu"},
     _SERVE_BENCH + ["--slots", "8", "--conversations", "24"]),
    ("serve-spec-ab", {"JAX_PLATFORMS": "cpu"},
     _SERVE_BENCH + ["--turns", "1", "--spec-ab"]),
    ("serve-gemma-baseline", {"JAX_PLATFORMS": "cpu"},
     _SERVE_BENCH + ["--turns", "1", "--config", "gemma_tpu_baseline"]),
    # the disaggregated-fleet goodput run: real prefill/decode
    # subprocesses under seeded faults, scored from the journal
    # (serve_fleet_bench owns the gate; the sweep records the trajectory)
    ("serve-fleet-goodput", {"JAX_PLATFORMS": "cpu"},
     ["scripts/serve_fleet_bench.py", "--print-json",
      "--out", "/tmp/BENCH_SERVE_FLEET_sweep.json"]),
    # overload robustness: capacity knee + 3x open-loop storm through
    # SLO admission / the degradation ladder + prefill autoscale
    # (overload_bench owns the gate vs the committed BENCH_OVERLOAD.json;
    # the sweep records knee_rps and the goodput ratio as trajectory)
    ("serve-overload", {"JAX_PLATFORMS": "cpu"},
     ["scripts/overload_bench.py", "--print-json",
      "--out", "/tmp/BENCH_OVERLOAD_sweep.json",
      "--baseline", "BENCH_OVERLOAD.json"]),
]

# MPMD pipeline rows (CPU fixture — the stage-group fleet spawns its own
# single-device CPU stage processes, so these run anywhere): the three
# pipeline-mode goodput scenarios, one row each so goodput/MTTR regress
# per-scenario in the trajectory log.  goodput_bench owns the committed
# BENCH_GOODPUT.json gate; the sweep writes to a scratch artifact and
# records the trajectory (docs/pipeline-mpmd.md).
_PIPE_BENCH = ["scripts/goodput_bench.py", "--print-json",
               "--out", "/tmp/BENCH_GOODPUT_pipe_sweep.json"]
_PIPE = [
    ("pipe-stage-loss", {"JAX_PLATFORMS": "cpu"},
     _PIPE_BENCH + ["--scenarios", "stage_loss_restart"]),
    ("pipe-dcn-stall", {"JAX_PLATFORMS": "cpu"},
     _PIPE_BENCH + ["--scenarios", "dcn_stall_mid_1f1b"]),
    ("pipe-fault-storm", {"JAX_PLATFORMS": "cpu"},
     _PIPE_BENCH + ["--scenarios", "fault_storm_during_pipeline_drain"]),
]

CONFIG_SETS = {
    "full": _FULL,
    "remat": _REMAT,
    "round5": _ROUND5,
    "short": _SHORT,
    "comm": _COMM,
    "serve": _SERVE,
    "pipe": _PIPE,
}

RUN_TIMEOUT_S = 1200
TERM_GRACE_S = 180


def run_one(label: str, env_over: dict, log, argv=None):
    env = {**os.environ, **env_over}
    t0 = time.time()
    argv = argv or ["bench.py"]   # cwd=REPO resolves the script path
    proc = subprocess.Popen([sys.executable] + argv,
                            env=env, stdout=subprocess.PIPE,
                            stderr=subprocess.DEVNULL, text=True, cwd=REPO)
    try:
        out, _ = proc.communicate(timeout=RUN_TIMEOUT_S)
    except subprocess.TimeoutExpired:
        sys.stderr.write(f"[sweep] {label}: timed out, SIGTERM + grace\n")
        proc.send_signal(signal.SIGTERM)
        try:
            out, _ = proc.communicate(timeout=TERM_GRACE_S)
        except subprocess.TimeoutExpired:
            sys.stderr.write(f"[sweep] {label}: run ignored SIGTERM and "
                            "still holds the chip; stopping the sweep\n")
            log.write(json.dumps({"label": label, "env": env_over,
                                  "wall_s": round(time.time() - t0, 1),
                                  "rc": None, "timeout": True,
                                  "result": None}) + "\n")
            log.flush()
            return False
    line = next((l for l in (out or "").splitlines()
                 if l.startswith("{")), None)
    try:
        result = json.loads(line) if line else None
    except json.JSONDecodeError:  # truncated line from a terminated run
        result = {"parse_error": line[:200]}
    rec = {"label": label, "env": env_over, "wall_s": round(time.time() - t0, 1),
           "rc": proc.returncode, "result": result}
    log.write(json.dumps(rec) + "\n")
    log.flush()
    mfu = (rec["result"] or {}).get("detail", {}).get("mfu")
    sys.stderr.write(f"[sweep] {label}: mfu={mfu} rc={proc.returncode}\n")
    return True


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("logfile", nargs="?", default=None,
                    help="JSONL results log "
                         "(default /tmp/mfu_sweep_<set>.jsonl)")
    ap.add_argument("--set", dest="config_set", default="full",
                    choices=sorted(CONFIG_SETS),
                    help="which sweep row list to run (default: full)")
    args = ap.parse_args(argv)
    configs = CONFIG_SETS[args.config_set]
    path = args.logfile or f"/tmp/mfu_sweep_{args.config_set}.jsonl"
    with open(path, "a") as log:
        for label, env_over, row_argv in configs:
            if not run_one(label, env_over, log, row_argv):
                break
    sys.stderr.write(f"[sweep:{args.config_set}] results in {path}\n")


if __name__ == "__main__":
    main()
