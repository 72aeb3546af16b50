#!/usr/bin/env python3
"""Negative controls of the logits check for the hybrid family's single-part
block (``nemotron_h``), at a serving cell's own sizes: the server runs with a
fault planted, the reference as it is on the weights as drawn, and the two
readings of ``compare.py`` say whether ``correct`` would turn false.

    python3 benchmarks/chip/reference/nemotron_h_control.py \
        --workload <cell> --seed <n> [<n> ...] --fault <name> [<name> ...] \
        [--ticks <n>]

It is ``hybrid_ssm_moe_control.py`` (its process, its ``readings``, its
faults in ``ops/pallas/ssm.py``: ``bf16_state``, ``chunk_edge``,
``pad_advance``, ``no_tail``) with this block's parameter layout for the two
faults planted in the weights: a run's stacks are one dict per position of
its unit, the routed experts' matrices are ``w_up`` / ``w_down``, and the
untied head is a matrix like any other.  The last line of each fault is
``CONTROL {...}``.
"""

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))
sys.path.insert(0, ROOT)

from benchmarks.chip.reference import hybrid_ssm_moe_control as base  # noqa: E402


def _each_part(fault, only=None):
    """``base``'s fault of the weights, which takes a run for one dict of
    stacks, on every position's stacks of every run (``only``: on those
    that hold that key)."""
    def one(part):
        if only is not None and only not in part:
            return part
        return fault({"runs": [part]})["runs"][0]

    return lambda params: {**params, "runs": [
        one(run) if isinstance(run, dict) else [one(part) for part in run]
        for run in params["runs"]]}


#: the routed product left out (the expert layers alone hold ``w_down``);
#: every matrix of every layer on 255 levels a channel
WEIGHTS = {"zero": _each_part(base._zero_routed, only="w_up"),
           "int8": _each_part(base._int8)}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, nargs="+", required=True)
    ap.add_argument("--fault", nargs="+", choices=base.FAULTS, required=True)
    ap.add_argument("--ticks", type=int, default=None,
                    help="decode ticks read (default: the check's own)")
    args = ap.parse_args(argv)
    from benchmarks.chip import harness
    from benchmarks.chip.reference import compare
    from deepspeed_tpu.utils.platform import enable_compile_cache, require_tpu
    require_tpu()
    enable_compile_cache()
    cell = harness.load_cell(ROOT, args.workload)
    base.WEIGHTS = WEIGHTS      # this process plants in this layout only
    for seed in args.seed:
        for fault in args.fault:
            worst, rms = base.readings(cell, seed, fault, args.ticks)
            print("CONTROL " + json.dumps({
                "workload": args.workload, "seed": seed, "fault": fault,
                "ticks": args.ticks or int(cell.traffic["check"]["ticks"]),
                "relative_error": worst, "tolerance": compare.TOLERANCE,
                "rms_error": rms, "rms_tolerance": compare.RMS_TOLERANCE,
                "logits_agree": bool(worst <= compare.TOLERANCE
                                     and rms <= compare.RMS_TOLERANCE)}),
                flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
