#!/usr/bin/env python3
"""One run of one cell of the chip benchmark.

    python3 benchmarks/chip/run.py --workload <name> --seed <n> \
        --seconds <s> --trace <0|1>

A new process per run: it needs a TPU (there is no CPU path, and a
``device_kind`` without published peaks is an error), keeps JAX's compile
cache at the fixed ``<checkout>/.jax_cache`` (or where
``JAX_COMPILATION_CACHE_DIR`` says), makes its weights and its traffic from
``--seed``, warms only its cell's programs, measures for ``--seconds``,
checks the logits against the plain reference outside the window, and
prints the result as one JSON object on the last line of standard output.
``--trace 0`` reports the cell's end-to-end metrics; ``--trace 1`` profiles
a short slice of the window and reports its per-layer metrics.
"""

import time

T_PROCESS = time.perf_counter()     # set-up is counted from here

import argparse   # noqa: E402
import json       # noqa: E402
import os         # noqa: E402
import sys        # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    sys.path.insert(0, ROOT)
    from benchmarks.chip import harness
    cell = harness.load_cell(ROOT, args.workload)
    result = harness.result_of(harness.measure(
        cell, args.seed, args.seconds, bool(args.trace), T_PROCESS))
    sys.stdout.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
