"""The tests' entry: a cell end to end at a tiny size on the CPU.

It runs the same harness, kinds and readers as ``run.py`` with the sizes cut
by ``overrides`` (``config_file``: keys of the configuration file; ``traffic``
and ``serving``: numbers of the traffic file) and, for a negative control of
the logits check, the precision lowered by ``control``, so
that a renamed entry point or a dropped counter fails here before it costs
chip time.  It can never produce a device metric: it refuses to run on
anything but the CPU, prints no result line, and returns the names of the
metrics it could compute with every value removed.
"""

from __future__ import annotations

import time

from . import harness

#: stand-ins so that shares of a peak can be computed; never reported
_NO_PEAKS = {"bf16_flops": 1.0, "hbm_bytes_per_s": 1.0}


def rehearse(root: str, workload: str, overrides: dict, seconds: float = 1.0,
             seed: int = 0, trace: bool = False,
             control: dict | None = None) -> dict:
    import jax
    if jax.default_backend() != "cpu":
        raise RuntimeError("a rehearsal runs on the CPU only; measure with "
                           "run.py on the chip")
    cell = harness.load_cell(root, workload)
    cell.chips = min(cell.chips, len(jax.devices()))
    cell.config = {**cell.config, **overrides.get("config_file", {})}
    cell.traffic = {**cell.traffic, **overrides.get("traffic", {})}
    if "serving" in overrides:
        cell.traffic["serving"] = overrides["serving"]
    if "check_prompt_lens" in overrides:
        cell.traffic["check"] = {**cell.traffic["check"],
                                 "prompt_lens": overrides["check_prompt_lens"]}
    ctx = harness.measure(cell, seed, seconds, trace, time.perf_counter(),
                          rehearsal_peaks=_NO_PEAKS, control=control)
    result = harness.result_of(ctx)
    return {"rehearsal": True, "correct": result["correct"],
            "attempted": result["attempted"], "failed": result["failed"],
            "metrics": sorted(result["metrics"]), "checks": dict(ctx.checks),
            "scalars": sorted(result["scalars"]),
            "compared": sorted(result["compared"]), "keys": list(result),
            "platform": result["device"]["platform"]}
