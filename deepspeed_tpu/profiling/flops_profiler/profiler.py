"""FLOPs / params / latency profiler.

Counterpart of the reference's ``profiling/flops_profiler/profiler.py``
(``FlopsProfiler``:17).  The reference monkey-patches ``torch.nn.functional``
to count MACs as modules execute; under XLA the compiler already knows the
exact op costs, so the TPU profiler asks the compiled executable
(``jax.jit(fn).lower(...).compile().cost_analysis()``) — flops come from the
HLO cost model, exact for the program actually run (post-fusion), rather
than re-derived per-module heuristics.

Same public surface: ``start_profile`` / ``stop_profile`` /
``get_total_flops`` / ``get_total_params`` / ``get_total_duration`` /
``print_model_profile``, plus the engine-driven ``profile_step`` gate.
"""

from __future__ import annotations

import time
from typing import Any, Callable, Dict, Optional, Tuple

import numpy as np

import jax

from ...utils.logging import logger

PyTree = Any


def _num(x) -> float:
    try:
        return float(x)
    except (TypeError, ValueError):
        return 0.0


def _cost_analysis(compiled) -> Dict[str, float]:
    return {k: _num(v) for k, v in compiled.cost_analysis().items()}


def count_params(params: PyTree) -> int:
    return sum(int(np.prod(l.shape))
               for l in jax.tree_util.tree_leaves(params))


def _human(n: float, unit: str = "") -> str:
    for mag, suffix in ((1e12, "T"), (1e9, "G"), (1e6, "M"), (1e3, "K")):
        if abs(n) >= mag:
            return f"{n / mag:.2f} {suffix}{unit}"
    return f"{n:.2f} {unit}"


class FlopsProfiler:
    """Profile a jittable step function (or a DeepSpeedEngine's train step)."""

    def __init__(self, model=None, ds_engine=None):
        self.model = model
        self.ds_engine = ds_engine
        self.started = False
        self._flops = 0.0
        self._bytes = 0.0
        self._params = 0
        self._duration = 0.0

    # ---------------------------------------------------- direct-fn profile

    def profile_fn(self, fn: Callable, *args, static_argnums=(),
                   warmup: int = 1, iters: int = 3) -> Dict[str, float]:
        """Compile ``fn``, read its HLO cost analysis, and time it."""
        # profiling compiles on purpose: the jit exists to be lowered
        # dslint: disable=jit-in-hot-path — timed once, then discarded
        jitted = jax.jit(fn, static_argnums=static_argnums)
        compiled = jitted.lower(*args).compile()
        costs = _cost_analysis(compiled)
        self._flops = costs.get("flops", 0.0)
        self._bytes = costs.get("bytes accessed", 0.0)
        for _ in range(max(warmup, 1)):  # at least one call: compile outside timing
            out = jitted(*args)
        jax.block_until_ready(out)
        t0 = time.perf_counter()
        for _ in range(iters):
            out = jitted(*args)
        jax.block_until_ready(out)
        self._duration = (time.perf_counter() - t0) / iters
        self._params = sum(count_params(a) for a in args
                           if isinstance(a, dict))
        self.started = True
        return {"flops": self._flops, "bytes": self._bytes,
                "duration": self._duration, "params": self._params}

    # ------------------------------------------------- engine-style surface

    def start_profile(self, ignore_list=None) -> None:
        self.started = True
        self._t0 = time.perf_counter()

    def stop_profile(self) -> None:
        if hasattr(self, "_t0"):
            self._duration = time.perf_counter() - self._t0

    def get_total_flops(self, as_string: bool = False):
        return _human(self._flops, "FLOPs") if as_string else self._flops

    def get_total_params(self, as_string: bool = False):
        return _human(self._params, "") if as_string else self._params

    def get_total_duration(self, as_string: bool = False):
        return (f"{self._duration * 1e3:.2f} ms" if as_string
                else self._duration)

    def get_flops_per_second(self) -> float:
        return self._flops / self._duration if self._duration else 0.0

    def print_model_profile(self, profile_step: int = 1,
                            module_depth: int = -1, top_modules: int = 1,
                            detailed: bool = True,
                            output_file: Optional[str] = None) -> None:
        lines = [
            "--------- DeepSpeed-TPU Flops Profiler ---------",
            f"profile step:                  {profile_step}",
            f"params:                        {self.get_total_params(True)}",
            f"flops (per step, post-fusion): {self.get_total_flops(True)}",
            f"bytes accessed:                {_human(self._bytes, 'B')}",
            f"step latency:                  {self.get_total_duration(True)}",
            f"achieved throughput:           "
            f"{_human(self.get_flops_per_second(), 'FLOPS')}",
        ]
        text = "\n".join(lines)
        if output_file:
            with open(output_file, "w") as f:
                f.write(text + "\n")
        else:
            logger.info("\n" + text)

    def end_profile(self) -> None:
        self.started = False


def get_model_profile(model_fn: Callable, args: Tuple = (),
                      kwargs: Optional[Dict] = None, print_profile: bool = True,
                      detailed: bool = True, warm_up: int = 1,
                      as_string: bool = True, output_file: Optional[str] = None,
                      ignore_modules=None):
    """Reference ``get_model_profile`` surface: returns (flops, macs, params).

    MACs are reported as flops/2 — under XLA the executable reports fused
    flops directly; the MAC notion only exists for API parity.
    """
    kwargs = kwargs or {}
    prof = FlopsProfiler()
    fn = (lambda *a: model_fn(*a, **kwargs)) if kwargs else model_fn
    stats = prof.profile_fn(fn, *args, warmup=warm_up)
    if print_profile:
        prof.print_model_profile(output_file=output_file)
    flops, params = stats["flops"], stats["params"]
    macs = flops / 2.0
    if as_string:
        return (_human(flops, "FLOPs"), _human(macs, "MACs"),
                _human(params, ""))
    return flops, macs, params
