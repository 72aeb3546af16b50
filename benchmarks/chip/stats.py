"""The arithmetic between samples and the numbers reported."""

from __future__ import annotations

import math
from typing import List, Optional, Sequence


def percentile(values: Sequence[float], q: float) -> float:
    """The ``q``-th percentile (0-100) by linear interpolation between
    order statistics (numpy's default).  Raises on an empty sample."""
    if not values:
        raise ValueError("percentile of an empty sample")
    v = sorted(values)
    pos = (len(v) - 1) * q / 100.0
    lo = math.floor(pos)
    hi = min(lo + 1, len(v) - 1)
    return v[lo] + (v[hi] - v[lo]) * (pos - lo)


def samples_beyond(n: int, q: float) -> int:
    """How many of ``n`` samples lie beyond the ``q``-th percentile."""
    return int(math.floor(n * (100.0 - q) / 100.0 + 1e-9))


def tpot_s(t_first: float, t_last: float, n_out: int) -> Optional[float]:
    """Time per output token of one request: the mean gap between its
    tokens.  None for a request of fewer than two tokens."""
    if n_out < 2:
        return None
    return (t_last - t_first) / (n_out - 1)


def lognormal_quantile(p: float, median_: float, sigma: float) -> float:
    """Quantile ``p`` of the lognormal with that median and sigma."""
    return median_ * math.exp(sigma * _norm_ppf(p))


def _norm_ppf(p: float) -> float:
    """Inverse of the standard normal distribution (Acklam's rational
    approximation, relative error under 1.2e-9)."""
    if not 0.0 < p < 1.0:
        raise ValueError(f"quantile of {p}")
    a = (-3.969683028665376e+01, 2.209460984245205e+02,
         -2.759285104469687e+02, 1.383577518672690e+02,
         -3.066479806614716e+01, 2.506628277459239e+00)
    b = (-5.447609879822406e+01, 1.615858368580409e+02,
         -1.556989798598866e+02, 6.680131188771972e+01,
         -1.328068155288572e+01)
    c = (-7.784894002430293e-03, -3.223964580411365e-01,
         -2.400758277161838e+00, -2.549732539343734e+00,
         4.374664141464968e+00, 2.938163982698783e+00)
    d = (7.784695709041462e-03, 3.224671290700398e-01,
         2.445134137142996e+00, 3.754408661907416e+00)
    lo = 0.02425
    if p < lo:
        q = math.sqrt(-2 * math.log(p))
        return (((((c[0] * q + c[1]) * q + c[2]) * q + c[3]) * q + c[4]) * q
                + c[5]) / ((((d[0] * q + d[1]) * q + d[2]) * q + d[3]) * q + 1)
    if p > 1 - lo:
        return -_norm_ppf(1 - p)
    q = p - 0.5
    r = q * q
    return (((((a[0] * r + a[1]) * r + a[2]) * r + a[3]) * r + a[4]) * r
            + a[5]) * q / (((((b[0] * r + b[1]) * r + b[2]) * r + b[3]) * r
                            + b[4]) * r + 1)


def quantile_grid(n: int, dist: dict) -> List[int]:
    """``n`` whole lengths at the quantiles ``(i + 0.5) / n`` of ``dist``:
    ``{"kind": "lognormal", "median", "sigma", "min", "max"}`` or
    ``{"kind": "uniform", "min", "max"}``.  The same list for every seed."""
    out = []
    for i in range(n):
        p = (i + 0.5) / n
        if dist["kind"] == "lognormal":
            x = lognormal_quantile(p, dist["median"], dist["sigma"])
        elif dist["kind"] == "uniform":
            x = dist["min"] + p * (dist["max"] - dist["min"])
        else:
            raise ValueError(f"no length distribution {dist['kind']!r}")
        out.append(int(min(max(round(x), dist["min"]), dist["max"])))
    return out
