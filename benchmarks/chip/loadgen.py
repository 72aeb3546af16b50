"""One general traffic generator, driven by a traffic file's parameters.

Adapted from the program's ``goodput/traffic.py::TrafficMix`` (seeded, open
loop, lognormal lengths, square-wave bursts), with one change that makes a
run repeat: the work of a window is a *fixed multiset*.  The (prompt,
output) lengths are the quantile grid of the file's two distributions, and
the gaps between arrivals are the quantile grid of the exponential
distribution at the file's rate, so every seed offers the same lengths and
the same gaps.  ``--seed`` decides the token ids and, nothing else, the
order: of a backlog a shuffle of the multiset; of an open loop the place
where the traffic's one fixed, stratified cycle of arrivals begins
(``open_loop_schedule``).  Locally the arrivals look Poisson (shuffled
exponential gaps, bursts by thinning the same grid); over the window, and
over every ``STRATA`` arrivals of an open loop, their number and their span
are constants, so no run holds two or nine 768-token prompts by luck.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Callable, List, Optional, Sequence

import numpy as np

from .stats import quantile_grid

#: fixed seed of the pairing of prompt and output lengths: part of the
#: traffic's definition, the same for every run
_PAIRING_SEED = 20260927


@dataclasses.dataclass
class Request:
    due_s: float            # relative to the window's opening; < 0: lead-in
    tokens: np.ndarray      # prompt, int32
    max_new_tokens: int
    counted: bool = True    # due inside the window
    # filled by the driver
    sent_s: Optional[float] = None
    handle: Any = None
    error: Optional[BaseException] = None


def length_pairs(n: int, prompt: dict, output: dict) -> List[tuple]:
    """``n`` (prompt, output) pairs: both quantile grids, paired by one
    fixed permutation.  Identical for every seed."""
    prompts = quantile_grid(n, prompt)
    outputs = quantile_grid(n, output)
    order = np.random.default_rng(_PAIRING_SEED + n).permutation(n)
    return [(prompts[i], outputs[int(j)]) for i, j in enumerate(order)]


def exponential_gaps(n: int, span_s: float) -> List[float]:
    """``n`` gaps that sum to ``span_s``: the quantile grid of the
    exponential distribution, rescaled (the grid's mean falls a little short
    of the distribution's because its tail is cut at the last quantile)."""
    gaps = [-math.log(1.0 - (i + 0.5) / n) for i in range(n)]
    scale = span_s / sum(gaps)
    return [g * scale for g in gaps]


def _arrivals(gaps: Sequence[float]) -> List[float]:
    """Each arrival in the middle of its gap, so all fall inside the span."""
    out, t = [], 0.0
    for g in gaps:
        out.append(t + g / 2.0)
        t += g
    return out


def _warp_bursts(times: List[float], span_s: float, burst: dict
                 ) -> List[float]:
    """Square-wave bursts at the same mean rate: every ``every_s`` the rate
    is ``factor`` times the off-burst rate for ``len_s``.  Implemented by
    warping the arrival times through the inverse cumulative rate, which
    keeps their number and their order."""
    every, length, factor = burst["every_s"], burst["len_s"], burst["factor"]
    mean = (length * factor + (every - length)) / every   # of off-burst rate
    out = []
    for t in times:
        mass = t * mean                      # off-burst seconds of arrivals
        cycle_mass = length * factor + (every - length)
        k, rem = divmod(mass, cycle_mass)
        inside = rem / factor if rem < length * factor \
            else length + (rem - length * factor)
        out.append(min(k * every + inside, span_s - 1e-9))
    return out


#: arrivals of an open loop are dealt in runs of this many (``stratified_order``)
STRATA = 16


def open_loop_schedule(traffic: dict, seed: int, seconds: float,
                       vocab: int) -> List[Request]:
    """Lead-in requests (due before 0, served, not counted) followed by the
    window's requests, sorted by due time.

    The arrival pattern is *fixed and stratified*, the traffic's own and the
    same for every seed.  Both orders (of the gaps, of the length pairs) are
    drawn once from the fixed pairing seed by ``stratified_order``: every
    run of ``STRATA`` consecutive arrivals holds one gap of each stratum of
    the exponential grid, so it spans the same time, and one pair of each
    stratum of cost (prompt + output), so it brings the same work.  Inside a
    run the gaps stay exponential and shuffled (two or three requests still
    land together), but over ``STRATA`` arrivals their number per time is a
    constant, so the stream is less bursty than a Poisson process.
    ``--seed`` decides where in that cycle the span begins, and the token
    ids: every seed offers the same arrivals with the same lengths, each
    next to the same neighbours, in another order only in that the cycle
    starts elsewhere.  A tail is made of coincidences (three arrivals inside
    20 ms, a long prompt among them), and a shuffle, stratified or not,
    deals each seed a different hand of them: ``ttft_p95_ms`` spread
    5.8 / 11.6% with a plain shuffle, 7.0 / 16.1% with a stratified one and
    3.0 / 5.3% with the one cycle (PERF.md 6)."""
    rng = np.random.default_rng(seed)
    rate = float(traffic["rate_hz"])
    out: List[Request] = []
    for start, span, counted in ((-float(traffic["lead_s"]),
                                  float(traffic["lead_s"]), False),
                                 (0.0, float(seconds), True)):
        n = int(round(rate * span))
        if n == 0:
            continue
        pairs = length_pairs(n, traffic["prompt_len"], traffic["output_len"])
        gaps = exponential_gaps(n, span)
        own = np.random.default_rng(_PAIRING_SEED + n)
        gap_order = stratified_order(own, gaps, STRATA)
        pair_order = stratified_order(own, [p + o for p, o in pairs], STRATA)
        shift = int(rng.integers(n))
        gap_order = gap_order[shift:] + gap_order[:shift]
        pair_order = pair_order[shift:] + pair_order[:shift]
        times = _arrivals([gaps[i] for i in gap_order])
        if traffic.get("burst"):
            times = _warp_bursts(times, span, traffic["burst"])
        for t, k in zip(times, pair_order):
            plen, olen = pairs[int(k)]
            out.append(Request(
                due_s=start + t, max_new_tokens=olen, counted=counted,
                tokens=rng.integers(0, vocab, plen).astype(np.int32)))
    return out


def standing_population(traffic: dict, seed: int, vocab: int
                        ) -> List[Request]:
    """The requests a server at this rate holds in steady state, admitted
    during set-up so that the window does not open on an empty server:
    ``rate x mean residency`` of them, lengths from the same grid, each
    with a different share of its output still to come."""
    n = int(traffic.get("standing", 0))
    if n == 0:
        return []
    rng = np.random.default_rng(seed + 1)
    # every seed finds the same population in place
    order = np.random.default_rng(_PAIRING_SEED + n + 1)
    pairs = length_pairs(n, traffic["prompt_len"], traffic["output_len"])
    left = (np.arange(n) + 0.5) / n
    return [Request(due_s=-math.inf, counted=False,
                    tokens=rng.integers(0, vocab, p).astype(np.int32),
                    max_new_tokens=max(1, int(round(o * left[int(j)]))))
            for (p, o), j in zip(pairs, order.permutation(n))]


def stratified_order(rng, costs: Sequence[float], strata: int) -> List[int]:
    """An order of ``range(len(costs))``, drawn from ``rng``, in which every
    run of ``strata`` consecutive indices holds one item of each stratum of
    cost.  The items, sorted by cost, are cut into ``strata`` strata as
    equal as can be; ``rng`` decides which item of a stratum comes when and
    the order inside each run."""
    by_cost = sorted(range(len(costs)), key=lambda k: (costs[k], k))
    columns = [[int(c[int(i)]) for i in rng.permutation(len(c))]
               for c in np.array_split(by_cost, strata) if len(c)]
    out = []
    for i in range(max(len(c) for c in columns)):
        row = [c[i] for c in columns if i < len(c)]
        out += [row[int(j)] for j in rng.permutation(len(row))]
    return out


def backlog_requests(traffic: dict, seed: int, vocab: int, slots: int
                     ) -> List[Request]:
    """One pass of the backlog's fixed multiset in the seed's order; the
    driver cycles it.  The first ``slots`` requests (the initial fill) keep
    only a staggered share of their output, so that slots do not free in
    step."""
    rng = np.random.default_rng(seed)
    n = int(traffic["pairs"])
    pairs = length_pairs(n, traffic["prompt_len"], traffic["output_len"])
    out = []
    for i, k in enumerate(rng.permutation(n)):
        plen, olen = pairs[int(k)]
        if i < slots:
            olen = max(1, int(round(olen * (i + 0.5) / slots)))
        out.append(Request(due_s=0.0, max_new_tokens=olen,
                           tokens=rng.integers(0, vocab,
                                               plen).astype(np.int32)))
    return out


def drive_open_loop(submit: Callable[[Request], Any],
                    schedule: Sequence[Request], t_open: float,
                    now: Callable[[], float],
                    sleep: Callable[[float], None]) -> None:
    """Send every request when it is due, whatever came back: the open
    loop.  ``t_open`` is the window's opening on ``now``'s clock.  A
    refusal is data (``error``), never an exception here."""
    for req in schedule:
        delay = t_open + req.due_s - now()
        if delay > 0:
            sleep(delay)
        req.sent_s = now() - t_open
        try:
            req.handle = submit(req)
        except Exception as e:   # the server saying no is a failed request
            req.error = e


class Backlog:
    """Keeps ``outstanding`` requests in the server: each one that finishes
    is replaced by the next of ``requests`` (cycled, so the multiset stays
    fixed however long the run)."""

    def __init__(self, submit: Callable[[Request], Any],
                 requests: Sequence[Request], outstanding: int,
                 sleep: Callable[[float], None], poll_s: float = 0.004,
                 on_poll: Optional[Callable[[], None]] = None):
        self._submit, self._requests = submit, requests
        self._outstanding, self._sleep = outstanding, sleep
        self._poll_s, self._on_poll = poll_s, on_poll
        self._next = 0
        self._live: List[Request] = []
        self.sent: List[Request] = []
        self.refused: Optional[BaseException] = None

    def run_until(self, until: Callable[[], bool]) -> None:
        """Poll, refill and call ``on_poll`` until ``until()`` is true or
        the server refuses a request (``refused``): a backlog that is
        refused cannot be kept."""
        while self.refused is None:
            self._live = [r for r in self._live if not r.handle.done()]
            while len(self._live) < self._outstanding:
                src = self._requests[self._next % len(self._requests)]
                req = src if self._next < len(self._requests) \
                    else dataclasses.replace(src, handle=None)
                self._next += 1
                self.sent.append(req)
                try:
                    req.handle = self._submit(req)
                except Exception as e:
                    req.error = self.refused = e
                    return
                self._live.append(req)
            if self._on_poll is not None:
                self._on_poll()
            if until():
                return
            self._sleep(self._poll_s)
