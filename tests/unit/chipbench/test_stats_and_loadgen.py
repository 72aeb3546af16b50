"""The arithmetic between samples and metrics, and the traffic generator:
deterministic per seed, one multiset of lengths for every seed, due times
accounted for in fake time."""

import collections

import numpy as np
import pytest

from benchmarks.chip import loadgen, stats

CHAT = {"rate_hz": 4.6, "lead_s": 3.0, "standing": 44,
        "prompt_len": {"kind": "lognormal", "median": 128, "sigma": 0.8,
                       "min": 16, "max": 768},
        "output_len": {"kind": "lognormal", "median": 40, "sigma": 0.6,
                       "min": 8, "max": 160}}
SAT = {"pairs": 32,
       "prompt_len": {"kind": "uniform", "min": 64, "max": 256},
       "output_len": {"kind": "uniform", "min": 128, "max": 384}}


@pytest.mark.parametrize("q", [0, 5, 50, 90, 95, 99, 100])
@pytest.mark.parametrize("n", [1, 2, 7, 200])
def test_percentile_is_numpys(q, n):
    values = list(np.random.default_rng(n).normal(size=n))
    assert stats.percentile(values, q) == pytest.approx(
        np.percentile(values, q), abs=1e-12)


def test_percentile_of_nothing_raises():
    with pytest.raises(ValueError):
        stats.percentile([], 95)


@pytest.mark.parametrize("n, q, beyond", [(200, 95, 10), (199, 95, 9),
                                          (100, 90, 10), (207, 95, 10),
                                          (90, 95, 4)])
def test_samples_beyond_a_percentile(n, q, beyond):
    assert stats.samples_beyond(n, q) == beyond


def test_tpot_is_the_mean_gap():
    # 5 tokens at 1.0, 1.2, 1.4, 1.6, 1.8 s: four gaps of 0.2 s
    assert stats.tpot_s(1.0, 1.8, 5) == pytest.approx(0.2)
    assert stats.tpot_s(1.0, 1.0, 1) is None


def test_lognormal_quantiles():
    assert stats.lognormal_quantile(0.5, 128, 0.8) == pytest.approx(128)
    # one sigma above the median is the 84.13th percentile
    assert stats.lognormal_quantile(0.841344746, 40, 0.6) == pytest.approx(
        40 * np.exp(0.6), rel=1e-6)
    grid = stats.quantile_grid(207, CHAT["prompt_len"])
    assert grid == sorted(grid) and grid[0] >= 16 and grid[-1] == 768
    assert 120 <= np.median(grid) <= 136


def _multiset(requests):
    return collections.Counter((len(r.tokens), r.max_new_tokens)
                               for r in requests if r.counted)


def test_open_loop_schedule_is_deterministic_per_seed():
    a = loadgen.open_loop_schedule(CHAT, 2 ** 31 + 5, 45, 50257)
    b = loadgen.open_loop_schedule(CHAT, 2 ** 31 + 5, 45, 50257)
    assert [r.due_s for r in a] == [r.due_s for r in b]
    assert all((x.tokens == y.tokens).all() for x, y in zip(a, b))


def test_every_seed_offers_the_same_lengths_and_gaps():
    runs = [loadgen.open_loop_schedule(CHAT, seed, 45, 50257)
            for seed in (0, 1, 2 ** 31 + 5)]
    first = [r for r in runs[0] if r.counted]
    assert len(first) == round(4.6 * 45)
    for other in runs[1:]:
        assert _multiset(other) == _multiset(runs[0])
        assert [r.due_s for r in other] != [r.due_s for r in runs[0]]
    due = [r.due_s for r in first]
    assert due == sorted(due) and 0 <= due[0] and due[-1] < 45
    lead = [r for r in runs[0] if not r.counted]
    assert len(lead) == round(4.6 * 3) and all(-3 <= r.due_s < 0
                                               for r in lead)


def test_gaps_are_one_multiset_that_sums_to_the_span():
    gaps = loadgen.exponential_gaps(207, 45.0)
    assert sum(gaps) == pytest.approx(45.0)
    assert np.mean(gaps) == pytest.approx(1 / 4.6)
    # exponential: the median gap is ln 2 of the mean, within the rescaling
    assert np.median(gaps) / np.mean(gaps) == pytest.approx(np.log(2),
                                                           rel=0.03)


def test_bursts_keep_the_count_and_the_mean_rate():
    bursty = dict(CHAT, burst={"every_s": 4.0, "len_s": 1.5, "factor": 3.0})
    run = [r for r in loadgen.open_loop_schedule(bursty, 3, 44, 50257)
           if r.counted]
    assert _multiset(run) == _multiset(
        loadgen.open_loop_schedule(CHAT, 9, 44, 50257))
    phase = np.mod([r.due_s for r in run], 4.0)
    inside = (phase < 1.5).sum()
    # 1.5 s at three times the rate against 2.5 s at the rate: 64% inside
    assert 0.55 <= inside / len(run) <= 0.73
    assert all(0 <= r.due_s < 44 for r in run)


def test_standing_population_has_staggered_remainders():
    pop = loadgen.standing_population(CHAT, 1, 50257)
    assert len(pop) == 44 and not any(r.counted for r in pop)
    assert len({r.max_new_tokens for r in pop}) > 10
    assert loadgen.standing_population(dict(CHAT, standing=0), 1, 9) == []


def test_backlog_is_one_multiset_in_the_seeds_order():
    a = loadgen.backlog_requests(SAT, 1, 50257, slots=4)
    b = loadgen.backlog_requests(SAT, 2, 50257, slots=4)
    prompts = lambda run: sorted(len(r.tokens) for r in run)
    assert prompts(a) == prompts(b) and len(a) == 32
    assert [len(r.tokens) for r in a] != [len(r.tokens) for r in b]
    assert all(64 <= len(r.tokens) <= 256 for r in a)
    # the initial fill keeps a staggered share of its output
    assert a[0].max_new_tokens < a[3].max_new_tokens <= 384


class FakeClock:
    def __init__(self):
        self.t = 100.0

    def now(self):
        return self.t

    def sleep(self, s):
        assert s > 0
        self.t += s


class FakeHandle:
    def __init__(self, done_after_polls=0):
        self.polls_left = done_after_polls

    def done(self):
        self.polls_left -= 1
        return self.polls_left < 0


def test_open_loop_keeps_its_schedule_in_fake_time():
    clock = FakeClock()
    schedule = [loadgen.Request(due_s=d, tokens=np.zeros(3, np.int32),
                                max_new_tokens=2) for d in (0.5, 1.0, 1.1)]
    sent = []

    def submit(req):
        sent.append((req.due_s, clock.now()))
        clock.t += 0.3          # a slow submit delays what is due next
        if req.due_s == 1.0:
            raise RuntimeError("queue full")
        return FakeHandle()

    loadgen.drive_open_loop(submit, schedule, 100.0, clock.now, clock.sleep)
    assert [round(r.sent_s, 6) for r in schedule] == [0.5, 1.0, 1.3]
    # lateness is counted from when a request was due, not from when the
    # generator got round to it
    assert [round(r.sent_s - r.due_s, 6) for r in schedule] == [0, 0, 0.2]
    assert schedule[1].handle is None and schedule[1].error is not None
    assert schedule[2].handle is not None


def test_backlog_refills_and_cycles_in_fake_time():
    clock = FakeClock()
    requests = loadgen.backlog_requests(SAT, 1, 100, slots=2)[:3]
    backlog = loadgen.Backlog(lambda r: FakeHandle(done_after_polls=2),
                              requests, outstanding=2, sleep=clock.sleep,
                              poll_s=0.01)
    backlog.run_until(lambda: clock.now() >= 100.1)
    assert len(backlog.sent) > 3                  # cycled past its 3 sources
    assert all(r.handle is not None for r in backlog.sent)
    assert len({id(r) for r in backlog.sent}) == len(backlog.sent)
    lens = [len(r.tokens) for r in backlog.sent]
    assert lens[:3] == lens[3:6]                  # the same order again


def test_backlog_stops_when_it_is_refused():
    def submit(req):
        raise RuntimeError("queue full")
    backlog = loadgen.Backlog(submit, loadgen.backlog_requests(
        SAT, 1, 100, slots=2), outstanding=2, sleep=lambda s: None)
    backlog.run_until(lambda: False)
    assert backlog.refused is not None and backlog.sent[0].error is not None


# ---- the orders: a backlog's shuffle, an open loop's one cycle (PR 32) ---

SAT256 = dict(SAT, pairs=256)


def _cost(run):
    return [len(r.tokens) + r.max_new_tokens for r in run]


@pytest.mark.parametrize("seed", [0, 7, 2 ** 31 + 5])
def test_a_stratified_order_gives_every_stretch_the_same_work(seed):
    rng = np.random.default_rng(seed)
    costs = _cost(loadgen.backlog_requests(SAT256, 1, 100, 0))
    order = loadgen.stratified_order(rng, costs, 16)
    assert sorted(order) == list(range(256))            # one multiset
    strat = [costs[k] for k in order]
    plain = [costs[int(k)] for k in rng.permutation(256)]
    total = sum(costs)
    for start in range(0, 256, 16):     # every run of 16 holds each stratum
        run = strat[start:start + 16]
        assert abs(sum(run) - total / 16) < 0.02 * total / 16
    by_64 = lambda c: [sum(c[i:i + 64]) for i in range(0, 256, 64)]
    swing = lambda sums: (max(sums) - min(sums)) / (total / 4)
    assert swing(by_64(strat)) < 0.01 < swing(by_64(plain))
    # strata that do not divide the multiset are as equal as can be
    assert sorted(loadgen.stratified_order(rng, costs, 7)) == sorted(order)


def test_a_backlog_is_one_multiset_in_the_seeds_shuffle():
    a = loadgen.backlog_requests(SAT256, 1, 100, 0)
    b = loadgen.backlog_requests(SAT256, 2, 100, 0)
    again = loadgen.backlog_requests(SAT256, 1, 100, 0)
    assert _cost(a) == _cost(again) and _cost(a) != _cost(b)
    assert sorted(_cost(a)) == sorted(_cost(b))
    assert all((x.tokens == y.tokens).all() for x, y in zip(a, again))
    # the parent's order, which the ledger's history of decode-sat rests on
    pairs = loadgen.length_pairs(256, SAT["prompt_len"], SAT["output_len"])
    want = [pairs[int(k)] for k in np.random.default_rng(1).permutation(256)]
    assert [(len(r.tokens), r.max_new_tokens) for r in a] == want


def test_the_arrivals_span_the_same_time_and_bring_the_same_work():
    import json
    import os

    from .common import BENCH_DIR
    with open(os.path.join(BENCH_DIR, "traffic", "chat-steady.json")) as f:
        chat = json.load(f)
    runs = [loadgen.open_loop_schedule(chat, seed, 45, 50257)
            for seed in (1, 2 ** 31 + 5)]
    body = [[r for r in run if r.counted] for run in runs]
    assert len(body[0]) == 1890 and _multiset(runs[0]) == _multiset(runs[1])
    assert [r.due_s for r in body[0]] != [r.due_s for r in body[1]]
    # a plain shuffle of the same gaps and pairs, for comparison
    rng = np.random.default_rng(3)
    gaps = np.diff([0.0] + [r.due_s for r in body[0]])
    dues = np.cumsum(gaps[rng.permutation(len(gaps))])
    plain = [(d, body[0][int(k)]) for d, k in
             zip(dues, rng.permutation(len(body[0])))]

    def swings(run, of):
        """Largest departure of a 5 s stretch from the mean stretch."""
        sums = [0.0] * 9
        for due, r in run:
            sums[min(8, int(due // 5))] += of(r)
        mean = sum(sums) / len(sums)
        return max(abs(x - mean) for x in sums) / mean
    count = lambda r: 1.0
    work = lambda r: len(r.tokens) + r.max_new_tokens
    for run in body:
        run = [(r.due_s, r) for r in run]
        assert swings(run, count) < 0.05 and swings(run, work) < 0.07
    assert swings(plain, work) > 0.08 and swings(plain, count) > 0.06
    # arrivals stay exponential inside a run of 16: some land together
    short = lambda dues: int((np.diff(dues) < 0.25 / 42).sum())
    mine = [r.due_s for r in body[0]]
    assert short(mine) > 50 and short(mine) > 0.5 * short(dues)
    assert np.diff(mine).max() > 2.5 / 42


def test_the_poll_period_and_the_orders_are_no_knobs_of_a_traffic_file():
    """One value each was in use (REVIEW of PR 32): the backlog polls every
    4 ms as it did at the parent, and no traffic file chooses an order."""
    import inspect
    import json
    import os

    from benchmarks.chip.kinds import backlog

    from .common import BENCH_DIR
    assert "poll_s" not in inspect.getsource(backlog)
    slept = []
    b = loadgen.Backlog(lambda r: FakeHandle(done_after_polls=1),
                        loadgen.backlog_requests(SAT, 1, 100, slots=2),
                        outstanding=2, sleep=slept.append)
    b.run_until(lambda: len(slept) >= 3)
    assert slept == [0.004, 0.004, 0.004]
    source = inspect.getsource(loadgen)
    for knob in ("strata", "seed_rotates", "poll_s", "trace_at_s"):
        assert f'"{knob}"' not in source
        for name in os.listdir(os.path.join(BENCH_DIR, "traffic")):
            with open(os.path.join(BENCH_DIR, "traffic", name)) as f:
                assert knob not in json.load(f), (name, knob)


# ---- the two sets' arithmetic (``spread.py``) ---------------------------

def test_spread_is_the_interquartile_distance_over_the_median():
    import statistics

    from benchmarks.chip import spread
    values = [100.0, 101.0, 99.0, 100.5, 99.5, 130.0]
    q = statistics.quantiles(values, n=4)
    assert spread.spread(values) == pytest.approx(
        (q[2] - q[0]) / statistics.median(values))
    assert spread.spread([5.0]) == 0.0
    # the run farthest from the median is left out for tightness
    assert 130.0 not in spread.without_farthest(values)
    assert spread.spread(spread.without_farthest(values)) < 0.02
    s = spread.summarize(values, [v * 1.02 for v in values])
    assert s["b_over_a"] == pytest.approx(1.02)
    assert s["tight"] < 0.02 < s["loose"]
    assert s["loose"] >= max(s["spread_a"], s["spread_b"])


def test_a_rotating_seed_offers_every_seed_the_same_neighbours():
    chat = dict(CHAT, rate_hz=42.0)
    runs = [[r for r in loadgen.open_loop_schedule(chat, seed, 45, 50257)
             if r.counted] for seed in (1, 2, 2 ** 31 + 5)]
    key = lambda r: (len(r.tokens), r.max_new_tokens)
    first = [key(r) for r in runs[0]]
    gaps0 = np.diff([r.due_s for r in runs[0]])
    for other in runs[1:]:
        seq = [key(r) for r in other]
        assert seq != first and sorted(seq) == sorted(first)
        # one cycle, begun elsewhere: some rotation of it is the first's
        at = next(i for i in range(len(seq))
                  if seq[i:] + seq[:i] == first)
        assert at > 0
        # the gaps rotate with the lengths: request k of the first run is
        # request k + at of this one, as far from its neighbour
        gaps = np.diff([r.due_s for r in other])
        assert np.allclose(gaps[at:at + 200], gaps0[:200])
        # and the token ids are the seed's own
        assert not (other[0].tokens[:8] == runs[0][(len(seq) - at)
                                                   % len(seq)].tokens[:8]).all()
    again = [r for r in loadgen.open_loop_schedule(chat, 1, 45, 50257)
             if r.counted]
    assert [r.due_s for r in again] == [r.due_s for r in runs[0]]
