"""Metric arithmetic: percentiles over all requests and all gaps, counter
shares over the window, and the serving loop's record of slow steps."""

import types

import numpy as np
import pytest

from chipbench_testlib import BENCH  # noqa: F401  (puts chipbench on the path)
from chipbench import check, readers, serving, traffic
from chipbench.traffic import Item


def rec(due, stamps):
    r = serving.Record(item=Item(due=due, prompt=np.zeros(4, np.int32),
                                max_new=len(stamps)))
    r.stamps = list(stamps)
    return r


def fake_run(records, measured, t0=10.0, t1=20.0, c0=None, c1=None):
    w = serving.Window(t0=t0, t1=t1, counters0=c0 or {}, counters1=c1 or {})
    return types.SimpleNamespace(records=records, measured=measured,
                                 window=w, trace=None)


def test_ttft_p95_is_over_every_request_from_its_due_time():
    rng = np.random.default_rng(0)
    recs = [rec(10 + i * 0.01, [10 + i * 0.01 + x, 30.0])
            for i, x in enumerate(rng.uniform(0.1, 2.0, 400))]
    run = fake_run(recs, recs)
    want = np.percentile([r.stamps[0] - r.item.due for r in recs], 95)
    assert readers.ttft_p95_ms(run) == pytest.approx(1e3 * want)


def test_tpot_p95_takes_each_request_over_all_its_gaps():
    recs = [rec(11, [12, 12.1, 12.2, 12.3]), rec(12, [13, 14]),
            rec(13, [14])]                # one token: no time per token
    run = fake_run(recs, recs)
    want = np.percentile([0.1, 1.0], 95)
    assert readers.tpot_p95_ms(run) == pytest.approx(1e3 * want)


def test_counter_shares_difference_the_window():
    c0 = {"prefix_hit_tokens": 100, "prefill_tokens": 900}
    c1 = {"prefix_hit_tokens": 400, "prefill_tokens": 1900}
    run = fake_run([], [], c0=c0, c1=c1)
    assert readers.prefix_hit_share(run) == pytest.approx(
        100 * 300 / 1300)
    assert readers.prefix_hit_share(fake_run([], [], c0=c0, c1=c0)) is None


def test_readers_find_nothing_without_a_trace():
    run = fake_run([], [])
    assert readers.device_idle_share(run) is None
    assert readers.program_ms(run, "_decode") is None


class _SlowEngine:
    """An engine whose third step stalls and emits a token to each slot."""

    n_slots = 2

    def __init__(self, clock):
        self.clock, self.n = clock, 0
        self.stats = types.SimpleNamespace(
            **{k: 0 for k in serving.COUNTERS})
        self.reqs = []

    def submit(self, prompt, max_new_tokens):
        r = types.SimpleNamespace(prompt=prompt, out_tokens=[], done=False,
                                  max_new=max_new_tokens)
        self.reqs.append(r)
        return r

    def step(self):
        self.n += 1
        self.clock.t += 1.0 if self.n == 3 else 0.01
        self.stats.decode_steps += 1
        for r in self.reqs:
            if not r.done:
                r.out_tokens.append(1)
                r.done = len(r.out_tokens) >= r.max_new


def test_a_slow_step_is_kept_with_the_counters_it_moved():
    clock = types.SimpleNamespace(t=0.0)
    engine = _SlowEngine(clock)
    mix = {"loop": "open", "unit": "request", "rate_per_s": 4.0,
           "lead_s": 0.0, "window_opens": "lead_end",
           "prompt": {"dist": "uniform", "min": 4, "max": 8},
           "output": {"dist": "uniform", "min": 6, "max": 6}}
    src = traffic.Source(mix, 1, vocab=10, horizon_s=0.2)
    records, window, late, stalls = serving.drive(
        engine, src, seconds=0.1, lead_s=0.0, opens="lead_end",
        clock=lambda: clock.t)
    assert len(stalls.steps) == 1
    start, secs, cpu, moved = stalls.steps[0]
    assert secs == pytest.approx(1.0) and cpu < 0.5
    assert moved == {"decode_steps": 1.0}
    assert all(r.req.done for r in records)


def test_a_slow_collection_is_kept_with_its_generation():
    clock = types.SimpleNamespace(t=5.0)
    stalls = serving.Stalls()
    gc_clock = serving._GcClock(stalls, 1.0, lambda: clock.t)
    gc_clock("start", {"generation": 0})
    clock.t += 0.01
    gc_clock("stop", {"generation": 0})            # quick: not kept
    gc_clock("start", {"generation": 2})
    clock.t += 0.2
    gc_clock("stop", {"generation": 2})
    assert stalls.gcs == [(pytest.approx(4.01), pytest.approx(0.2), 2)]


def test_readings_and_the_judge():
    """Every served token's gap counts; a run is correct only where every
    limit is met, and a limit with no reading fails it."""
    gap = np.array([0.0, 0.0, 0.01, 0.06, 0.2])
    read = check.readings(gap)
    assert read["tokens"] == 5 and read["disagreements"] == 3
    assert read["mean_gap"] == pytest.approx(0.054)
    assert read["share_over_0.05"] == pytest.approx(0.4)
    assert check.judge(read, {"mean_gap": 0.06, "share_over_0.05": 0.5})
    assert not check.judge(read, {"mean_gap": 0.05})
    assert not check.judge(read, {"no_such_reading": 1.0})
    assert not check.judge(read, {})
