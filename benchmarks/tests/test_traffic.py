"""The traffic generators and the serving loops, on a fake system with a
fake clock: no JAX, no program. The same seed gives the same schedule;
two seeds give the same multiset of lengths and the same count; latency
is taken from the instant a request was due; a late first token or a
straggler counts in ``failed``."""
import contextlib
from collections import Counter

import numpy as np
import pytest

from benchmarks.harness.traffic import (closed_loop, lengths, open_loop,
                                        train_steps)

CHAT = {"kind": "open_loop", "max_batch": 4, "rate_per_s": 5.0,
        "ramp_s": 2.0, "cooldown_s": 4.0, "trace_s": 1.0,
        "prompt": {"dist": "lognormal", "median": 768, "sigma": 0.8,
                   "min": 64, "max": 2048},
        "output": {"dist": "lognormal", "median": 128, "sigma": 0.7,
                   "min": 32, "max": 384},
        "ttft_limit_ms": 400, "check_requests": 2}


def judged(plan):
    return [r for r in plan["requests"] if r.judged]


def test_same_seed_same_schedule():
    a = open_loop.plan(CHAT, 2 ** 31 + 7, 10.0, 1000)["requests"]
    b = open_loop.plan(CHAT, 2 ** 31 + 7, 10.0, 1000)["requests"]
    assert len(a) == len(b)
    for x, y in zip(a, b):
        assert (x.due, x.n_out, x.judged) == (y.due, y.n_out, y.judged)
        assert np.array_equal(x.prompt, y.prompt)


def test_two_seeds_same_lengths_and_count():
    a = judged(open_loop.plan(CHAT, 1, 10.0, 1000))
    b = judged(open_loop.plan(CHAT, 2, 10.0, 1000))
    assert len(a) == len(b) == 50                  # rate x window, exactly
    assert Counter(len(r.prompt) for r in a) == \
        Counter(len(r.prompt) for r in b)
    assert Counter(r.n_out for r in a) == Counter(r.n_out for r in b)
    assert [r.due for r in a] != [r.due for r in b]          # other times
    assert [len(r.prompt) for r in a] != [len(r.prompt) for r in b]
    assert all(2.0 <= r.due < 12.0 for r in a)     # due inside the window
    assert [r.due for r in a] == sorted(r.due for r in a)


def test_stratified_quantiles():
    q = lengths.quantiles(CHAT["prompt"], 1001)
    assert q.min() >= 64 and q.max() <= 2048
    assert q[500] == 768                           # the median
    u = lengths.quantiles({"dist": "uniform", "min": 1024, "max": 2048}, 4)
    assert list(u) == [1152, 1408, 1664, 1920]


def test_train_batches_from_seed():
    p = {"kind": "train_steps", "batch": 2, "seq": 8, "distinct": 3}
    a = train_steps.plan(p, 5, 1.0, 100)["ids"]
    assert a.shape == (3, 2, 9) and a.min() >= 0 and a.max() < 100
    assert np.array_equal(a, train_steps.plan(p, 5, 1.0, 100)["ids"])
    assert not np.array_equal(a, train_steps.plan(p, 6, 1.0, 100)["ids"])
    rows = a.reshape(-1, 9)
    assert len({tuple(r) for r in rows}) == len(rows)     # all differ


class Clock:
    def __init__(self):
        self.t = 100.0

    def __call__(self):
        return self.t

    def sleep(self, s):
        self.t += s


class Phases:
    def span(self, name):
        return contextlib.nullcontext()

    def open_window(self, at=None):
        self.opened = at

    def close_window(self):
        pass

    def traced(self, fn):
        return None


class FakeEngine:
    """Every step takes ``step_s``; a request's first token comes
    ``first_s`` after the step that admits it and each further token one
    step later. ``stuck`` requests never finish."""

    def __init__(self, clock, traffic, step_s=0.05, first_s=0.1,
                 stuck=()):
        self.clock, self.traffic = clock, traffic
        self.max_batch = traffic["max_batch"]
        self.step_s, self.first_s, self.stuck = step_s, first_s, set(stuck)
        self.live, self.out, self.n = {}, [], 0
        self.admit = {}

    def submit(self, prompt, n_out):
        self.n += 1
        self.live[self.n] = {"n": n_out, "got": 0, "first": None,
                             "t_in": self.clock()}
        return self.n

    def busy(self):
        return bool(self.live)

    def step(self):
        self.clock.sleep(self.step_s)
        now = self.clock()
        for rid, r in list(self.live.items()):
            if rid in self.stuck:
                continue
            if r["first"] is None:
                if now - r["t_in"] >= self.first_s:
                    r["first"], r["got"] = now, 1
                    self.admit[rid] = now
            else:
                r["got"] += 1
            if r["got"] >= r["n"]:
                self.out.append((rid, r["first"], now,
                                 np.zeros(r["n"], np.int64)))
                del self.live[rid]

    def pop_finished(self):
        out, self.out = self.out, []
        return out

    def active(self):
        return min(len(self.live), self.max_batch)

    def queued(self):
        return max(len(self.live) - self.max_batch, 0)

    def decode_rows(self):
        return []

    def admit_times(self):
        return self.admit


def run_open(traffic, engine_kw, seconds=5.0, seed=3):
    clock = Clock()
    plan = open_loop.plan(traffic, seed, seconds, 1000)
    eng = FakeEngine(clock, traffic, **engine_kw)
    res = open_loop.run(eng, plan, seconds, Phases(), clock=clock,
                        sleep=clock.sleep)
    return res, plan


def test_latency_is_taken_from_due_time():
    fast = dict(CHAT, output={"dist": "uniform", "min": 4, "max": 8})
    res, plan = run_open(fast, {"step_s": 0.05, "first_s": 0.1})
    assert res["attempted"] == 25 and res["failed"] == 0
    for r in res["finished"]:
        assert r.t_submit >= r.due                 # never sent early
        assert r.ttft_ms == pytest.approx(1e3 * (r.t_first - r.due))
        assert r.ttft_ms >= 1e3 * (r.t_first - r.t_submit)
        # n tokens, one per 50 ms step after the first
        assert r.tpot_ms == pytest.approx(50.0)
    assert res["metrics"]["tpot_p90_ms"] == pytest.approx(50.0)
    # the generator runs late by up to one step: reported, not hidden
    assert 0 <= res["host"]["gen_late_p99_ms"] <= 51
    assert res["host"]["ttft_p50_ms"] >= 100


def test_late_first_token_counts_as_failed():
    fast = dict(CHAT, output={"dist": "uniform", "min": 4, "max": 8})
    res, _ = run_open(fast, {"step_s": 0.05, "first_s": 0.6})
    assert res["failed"] == res["attempted"] == 25     # all over 400 ms
    assert res["counts"]["ttft_over_limit"] == 25


def test_straggler_counts_as_failed():
    fast = dict(CHAT, output={"dist": "uniform", "min": 4, "max": 8})
    plan = open_loop.plan(fast, 3, 5.0, 1000)
    first_judged = next(i for i, r in enumerate(plan["requests"])
                        if r.judged) + 1          # rids count from 1
    res, _ = run_open(fast, {"stuck": [first_judged]})
    assert res["failed"] == 1 and res["counts"]["finished"] == 24
    assert res["host"]["cooldown_s"] >= fast["cooldown_s"]   # waited it out


def test_closed_loop_keeps_clients_busy():
    t = {"kind": "closed_loop", "max_batch": 2, "clients": 4, "ramp_s": 0.5,
         "cycle": 4, "trace_s": 1.0,
         "prompt": {"dist": "uniform", "min": 1024, "max": 2048},
         "output": {"dist": "uniform", "min": 16, "max": 64},
         "check_requests": 2}
    clock = Clock()
    eng = FakeEngine(clock, t, step_s=0.01, first_s=0.02)
    plan = closed_loop.plan(t, 9, 3.0, 1000)
    res = closed_loop.run(eng, plan, 3.0, Phases(), clock=clock)
    assert len(eng.live) == 4                  # every client has one out
    done = res["finished"]
    assert res["attempted"] == len(done) > 8 and res["failed"] == 0
    tokens = sum(len(r.prompt) + len(r.tokens) for r in done)
    span = max(r.t_finish for r in done) - (100.0 + 0.5)
    assert res["metrics"]["served_tokens_per_s"] == \
        pytest.approx(tokens / span, rel=1e-3)
    # every cycle of 4 holds the same multiset of lengths
    s1 = closed_loop._Stream(t, 1, 1000)
    s2 = closed_loop._Stream(t, 2, 1000)
    a = [s1.next() for _ in range(8)]
    b = [s2.next() for _ in range(8)]
    for lo in (0, 4):
        assert Counter(len(r.prompt) for r in a[lo:lo + 4]) == \
            Counter(len(r.prompt) for r in b[lo:lo + 4])


def test_arrivals_are_poisson_conditioned_on_the_count():
    a = judged(open_loop.plan(CHAT, 1, 40.0, 1000))
    assert len(a) == 200                        # rate x window, exactly
    due = np.array([r.due for r in a])
    assert (np.diff(due) >= 0).all() and 2.0 <= due[0] and due[-1] < 42.0
    gaps = np.diff(due)
    assert 0.8 < gaps.std() / gaps.mean() < 1.25   # exponential gaps: 1
    per_slot = Counter(int((t - 2.0) / 0.2) for t in due)    # 1/rate
    assert max(per_slot.values()) >= 3          # users arrive in clumps
    assert len(per_slot) < 160                  # and leave slots empty


def test_stall_watch_writes_down_a_step_that_hangs():
    import time

    from benchmarks.harness.traffic import serving_common as sc

    class Slow:
        max_batch = 1

        def __init__(self):
            self.n = 0

        def step(self):
            self.n += 1
            time.sleep(0.25 if self.n == 2 else 0.0)

        def active(self):
            return 1

        def queued(self):
            return 0

    watch = sc.StallWatch(after_s=0.1, every_s=0.02)
    watch.start()
    ticks, eng = sc.Ticks(), Slow()
    for _ in range(3):
        sc.step_once(eng, ticks, Phases(), False, time.perf_counter, watch)
    watch.close()
    assert not watch.is_alive() and len(watch.seen) == 1
    began, into, frames = watch.seen[0]
    assert began == pytest.approx(ticks.start[1], abs=0.01) and into >= 0.1
    assert any(f.endswith(" step") for f in frames)
    host = sc.window_host(eng, ticks, ticks.start[0], ticks.end[-1] + 1)
    assert host["longest_step_gap_ms"] >= 250
    assert host["longest_gap_in_step_ms"] == pytest.approx(
        1e3 * (ticks.end[1] - ticks.start[1]))
