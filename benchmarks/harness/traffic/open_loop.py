"""Open-loop serving traffic: requests are sent when they are due whether
or not earlier ones have finished, as independent users do.

Parameters (the traffic file): ``rate_per_s``; ``ramp_s`` of the same
traffic before the window (set-up: the batch is at its level when the
window opens); ``cooldown_s``, the longest the same traffic goes on after
the window so that no judged request decodes in a draining batch;
``prompt`` / ``output`` length distributions; ``ttft_limit_ms``;
``max_batch`` (the engine's rows); ``trace_s``; ``check_requests``.

Each stretch (ramp, window, tail) gets exactly rate x length requests
with stratified lengths (``lengths.py``), so every seed offers the same
tokens in another order. Arrivals are a Poisson process conditioned on
its count: sorted uniform times from the seed. The requests JUDGED are
those due inside the window. Latency runs from the instant a
request was due, never from ``submit()``. Every request generates exactly
its ``n_out`` tokens, greedy, so the work is fixed.
"""
from __future__ import annotations

import time
from typing import Dict, List

import numpy as np

from . import serving_common as sc
from .lengths import paired, seeded

TAIL_SLACK_S = 8.0     # room for the profiler to start and stop


def _stretch(rng, rate, t0, length, params, vocab, judged, out: List):
    n = int(round(rate * length))
    due = np.sort(rng.uniform(t0, t0 + length, n))
    p_len, o_len = paired(params["prompt"], params["output"], n, rng)
    for k in range(n):
        prompt = rng.integers(0, vocab, int(p_len[k]), dtype="int32")
        out.append(sc.Req(len(out), prompt, int(o_len[k]), float(due[k]),
                          judged))


def plan(params: Dict, seed: int, seconds: float, vocab: int) -> Dict:
    rng = seeded(seed, 2)
    rate, ramp = params["rate_per_s"], params["ramp_s"]
    tail = params["cooldown_s"] + params["trace_s"] + TAIL_SLACK_S
    reqs: List[sc.Req] = []
    _stretch(rng, rate, 0.0, ramp, params, vocab, False, reqs)
    _stretch(rng, rate, ramp, seconds, params, vocab, True, reqs)
    _stretch(rng, rate, ramp + seconds, tail, params, vocab, False, reqs)
    return {"requests": reqs, "ramp_s": ramp,
            "prompt_lens": sorted({len(r.prompt) for r in reqs})}


def run(system, plan_: Dict, seconds: float, phases,
        clock=time.perf_counter, sleep=time.sleep) -> Dict:
    params = system.traffic
    reqs: List[sc.Req] = plan_["requests"]
    ticks = sc.Ticks()
    by_rid: Dict[int, sc.Req] = {}
    t_start = clock()
    for r in reqs:
        r.due += t_start
    w_open = t_start + plan_["ramp_s"]
    w_close = w_open + seconds
    hard_stop = w_close + params["cooldown_s"]
    judged = [r for r in reqs if r.judged]
    state = {"next": 0, "open": False}
    watch = sc.StallWatch()

    def pump(until, log_rows=False, stop_when=None):
        """Send what is due, step, collect; until ``until`` or
        ``stop_when()``."""
        while True:
            now = clock()
            if now >= until or (stop_when is not None and stop_when()):
                return
            if not state["open"] and now >= w_open:
                phases.open_window(at=w_open)
                state["open"] = True
            i = state["next"]
            if i < len(reqs) and reqs[i].due <= now:
                with phases.span(sc.SUBMIT_SPAN):
                    while i < len(reqs) and reqs[i].due <= now:
                        r = reqs[i]
                        r.rid = system.submit(r.prompt, r.n_out)
                        r.t_submit = clock()
                        by_rid[r.rid] = r
                        i += 1
                state["next"] = i
            if system.busy():
                sc.step_once(system, ticks, phases, log_rows, clock, watch)
                sc.collect(system, by_rid, phases)
            else:
                nxt = reqs[i].due if i < len(reqs) else until
                sleep(max(0.0, min(nxt, until) - clock(), 0.0005))

    watch.start()
    try:
        pump(w_close)
        # the same traffic goes on until the last judged request is done
        pump(hard_stop, stop_when=lambda: all(r.done for r in judged))
    finally:
        watch.close()
    t_end = clock()
    phases.close_window()

    span = {}

    def traced_tail(trace_seconds: float):
        # arrivals that came due while the profiler started are dropped:
        # nothing judges them, and a burst is not this cell's traffic
        now = clock()
        while state["next"] < len(reqs) and reqs[state["next"]].due < now:
            state["next"] += 1
        span["lo"] = now
        pump(now + trace_seconds, log_rows=True)
        span["hi"] = clock()
        return len(ticks.inside(span["lo"], span["hi"]))

    phases.traced(traced_tail)

    done = [r for r in judged if r.done]
    limit = params["ttft_limit_ms"]
    late = [r for r in done if r.ttft_ms > limit]
    failed = (len(judged) - len(done)) + len(late)
    tpot = [r.tpot_ms for r in done]
    ttft = [r.ttft_ms for r in done]
    admit = system.admit_times()
    waits = [1e3 * (admit[r.rid] - r.due) for r in done if r.rid in admit]
    host = sc.window_host(system, ticks, w_open, w_close)
    host.update({
        "gen_late_p99_ms": sc.pct([1e3 * (r.t_submit - r.due)
                                   for r in judged if r.rid is not None], 99),
        "queue_wait_p90_ms": sc.pct(waits, 90),
        "ttft_p50_ms": sc.pct(ttft, 50), "ttft_p90_ms": sc.pct(ttft, 90),
        "ttft_max_ms": max(ttft) if ttft else None,
        "tpot_p90_ms.tail": sc.pct(tpot, 90),
        "cooldown_s": t_end - w_close,
    })
    if span:
        host.update(sc.traced_rows(ticks, span["lo"],
                                   span["hi"]))
    prefills = sum(1 for r in reqs if w_open <= r.t_first < w_close)
    return {
        "attempted": len(judged), "failed": failed,
        "metrics": {"tpot_p90_ms": sc.pct(tpot, 90),
                    "tpot_p50_ms": sc.pct(tpot, 50)},
        "host": host,
        "finished": done,
        "counts": {"judged": len(judged), "finished": len(done),
                   "ttft_over_limit": len(late),
                   "prefills_in_window": prefills,
                   "occupancy_pct": host.get("batch_occupancy"),
                   "longest_step_gap_ms": host.get("longest_step_gap_ms"),
                   "longest_gap_in_step_ms":
                       host.get("longest_gap_in_step_ms"),
                   "longest_gap_rows": host.get("longest_gap_rows"),
                   "stalled_steps": [[round(into, 3), frames]
                                     for _, into, frames in watch.seen[:3]],
                   "gen_late_p99_ms": host["gen_late_p99_ms"],
                   "ttft_max_ms": host["ttft_max_ms"],
                   "queue_depth_max": host.get("queue_depth_max"),
                   "queue_depth_halves": host.get("queue_depth_halves"),
                   "ttft_p50_ms": host["ttft_p50_ms"],
                   "ttft_p90_ms": host["ttft_p90_ms"],
                   "cooldown_s": host["cooldown_s"],
                   "tpot_p50_ms": sc.pct(tpot, 50),
                   "tpot_p90_ms": sc.pct(tpot, 90),
                   "tpot_max_ms": max(tpot) if tpot else None},
    }
