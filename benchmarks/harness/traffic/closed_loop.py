"""Closed-loop serving traffic: ``clients`` callers, each sending its next
request the moment its last one completes, so the queue is never empty
(offline batches: summarisation, classification, retrieval).

Parameters (the traffic file): ``clients``, ``max_batch``, ``ramp_s`` of
the same traffic before the window (set-up), ``prompt`` / ``output``
length distributions, ``cycle`` (requests per stratified cycle),
``trace_s``, ``check_requests``. The request stream is a sequence of
cycles; each cycle holds the ``cycle`` evenly spaced quantiles of both
distributions, paired and ordered from the seed, so any long stretch of
the stream carries the same mix of lengths whatever the seed.

``served_tokens_per_s`` = prompt plus generated tokens of the requests
completed in the window, over the time from the window's opening to the
last completion in it (a request cut by the window's end costs no step
in the number).
"""
from __future__ import annotations

import time
from typing import Dict, List

from . import serving_common as sc
from .lengths import paired, seeded


class _Stream:
    """Requests on demand, cycle by cycle, from the seed."""

    def __init__(self, params: Dict, seed: int, vocab: int):
        self.params, self.vocab = params, vocab
        self.rng = seeded(seed, 3)
        self.buf: List[sc.Req] = []
        self.n = 0

    def next(self) -> sc.Req:
        if not self.buf:
            c = self.params["cycle"]
            p, o = paired(self.params["prompt"], self.params["output"], c,
                          self.rng)
            for k in range(c):
                prompt = self.rng.integers(0, self.vocab, int(p[k]),
                                           dtype="int32")
                self.buf.append(sc.Req(self.n + k, prompt, int(o[k])))
            self.n += c
            self.buf.reverse()
        return self.buf.pop()


def plan(params: Dict, seed: int, seconds: float, vocab: int) -> Dict:
    return {"stream": _Stream(params, seed, vocab),
            "prompt_lens": [params["prompt"]["min"],
                            params["prompt"]["max"]]}


def run(system, plan_: Dict, seconds: float, phases,
        clock=time.perf_counter) -> Dict:
    params = system.traffic
    stream: _Stream = plan_["stream"]
    ticks = sc.Ticks()
    by_rid: Dict[int, sc.Req] = {}
    all_reqs: List[sc.Req] = []
    finished: List[sc.Req] = []

    def send():
        with phases.span(sc.SUBMIT_SPAN):
            r = stream.next()
            r.due = r.t_submit = clock()
            r.rid = system.submit(r.prompt, r.n_out)
            by_rid[r.rid] = r
            all_reqs.append(r)

    def pump(until, log_rows=False):
        while clock() < until:
            sc.step_once(system, ticks, phases, log_rows, clock)
            for r in sc.collect(system, by_rid, phases):
                finished.append(r)
                send()                       # that client's next request

    for _ in range(params["clients"]):
        send()
    t_start = clock()
    w_open = t_start + params["ramp_s"]
    pump(w_open)
    phases.open_window(at=w_open)
    w_close = w_open + seconds
    pump(w_close)
    phases.close_window()

    span = {}

    def traced_tail(trace_seconds: float):
        span["lo"] = clock()
        pump(span["lo"] + trace_seconds, log_rows=True)
        span["hi"] = clock()
        return len(ticks.inside(span["lo"], span["hi"]))

    phases.traced(traced_tail)

    done = [r for r in finished if w_open <= r.t_finish < w_close]
    tokens = sum(len(r.prompt) + len(r.tokens) for r in done)
    last = max((r.t_finish for r in done), default=w_open)
    host = sc.window_host(system, ticks, w_open, w_close)
    if span:
        host.update(sc.traced_rows(ticks, span["lo"],
                                   span["hi"]))
    short = [r for r in done if len(r.tokens) != r.n_out]
    return {
        "attempted": len(done), "failed": len(short),
        "metrics": {"served_tokens_per_s":
                    tokens / (last - w_open) if done else None},
        "host": host,
        "finished": done,
        "counts": {"completed": len(done), "tokens": tokens,
                   "span_s": last - w_open,
                   "occupancy_pct": host.get("batch_occupancy"),
                   "longest_step_gap_ms": host.get("longest_step_gap_ms"),
                   "queue_depth_max": host.get("queue_depth_max")},
    }
