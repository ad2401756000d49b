"""Training traffic: optimizer steps back to back for the window, each
ending in a wait for the device. Parameters (the traffic file):
``batch`` sequences of ``seq`` tokens per step (for a pipeline also
``micro_batch``), ``distinct`` different batches cycled in order; token
ids are uniform over the vocabulary, from the seed.

The system under test offers ``step(i) -> loss`` (a float: the call
waits for the device) and ``tokens_per_step``.
"""
from __future__ import annotations

import statistics
import time
from typing import Dict

from .lengths import seeded

NAME_SPAN = "bench/train.step"


def plan(params: Dict, seed: int, seconds: float, vocab: int) -> Dict:
    rng = seeded(seed, 1)
    ids = rng.integers(0, vocab, (params["distinct"], params["batch"],
                                  params["seq"] + 1), dtype="int32")
    return {"ids": ids, "batch": params["batch"], "seq": params["seq"],
            "micro_batch": params.get("micro_batch")}


def run(system, plan_: Dict, seconds: float, phases) -> Dict:
    """phases.open_window() ends set-up; phases.span(name) marks a host
    span; phases.traced(fn) runs fn under the profiler when tracing."""
    i = system.steps_done
    phases.open_window()
    t0 = time.perf_counter()
    ends = []
    losses = []
    while True:
        with phases.span(NAME_SPAN):
            losses.append(system.step(i))
        i += 1
        now = time.perf_counter()
        ends.append(now)
        if now - t0 >= seconds:
            break
    elapsed = ends[-1] - t0
    phases.close_window()
    n = len(ends)
    tokens = n * system.tokens_per_step
    durs = [b - a for a, b in zip([t0] + ends[:-1], ends)]

    def traced_steps(trace_seconds: float):
        t = time.perf_counter()
        k = 0
        while time.perf_counter() - t < trace_seconds or k < 2:
            with phases.span(NAME_SPAN):
                system.step(i + k)
            k += 1
        return k

    k = phases.traced(traced_steps)
    finite = all(x == x and abs(x) != float("inf") for x in losses)
    return {
        "attempted": n, "failed": 0 if finite else n,
        "metrics": {
            "train_tokens_per_s_chip": tokens / elapsed / system.n_chips,
        },
        "host": {
            "step_ms": 1e3 * statistics.median(durs),
            "steps": n, "window_s": elapsed, "traced_steps": k or 0,
            "last_loss": losses[-1], "first_loss": losses[0],
        },
        "counts": {"steps": n, "tokens": tokens,
                   "step_ms_min": 1e3 * min(durs),
                   "step_ms_max": 1e3 * max(durs),
                   "loss_first": losses[0], "loss_last": losses[-1]},
    }
