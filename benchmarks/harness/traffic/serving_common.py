"""What the open and the closed serving loops share: the request record,
the tick log, and the reduction of both to numbers.

The system under test offers (``families/llama_serving.py``):
``submit(prompt, n_out) -> rid``, ``step()``, ``busy()``,
``pop_finished() -> [(rid, t_first_token, t_finish, tokens)]``,
``active()``, ``queued()``, ``max_batch``, ``decode_rows() -> [kv_len]``,
``admit_times() -> {rid: t}``. All times are ``time.perf_counter()``.
"""
from __future__ import annotations

import sys
import threading
import time
import traceback
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

import numpy as np

STEP_SPAN = "bench/eng.step"
SUBMIT_SPAN = "bench/loadgen.submit"
COLLECT_SPAN = "bench/collect"


@dataclass
class Req:
    idx: int
    prompt: np.ndarray
    n_out: int
    due: float = 0.0                  # absolute, perf_counter clock
    judged: bool = False
    rid: Optional[int] = None
    t_submit: float = 0.0
    t_first: float = 0.0
    t_finish: float = 0.0
    tokens: Optional[np.ndarray] = None

    @property
    def done(self) -> bool:
        return self.tokens is not None

    @property
    def tpot_ms(self) -> float:
        return 1e3 * (self.t_finish - self.t_first) / (len(self.tokens) - 1)

    @property
    def ttft_ms(self) -> float:
        return 1e3 * (self.t_first - self.due)


@dataclass
class Ticks:
    """One row per engine step: when it started and ended, rows active
    and requests queued after it, and (traced phase only) the context
    length of every row that decoded in it."""
    start: List[float] = field(default_factory=list)
    end: List[float] = field(default_factory=list)
    active: List[int] = field(default_factory=list)
    queued: List[int] = field(default_factory=list)
    rows: Dict[int, List[int]] = field(default_factory=dict)

    def inside(self, lo: float, hi: float) -> List[int]:
        return [i for i, t in enumerate(self.start) if lo <= t < hi]


class StallWatch(threading.Thread):
    """Whose stall it is: a thread that sleeps, and writes down the main
    thread's stack once for every engine step that is still running
    ``after_s`` seconds after it began (a decode step takes a tenth of
    that). ``seen`` holds (when the step began, seconds into it,
    innermost frames)."""

    def __init__(self, after_s: float = 0.5, every_s: float = 0.05,
                 frames: int = 6):
        super().__init__(daemon=True)
        self.after_s, self.every_s, self.frames = after_s, every_s, frames
        self.began: Optional[float] = None     # time.perf_counter()
        self.seen: List[Tuple[float, float, List[str]]] = []
        self._main = threading.main_thread().ident
        self._halt = threading.Event()

    def run(self) -> None:
        noted = None
        while not self._halt.wait(self.every_s):
            began = self.began
            if began is None or began == noted:
                continue
            into = time.perf_counter() - began
            if into >= self.after_s:
                stack = traceback.extract_stack(
                    sys._current_frames().get(self._main))
                self.seen.append((began, into, [
                    f"{f.filename.rsplit('/', 1)[-1]}:{f.lineno} {f.name}"
                    for f in stack[-self.frames:]]))
                noted = began

    def close(self) -> None:
        self._halt.set()
        self.join()


def step_once(system, ticks: Ticks, phases, log_rows: bool, clock,
              watch: Optional[StallWatch] = None) -> None:
    if log_rows:
        ticks.rows[len(ticks.start)] = system.decode_rows()
    ticks.start.append(clock())
    if watch is not None:
        watch.began = time.perf_counter()
    with phases.span(STEP_SPAN):
        system.step()
    if watch is not None:
        watch.began = None
    ticks.end.append(clock())
    ticks.active.append(system.active())
    ticks.queued.append(system.queued())


def collect(system, by_rid: Dict[int, Req], phases) -> List[Req]:
    out = []
    with phases.span(COLLECT_SPAN):
        for rid, t_first, t_finish, tokens in system.pop_finished():
            r = by_rid.pop(rid, None)
            if r is None:
                continue
            r.t_first, r.t_finish, r.tokens = t_first, t_finish, tokens
            out.append(r)
    return out


def pct(values, q: float) -> Optional[float]:
    return float(np.percentile(values, q)) if len(values) else None


def window_host(system, ticks: Ticks, lo: float, hi: float) -> Dict:
    """Host-side readings of the ticks that started inside [lo, hi)."""
    idx = ticks.inside(lo, hi)
    if not idx:
        return {"ticks": 0}
    act = [ticks.active[i] for i in idx]
    starts = [ticks.start[i] for i in idx]
    gaps = np.diff(starts) if len(starts) > 1 else np.array([0.0])
    q = [ticks.queued[i] for i in idx]
    half = len(q) // 2
    # the longest gap between two steps' starts, and how much of it was
    # spent inside the step (the program's) and after it (the loop's)
    k = idx[int(gaps.argmax())]
    return {"ticks": len(idx),
            "batch_occupancy": 100.0 * float(np.mean(act))
            / system.max_batch,
            "longest_step_gap_ms": 1e3 * float(gaps.max()),
            "longest_gap_in_step_ms": 1e3 * (ticks.end[k] - ticks.start[k]),
            "longest_gap_rows": [ticks.active[k - 1] if k else 0,
                                 ticks.active[k]],
            "queue_depth_max": max(q),
            "queue_depth_halves": [float(np.mean(q[:half] or [0])),
                                   float(np.mean(q[half:]))]}


def traced_rows(ticks: Ticks, lo: float, hi: float) -> Dict:
    """The attention work of the traced stretch, as (q_len, kv_len)
    rows: one per decoding row per step."""
    decode = []
    for i in ticks.inside(lo, hi):
        decode.extend((1, kv) for kv in ticks.rows.get(i, ()))
    return {"decode_rows": decode}
