"""Profiler (paddle.profiler analog).

(reference: python/paddle/profiler/profiler.py:79,99 — Profiler with
states/targets, export_chrome_tracing:215, RecordEvent host events,
profiler_statistic.py summaries; C++ host tracer
fluid/platform/profiler/host_tracer.cc + CUPTI cuda_tracer.)

TPU-native: the device side is the XLA/TPU profiler (xplane) reached
through ``jax.profiler`` — traces open in TensorBoard/Perfetto, covering
what CUPTI covered. The host side is a lightweight in-process event
recorder (RecordEvent) feeding ``summary()`` and the chrome-trace
exporter, the host_tracer role; a RecordEvent also reaches the xplane
as a ``TraceAnnotation`` of its name.
"""
from __future__ import annotations

import contextlib
import json
import os
import threading
import time
from enum import Enum
from typing import Callable, List, Optional, Tuple

__all__ = ["Profiler", "ProfilerTarget", "ProfilerState", "RecordEvent",
           "export_chrome_tracing", "make_scheduler", "load_profiler_result"]


class ProfilerTarget(Enum):
    CPU = 0
    GPU = 1
    XPU = 2
    CUSTOM_DEVICE = 3
    TPU = 4


class ProfilerState(Enum):
    CLOSED = 0
    READY = 1
    RECORD = 2
    RECORD_AND_RETURN = 3


# event rows: (name, t0, t1, category, thread-id, thread-name). The
# thread id feeds the chrome exporter's `tid` so ServingEngine worker
# threads and the watchdog monitor thread separate into lanes.
_events: List[Tuple[str, float, float, str, int, str]] = []
_events_lock = threading.Lock()
_active = 0


def _append_event(name: str, t0: float, t1: float, cat: str):
    th = threading.current_thread()
    with _events_lock:
        _events.append((name, t0, t1, cat, th.ident or 0, th.name))


class RecordEvent:
    """Host-side named range (reference profiler/utils.py RecordEvent).
    While it is open it also holds a ``jax.profiler.TraceAnnotation`` of
    the same name, so under a device trace (``jax.profiler.start_trace``,
    or a ``Profiler`` that is not ``timer_only``) an operator's own
    ranges land in the same ``.xplane.pb`` as the engines' spans
    (``observability/trace.py::span``); with no session open that costs
    about a microsecond and records nothing."""

    def __init__(self, name: str, event_type: str = "UserDefined"):
        self.name = name
        self.event_type = event_type
        self._t0 = None
        self._ann = None

    def begin(self):
        import jax

        self._ann = jax.profiler.TraceAnnotation(self.name)
        self._ann.__enter__()
        self._t0 = time.perf_counter()

    def end(self):
        ann, self._ann = self._ann, None
        if ann is not None:
            ann.__exit__(None, None, None)
        if self._t0 is None or not _active:
            return
        _append_event(self.name, self._t0, time.perf_counter(),
                      self.event_type)
        self._t0 = None

    def __enter__(self):
        self.begin()
        return self

    def __exit__(self, *exc):
        self.end()


@contextlib.contextmanager
def _op_record(name: str):
    t0 = time.perf_counter()
    try:
        yield
    finally:
        # same `_active` gate as RecordEvent.end: an unstarted (or
        # already-stopped) profiler must not grow the global event list
        if _active:
            _append_event(name, t0, time.perf_counter(), "Operator")


def make_scheduler(*, closed: int, ready: int, record: int, repeat: int = 0,
                   skip_first: int = 0) -> Callable[[int], ProfilerState]:
    """(reference profiler.py make_scheduler) step → state."""
    period = closed + ready + record

    def schedule(step: int) -> ProfilerState:
        if step < skip_first:
            return ProfilerState.CLOSED
        s = step - skip_first
        if repeat and s >= repeat * period:
            return ProfilerState.CLOSED
        pos = s % period
        if pos < closed:
            return ProfilerState.CLOSED
        if pos < closed + ready:
            return ProfilerState.READY
        if pos == period - 1:
            return ProfilerState.RECORD_AND_RETURN
        return ProfilerState.RECORD

    return schedule


def export_chrome_tracing(dir_name: str, worker_name: Optional[str] = None):
    """on_trace_ready callback writing chrome://tracing json
    (reference profiler.py:215)."""

    def handler(prof: "Profiler"):
        os.makedirs(dir_name, exist_ok=True)
        fname = f"{worker_name or 'worker'}_{os.getpid()}.pt.trace.json"
        prof._export_chrome(os.path.join(dir_name, fname))

    return handler


class Profiler:
    """paddle.profiler.Profiler analog.

    ``timer_only=True`` records host events only; otherwise the XLA/TPU
    device trace runs too (``jax.profiler``), written to ``log_dir`` for
    TensorBoard. ``scheduler`` is (start, end) step bounds or a
    make_scheduler callable.
    """

    def __init__(self, *, targets=None, scheduler=None,
                 on_trace_ready=None, timer_only: bool = False,
                 record_shapes: bool = False, profile_memory: bool = False,
                 log_dir: str = "./profiler_log"):
        self.timer_only = timer_only
        self.log_dir = log_dir
        self.on_trace_ready = on_trace_ready
        if isinstance(scheduler, tuple):
            lo, hi = scheduler
            scheduler = make_scheduler(closed=lo, ready=0, record=hi - lo,
                                       repeat=1)
        self.scheduler = scheduler
        self.step_num = 0
        self._state = ProfilerState.CLOSED
        self._device_tracing = False
        self._step_times: List[float] = []
        # (interval seconds, samples) pairs from step(num_samples=...)
        self._samples: List[Tuple[float, float]] = []
        self._last_step_t = None

    # -- lifecycle ------------------------------------------------------
    def start(self):
        global _active
        _active += 1
        if _active == 1:
            # only the OUTERMOST profiler resets the global recorder: a
            # nested start must neither clear the outer run's events nor
            # (on its stop) tear the dispatch hook out from under it
            with _events_lock:
                _events.clear()
            from ..core import dispatch as _dispatch

            _dispatch._profile_hook = _op_record
        self._state = (self.scheduler(self.step_num)
                       if self.scheduler else ProfilerState.RECORD)
        self._maybe_device(True)
        self._last_step_t = time.perf_counter()

    def stop(self):
        global _active
        self._maybe_device(False)
        _active = max(0, _active - 1)
        if _active == 0:
            from ..core import dispatch as _dispatch

            _dispatch._profile_hook = None
        if self.on_trace_ready:
            self.on_trace_ready(self)

    def _maybe_device(self, start: bool):
        if self.timer_only:
            return
        try:
            import jax

            if start and not self._device_tracing and \
                    self._state in (ProfilerState.RECORD,
                                    ProfilerState.RECORD_AND_RETURN):
                jax.profiler.start_trace(self.log_dir)
                self._device_tracing = True
            elif not start and self._device_tracing:
                jax.profiler.stop_trace()
                self._device_tracing = False
        except Exception:
            self._device_tracing = False

    def step(self, num_samples: Optional[int] = None):
        now = time.perf_counter()
        if self._last_step_t is not None:
            dur = now - self._last_step_t
            self._step_times.append(dur)
            if num_samples:
                # throughput accounting (reference profiler.py ips):
                # num_samples processed over the interval just ended
                self._samples.append((dur, float(num_samples)))
        self._last_step_t = now
        self.step_num += 1
        if self.scheduler:
            new = self.scheduler(self.step_num)
            if new != self._state:
                old, self._state = self._state, new
                if new in (ProfilerState.RECORD,
                           ProfilerState.RECORD_AND_RETURN):
                    self._maybe_device(True)
                elif old in (ProfilerState.RECORD,
                             ProfilerState.RECORD_AND_RETURN):
                    self._maybe_device(False)

    def __enter__(self):
        self.start()
        return self

    def __exit__(self, *exc):
        self.stop()

    # -- reporting ------------------------------------------------------
    def summary(self, sorted_by=None, op_detail: bool = True,
                thread_sep: bool = False, time_unit: str = "ms") -> str:
        unit = {"s": 1.0, "ms": 1e3, "us": 1e6}[time_unit]
        agg = {}
        with _events_lock:
            for name, t0, t1, *_ in _events:
                tot, cnt = agg.get(name, (0.0, 0))
                agg[name] = (tot + (t1 - t0), cnt + 1)
        lines = [f"{'Name':<40} {'Calls':>8} {'Total(' + time_unit + ')':>14}"
                 f" {'Avg(' + time_unit + ')':>12}"]
        for name, (tot, cnt) in sorted(agg.items(),
                                       key=lambda kv: -kv[1][0]):
            lines.append(f"{name[:40]:<40} {cnt:>8} {tot * unit:>14.3f} "
                         f"{tot * unit / cnt:>12.3f}")
        if self._step_times:
            import numpy as np

            st = np.asarray(self._step_times)
            lines.append(f"steps: {len(st)}  avg "
                         f"{st.mean() * unit:.3f}{time_unit}  p50 "
                         f"{np.percentile(st, 50) * unit:.3f}  p99 "
                         f"{np.percentile(st, 99) * unit:.3f}")
        if self._samples:
            tot_t = sum(d for d, _ in self._samples)
            tot_n = sum(n for _, n in self._samples)
            ips = tot_n / tot_t if tot_t > 0 else 0.0
            lines.append(f"throughput: {ips:.2f} ips "
                         f"({int(tot_n)} samples / {tot_t:.3f}s)")
        out = "\n".join(lines)
        print(out)
        return out

    def _export_chrome(self, path: str):
        with _events_lock:
            evs = list(_events)
        base = min((e[1] for e in evs), default=0.0)
        pid = os.getpid()
        events = []
        lanes = {}                  # tid -> thread name (first seen)
        for name, t0, t1, cat, tid, tname in evs:
            lanes.setdefault(tid, tname)
            events.append(
                {"name": name, "ph": "X", "pid": pid, "tid": tid,
                 "ts": (t0 - base) * 1e6, "dur": (t1 - t0) * 1e6,
                 "cat": cat})
        # chrome://tracing / Perfetto label each lane from thread_name
        # metadata — serving workers and the watchdog monitor get their
        # python thread names
        for tid, tname in lanes.items():
            events.append({"name": "thread_name", "ph": "M", "pid": pid,
                           "tid": tid, "args": {"name": tname}})
        with open(path, "w") as f:
            json.dump({"traceEvents": events}, f)

    export = _export_chrome


def load_profiler_result(path: str):
    with open(path) as f:
        return json.load(f)
