"""From a device trace to numbers. Pure functions over a :class:`Trace`
(plain lists, JSON round-trip), so the arithmetic is tested on a small
recorded trace with hand-worked values (``benchmarks/tests/test_reduce.py``)
and every PR computes the same number the same way. ``xplane.py`` turns
the profiler's ``.xplane.pb`` into a Trace; nothing here imports JAX.

All times are seconds on the trace's own clock. ``window`` is the span
the benchmark put around the traced work (``bench/trace_window``); every
reduction clips to it.
"""
from __future__ import annotations

import json
import re
from dataclasses import asdict, dataclass, field
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

Interval = Tuple[float, float]

# HLO ops that move data between chips. "-start"/"-done" halves of an
# async collective both count: the wire is busy from start to done.
COLLECTIVE = re.compile(
    r"^(all-reduce|all-gather|reduce-scatter|all-to-all|"
    r"collective-permute|collective-broadcast|send|recv)")


@dataclass
class Op:
    name: str            # HLO op name as traced, e.g. "fusion.123"
    start: float
    dur: float
    device: int
    program: str = ""    # the XLA module (jitted program) it ran in
    detail: str = ""     # result type and shape; "mosaic" for a Pallas call
    overlapped: bool = False   # from the "Async XLA Ops" line: the span
    #                            from an async op's start to its done,
    #                            during which other ops run


@dataclass
class Span:
    name: str            # host span, e.g. "bench/eng.step"
    start: float
    dur: float


@dataclass
class Trace:
    ops: List[Op] = field(default_factory=list)
    spans: List[Span] = field(default_factory=list)
    window: Interval = (0.0, 0.0)

    def to_json(self) -> str:
        return json.dumps({"window": list(self.window),
                           "ops": [asdict(o) for o in self.ops],
                           "spans": [asdict(s) for s in self.spans]})

    @classmethod
    def from_json(cls, text: str) -> "Trace":
        d = json.loads(text)
        return cls([Op(**o) for o in d["ops"]],
                   [Span(**s) for s in d["spans"]], tuple(d["window"]))

    @property
    def window_s(self) -> float:
        return self.window[1] - self.window[0]

    @property
    def devices(self) -> List[int]:
        return sorted({o.device for o in self.ops})


def is_collective(op: "Op") -> bool:
    """By the op's name or by its opcode (kept in the detail)."""
    return bool(COLLECTIVE.match(op.name)) or any(
        COLLECTIVE.match(w) for w in op.detail.split())


# -- interval arithmetic ---------------------------------------------------
def clip(iv: Iterable[Interval], lo: float, hi: float) -> List[Interval]:
    out = []
    for a, b in iv:
        a, b = max(a, lo), min(b, hi)
        if b > a:
            out.append((a, b))
    return out


def union(iv: Iterable[Interval]) -> List[Interval]:
    """Merged, sorted, non-overlapping intervals."""
    out: List[Interval] = []
    for a, b in sorted(iv):
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1] = (out[-1][0], b)
        else:
            out.append((a, b))
    return out


def total(iv: Iterable[Interval]) -> float:
    return sum(b - a for a, b in iv)


def subtract(iv: Sequence[Interval], cut: Sequence[Interval]
             ) -> List[Interval]:
    """Parts of ``iv`` (merged) not covered by ``cut`` (merged)."""
    out = []
    cut = list(cut)
    for a, b in iv:
        cur = a
        for c, d in cut:
            if d <= cur:
                continue
            if c >= b:
                break
            if c > cur:
                out.append((cur, c))
            cur = max(cur, d)
            if cur >= b:
                break
        if cur < b:
            out.append((cur, b))
    return out


def _intervals(ops: Iterable[Op]) -> List[Interval]:
    return [(o.start, o.start + o.dur) for o in ops]


# -- selections ---------------------------------------------------------------
def matches(op: Op, names: Sequence[str]) -> bool:
    """An op is a kernel's if the kernel's stable name is in the op's
    name or in its traced detail (Mosaic custom calls carry the
    ``pallas_call`` name there)."""
    return any(n in op.name or n in op.detail for n in names)


def select(trace: Trace, names: Optional[Sequence[str]] = None,
           program: Optional[str] = None,
           device: Optional[int] = None,
           overlapped: bool = False) -> List[Op]:
    """Ops inside the window. The ops that ran one after another on the
    device by default; ``overlapped=True`` gives the async spans instead
    (they overlap the others and never count as busy time)."""
    lo, hi = trace.window
    out = []
    for o in trace.ops:
        if o.overlapped != overlapped:
            continue
        if o.start + o.dur <= lo or o.start >= hi:
            continue
        if device is not None and o.device != device:
            continue
        if program is not None and program not in o.program:
            continue
        if names is not None and not matches(o, names):
            continue
        out.append(o)
    return out


def op_seconds(ops: Iterable[Op], window: Interval) -> float:
    """Sum of the ops' durations, each clipped to the window."""
    return total(clip(_intervals(ops), *window))


# -- the reductions -----------------------------------------------------------
def busy_by_device(trace: Trace) -> Dict[int, float]:
    """Seconds in which some op ran, per device: the union of its op
    intervals inside the window."""
    return {d: total(union(clip(_intervals(select(trace, device=d)),
                                *trace.window)))
            for d in trace.devices}


def busy_seconds(trace: Trace) -> float:
    """Mean over the devices used (the contract's ``busy_s``)."""
    b = busy_by_device(trace)
    return sum(b.values()) / len(b) if b else 0.0


def idle_share(trace: Trace) -> Optional[float]:
    """1 - busy/window on the WORST device, in percent."""
    b = busy_by_device(trace)
    if not b or trace.window_s <= 0:
        return None
    return 100.0 * (1.0 - min(b.values()) / trace.window_s)


def time_share(trace: Trace, names: Sequence[str],
               program: Optional[str] = None) -> Optional[float]:
    """Device time of the matching ops over device busy time, percent,
    summed over devices."""
    busy = sum(busy_by_device(trace).values())
    if busy <= 0:
        return None
    return 100.0 * op_seconds(select(trace, names, program),
                              trace.window) / busy


def program_share(trace: Trace, program: str) -> Optional[float]:
    """Device time of every op inside the matching programs over device
    busy time, percent."""
    busy = sum(busy_by_device(trace).values())
    if busy <= 0:
        return None
    return 100.0 * op_seconds(select(trace, None, program),
                              trace.window) / busy


def exposed_collective_share(trace: Trace) -> Optional[float]:
    """Time in collective ops during which no compute op ran on that
    device, over the window, worst device, percent. None when the trace
    holds no collective (a one-chip cell)."""
    worst = None
    for d in trace.devices:
        ops = select(trace, device=d)
        coll = [o for o in ops + select(trace, device=d, overlapped=True)
                if is_collective(o)]
        if not coll:
            continue
        comp = [o for o in ops if not is_collective(o)]
        c_iv = union(clip(_intervals(coll), *trace.window))
        k_iv = union(clip(_intervals(comp), *trace.window))
        share = 100.0 * total(subtract(c_iv, k_iv)) / trace.window_s
        worst = share if worst is None else max(worst, share)
    return worst


_NUM = re.compile(r"[._]+\d+$")


def group_name(op: Op) -> str:
    """Name a breakdown row groups by: the op's name without its
    numeric suffix, then its result's type and shape, as in
    ``copy_bf16_512_8_128_128`` (the ledger's spelling, PR 22)."""
    base = _NUM.sub("", op.name).rstrip("_.")
    shape = re.sub(r"[^A-Za-z0-9]+", "_", op.detail).strip("_")
    return f"{base}_{shape}" if shape else base


def top_ops(trace: Trace, n: int = 10) -> List[List]:
    """[name, seconds] of the device ops that took most time (summed
    over devices, divided by their number), largest first."""
    acc: Dict[str, float] = {}
    lo, hi = trace.window
    for o in select(trace):
        a, b = max(o.start, lo), min(o.start + o.dur, hi)
        acc[group_name(o)] = acc.get(group_name(o), 0.0) + (b - a)
    nd = max(len(trace.devices), 1)
    rows = sorted(acc.items(), key=lambda kv: -kv[1])[:n]
    return [[k, v / nd] for k, v in rows]


def idle_gaps(trace: Trace, n: int = 10) -> List[List]:
    """[host span, seconds]: the idle time of the worst device inside the
    window, each gap charged to the innermost benchmark span that was
    open on the host while it lasted ("(no span)" if none)."""
    b = busy_by_device(trace)
    if not b:
        return []
    dev = min(b, key=b.get)
    busy = union(clip(_intervals(select(trace, device=dev)),
                      *trace.window))
    gaps = subtract([trace.window], busy)
    # innermost = latest-starting span that covers the instant
    spans = sorted(trace.spans, key=lambda s: s.start)
    acc: Dict[str, float] = {}
    for a, c in gaps:
        cuts = sorted({a, c} | {t for s in spans
                                for t in (s.start, s.start + s.dur)
                                if a < t < c})
        for x, y in zip(cuts, cuts[1:]):
            mid = (x + y) / 2
            owner = "(no span)"
            for s in spans:
                if s.start <= mid < s.start + s.dur:
                    owner = s.name
            acc[owner] = acc.get(owner, 0.0) + (y - x)
    rows = sorted(acc.items(), key=lambda kv: -kv[1])[:n]
    return [[k, v] for k, v in rows]
