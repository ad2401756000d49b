"""The program's OWN host spans and scopes, read from the trace the
harness already took: what ``xplane.load`` leaves out.

``paddle_tpu/observability/trace.py::span`` writes the engines' phases
into the profiler's file as host events named ``paddle_tpu/<name>``
(``serving.step``, ``serving.prefill.dispatch``, ``train.dispatch``,
...) with their fields as stats, on the device trace's clock; the
compiled train step carries four scopes (``forward``, ``backward``,
``grad_sync``, ``optimizer``) in the XLA ``op_name`` of its ops, which a
TPU trace keeps as the ``tf_op`` stat of an op's event METADATA
(``jit(flat_step)/forward/gpt/embed/jit(_take)/gather:``; read on the
chip, PR 37). ``jax.profiler.ProfileData`` shows an event's own stats
and not its metadata's, so ``op_names`` reads that one stat from the
file's protobuf wire format by hand (field numbers of
``tsl/profiler/protobuf/xplane.proto``; no other package is imported).
``xplane.load`` keeps ``bench/`` host events only and drops an op's
metadata, and a reducer's ``ctx`` holds no path to the file: this module
finds the file where the runner put it (``<root>/TRACE_DIR``, as
``tools/describe_trace.py`` does), checks that it is THIS run's (its
``bench/trace_window`` is ``ctx["trace"].window``), and gives

- ``ProgramSpan``: a ``reduce.Span`` plus ``fields`` and the host line,
- ``for_run(ctx)``: a ``reduce.Trace`` with the run's ops and window
  whose spans are the program's, so the arithmetic below and
  ``reduce.idle_gaps`` apply to it unchanged,
- ``scoped(ctx)``: a ``reduce.Trace`` whose ops carry their scope in
  ``program``, so ``reduce.program_share(trace, "forward")`` is the
  scope's share of busy time.

A tree without the spans (this PR's parent) gives None everywhere and
nothing raises. Everything under "arithmetic" is pure over
``reduce.Trace`` and tested on hand-built traces
(``benchmarks/tests/test_program_spans.py``).
"""
from __future__ import annotations

import os
import statistics
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Sequence, Tuple

from . import reduce as R
from .runner import TRACE_DIR
from .xplane import WINDOW_SPAN, _DEVICE, find_xplane, leaf_ops, parse_op

PREFIX = "paddle_tpu/"
SCOPES = ("forward", "backward", "grad_sync", "optimizer")
NO_SPAN = "(no span)"       # reduce.idle_gaps' name for it


@dataclass
class ProgramSpan(R.Span):
    fields: Dict[str, Any] = field(default_factory=dict)
    line: str = ""          # the host thread (trace line) it ran on


@dataclass
class Program:
    """What one ``.xplane.pb`` holds of the program's own names."""
    spans: List[ProgramSpan] = field(default_factory=list)
    window: Optional[R.Interval] = None
    # leaf device ops with their scope ("" where the op names none) in
    # ``program``; None until a scope reducer asks (``scoped``)
    scoped_ops: Optional[List[R.Op]] = None
    # (device, program name, start, dur) of every program run
    modules: List[Tuple[int, str, float, float]] = field(
        default_factory=list)


# -- reading the file ---------------------------------------------------------
def scope_of(op_name: Optional[str]) -> str:
    """The scope an op ran under, from its XLA ``op_name`` as a trace
    gives it (``tf_op``: ``<op_name>:<type>``): the FIRST path component
    that is one of ``SCOPES``, "" where there is none.
    ``jit(step)/backward/transpose(jvp(forward))/dot`` is ``backward``:
    a component must equal the name, not hold it."""
    for part in (op_name or "").split("/"):
        if part in SCOPES:
            return part
    return ""


# -- the one stat ProfileData hides: an op's ``tf_op`` ------------------------
def _varint(buf, i: int) -> Tuple[int, int]:
    n = shift = 0
    while True:
        b = buf[i]
        i += 1
        n |= (b & 0x7F) << shift
        if not b & 0x80:
            return n, i
        shift += 7


def _fields(buf):
    """(field number, value) of one protobuf message: an int for a
    varint, a memoryview for a length-delimited or fixed-width field."""
    i = 0
    while i < len(buf):
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            v, i = _varint(buf, i)
        else:
            if wire == 2:
                n, i = _varint(buf, i)
            elif wire in (1, 5):
                n = 8 if wire == 1 else 4
            else:
                raise ValueError(f"wire type {wire} at byte {i}")
            v, i = buf[i:i + n], i + n
        yield key >> 3, v


def _map_value(entry) -> Tuple[int, Any]:
    """A ``map<int64, Message>`` entry: (key, the message's bytes)."""
    d = dict(_fields(entry))
    return d.get(1, 0), d.get(2, b"")


def op_names(data: bytes) -> Dict[str, str]:
    """``{event name: tf_op}`` over the device planes of a serialized
    ``XSpace``: XSpace.planes = 1; XPlane.name = 2, .event_metadata = 4,
    .stat_metadata = 5; XEventMetadata.name = 2, .stats = 5;
    XStatMetadata.name = 2; XStat.metadata_id = 1, .str_value = 5,
    .ref_value = 7 (an index into stat_metadata, whose NAME is the
    string). An event's name is its HLO instruction's text, unique in
    its program."""
    out: Dict[str, str] = {}
    for f, plane in _fields(memoryview(data)):
        if f != 1:
            continue
        name, events, stat_names = "", [], {}
        for g, v in _fields(plane):
            if g == 2:
                name = bytes(v).decode()
            elif g == 4:
                events.append(_map_value(v)[1])
            elif g == 5:
                k, meta = _map_value(v)
                stat_names[k] = bytes(
                    dict(_fields(meta)).get(2, b"")).decode()
        if not _DEVICE.match(name):
            continue
        for ev in events:
            ev_name = op = None
            for g, v in _fields(ev):
                if g == 2:
                    ev_name = bytes(v).decode()
                elif g == 5:
                    st = dict(_fields(v))
                    if stat_names.get(st.get(1)) == "tf_op":
                        op = bytes(st[5]).decode() if 5 in st \
                            else stat_names.get(st.get(7), "")
            if ev_name and op:
                out[ev_name] = op
    return out


def read(path: str, scopes: bool = False) -> Program:
    """Host events named ``paddle_tpu/...`` (and the window) from the
    file; with ``scopes`` also every leaf device op with its scope."""
    from jax.profiler import ProfileData

    prog = Program(scoped_ops=[] if scopes else None)
    tf_op: Dict[str, str] = {}
    if scopes:
        with open(path, "rb") as f:
            data = f.read()
        try:
            tf_op = op_names(data)
        except (ValueError, IndexError):
            pass        # not the layout this reader knows: no scope read
    for plane in ProfileData.from_file(path).planes:
        m = _DEVICE.match(plane.name)
        if m:
            dev = int(m.group(1))
            for line in plane.lines:
                if line.name == "XLA Modules":
                    prog.modules.extend(
                        (dev, e.name, e.start_ns * 1e-9,
                         e.duration_ns * 1e-9) for e in line.events)
                elif line.name == "XLA Ops" and scopes:
                    ops = []
                    for e in line.events:
                        name, detail = parse_op(e.name)
                        ops.append(R.Op(name, e.start_ns * 1e-9,
                                        e.duration_ns * 1e-9, dev,
                                        scope_of(tf_op.get(e.name)),
                                        detail))
                    prog.scoped_ops.extend(leaf_ops(ops))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(PREFIX):
                        prog.spans.append(ProgramSpan(
                            e.name[len(PREFIX):], e.start_ns * 1e-9,
                            e.duration_ns * 1e-9, dict(e.stats),
                            line.name))
                    elif e.name == WINDOW_SPAN:
                        prog.window = (
                            e.start_ns * 1e-9,
                            (e.start_ns + e.duration_ns) * 1e-9)
    prog.spans.sort(key=lambda s: (s.start, -s.dur))
    return prog


def last_trace() -> Optional[str]:
    root = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    return find_xplane(os.path.join(root, TRACE_DIR))


def _program(ctx: Dict, scopes: bool) -> Optional[Program]:
    """This run's ``Program``, read once a run (kept in ``ctx``); None
    where there is no file, the file is another run's, or the program
    wrote no span of its own."""
    prog = ctx.get("_program_spans")
    if prog is None or (scopes and prog.scoped_ops is None):
        path = last_trace()
        prog = read(path, scopes) if path else Program()
        ctx["_program_spans"] = prog
    tr = ctx["trace"]
    if not prog.spans or prog.window is None or not tr.ops \
            or max(abs(a - b) for a, b in zip(prog.window, tr.window)) > 1e-9:
        return None
    return prog


def for_run(ctx: Dict) -> Optional[R.Trace]:
    prog = _program(ctx, False)
    if prog is None:
        return None
    tr = ctx["trace"]
    return R.Trace(tr.ops, prog.spans, tr.window)


def idle_share_of_run(ctx: Dict, names: Sequence[str]) -> Optional[float]:
    """``idle_share_under`` on this run's trace; the sweep over its ops
    is made once a run (kept in ``ctx``), not once a metric."""
    tr = for_run(ctx)
    if tr is None:
        return None
    if "_idle_by_owner" not in ctx:
        ctx["_idle_by_owner"] = idle_by_owner(tr)
    return idle_share_under(tr, names, ctx["_idle_by_owner"])


def scoped(ctx: Dict) -> Optional[R.Trace]:
    prog = _program(ctx, True)
    if prog is None or not any(o.program for o in prog.scoped_ops):
        return None
    return R.Trace(prog.scoped_ops, prog.spans, ctx["trace"].window)


# -- arithmetic over reduce.Trace ---------------------------------------------
def owners(spans: Sequence[R.Span]) -> List[Tuple[float, float, str]]:
    """Disjoint, sorted ``(from, to, name)``: at every instant that some
    span covers, the innermost one, which is ``reduce.idle_gaps``' rule:
    the latest-starting span that covers the instant (of two that start
    together, the later in ``sorted(spans, key=start)``)."""
    edges = sorted({t for s in spans for t in (s.start, s.start + s.dur)})
    order = sorted(spans, key=lambda s: s.start)
    out: List[Tuple[float, float, str]] = []
    open_: List[R.Span] = []        # by start, ascending
    k = 0
    for a, b in zip(edges, edges[1:]):
        while k < len(order) and order[k].start <= a:
            open_.append(order[k])
            k += 1
        open_ = [s for s in open_ if s.start + s.dur > a]
        if open_:
            name = open_[-1].name
            if out and out[-1][2] == name and out[-1][1] == a:
                out[-1] = (out[-1][0], b, name)
            else:
                out.append((a, b, name))
    return out


def idle_by_owner(trace: R.Trace) -> Dict[str, float]:
    """``dict(reduce.idle_gaps(trace, n=all))``: the worst device's idle
    seconds inside the window by the innermost span open on the host
    meanwhile, ``NO_SPAN`` for the rest. One sweep over gaps and spans
    where ``idle_gaps`` looks at every span for every gap (a serving
    trace holds 10^5 gaps between ops and 10^3..10^4 spans); the test
    holds the two to the same answer."""
    busy = R.busy_by_device(trace)
    if not busy:
        return {}
    dev = min(busy, key=busy.get)
    ops = R.select(trace, device=dev)
    gaps = R.subtract([trace.window], R.union(R.clip(
        [(o.start, o.start + o.dur) for o in ops], *trace.window)))
    segs = owners(trace.spans)
    acc: Dict[str, float] = {}
    j = 0
    for a, c in gaps:
        while j < len(segs) and segs[j][1] <= a:
            j += 1
        covered = 0.0
        i = j
        while i < len(segs) and segs[i][0] < c:
            x, y = max(segs[i][0], a), min(segs[i][1], c)
            if y > x:
                acc[segs[i][2]] = acc.get(segs[i][2], 0.0) + (y - x)
                covered += y - x
            i += 1
        if c - a > covered:
            acc[NO_SPAN] = acc.get(NO_SPAN, 0.0) + (c - a - covered)
    return acc


def idle_share_under(trace: R.Trace, names: Sequence[str],
                     idle: Optional[Dict[str, float]] = None
                     ) -> Optional[float]:
    """The worst device's idle time charged to the spans ``names``
    (each as the innermost), over the window, percent. Over ALL names
    plus ``NO_SPAN`` the shares add up to ``reduce.idle_share``.
    ``idle`` is ``idle_by_owner(trace)`` where the caller kept it."""
    if trace.window_s <= 0 or not trace.ops:
        return None
    if idle is None:
        idle = idle_by_owner(trace)
    return 100.0 * sum(idle.get(n, 0.0) for n in names) / trace.window_s


def inside(trace: R.Trace, name: str) -> List[R.Span]:
    """The spans ``name`` that overlap the window."""
    lo, hi = trace.window
    return [s for s in trace.spans
            if s.name == name and s.start < hi and s.start + s.dur > lo]


def median_ms(trace: R.Trace, name: str) -> Optional[float]:
    durs = [s.dur for s in inside(trace, name)]
    return 1e3 * statistics.median(durs) if durs else None


def seconds_share(trace: R.Trace, name: str) -> Optional[float]:
    """Summed duration of the spans ``name``, each clipped to the
    window, over the window, percent."""
    spans = inside(trace, name)
    if not spans or trace.window_s <= 0:
        return None
    return 100.0 * R.total(R.clip(
        [(s.start, s.start + s.dur) for s in spans],
        *trace.window)) / trace.window_s


def scope_share(trace: R.Trace, scopes: Sequence[str]) -> Optional[float]:
    """Device time of the ops under any of ``scopes`` over busy time,
    percent, on a trace from ``scoped``."""
    shares = [R.program_share(trace, s) for s in scopes]
    return None if None in shares else sum(shares)


def self_seconds(spans: Sequence[R.Span]) -> Dict[str, List[float]]:
    """Every span's duration less its children's, by name: what the
    host spent in the span's own code."""
    acc: Dict[str, List[float]] = {}
    stack: List[List] = []          # [span, seconds of its children]
    for s in sorted(spans, key=lambda s: (s.start, -s.dur)):
        while stack and stack[-1][0].start + stack[-1][0].dur <= s.start:
            done, kids = stack.pop()
            acc.setdefault(done.name, []).append(done.dur - kids)
        if stack:
            stack[-1][1] += s.dur
        stack.append([s, 0.0])
    for done, kids in stack:
        acc.setdefault(done.name, []).append(done.dur - kids)
    return acc
