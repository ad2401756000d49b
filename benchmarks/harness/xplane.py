"""Read the JAX profiler's ``.xplane.pb`` into a :class:`reduce.Trace`.

Device planes are ``/device:TPU:<n>``; their ``XLA Ops`` line holds one
event per executed HLO op and ``XLA Modules`` one per program run. Host
spans are the ``bench/...`` events the benchmark wrote with
``jax.profiler.TraceAnnotation``; ``bench/trace_window`` bounds the
traced work. Uses ``jax.profiler.ProfileData`` and nothing else.
"""
from __future__ import annotations

import glob
import os
import re
from typing import Optional

from .reduce import COLLECTIVE, Op, Span, Trace

WINDOW_SPAN = "bench/trace_window"
_DEVICE = re.compile(r"^/device:TPU:(\d+)")


def find_xplane(log_dir: str) -> Optional[str]:
    files = sorted(glob.glob(os.path.join(
        log_dir, "plugins", "profile", "*", "*.xplane.pb")))
    return files[-1] if files else None


_HLO = re.compile(r"^%(\S+) = \(?([a-z0-9]+\[[0-9,]*\])?")
_OPCODE = re.compile(r"\s([a-z][a-z0-9\-]*)\(")
MOSAIC = "mosaic"     # in the detail of every Pallas (Mosaic) custom call


def parse_op(text: str):
    """A TPU trace names an op by its whole HLO instruction,
    ``%fusion.12 = bf16[2,2048]{...} fusion(...), kind=...``. Returns
    (name, detail): ``fusion.12`` and the first result's type and shape
    (``bf16[2,2048]``), plus the opcode where it is a collective (JAX
    names an all-reduce ``psum.3``: the name alone does not say) and
    ``mosaic`` for a ``tpu_custom_call``."""
    m = _HLO.match(text)
    if not m:
        return text[:120], ""
    detail = m.group(2) or ""
    op = _OPCODE.search(text, m.end())
    if op and COLLECTIVE.match(op.group(1)):
        detail = (detail + " " + op.group(1)).strip()
    if 'custom_call_target="tpu_custom_call"' in text:
        detail = (detail + " " + MOSAIC).strip()
    return m.group(1), detail


def _op(ev, dev: int, overlapped: bool) -> Op:
    name, detail = parse_op(ev.name)
    return Op(name, ev.start_ns * 1e-9, ev.duration_ns * 1e-9, dev, "",
              detail, overlapped)


def leaf_ops(ops):
    """Drop every op that encloses another (a ``while`` or a ``call``
    spans its body's ops on the same line): what is left ran, and its
    durations add up."""
    ops = sorted(ops, key=lambda o: (o.start, -o.dur))
    keep = []
    for i, o in enumerate(ops):
        end = o.start + o.dur
        if i + 1 < len(ops) and ops[i + 1].start < end \
                and ops[i + 1].start + ops[i + 1].dur <= end + 1e-12:
            continue
        keep.append(o)
    return keep


def _cpu_ops(pd):
    """Rehearsal only: the CPU client's op events stand in for a device
    plane, so that the harness's tests can drive the reducers."""
    ops = []
    for plane in pd.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            if not line.name.startswith("tf_XLAPjRtCpuClient"):
                continue
            for e in line.events:
                st = dict(e.stats)
                if "hlo_module" in st:
                    ops.append(Op(e.name, e.start_ns * 1e-9,
                                  e.duration_ns * 1e-9,
                                  int(st.get("device_ordinal", 0)),
                                  str(st["hlo_module"]), ""))
    return ops


def load(path: str, cpu_fallback: bool = False) -> Trace:
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    tr = Trace()
    if cpu_fallback:
        tr.ops = _cpu_ops(pd)
    for plane in pd.planes:
        m = _DEVICE.match(plane.name)
        if m:
            dev = int(m.group(1))
            modules = []
            ops = []
            asyncs = []
            for line in plane.lines:
                if line.name == "XLA Modules":
                    modules = [(e.start_ns * 1e-9,
                                (e.start_ns + e.duration_ns) * 1e-9,
                                e.name) for e in line.events]
                elif line.name == "XLA Ops":
                    ops = [_op(e, dev, False) for e in line.events]
                elif line.name == "Async XLA Ops":
                    asyncs = [_op(e, dev, True) for e in line.events]
            modules.sort()
            j = 0
            for o in leaf_ops(ops):
                while j < len(modules) and modules[j][1] <= o.start:
                    j += 1
                if j < len(modules) and modules[j][0] <= o.start:
                    o.program = modules[j][2]
                tr.ops.append(o)
            tr.ops.extend(asyncs)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith("bench/"):
                        tr.spans.append(Span(e.name, e.start_ns * 1e-9,
                                             e.duration_ns * 1e-9))
    win = [s for s in tr.spans if s.name == WINDOW_SPAN]
    if win:
        tr.window = (win[0].start, win[0].start + win[0].dur)
        tr.spans = [s for s in tr.spans if s.name != WINDOW_SPAN]
    elif tr.ops:
        tr.window = (min(o.start for o in tr.ops),
                     max(o.start + o.dur for o in tr.ops))
    return tr


def describe(path: str, limit: int = 12) -> str:
    """What the file holds, for a person: planes, lines, first events
    with their stats. Look at one trace by hand before trusting ``load``."""
    from jax.profiler import ProfileData

    out = []
    for plane in ProfileData.from_file(path).planes:
        out.append(f"PLANE {plane.name}")
        for line in plane.lines:
            evs = list(line.events)
            out.append(f"  LINE {line.name} ({len(evs)} events)")
            for e in evs[:limit]:
                stats = {k: (v[:80] if isinstance(v, str) else v)
                         for k, v in list(e.stats)[:12]}
                out.append(f"    {e.name} start={e.start_ns} "
                           f"dur={e.duration_ns} {stats}")
    return "\n".join(out)
