"""Device time of the ops whose INNERMOST program scope ends with one of
``endings``, over device busy time, percent.

The program names parts of its forward with scopes
(``observability.annotate``), which reach a TPU trace as the path
components of an op's XLA ``op_name`` (the ``tf_op`` stat of its event
metadata: ``program_spans.op_names``), e.g.
``jit(step)/hybrid_moe/layer3.attn.sparse/layer3.attn.sparse.select/
while/body/reduce_sum:``. A scope of the program is a component that
ends with one of ``KNOWN``; an op is charged to the innermost such
component of its path (the last one), and counts here if that component
ends with one of ``endings`` -- so ``[".index", ".select"]`` reads those
two parts, ``["attn.sparse", ".index", ".select", ".attend"]`` the whole
layer's attention, and the layer's number in a scope's name does not
matter. A fusion counts where XLA put its root.

``scope_time_share`` knows the train step's four scopes only and looks
for the FIRST; this reads serving programs' nested scopes. Nothing to
read (None) where there is no trace file of this run or no op names one
of ``KNOWN``.
"""
from jax.profiler import ProfileData

from .. import program_spans as PS
from .. import reduce as R
from ..xplane import WINDOW_SPAN, leaf_ops, parse_op

# every scope ending this reducer is asked about, innermost first where
# one is a suffix of another's parent
KNOWN = ("attn.sparse.index", "attn.sparse.select", "attn.sparse.attend",
         "attn.sparse")


def innermost(op_name, known=KNOWN) -> str:
    """The last path component of ``op_name`` that ends with one of
    ``known``; "" where there is none."""
    for part in reversed((op_name or "").split("/")):
        if part.endswith(known):
            return part
    return ""


def scoped_trace(path: str, known=KNOWN):
    """(``reduce.Trace`` whose leaf ops carry their innermost scope in
    ``program``, the file's ``bench/trace_window`` or None)."""
    with open(path, "rb") as f:
        try:
            tf_op = PS.op_names(f.read())
        except (ValueError, IndexError):
            tf_op = {}          # not the layout the reader knows
    ops, window = [], None
    for plane in ProfileData.from_file(path).planes:
        m = PS._DEVICE.match(plane.name)
        for line in plane.lines:
            if m and line.name == "XLA Ops":
                dev_ops = []
                for e in line.events:
                    name, detail = parse_op(e.name)
                    dev_ops.append(R.Op(
                        name, e.start_ns * 1e-9, e.duration_ns * 1e-9,
                        int(m.group(1)),
                        innermost(tf_op.get(e.name), known), detail))
                ops.extend(leaf_ops(dev_ops))
            elif plane.name.startswith("/host:"):
                for e in line.events:
                    if e.name == WINDOW_SPAN:
                        window = (e.start_ns * 1e-9,
                                  (e.start_ns + e.duration_ns) * 1e-9)
    return R.Trace(ops, [], window or (0.0, 0.0)), window


def share(trace: R.Trace, endings) -> float:
    """Percent of busy time in the ops whose scope ends with one of
    ``endings``; None without busy time."""
    busy = sum(R.busy_by_device(trace).values())
    if busy <= 0:
        return None
    mine = [o for o in R.select(trace)
            if o.program and o.program.endswith(tuple(endings))]
    return 100.0 * R.op_seconds(mine, trace.window) / busy


def read(ctx, endings):
    tr = ctx.get("_inner_scopes")
    if tr is None:
        path = PS.last_trace()
        if not path:
            return None
        tr, window = scoped_trace(path)
        run = ctx["trace"].window
        if window is None or max(
                abs(a - b) for a, b in zip(window, run)) > 1e-9:
            return None         # another run's file
        ctx["_inner_scopes"] = tr
    if not any(o.program for o in tr.ops):
        return None
    return share(tr, endings)
