"""``inner_scope_share`` for a program whose scopes that reducer's
``KNOWN`` does not hold: device time of the ops whose INNERMOST program
scope ends with one of ``endings``, over device busy time, percent,
where a scope of the program is a path component that ends with one of
``known`` (an argument here: the metric's file names the program's
scopes, e.g. a shortcut-connected layer's ``attn0`` / ``attn1`` /
``mlp0`` / ``mlp1`` / ``moe.shortcut``). The trace is read by
``inner_scope_share.scoped_trace``.

``names`` (optional) are ops that belong to the part by their NAME where
the trace gives them no scope at all: XLA's grouped matmul reaches a
TPU trace as a Mosaic call ``ragged-dot-none`` whose ``op_name`` is that
word and no path (my chip run, PR 45), so the sorted expert products of
a prefill program would fall out of their layer's scope. An op with a
known scope is never counted by name.

Nothing to read (None) where there is no trace file of this run or no op
names one of ``known``, as on a tree whose program has no such scope.
"""
from .. import program_spans as PS
from .. import reduce as R
from . import inner_scope_share as inner


def share(trace: R.Trace, endings, names=()) -> float:
    """``inner_scope_share.share`` with the unscoped ops named ``names``
    counted in; None without busy time."""
    busy = sum(R.busy_by_device(trace).values())
    if busy <= 0:
        return None
    endings = tuple(endings)
    mine = [o for o in R.select(trace)
            if (o.program.endswith(endings) if o.program
                else any(n in o.name for n in names))]
    return 100.0 * R.op_seconds(mine, trace.window) / busy


def read(ctx, endings, known, names=()):
    known = tuple(known)
    cache = ctx.setdefault("_named_scopes", {})
    tr = cache.get(known)
    if tr is None:
        path = PS.last_trace()
        if not path:
            return None
        tr, window = inner.scoped_trace(path, known)
        run = ctx["trace"].window
        if window is None or max(
                abs(a - b) for a, b in zip(window, run)) > 1e-9:
            return None         # another run's file
        cache[known] = tr
    if not any(o.program for o in tr.ops):
        return None
    return share(tr, endings, names)
