"""The host's time in one of the program's own spans
(``paddle_tpu/<span>``) over the traced window: ``stat`` is
``"median_ms"`` (the median duration of the spans, ms) or ``"share"``
(their summed duration over the window, percent). Nothing to read (None)
on a tree whose program writes no spans, or in a window without one."""
from .. import program_spans as PS

STATS = {"median_ms": PS.median_ms, "share": PS.seconds_share}


def read(ctx, span, stat):
    tr = PS.for_run(ctx)
    return None if tr is None else STATS[stat](tr, span)
