"""The worst device's idle time inside the traced window charged to the
program's own host spans ``spans`` (``paddle_tpu/<name>``, each as the
innermost span open meanwhile), over the window, percent: what the
device waited ON, where ``device_idle`` says only that it waited. Over
every span of the program plus the time outside them the shares add up
to ``device_idle``'s number. Nothing to read (None) on a tree whose
program writes no spans."""
from .. import program_spans as PS


def read(ctx, spans):
    return PS.idle_share_of_run(ctx, spans)
