"""Time in collective ops during which no compute op ran on that device,
over the traced window, worst device, percent. Nothing to read on one
chip."""
from .. import reduce as R


def read(ctx):
    return R.exposed_collective_share(ctx["trace"])
