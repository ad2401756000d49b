"""1 - union of the device's op intervals over the traced window, worst
device, percent."""
from .. import reduce as R


def read(ctx):
    return R.idle_share(ctx["trace"])
