"""Device time of the ops whose name or traced detail holds one of
``names`` (e.g. the Pallas kernels' stable names), over device busy time,
percent; optionally only inside programs whose name holds ``program``."""
from .. import reduce as R


def read(ctx, names, program=None):
    return R.time_share(ctx["trace"], names, program)
