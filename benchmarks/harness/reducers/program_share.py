"""Device time of every op inside the programs whose name holds
``program``, over device busy time, percent."""
from .. import reduce as R


def read(ctx, program):
    tr = ctx["trace"]
    if not R.select(tr, None, program):
        return None
    return R.program_share(tr, program)
