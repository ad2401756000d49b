"""Device time of the ops the compiled step ran under the named scopes
``scopes`` (``forward``, ``backward``, ``grad_sync``, ``optimizer``: the
first such component of an op's XLA ``op_name``), over device busy time,
percent. An op XLA fused across two scopes counts where its root came
from. Nothing to read (None) where the trace names no scope."""
from .. import program_spans as PS


def read(ctx, scopes):
    tr = PS.scoped(ctx)
    return None if tr is None else PS.scope_share(tr, scopes)
