"""Semantic device-trace annotations + host-side in-flight regions.

Two context managers, one region stack. ``annotate(name)`` is for code
that is TRACED (models, kernels, the compiled train step); ``span(name,
**fields)`` is for HOST code (the engines' own phases): it opens a
``jax.profiler.TraceAnnotation`` named ``paddle_tpu/<name>``, which
lands in the profiler's ``.xplane.pb`` on the device trace's clock while
a profiler session is open (``jax.profiler.start_trace``) and is a
sub-microsecond no-op otherwise. The profiler's file is the span store:
nesting on the thread gives a span its parent, ``fields`` are the
event's stats. No ``named_scope`` there: a scope opened on the host
would rename every program traced under it.

``annotate(name)`` does two jobs at once:

- inside a ``jax.jit``/``shard_map`` trace it opens a
  ``jax.named_scope``, so the XLA metadata (and therefore the
  TensorBoard/Perfetto device trace the TPU profiler captures) carries
  framework names — ``llama/layer3/attention``, ``ag_matmul_ring``,
  ``paged_decode_attention`` — instead of bare HLO ops (the reference
  gets this from its C++ RecordEvent annotations feeding CUPTI),
- on the host it pushes the name on a per-thread region stack, so a
  stall flight-record (flight.py) can report what every thread was
  doing when the watchdog fired — including mid-trace hangs, where the
  region stack shows how deep into the model the tracer got.

The host bookkeeping is plain list push/pop under no lock (each thread
touches only its own stack; the flight dump reads other threads'
stacks racily, which is fine for a post-mortem).
"""
from __future__ import annotations

import contextlib
import threading
from typing import Dict, List

__all__ = ["annotate", "span", "current_regions"]

# tid -> region-name stack. Threads insert their own entry on first
# annotate; the dict itself is only ever appended to (no rebalancing),
# so racy reads from the flight dump see a consistent-enough view.
_regions: Dict[int, List[str]] = {}


SPAN_PREFIX = "paddle_tpu/"


def _stack() -> List[str]:
    tid = threading.get_ident()
    stack = _regions.get(tid)
    if stack is None:
        stack = _regions[tid] = []
    return stack


@contextlib.contextmanager
def annotate(name: str):
    """Named region: jax.named_scope for the device trace + an in-flight
    marker for stall flight-records. Cheap enough for per-layer use."""
    import jax

    stack = _stack()
    stack.append(name)
    try:
        with jax.named_scope(name):
            yield
    finally:
        stack.pop()


@contextlib.contextmanager
def span(name: str, **fields):
    """Host span ``paddle_tpu/<name>`` in the profiler's trace (only
    while a profiler session is open) + the same in-flight marker as
    ``annotate``. ``fields`` must be known when the span opens."""
    import jax

    stack = _stack()
    stack.append(name)
    try:
        with jax.profiler.TraceAnnotation(SPAN_PREFIX + name, **fields):
            yield
    finally:
        stack.pop()


def current_regions() -> Dict[str, List[str]]:
    """{thread-name (tid): open-region stack}, innermost last — what
    each thread is inside right now (flight records embed this)."""
    names = {t.ident: t.name for t in threading.enumerate()}
    out = {}
    for tid, stack in list(_regions.items()):
        if stack:
            out[f"{names.get(tid, 'dead')} ({tid})"] = list(stack)
    return out
