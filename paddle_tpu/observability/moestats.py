"""MoE routing telemetry: the trace-time collector MoELayer records
into and ParallelEngine drains into compiled-step outputs.

Expert-load / token-drop / aux-loss values are TRACED arrays computed
inside the compiled step (``MoELayer.forward``'s non-differentiated
stats aux). They cannot be fetched mid-trace, so the flow is:

1. the engine ``begin()``s a collection before calling the loss fn,
2. each MoELayer forward ``record()``s its stats dict (layer order =
   call order, stable per compiled program),
3. the engine ``drain()``s the list, psums the token counts over the
   batch-sharding axes, and returns the dict as an extra (replicated)
   step output,
4. the fetched host values feed the ``paddle_tpu_moe_*`` gauges with
   the same one-step lag as loss/grad-norm (catalog.train_metrics).

``GatedMoELayer`` (the serving-side expert layer) records through the
same collector while one is open: ``choices`` and ``load``, ``form`` /
``rows`` / ``passes`` / ``grouped`` of its products (the last:
``moe_layer.grouped_product``, which grouped matmul the sorted form
took) and, under a gate that keeps groups
of experts first, ``groups`` (the kept groups' ids a token) and
``group_load`` (the call's routed pairs by group, so a skewed group
shows); ``ServingEngine`` and the benchmark's probes read those.

A Llama-family attention layer records ``attention``, the form its
attention over the page pool took (``models/llama.py``), and a hybrid
model's plain prefill layer the form of the prompt's own attention
(``models/hybrid_moe.py``), which
``ServingEngine.prefill_attention_forms`` keeps a prefill bucket.

When no collection is active (eager forwards, serving, the pipelined
path — whose stage-masked scan would record misleading values),
``record()`` is a no-op, so MoE layers stay usable everywhere.
"""
from __future__ import annotations

import threading
from typing import Any, Dict, List, Optional

__all__ = ["begin", "record", "drain", "active", "publish"]


class _State(threading.local):
    def __init__(self):
        self.records: Optional[List[Dict[str, Any]]] = None


_state = _State()


def active() -> bool:
    return _state.records is not None


def begin() -> None:
    """Start collecting (engine, just before tracing the loss fn)."""
    _state.records = []


def record(stats: Dict[str, Any]) -> None:
    """Append one MoE layer's routing stats (no-op unless a collection
    is active on this thread)."""
    if _state.records is not None:
        _state.records.append(stats)


def drain() -> List[Dict[str, Any]]:
    """End the collection and return the per-layer stats in call
    order."""
    recs, _state.records = _state.records, None
    return recs or []


def publish(fetched: Dict[str, Dict[str, Any]],
            metrics: Dict[str, Any]) -> None:
    """Feed fetched host values into the moe_* gauges.

    ``fetched``: {layer_label: {"load": [E] array, "routed": scalar,
    "dropped": scalar, "aux": scalar}} — the engine's extra step output
    after device fetch.
    """
    import numpy as np

    for layer, st in fetched.items():
        load = np.asarray(st["load"], dtype=np.float64)
        total = float(load.sum())
        for e in range(load.shape[0]):
            # fraction of routed-and-kept tokens landing on expert e:
            # uniform routing reads 1/E on every series
            metrics["moe_expert_load"].set(
                float(load[e]) / total if total > 0 else 0.0,
                layer=layer, expert=str(e))
        routed = float(np.asarray(st["routed"]))
        dropped = float(np.asarray(st["dropped"]))
        metrics["moe_drop_rate"].set(
            dropped / routed if routed > 0 else 0.0, layer=layer)
        metrics["moe_aux_loss"].set(float(np.asarray(st["aux"])),
                                    layer=layer)
