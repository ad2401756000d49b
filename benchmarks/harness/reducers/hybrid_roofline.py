"""Roofline share of one of the two decode attention kernels of a model
with window and full layers, in the serving programs whose name holds
``program`` (the decode program).

``kind`` is ``"window"`` (kernel ``paged_window_decode_attention``, cost
``hybrid_cost.window_decode``) or ``"full"`` (``paged_decode_attention``,
``hybrid_cost.global_decode``). Time: the traced durations of that
kernel inside those programs. Work: the rows the host logged for the
traced stretch (``rows`` names the list in the traffic loop's host
readings: (1, context) per decoding row per step), once per layer of
that kind (``hybrid_layer_pattern`` up to ``num_hidden_layers``). An
engine step of ``decode_chunk`` fused positions runs the kernel that
many times a layer, a row's context one longer each time. Nothing to
read (None) where the trace holds no such kernel or the configuration
has no such layers, as on a tree or a cell without them.
"""
from .. import reduce as R
from ..hybrid_cost import global_decode, window_decode
from ..kernel_cost import least_seconds

KERNELS = {"window": "paged_window_decode_attention",
           "full": "paged_decode_attention"}


def read(ctx, program, rows, kind):
    tr, cfg = ctx["trace"], ctx["cfg"]
    work = ctx["host"].get(rows)
    pattern = cfg.get("hybrid_layer_pattern")
    if not work or pattern is None:
        return None
    pattern = pattern[:cfg["num_hidden_layers"]]
    layers = sum(1 for p in pattern if bool(p) == (kind == "window"))
    seconds = R.op_seconds(R.select(tr, [KERNELS[kind]], program),
                           tr.window)
    if seconds <= 0 or not layers:
        return None
    chunk = int(cfg["serving"].get("decode_chunk", 1))
    work = [(q, kv + i) for q, kv in work for i in range(chunk)]
    H, dk, dv = (cfg["num_attention_heads"], cfg["head_dim"],
                 cfg["v_head_dim"])
    if kind == "window":
        flops, nbytes = window_decode(
            work, H, cfg["swa_num_key_value_heads"], dk, dv,
            cfg["sliding_window"])
    else:
        flops, nbytes = global_decode(
            work, H, cfg["num_key_value_heads"], dk, dv,
            cfg["serving"]["page_size"])
    least = least_seconds(flops, nbytes, ctx["peaks"])
    return 100.0 * layers * least / seconds
