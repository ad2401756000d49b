"""Roofline share of the decode attention of a model whose layers attend
to the keys a learned index kept (kernel
``paged_sparse_decode_attention``), in the serving programs whose name
holds ``program`` (the decode program).

Time: the traced durations of that kernel inside those programs. Work:
the rows the host logged for the traced stretch (``rows`` names the list
in the traffic loop's host readings: (1, context) per decoding row per
step), once per layer (every layer of ``num_hidden_layers`` is of this
kind where the configuration has ``sa_config``), from
``sparse_cost.sparse_decode``: the USEFUL work, ``min(context, topk)``
keys a row, whatever the kernel read to reach them. An engine step of
``decode_chunk`` fused positions runs the kernel that many times a
layer, a row's context one longer each time. Nothing to read (None)
where the trace holds no such kernel or the configuration names no
selection, as on a tree or a cell without them.
"""
from .. import reduce as R
from ..kernel_cost import least_seconds
from ..sparse_cost import sparse_decode

KERNEL = "paged_sparse_decode_attention"


def read(ctx, program, rows):
    tr, cfg = ctx["trace"], ctx["cfg"]
    work = ctx["host"].get(rows)
    sa = cfg.get("sa_config")
    if not work or not sa:
        return None
    seconds = R.op_seconds(R.select(tr, [KERNEL], program), tr.window)
    if seconds <= 0:
        return None
    chunk = int(cfg["serving"].get("decode_chunk", 1))
    work = [(q, kv + i) for q, kv in work for i in range(chunk)]
    flops, nbytes = sparse_decode(
        work, cfg["num_attention_heads"], cfg["num_key_value_heads"],
        cfg["head_dim"], cfg["head_dim"], sa["topk"])
    least = least_seconds(flops, nbytes, ctx["peaks"])
    return 100.0 * cfg["num_hidden_layers"] * least / seconds
