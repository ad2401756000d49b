"""Roofline share of the latent decode attention of a model whose
queries attend to the cache rows a learned index kept (kernel
``mla_paged_sparse_decode_attention``), in the serving programs whose
name holds ``program`` (the decode program).

Time: the traced durations of that kernel inside those programs. Work:
the rows the host logged for the traced stretch (``rows`` names the list
in the traffic loop's host readings: (1, context) per decoding row per
step), once per layer (every layer of ``num_hidden_layers`` attends so
where the configuration has ``index_topk`` and ``kv_lora_rank``), from
``sparse_mla_cost.sparse_latent_decode``: the USEFUL work, ``min(context,
index_topk)`` cache rows a row, whatever the kernel read or masked to
reach them, so the share reads the same whichever form of the kernel
runs. An engine step of ``decode_chunk`` fused positions runs the kernel
that many times a layer, a row's context one longer each time. Nothing
to read (None) where the trace holds no such kernel or the configuration
names no such selection, as on a tree or a cell without them.
"""
from .. import reduce as R
from ..kernel_cost import least_seconds
from ..sparse_mla_cost import sparse_latent_decode

KERNEL = "mla_paged_sparse_decode_attention"


def read(ctx, program, rows):
    tr, cfg = ctx["trace"], ctx["cfg"]
    work = ctx["host"].get(rows)
    if not work or "index_topk" not in cfg or "kv_lora_rank" not in cfg:
        return None
    seconds = R.op_seconds(R.select(tr, [KERNEL], program), tr.window)
    if seconds <= 0:
        return None
    chunk = int(cfg["serving"].get("decode_chunk", 1))
    work = [(q, kv + i) for q, kv in work for i in range(chunk)]
    flops, nbytes = sparse_latent_decode(
        work, cfg["num_attention_heads"], cfg["kv_lora_rank"],
        cfg["qk_rope_head_dim"], cfg["index_topk"])
    least = least_seconds(flops, nbytes, ctx["peaks"])
    return 100.0 * cfg["num_hidden_layers"] * least / seconds
