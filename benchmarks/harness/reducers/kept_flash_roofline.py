"""Roofline share of the prefill attention over the kept sets (kernel
``kept_flash_attention``), in the serving programs whose name holds
``program`` (the prefill programs).

Time: the traced durations of that kernel inside those programs. Work: a
call is one layer of one prompt at its bucket's length, so calls x
``sparse_mla_cost.kept_prefill(bucket)``: the USEFUL work, ``min(t + 1,
index_topk)`` kept keys a row, whatever blocks the kernel walked under
the mask. The bucket is the ONE power of two that holds every prompt of
the cell's traffic (``prompt.min`` .. ``prompt.max``); a mix that spans
several buckets gives nothing to read here (the calls' lengths are not
in the host's readings), nor does a trace without the kernel or a
configuration without such a selection.
"""
from .. import reduce as R
from ..kernel_cost import least_seconds
from ..sparse_mla_cost import kept_prefill

KERNEL = "kept_flash_attention"


def _bucket(n: int) -> int:
    return 1 << max(int(n) - 1, 0).bit_length()


def read(ctx, program):
    tr, cfg, prompt = ctx["trace"], ctx["cfg"], ctx["traffic"].get("prompt")
    if not prompt or "index_topk" not in cfg or "kv_lora_rank" not in cfg:
        return None
    S = _bucket(prompt["max"])
    if _bucket(prompt["min"]) != S:
        return None
    ops = R.select(tr, [KERNEL], program)
    seconds = R.op_seconds(ops, tr.window)
    if seconds <= 0:
        return None
    flops, nbytes = kept_prefill(
        S, cfg["num_attention_heads"],
        cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"],
        cfg["v_head_dim"], cfg["index_topk"])
    least = least_seconds(flops, nbytes, ctx["peaks"])
    return 100.0 * len(ops) * least / seconds
