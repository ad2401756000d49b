"""Roofline share of ``mla_paged_decode_attention`` in the serving
programs whose name holds ``program`` (the decode program).

Time: the traced durations of the kernel inside those programs. Work:
the rows the host logged for the traced stretch (``rows`` names the list
in the traffic loop's host readings: (1, kv_len) per decoding row per
step), once per layer, from ``mla_cost.latent_decode``: the pages a row
references read once for all heads. An engine step of ``decode_chunk``
fused positions runs the kernel that many times a layer, a row's context
one longer each time. Nothing to read (None) where the
trace holds no such kernel, as on a tree without it.
"""
from .. import reduce as R
from ..kernel_cost import least_seconds
from ..mla_cost import latent_decode

KERNEL = "mla_paged_decode_attention"


def read(ctx, program, rows):
    tr, cfg = ctx["trace"], ctx["cfg"]
    work = ctx["host"].get(rows)
    seconds = R.op_seconds(R.select(tr, [KERNEL], program), tr.window)
    if not work or seconds <= 0 or "kv_lora_rank" not in cfg:
        return None
    chunk = int(cfg["serving"].get("decode_chunk", 1))
    work = [(q, kv + i) for q, kv in work for i in range(chunk)]
    flops, nbytes = latent_decode(
        work, cfg["num_attention_heads"], cfg["kv_lora_rank"],
        cfg["qk_rope_head_dim"], cfg["serving"]["page_size"])
    least = least_seconds(flops, nbytes, ctx["peaks"])
    return 100.0 * cfg["num_hidden_layers"] * least / seconds
