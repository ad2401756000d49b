"""Roofline share of ``paged_decode_attention`` in the serving programs
whose name holds ``program`` (the decode program: Sq = 1).

Time: the traced durations of the kernel inside those programs. Work:
the rows the host logged for the traced stretch (``rows`` names the list
in the traffic loop's host readings: (q_len, kv_len) per decoding row
per step), once per layer, from the cost function named
``cost`` (``<module>.<function>`` beside ``kernel_cost.py``): only the
pages a row references, not the pool.
"""
import importlib

from .. import reduce as R
from ..kernel_cost import least_seconds

KERNEL = "paged_decode_attention"


def read(ctx, program, rows, cost="kernel_cost.paged_attention"):
    tr, cfg = ctx["trace"], ctx["cfg"]
    work = ctx["host"].get(rows)
    seconds = R.op_seconds(R.select(tr, [KERNEL], program), tr.window)
    if not work or seconds <= 0:
        return None
    mod, fn = cost.rsplit(".", 1)
    mod = importlib.import_module(f"benchmarks.harness.{mod}")
    flops, nbytes = getattr(mod, fn)(
        work, cfg["num_attention_heads"], cfg["num_key_value_heads"],
        cfg["head_dim"], cfg["serving"]["page_size"])
    least = least_seconds(flops, nbytes, ctx["peaks"])
    return 100.0 * cfg["num_hidden_layers"] * least / seconds
