"""Roofline share of the flash-attention kernels in a training step.

Time: the traced durations of ``flash_attention_fwd/dq/dkv``. Work: one
forward, one dq and one dkv per layer call, at the shapes one device
sees (micro-batch rows, heads / mp_degree), from
``kernel_cost.flash_attention_*``; a forward recomputed for the backward
counts as time, not as work. The number of layer calls is the number of
``dq`` events.
"""
import importlib

from .. import reduce as R


def read(ctx, cost_module="kernel_cost"):
    tr, cfg, plan = ctx["trace"], ctx["cfg"], ctx["traffic"]
    cost = importlib.import_module(f"benchmarks.harness.{cost_module}")
    kernels = ("flash_attention_fwd", "flash_attention_dq",
               "flash_attention_dkv")
    ops = {k: R.select(tr, [k]) for k in kernels}
    seconds = sum(R.op_seconds(v, tr.window) for v in ops.values())
    calls = len(ops["flash_attention_dq"])
    if not calls or seconds <= 0:
        return None
    par = cfg["parallel"]
    B = plan.get("micro_batch") or plan["batch"] // par.get("dp_degree", 1)
    H = cfg["n_heads"] // par.get("mp_degree", 1)
    shape = (B, plan["seq"], H, cfg["d_head"])
    least = sum(cost.least_seconds(*getattr(cost, k)(*shape), ctx["peaks"])
                for k in kernels)
    return 100.0 * calls * least / seconds
