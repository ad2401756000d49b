"""Operations and bytes of decode attention over the keys a learned
index KEPT (``paged_sparse_decode_attention``), from its shapes alone;
conventions as ``kernel_cost.py`` (a multiply-add is 2 operations; each
operand read once, each result written once; USEFUL work only: the kept
keys, whatever pages an implementation walks to reach them).
"""
from __future__ import annotations

from typing import Tuple


def sparse_decode(rows, H: int, KVH: int, Dk: int, Dv: int, topk: int,
                  itemsize: int = 2) -> Tuple[float, float]:
    """Attention of ONE new position per row to its kept keys, one layer.

    ``rows`` is a list of (q_len, ctx) with q_len = 1: the row's new
    position is the last of ``ctx`` and attends to ``min(ctx, topk)``
    keys, never more, however long the context. Scores take Dk
    multiply-adds a query head a key, the values Dv more: 2 * H * keys *
    (Dk + Dv) operations. Bytes: those keys and values of KVH heads read
    ONCE (not the pages they lie in, not the context), the query in
    (H x Dk) and the result out (H x Dv). Neither the index scores nor
    the selection is counted: they are other ops.
    """
    flops = 0.0
    nbytes = 0.0
    for q_len, ctx in rows:
        keys = min(ctx, topk)
        flops += 2.0 * q_len * H * keys * (Dk + Dv)
        nbytes += keys * KVH * (Dk + Dv) * itemsize
        nbytes += q_len * H * (Dk + Dv) * itemsize
    return flops, nbytes
