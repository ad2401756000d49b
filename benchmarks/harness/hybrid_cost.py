"""Operations and bytes of the two decode attention kernels of a model
with window and full layers (``paged_window_decode_attention`` and
``paged_decode_attention`` with keys wider than values), from their
shapes alone; conventions as ``kernel_cost.py`` (a multiply-add is 2
operations; each operand read once, each result written once; USEFUL
work only: the published widths, not the lanes a 192-wide key is padded
to in the pool).
"""
from __future__ import annotations

from typing import Tuple


def window_decode(rows, H: int, KVH: int, Dk: int, Dv: int, window: int,
                  itemsize: int = 2) -> Tuple[float, float]:
    """Window attention of ONE new position per row, one layer.

    ``rows`` is a list of (q_len, ctx) with q_len = 1: the row's new
    position is the last of ``ctx`` and sees ``min(ctx, window)`` keys,
    never more, whatever the ring holds. Scores take Dk multiply-adds a
    query head a key, the values Dv more: 2 * H * keys * (Dk + Dv)
    operations. Bytes: those keys and values of KVH heads read ONCE
    (not a whole page, not the ring), the query in (H x Dk) and the
    result out (H x Dv).
    """
    flops = 0.0
    nbytes = 0.0
    for q_len, ctx in rows:
        keys = min(ctx, window)
        flops += 2.0 * q_len * H * keys * (Dk + Dv)
        nbytes += keys * KVH * (Dk + Dv) * itemsize
        nbytes += q_len * H * (Dk + Dv) * itemsize
    return flops, nbytes


def global_decode(rows, H: int, KVH: int, Dk: int, Dv: int, page: int,
                  itemsize: int = 2) -> Tuple[float, float]:
    """Full attention of ONE new position per row over a paged K/V pool
    whose keys are Dk wide and values Dv wide, one layer: the position
    sees all ``ctx`` keys; bytes are the pages the row references
    (ceil(ctx / page) pages of K and of V, KVH heads each) read once,
    the query in and the result out."""
    flops = 0.0
    nbytes = 0.0
    for q_len, ctx in rows:
        flops += 2.0 * q_len * H * ctx * (Dk + Dv)
        pages = -(-ctx // page)
        nbytes += pages * page * KVH * (Dk + Dv) * itemsize
        nbytes += q_len * H * (Dk + Dv) * itemsize
    return flops, nbytes
