"""Operations and bytes of the latent decode kernel
(``mla_paged_decode_attention``), from its shapes alone; conventions as
``kernel_cost.py`` (a multiply-add is 2 operations; each operand read
once, each result written once; USEFUL work only).
"""
from __future__ import annotations

from typing import Tuple


def latent_decode(rows, H: int, d_c: int, d_r: int, page: int,
                  itemsize: int = 2) -> Tuple[float, float]:
    """Absorbed latent attention of ONE new position per row over a
    paged latent cache, one layer.

    ``rows`` is a list of (q_len, kv_len) with q_len = 1: the row's new
    position sees kv_len cache rows, each ``[c | k_r]`` = d_c + d_r
    numbers shared by all H heads. Scores take d_c + d_r multiply-adds
    a head a cache row, the value (the latent itself) d_c more:
    2 * H * kv_len * (d_c + d_r + d_c) operations. Bytes: the pages the
    row references (ceil(kv_len / page) pages of d_c + d_r numbers a
    position) read ONCE, whatever the number of heads; the query in
    (H x (d_c + d_r)) and u out (H x d_c). Not the pool, not the lanes
    a 64-wide array is padded to on the chip.
    """
    flops = 0.0
    nbytes = 0.0
    for q_len, kv_len in rows:
        flops += 2.0 * q_len * H * kv_len * (2 * d_c + d_r)
        pages = -(-kv_len // page)
        nbytes += pages * page * (d_c + d_r) * itemsize
        nbytes += q_len * H * (2 * d_c + d_r) * itemsize
    return flops, nbytes
