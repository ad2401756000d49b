"""Operations and bytes of absorbed latent decode attention over the
cache rows a learned index KEPT (``mla_paged_sparse_decode_attention``),
from its shapes alone; conventions as ``kernel_cost.py`` (a multiply-add
is 2 operations; each operand read once, each result written once;
USEFUL work only: the kept rows, whatever pages an implementation walks
or masks to reach them).
"""
from __future__ import annotations

from typing import Tuple


def sparse_latent_decode(rows, H: int, d_c: int, d_r: int, topk: int,
                         itemsize: int = 2) -> Tuple[float, float]:
    """Absorbed latent attention of ONE new position per row to its kept
    cache rows, one layer.

    ``rows`` is a list of (q_len, ctx) with q_len = 1: the row's new
    position is the last of ``ctx`` and attends to ``min(ctx, topk)``
    cache rows, never more, however long the context. A cache row is
    ``[c | k_r]`` = d_c + d_r numbers shared by all H heads: scores take
    d_c + d_r multiply-adds a head a kept row, the value (the latent
    itself) d_c more: 2 * H * kept * (2 * d_c + d_r) operations. Bytes:
    the kept rows read ONCE, whatever the number of heads (not the pages
    they lie in, not the context, not the lanes a 64-wide array is
    padded to on the chip); the query in (H x (d_c + d_r)) and u out
    (H x d_c). Neither the index scores nor the selection is counted:
    they are other ops.
    """
    flops = 0.0
    nbytes = 0.0
    for q_len, ctx in rows:
        kept = min(ctx, topk)
        flops += 2.0 * q_len * H * kept * (2 * d_c + d_r)
        nbytes += kept * (d_c + d_r) * itemsize
        nbytes += q_len * H * (2 * d_c + d_r) * itemsize
    return flops, nbytes


def kept_prefill(S: int, H: int, Dk: int, Dv: int, topk: int,
                 itemsize: int = 2) -> Tuple[float, float]:
    """Causal self-attention of S positions in which row ``t`` attends
    to its ``min(t + 1, topk)`` kept keys (``kept_flash_attention``),
    one call = one layer of one prompt at its bucket's length (the
    bucket's padding rows are computed like any other and are counted).

    Scores take Dk multiply-adds a head a kept pair, the values Dv more:
    2 * H * pairs * (Dk + Dv) operations over ``pairs = sum_t min(t + 1,
    topk)``, never the S * (S + 1) / 2 pairs of the blocks the kernel
    walks. Bytes: q and k in (S x H x Dk each), v in and the result out
    (S x H x Dv each), every head its own keys and values; the mask's
    bytes are not counted (a lower bound).
    """
    full = min(S, topk)
    pairs = full * (full + 1) / 2.0 + (S - full) * topk
    flops = 2.0 * H * pairs * (Dk + Dv)
    nbytes = 2.0 * S * H * (Dk + Dv) * itemsize
    return flops, nbytes
