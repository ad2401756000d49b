"""Operations and bytes the algorithm needs for one kernel call, from its
shapes alone. A roofline share is ``least_seconds(...) / measured time``;
the counts here are the USEFUL work (a lower bound on what any
implementation must do), so a share cannot pass 100% unless the time is
wrong. A new kernel's cost function is a new file beside this one; the
metric file names it as ``<module>.<function>``.

Conventions: a multiply-add is 2 operations; causal attention over S
query rows that each see the rows up to their own counts S*(S+1)/2
query-key pairs; bytes are each operand read once and each result
written once.
"""
from __future__ import annotations

from typing import Tuple


def least_seconds(flops: float, nbytes: float, peaks) -> float:
    """The least time the chip could take: the larger of operations over
    peak FLOP/s and bytes over peak bytes/s."""
    return max(flops / peaks.flops, nbytes / peaks.hbm_bytes)


def _causal_pairs(S: int) -> float:
    return S * (S + 1) / 2.0


def flash_attention_fwd(B: int, S: int, H: int, D: int,
                        itemsize: int = 2) -> Tuple[float, float]:
    """Causal self-attention forward, q/k/v/o all [B, S, H, D].
    QK^T and PV: 2 matmuls x 2 ops x D per query-key pair."""
    flops = 4.0 * D * _causal_pairs(S) * B * H
    nbytes = 4.0 * B * S * H * D * itemsize          # q, k, v in; o out
    return flops, nbytes


def flash_attention_dq(B: int, S: int, H: int, D: int,
                       itemsize: int = 2) -> Tuple[float, float]:
    """dq pass: recompute S=QK^T, dP=dO V^T, dQ=dS K: 3 matmuls."""
    flops = 6.0 * D * _causal_pairs(S) * B * H
    nbytes = 5.0 * B * S * H * D * itemsize      # q, k, v, do in; dq out
    return flops, nbytes


def flash_attention_dkv(B: int, S: int, H: int, D: int,
                        itemsize: int = 2) -> Tuple[float, float]:
    """dk/dv pass: recompute S, dV=P^T dO, dP=dO V^T, dK=dS^T Q: 4."""
    flops = 8.0 * D * _causal_pairs(S) * B * H
    nbytes = 6.0 * B * S * H * D * itemsize   # q, k, v, do in; dk, dv out
    return flops, nbytes


def paged_attention(rows, H: int, KVH: int, D: int, page: int,
                    itemsize: int = 2) -> Tuple[float, float]:
    """Attention of query rows over a paged K/V pool, one layer.

    ``rows`` is a list of (q_len, kv_len): q_len new query positions
    (1 in decode, the prompt length in a prefill) that end at position
    kv_len of the row's context. Query i of the q_len sees
    kv_len - q_len + i + 1 keys. Bytes: q read and o written for the
    real query positions, and ONLY the pages the row references
    (ceil(kv_len / page) pages of K and of V, KVH heads each) — not the
    pool, and not the padding of a bucket.
    """
    flops = 0.0
    nbytes = 0.0
    for q_len, kv_len in rows:
        first = kv_len - q_len + 1
        pairs = q_len * (first + kv_len) / 2.0
        flops += 4.0 * D * H * pairs
        pages = -(-kv_len // page)
        nbytes += 2.0 * pages * page * KVH * D * itemsize     # K and V
        nbytes += 2.0 * q_len * H * D * itemsize              # q in, o out
    return flops, nbytes
