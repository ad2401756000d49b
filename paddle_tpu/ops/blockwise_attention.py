"""Causal self-attention over a prompt in blocks of rows and keys, with
a running maximum and sum: no ``[H, S, S]`` array is ever built, a q
block meets only the key blocks it can see (its band alone on a window
layer), q/k heads may be wider than v heads, and a learned sink logit a
head may join the denominator. Plain ``lax`` (XLA fuses each block's
mask, exponent and sums; the two products are batched matmuls), every
slice static.

What still runs it (``models/hybrid_moe.py`` picks, from what it sees in
its input): a prefill layer with keys wider than its values (192 against
128: a 192-wide head is no lane-aligned column slice of ``[B, S, H *
D]``), a layer with a sink logit, every layer on the CPU or with the
kernels off, and shapes ``flash_gqa_supported`` refuses (a head under
128 lanes, a bucket no block of 128 rows divides). A layer whose q, k
and v are one 128-multiple wide with no sink, full or window, runs
``flash_attention_gqa`` instead (``ops/pallas/flash_attention.py``): on
a v5e each block's float32 scores go through HBM here: at 32 heads on 4
KV heads of 128 and a window of 2,048 this form reads 2.8 / 4.2 / 4.7 us
a 512 x 512 block pair a head at 2,048 / 4,096 / 8,192 rows against the
kernel's 1.40 / 1.29 / 1.43, and 23.0 ms against 5.4 for a full layer at
8,192 (my chip run, PR 46; ``tools/paged_attention_timing.py --prefill
--window 2048 --kv-heads 4``).
"""
from __future__ import annotations

from typing import Optional

import jax.numpy as jnp

__all__ = ["blockwise_causal_attention"]

_NEG = -1e30


def blockwise_causal_attention(q, k, v, scale: float,
                               window: Optional[int] = None, sinks=None,
                               block: int = 512):
    """q [B, S, H, D] against k [B, S, KV, D] and v [B, S, KV, Dv], all at
    positions 0..S-1; row i sees keys j <= i and, with ``window``, also
    i - j < window. ``sinks [H]``: ``p_ij = exp(s_ij) / (sum_j exp(s_ij)
    + exp(sink_h))``. Scores and sums in float32, the probabilities cast
    to v's type for the second product. Returns [B, S, H, Dv] in q's
    type."""
    B, S, H, D = q.shape
    KV, Dv = k.shape[2], v.shape[-1]
    G = H // KV
    q5 = q.reshape(B, S, KV, G, D)
    if sinks is not None:
        sk = jnp.asarray(sinks, jnp.float32).reshape(1, KV, G, 1, 1)
    outs = []
    for a in range(0, S, block):
        e = min(a + block, S)
        qb = q5[:, a:e]
        lo = 0 if window is None else max(0, a - window + 1)
        # a window layer's band for these rows is one key block
        step = block if window is None else e - lo
        m = jnp.full((B, KV, G, e - a, 1), _NEG, jnp.float32)
        l = jnp.zeros((B, KV, G, e - a, 1), jnp.float32)
        acc = jnp.zeros((B, KV, G, e - a, Dv), jnp.float32)
        qpos = jnp.arange(a, e, dtype=jnp.int32)[:, None]
        for c in range(lo, e, step):
            ce = min(c + step, e)
            s = jnp.einsum("bqkgd,bmkd->bkgqm", qb, k[:, c:ce],
                           preferred_element_type=jnp.float32) * scale
            kpos = jnp.arange(c, ce, dtype=jnp.int32)[None]
            keep = kpos <= qpos
            if window is not None:
                keep = keep & (kpos > qpos - window)
            s = jnp.where(keep, s, _NEG)
            m_new = jnp.maximum(m, s.max(-1, keepdims=True))
            p = jnp.where(keep, jnp.exp(s - m_new), 0.0)
            corr = jnp.exp(m - m_new)
            l = l * corr + p.sum(-1, keepdims=True)
            acc = acc * corr + jnp.einsum(
                "bkgqm,bmkd->bkgqd", p.astype(v.dtype), v[:, c:ce],
                preferred_element_type=jnp.float32)
            m = m_new
        if sinks is not None:
            m_fin = jnp.maximum(m, sk)
            corr = jnp.exp(m - m_fin)
            l = l * corr + jnp.exp(sk - m_fin)
            acc = acc * corr
        o = acc / l                                  # [B, KV, G, rows, Dv]
        outs.append(jnp.transpose(o, (0, 3, 1, 2, 4)).reshape(
            B, e - a, H, Dv).astype(q.dtype))
    return outs[0] if len(outs) == 1 else jnp.concatenate(outs, axis=1)
