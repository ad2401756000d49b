"""Pallas TPU prefill attention over a KEPT set: causal self-attention in
which row ``t`` attends to the positions a mask allows and to no others.

The prefill of a layer whose queries attend to the keys (or latent cache
rows) a learned index kept (``ops/sparse_attention.py``): the index and
the exact selection give ``keep [B, S, S]`` (row t, key s; a subset of
``s <= t``), and this kernel is ``flash_attention_fwd``'s forward with
that mask in the place of the causal one: K/V stream through VMEM as the
innermost grid dimension, the online-softmax statistics and the
accumulator live in VMEM scratch, a block above the diagonal is neither
fetched (the index map clamps to the diagonal block) nor computed. What
it adds to the flash kernel: the mask, one int8 ``[block, block]`` tile a
grid step, shared by every head; and keys WIDER than values (192
against 128: a latent layer's unabsorbed heads), so it has no backward.

The plain-``lax`` form of the same mathematics is
``sparse_causal_attention``'s loop over key blocks: it writes a block's
float32 scores ``[heads, block, block]`` through HBM several times over,
which at 128 heads is most of a prefill's time; here they never leave
VMEM.

Layout [B, S, H, D] in and out, as ``flash_attention_fwd``; grid
(B * H, S / block, S / block).
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import compiler_params as _compiler_params

__all__ = ["kept_flash_attention", "kept_attention_dense",
           "kept_flash_supported"]

_NEG = -1e30
BLOCK = 512


def _kernel(q_ref, k_ref, v_ref, keep_ref, o_ref, m_s, l_s, acc_s, *,
            scale, nkv):
    qi, j = pl.program_id(1), pl.program_id(2)

    @pl.when(j == 0)
    def _():
        m_s[...] = jnp.full_like(m_s, _NEG)
        l_s[...] = jnp.zeros_like(l_s)
        acc_s[...] = jnp.zeros_like(acc_s)

    @pl.when(j <= qi)           # at or under the diagonal block
    def _():
        qb, kb, vb = q_ref[0], k_ref[0], v_ref[0]
        s = lax.dot_general(qb, kb, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
        keep = keep_ref[0] != 0                         # [block, block]
        s = jnp.where(keep, s, _NEG)
        m_prev = m_s[:, :1]
        m_new = jnp.maximum(m_prev, jnp.max(s, -1, keepdims=True))
        p = jnp.where(keep, jnp.exp(s - m_new), 0.0)
        corr = jnp.exp(m_prev - m_new)
        l_s[:, :1] = l_s[:, :1] * corr + jnp.sum(p, -1, keepdims=True)
        acc_s[...] = acc_s[...] * corr + lax.dot_general(
            p.astype(vb.dtype), vb, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_s[:, :1] = m_new

    @pl.when(j == nkv - 1)
    def _():
        l = jnp.maximum(l_s[:, :1], 1e-30)
        o_ref[0] = (acc_s[...] / l).astype(o_ref.dtype)


def kept_flash_supported(q_shape, v_shape, block: int = BLOCK) -> bool:
    """Mosaic shape gate: whole blocks of rows, a value head that fills
    the lanes, a key head of whole sublane tiles."""
    B, S, H, D = q_shape
    return S % block == 0 and v_shape[-1] % 128 == 0 and D % 64 == 0


def kept_flash_attention(q, k, v, keep, scale: float, block: int = BLOCK,
                         interpret: bool = False):
    """q, k [B, S, H, D], v [B, S, H, Dv] at positions 0..S-1 (every
    head its own keys and values); keep [B, S, S] (bool or int8; row t,
    key s), a subset of ``s <= t`` with at least one key a row. Returns
    [B, S, H, Dv] in q's type: softmax over the kept keys alone."""
    B, S, H, D = q.shape
    Dv = v.shape[-1]
    n = S // block
    to3 = lambda x: jnp.swapaxes(x, 1, 2).reshape(B * H, S, x.shape[-1])

    def kv_index(b, i, j):
        # clamp past the diagonal: the resident block again, no DMA
        return (b, jnp.minimum(j, i), 0)

    out = pl.pallas_call(
        partial(_kernel, scale=float(scale), nkv=n),
        grid=(B * H, n, n),
        in_specs=[
            pl.BlockSpec((1, block, D), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block, D), kv_index),
            pl.BlockSpec((1, block, Dv), kv_index),
            pl.BlockSpec((1, block, block),
                         lambda b, i, j: (b // H, i, jnp.minimum(j, i))),
        ],
        out_specs=pl.BlockSpec((1, block, Dv), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((B * H, S, Dv), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block, 128), jnp.float32),
            pltpu.VMEM((block, 128), jnp.float32),
            pltpu.VMEM((block, Dv), jnp.float32),
        ],
        interpret=interpret,
        name="kept_flash_attention",
        **_compiler_params(2, interpret),
    )(to3(q), to3(k), to3(v), keep.astype(jnp.int8))
    return jnp.swapaxes(out.reshape(B, H, S, Dv), 1, 2)


def kept_attention_dense(q, k, v, keep, scale: float):
    """The dense twin's arithmetic: every score written out (an
    ``[H, S, S]`` array: for tests at small sizes)."""
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                   preferred_element_type=jnp.float32) * scale
    kp = keep[:, None] != 0
    s = jnp.where(kp, s, _NEG)
    p = jnp.where(kp, jnp.exp(s - s.max(-1, keepdims=True)), 0.0)
    p = (p / p.sum(-1, keepdims=True)).astype(v.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", p, v,
                      preferred_element_type=jnp.float32).astype(q.dtype)
