"""Pallas TPU unified ragged paged attention: ONE kernel for mixed
prefill-chunk and decode rows over the shared KV page pool.

This is the serving-side redesign the Ragged Paged Attention paper
(PAPERS.md) builds: instead of one bucketed program per prompt prefill
plus a separate shared decode step (the head-of-line pathology — a long
prompt's prefill stalls every in-flight decode row), a single
``pallas_call`` serves a batch whose rows are RAGGED along two axes:

- ``starts[b]``   — the row's absolute cache position of its first new
  token this dispatch (prefill chunk offset, or the decode position),
- ``seq_lens[b]`` — how many of the row's ``Sb`` q slots carry real
  tokens: a prefill chunk feeds up to ``Sb``, a decode row exactly 1,
  an idle/empty slot 0 (its lane computes nothing and outputs zeros).

The row kind never reaches the kernel — decode IS a seq_len-1 chunk;
the scheduler (inference/serving.py) keeps ``kind`` host-side only.

Design, inherited from decode_attention.py's paged kernel:

- grid = (B, KV_heads, npages); the page axis streams through VMEM,
  online-softmax stats in scratch. The BlockSpec index map gathers the
  physical page id from the scalar-prefetched block table AND clamps
  the page index at each row's OWN frontier ``(start + seq_len - 1) //
  page`` — a decode row DMAs exactly the pages holding its history,
  never the ``Sb``-wide window a uniform chunk program would touch.
  That per-row clamp is where the unified program's HBM traffic comes
  in at or below the old prefill+decode two-program sum.
- causal masking is positional: q slot ``i`` of row ``b`` sits at
  absolute position ``starts[b] + i`` and attends cache positions
  ``<= starts[b] + i``; slots ``i >= seq_lens[b]`` are dead (masked
  everywhere, output zeroed).
- GQA native: the q heads of one KV group form the sublane axis, the
  pool is read once per KV head.

``ragged_paged_attention_dense`` is the XLA fallback (gather the pages,
ragged dense mask) — the CPU/tier-1 reference the kernel is
parity-gated against (bench ``serving_ragged_kernel_parity``).
"""
from __future__ import annotations

from functools import partial

import jax
import numpy as np
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import compiler_params as _compiler_params

__all__ = ["ragged_paged_attention", "ragged_paged_attention_dense",
           "ragged_supported"]

_NEG = -1e30


def _ragged_kernel(len_ref, nv_ref, tbl_ref, q_ref, k_ref, v_ref, o_ref,
                   m_s, l_s, acc_s, *, scale, page, npages, Sq, G):
    b = pl.program_id(0)
    j = pl.program_id(2)
    off = len_ref[b]                      # row's first q position
    nv = nv_ref[b]                        # valid q slots (0 = dead row)
    j_last = jnp.maximum(off + nv - 1, 0) // page

    @pl.when(j == 0)
    def _():
        m_s[...] = jnp.full_like(m_s, _NEG)
        l_s[...] = jnp.zeros_like(l_s)
        acc_s[...] = jnp.zeros_like(acc_s)

    @pl.when(j <= j_last)
    def _():
        qb = q_ref[0, :, 0, :, :].reshape(Sq * G, -1)      # [Sq*G, D]
        kb = k_ref[0, 0]                                   # [page, D]
        vb = v_ref[0, 0]
        s = lax.dot_general(qb, kb, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
        rows = lax.broadcasted_iota(jnp.int32, (Sq * G, page), 0) // G
        cols = j * page + lax.broadcasted_iota(
            jnp.int32, (Sq * G, page), 1)
        keep = (cols <= off + rows) & (rows < nv)
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

    @pl.when(j == npages - 1)
    def _():
        # dead slots (nv == 0) kept l == 0 -> output exactly 0, the
        # same definition the dense fallback zero-masks to
        l = jnp.maximum(l_s[:, :1], 1e-30)
        o_ref[0, :, 0, :, :] = (acc_s[...] / l).reshape(
            Sq, G, -1).astype(o_ref.dtype)


def ragged_supported(q_shape, pool_shape) -> bool:
    """Same Mosaic gates as the paged decode kernel: whole-lane head
    dim, sublane-tileable page, q block resident in VMEM."""
    B, Sq, H, D = q_shape
    KV, page = pool_shape[1], pool_shape[2]
    if H % KV or D % 128 != 0:
        return False
    if page % 8 or page < 8:
        return False
    return Sq * (H // KV) <= 2048


def ragged_paged_attention(q, k_pool, v_pool, block_tables, starts,
                           seq_lens, scale=None, interpret=False):
    """Unified mixed prefill/decode attention over the paged KV pool.

    q            [B, Sb, H, D]  slot i of row b sits at absolute cache
                                position starts[b]+i; only slots
                                i < seq_lens[b] are real
    k/v_pool     [P, KV, page, D]  shared physical page pool
    block_tables [B, npages]    logical->physical page map per row
    starts       [B]            first q position per row (= tokens
                                already in cache before this dispatch)
    seq_lens     [B]            valid q slots per row: prefill chunk
                                width, 1 for decode, 0 for a dead row
                                (outputs zeros, DMAs one clamped page)
    """
    B, Sq, H, D = q.shape
    KV, page = k_pool.shape[1], k_pool.shape[2]
    npages = block_tables.shape[1]
    G = H // KV
    if scale is None:
        scale = 1.0 / np.sqrt(D)
    q5 = q.reshape(B, Sq, KV, G, D)
    starts = jnp.asarray(starts, jnp.int32).reshape(B)
    seq_lens = jnp.asarray(seq_lens, jnp.int32).reshape(B)
    tbl = jnp.asarray(block_tables, jnp.int32).reshape(B * npages)

    def pool_index(b, h, j, ln, nv, tb):
        # clamp the streamed page index at the row's OWN frontier: a
        # decode row never DMAs the Sb-wide window a chunk row needs
        jc = jnp.minimum(j, jnp.maximum(ln[b] + nv[b] - 1, 0) // page)
        return (tb[b * npages + jc], h, 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(B, KV, npages),
        in_specs=[
            pl.BlockSpec((1, Sq, 1, G, D), lambda b, h, j, ln, nv, tb:
                         (b, 0, h, 0, 0)),
            pl.BlockSpec((1, 1, page, D), pool_index),
            pl.BlockSpec((1, 1, page, D), pool_index),
        ],
        out_specs=pl.BlockSpec((1, Sq, 1, G, D),
                               lambda b, h, j, ln, nv, tb: (b, 0, h, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((Sq * G, 128), jnp.float32),
            pltpu.VMEM((Sq * G, 128), jnp.float32),
            pltpu.VMEM((Sq * G, D), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        partial(_ragged_kernel, scale=scale, page=page, npages=npages,
                Sq=Sq, G=G),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, Sq, KV, G, D), q.dtype),
        interpret=interpret,
        name="ragged_paged_attention",
        **_compiler_params(2, interpret),
    )(starts, seq_lens, tbl, q5, k_pool, v_pool)
    return out.reshape(B, Sq, H, D)


def ragged_paged_attention_dense(q, k_pool, v_pool, block_tables,
                                 starts, seq_lens):
    """XLA reference/fallback: gather the pages into a contiguous view,
    run the doubly-ragged dense mask, zero the dead q slots (matching
    the kernel's l==0 -> 0 definition exactly)."""
    B, Sq, H, D = q.shape
    page = k_pool.shape[2]
    npages = block_tables.shape[1]

    def gather(pool):
        g = pool[block_tables]                  # [B, npages, KV, page, D]
        g = jnp.swapaxes(g, 1, 2)               # [B, KV, npages, page, D]
        return g.reshape(B, pool.shape[1], npages * page, D)

    k_cache, v_cache = gather(k_pool), gather(v_pool)
    KV, M = k_cache.shape[1], k_cache.shape[2]
    rep = H // KV
    qf = jnp.swapaxes(q, 1, 2).astype(jnp.float32)          # [B, H, Sq, D]
    qf = qf.reshape(B, KV, rep, Sq, D)
    kf = k_cache.astype(jnp.float32)
    vf = v_cache.astype(jnp.float32)
    scores = jnp.einsum("bkrsd,bkmd->bkrsm", qf, kf) / np.sqrt(D)
    off = jnp.asarray(starts, jnp.int32).reshape(B)
    nv = jnp.asarray(seq_lens, jnp.int32).reshape(B)
    q_pos = off[:, None] + jnp.arange(Sq)[None, :]           # [B, Sq]
    alive = jnp.arange(Sq)[None, :] < nv[:, None]            # [B, Sq]
    keep = (jnp.arange(M)[None, None, :] <= q_pos[:, :, None]) \
        & alive[:, :, None]
    scores = jnp.where(keep[:, None, None], scores, _NEG)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bkrsm,bkmd->bkrsd", probs, vf)
    out = jnp.where(alive[:, None, None, :, None], out, 0.0)
    return jnp.swapaxes(out.reshape(B, H, Sq, D), 1, 2).astype(q.dtype)
