"""Pallas TPU decode attention over a paged LATENT cache (multi-head
latent attention in its absorbed form).

One token's cache row is ``[c | k_r]``: a ``d_c``-wide latent shared by
every head and a ``d_r``-wide rotated key, also shared. With the
key-side up-projection absorbed into the query (``q_lat = q_nope @
W_k^T``) a head's score is ``q_lat . c + q_rope . k_r`` and its value is
the latent itself (``u = sum_s p * c(s)``; the caller applies ``W_v``).
So all H query heads read ONE row per token, and the value is the first
``d_c`` columns of the key: ``paged_decode_attention`` would read every
page twice (a K pool and a V pool) and once per KV head; this kernel
reads each referenced page once.

Design as ``decode_attention.py``'s paged kernel: grid = (B, npages),
the physical page id comes from the scalar-prefetched block table in
the BlockSpec index map, pages past a row's frontier are never fetched
(the index is clamped to the last valid page, so the DMA is elided) and
their compute is skipped; online-softmax statistics and the ``[H, d_c]``
accumulator live in VMEM scratch across the page axis.

Pools are ``[P, 1, page, d_c]`` and ``[P, 1, page, d_r]``: the page
pool's layout with ONE cache head, so ``paged_kv_write`` and the
engine's page programs serve them unchanged.

With ``keep=`` it is the same walk for a layer whose queries attend to a
SET of cache rows a learned index kept (``ops/sparse_attention.py``;
kernel name ``mla_paged_sparse_decode_attention``): one more input, the
row's kept positions
``[npages, page]``, and a cache row takes part only where it is set as
well, exactly. At 128 heads the absorbed form does 242 FLOP a byte of
cache row, the v5e's ridge, so the walk pays for the rows it masks
(passing over a page that holds no kept row cost more than it saved
where one row in 3.6 is kept: PERF.md, PR 41). It carries its own kernel
name in a device trace.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import compiler_params as _compiler_params

__all__ = ["mla_paged_decode_attention", "mla_paged_attention_dense",
           "mla_attention_dense", "mla_paged_supported"]

_NEG = -1e30


def _kernel(len_ref, tbl_ref, ql_ref, qr_ref, *refs, scale, page, npages,
            kept=False):
    """``kept``: one more input ahead of the pools, the row's kept
    positions [npages, page] (1.0 | 0.0)."""
    refs = list(refs)
    keep_ref = refs.pop(0) if kept else None
    c_ref, r_ref, o_ref, m_s, l_s, acc_s = refs
    j = pl.program_id(1)
    off = len_ref[pl.program_id(0)]
    j_last = off // page

    @pl.when(j == 0)
    def _():
        m_s[...] = jnp.full_like(m_s, _NEG)
        l_s[...] = jnp.zeros_like(l_s)
        acc_s[...] = jnp.zeros_like(acc_s)

    @pl.when(j <= j_last)
    def _():
        ql = ql_ref[0]                                     # [H, d_c]
        qr = qr_ref[0]                                     # [H, d_r]
        cb = c_ref[0, 0]                                   # [page, d_c]
        rb = r_ref[0, 0]                                   # [page, d_r]
        dn = (((1,), (1,)), ((), ()))
        s = (lax.dot_general(ql, cb, dn,
                             preferred_element_type=jnp.float32)
             + lax.dot_general(qr, rb, dn,
                               preferred_element_type=jnp.float32)) * scale
        cols = j * page + lax.broadcasted_iota(jnp.int32, s.shape, 1)
        keep = cols <= off
        if kept:
            keep = keep & (keep_ref[0, pl.ds(j, 1), :] > 0.5)  # [1, page]
        s = jnp.where(keep, s, _NEG)
        m_prev = m_s[:, :1]
        m_new = jnp.maximum(m_prev, jnp.max(s, -1, keepdims=True))
        p = jnp.where(keep, jnp.exp(s - m_new), 0.0)
        corr = jnp.exp(m_prev - m_new)
        l_s[:, :1] = l_s[:, :1] * corr + jnp.sum(p, -1, keepdims=True)
        acc_s[...] = acc_s[...] * corr + lax.dot_general(
            p.astype(cb.dtype), cb, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_s[:, :1] = m_new

    @pl.when(j == npages - 1)
    def _():
        l = jnp.maximum(l_s[:, :1], 1e-30)
        o_ref[0] = (acc_s[...] / l).astype(o_ref.dtype)


def mla_paged_supported(q_lat_shape, c_pool_shape, r_pool_shape) -> bool:
    """Mosaic shape gate: one query position per row (decode), one cache
    head, a lane-filling latent, a sublane-tileable page and head
    count."""
    if len(q_lat_shape) != 3:
        return False
    B, H, dc = q_lat_shape
    P, KV, page, dc2 = c_pool_shape
    if KV != 1 or r_pool_shape[1] != 1 or dc != dc2:
        return False
    if dc % 128 or page % 16 or H % 8:
        return False
    return H <= 2048


def mla_paged_decode_attention(q_lat, q_rope, c_pool, r_pool,
                               block_tables, lengths, scale,
                               interpret=False, keep=None):
    """Absorbed latent attention of ONE new position per row over the
    paged latent cache.

    q_lat        [B, H, d_c]    q_nope @ W_k^T (absorbed)
    q_rope       [B, H, d_r]    the rotated part of the query
    c_pool       [P, 1, page, d_c]  latents
    r_pool       [P, 1, page, d_r]  rotated shared keys
    block_tables [B, npages]    logical -> physical page per row
    lengths      [B]            tokens in cache BEFORE this position;
                                the row attends positions <= lengths[b]
                                (its own row is already written)
    scale        the softmax scale (the caller's: it carries YaRN's)
    keep         None or [B, npages * page] bool (by position): the
                 cache rows a row's index kept; the softmax then runs
                 over those alone, among the rows at or before its own,
                 and the kernel carries the name
                 ``mla_paged_sparse_decode_attention``

    Returns u [B, H, d_c] = softmax(scores) @ c, in q_lat's type.
    """
    B, H, dc = q_lat.shape
    dr = q_rope.shape[-1]
    page = c_pool.shape[2]
    npages = block_tables.shape[1]
    lengths = jnp.asarray(lengths, jnp.int32).reshape(B)
    tbl = jnp.asarray(block_tables, jnp.int32).reshape(B * npages)

    def pool_index(b, j, ln, tb):
        return (tb[b * npages + jnp.minimum(j, ln[b] // page)], 0, 0, 0)

    def row_index(b, j, ln, tb):
        return (b, 0, 0)

    ins, in_specs, kw = [q_lat, q_rope], [
        pl.BlockSpec((1, H, dc), row_index),
        pl.BlockSpec((1, H, dr), row_index)], {}
    if keep is not None:
        ins.append(keep.astype(jnp.float32).reshape(B, npages, page))
        in_specs.append(pl.BlockSpec((1, npages, page), row_index))
        kw = {"kept": True}
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, npages),
        in_specs=in_specs + [
            pl.BlockSpec((1, 1, page, dc), pool_index),
            pl.BlockSpec((1, 1, page, dr), pool_index),
        ],
        out_specs=pl.BlockSpec((1, H, dc), row_index),
        scratch_shapes=[
            pltpu.VMEM((H, 128), jnp.float32),
            pltpu.VMEM((H, 128), jnp.float32),
            pltpu.VMEM((H, dc), jnp.float32),
        ],
    )
    return pl.pallas_call(
        partial(_kernel, scale=float(scale), page=page, npages=npages,
                **kw),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, H, dc), q_lat.dtype),
        interpret=interpret,
        name="mla_paged_decode_attention" if keep is None
        else "mla_paged_sparse_decode_attention",
        **_compiler_params(1, interpret),
    )(lengths, tbl, *ins, c_pool, r_pool)


def mla_attention_dense(q_lat, q_rope, c, r, lengths, scale, keep=None):
    """The dense twin's arithmetic (and the general XLA path: any
    number S of new positions per row, any per-row offset).

    q_lat [B, S, H, d_c], q_rope [B, S, H, d_r] at positions
    lengths[b] .. lengths[b]+S-1; c [B, M, d_c], r [B, M, d_r] the
    contiguous cache; ``keep`` ([B, M] or [B, S, M] bool): and only the
    positions a row's index kept. Returns u [B, S, H, d_c]."""
    B, S = q_lat.shape[0], q_lat.shape[1]
    M = c.shape[1]
    f32 = jnp.float32
    s = (jnp.einsum("bshc,bmc->bhsm", q_lat.astype(f32), c.astype(f32))
         + jnp.einsum("bshr,bmr->bhsm", q_rope.astype(f32),
                      r.astype(f32))) * scale
    off = jnp.asarray(lengths, jnp.int32).reshape(B)
    q_pos = off[:, None] + jnp.arange(S)[None, :]
    seen = jnp.arange(M)[None, None, :] <= q_pos[:, :, None]   # [B,S,M]
    if keep is not None:
        seen = seen & (keep[:, None] if keep.ndim == 2 else keep)
    s = jnp.where(seen[:, None], s, _NEG)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhsm,bmc->bshc", p, c.astype(f32)).astype(
        q_lat.dtype)


def mla_paged_attention_dense(q_lat, q_rope, c_pool, r_pool, block_tables,
                              lengths, scale, keep=None):
    """XLA reference/fallback: gather each row's pages into a contiguous
    cache, then :func:`mla_attention_dense`."""
    B = q_lat.shape[0]
    page = c_pool.shape[2]
    npages = block_tables.shape[1]

    def gather(pool):
        return pool[block_tables][:, :, 0].reshape(
            B, npages * page, pool.shape[-1])

    return mla_attention_dense(q_lat, q_rope, gather(c_pool),
                               gather(r_pool), lengths, scale, keep)
