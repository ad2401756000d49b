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

Design as ``decode_attention.py``'s paged kernel, and on its page walk
(``_page_walk``): grid = (B,), one step a row, and inside it a loop over
the pages the row owns (``lengths[b] // page + 1``, never past the
table; entries past a row's frontier are never read). The pools stay in
HBM; a VISIT is one or two pages of the row (``_latent_plan``, from the
shapes: two wherever their bytes are no more than the walk keeps in
flight, and all three cells' are), each page two copies (``[page, d_c]``
of latents, ``[page, d_r]`` of rotated keys) into one of ``depth`` VMEM
slots a pool, started ``depth - 1`` visits ahead of the one being
computed, across rows and grid steps alike, so a row of one visit has
its successors' pages on their way while it computes. A visit of two
pages is ONE score tile ``[H, 2 * page]`` and one ``p @ c``: at 64 heads
a visit's chain (products, softmax, the accumulator's read and write)
takes 0.61 us whether it holds one page or two, against 0.2 us of bytes
a page (PERF.md, PR 47); a row with an odd count of pages copies its
last page twice and masks the second by position. Online-softmax
statistics and the ``[H, d_c]`` accumulator live in VMEM scratch across
a row's visits. (Until PR 47 the grid was (B, npages) over
BlockSpec-fetched pages: every row paid a grid step a table column
whatever its context, 1,280 steps a call where 580 had a page to read.)

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
from .decode_attention import (_PAGED_IN_FLIGHT, _PAGED_VMEM_DEEP,
                               _page_walk, _walk_depth)

__all__ = ["mla_paged_decode_attention", "mla_paged_attention_dense",
           "mla_attention_dense", "mla_paged_supported"]

_NEG = -1e30


def _latent_vmem_bytes(H, page, dc, dr, itemsize, depth=2, pages=1) -> int:
    """VMEM the kernel needs, counted as ``_paged_vmem_bytes`` counts:
    the ``depth`` buffers a pool of ``pages`` pages each, the two
    queries in and u out (two pipeline buffers each), the softmax
    statistics and the accumulator, and the f32 temporaries of one
    visit's scores (six). A row's kept positions (``[npages, page]``
    f32, 68 kB at 66 pages) are not counted: the table's width is not
    the gate's to see."""
    visit = pages * page
    bufs = depth * visit * (dc + dr) * itemsize
    qo = 2 * H * (2 * dc + dr) * itemsize
    stats = H * (2 * 128 + dc) * 4
    scores = 6 * H * max(visit, 128) * 4
    return bufs + qo + stats + scores


def _latent_plan(H, page, dc, dr, itemsize):
    """(pages a visit, VMEM slots a pool), from the call's shapes alone.

    Pages: two where a visit of two is no more than ``_PAGED_IN_FLIGHT``
    bytes and fits ``_PAGED_VMEM_DEEP`` at two slots, else one. Slots:
    ``_paged_plan``'s rule (``_walk_depth``) for a visit's bytes. At the
    cells' shapes (bf16, a 512 + 128 row in pages of 128: 328 kB a visit
    of two) both 64 and 128 heads take (2, 3)."""
    vmem = partial(_latent_vmem_bytes, H, page, dc, dr, itemsize)
    fetch = page * (dc + dr) * itemsize
    pages = 2 if (2 * fetch <= _PAGED_IN_FLIGHT
                  and vmem(2, 2) <= _PAGED_VMEM_DEEP) else 1
    return pages, _walk_depth(pages * fetch,
                              lambda depth: vmem(depth, pages))


def _kernel(len_ref, tbl_ref, ql_ref, qr_ref, *refs, scale, page, npages,
            depth, pages, kept=False):
    """One grid step a row, and inside it a loop over the row's visits
    of ``pages`` pages, fetched ``depth - 1`` visits ahead
    (``_page_walk``). ``kept``: one more input ahead of the pools, the
    row's kept positions, a visit a row: [visits, pages * page]
    (1.0 | 0.0)."""
    refs = list(refs)
    keep_ref = refs.pop(0) if kept else None
    (c_hbm, r_hbm, o_ref, c_buf, r_buf, sem, cur, m_s, l_s, acc_s) = refs
    b, B = pl.program_id(0), pl.num_programs(0)

    def last_page(row):
        """The page the row's own position falls in; never past the
        table."""
        return jnp.minimum(len_ref[row] // page, npages - 1)

    def span(row):
        """Visits up to the row's last page, so at least one (a free
        slot, position 0)."""
        return 0, last_page(row) // pages + 1

    def fetch(row, j, slot):
        """The copies of visit ``j`` of ``row``. A page past the row's
        last is the last again (no table entry past the frontier is
        read): its columns lie past the row's position and are masked."""
        last = last_page(row)
        copies = []
        for h in range(pages):
            pid = tbl_ref[row * npages + jnp.minimum(j * pages + h, last)]
            rows = pl.ds(h * page, page)
            copies += [
                pltpu.make_async_copy(c_hbm.at[pid, 0], c_buf.at[slot, rows],
                                      sem.at[0, slot, h]),
                pltpu.make_async_copy(r_hbm.at[pid, 0], r_buf.at[slot, rows],
                                      sem.at[1, slot, h])]
        return copies

    visits = _page_walk(cur, depth, b, B, span, fetch)
    off = len_ref[b]
    lo, n = span(b)
    i0 = cur[3]
    m_s[...] = jnp.full_like(m_s, _NEG)
    l_s[...] = jnp.zeros_like(l_s)
    acc_s[...] = jnp.zeros_like(acc_s)
    ql = ql_ref[0]                                         # [H, d_c]
    qr = qr_ref[0]                                         # [H, d_r]

    def compute(j, slot):
        cb = c_buf[slot]                           # [pages * page, d_c]
        rb = r_buf[slot]                           # [pages * page, d_r]
        dn = (((1,), (1,)), ((), ()))
        s = (lax.dot_general(ql, cb, dn,
                             preferred_element_type=jnp.float32)
             + lax.dot_general(qr, rb, dn,
                               preferred_element_type=jnp.float32)) * scale
        cols = j * (pages * page) + lax.broadcasted_iota(
            jnp.int32, s.shape, 1)
        keep = cols <= off
        if kept:
            keep = keep & (keep_ref[0, pl.ds(j, 1), :] > 0.5)  # one row
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

    visits(i0, lo, n, compute)
    l = jnp.maximum(l_s[:, :1], 1e-30)
    o_ref[0] = (acc_s[...] / l).astype(o_ref.dtype)


# Mosaic's scoped VMEM default. ``_latent_vmem_bytes`` at two slots a
# pool tracks it closely: at d_c 512, d_r 128, page 128, bf16, the real
# compiler takes 1,448 heads (a count of 15.9 MB) and refuses 1,536
# (16.8 MB): AOT compiles for a v5e, PR 47.
_VMEM_SCOPED = 16 * 1024 * 1024


def mla_paged_supported(q_lat_shape, c_pool_shape, r_pool_shape) -> bool:
    """Mosaic shape gate: one query position per row (decode), one cache
    head, a lane-filling latent and rotated key (a page is copied whole
    from a pool in HBM: the models pool ``rope_cache_width`` columns,
    whole lanes), a sublane-tileable page and head count, and a row's
    blocks within the scoped VMEM at two slots a pool (counted for
    bf16, what the cells pool)."""
    if len(q_lat_shape) != 3:
        return False
    B, H, dc = q_lat_shape
    P, KV, page, dc2 = c_pool_shape
    dr = r_pool_shape[-1]
    if KV != 1 or r_pool_shape[1] != 1 or dc != dc2:
        return False
    if dc % 128 or dr % 128 or page % 16 or H % 8:
        return False
    return _latent_vmem_bytes(H, page, dc, dr, 2) <= _VMEM_SCOPED


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

    Table entries up to a row's frontier page must name pages of the
    pool; later entries are never read.

    Returns u [B, H, d_c] = softmax(scores) @ c, in q_lat's type.
    """
    return _latent_walk(q_lat, q_rope, c_pool, r_pool, block_tables,
                        lengths, keep, scale=float(scale),
                        interpret=interpret)


# Jitted on its own, as ``paged_decode_attention`` is: a decode program
# calls it once an attention with the same shapes, and then traces and
# lowers the kernel once. Its name holds neither kernel's: a trace
# selects the kernels' ops by theirs.
@partial(jax.jit, static_argnames=("scale", "interpret"))
def _latent_walk(q_lat, q_rope, c_pool, r_pool, block_tables, lengths,
                 keep, scale, interpret):
    B, H, dc = q_lat.shape
    dr = q_rope.shape[-1]
    page = c_pool.shape[2]
    npages = block_tables.shape[1]
    pages, depth = _latent_plan(H, page, dc, dr, c_pool.dtype.itemsize)
    nv = -(-npages // pages)                    # visits a full table
    lengths = jnp.asarray(lengths, jnp.int32).reshape(B)
    tbl = jnp.asarray(block_tables, jnp.int32).reshape(B * npages)

    def row_index(b, ln, tb):
        return (b, 0, 0)

    ins, in_specs, kw = [q_lat, q_rope], [
        pl.BlockSpec((1, H, dc), row_index),
        pl.BlockSpec((1, H, dr), row_index)], {}
    if keep is not None:
        keep = jnp.pad(keep.astype(jnp.float32),
                       ((0, 0), (0, (nv * pages - npages) * page)))
        ins.append(keep.reshape(B, nv, pages * page))
        in_specs.append(pl.BlockSpec((1, nv, pages * page), row_index))
        kw = {"kept": True}
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B,),
        in_specs=in_specs + [
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec((1, H, dc), row_index),
        scratch_shapes=[
            pltpu.VMEM((depth, pages * page, dc), c_pool.dtype),
            pltpu.VMEM((depth, pages * page, dr), r_pool.dtype),
            pltpu.SemaphoreType.DMA((2, depth, pages)),
            pltpu.SMEM((4,), jnp.int32),
            pltpu.VMEM((H, 128), jnp.float32),
            pltpu.VMEM((H, 128), jnp.float32),
            pltpu.VMEM((H, dc), jnp.float32),
        ],
    )
    return pl.pallas_call(
        partial(_kernel, scale=scale, page=page, npages=npages,
                depth=depth, pages=pages, **kw),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, H, dc), q_lat.dtype),
        interpret=interpret,
        name="mla_paged_decode_attention" if keep is None
        else "mla_paged_sparse_decode_attention",
        # one sequential axis: a step starts later steps' pages
        **_compiler_params(0, interpret),
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
