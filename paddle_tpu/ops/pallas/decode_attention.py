"""Pallas TPU decode attention: short q against a long KV cache.

TPU-native replacement for the reference's CUDA decode kernels
(reference: fluid/operators/fused/fused_multi_transformer_op.cu.h —
the 2,023-LoC masked cache-KV decoder loop — and
phi/kernels/fusion/gpu/block_multi_head_attention_kernel.cu, the
paged/block KV-cache attention kernel).

Design — the cache STREAMS through VMEM a block at a time; nothing is
ever resident at O(cache_len), and no step is spent on a block past a
row's frontier (``offset`` + new tokens, a SCALAR-PREFETCH input):

- ``decode_attention`` (contiguous cache): grid = (B, KV_heads,
  cache_blocks). Online-softmax statistics and the output accumulator
  live in VMEM scratch, carried across the sequentially-iterated
  cache-block axis. The BlockSpec index maps clamp the cache block index
  to the last valid block, so blocks past the frontier are never DMA'd
  from HBM; compute for those steps is skipped with ``pl.when`` (each
  still costs its grid step).
- ``paged_decode_attention`` (page pool): the work follows the pages the
  rows own. Grid = (B * KV/hb,), one step per row and block of ``hb`` KV
  heads; inside it a ``fori_loop`` over the row's OWN page count. One
  page of one grid step is a VISIT: ``[hb, page, D]`` of K and of V
  (contiguous in the head-major pool, which stays in HBM) copied by hand
  into one of ``depth`` VMEM slots a pool. The kernel's visits, in grid
  order, are ONE sequence, and a cursor in SMEM runs ``depth - 1``
  visits ahead of the one being computed, across rows and grid steps
  alike (``_page_walk``: the cursor exists once, and the latent kernel
  of ``mla_attention.py`` walks its one-head pools on it too): a row of
  one page (a free slot, length 0, costs one step and one page) has its
  successors' pages on their way while it computes, and the batch's
  last visits find nothing left to start. Why: with one
  fetch in flight, started when the page before it began to compute, a
  page cost ``0.32 us + bytes / 819 GB/s`` on a v5e whatever its size
  (half the bandwidth at 262 kB a fetch); of that 0.32 us the copies'
  start was the part a second fetch in flight hides, and the visit's
  own compute chain the rest (PERF.md, PR 36). ``hb`` and ``depth``
  follow the shape (``_paged_plan``, a pure function, what the tests
  and ``tools/paged_attention_timing.py`` read): every KV head when the
  q rows are few (decode), one head for a prefill bucket; slots enough
  that two fetches and half a megabyte fly beside the page being
  computed, and never at the price of a head. The heads of a fetch are
  the BATCH of the visit's two products: scores are ``[hb, Sq*G,
  page]``, one ``[Sq*G, D] x [D, page]`` product a head, masked by
  position alone. (Until PR 36 they shared one ``[hb*Sq*G, hb*page]``
  product whose mask also kept a row to its own head's columns: at
  ``hb`` heads ``hb`` times the softmax for the same MXU tiles, 0.47
  against 0.41 us a page of compute at 4 heads.)
  K and V may differ in width (the result is V's), a learned SINK logit
  a query head may join the softmax's denominator, and with ``window=``
  (kernel name ``paged_window_decode_attention``) the table is a ring of
  pages a row and a step visits only the pages that intersect the row's
  last ``window`` positions, masked by position.
- GQA is native: the q heads of one KV group form the sublane axis of a
  single [Sq*G, D] block, so the cache is read once per KV head (the
  dense fallback repeats it per q head).

The q rows sit at absolute positions offset..offset+Sq-1 and attend to
cache positions <= their own (causal within the freshly-appended chunk,
everything before ``offset`` visible). This covers both decode (Sq=1)
and chunked prefill (Sq=block).

Layout: q [B, Sq, H, D], caches [B, KV, M, D] — head-major so each
head's [M, D] plane is a contiguous Mosaic-tileable block (the
static-shape cache layout of models/llama.py).
"""
from __future__ import annotations

import operator
from functools import partial
from typing import NamedTuple

import jax
import numpy as np
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import (_BLOCKS_LARGE as _BLOCKS, compiler_params as
               _compiler_params, pick_block as _pick_block)

__all__ = ["decode_attention", "paged_decode_attention",
           "paged_attention_dense", "paged_supported", "paged_kv_write",
           "attention_dense_masked"]

_NEG = -1e30


def _kernel(len_ref, q_ref, k_ref, v_ref, o_ref, m_s, l_s, acc_s, *,
            scale, block_kv, nkv, Sq, G):
    j = pl.program_id(2)
    off = len_ref[pl.program_id(0)]       # this row's q start (ragged)
    j_last = (off + Sq - 1) // block_kv   # last cache block with valid cols

    @pl.when(j == 0)
    def _():
        m_s[...] = jnp.full_like(m_s, _NEG)
        l_s[...] = jnp.zeros_like(l_s)
        acc_s[...] = jnp.zeros_like(acc_s)

    @pl.when(j <= j_last)
    def _():
        qb = q_ref[0, :, 0, :, :].reshape(Sq * G, -1)      # [Sq*G, D]
        kb = k_ref[0, 0]                                   # [bkv, D]
        vb = v_ref[0, 0]
        s = lax.dot_general(qb, kb, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
        rows = lax.broadcasted_iota(jnp.int32, (Sq * G, block_kv), 0) // G
        cols = j * block_kv + lax.broadcasted_iota(
            jnp.int32, (Sq * G, block_kv), 1)
        keep = cols <= off + rows
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
        o_ref[0, :, 0, :, :] = (acc_s[...] / l).reshape(
            Sq, G, -1).astype(o_ref.dtype)


def supported(q_shape, cache_shape) -> bool:
    B, Sq, H, D = q_shape
    KV, M = cache_shape[1], cache_shape[2]
    if H % KV or _pick_block(M, prefer=_BLOCKS) <= 0:
        return False
    # D must fill whole VPU lanes: the in-kernel [Sq,G,D]->[Sq*G,D]
    # reshape with sub-lane D (e.g. tiny-model D=16) sends Mosaic into
    # a pathological relayout (observed: compile hang on v5e)
    if D % 128 != 0:
        return False
    return Sq * (H // KV) <= 2048  # q block must sit in VMEM


def decode_attention(q, k_cache, v_cache, offset, scale=None,
                     interpret=False):
    """q [B,Sq,H,D] against caches [B,KV,M,D] (head-major: each head's
    [M,D] plane is contiguous, the Mosaic-tileable layout); cache
    positions <= offset+row are attended. offset may be traced, and may
    be a PER-ROW vector [B] (ragged batches: each row's frontier clamps
    its own DMA + mask independently)."""
    B, Sq, H, D = q.shape
    KV, M = k_cache.shape[1], k_cache.shape[2]
    G = H // KV
    if scale is None:
        scale = 1.0 / np.sqrt(D)
    block_kv = _pick_block(M, prefer=_BLOCKS)
    nkv = M // block_kv
    q5 = q.reshape(B, Sq, KV, G, D)
    lengths = jnp.broadcast_to(jnp.asarray(offset, jnp.int32).reshape(-1),
                               (B,))

    def kv_index(b, h, j, ln):
        return (b, h, jnp.minimum(j, (ln[b] + Sq - 1) // block_kv), 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(B, KV, nkv),
        in_specs=[
            pl.BlockSpec((1, Sq, 1, G, D), lambda b, h, j, ln:
                         (b, 0, h, 0, 0)),
            pl.BlockSpec((1, 1, block_kv, D), kv_index),
            pl.BlockSpec((1, 1, block_kv, D), kv_index),
        ],
        out_specs=pl.BlockSpec((1, Sq, 1, G, D),
                               lambda b, h, j, ln: (b, 0, h, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((Sq * G, 128), jnp.float32),
            pltpu.VMEM((Sq * G, 128), jnp.float32),
            pltpu.VMEM((Sq * G, D), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        partial(_kernel, scale=scale, block_kv=block_kv, nkv=nkv, Sq=Sq,
                G=G),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, Sq, KV, G, D), q.dtype),
        interpret=interpret,
        name="decode_attention",
        **_compiler_params(2, interpret),
    )(lengths, q5, k_cache, v_cache)
    return out.reshape(B, Sq, H, D)


# ---------------------------------------------------------------------------
# Paged (block-table) KV cache attention
# ---------------------------------------------------------------------------
# What a block of several KV heads may hold in VMEM at TWO slots a pool,
# by the count of ``_paged_vmem_bytes``: the rule that picks ``hb``. A
# quarter of Mosaic's 16 MiB scoped default: the count of the compiler's
# temporaries is an estimate.
_PAGED_VMEM_BUDGET = 4 * 1024 * 1024
# What the same count may reach with the slots the lookahead adds (they
# are counted exactly: page buffers). Half of the scoped default, so no
# call raises ``vmem_limit_bytes`` for its depth.
_PAGED_VMEM_DEEP = 8 * 1024 * 1024
# Bytes to keep on their way beside the page being computed: what the
# chip moves in the ~0.5 us a copy takes from ``start()`` to its first
# byte (PERF.md, PR 36). Never fewer than two fetches, whatever their
# size: with one, started as the page before it begins to compute, the
# queue behind a finished copy is empty and every page pays that start
# (786 kB a fetch: 1.27 us a page with one in flight, 1.15 with two,
# the copies alone 1.15).
_PAGED_IN_FLIGHT = 512 * 1024
_PAGED_MAX_DEPTH = 4


class PagedPlan(NamedTuple):
    """What ``paged_decode_attention`` does with a call's shapes."""
    hb: int                 # KV heads one fetch brings
    depth: int              # VMEM slots a pool: depth - 1 fetches fly
    in_flight_bytes: int    # (depth - 1) fetches of K and V


def _paged_vmem_bytes(hb, Sq, G, page, D, itemsize, Dv=None,
                      depth=2) -> int:
    """VMEM the paged kernel needs for a block of ``hb`` KV heads: the
    ``depth`` page buffers of K (``D`` wide) and of V (``Dv`` wide,
    ``D`` where not given), q in and o out (two pipeline buffers each),
    the softmax statistics and the accumulator, and the f32 temporaries
    of one page's scores (the score, its mask bound, the probabilities
    in f32 and in the pool's dtype, and what the compiler keeps beside
    them: counted as six). The scores are counted as ``[hb*Sq*G,
    hb*page]``, the heads' one shared product until PR 36; they are
    ``[hb, Sq*G, page]`` since, a product a head, so the count is an
    upper bound and every call keeps the ``hb`` it had."""
    Dv = D if Dv is None else Dv
    rows, cols = hb * Sq * G, hb * page
    pages = depth * cols * (D + Dv) * itemsize
    qo = 2 * rows * (D + Dv) * itemsize
    stats = rows * (2 * 128 + Dv) * 4
    scores = 6 * rows * max(cols, 128) * 4
    return pages + qo + stats + scores


def _paged_plan(Sq, G, KV, page, D, itemsize, Dv=None) -> PagedPlan:
    """The one rule, from the call's shapes alone.

    ``hb``: the largest divisor of ``KV`` whose block fits
    ``_PAGED_VMEM_BUDGET`` at two slots; one head where none does (the
    gate's ``Sq*G <= 2048`` bounds that block): few q rows take every
    head and a prefill bucket takes one.

    ``depth``: slots enough that two fetches, and ``_PAGED_IN_FLIGHT``
    bytes, fly beside the page being computed: 3, or
    ``_PAGED_MAX_DEPTH`` where a fetch is small; fewer only where
    ``_PAGED_VMEM_DEEP`` has no room (a single head at the gate's
    edge). Depth never costs a head: ``hb`` is chosen first."""
    Dv = D if Dv is None else Dv
    hb = next((h for h in range(KV, 1, -1) if KV % h == 0
               and _paged_vmem_bytes(h, Sq, G, page, D, itemsize, Dv)
               <= _PAGED_VMEM_BUDGET), 1)
    fetch = hb * page * (D + Dv) * itemsize
    depth = _walk_depth(fetch, lambda depth: _paged_vmem_bytes(
        hb, Sq, G, page, D, itemsize, Dv, depth))
    return PagedPlan(hb, depth, (depth - 1) * fetch)


def _walk_depth(fetch, vmem_bytes) -> int:
    """VMEM slots a pool for a page walk whose visit copies ``fetch``
    bytes (``_paged_plan``'s rule, and the latent kernel's): enough that
    two fetches, and ``_PAGED_IN_FLIGHT`` bytes, fly beside the page
    being computed, at most ``_PAGED_MAX_DEPTH``; fewer only where
    ``vmem_bytes(depth)`` passes ``_PAGED_VMEM_DEEP``."""
    depth = min(max(3, 1 + -(-_PAGED_IN_FLIGHT // fetch)), _PAGED_MAX_DEPTH)
    while depth > 2 and vmem_bytes(depth) > _PAGED_VMEM_DEEP:
        depth -= 1
    return depth


def _page_walk(cur, depth, t, steps, span, fetch):
    """The visits of a paged kernel as ONE sequence, for ``_paged_kernel``
    and the latent kernel (``mla_attention.py``), each with its own
    pools. A VISIT is one page of one grid step; the kernel's visits, in
    grid order, form one sequence, and a cursor runs ``depth - 1``
    visits ahead of the one being computed, across rows and grid steps,
    starting each visit's copies into the slot the visit before the
    current one has left.

    ``cur``: four int32 in SMEM = (the grid step of the next visit to
    fetch, its visit in that step, visits fetched, visits computed).
    ``span(step)`` -> (first logical page, pages) of a grid step;
    ``fetch(step, j, slot)`` -> the copies of logical page ``j`` of that
    step into slot ``slot``. Called once a grid step ``t`` of ``steps``,
    before the step's own work: at the first step it starts the first
    ``depth - 1`` visits. Returns ``visits(i0, lo, n, compute)``: the
    step's ``n`` visits from page ``lo``, where ``i0 = cur[3]`` is read
    by the caller beside its ``span``; each waits for its page, having
    started the one ``depth - 1`` ahead, and runs ``compute(k, slot)``."""

    def issue():
        """Start the copies of the next visit not yet fetched, if the
        batch has one left, and move the cursor on."""
        step, k, i = cur[0], cur[1], cur[2]

        @pl.when(step < steps)
        def _():
            first, pages = span(step)
            for copy in fetch(step, first + k, i % depth):
                copy.start()
            end = k + 1 >= pages
            cur[0] = jnp.where(end, step + 1, step)
            cur[1] = jnp.where(end, 0, k + 1)
            cur[2] = i + 1

    @pl.when(t == 0)
    def _():
        for c in range(4):
            cur[c] = 0
        for _ in range(depth - 1):
            issue()

    def visits(i0, lo, n, compute):
        def visit(k, _):
            slot = (i0 + k) % depth
            issue()         # into the slot the visit before this one read
            for copy in fetch(t, lo + k, slot):
                copy.wait()
            compute(k, slot)

        lax.fori_loop(0, n, visit, None)
        cur[3] = i0 + n

    return visits


def _paged_kernel(len_ref, tbl_ref, q_ref, *refs, scale, page, npages, Sq,
                  G, hb, nh, depth, window=None, sink=False, kept=False):
    """One grid step a (row, block of ``hb`` KV heads), and inside it a
    loop over the pages the row owns, fetched ``depth - 1`` visits ahead
    (``_page_walk``). The heads of a fetch are the batch of a visit's
    products: rows are (s, g) within a head, and the mask knows
    positions only.

    ``window``: the rows see only the last ``window`` positions up to
    their own, the table is a RING of ``npages`` columns (logical page
    ``j`` sits in column ``j % npages``) and a step visits only the
    pages that intersect the window. ``sink``: one more input, a logit a
    query row that takes weight in the softmax and gives no value.
    ``kept``: one more input, the row's KEPT positions ``[npages, page]``
    (1.0 | 0.0): a key takes part only where it is set as well."""
    refs = list(refs)
    sink_ref = refs.pop(0) if sink else None
    keep_ref = refs.pop(0) if kept else None
    (k_hbm, v_hbm, o_ref, k_buf, v_buf, sem, cur, m_s, l_s,
     acc_s) = refs
    t, steps = pl.program_id(0), pl.num_programs(0)
    b = t // nh
    rows = Sq * G

    def span(row):
        """(first logical page, pages) a grid step of ``row`` visits: up
        to the page the last q row's own position falls in, so at least
        one (a free slot, position 0); never past the table (a ring has
        no end: its columns are reused), from the first page the window
        of the first q row reaches."""
        last = (len_ref[row] + Sq - 1) // page
        if window is None:
            return 0, jnp.minimum(last, npages - 1) + 1
        lo = jnp.maximum(len_ref[row] - (window - 1), 0) // page
        return lo, last + 1 - lo

    def fetch(step, j, slot):
        """The copies of logical page ``j`` of grid step ``step``: K and
        V of ``hb`` heads, ``[hb, page, D]`` contiguous in each pool."""
        pid = tbl_ref[step // nh * npages
                      + (j if window is None else j % npages)]
        heads = pl.ds((step % nh) * hb, hb)
        return (pltpu.make_async_copy(k_hbm.at[pid, heads], k_buf.at[slot],
                                      sem.at[0, slot]),
                pltpu.make_async_copy(v_hbm.at[pid, heads], v_buf.at[slot],
                                      sem.at[1, slot]))

    visits = _page_walk(cur, depth, t, steps,
                        lambda step: span(step // nh), fetch)
    off = len_ref[b]
    lo, n = span(b)
    i0 = cur[3]
    m_s[...] = jnp.full_like(m_s, _NEG)
    l_s[...] = jnp.zeros_like(l_s)
    acc_s[...] = jnp.zeros_like(acc_s)
    # one [Sq*G, D] block a head, rows (s, g): the heads are the batch
    # of both products
    qb = jnp.stack([q_ref[0, :, h].reshape(rows, -1) for h in range(hb)])

    def mask_bound():
        """Column p of page j is position j*page + p; row (s, g) sees
        it if the position is <= off + s: if ``j * page <= bound``. The
        same for every head."""
        ri = lax.broadcasted_iota(jnp.int32, (rows, page), 0)
        ci = lax.broadcasted_iota(jnp.int32, (rows, page), 1)
        return (off + ri // G - ci)[None]

    # the same for every page. A block of several heads has few rows; a
    # single head may fill VMEM (the gate's edge), and builds it page by
    # page as its grid steps did
    bound = mask_bound() if hb > 1 else None

    def compute(k, slot):
        s = lax.dot_general(qb, k_buf[slot], (((2,), (2,)), ((0,), (0,))),
                            preferred_element_type=jnp.float32) * scale
        first = (lo + k) * page             # the page's first position
        bd = mask_bound() if bound is None else bound
        keep = first <= bd
        if window is not None:  # and no further back than the window
            keep = keep & (first > bd - window)
        if kept:                # and only what the row's index chose
            keep = keep & (keep_ref[0, pl.ds(lo + k, 1), :] > 0.5)[None]
        s = jnp.where(keep, s, _NEG)                 # [hb, rows, page]
        m_prev = m_s[:, :, :1]
        m_new = jnp.maximum(m_prev, jnp.max(s, -1, keepdims=True))
        p = jnp.where(keep, jnp.exp(s - m_new), 0.0)
        corr = jnp.exp(m_prev - m_new)
        l_s[:, :, :1] = l_s[:, :, :1] * corr + jnp.sum(p, -1, keepdims=True)
        acc_s[...] = acc_s[...] * corr + lax.dot_general(
            p.astype(v_buf.dtype), v_buf[slot],
            (((2,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.float32)
        m_s[:, :, :1] = m_new

    visits(i0, lo, n, compute)
    if not sink:
        out = acc_s[...] / jnp.maximum(l_s[:, :, :1], 1e-30)
    else:   # the sink joins the denominator as one more score, no value
        m, sk = m_s[:, :, :1], sink_ref[0]               # [hb, rows, 1]
        m_fin = jnp.maximum(m, sk)
        corr = jnp.exp(m - m_fin)
        out = acc_s[...] * corr / (l_s[:, :, :1] * corr + jnp.exp(sk - m_fin))
    for h in range(hb):
        o_ref[0, :, h] = out[h].reshape(Sq, G, -1).astype(o_ref.dtype)


def paged_supported(q_shape, pool_shape, v_shape=None) -> bool:
    B, Sq, H, D = q_shape
    P, KV, page = pool_shape[0], pool_shape[1], pool_shape[2]
    if H % KV or D % 128 != 0:
        return False
    if v_shape is not None and v_shape[-1] % 128 != 0:
        return False
    if page % 8 or page < 8:  # sublane-tileable page
        return False
    return Sq * (H // KV) <= 2048


# A prefill at offset 0 of more rows than this attends to its layer's
# fresh K/V as flash attention even where ``paged_supported`` admits the
# shape. On a v5e at 32 query heads on 8 KV heads of 128 and a table of 19
# pages the flash form alone is the faster at every bucket its gate
# admits, us a call, paged | flash (``tools/paged_attention_timing.py
# --prefill``; my chip runs, PR 43): 32.7 | 22.8 at 128 rows, 61.0 | 34.2
# at 256, 166.7 | 69.4 at 512. The buckets the paged kernel serves keep
# it all the same: they are a few percent of a serving cell's busy time,
# and the chat cell's warm ``setup_s`` read +11% with every bucket on the
# flash kernel against +5.6% with two (readings that a capped compile
# cache shared by four trees confounds: PERF.md section 6 has both
# sides). Lower it once a clean reading shows no set-up cost.
FLASH_OVER_ROWS = 512


def paged_attention_form(q_shape, pool_shape, fresh_shape=None, offset=None,
                         valid=None) -> str:
    """The form a layer's attention over the page pool takes where the
    kernels are on (a dispatch site runs "dense" wherever they are not),
    from what a trace can see:

    - ``"flash"``: ``flash_attention.flash_attention_gqa`` over the
      ``[B, S, KV, D]`` K/V the call has just written (``fresh_shape``),
      where ``offset`` is a concrete 0 (``_concrete_zero``: the row's
      cache then holds nothing the call did not write itself), no
      ``valid`` metadata came, that kernel's gate admits the shape, and
      the prompt has more than ``FLASH_OVER_ROWS`` rows or the paged
      kernel refuses it. Rows past a prompt's end lie after every real
      row, so under the causal mask no real row sees them, through the
      pool or not.
    - ``"paged"``: ``paged_decode_attention`` (``paged_supported``).
    - ``"dense"``: ``paged_attention_dense``."""
    from .flash_attention import flash_gqa_supported

    paged = paged_supported(q_shape, pool_shape)
    fresh = (fresh_shape is not None and valid is None
             and _concrete_zero(offset)
             and flash_gqa_supported(q_shape, fresh_shape))
    if fresh and (q_shape[1] > FLASH_OVER_ROWS or not paged):
        return "flash"
    return "paged" if paged else "dense"


@partial(jax.jit, static_argnames=("scale", "interpret", "window"))
def paged_decode_attention(q, k_pool, v_pool, block_tables, lengths,
                           scale=None, interpret=False, sinks=None,
                           window=None, keep=None):
    """Block-table KV attention (the TPU redesign of the reference's
    paged cache kernel: phi/kernels/fusion/gpu/
    block_multi_head_attention_kernel.cu + block_attn.h — there, CUDA
    threads chase the block table; here a loop inside the kernel does:
    the physical page id is read from a scalar-prefetched table and the
    page is copied from the pool, which stays in HBM, so the kernel
    fetches exactly the pages a row owns and spends no step on a page
    past its frontier).

    q            [B, Sq, H, D]  rows at absolute positions
                                lengths[b]..lengths[b]+Sq-1
    k/v_pool     [P, KV, page, D / Dv]  shared physical page pool,
                                head-major pages (each [page, D] plane
                                contiguous); V may be narrower or wider
                                than K, and the result is ``Dv`` wide
    block_tables [B, npages]    logical->physical page map per row
    lengths      [B]            tokens already in cache per row (ragged)
    sinks        None or [H]    a learned logit a query head: it takes
                                weight in the softmax's denominator and
                                gives no value
    window       None or int    a row sees only the last ``window``
                                positions, its own included; the table
                                is then a RING, ``[B, ring]``: logical
                                page j sits in column ``j % ring``, and
                                only the pages that intersect the window
                                are fetched (kernel name
                                ``paged_window_decode_attention``)
    keep         None or [B, npages * page] bool: the positions a row's
                                learned index KEPT (logical positions,
                                page-major as the table); a key takes
                                part only if it is also kept. Every page
                                up to the frontier is still walked
                                (kernel name
                                ``paged_sparse_decode_attention``)

    Table entries up to a row's frontier page must name pages of the
    pool; later entries are never read.

    Jitted on its own: a serving program calls it once a layer with the
    same shapes, and then traces and lowers it once, not once a layer
    (a second of set-up a program at 16 layers).
    """
    B, Sq, H, D = q.shape
    P, KV, page = k_pool.shape[0], k_pool.shape[1], k_pool.shape[2]
    Dv = v_pool.shape[-1]
    npages = block_tables.shape[1]
    G = H // KV
    if scale is None:
        scale = 1.0 / np.sqrt(D)
    hb, depth, _ = _paged_plan(Sq, G, KV, page, D, k_pool.dtype.itemsize,
                               Dv)
    nh = KV // hb
    rows = Sq * G
    q5 = q.reshape(B, Sq, KV, G, D)
    lengths = jnp.asarray(lengths, jnp.int32).reshape(B)
    tbl = jnp.asarray(block_tables, jnp.int32).reshape(B * npages)

    def q_index(t, ln, tb):
        return (t // nh, 0, t % nh, 0, 0)

    ins, in_specs = [q5], [pl.BlockSpec((1, Sq, hb, G, D), q_index)]
    if sinks is not None:
        # one logit a score row, in the kernel's order: head, then (s, g)
        ins.append(jnp.broadcast_to(
            jnp.asarray(sinks, jnp.float32).reshape(nh, hb, 1, G),
            (nh, hb, Sq, G)).reshape(nh, hb, rows, 1))
        in_specs.append(pl.BlockSpec((1, hb, rows, 1),
                                     lambda t, ln, tb: (t % nh, 0, 0, 0)))
    if keep is not None:
        ins.append(keep.astype(jnp.float32).reshape(B, npages, page))
        in_specs.append(pl.BlockSpec((1, npages, page),
                                     lambda t, ln, tb: (t // nh, 0, 0)))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B * nh,),
        in_specs=in_specs + [
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec((1, Sq, hb, G, Dv), q_index),
        scratch_shapes=[
            pltpu.VMEM((depth, hb, page, D), k_pool.dtype),
            pltpu.VMEM((depth, hb, page, Dv), v_pool.dtype),
            pltpu.SemaphoreType.DMA((2, depth)),
            pltpu.SMEM((4,), jnp.int32),
            pltpu.VMEM((hb, rows, 128), jnp.float32),
            pltpu.VMEM((hb, rows, 128), jnp.float32),
            pltpu.VMEM((hb, rows, Dv), jnp.float32),
        ],
    )
    kw = {}
    if window is not None:
        kw["window"] = int(window)
    if sinks is not None:
        kw["sink"] = True
    if keep is not None:
        kw["kept"] = True
    name = "paged_decode_attention" if window is None \
        else "paged_window_decode_attention"
    if keep is not None:
        name = "paged_sparse_decode_attention"
    out = pl.pallas_call(
        partial(_paged_kernel, scale=scale, page=page, npages=npages,
                Sq=Sq, G=G, hb=hb, nh=nh, depth=depth, **kw),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, Sq, KV, G, Dv), q.dtype),
        interpret=interpret,
        name=name,
        # one sequential axis: a step starts later steps' pages
        **_compiler_params(0, interpret),
    )(lengths, tbl, *ins, k_pool, v_pool)
    return out.reshape(B, Sq, H, Dv)


def _concrete_zero(offset) -> bool:
    """True for an integer 0 known at trace time (int, numpy int, a
    concrete 0-d integer array); False for tracers and per-row
    offsets."""
    try:
        return operator.index(offset) == 0
    except TypeError:       # tracers, [B] arrays, floats
        return False


def paged_kv_write(k_pool, v_pool, k_new, v_new, block_tables, offset,
                   valid=None, ring=False, more=()):
    """Land new K/V rows in their physical pages, touching only the
    pages written; returns the updated ``(k_pool, v_pool)``. ``more``:
    further ``(pool, new)`` pairs under the same table (a layer that
    pools a third array, its index keys); their updated pools follow.

    k/v_pool     [P, KV, page, D]  (the layout the kernels above read;
                                   the two may differ in D: a latent
                                   cache pools a latent and a rotated key)
    k/v_new      [B, S, KV, D]     rows at positions offset[b]..+S-1
    block_tables [B, npages]       logical->physical page map per row
    offset       Python int, scalar or [B]
    valid        None or [B]: only the first valid[b] rows of row b are
                 real; the rest go to the page of the table's LAST
                 column (the caller's trash column)
    ring         the table is a ring (the page class of a window layer,
                 ``PagedKVCache``): position ``pos`` lands in column
                 ``(pos // page) % npages``, over what was ``npages``
                 pages back. Rows form only; a prefill (concrete
                 ``offset`` 0) is given the row's LOGICAL table instead,
                 in which the host has sent every page but the prompt's
                 last ``npages`` to the trash page

    The form is chosen from what the trace can see. An advanced-index
    scatter ``pool.at[pid, :, slot, :]`` has two scatter dims around a
    window dim, and XLA's TPU scatter then copies the WHOLE pool into a
    window-contiguous layout and back (two pool-sized copies per pool
    per call). Both forms below scatter along the leading dim of a
    view whose layout is the pool's own, so they compile in place:

    - whole pages, when ``offset`` is a concrete integer 0 (a Python or
      numpy int, not a tracer) and the rows fill pages from slot 0
      (``S < page`` or ``S % page == 0``: every prefill bucket):
      ``[KV, page, D]`` windows by page id (``[KV, S, D]`` at slot 0
      when ``S < page``);
    - rows otherwise (decode, ragged chunks, ``valid``): rows of ``D``
      into the flat ``[P*KV*page, D]`` view (a bitcast).

    A row whose position lies past the block table (a scan that steps
    on after a request's last token, a speculative write-ahead at the
    context limit) or whose page id is outside the pool is DROPPED, as
    the advanced-index scatter dropped it: no page is written for it.
    Several rows may name one slot (dead rows, padding: the trash
    page); any of them may win there, as with any scatter.
    """
    P, KV, page = k_pool.shape[:3]
    B, S = k_new.shape[0], k_new.shape[1]
    tbl = jnp.asarray(block_tables, jnp.int32)
    npages = tbl.shape[1]
    if (valid is None and not ring and _concrete_zero(offset)
            and (S < page or S % page == 0)):
        n = -(-S // page)
        pids = tbl[:, :n].reshape(B * n)

        def put(pool, new):
            new = new.astype(pool.dtype).reshape(B * n, -1, pool.shape[1],
                                                 pool.shape[-1])
            new = jnp.swapaxes(new, 1, 2)          # [B*n, KV, rows, D]
            return pool.at[pids, :, :new.shape[2], :].set(new)
    else:
        off = jnp.broadcast_to(
            jnp.asarray(offset, jnp.int32).reshape(-1), (B,))
        pos = off[:, None] + jnp.arange(S, dtype=jnp.int32)[None]
        if valid is not None:
            alive = jnp.arange(S, dtype=jnp.int32)[None] \
                < jnp.asarray(valid, jnp.int32).reshape(B, 1)
            pos = jnp.where(alive, pos, (npages - 1) * page)
        lpage = pos // page
        if ring:
            lpage = lpage % npages
        pid = jnp.take_along_axis(
            tbl, jnp.minimum(lpage, npages - 1), axis=1)        # [B,S]
        def flat_rows(KV):
            """Rows of the flat ``[P*KV*page, D]`` view of a pool of
            ``KV`` heads."""
            heads = jnp.arange(KV, dtype=jnp.int32)
            rows = (pid[:, :, None] * KV + heads) * page \
                + (pos % page)[:, :, None]
            # the flat index of a page id outside the pool would wrap
            # or land in another page: send it, and positions past the
            # table, one past the view's end, where the scatter drops it
            lost = (lpage >= npages) | (pid < 0) | (pid >= P)
            rows = jnp.where(lost[:, :, None], P * KV * page, rows)
            return rows.reshape(B * S * KV)

        by_heads = {KV: flat_rows(KV)}

        def put(pool, new):
            KVp, D = pool.shape[1], pool.shape[-1]
            if KVp not in by_heads:     # a third array of other heads
                by_heads[KVp] = flat_rows(KVp)
            flat = pool.reshape(P * KVp * page, D)
            flat = flat.at[by_heads[KVp]].set(
                new.astype(pool.dtype).reshape(B * S * KVp, D),
                mode="drop")
            return flat.reshape(P, KVp, page, D)

    return (put(k_pool, k_new), put(v_pool, v_new)) + tuple(
        put(pool, new) for pool, new in more)


def gather_pages(pool, block_tables):
    """A row's pages side by side: pool [P, KV, page, D] through
    block_tables [B, npages] -> [B, KV, npages * page, D]."""
    B, npages = block_tables.shape
    g = jnp.swapaxes(pool[block_tables], 1, 2)   # [B, KV, npages, page, D]
    return g.reshape(B, pool.shape[1], npages * pool.shape[2],
                     pool.shape[-1])


def paged_attention_dense(q, k_pool, v_pool, block_tables, lengths,
                          scale=None, sinks=None, window=None, keep=None):
    """XLA reference/fallback: gather the pages into a contiguous view,
    then run the (ragged-aware) dense cache attention. ``scale``,
    ``sinks`` and ``window`` as ``paged_decode_attention``; with a
    window the table is the ring, and a column is masked by the POSITION
    its page holds now (the newest logical page of its ring column that
    is not past the row's last q position). ``keep`` [B, npages * page]
    (or [B, Sq, npages * page]): the positions a row's index kept."""
    B, Sq, H, D = q.shape
    page = k_pool.shape[2]
    npages = block_tables.shape[1]
    gather = partial(gather_pages, block_tables=block_tables)
    if scale is None and sinks is None and window is None and keep is None:
        return _dense_ragged(q, gather(k_pool), gather(v_pool), lengths)
    off = jnp.asarray(lengths, jnp.int32).reshape(B)
    cols = jnp.arange(npages, dtype=jnp.int32)[None]
    if window is None:
        held = jnp.broadcast_to(cols, (B, npages))
    else:
        last = ((off + Sq - 1) // page)[:, None]
        held = last - (last - cols) % npages          # may be < 0: empty
    pos = (held[:, :, None] * page + jnp.arange(page, dtype=jnp.int32)
           ).reshape(B, npages * page)
    return attention_dense_masked(q, gather(k_pool), gather(v_pool), pos,
                                  off, scale, sinks, window, keep)


def attention_dense_masked(q, k_cache, v_cache, pos, lengths, scale=None,
                           sinks=None, window=None, keep=None):
    """Dense cache attention in float32 with everything the paged kernel
    takes: q [B, S, H, D] at positions lengths[b].., caches
    [B, KV, M, D / Dv] whose column m holds position ``pos[b, m]``
    (negative: nothing), a row sees positions <= its own and, with a
    window, > its own - window; ``sinks [H]`` joins the denominator;
    ``keep`` ([B, M] or [B, S, M] bool, by column): and only the columns
    it sets."""
    kept = keep
    B, S, H, D = q.shape
    KV, Dv = k_cache.shape[1], v_cache.shape[-1]
    rep = H // KV
    if scale is None:
        scale = 1.0 / np.sqrt(D)
    qf = jnp.swapaxes(q, 1, 2).astype(jnp.float32).reshape(B, KV, rep, S, D)
    scores = jnp.einsum("bkrsd,bkmd->bkrsm", qf,
                        k_cache.astype(jnp.float32)) * scale
    q_pos = (jnp.asarray(lengths, jnp.int32).reshape(B)[:, None]
             + jnp.arange(S, dtype=jnp.int32)[None])[:, :, None]   # [B,S,1]
    keep = (pos[:, None, :] >= 0) & (pos[:, None, :] <= q_pos)
    if window is not None:
        keep = keep & (pos[:, None, :] > q_pos - window)
    if kept is not None:
        keep = keep & (kept[:, None, :] if kept.ndim == 2 else kept)
    scores = jnp.where(keep[:, None, None], scores, _NEG)
    if sinks is not None:
        sk = jnp.broadcast_to(
            jnp.asarray(sinks, jnp.float32).reshape(1, KV, rep, 1, 1),
            scores.shape[:-1] + (1,))
        scores = jnp.concatenate([scores, sk], axis=-1)
    probs = jax.nn.softmax(scores, axis=-1)
    if sinks is not None:
        probs = probs[..., :-1]
    out = jnp.einsum("bkrsm,bkmd->bkrsd", probs,
                     v_cache.astype(jnp.float32))
    return jnp.swapaxes(out.reshape(B, H, S, Dv), 1, 2).astype(q.dtype)


def _dense_ragged(q, k_cache, v_cache, lengths):
    """Dense cache attention with per-row offsets (ragged).

    GQA never copies K/V per query head: q reshapes to [B, KV, rep, S,
    D] (query head h reads kv head h // rep) and the einsums broadcast
    the shared kv plane over the rep dim."""
    B, S, H, D = q.shape
    KV, M = k_cache.shape[1], k_cache.shape[2]
    rep = H // KV
    qf = jnp.swapaxes(q, 1, 2).astype(jnp.float32)          # [B, H, S, D]
    qf = qf.reshape(B, KV, rep, S, D)
    kf = k_cache.astype(jnp.float32)                        # [B, KV, M, D]
    vf = v_cache.astype(jnp.float32)
    scores = jnp.einsum("bkrsd,bkmd->bkrsm", qf, kf) / np.sqrt(D)
    off = jnp.asarray(lengths, jnp.int32).reshape(B)
    q_pos = off[:, None] + jnp.arange(S)[None, :]          # [B, S]
    keep = jnp.arange(M)[None, None, :] <= q_pos[:, :, None]
    scores = jnp.where(keep[:, None, None], scores, _NEG)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bkrsm,bkmd->bkrsd", probs, vf)
    return jnp.swapaxes(out.reshape(B, H, S, D), 1, 2).astype(q.dtype)
