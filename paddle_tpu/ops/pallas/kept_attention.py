"""Pallas TPU prefill attention over a KEPT set: causal self-attention in
which row ``t`` attends to the positions a mask allows and to no others.

The prefill of a layer whose queries attend to the keys (or latent cache
rows) a learned index kept (``ops/sparse_attention.py``): the index and
the exact selection give ``keep [B, S, S]`` (row t, key s; a subset of
``s <= t``), and this kernel is a flash forward with that mask in the
place of the causal one: the online-softmax statistics and the
accumulator live in VMEM scratch, the scores never leave VMEM. Keys may
be WIDER than values (192 against 128: a latent layer's unabsorbed
heads), so it has no backward.

What a grid step holds (PR 42; every reading is ms a layer alone on a
v5e at ``[1, 8192, 128, 192]`` against 128, the layout copies around
the call included (2.7 GB, ~3 ms by their bytes): 45.6 before, 24.1
now, 23.5 with the softmax taken out altogether; PERF.md section 6 has
the table):

- **The grid** is ``(B * H / G, n (n + 1) / 2)``: the second axis is the
  block pairs AT OR UNDER the diagonal alone, row block by row block,
  the ``(i, j)`` of a step read from two scalar-prefetched tables. No
  step lies above the diagonal (15,360 empty steps of 32,768 a call
  went: 3.5 ms); a row block's first step (``j == 0``) initialises the
  scratch, its last (``j == i``) writes the result.
- **G heads a step under ONE mask tile** (``_heads_a_step``: the
  largest divisor of ``H`` up to 8 that VMEM holds; 8 at 128 heads; 4
  read 25.0, 16 read 23.6 at three times the compile and twice the
  VMEM). The int8 ``[block, block]`` tile is fetched and turned into a
  predicate once for the ``G`` heads. The heads are unrolled, and head
  ``g + 1``'s score product is issued BEFORE head ``g``'s softmax, so
  the MXU works beside the VPU: a ``fori_loop`` over 8 heads read 40.0
  where the unrolled loop read 33.6, and this order took 2.0 more off
  the unrolled loop (29.0 -> 27.0 at 4 heads and keys of 1,024).
- **The mask is one select**: a score the mask drops becomes ``-inf``
  while the running maximum starts at a FINITE floor (``-1e30``), so
  ``exp(-inf - m) = 0`` with no NaN, a row with no kept key so far keeps
  ``l = 0`` and ``acc = 0`` (its ``m`` stays at the floor, its
  correction ``exp(0) = 1``), and no second select follows the ``exp``.
  An additive tile of ``0 / -inf`` (float32 made in the step from the
  int8 one, or bf16 / float32 made by XLA) read the select's time to
  0.4 ms at 4 heads; the int8 tile is the fewest bytes. A ``where`` of
  two CONSTANTS under the int8 tile's predicate is refused by Mosaic
  ("Invalid relayout").
- **The row sums stay a lane apart**: ``l`` is 128 per-lane partial
  sums a row (VPU adds alone), summed across lanes once, in a row
  block's last step; the cross-lane sum a step cost 3.6 ms (33.0 ->
  29.4 at 4 heads). The row maximum is needed before the ``exp`` and
  stays a cross-lane reduction a step. The scale stays one pass on the
  scores: folded into ``exp2``'s argument it read the same time.
- **Blocks**: rows and keys both ``block`` (512: ``cfg.attention_block``,
  the selection tiers' own). In the shipped form keys of 1,024 read
  25.7 (half the steps, 6% more pairs walked at the diagonal), rows of
  256 and of 1,024 against keys of 512 read 25.5 and 24.7; before the
  other changes keys of 256 read 60.1 against 32.5. Keys handed over
  transposed (``[H, D, S]``) read 24.0: no gain.

The plain-``lax`` form of the same mathematics is
``sparse_causal_attention``'s loop over key blocks: it writes a block's
float32 scores ``[heads, block, block]`` through HBM several times over,
which at 128 heads is most of a prefill's time.

Layout [B, S, H, D] in and out, as ``flash_attention_fwd``.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import compiler_params as _compiler_params

__all__ = ["kept_flash_attention", "kept_attention_dense",
           "kept_flash_supported"]

_NEG = -1e30            # the running maximum's floor: finite
BLOCK = 512
_MAX_HEADS = 8
# What a step's blocks, scratch and float32 temporaries may count
# (``_vmem_bytes``), and the scoped limit the call asks Mosaic for: the
# default 16 MiB holds 4 heads of 192 at blocks of 512, not 8.
_VMEM_BUDGET = 32 * 1024 * 1024
_VMEM_LIMIT = 48 * 1024 * 1024


def _vmem_bytes(G, block, D, Dv, itemsize) -> int:
    """VMEM a step of ``G`` heads counts: q, k, v, the result and the
    mask tile (two pipeline buffers each), the statistics and the
    accumulator, and the float32 temporaries of TWO heads' scores (one
    in its softmax, the next one's product), counted as four arrays
    each."""
    lanes = min(block, 128)
    blocks = 2 * (G * block * (2 * D + 2 * Dv) * itemsize + block * block)
    scratch = G * block * (2 * lanes + Dv) * 4
    return blocks + scratch + 2 * 4 * block * block * 4


def _heads_a_step(H, block, D, Dv, itemsize) -> int:
    """The largest divisor of ``H`` up to ``_MAX_HEADS`` whose step
    fits ``_VMEM_BUDGET``; one head where none does."""
    return next((g for g in range(min(H, _MAX_HEADS), 1, -1)
                 if H % g == 0 and _vmem_bytes(g, block, D, Dv, itemsize)
                 <= _VMEM_BUDGET), 1)


def _kernel(qi_ref, kj_ref, q_ref, k_ref, v_ref, keep_ref, o_ref,
            m_s, l_s, acc_s, *, scale):
    t = pl.program_id(1)
    i, j = qi_ref[t], kj_ref[t]
    G, block, lanes = l_s.shape

    @pl.when(j == 0)            # a row block's first step
    def _():
        m_s[...] = jnp.full_like(m_s, _NEG)
        l_s[...] = jnp.zeros_like(l_s)
        acc_s[...] = jnp.zeros_like(acc_s)

    keep = keep_ref[0] != 0     # [block, block], once for the G heads

    def product(g):
        return lax.dot_general(q_ref[g], k_ref[g], (((1,), (1,)), ((), ())),
                               preferred_element_type=jnp.float32)

    s_next = product(0)
    for g in range(G):
        s = s_next
        if g + 1 < G:           # the next head's product before this softmax
            s_next = product(g + 1)
        s = jnp.where(keep, s * scale, -jnp.inf)
        m_prev = m_s[g]                                 # lane-replicated
        m_new = jnp.maximum(m_prev, jnp.max(s, -1, keepdims=True))
        p = jnp.exp(s - m_new[:, :1])       # a dropped score: exp(-inf) = 0
        corr = jnp.exp(m_prev - m_new)
        part = p[:, :lanes]                 # per-lane partial row sums
        for w in range(1, block // lanes):
            part = part + p[:, w * lanes:(w + 1) * lanes]
        l_s[g] = l_s[g] * corr + part
        vb = v_ref[g]
        acc_s[g] = acc_s[g] * corr[:, :1] + lax.dot_general(
            p.astype(vb.dtype), vb, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_s[g] = m_new

    @pl.when(j == i)            # the diagonal block: a row block's last
    def _():
        for g in range(G):
            l = jnp.maximum(jnp.sum(l_s[g], -1, keepdims=True), 1e-30)
            o_ref[g] = (acc_s[g] / l).astype(o_ref.dtype)


def kept_flash_supported(q_shape, v_shape, block: int = BLOCK) -> bool:
    """Mosaic shape gate: whole blocks of rows that fill the lanes, a
    value head that fills the lanes, a key head of whole sublane tiles."""
    B, S, H, D = q_shape
    return (S % block == 0 and block % 128 == 0
            and v_shape[-1] % 128 == 0 and D % 64 == 0)


def kept_flash_attention(q, k, v, keep, scale: float, block: int = BLOCK,
                         interpret: bool = False):
    """q, k [B, S, H, D], v [B, S, H, Dv] at positions 0..S-1 (every
    head its own keys and values); keep [B, S, S] (bool or int8; row t,
    key s), a subset of ``s <= t`` with at least one key a row. Returns
    [B, S, H, Dv] in q's type: softmax over the kept keys alone."""
    B, S, H, D = q.shape
    Dv = v.shape[-1]
    n = S // block
    G = _heads_a_step(H, block, D, Dv, q.dtype.itemsize)
    lanes = min(block, 128)
    to3 = lambda x: jnp.swapaxes(x, 1, 2).reshape(B * H, S, x.shape[-1])
    # the block pairs at or under the diagonal, row block by row block
    qi, kj = np.tril_indices(n)
    rows = lambda b, t, qi, kj: (b, qi[t], 0)
    keys = lambda b, t, qi, kj: (b, kj[t], 0)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B * H // G, len(qi)),
        in_specs=[
            pl.BlockSpec((G, block, D), rows),
            pl.BlockSpec((G, block, D), keys),
            pl.BlockSpec((G, block, Dv), keys),
            pl.BlockSpec((1, block, block),
                         lambda b, t, qi, kj: (b // (H // G), qi[t], kj[t])),
        ],
        out_specs=pl.BlockSpec((G, block, Dv), rows),
        scratch_shapes=[
            pltpu.VMEM((G, block, lanes), jnp.float32),
            pltpu.VMEM((G, block, lanes), jnp.float32),
            pltpu.VMEM((G, block, Dv), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        partial(_kernel, scale=float(scale)),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B * H, S, Dv), q.dtype),
        interpret=interpret,
        name="kept_flash_attention",
        **_compiler_params(1, interpret, vmem_limit_bytes=_VMEM_LIMIT),
    )(jnp.asarray(qi, jnp.int32), jnp.asarray(kj, jnp.int32),
      to3(q), to3(k), to3(v), keep.astype(jnp.int8))
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
