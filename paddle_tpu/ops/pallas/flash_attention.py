"""Pallas TPU flash attention (forward + backward kernels).

TPU-native replacement for the reference's dynloaded flashattn-v2 CUDA
library (reference: phi/kernels/gpu/flash_attn_kernel.cu,
flash_attn_grad_kernel.cu, backends/dynload/flashattn.h, python surface
nn/functional/flash_attention.py:147).

Design: K/V STREAM through VMEM as the innermost *grid* dimension (no
full-KV VMEM pin), with the online-softmax statistics (m, l) and the
output accumulator carried across grid steps in VMEM scratch — TPU grid
iteration is sequential over the last axis, which is exactly the
guarantee the recurrence needs. Causal blocks above the diagonal are
skipped two ways: the compute is guarded by ``pl.when`` and the
BlockSpec index map clamps to the last valid block so Pallas re-uses
the resident block instead of issuing a DMA.

Rectangular attention (seq_q != seq_kv) follows the flash-attn
convention: the q rows are the LAST seq_q rows of the seq_kv-length
sequence (q_offset = seq_kv - seq_q) under ``causal``.

Varlen/packed sequences are expressed with integer segment ids
(q_segment_ids [B, Sq], kv_segment_ids [B, Skv]): position pairs in
different segments never attend. ``flash_attn_unpadded`` builds these
from cu_seqlens (see ops/attention.py).

Backward (FlashAttention-2 recurrence, the capability of the
reference's flash_attn_grad_kernel.cu): the forward additionally emits
the per-row logsumexp L; backward recomputes P = exp(S - L) blockwise in
VMEM and runs TWO kernels — a dq kernel (grid over q blocks, kv
streaming innermost) and a dk/dv kernel (grid over kv blocks, q
streaming innermost); TPU has no atomics, so each output owns its
reduction. Residual memory is O(S) per head (L + delta), never O(S²).

Layout [B, S, H, D] (the paddle flash_attention layout). Grid:
(B*H, blocks, blocks); f32 accumulation; MXU-shaped tiles (128 lanes).
"""
from __future__ import annotations

from functools import partial

import jax
import numpy as np
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import (_BLOCKS_LARGE as _BLOCKS, compiler_params as
               _compiler_params, pick_block as _pick_block)

__all__ = ["flash_attention_fwd", "flash_attention_with_lse",
           "flash_attention_bwd", "flash_supported",
           "flash_attention_gqa", "flash_gqa_supported"]

_VMEM = pltpu.VMEM

_NEG = -1e30


def _mask(qi, j, block_q, block_kv, q_off, causal, qseg, kseg):
    """[block_q, block_kv] keep-mask (True = attend) or None if nothing
    is masked. qseg/kseg are VMEM blocks or None."""
    keep = None
    if causal:
        rows = q_off + qi * block_q + lax.broadcasted_iota(
            jnp.int32, (block_q, block_kv), 0)
        cols = j * block_kv + lax.broadcasted_iota(
            jnp.int32, (block_q, block_kv), 1)
        keep = rows >= cols
    if qseg is not None:
        same = qseg[0, 0][:, None] == kseg[0, 0][None, :]
        keep = same if keep is None else (keep & same)
    return keep


def _last_kv_block(qi, block_q, block_kv, q_off, causal, nkv):
    """Index of the last kv block any row of q-block ``qi`` attends to."""
    if not causal:
        return nkv - 1
    return jnp.minimum(
        (q_off + (qi + 1) * block_q - 1) // block_kv, nkv - 1)


def _first_q_block(ki, block_q, block_kv, q_off, causal, nq):
    """Index of the first q block that sees kv block ``ki`` (causal)."""
    if not causal:
        return 0
    return jnp.clip((ki * block_kv - q_off) // block_q, 0, nq - 1)


# ---------------------------------------------------------------------------
# Forward
# ---------------------------------------------------------------------------


def _fwd_kernel(q_ref, k_ref, v_ref, *refs, scale, causal, block_q,
                block_kv, q_off, nkv, has_seg):
    if has_seg:
        qseg_ref, kseg_ref, o_ref, lse_ref, m_s, l_s, acc_s = refs
    else:
        o_ref, lse_ref, m_s, l_s, acc_s = refs
        qseg_ref = kseg_ref = None
    qi = pl.program_id(1)
    j = pl.program_id(2)
    j_last = _last_kv_block(qi, block_q, block_kv, q_off, causal, nkv)

    @pl.when(j == 0)
    def _():
        m_s[...] = jnp.full_like(m_s, _NEG)
        l_s[...] = jnp.zeros_like(l_s)
        acc_s[...] = jnp.zeros_like(acc_s)

    @pl.when(j <= j_last)
    def _():
        # matmuls run in the INPUT dtype (bf16 = native MXU mode; f32
        # inputs stay accurate) with f32 accumulation
        qb = q_ref[0]                                    # [bq, D]
        kb = k_ref[0]                                    # [bkv, D]
        vb = v_ref[0]
        s = lax.dot_general(qb, kb, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
        keep = _mask(qi, j, block_q, block_kv, q_off, causal,
                     qseg_ref, kseg_ref)
        if keep is not None:
            s = jnp.where(keep, s, _NEG)
        m_prev = m_s[:, :1]
        m_new = jnp.maximum(m_prev, jnp.max(s, -1, keepdims=True))
        p = jnp.exp(s - m_new)
        if keep is not None:
            p = jnp.where(keep, p, 0.0)
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
        lse_ref[0, 0, :] = (m_s[:, :1] + jnp.log(l))[:, 0]


def _seg_specs(H, block_q, block_kv, kv_index, kw):
    """BlockSpecs for segment-id arrays reshaped to [B, 1, S] (3-D so
    the Mosaic last-two-dims tiling rule is satisfiable for B > 1);
    the BH grid axis maps to batch via // H."""
    qs = pl.BlockSpec((1, 1, block_q), lambda b, i, j: (b // H, 0, i),
                      **kw)
    ks = pl.BlockSpec((1, 1, block_kv),
                      lambda b, i, j: (b // H, 0, kv_index(b, i, j)), **kw)
    return qs, ks


def _pallas_fa(q3, k3, v3, qseg, kseg, H, causal, scale, block_q, block_kv,
               interpret):
    BH, Sq, D = q3.shape
    Skv = k3.shape[1]
    q_off = Skv - Sq
    nq, nkv = Sq // block_q, Skv // block_kv
    kw = {"memory_space": _VMEM}

    def kv_index(b, i, j):
        # clamp past the causal frontier: re-use the resident block, no DMA
        return jnp.minimum(
            j, _last_kv_block(i, block_q, block_kv, q_off, causal, nkv))

    in_specs = [
        pl.BlockSpec((1, block_q, D), lambda b, i, j: (b, i, 0), **kw),
        pl.BlockSpec((1, block_kv, D),
                     lambda b, i, j: (b, kv_index(b, i, j), 0), **kw),
        pl.BlockSpec((1, block_kv, D),
                     lambda b, i, j: (b, kv_index(b, i, j), 0), **kw),
    ]
    args = [q3, k3, v3]
    if qseg is not None:
        qs, ks = _seg_specs(H, block_q, block_kv, kv_index, kw)
        in_specs += [qs, ks]
        args += [qseg, kseg]
    kernel = partial(_fwd_kernel, scale=scale, causal=causal,
                     block_q=block_q, block_kv=block_kv, q_off=q_off,
                     nkv=nkv, has_seg=qseg is not None)
    return pl.pallas_call(
        kernel,
        grid=(BH, nq, nkv),
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((1, block_q, D), lambda b, i, j: (b, i, 0), **kw),
            pl.BlockSpec((1, 1, block_q), lambda b, i, j: (b, 0, i), **kw),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((BH, Sq, D), q3.dtype),
            jax.ShapeDtypeStruct((BH, 1, Sq), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, 128), jnp.float32),
            pltpu.VMEM((block_q, 128), jnp.float32),
            pltpu.VMEM((block_q, D), jnp.float32),
        ],
        interpret=interpret,
        name="flash_attention_fwd",
        **_compiler_params(2, interpret),
    )(*args)


# ---------------------------------------------------------------------------
# Backward kernels (reference capability: flash_attn_grad_kernel.cu)
# ---------------------------------------------------------------------------


def _dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, dl_ref, *refs, scale,
               causal, block_q, block_kv, q_off, nkv, has_seg):
    if has_seg:
        qseg_ref, kseg_ref, dq_ref, dq_s = refs
    else:
        dq_ref, dq_s = refs
        qseg_ref = kseg_ref = None
    qi = pl.program_id(1)
    j = pl.program_id(2)
    j_last = _last_kv_block(qi, block_q, block_kv, q_off, causal, nkv)

    @pl.when(j == 0)
    def _():
        dq_s[...] = jnp.zeros_like(dq_s)

    @pl.when(j <= j_last)
    def _():
        qb = q_ref[0]
        dob = do_ref[0]
        lse = lse_ref[0, 0, :][:, None]
        delta = dl_ref[0, 0, :][:, None]
        kb = k_ref[0]
        vb = v_ref[0]
        s = lax.dot_general(qb, kb, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
        keep = _mask(qi, j, block_q, block_kv, q_off, causal,
                     qseg_ref, kseg_ref)
        if keep is not None:
            s = jnp.where(keep, s, _NEG)
        p = jnp.exp(s - lse)
        if keep is not None:
            p = jnp.where(keep, p, 0.0)
        dp = lax.dot_general(dob, vb, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)
        ds = p * (dp - delta)
        dq_s[...] += lax.dot_general(ds.astype(kb.dtype), kb,
                                     (((1,), (0,)), ((), ())),
                                     preferred_element_type=jnp.float32)

    @pl.when(j == nkv - 1)
    def _():
        dq_ref[0] = (dq_s[...] * scale).astype(dq_ref.dtype)


def _dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, dl_ref, *refs, scale,
                causal, block_q, block_kv, q_off, nq, has_seg):
    if has_seg:
        qseg_ref, kseg_ref, dk_ref, dv_ref, dk_s, dv_s = refs
    else:
        dk_ref, dv_ref, dk_s, dv_s = refs
        qseg_ref = kseg_ref = None
    ki = pl.program_id(1)
    i = pl.program_id(2)
    i_first = _first_q_block(ki, block_q, block_kv, q_off, causal, nq)

    @pl.when(i == 0)
    def _():
        dk_s[...] = jnp.zeros_like(dk_s)
        dv_s[...] = jnp.zeros_like(dv_s)

    @pl.when(i >= i_first)
    def _():
        kb = k_ref[0]
        vb = v_ref[0]
        qb = q_ref[0]
        dob = do_ref[0]
        lse = lse_ref[0, 0, :][:, None]
        delta = dl_ref[0, 0, :][:, None]
        s = lax.dot_general(qb, kb, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
        keep = _mask(i, ki, block_q, block_kv, q_off, causal,
                     qseg_ref, kseg_ref)
        if keep is not None:
            s = jnp.where(keep, s, _NEG)
        p = jnp.exp(s - lse)
        if keep is not None:
            p = jnp.where(keep, p, 0.0)
        dv_s[...] += lax.dot_general(p.astype(dob.dtype), dob,
                                     (((0,), (0,)), ((), ())),
                                     preferred_element_type=jnp.float32)
        dp = lax.dot_general(dob, vb, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)
        ds = (p * (dp - delta))
        dk_s[...] += lax.dot_general(ds.astype(qb.dtype), qb,
                                     (((0,), (0,)), ((), ())),
                                     preferred_element_type=jnp.float32)

    @pl.when(i == nq - 1)
    def _():
        dk_ref[0] = (dk_s[...] * scale).astype(dk_ref.dtype)
        dv_ref[0] = dv_s[...].astype(dv_ref.dtype)


def _pallas_fa_bwd(q3, k3, v3, do3, lse, delta, qseg, kseg, H, causal,
                   scale, block_q, block_kv, interpret):
    BH, Sq, D = q3.shape
    Skv = k3.shape[1]
    q_off = Skv - Sq
    nq, nkv = Sq // block_q, Skv // block_kv
    kw = {"memory_space": _VMEM}
    scratch = [pltpu.VMEM((block_q, D), jnp.float32)]

    def kv_index(b, i, j):
        return jnp.minimum(
            j, _last_kv_block(i, block_q, block_kv, q_off, causal, nkv))

    dq_specs = [
        pl.BlockSpec((1, block_q, D), lambda b, i, j: (b, i, 0), **kw),
        pl.BlockSpec((1, block_kv, D),
                     lambda b, i, j: (b, kv_index(b, i, j), 0), **kw),
        pl.BlockSpec((1, block_kv, D),
                     lambda b, i, j: (b, kv_index(b, i, j), 0), **kw),
        pl.BlockSpec((1, block_q, D), lambda b, i, j: (b, i, 0), **kw),
        pl.BlockSpec((1, 1, block_q), lambda b, i, j: (b, 0, i), **kw),
        pl.BlockSpec((1, 1, block_q), lambda b, i, j: (b, 0, i), **kw),
    ]
    dq_args = [q3, k3, v3, do3, lse, delta]
    if qseg is not None:
        qs, ks = _seg_specs(H, block_q, block_kv, kv_index, kw)
        dq_specs += [qs, ks]
        dq_args += [qseg, kseg]
    dq_kernel = partial(_dq_kernel, scale=scale, causal=causal,
                        block_q=block_q, block_kv=block_kv, q_off=q_off,
                        nkv=nkv, has_seg=qseg is not None)
    dq = pl.pallas_call(
        dq_kernel,
        grid=(BH, nq, nkv),
        in_specs=dq_specs,
        out_specs=pl.BlockSpec((1, block_q, D), lambda b, i, j: (b, i, 0),
                               **kw),
        out_shape=jax.ShapeDtypeStruct((BH, Sq, D), q3.dtype),
        scratch_shapes=scratch,
        interpret=interpret,
        name="flash_attention_dq",
        **_compiler_params(2, interpret),
    )(*dq_args)

    def q_index(b, j, i):
        # clamp before the causal frontier: skip the DMA for q blocks
        # that cannot see this kv block
        return jnp.maximum(
            i, _first_q_block(j, block_q, block_kv, q_off, causal, nq))

    dkv_specs = [
        pl.BlockSpec((1, block_q, D),
                     lambda b, j, i: (b, q_index(b, j, i), 0), **kw),
        pl.BlockSpec((1, block_kv, D), lambda b, j, i: (b, j, 0), **kw),
        pl.BlockSpec((1, block_kv, D), lambda b, j, i: (b, j, 0), **kw),
        pl.BlockSpec((1, block_q, D),
                     lambda b, j, i: (b, q_index(b, j, i), 0), **kw),
        pl.BlockSpec((1, 1, block_q),
                     lambda b, j, i: (b, 0, q_index(b, j, i)), **kw),
        pl.BlockSpec((1, 1, block_q),
                     lambda b, j, i: (b, 0, q_index(b, j, i)), **kw),
    ]
    dkv_args = [q3, k3, v3, do3, lse, delta]
    if qseg is not None:
        qs = pl.BlockSpec(
            (1, 1, block_q),
            lambda b, j, i: (b // H, 0, q_index(b, j, i)), **kw)
        ks = pl.BlockSpec((1, 1, block_kv),
                          lambda b, j, i: (b // H, 0, j), **kw)
        dkv_specs += [qs, ks]
        dkv_args += [qseg, kseg]
    dkv_kernel = partial(_dkv_kernel, scale=scale, causal=causal,
                         block_q=block_q, block_kv=block_kv, q_off=q_off,
                         nq=nq, has_seg=qseg is not None)
    dkv_scratch = [pltpu.VMEM((block_kv, D), jnp.float32),
                   pltpu.VMEM((block_kv, D), jnp.float32)]
    dk, dv = pl.pallas_call(
        dkv_kernel,
        grid=(BH, nkv, nq),
        in_specs=dkv_specs,
        out_specs=[
            pl.BlockSpec((1, block_kv, D), lambda b, j, i: (b, j, 0), **kw),
            pl.BlockSpec((1, block_kv, D), lambda b, j, i: (b, j, 0), **kw),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((BH, Skv, D), k3.dtype),
            jax.ShapeDtypeStruct((BH, Skv, D), v3.dtype),
        ],
        scratch_shapes=dkv_scratch,
        interpret=interpret,
        name="flash_attention_dkv",
        **_compiler_params(2, interpret),
    )(*dkv_args)
    return dq, dk, dv


def _blockable(q_shape, k_shape) -> bool:
    """What the kernel needs in any mode: a block that divides each
    sequence, and (rectangular causal convention) q a suffix of the kv
    span."""
    Sq, Skv = q_shape[1], k_shape[1]
    return (_pick_block(Sq) > 0 and _pick_block(Skv) > 0 and Skv >= Sq)


def flash_supported(q_shape, k_shape) -> bool:
    """Mosaic shape gate for dispatch sites (ops/attention.py): the
    kernel's own needs plus a head dim that fills whole 128-wide VPU
    lanes (the same gate as rms_norm/decode_attention). Shapes it
    rejects take the dense XLA path."""
    return _blockable(q_shape, k_shape) and q_shape[3] % 128 == 0


def _to3(x):
    B, S, H, D = x.shape
    return jnp.swapaxes(x, 1, 2).reshape(B * H, S, D)


def _from3(x3, B, H):
    BH, S, D = x3.shape
    return jnp.swapaxes(x3.reshape(B, H, S, D), 1, 2)


@partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def flash_attention_fwd(q, k, v, causal=False, scale=None, interpret=False,
                        q_segment_ids=None, kv_segment_ids=None):
    """[B, S, H, D] → [B, S, H, D]. Dispatch sites ask
    :func:`flash_supported` first; a shape no block divides is a
    ValueError here. Optional int32 segment ids [B, Sq]/[B, Skv]
    restrict attention to equal segments (varlen). ``interpret=True``
    (tests) runs the Pallas interpreter instead of Mosaic."""
    out, _ = _fa_fwd(q, k, v, causal, scale, interpret, q_segment_ids,
                     kv_segment_ids)
    return out


def _prep(q, k, causal, scale, interpret, qseg, kseg):
    B, Sq, H, D = q.shape
    if scale is None:
        scale = 1.0 / np.sqrt(D)
    if (qseg is None) != (kseg is None):
        raise ValueError("flash: q/kv segment ids must be given together")
    if qseg is not None:
        # [B, S] -> [B, 1, S] (see _seg_specs)
        qseg = jnp.asarray(qseg, jnp.int32)[:, None, :]
        kseg = jnp.asarray(kseg, jnp.int32)[:, None, :]
    # 512-blocks measured fastest on v5e at S=8192 (44.9 TF/s vs 9.7 at
    # 128); smaller sizes only when the sequence doesn't divide
    block_q = _pick_block(Sq, prefer=_BLOCKS)
    block_kv = _pick_block(k.shape[1], prefer=_BLOCKS)
    from ...core import flags as _flags

    if (_flags._get("use_autotune", False) and not interpret
            and qseg is None):
        # measured block selection, cached per shape/dtype (reference
        # AlgorithmsCache); runs eager side-benchmarks even while an
        # outer jit traces — block sizes are trace-time constants
        from .autotune import autotune, measure_flash_blocks

        B, Sq_, H, D = q.shape
        key = (f"flash:{B}x{Sq_}x{H}x{D}:{k.shape[1]}:{q.dtype}:"
               f"{bool(causal)}")
        cands = [(bq, bk) for bq in (512, 256, 128)
                 for bk in (512, 256, 128)
                 if Sq_ % bq == 0 and k.shape[1] % bk == 0]
        if len(cands) > 1:
            block_q, block_kv = autotune(
                key, cands,
                measure_flash_blocks(q.shape, k.shape[1], q.dtype,
                                     bool(causal)))
    return scale, interpret, qseg, kseg, block_q, block_kv


def _fa_fwd(q, k, v, causal, scale, interpret, qseg=None, kseg=None):
    if not _blockable(q.shape, k.shape):
        raise ValueError("flash pallas kernel: no block tiles shape "
                         f"{q.shape}/{k.shape}")
    B, Sq, H, D = q.shape
    scale, interpret, qseg3, kseg3, block_q, block_kv = _prep(
        q, k, causal, scale, interpret, qseg, kseg)
    o3, lse = _pallas_fa(_to3(q), _to3(k), _to3(v), qseg3, kseg3, H,
                         causal, scale, block_q, block_kv, interpret)
    out = _from3(o3, B, H)
    # residuals keep the RAW [B, S] ids — _fa_bwd re-runs _prep
    return out, (q, k, v, out, lse, qseg, kseg)


def _fa_bwd(causal, scale, interpret, res, g):
    q, k, v, out, lse, qseg, kseg = res
    B, Sq, H, D = q.shape
    scale, interpret, qseg, kseg, block_q, block_kv = _prep(
        q, k, causal, scale, interpret, qseg, kseg)
    q3, k3, v3 = _to3(q), _to3(k), _to3(v)
    do3, o3 = _to3(g), _to3(out)
    # delta_i = rowsum(dO ∘ O): O(S) per head, fused by XLA
    delta = jnp.sum(do3.astype(jnp.float32) * o3.astype(jnp.float32),
                    axis=-1)[:, None, :]
    dq3, dk3, dv3 = _pallas_fa_bwd(q3, k3, v3, do3, lse, delta, qseg, kseg,
                                   H, causal, scale, block_q, block_kv,
                                   interpret)
    return (_from3(dq3, B, H), _from3(dk3, B, H), _from3(dv3, B, H),
            None, None)


flash_attention_fwd.defvjp(
    lambda q, k, v, causal, scale, interpret, qseg=None, kseg=None:
    _fa_fwd(q, k, v, causal, scale, interpret, qseg, kseg),
    _fa_bwd)


def _fa_fwd_with_lse(q, k, v, causal, scale, interpret, qseg=None,
                     kseg=None):
    out, res = _fa_fwd(q, k, v, causal, scale, interpret, qseg, kseg)
    return (out, res[4]), res


@partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def flash_attention_with_lse(q, k, v, causal=False, scale=None,
                             interpret=False, q_segment_ids=None,
                             kv_segment_ids=None):
    """:func:`flash_attention_fwd` handing out what its backward needs
    beside ``out``: the rows' logsumexp ``lse`` [B*H, 1, Sq] f32 (the
    reference's ``softmax_lse``). For a caller that keeps both and calls
    :func:`flash_attention_bwd` itself (the tape's grad kernel,
    ops/attention.py) instead of having ``jax.vjp`` run the forward
    again for them. ``lse`` is a residual, not a second result: its
    cotangent is ignored."""
    return _fa_fwd_with_lse(q, k, v, causal, scale, interpret,
                            q_segment_ids, kv_segment_ids)[0]


flash_attention_with_lse.defvjp(
    _fa_fwd_with_lse,
    lambda causal, scale, interpret, res, g:
    _fa_bwd(causal, scale, interpret, res, g[0]))


def flash_attention_bwd(q, k, v, out, lse, g, causal=False, scale=None,
                        interpret=False, q_segment_ids=None,
                        kv_segment_ids=None):
    """(dq, dk, dv) from the forward's own ``out`` and ``lse`` and the
    cotangent ``g`` of ``out``: the two backward kernels, no forward."""
    return _fa_bwd(causal, scale, interpret,
                   (q, k, v, out, lse, q_segment_ids, kv_segment_ids), g)[:3]


# ---------------------------------------------------------------------------
# Causal forward for grouped-query heads, no backward (a prefill program's
# attention of a prompt to itself: models/llama.py)
# ---------------------------------------------------------------------------

_GQA_MAX_HEADS = 8          # query heads a grid step (unrolled)
_GQA_VMEM_LIMIT = 48 * 1024 * 1024


def _gqa_kernel(qi_ref, kj_ref, q_ref, k_ref, v_ref, o_ref, m_s, l_s,
                acc_s, *, scale, D, window=None):
    """One block pair at or under the diagonal for the ``hs`` query heads
    of a step, all under ONE K/V tile. q / o blocks are ``[block, hs *
    D]`` (a head is a lane-aligned column slice), k / v ``[block, D]``.
    The heads are unrolled with the next head's score product issued
    before this head's softmax (the MXU beside the VPU), the row sums
    stay per-lane until a row block's last step, and a masked score is
    ``-inf`` over a finite running maximum (``kept_attention.py`` has
    the readings of each); only the diagonal block is masked at all
    and, under a ``window`` (static), the pairs ``far`` blocks under the
    diagonal or further, where some row has lost a key of the block to
    the window: one more compare of the same two ``iota``s. With no
    window the steps are what they were without the argument."""
    t = pl.program_id(2)
    i, j = qi_ref[t], kj_ref[t]
    hs, block, lanes = l_s.shape
    if window is None:
        first, far = 0, None
    else:       # the tables' rule (flash_attention_gqa), and its far end
        first = jnp.maximum(i * block - window + 1, 0) // block
        far = max(0, -((block - 1 - window) // block))

    @pl.when(j == first)        # a row block's first step
    def _():
        m_s[...] = jnp.full_like(m_s, _NEG)
        l_s[...] = jnp.zeros_like(l_s)
        acc_s[...] = jnp.zeros_like(acc_s)

    def step(diagonal, edge=False):
        kb, vb = k_ref[0], v_ref[0]

        def product(g):
            return lax.dot_general(
                q_ref[0, :, g * D:(g + 1) * D], kb,
                (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)

        if diagonal or edge:    # rows and keys of a pair: local indices
            row = lax.broadcasted_iota(jnp.int32, (block, block), 0)
            key = lax.broadcasted_iota(jnp.int32, (block, block), 1)
        if diagonal:
            keep = row >= key
        if edge:        # row t keeps key s while t - s < window
            near = row - key < window - (i - j) * block
            keep = keep & near if diagonal else near
        s_next = product(0)
        for g in range(hs):
            s = s_next
            if g + 1 < hs:
                s_next = product(g + 1)
            s = s * scale
            if diagonal or edge:
                s = jnp.where(keep, s, -jnp.inf)
            m_prev = m_s[g]                             # lane-replicated
            m_new = jnp.maximum(m_prev, jnp.max(s, -1, keepdims=True))
            p = jnp.exp(s - m_new[:, :1])
            corr = jnp.exp(m_prev - m_new)
            part = p[:, :lanes]                 # per-lane partial row sums
            for w in range(1, block // lanes):
                part = part + p[:, w * lanes:(w + 1) * lanes]
            l_s[g] = l_s[g] * corr + part
            acc_s[g] = acc_s[g] * corr[:, :1] + lax.dot_general(
                p.astype(vb.dtype), vb, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            m_s[g] = m_new

    if far is None:
        pl.when(j < i)(lambda: step(False))
    else:
        if far > 1:             # else every pair under the diagonal is far
            pl.when((j < i) & (i - j < far))(lambda: step(False))
        pl.when((j < i) & (i - j >= far))(lambda: step(False, edge=True))

    @pl.when(j == i)            # the diagonal block: a row block's last
    def _():
        step(True, edge=far == 0)
        for g in range(hs):
            l = jnp.sum(l_s[g], -1, keepdims=True)  # > 0: a row sees itself
            o_ref[0, :, g * D:(g + 1) * D] = (acc_s[g] / l).astype(
                o_ref.dtype)


def _gqa_block(S: int) -> int:
    return _pick_block(S, prefer=_BLOCKS)


def flash_gqa_supported(q_shape, k_shape) -> bool:
    """Mosaic shape gate of :func:`flash_attention_gqa`: self-attention
    (as many keys as rows) in whole blocks of at least 128 rows, a head
    that fills the 128 lanes, whole groups of query heads a KV head."""
    B, S, H, D = q_shape
    return (k_shape[1] == S and _gqa_block(S) >= 128 and D % 128 == 0
            and k_shape[3] == D and H % k_shape[2] == 0)


def _gqa_pairs(n: int, block: int, window=None):
    """The block pairs ``(qi, kj)`` of ``n`` row blocks that hold a key
    some row sees, a row block's pairs together and its diagonal last:
    all at or under the diagonal and, under a window, those alone whose
    last key the row block's first row still sees."""
    qi, kj = np.tril_indices(n)
    if window is not None:
        seen = kj * block + block - 1 > qi * block - window
        qi, kj = qi[seen], kj[seen]
    return qi, kj


@partial(jax.jit,
         static_argnames=("scale", "window", "block", "interpret"))
def flash_attention_gqa(q, k, v, scale=None, window=None, block=None,
                        interpret=False):
    """Causal self-attention of q [B, S, H, D] over k, v [B, S, KV, D]
    (query head h reads KV head ``h // (H / KV)``; row t sees keys ``s
    <= t`` and, with a ``window``, ``t - s < window``:
    ``blockwise_causal_attention``'s rule), forward only: [B, S, H, D]
    in q's type. Products in the input type with float32 accumulation,
    float32 softmax statistics.

    No transposed copy and no widened K/V: the arrays are read as ``[B,
    S, heads * D]`` (a reshape), a step's q / o block is the ``hs`` heads'
    columns of a row block and its K/V tile ONE KV head's, shared by the
    ``hs`` query heads of the step (``hs``: the largest divisor of the
    group up to 8). The grid is ``(B, H / hs, pairs)``: the block pairs
    that hold a visible key alone (``_gqa_pairs``: ``n (n + 1) / 2``
    with no window, at most ``window / block + 1`` a row block under
    one), from two scalar-prefetched tables, as
    ``kept_flash_attention``. A window that drops no key of ``S`` rows
    (``window >= S``) is no window: the same tables, the same kernel.
    ``block`` (rows and keys): the
    largest of 512, 256, 128 that divides ``S``; alone on a v5e at ``[1,
    2048, 32, 128]`` on 8 KV heads 512 read 461 us a call (38% of the
    MXU by the causal half of ``4 S^2 D H``), 256 read 590, 128 read
    1,127, and ``flash_attention_fwd`` over K/V widened to the query
    heads, layout copies and all, 720 (my chip run, PR 43).

    Jitted on its own, as ``paged_decode_attention``: a prefill program
    calls it once a layer with the same shapes (and the same window, on
    the layers of one kind), and then traces and lowers it once
    (unjitted, 16 layers of two buckets added 5 s to a warm set-up; my
    chip run, PR 43)."""
    B, S, H, D = q.shape
    KV = k.shape[2]
    G = H // KV
    if scale is None:
        scale = 1.0 / np.sqrt(D)
    if block is None:
        block = _gqa_block(S)
    if not flash_gqa_supported(q.shape, k.shape) or S % block:
        raise ValueError("flash_attention_gqa: no block tiles shape "
                         f"{q.shape}/{k.shape}")
    if window is not None and window >= S:
        window = None
    hs = next(g for g in range(min(G, _GQA_MAX_HEADS), 0, -1) if G % g == 0)
    lanes = min(block, 128)
    qi, kj = _gqa_pairs(S // block, block, window)
    rows = lambda b, h, t, qi, kj: (b, qi[t], h)
    keys = lambda b, h, t, qi, kj: (b, kj[t], h * hs // G)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, H // hs, len(qi)),
        in_specs=[
            pl.BlockSpec((1, block, hs * D), rows),
            pl.BlockSpec((1, block, D), keys),
            pl.BlockSpec((1, block, D), keys),
        ],
        out_specs=pl.BlockSpec((1, block, hs * D), rows),
        scratch_shapes=[
            pltpu.VMEM((hs, block, lanes), jnp.float32),
            pltpu.VMEM((hs, block, lanes), jnp.float32),
            pltpu.VMEM((hs, block, D), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        partial(_gqa_kernel, scale=float(scale), D=D, window=window),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, S, H * D), q.dtype),
        interpret=interpret,
        name="flash_attention_fwd_gqa",
        **_compiler_params(2, interpret,
                           vmem_limit_bytes=_GQA_VMEM_LIMIT),
    )(jnp.asarray(qi, jnp.int32), jnp.asarray(kj, jnp.int32),
      q.reshape(B, S, H * D), k.reshape(B, S, KV * D),
      v.reshape(B, S, KV * D))
    return out.reshape(B, S, H, D)
