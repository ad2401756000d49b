"""Attention ops: flash attention as a Pallas TPU kernel or plain XLA.

(reference: phi/kernels/gpu/flash_attn_kernel.cu — dynloaded flashattn v2
lib; YAML ops.yaml:1030 with spmd_rule FlashAttInferSpmd. Here the TPU
path is a Pallas kernel (ops/pallas/flash_attention.py) and the portable
path is plain XLA, selected at trace time from the platform and the
kernel's shape gate. A kernel the gate admitted is called bare: if
Mosaic refuses it, the run fails with the compiler's message.)

The two paths are two ops, chosen once, in the forward. The Pallas op
returns the rows' logsumexp beside ``out`` and has an explicit grad
kernel that runs the two backward kernels on them: the tape's generic
backward (``jax.vjp`` of the op) would run the forward kernel a second
time for the same residuals, and XLA does not merge two Mosaic calls.
The XLA op computes no residual, XLA removes its replay, and it keeps
the generic backward.
"""
from __future__ import annotations

import jax
import numpy as np
import jax.numpy as jnp

from ..core import flags
from ..core.dispatch import def_grad, def_op
from .nn_ops import scaled_dot_product_attention as _sdpa_public
from .pallas import is_tpu_platform
from .pallas.flash_attention import (flash_attention_bwd,
                                     flash_attention_with_lse,
                                     flash_supported)

_sdpa_raw = _sdpa_public.raw


def _use_pallas(q_shape, k_shape) -> bool:
    return (flags._get("use_pallas_kernels", True) and is_tpu_platform()
            and flash_supported(q_shape, k_shape))


def _gqa_sdpa(q, k, v, causal):
    """Grouped-query attention without materializing repeated K/V:
    q reshapes to [B, KV, rep, S, D] (query head h reads kv head
    h // rep) and the kv planes broadcast over the rep dim — the XLA
    fallback analog of the decode kernel's native GQA grouping."""
    B, S, H, D = q.shape
    Sk, KV = k.shape[1], k.shape[2]
    rep = H // KV
    scale = 1.0 / float(np.sqrt(D))
    qf = jnp.swapaxes(q, 1, 2).astype(jnp.float32).reshape(B, KV, rep, S, D)
    kf = jnp.swapaxes(k, 1, 2).astype(jnp.float32)          # [B, KV, Sk, D]
    vf = jnp.swapaxes(v, 1, 2).astype(jnp.float32)
    scores = jnp.einsum("bkrqd,bktd->bkrqt", qf, kf) * scale
    if causal:
        mask = jnp.tril(jnp.ones((S, Sk), bool), k=Sk - S)
        scores = jnp.where(mask, scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bkrqt,bktd->bkrqd", probs, vf)
    return jnp.swapaxes(out.reshape(B, H, S, D), 1, 2).astype(q.dtype)


def _widen_kv(k, v, Hq):
    """The kernel wants equal head counts: repeat each KV head over its
    query group at the kernel's boundary."""
    rep = Hq // k.shape[2]
    if rep == 1:
        return k, v
    return jnp.repeat(k, rep, axis=2), jnp.repeat(v, rep, axis=2)


# The grad kernels below get the node's tensors in call order and only
# the KEYWORD statics (core/registry.py run_grad), so the tensors are
# positional-only and the statics keyword-only: a static passed by
# position would run the backward with its default.
@def_op("flash_attention_pallas")
def flash_attention_pallas(q, k, v, /, *, causal=False):
    """The Pallas path of :func:`flash_attention`: ``(out, lse)``."""
    kk, vv = _widen_kv(k, v, q.shape[2])
    # positional: custom_vjp nondiff args reject keywords
    return flash_attention_with_lse(q, kk, vv, causal, None, False)


@def_grad("flash_attention_pallas")
def _flash_attention_pallas_grad(in_values, out_values, out_grads, *,
                                 causal=False):
    q, k, v = in_values
    out, lse = out_values
    (kk, vv), narrow = jax.vjp(lambda k, v: _widen_kv(k, v, q.shape[2]),
                               k, v)
    dq, dk, dv = flash_attention_bwd(q, kk, vv, out, lse, out_grads[0],
                                     causal, None, False)
    return (dq,) + narrow((dk, dv))     # dk, dv summed over each group


@def_op("flash_attention")
def flash_attention_xla(q, k, v, causal=False, dropout=0.0,
                        dropout_key=None):
    """The XLA path of :func:`flash_attention`."""
    Hq, Hk = q.shape[2], k.shape[2]
    if Hk != Hq and not dropout:
        return _gqa_sdpa(q, k, v, causal)
    if Hk != Hq:
        k = jnp.repeat(k, Hq // Hk, axis=2)
        v = jnp.repeat(v, Hq // Hk, axis=2)
    return _sdpa_raw(q, k, v, attn_mask=None, dropout_p=dropout,
                     is_causal=causal, dropout_key=dropout_key)


def flash_attention(q, k, v, causal=False, dropout=0.0, dropout_key=None):
    """Layout [batch, seqlen, num_heads, head_dim]. GQA accepted: k/v
    may carry fewer (dividing) heads — the XLA path broadcasts the
    shared kv plane per query group (no per-query-head K/V copies); the
    Pallas kernel path repeats at the kernel boundary only (the kernel
    requires equal head counts)."""
    if not dropout and _use_pallas(q.shape, k.shape):
        return flash_attention_pallas(q, k, v, causal=causal)[0]
    return flash_attention_xla(q, k, v, causal=causal, dropout=dropout,
                               dropout_key=dropout_key)


def _segments_from_cu(cu, total):
    """cu_seqlens [n+1] -> per-token segment ids [total] (padding past
    cu[-1] gets id -1, which still self-matches so padded rows stay
    finite and are sliced away by the caller)."""
    cu = jnp.asarray(cu, jnp.int32)
    pos = jnp.arange(total, dtype=jnp.int32)
    seg = jnp.searchsorted(cu[1:], pos, side="right").astype(jnp.int32)
    return jnp.where(pos < cu[-1], seg, -1)


def _pack_segments(cu_seqlens_q, cu_seqlens_k, Tq, Tk):
    return (_segments_from_cu(cu_seqlens_q, Tq)[None],
            _segments_from_cu(cu_seqlens_k, Tk)[None])


def _as_array(cu):
    return cu if hasattr(cu, "shape") else np.asarray(cu, np.int32)


@def_op("flash_attn_varlen_pallas")
def flash_attn_varlen_pallas(q, k, v, cu_seqlens_q, cu_seqlens_k, /, *,
                             causal=False, scale=None):
    """The Pallas path of :func:`flash_attn_varlen`: the flash kernel
    with segment-id masking, ``(out, lse)``."""
    qseg, kseg = _pack_segments(cu_seqlens_q, cu_seqlens_k, q.shape[0],
                                k.shape[0])
    out, lse = flash_attention_with_lse(q[None], k[None], v[None], causal,
                                        scale, False, qseg, kseg)
    return out[0], lse


@def_grad("flash_attn_varlen_pallas")
def _flash_attn_varlen_pallas_grad(in_values, out_values, out_grads, *,
                                   causal=False, scale=None):
    q, k, v, cu_seqlens_q, cu_seqlens_k = in_values
    out, lse = out_values
    qseg, kseg = _pack_segments(cu_seqlens_q, cu_seqlens_k, q.shape[0],
                                k.shape[0])
    dq, dk, dv = flash_attention_bwd(
        q[None], k[None], v[None], out[None], lse, out_grads[0][None],
        causal, scale, False, qseg, kseg)
    return dq[0], dk[0], dv[0], None, None


@def_op("flash_attn_varlen")
def flash_attn_varlen_xla(q, k, v, cu_seqlens_q, cu_seqlens_k, causal=False,
                          scale=None, dropout=0.0, dropout_key=None):
    """The XLA path of :func:`flash_attn_varlen`: a dense mask."""
    Tq, Tk = q.shape[0], k.shape[0]
    qseg = _segments_from_cu(cu_seqlens_q, Tq)
    kseg = _segments_from_cu(cu_seqlens_k, Tk)
    q4, k4, v4 = q[None], k[None], v[None]
    mask = qseg[:, None] == kseg[None, :]
    if causal:
        # per-sequence causal frontier: q row r of sequence s (at
        # in-sequence position qp) sees k columns of s up to
        # qp + (len_k(s) - len_q(s)) — the bottom-right-aligned
        # rectangular convention applied within EACH packed sequence
        cq = jnp.asarray(cu_seqlens_q._value if hasattr(cu_seqlens_q,
                                                        "_value")
                         else cu_seqlens_q, jnp.int32)
        ck = jnp.asarray(cu_seqlens_k._value if hasattr(cu_seqlens_k,
                                                        "_value")
                         else cu_seqlens_k, jnp.int32)
        qs_c = jnp.clip(qseg, 0, cq.shape[0] - 2)
        ks_c = jnp.clip(kseg, 0, ck.shape[0] - 2)
        q_pos = jnp.arange(Tq, dtype=jnp.int32) - cq[qs_c]
        k_pos = jnp.arange(Tk, dtype=jnp.int32) - ck[ks_c]
        len_q = (cq[qs_c + 1] - cq[qs_c])
        len_k = (ck[ks_c + 1] - ck[ks_c])
        frontier = q_pos[:, None] + (len_k[None, :] - len_q[:, None])
        mask = mask & (frontier >= k_pos[None, :])
    out = _sdpa_raw(q4, k4, v4, attn_mask=mask[None, None], scale=scale,
                    dropout_p=dropout, is_causal=False,
                    dropout_key=dropout_key)
    return out[0]


def flash_attn_varlen(q, k, v, cu_seqlens_q, cu_seqlens_k, causal=False,
                      scale=None, dropout=0.0, dropout_key=None):
    """Packed varlen attention (reference: flash_attn_unpadded /
    flash_attn_varlen_func, python/paddle/nn/functional/
    flash_attention.py:384 over phi flash_attn_unpadded kernel).

    q/k/v: [total_tokens, H, D] packed concatenations of sequences with
    boundaries cu_seqlens (e.g. [0, s1, s1+s2, ...]). Tokens never
    attend across sequence boundaries. TPU path: the Pallas flash
    kernel with segment-id masking; portable path: dense mask."""
    Tq, Tk = q.shape[0], k.shape[0]
    # the Pallas kernel's causal mask is the global row>=col frontier,
    # which is only correct when the q and k packs share boundaries
    same_pack = Tq == Tk and (cu_seqlens_q is cu_seqlens_k
                              or not causal)
    if not dropout and same_pack and _use_pallas(
            (1,) + tuple(q.shape), (1,) + tuple(k.shape)):
        # boundaries as arrays: the grad kernel reads them back from
        # the node's inputs
        return flash_attn_varlen_pallas(
            q, k, v, _as_array(cu_seqlens_q), _as_array(cu_seqlens_k),
            causal=causal, scale=scale)[0]
    return flash_attn_varlen_xla(
        q, k, v, cu_seqlens_q, cu_seqlens_k, causal=causal, scale=scale,
        dropout=dropout, dropout_key=dropout_key)
