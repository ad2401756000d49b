"""Fused functional ops (reference: python/paddle/incubate/nn/functional/
— fused_rms_norm, fused_layer_norm, fused_rotary_position_embedding,
fused_bias_act, fused_dropout_add, swiglu).

Each fuses into the surrounding XLA program; on TPU the rms_norm and
flash-attention paths dispatch to the Pallas kernels (ops/pallas/).
"""
from __future__ import annotations

from typing import Optional

from ....ops import nn_ops as _nn
from ....ops.nn_ops import fused_rope as _fused_rope
from ....tensor import Tensor

import jax
import jax.numpy as jnp

__all__ = [
    "fused_rms_norm", "fused_layer_norm",
    "fused_rotary_position_embedding", "fused_bias_act",
    "fused_dropout_add", "swiglu", "fused_linear",
    "fused_multi_transformer", "masked_multihead_attention",
    "block_multihead_attention",
]


def fused_rms_norm(x, norm_weight, norm_bias=None, epsilon=1e-6,
                   begin_norm_axis=-1, bias=None, residual=None,
                   quant_scale=-1, **kw):
    """(reference: incubate/nn/functional/fused_rms_norm.py →
    phi/kernels/gpu/rms_norm_kernel.cu). Returns (out, residual_out) like
    the reference when a residual is supplied, else out."""
    from ....core.enforce import enforce as _enf

    _enf(quant_scale in (-1, None),
         "fused_rms_norm: in-kernel output quantization is served by "
         "nn.quant on TPU — leave quant_scale at -1")
    if bias is not None:
        x = x + bias
    if residual is not None:
        x = x + residual
        residual_out = x
    out = _nn.rms_norm(x, norm_weight, norm_bias, epsilon=epsilon,
                       begin_norm_axis=begin_norm_axis)
    if residual is not None:
        return out, residual_out
    return out


def fused_layer_norm(x, norm_weight, norm_bias, epsilon=1e-5,
                     begin_norm_axis=-1, bias=None, residual=None, **kw):
    """(reference: phi/kernels/fusion/gpu/fused_layernorm_kernel.cu —
    residual-add + layernorm fusion)."""
    if bias is not None:
        x = x + bias
    if residual is not None:
        x = x + residual
        residual_out = x
    out = _nn.layer_norm(x, norm_weight, norm_bias, epsilon=epsilon,
                         begin_norm_axis=begin_norm_axis)
    if residual is not None:
        return out, residual_out
    return out


def fused_rotary_position_embedding(q, k=None, v=None, sin=None, cos=None,
                                    position_ids=None,
                                    use_neox_rotary_style=True, **kw):
    """(reference: incubate/nn/functional/fused_rotary_position_embedding
    → phi/kernels/fusion/gpu/fused_rope_kernel.cu; SPMD rule
    spmd_rules/fused_rope.cc). q/k: [B, S, H, D]; returns the same tuple
    arity as the reference (q, k, v)."""
    from ....core.enforce import enforce as _enf

    _enf(use_neox_rotary_style,
         "fused_rotary_position_embedding: only the neox (rotate-half) "
         "style is served on TPU (ops/nn_ops.fused_rope); the GPT-J "
         "interleaved style is not implemented — pass "
         "use_neox_rotary_style=True")
    outs = _fused_rope(q, q if k is None else k, cos, sin,
                       position_ids=position_ids)
    q_out, k_out = outs if isinstance(outs, (tuple, list)) else (outs, None)
    return q_out, (None if k is None else k_out), v


def fused_bias_act(x, bias=None, act_method: str = "gelu", **kw):
    """(reference: phi/kernels/fusion/gpu/fused_bias_act_kernel.cu)."""
    from ....nn import functional as F

    if bias is not None:
        x = x + bias
    act = {"gelu": F.gelu, "relu": F.relu, "silu": F.silu,
           "swiglu": swiglu, "geglu": None}.get(act_method)
    if act_method == "geglu":
        from ....ops import manipulation as M

        a, b = M.split(x, 2, axis=-1)
        return F.gelu(a) * b
    if act is None:
        raise ValueError(f"unknown act_method {act_method!r}")
    return act(x)


def swiglu(x, y=None):
    """(reference: incubate/nn/functional/swiglu → phi swiglu kernel).
    swiglu(x, y) = silu(x) * y; single-arg form splits x in half."""
    from ....nn import functional as F

    if y is None:
        from ....ops import manipulation as M

        x, y = M.split(x, 2, axis=-1)
    return F.silu(x) * y


def fused_dropout_add(x, y, p=0.0, training=True, mode="upscale_in_train",
                      **kw):
    """(reference: phi/kernels/fusion/gpu/fused_dropout_add_kernel.cu)."""
    from ....nn import functional as F

    return F.dropout(x, p=p, training=training, mode=mode) + y


def fused_linear(x, weight, bias=None, transpose_weight=False, **kw):
    """(reference: fused_gemm_epilogue — cuBLASLt matmul+bias; XLA fuses
    the epilogue natively on the MXU)."""
    from ....ops import math as M

    out = M.matmul(x, weight, transpose_y=transpose_weight)
    if bias is not None:
        out = out + bias
    return out


def masked_multihead_attention(x, cache_kv=None, bias=None, src_mask=None,
                               cum_offsets=None, sequence_lengths=None,
                               rotary_tensor=None, beam_cache_offset=None,
                               qkv_out_scale=None, out_shift=None,
                               out_smooth=None, seq_len=1,
                               rotary_emb_dims=0,
                               use_neox_rotary_style=False,
                               compute_dtype="default", out_scale=-1,
                               quant_round_type=1, quant_max_bound=127.0,
                               quant_min_bound=-127.0):
    """One fused decode step of cache-KV attention (reference:
    incubate/nn/functional/masked_multihead_attention.py:19 over
    masked_multihead_attention_kernel.cu).

    x: [B, 3*H*D] fused qkv of the new token; cache_kv: [2, B, H, M, D];
    sequence_lengths: [B, 1] per-row write/attend offsets (the ragged
    primitive of ops/pallas/decode_attention.py). Returns
    (out [B, H*D], updated cache_kv). src_mask/cum_offsets/
    beam_cache_offset and the quant knobs are NOT served here (the TPU
    path masks by the per-row frontier, packs via the Predictor, and
    quantizes via nn.quant) — they are enforced to their defaults so
    divergence is loud, mirroring block_multihead_attention."""
    from ....ops.pallas.decode_attention import _dense_ragged
    from ....core.enforce import enforce as _enf

    for knob, name in ((src_mask, "src_mask"),
                       (cum_offsets, "cum_offsets"),
                       (beam_cache_offset, "beam_cache_offset"),
                       (rotary_tensor, "rotary_tensor"),
                       (qkv_out_scale, "qkv_out_scale"),
                       (out_shift, "out_shift"),
                       (out_smooth, "out_smooth")):
        _enf(knob is None,
             f"masked_multihead_attention: {name} is not served by the "
             "TPU decode step (masking is the per-row frontier, "
             "packing is the Predictor serving path, quantization is "
             "nn.quant) — pass None")
    _enf(out_scale in (-1, None) and compute_dtype == "default"
         and quant_round_type == 1 and quant_max_bound == 127.0
         and quant_min_bound == -127.0,
         "masked_multihead_attention: output/cache quantization is "
         "served by nn.quant on TPU, not in-kernel — leave the quant "
         "knobs at their defaults")
    _enf(seq_len == 1, "masked_multihead_attention decodes one token "
                       "per row (seq_len must be 1)")
    xv = x._value if isinstance(x, Tensor) else jnp.asarray(x)
    cv = cache_kv._value if isinstance(cache_kv, Tensor) \
        else jnp.asarray(cache_kv)
    _enf(cv.ndim == 5 and cv.shape[0] == 2,
         "cache_kv must be [2, B, H, max_seq, D]")
    B = xv.shape[0]
    _, _, H, M, D = cv.shape
    qkv = xv.reshape(B, 3, H, D)
    if bias is not None:
        bv = bias._value if isinstance(bias, Tensor) else jnp.asarray(bias)
        qkv = qkv + bv.reshape(1, 3, H, D)
    q, k, v = qkv[:, 0], qkv[:, 1], qkv[:, 2]          # [B, H, D]
    if sequence_lengths is not None:
        sl = sequence_lengths._value if isinstance(
            sequence_lengths, Tensor) else jnp.asarray(sequence_lengths)
        off = sl.reshape(B).astype(jnp.int32)
    else:
        off = jnp.zeros((B,), jnp.int32)
    from ....core.enforce import enforce as _enf2
    _enf2(rotary_emb_dims == 0 and not use_neox_rotary_style,
          "masked_multihead_attention: apply rotary embeddings at the "
          "model level (ops/nn_ops.fused_rope); the fused in-kernel "
          "rotary path (rotary_emb_dims/use_neox_rotary_style) is not "
          "provided here")
    k_cache = cv[0].at[jnp.arange(B), :, off, :].set(
        k.astype(cv.dtype))
    v_cache = cv[1].at[jnp.arange(B), :, off, :].set(
        v.astype(cv.dtype))
    out = _dense_ragged(q[:, None], k_cache, v_cache, off)
    new_cache = jnp.stack([k_cache, v_cache])
    return (Tensor(out.reshape(B, H * D), stop_gradient=True),
            Tensor(new_cache, stop_gradient=True))


def fused_multi_transformer(x, ln_scales, ln_biases, qkv_weights,
                            qkv_biases, linear_weights, linear_biases,
                            ffn_ln_scales, ffn_ln_biases, ffn1_weights,
                            ffn1_biases, ffn2_weights, ffn2_biases,
                            pre_layer_norm=True, epsilon=1e-5,
                            cache_kvs=None, pre_caches=None, seq_lens=None,
                            rotary_embs=None, time_step=None,
                            attn_mask=None, dropout_rate=0.0,
                            rotary_emb_dims=0, activation="gelu",
                            training=False, mode="upscale_in_train",
                            trans_qkvw=True, ring_id=-1, name=None,
                            num_heads=None):
    """Stateless functional form of the FusedMultiTransformer stack
    (num_heads: required with 2-D [h, 3h] qkv weights; inferred from
    the reference 4-D layout or the caches otherwise).
    (reference: incubate/nn/functional/fused_transformer.py:964 over
    fused_multi_transformer_op.cu.h — here the same math as
    incubate.nn.FusedMultiTransformer._layer, with caller-owned weight
    lists). qkv_weights: per layer [3*h, h] when trans_qkvw (reference
    default) else [h, 3*h]. Returns out, or (out, cache_kvs) when
    caches are passed."""
    from ....nn import functional as F
    from ....ops import manipulation as M
    from ....nn.functional import flash_attention
    from ....models.llama import _cache_attention
    from ....core.enforce import enforce as _enf

    for knob, kname in ((pre_caches, "pre_caches"),
                        (seq_lens, "seq_lens"),
                        (rotary_embs, "rotary_embs"),
                        (attn_mask, "attn_mask")):
        _enf(knob is None,
             f"fused_multi_transformer: {kname} is not served by this "
             "functional form (ragged/packed prefill is the Predictor "
             "serving path, rotary embeddings apply at the model level "
             "via ops/nn_ops.fused_rope, masking is causal+frontier) — "
             "pass None")
    _enf(rotary_emb_dims == 0,
         "fused_multi_transformer: in-kernel rotary "
         "(rotary_emb_dims != 0) is not served; apply "
         "ops/nn_ops.fused_rope at the model level")
    _enf(ring_id == -1,
         "fused_multi_transformer: ring_id tensor-parallelism is the "
         "distributed engine's job (distributed/engine.py shards the "
         "weights); pass ring_id=-1")

    def val(t):
        return t._value if isinstance(t, Tensor) else jnp.asarray(t)

    xv = x if isinstance(x, Tensor) else Tensor(jnp.asarray(x))
    B, S = xv.shape[0], xv.shape[1]
    offset = 0
    if time_step is not None:
        offset = (time_step._value if isinstance(time_step, Tensor)
                  else time_step)
    act = {"relu": F.relu, "gelu": F.gelu, "silu": F.silu}[activation]
    n_layers = len(qkv_weights)
    new_caches = []
    h = xv
    for i in range(n_layers):
        residual = h
        if pre_layer_norm:
            h = F.layer_norm(h, ln_scales[i], ln_biases[i],
                             epsilon=epsilon)
        qw = val(qkv_weights[i])
        embed_dim = residual.shape[-1]
        # reference qkv weight: [3, num_head, head_dim, h] when
        # trans_qkvw (default) else [h, 3, num_head, head_dim]
        if qw.ndim == 4:
            Hn = qw.shape[1] if trans_qkvw else qw.shape[2]
        elif num_heads is not None:
            Hn = int(num_heads)
        elif cache_kvs is not None:
            Hn = (cache_kvs[i][0].shape[1]
                  if isinstance(cache_kvs[i], (tuple, list))
                  else val(cache_kvs[i]).shape[2])
        else:
            from ....core.enforce import enforce as _enf3

            _enf3(False,
                  "fused_multi_transformer: with 2-D qkv weights pass "
                  "num_heads= (the reference's 4-D [3, num_head, "
                  "head_dim, h] layout carries it implicitly)")
        Dh = embed_dim // Hn
        if trans_qkvw:
            qw = qw.reshape(-1, qw.shape[-1]).T     # [h, 3h]
        else:
            qw = qw.reshape(qw.shape[0], -1)
        qkv_v = h._value @ qw.astype(h._value.dtype)
        if qkv_biases is not None and qkv_biases[i] is not None:
            qkv_v = qkv_v + val(qkv_biases[i]).reshape(-1)
        if val(qkv_weights[i]).ndim == 4:
            # reference layout: qkv-major (q all heads, k, v)
            qkv5 = qkv_v.reshape(B, S, 3, Hn, Dh)
            q = Tensor(qkv5[:, :, 0])
            k = Tensor(qkv5[:, :, 1])
            v = Tensor(qkv5[:, :, 2])
        else:
            # 2-D [h, 3*h] layer convention: head-major, qkv within
            qkv4 = M.reshape(Tensor(qkv_v), (B, S, Hn, 3 * Dh))
            q, k, v = M.split(qkv4, 3, axis=-1)
        if cache_kvs is not None:
            c = cache_kvs[i]
            if not isinstance(c, (tuple, list)):
                cv = val(c)
                c = (cv[0], cv[1])
            k_cache = jax.lax.dynamic_update_slice_in_dim(
                c[0], jnp.swapaxes(k._value, 1, 2).astype(c[0].dtype),
                offset, axis=2)
            v_cache = jax.lax.dynamic_update_slice_in_dim(
                c[1], jnp.swapaxes(v._value, 1, 2).astype(c[1].dtype),
                offset, axis=2)
            ov = _cache_attention(q._value, k_cache, v_cache, offset, S)
            out = Tensor(ov.reshape(B, S, embed_dim), stop_gradient=True)
            new_caches.append((k_cache, v_cache))
        else:
            out = flash_attention(q, k, v, causal=True)[0]
            out = M.reshape(out, (B, S, embed_dim))
        out = F.linear(out, linear_weights[i], linear_biases[i])
        if dropout_rate:
            # reference: residual + dropout(attn_out) (fused_transformer
            # pseudo-code); same placement after the ffn below
            out = F.dropout(out, p=dropout_rate, training=training,
                            mode=mode)
        h = residual + out
        if not pre_layer_norm:
            # post-LN: the attention block's LayerNorm applies AFTER
            # its residual (reference pseudo-code, fused_transformer.py)
            h = F.layer_norm(h, ln_scales[i], ln_biases[i],
                             epsilon=epsilon)
        residual = h
        if pre_layer_norm:
            f = F.layer_norm(h, ffn_ln_scales[i], ffn_ln_biases[i],
                             epsilon=epsilon)
        else:
            f = h
        f = act(F.linear(f, ffn1_weights[i], ffn1_biases[i]))
        f = F.linear(f, ffn2_weights[i], ffn2_biases[i])
        if dropout_rate:
            f = F.dropout(f, p=dropout_rate, training=training,
                          mode=mode)
        h = residual + f
        if not pre_layer_norm:
            h = F.layer_norm(h, ffn_ln_scales[i], ffn_ln_biases[i],
                             epsilon=epsilon)
    if cache_kvs is not None:
        return h, new_caches
    return h


def block_multihead_attention(qkv, key_cache, value_cache,
                              seq_lens_encoder, seq_lens_decoder,
                              seq_lens_this_time, padding_offsets,
                              cum_offsets, cu_seqlens_q, cu_seqlens_k,
                              block_tables, pre_key_cache=None,
                              pre_value_cache=None,
                              cache_k_quant_scales=None,
                              cache_v_quant_scales=None,
                              cache_k_dequant_scales=None,
                              cache_v_dequant_scales=None,
                              qkv_out_scale=None, qkv_bias=None,
                              out_shift=None, out_smooth=None,
                              rope_emb=None, mask=None, tgt_mask=None,
                              max_seq_len=-1, block_size=64,
                              use_neox_style=False,
                              use_dynamic_cachekv_quant=False,
                              quant_round_type=1, quant_max_bound=127.0,
                              quant_min_bound=-127.0, out_scale=-1,
                              compute_dtype="default"):
    """Paged (block-table) KV-cache attention, decode phase (reference:
    incubate/nn/functional/block_multihead_attention.py:19 over
    phi/kernels/fusion/gpu/block_multi_head_attention_kernel.cu).

    The TPU redesign of the paged cache lives in
    ops/pallas/decode_attention.paged_decode_attention (the physical
    page id is gathered from a scalar-prefetched block table inside the
    BlockSpec index map); this wrapper serves the reference surface for
    the DECODE phase: one new token per row (seq_lens_this_time == 1),
    per-row write position = seq_lens_decoder, ragged frontiers. The
    encoder/prefill phase, cache quantization, in-kernel rope, and
    pre-caches are served by the Predictor paged path (inference/
    __init__.py) and nn.quant — pass those knobs there.

    Returns (out [B, H*D], qkv, key_cache, value_cache) with the
    caches functionally updated (immutable arrays: returned, the
    reference updates in place).
    """
    from ....core.enforce import enforce as _enf
    from ....ops.pallas import is_tpu_platform
    from ....ops.pallas.decode_attention import (paged_attention_dense,
                                                 paged_supported,
                                                 paged_decode_attention,
                                                 paged_kv_write)
    from ....core import flags as _flags

    for knob, name in ((pre_key_cache, "pre_key_cache"),
                       (pre_value_cache, "pre_value_cache"),
                       (cache_k_quant_scales, "cache_k_quant_scales"),
                       (cache_v_quant_scales, "cache_v_quant_scales"),
                       (cache_k_dequant_scales, "cache_k_dequant_scales"),
                       (cache_v_dequant_scales, "cache_v_dequant_scales"),
                       (qkv_out_scale, "qkv_out_scale"),
                       (out_shift, "out_shift"),
                       (out_smooth, "out_smooth"),
                       (rope_emb, "rope_emb"),
                       (mask, "mask"), (tgt_mask, "tgt_mask")):
        _enf(knob is None,
             f"block_multihead_attention: {name} is served by the "
             "Predictor paged path / nn.quant on TPU, not in-kernel")
    _enf(not use_dynamic_cachekv_quant and out_scale in (-1, None)
         and compute_dtype == "default" and quant_round_type == 1
         and quant_max_bound == 127.0 and quant_min_bound == -127.0,
         "block_multihead_attention: cache-kv quantization / output "
         "quant are served by nn.quant on TPU, not in-kernel — leave "
         "the quant knobs at their defaults")
    for knob, kname in ((padding_offsets, "padding_offsets"),
                        (cum_offsets, "cum_offsets"),
                        (cu_seqlens_q, "cu_seqlens_q"),
                        (cu_seqlens_k, "cu_seqlens_k")):
        _enf(knob is None,
             f"block_multihead_attention: {kname} is ragged-prefill "
             "packing metadata, served by the Predictor paged path "
             "(inference/__init__.py) — pass None in the decode phase")
    _enf(not use_neox_style,
         "block_multihead_attention: in-kernel neox rope is not served "
         "(rope applies at the model level via ops/nn_ops.fused_rope)")
    qv = qkv._value if isinstance(qkv, Tensor) else jnp.asarray(qkv)
    kp = key_cache._value if isinstance(key_cache, Tensor) \
        else jnp.asarray(key_cache)
    vp = value_cache._value if isinstance(value_cache, Tensor) \
        else jnp.asarray(value_cache)
    tbl = block_tables._value if isinstance(block_tables, Tensor) \
        else jnp.asarray(block_tables)
    sld = seq_lens_decoder._value if isinstance(seq_lens_decoder,
                                                Tensor) \
        else jnp.asarray(seq_lens_decoder)
    B = tbl.shape[0]
    P, KV, page, D = kp.shape
    _enf(block_size == page,
         lambda: f"block_multihead_attention: block_size ({block_size}) "
                 f"does not match the physical cache page size ({page}) "
                 "— the page size is fixed by the cache layout "
                 "[P, KV, page, D], it cannot be re-specified per call")
    _enf(max_seq_len in (-1, tbl.shape[1] * page),
         lambda: f"block_multihead_attention: max_seq_len "
                 f"({max_seq_len}) disagrees with the block-table "
                 f"capacity ({tbl.shape[1]} pages x {page}); pass -1 "
                 "(the capacity is fixed by the table shape)")
    import numpy as _np

    def _host(v):
        a = v._value if isinstance(v, Tensor) else v
        return None if isinstance(a, jax.core.Tracer) else _np.asarray(a)

    if seq_lens_encoder is not None:
        enc = _host(seq_lens_encoder)
        _enf(enc is None or bool((enc == 0).all()),
             "block_multihead_attention: this wrapper serves the DECODE "
             "phase only (seq_lens_encoder must be all zero); the "
             "encoder/prefill phase is the Predictor paged path")
    if seq_lens_this_time is not None:
        this = _host(seq_lens_this_time)
        _enf(this is None or bool((this == 1).all()),
             "block_multihead_attention: decode phase writes ONE new "
             "token per row (seq_lens_this_time must be all one); "
             "ragged prefill is the Predictor paged path")
    _enf(qv.shape[0] == B and qv.ndim == 2,
         "decode phase: qkv is [batchsize, 3*num_head*head_dim] "
         "(one new token per row; ragged prefill is the Predictor "
         "paged path)")
    # GQA layout (reference): qkv packs (H + 2*KV) head planes of D
    total_heads = qv.shape[1] // D
    _enf(qv.shape[1] % D == 0 and total_heads > 2 * KV,
         lambda: "block_multihead_attention: qkv width "
                 f"{qv.shape[1]} is not (num_q_heads + 2*{KV})*{D}")
    H = total_heads - 2 * KV
    if qkv_bias is not None:
        bv = qkv_bias._value if isinstance(qkv_bias, Tensor) \
            else jnp.asarray(qkv_bias)
        qv = qv + bv.reshape(1, -1)
    heads = qv.reshape(B, total_heads, D)
    q = heads[:, :H]                                       # [B, H, D]
    kw = heads[:, H:H + KV]                                # [B, KV, D]
    vw = heads[:, H + KV:]
    off = sld.reshape(B).astype(jnp.int32)
    if not isinstance(off, jax.core.Tracer):
        _enf(bool((_np.asarray(off) < tbl.shape[1] * page).all()),
             lambda: "block_multihead_attention: a row's "
                     "seq_lens_decoder exceeds its block table "
                     f"({tbl.shape[1]} pages x {page}); allocate more "
                     "pages")
    kp, vp = paged_kv_write(kp, vp, kw[:, None], vw[:, None], tbl, off)
    q4 = q[:, None]                                        # [B,1,H,D]
    if (_flags._get("use_pallas_kernels", True)
            and is_tpu_platform()
            and paged_supported(q4.shape, kp.shape)):
        out = paged_decode_attention(q4, kp, vp, tbl, off)
    else:
        out = paged_attention_dense(q4, kp, vp, tbl, off)
    return (Tensor(out.reshape(B, H * D), stop_gradient=True),
            Tensor(qv, stop_gradient=True),
            Tensor(kp, stop_gradient=True),
            Tensor(vp, stop_gradient=True))
