"""Latent-attention mixture-of-experts decoder, for serving.

The block of the DeepSeek-V2/V3 line of models (``model_type``
``deepseek_v2``/``deepseek_v3``/``deepseek_v32``, ``sarvam_mla``):
multi-head latent attention (one ``kv_lora_rank``-wide latent and one
rotated key per token, shared by every head), YaRN-scaled rotary
positions on the rotated part, SwiGLU experts chosen by a sigmoid top-k
router with a selection bias, shared experts, and leading dense layers.
What the members of the line differ in is data of ``MLAMoEConfig``,
every piece off by default; nothing asks a model's name:

- ``q_lora_rank``: the query goes through a LATENT too (``x W_dq``,
  an RMSNorm on those ``q_lora_rank`` numbers, then ``W_uq`` to the
  heads); without it a direct ``W_q``. ``use_qk_norm`` (an RMSNorm on
  each head's query) is a switch of its own;
- ``index_heads`` x ``index_head_dim``, ``index_topk``: a learned INDEX
  (``ops/sparse_attention.py`` has the equations) chooses, for every
  query, the ``index_topk`` rows of the LATENT cache it attends to,
  exactly; all of them while there are no more than that. Index queries
  are projected from the query latent (from the layer's input without
  one), ONE index key a position (a LayerNorm) and a weight an index
  head from the layer's input; the first ``qk_rope_head_dim`` numbers of
  every index query and key turn by the layer's own rotary tables, the
  rest do not. ``collect_selection()`` hands a check the kept sets;
- ``n_group`` / ``topk_group``: the router keeps that many groups of
  experts before its top-k (``SigmoidTopKGate``);
- ``head_on_last_row``: a prefill program hands ``forward`` the rows'
  ``lengths`` and gets ``[B, vocab]``, the head on one row a prompt;
- ``shortcut_moe``: the SHORTCUT-CONNECTED double layer of the
  LongCat-Flash line (``ShortcutMoEDecoderLayer``): two latent
  attentions and two dense SwiGLUs a layer around ONE expert branch,
  which reads the first attention's output and whose result is added a
  sublayer later (so that an expert exchange could run beside the dense
  path and the second attention); each attention pools its own
  ``[c | k_r]``, so the model pools ``2 x num_layers`` tuples;
- ``zero_expert_num``: the router's last outputs are IDENTITY experts
  (``GatedMoELayer``); ``router_score_func`` / ``router_bias`` /
  ``norm_topk_prob``: the router's score (``"sigmoid"`` |
  ``"softmax"``), whether a bias steers its choice (None: a sigmoid's
  does, a softmax's has none) and whether the chosen weights are
  renormalised (``SigmoidTopKGate``);
- ``mla_scale_q_lora`` / ``mla_scale_kv_lora``: the query heads off the
  query latent times ``(hidden_size / q_lora_rank) ** 0.5``, the keys
  and values off the latent times ``(hidden_size / kv_lora_rank) **
  0.5``. The cache stays the normed latent: in the absorbed form the
  factor stands on ``q_lat`` and on the output product.

It honours the serving contract of ``LlamaForCausalLM``:
``forward(input_ids, caches, offset)`` with per-layer paged tuples
``(c_pool, r_pool[, index_pool], tables[, counts])``, so
``Config.enable_paged_kv`` -> ``create_predictor`` -> ``ServingEngine``
runs it in the default mode (prefill buckets + the decode program).
``kv_pool_shapes`` tells the engine what to pool: per layer a latent
pool ``[P, 1, page, d_c]`` and a rotated-key pool ``[P, 1, page, d_r
rounded up to the lanes]`` and nothing per head; with an index, its keys
as a third array ``[P, 1, page, index_head_dim rounded up]`` under the
same table. The forward takes no ``valid``: the unified ragged step
(chunked prefill, and with it the prefix cache, host spill, speculative
decoding and the disaggregated phases) is refused by the engine at
construction; ``key_selection`` tells it a model selects.

Three attention forms, chosen at trace time:

- prefill (``offset`` a concrete 0): the UNABSORBED form over the new
  positions, per-head keys and values built from the latent, causal
  self-attention (with an index: the kept sets first, ``kept_mask``,
  then the Pallas kernel ``kept_flash_attention`` over them, every head
  a KV head of its own, keys wider than values; off the TPU, or where
  the rows are not whole blocks, ``sparse_causal_attention``'s loop in
  plain ``lax``); only ``[c | k_r]`` (and the index key) is written to
  the cache;
- decode (one new position per row, paged cache): the ABSORBED form,
  ``q_lat = q_nope @ W_k^T`` against the latent itself, the Pallas
  kernel ``mla_paged_decode_attention`` on TPU, its dense twin
  elsewhere; with an index the row's index-key pages are scored and
  selected first and ``mla_paged_sparse_decode_attention`` walks the
  row's pages with the kept positions as one more mask;
- anything else (several positions at an offset, the static cache of
  ``Predictor.generate``): the absorbed form through the dense function.

Inference only: parameters are plain arrays, nothing records a tape.
An expert layer holds ``num_local_experts`` of the router's
``num_experts`` (``expert_offset`` on), one holder's share of an
expert-parallel layer; see ``GatedMoELayer``.
"""
from __future__ import annotations

import contextlib
import math
from dataclasses import dataclass, field
from typing import Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from ..core.enforce import enforce
from ..framework.param_attr import ParamAttr
from ..incubate.distributed.models.moe import GatedMoELayer
from ..incubate.distributed.models.moe.moe_layer import swiglu
from ..nn import initializer as I
from ..nn.container import LayerList
from ..nn.layer import Layer
from ..observability import annotate as _annotate
from ..ops.pallas.decode_attention import _concrete_zero
from ..ops.sparse_attention import (count_kept, kept_mask, select_rows,
                                    selection_sink,
                                    sparse_causal_attention)
from ..tensor import Tensor
from .llama import _apply_rope, _dispatch_kernel

__all__ = ["MLAMoEConfig", "MLAMoEForCausalLM", "mla_moe_tiny",
           "sparse_mla_tiny", "shortcut_moe_tiny", "yarn_inv_freq",
           "yarn_mscale"]

# index heads whose products stand side by side in one pass of the
# index scores (``index_scores(head_block=)``): 16 x 512 rows x 8,192
# keys float32 are 256 MiB
_INDEX_HEADS_A_PASS = 16


@dataclass
class MLAMoEConfig:
    vocab_size: int = 32000
    hidden_size: int = 4096
    num_layers: int = 6
    num_heads: int = 64
    q_lora_rank: int = 0                    # 0 = a direct W_q
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    intermediate_size: int = 16384          # the leading dense layers
    moe_intermediate_size: int = 2048       # one expert
    num_experts: int = 128                  # the router's width
    num_local_experts: Optional[int] = None     # held here; None = all
    expert_offset: int = 0
    num_experts_per_tok: int = 8
    # the router keeps topk_group of n_group groups first; 0 = no groups
    n_group: int = 0
    topk_group: int = 0
    num_shared_experts: int = 1
    first_k_dense_replace: int = 1
    routed_scaling_factor: float = 2.5
    use_qk_norm: bool = True                # an RMSNorm a query HEAD
    # the index that selects cache rows (module docstring); 0 = none
    index_heads: int = 0
    index_head_dim: int = 0
    index_topk: int = 0
    attention_block: int = 512      # rows and keys a block, with an index
    head_on_last_row: bool = False
    # the shortcut-connected double layer and its router (docstring)
    shortcut_moe: bool = False
    zero_expert_num: int = 0
    router_score_func: str = "sigmoid"
    router_bias: Optional[bool] = None      # None = the score's default
    norm_topk_prob: bool = True
    mla_scale_q_lora: bool = False
    mla_scale_kv_lora: bool = False
    max_position_embeddings: int = 4096
    rope_theta: float = 10000.0
    # YaRN as DeepSeek's ``deepseek_yarn``; None = plain rotary
    rope_scaling: Optional[Dict] = field(default_factory=lambda: {
        "factor": 40, "original_max_position_embeddings": 4096,
        "beta_fast": 32, "beta_slow": 1, "mscale": 1, "mscale_all_dim": 1})
    rms_norm_eps: float = 1e-6
    initializer_range: float = 0.02
    dtype: str = "float32"

    def __post_init__(self):
        if self.num_local_experts is None:
            self.num_local_experts = self.num_experts
        if self.index_topk:
            enforce(self.index_heads >= 1
                    and self.index_head_dim >= self.qk_rope_head_dim,
                    "an index needs index_heads and an index_head_dim "
                    "that holds the qk_rope_head_dim numbers it turns")
        enforce(not (self.mla_scale_q_lora and not self.q_lora_rank),
                "mla_scale_q_lora scales the query off a query latent: "
                "set q_lora_rank")
        enforce(not (self.shortcut_moe and (
            self.index_topk or self.first_k_dense_replace
            or self.num_shared_experts)),
            "a shortcut-connected layer has its two dense parts and one "
            "expert branch in EVERY layer, no shared expert and no "
            "index: first_k_dense_replace, num_shared_experts and "
            "index_topk are 0 with shortcut_moe")

    @property
    def attention_sublayers(self) -> int:
        """Latent attentions a layer, each with its own pooled arrays."""
        return 2 if self.shortcut_moe else 1

    @property
    def q_lora_scale(self) -> float:
        return (self.hidden_size / self.q_lora_rank) ** 0.5 \
            if self.mla_scale_q_lora else 1.0

    @property
    def kv_lora_scale(self) -> float:
        return (self.hidden_size / self.kv_lora_rank) ** 0.5 \
            if self.mla_scale_kv_lora else 1.0

    @property
    def index_cache_width(self) -> int:
        """Columns of the pooled index key: whole lanes, as
        ``rope_cache_width``."""
        return -(-self.index_head_dim // 128) * 128

    @property
    def q_head_dim(self) -> int:
        return self.qk_nope_head_dim + self.qk_rope_head_dim

    @property
    def rope_cache_width(self) -> int:
        """Columns of the rotated key's cache array: ``qk_rope_head_dim``
        rounded up to whole 128-wide lanes, the rest zeros. A narrower
        array gets a transposed layout from XLA on the TPU and a copy of
        the whole pool on either side of every kernel call; the chip
        pads it to the lanes anyway."""
        return -(-self.qk_rope_head_dim // 128) * 128

    @property
    def softmax_scale(self) -> float:
        m = 1.0
        if self.rope_scaling:
            m = yarn_mscale(self.rope_scaling["factor"],
                            self.rope_scaling.get("mscale_all_dim", 0))
        return self.q_head_dim ** -0.5 * m * m


def yarn_mscale(factor: float, mscale: float) -> float:
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def yarn_inv_freq(dim: int, theta: float, rs: Optional[Dict]) -> np.ndarray:
    """Inverse frequencies of the rotated part: below ``low`` rotations
    the plain ones, above ``high`` the interpolated ones (plain /
    factor), a linear ramp between (``deepseek_yarn``)."""
    plain = 1.0 / theta ** (np.arange(0, dim, 2, dtype=np.float64) / dim)
    if not rs:
        return plain
    orig = rs["original_max_position_embeddings"]

    def corr(rot):
        return dim * math.log(orig / (rot * 2 * math.pi)) \
            / (2 * math.log(theta))

    low = max(math.floor(corr(rs["beta_fast"])), 0)
    high = min(math.ceil(corr(rs["beta_slow"])), dim - 1)
    ramp = np.clip((np.arange(dim // 2, dtype=np.float64) - low)
                   / max(high - low, 1e-3), 0, 1)
    return plain / rs["factor"] * ramp + plain * (1 - ramp)


def _rope_tables(cfg: MLAMoEConfig):
    inv = yarn_inv_freq(cfg.qk_rope_head_dim, cfg.rope_theta,
                        cfg.rope_scaling)
    f = np.outer(np.arange(cfg.max_position_embeddings, dtype=np.float64),
                 inv)
    emb = np.concatenate([f, f], axis=-1)
    rs = cfg.rope_scaling
    m = 1.0 if not rs else (yarn_mscale(rs["factor"], rs.get("mscale", 1))
                            / yarn_mscale(rs["factor"],
                                          rs.get("mscale_all_dim", 0)))
    return (jnp.asarray(np.cos(emb) * m, jnp.float32),
            jnp.asarray(np.sin(emb) * m, jnp.float32))


def _attr(std):
    return ParamAttr(initializer=I.Normal(mean=0.0, std=std))


def _rms(x, weight, eps):
    from ..ops.pallas.rms_norm import (rms_norm_dense, rms_norm_fused,
                                       rms_norm_supported)

    return _dispatch_kernel(
        "rms_norm", lambda: rms_norm_supported(x.shape),
        lambda: rms_norm_fused(x, weight, float(eps)),
        lambda: rms_norm_dense(x, weight, float(eps)))


def _mm(x, w, scale: float = 1.0):
    """x @ w, accumulated in float32; a ``scale`` other than 1 stands on
    that float32 product, before it is rounded to x's type."""
    return _scaled(jnp.dot(x, w, preferred_element_type=jnp.float32),
                   scale).astype(x.dtype)


def _scaled(y, scale: float):
    """``y * scale``; ``y`` itself (no op traced) at a scale of 1."""
    return y if scale == 1.0 else y * scale


def _causal_attention(q, k, v, scale):
    """Prefill's self-attention over the new positions, [B, S, H, D_qk]
    against [B, S, H, D_v]. The flash kernel wants one head size that
    fills the lanes; a q/k head of 192 against a v head of 128 takes
    the dense path (scores in float32)."""
    from ..ops.pallas.flash_attention import (flash_attention_fwd,
                                              flash_supported)

    def dense():
        S = q.shape[1]
        s = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                       preferred_element_type=jnp.float32) * scale
        s = jnp.where(jnp.tril(jnp.ones((S, S), bool)), s, -1e30)
        p = jax.nn.softmax(s, axis=-1).astype(v.dtype)
        return jnp.einsum("bhqk,bkhd->bqhd", p, v,
                          preferred_element_type=jnp.float32
                          ).astype(q.dtype)

    return _dispatch_kernel(
        "flash_attention",
        lambda: q.shape[-1] == v.shape[-1]
        and flash_supported(q.shape, k.shape),
        lambda: flash_attention_fwd(q, k, v, True, scale, False), dense)


class LatentAttention(Layer):
    def __init__(self, cfg: MLAMoEConfig, scope: str = "attn"):
        super().__init__()
        self.cfg, self.scope = cfg, scope
        h, H = cfg.hidden_size, cfg.num_heads
        std = cfg.initializer_range
        ones = ParamAttr(initializer=I.Constant(1.0))
        if cfg.q_lora_rank:
            self.q_a_proj = self.create_parameter((h, cfg.q_lora_rank),
                                                  attr=_attr(std))
            self.q_a_norm = self.create_parameter((cfg.q_lora_rank,),
                                                  attr=ones)
            self.q_b_proj = self.create_parameter(
                (cfg.q_lora_rank, H * cfg.q_head_dim), attr=_attr(std))
        else:
            self.q_proj = self.create_parameter((h, H * cfg.q_head_dim),
                                                attr=_attr(std))
        self.kv_a_proj = self.create_parameter(
            (h, cfg.kv_lora_rank + cfg.qk_rope_head_dim), attr=_attr(std))
        self.kv_a_norm = self.create_parameter((cfg.kv_lora_rank,),
                                               attr=ones)
        if cfg.use_qk_norm:
            self.q_norm = self.create_parameter((cfg.q_head_dim,),
                                                attr=ones)
        self.kv_b_proj = self.create_parameter(
            (cfg.kv_lora_rank,
             H * (cfg.qk_nope_head_dim + cfg.v_head_dim)), attr=_attr(std))
        self.o_proj = self.create_parameter(
            (H * cfg.v_head_dim, h),
            attr=_attr(std / math.sqrt(2 * cfg.num_layers)))
        self._rope = _rope_tables(cfg)
        self.sparse = bool(cfg.index_topk)
        self.n_pools = 3 if self.sparse else 2      # arrays it pools
        if self.sparse:
            Hi, di = cfg.index_heads, cfg.index_head_dim
            self.index_q_proj = self.create_parameter(
                (cfg.q_lora_rank or h, Hi * di), attr=_attr(std))
            self.index_k_proj = self.create_parameter((h, di),
                                                      attr=_attr(std))
            self.index_w_proj = self.create_parameter((h, Hi),
                                                      attr=_attr(std))
            self.index_k_norm = self.create_parameter((di,), attr=ones)
            self.index_k_norm_bias = self.create_parameter(
                (di,), attr=ParamAttr(initializer=I.Constant(0.0)))

    def _kv_b(self):
        cfg = self.cfg
        w = self.kv_b_proj._value.reshape(
            cfg.kv_lora_rank, cfg.num_heads,
            cfg.qk_nope_head_dim + cfg.v_head_dim)
        return w[..., :cfg.qk_nope_head_dim], w[..., cfg.qk_nope_head_dim:]

    def _index(self, x, cq, offset):
        """The index of the new positions: queries [B, S, Hi, di] off
        the query latent ``cq`` and the ONE key [B, S, 1, di] off the
        layer's input (a LayerNorm), the first ``qk_rope_head_dim``
        numbers of each turned by the layer's rotary tables, and the
        heads' weights [B, S, Hi] float32 (scaled by ``Hi ** -0.5 *
        di ** -0.5``)."""
        cfg = self.cfg
        B, S = x.shape[0], x.shape[1]
        Hi, di, dr = (cfg.index_heads, cfg.index_head_dim,
                      cfg.qk_rope_head_dim)
        cos, sin = self._rope
        iq = _mm(cq, self.index_q_proj._value).reshape(B, S, Hi, di)
        ik = _mm(x, self.index_k_proj._value).astype(jnp.float32)
        ik = ik - ik.mean(-1, keepdims=True)            # LayerNorm
        ik = ik * lax.rsqrt((ik * ik).mean(-1, keepdims=True)
                            + cfg.rms_norm_eps)
        ik = (ik * self.index_k_norm._value.astype(jnp.float32)
              + self.index_k_norm_bias._value.astype(jnp.float32)
              ).astype(x.dtype).reshape(B, S, 1, di)
        iw = _mm(x, self.index_w_proj._value).astype(jnp.float32) \
            * (Hi ** -0.5 * di ** -0.5)
        turn = lambda a: jnp.concatenate(
            [_apply_rope(a[..., :dr], cos, sin, offset), a[..., dr:]], -1)
        return turn(iq), turn(ik), iw

    def _kept_prefill(self, q, k, v, iq, ik, iw):
        """Causal self-attention of positions 0..S-1 over the sets the
        index keeps, [B, S, H, dv]: on TPU the kept sets first
        (``kept_mask``) and then ``kept_flash_attention`` over them,
        where whole blocks of rows fill the sequence; else
        ``sparse_causal_attention``'s loop over key blocks in plain
        ``lax`` (the same sets, the same sums)."""
        from ..ops.pallas import kept_attention as _ka

        cfg, scale = self.cfg, self.cfg.softmax_scale
        topk, block = cfg.index_topk, cfg.attention_block
        names = tuple(f"{self.scope}.{part}"
                      for part in ("index", "select", "attend"))
        kept = selection_sink()

        def kernel():
            keep = kept_mask(iq, ik, iw, topk, block, names[:2],
                             _INDEX_HEADS_A_PASS)
            if kept is not None:
                kept.append(keep)
            with _annotate(names[2]):
                return _ka.kept_flash_attention(q, k, v, keep, scale, block)

        def loop():
            o = sparse_causal_attention(
                q, k, v, iq, ik, iw, scale, topk, block, scopes=names,
                want_mask=kept is not None,
                head_block=_INDEX_HEADS_A_PASS)
            if kept is not None:
                o, mask = o
                kept.append(mask)
            return o

        return _dispatch_kernel(
            "kept_flash_attention",
            lambda: _ka.kept_flash_supported(q.shape, v.shape, block),
            kernel, loop)

    def forward(self, x, cache=None, offset=0):
        """x: values [B, S, hidden]. Returns (values [B, S, hidden],
        the cache tuple with its pooled arrays updated)."""
        cfg = self.cfg
        B, S = x.shape[0], x.shape[1]
        H, dc = cfg.num_heads, cfg.kv_lora_rank
        dn, dr, dv = (cfg.qk_nope_head_dim, cfg.qk_rope_head_dim,
                      cfg.v_head_dim)
        scale, kvs = cfg.softmax_scale, cfg.kv_lora_scale
        cos, sin = self._rope
        if cfg.q_lora_rank:
            cq = _rms(_mm(x, self.q_a_proj._value), self.q_a_norm._value,
                      cfg.rms_norm_eps)
            q = _mm(cq, self.q_b_proj._value, cfg.q_lora_scale).reshape(
                B, S, H, dn + dr)
        else:
            cq = x
            q = _mm(x, self.q_proj._value).reshape(B, S, H, dn + dr)
        if cfg.use_qk_norm:
            q = _rms(q, self.q_norm._value, cfg.rms_norm_eps)
        q_n, q_r = q[..., :dn], _apply_rope(q[..., dn:], cos, sin, offset)
        ckr = _mm(x, self.kv_a_proj._value)
        c = _rms(ckr[..., :dc], self.kv_a_norm._value, cfg.rms_norm_eps)
        k_r = _apply_rope(ckr[..., None, dc:], cos, sin, offset)  # [B,S,1,dr]
        w_k, w_v = self._kv_b()
        lanes = ((0, 0),) * 3 + ((0, cfg.rope_cache_width - dr),)
        n = self.n_pools
        more = ()
        if self.sparse:
            with _annotate(f"{self.scope}.index"):
                iq, ik, iw = self._index(x, cq, offset)
            if cache is not None:   # the index key joins c and k_r
                more = ((cache[2], jnp.pad(ik, ((0, 0),) * 3 + ((
                    0, cfg.index_cache_width - ik.shape[-1]),))),)

        paged = cache is not None and len(cache) > n
        if paged:
            from ..ops.pallas.decode_attention import paged_kv_write

            tables = cache[n]
            pools = paged_kv_write(
                cache[0], cache[1], c[:, :, None, :], jnp.pad(k_r, lanes),
                tables, offset, **({"more": more} if more else {}))
            new_cache = pools + tuple(cache[n:])
        elif cache is not None:         # static [B, 1, M, d] caches
            off = jnp.broadcast_to(
                jnp.asarray(offset, jnp.int32).reshape(-1), (B,))
            dus = lambda buf, new, o: lax.dynamic_update_slice_in_dim(
                buf, new, o, axis=1)
            new_cache = pools = tuple(
                jax.vmap(dus)(buf, jnp.swapaxes(new, 1, 2).astype(
                    buf.dtype), off)
                for buf, new in ((cache[0], c[:, :, None, :]),
                                 (cache[1], jnp.pad(k_r, lanes))) + more)
        else:
            new_cache = pools = None
        if pools is not None:
            c_pool, r_pool = pools[:2]

        if cache is None or _concrete_zero(offset):
            # unabsorbed: per-head keys and values from the latent
            kv_n = _scaled(jnp.einsum("bsc,chd->bshd", c, w_k,
                                      preferred_element_type=jnp.float32),
                           kvs).astype(x.dtype)
            v = _scaled(jnp.einsum("bsc,chd->bshd", c, w_v,
                                   preferred_element_type=jnp.float32),
                        kvs).astype(x.dtype)
            k = jnp.concatenate(
                [kv_n, jnp.broadcast_to(k_r, (B, S, H, dr))], axis=-1)
            q = jnp.concatenate([q_n, q_r], -1)
            if self.sparse:
                o = self._kept_prefill(q, k, v, iq, ik[:, :, 0], iw)
            else:
                o = _causal_attention(q, k, v, scale)
        else:
            from ..ops.pallas import mla_attention as _ma

            off = jnp.broadcast_to(
                jnp.asarray(offset, jnp.int32).reshape(-1), (B,))
            q_lat = _scaled(jnp.einsum("bshd,chd->bshc", q_n, w_k,
                                       preferred_element_type=jnp.float32),
                            kvs).astype(x.dtype)
            q_r = jnp.pad(q_r, lanes)
            keep = None
            if self.sparse:
                di = cfg.index_head_dim
                if paged:
                    from ..ops.pallas.decode_attention import gather_pages

                    with _annotate(f"{self.scope}.index"):
                        keys = gather_pages(pools[2], tables)[:, 0, :, :di]
                else:
                    keys = pools[2][:, 0, :, :di]
                keep = select_rows(iq, iw, keys, off, cfg.index_topk,
                                   self.scope)
                if paged and len(cache) == n + 2:   # the device counter
                    with _annotate(f"{self.scope}.select"):
                        new_cache = new_cache[:-1] + (count_kept(
                            keep, off, cfg.index_topk, new_cache[-1]),)
            # a layer that selects attends under the kept mask, by the
            # same walk under another kernel name
            with _annotate(f"{self.scope}.attend") if self.sparse \
                    else contextlib.nullcontext():
                if paged:
                    u = _dispatch_kernel(
                        "mla_paged_sparse_decode_attention" if self.sparse
                        else "mla_paged_decode_attention",
                        lambda: S == 1 and _ma.mla_paged_supported(
                            (B, H, dc), c_pool.shape, r_pool.shape),
                        lambda: _ma.mla_paged_decode_attention(
                            q_lat[:, 0], q_r[:, 0], c_pool, r_pool, tables,
                            off, scale, keep=None if keep is None
                            else keep[:, 0])[:, None],
                        lambda: _ma.mla_paged_attention_dense(
                            q_lat, q_r, c_pool, r_pool, tables, off, scale,
                            keep))
                else:
                    u = _ma.mla_attention_dense(
                        q_lat, q_r, c_pool[:, 0], r_pool[:, 0], off, scale,
                        keep)
            o = _scaled(jnp.einsum("bshc,chd->bshd", u, w_v,
                                   preferred_element_type=jnp.float32),
                        kvs).astype(x.dtype)
        return _mm(o.reshape(B, S, H * dv), self.o_proj._value), new_cache


class DenseSwiGLU(Layer):
    def __init__(self, cfg: MLAMoEConfig):
        super().__init__()
        h, m, std = (cfg.hidden_size, cfg.intermediate_size,
                     cfg.initializer_range)
        self.gate_proj = self.create_parameter((h, m), attr=_attr(std))
        self.up_proj = self.create_parameter((h, m), attr=_attr(std))
        self.down_proj = self.create_parameter(
            (m, h), attr=_attr(std / math.sqrt(2 * cfg.num_layers)))

    def forward(self, x):
        return swiglu(x, self.gate_proj._value, self.up_proj._value,
                      self.down_proj._value).astype(x.dtype)


def _expert_layer(cfg: MLAMoEConfig) -> GatedMoELayer:
    std = cfg.initializer_range
    return GatedMoELayer(
        cfg.hidden_size, cfg.moe_intermediate_size,
        cfg.num_experts, cfg.num_local_experts, cfg.expert_offset,
        top_k=cfg.num_experts_per_tok,
        routed_scaling_factor=cfg.routed_scaling_factor,
        num_shared_experts=cfg.num_shared_experts,
        weight_attr=_attr(std),
        down_attr=_attr(std / math.sqrt(2 * cfg.num_layers)),
        score_func=cfg.router_score_func,
        n_group=cfg.n_group, topk_group=cfg.topk_group,
        zero_expert_num=cfg.zero_expert_num, router_bias=cfg.router_bias,
        norm_topk_prob=cfg.norm_topk_prob)


def _counted(mlp: GatedMoELayer, h, cache):
    """The expert layer on ``h`` with the routing counter that ends the
    cache tuple brought up to date: (values, the cache tuple). The
    counter follows the pools and their table; a selecting model's two
    slots follow it."""
    counts = cache[-1]
    m = mlp.num_local_experts + 3 + bool(mlp.zero_expert_num)
    more = counts.shape[0] > m
    y, moe = mlp(h, counts=counts[:m] if more else counts)
    if more:
        moe = jnp.concatenate([moe, counts[m:]])
    return y._value, cache[:-1] + (moe,)


class MLAMoEDecoderLayer(Layer):
    def __init__(self, cfg: MLAMoEConfig, index: int):
        super().__init__()
        self.cfg = cfg
        ones = ParamAttr(initializer=I.Constant(1.0))
        self.input_layernorm = self.create_parameter((cfg.hidden_size,),
                                                     attr=ones)
        self.self_attn = LatentAttention(cfg, f"layer{index}.attn.sparse")
        self.post_attention_layernorm = self.create_parameter(
            (cfg.hidden_size,), attr=ones)
        self.is_moe = index >= cfg.first_k_dense_replace
        self.mlp = _expert_layer(cfg) if self.is_moe else DenseSwiGLU(cfg)

    def forward(self, x, cache=None, offset=0):
        eps, attn = self.cfg.rms_norm_eps, self.self_attn
        # a selecting layer's ops carry the scopes the sparse layers of
        # ``hybrid_moe.py`` carry
        with _annotate("attention"), (
                _annotate(attn.scope) if attn.sparse
                else contextlib.nullcontext()):
            a, cache = attn(
                _rms(x, self.input_layernorm._value, eps), cache=cache,
                offset=offset)
        x = x + a
        with _annotate("mlp"):
            h = _rms(x, self.post_attention_layernorm._value, eps)
            if not self.is_moe:
                y = self.mlp(h)
            elif cache is not None and len(cache) == attn.n_pools + 2:
                y, cache = _counted(self.mlp, h, cache)
            else:
                y = self.mlp(h)._value
        return x + y, cache


class ShortcutMoEDecoderLayer(Layer):
    """The shortcut-connected double layer (``shortcut_moe``), with x
    the layer's input::

        x1 = x  + MLA_0(RMSNorm(x))
        h  = RMSNorm(x1);  s = MoE(h)        # the shortcut branch
        x2 = x1 + FFN_0(h)
        x3 = x2 + MLA_1(RMSNorm(x2))
        x4 = x3 + FFN_1(RMSNorm(x3)) + s     # the layer's output

    ``forward``'s ``cache`` is a PAIR of cache tuples, one an attention;
    the routing counter, when the engine lends one, ends the first (the
    second's is lent alike and stays 0: the pool's ``lend`` knows one
    counter a pooled tuple). The norms are ``[2, hidden]``, a row a
    sublayer. The ops carry the scopes ``layerN.attn0`` / ``.attn1`` /
    ``.mlp0`` / ``.mlp1`` / ``.moe.shortcut``."""

    def __init__(self, cfg: MLAMoEConfig, index: int):
        super().__init__()
        self.cfg, self.scope = cfg, f"layer{index}"
        ones = ParamAttr(initializer=I.Constant(1.0))
        self.input_layernorm = self.create_parameter(
            (2, cfg.hidden_size), attr=ones)
        self.post_attention_layernorm = self.create_parameter(
            (2, cfg.hidden_size), attr=ones)
        self.self_attn = LayerList([
            LatentAttention(cfg, f"{self.scope}.attn{j}") for j in (0, 1)])
        self.mlps = LayerList([DenseSwiGLU(cfg) for _ in (0, 1)])
        self.mlp = _expert_layer(cfg)

    def forward(self, x, cache=None, offset=0):
        eps, name = self.cfg.rms_norm_eps, self.scope
        c0, c1 = (None, None) if cache is None else cache
        pre, post = (self.input_layernorm._value,
                     self.post_attention_layernorm._value)
        with _annotate(f"{name}.attn0"):
            a, c0 = self.self_attn[0](_rms(x, pre[0], eps), cache=c0,
                                      offset=offset)
        x = x + a
        h = _rms(x, post[0], eps)
        with _annotate(f"{name}.moe.shortcut"):
            if c0 is not None and len(c0) == self.self_attn[0].n_pools + 2:
                s, c0 = _counted(self.mlp, h, c0)
            else:
                s = self.mlp(h)._value
        with _annotate(f"{name}.mlp0"):
            x = x + self.mlps[0](h)
        with _annotate(f"{name}.attn1"):
            a, c1 = self.self_attn[1](_rms(x, pre[1], eps), cache=c1,
                                      offset=offset)
        x = x + a
        with _annotate(f"{name}.mlp1"):
            x = x + self.mlps[1](_rms(x, post[1], eps)) + s
        return x, (c0, c1)


class MLAMoEForCausalLM(Layer):
    """The decoder with an untied output head, over ``vocab_size`` rows
    (one holder's slice of the vocabulary is a smaller vocabulary)."""

    def __init__(self, config: MLAMoEConfig):
        super().__init__()
        self.config = config
        cfg = config
        std = cfg.initializer_range
        self.embed_tokens = self.create_parameter(
            (cfg.vocab_size, cfg.hidden_size), attr=_attr(std))
        block = ShortcutMoEDecoderLayer if cfg.shortcut_moe \
            else MLAMoEDecoderLayer
        self.layers = LayerList([block(cfg, i)
                                 for i in range(cfg.num_layers)])
        self.norm = self.create_parameter(
            (cfg.hidden_size,),
            attr=ParamAttr(initializer=I.Constant(1.0)))
        self.lm_head = self.create_parameter(
            (cfg.hidden_size, cfg.vocab_size), attr=_attr(std))
        if cfg.dtype not in ("float32", None):
            self.astype(cfg.dtype)

    # -- what the serving engine asks of a model -------------------------
    def kv_pool_shapes(self, P: int, page: int):
        """Per ATTENTION (one a layer; two a shortcut-connected layer,
        in the order they run), the shapes of the pooled arrays: the
        latent and the rotated shared key, ONE cache head each; with an
        index, its one key a position as a third."""
        cfg = self.config
        return [((P, 1, page, cfg.kv_lora_rank),
                 (P, 1, page, cfg.rope_cache_width))
                + (((P, 1, page, cfg.index_cache_width),)
                   if cfg.index_topk else ())
                for _ in range(cfg.num_layers * cfg.attention_sublayers)]

    @property
    def key_selection(self) -> Optional[int]:
        """How many cache rows a query keeps; None without an index. The
        serving engine asks: it states its refusals for such a model and
        reports the share of rows kept."""
        return self.config.index_topk or None

    def moe_counter_shape(self):
        """[pooled tuples, held experts + 3] routing counters
        (``GatedMoELayer``), one a tuple of ``kv_pool_shapes``; rows of
        dense layers, and of a shortcut-connected layer's second
        attention, stay 0. With identity experts a slot more (their
        pairs, before the tokens). A model that selects has two slots
        more a layer: the decode step's rows whose kept count was not
        ``min(t + 1, index_topk)``, and its rows."""
        cfg = self.config
        return (cfg.num_layers * cfg.attention_sublayers,
                cfg.num_local_experts + 3 + bool(cfg.zero_expert_num)
                + (2 if self.key_selection else 0))

    def _empty_caches(self, B: int, max_len: int, dtype):
        return [tuple(jnp.zeros((B, 1, max_len, a[-1]), dtype)
                      for a in layer)
                for layer in self.kv_pool_shapes(1, 1)]

    @property
    def head_on_last_row(self) -> bool:
        """Whether a prefill program hands ``forward`` the rows'
        ``lengths`` (``Predictor._prefill_fn`` asks)."""
        return self.config.head_on_last_row

    def _head(self, x):
        x = _rms(x, self.norm._value, self.config.rms_norm_eps)
        return Tensor(jnp.dot(x, self.lm_head._value,
                              preferred_element_type=jnp.float32
                              ).astype(x.dtype), stop_gradient=True)

    def forward(self, input_ids, caches=None, offset=0, lengths=None):
        """Logits ``[B, S, vocab]``; with ``lengths`` ``[B]`` (a prefill
        of a ``head_on_last_row`` model) ``[B, vocab]``, of row b's
        position ``lengths[b] - 1`` alone."""
        ids = input_ids._value if isinstance(input_ids, Tensor) \
            else jnp.asarray(input_ids)
        with _annotate("mla_moe"):
            with _annotate("embed"):
                x = self.embed_tokens._value[ids]
            new_caches = []
            two = self.config.shortcut_moe      # two tuples a layer
            for i, layer in enumerate(self.layers):
                with _annotate(f"layer{i}"):
                    x, nc = layer(
                        x, cache=None if caches is None
                        else tuple(caches[2 * i:2 * i + 2]) if two
                        else caches[i], offset=offset)
                if two:
                    new_caches.extend(nc)
                else:
                    new_caches.append(nc)
            if lengths is None:
                logits = self._head(x)
            else:
                with _annotate("head"):
                    last = jnp.asarray(lengths, jnp.int32) - 1
                    logits = self._head(jnp.take_along_axis(
                        x, last[:, None, None], axis=1)[:, 0])
        return logits if caches is None else (logits, new_caches)


def mla_moe_tiny(**kw) -> MLAMoEConfig:
    """CPU-test size: every mechanism present (a leading dense layer,
    held experts a strict share of the router's, YaRN, q/k norms)."""
    base = dict(vocab_size=256, hidden_size=64, num_layers=3, num_heads=4,
                kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8,
                v_head_dim=16, intermediate_size=128,
                moe_intermediate_size=32, num_experts=16,
                num_local_experts=4, expert_offset=0,
                num_experts_per_tok=4, max_position_embeddings=128,
                rope_scaling={"factor": 4,
                              "original_max_position_embeddings": 32,
                              "beta_fast": 32, "beta_slow": 1, "mscale": 1,
                              "mscale_all_dim": 1})
    base.update(kw)
    return MLAMoEConfig(**base)


def sparse_mla_tiny(**kw) -> MLAMoEConfig:
    """CPU-test size with every switch of the line on: a query latent
    (no per-head query norm), 2 index heads of 16 (8 of them turned)
    that keep 8 cache rows over pages of 8 (contexts several times
    that), 8 experts in 4 groups of which 2 are kept, 4 of them held
    beside a shared one, a leading dense layer, the head on a prefill's
    last row."""
    base = dict(q_lora_rank=24, use_qk_norm=False, index_heads=2,
                index_head_dim=16, index_topk=8, attention_block=16,
                num_experts=8, num_local_experts=4, expert_offset=0,
                num_experts_per_tok=2, n_group=4, topk_group=2,
                head_on_last_row=True)
    base.update(kw)
    return mla_moe_tiny(**base)


def shortcut_moe_tiny(**kw) -> MLAMoEConfig:
    """CPU-test size of the shortcut-connected line: two attentions off
    a query latent and two dense parts a layer around one expert branch,
    both latent scale factors, a softmax router of 8 + 4 outputs (the
    last 4 identity experts) with a bias on the choice and no
    renormalisation, top-3, 4 of the 8 real experts held, plain rotary,
    no shared expert, no leading dense layer."""
    base = dict(num_layers=2, q_lora_rank=24, use_qk_norm=False,
                shortcut_moe=True, zero_expert_num=4,
                router_score_func="softmax", router_bias=True,
                norm_topk_prob=False, mla_scale_q_lora=True,
                mla_scale_kv_lora=True, num_experts=8,
                num_local_experts=4, expert_offset=0,
                num_experts_per_tok=3, num_shared_experts=0,
                first_k_dense_replace=0, routed_scaling_factor=6.0,
                rope_scaling=None, rms_norm_eps=1e-5)
    base.update(kw)
    return mla_moe_tiny(**base)
