"""Latent-attention mixture-of-experts decoder, for serving.

The block of the DeepSeek-V2/V3 line of models (``model_type``
``deepseek_v2``/``deepseek_v3``, ``sarvam_mla``): multi-head latent
attention (one ``kv_lora_rank``-wide latent and one rotated key per
token, shared by every head), YaRN-scaled rotary positions on the
rotated part, SwiGLU experts chosen by a sigmoid top-k router with a
selection bias, shared experts, and leading dense layers.

It honours the serving contract of ``LlamaForCausalLM``:
``forward(input_ids, caches, offset)`` with per-layer paged tuples
``(c_pool, r_pool, tables[, counts])``, so ``Config.enable_paged_kv`` ->
``create_predictor`` -> ``ServingEngine`` runs it in the default mode
(prefill buckets + the decode program). ``kv_pool_shapes`` tells the
engine what to pool: per layer a latent pool ``[P, 1, page, d_c]`` and a
rotated-key pool ``[P, 1, page, d_r rounded up to the lanes]`` and nothing
per head. The forward
takes no ``valid``: the unified ragged step (chunked prefill, and with it
the prefix cache, host spill, speculative decoding and the
disaggregated phases) is refused by the engine at construction.

Three attention forms, chosen at trace time:

- prefill (``offset`` a concrete 0): the UNABSORBED form over the new
  positions, per-head keys and values built from the latent, causal
  self-attention; only ``[c | k_r]`` is written to the cache;
- decode (one new position per row, paged cache): the ABSORBED form,
  ``q_lat = q_nope @ W_k^T`` against the latent itself, the Pallas
  kernel ``mla_paged_decode_attention`` on TPU, its dense twin elsewhere;
- anything else (several positions at an offset, the static cache of
  ``Predictor.generate``): the absorbed form through the dense function.

Inference only: parameters are plain arrays, nothing records a tape.
An expert layer holds ``num_local_experts`` of the router's
``num_experts`` (``expert_offset`` on), one holder's share of an
expert-parallel layer; see ``GatedMoELayer``.
"""
from __future__ import annotations

import math
from dataclasses import dataclass, field
from typing import Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from ..framework.param_attr import ParamAttr
from ..incubate.distributed.models.moe import GatedMoELayer
from ..incubate.distributed.models.moe.moe_layer import swiglu
from ..nn import initializer as I
from ..nn.container import LayerList
from ..nn.layer import Layer
from ..observability import annotate as _annotate
from ..ops.pallas.decode_attention import _concrete_zero
from ..tensor import Tensor
from .llama import _apply_rope, _dispatch_kernel

__all__ = ["MLAMoEConfig", "MLAMoEForCausalLM", "mla_moe_tiny",
           "yarn_inv_freq", "yarn_mscale"]


@dataclass
class MLAMoEConfig:
    vocab_size: int = 32000
    hidden_size: int = 4096
    num_layers: int = 6
    num_heads: int = 64
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
    num_shared_experts: int = 1
    first_k_dense_replace: int = 1
    routed_scaling_factor: float = 2.5
    use_qk_norm: bool = True
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


def _mm(x, w):
    return jnp.dot(x, w, preferred_element_type=jnp.float32).astype(x.dtype)


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
    def __init__(self, cfg: MLAMoEConfig):
        super().__init__()
        self.cfg = cfg
        h, H = cfg.hidden_size, cfg.num_heads
        std = cfg.initializer_range
        ones = ParamAttr(initializer=I.Constant(1.0))
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

    def _kv_b(self):
        cfg = self.cfg
        w = self.kv_b_proj._value.reshape(
            cfg.kv_lora_rank, cfg.num_heads,
            cfg.qk_nope_head_dim + cfg.v_head_dim)
        return w[..., :cfg.qk_nope_head_dim], w[..., cfg.qk_nope_head_dim:]

    def forward(self, x, cache=None, offset=0):
        """x: values [B, S, hidden]. Returns (values [B, S, hidden],
        the cache tuple with its two arrays updated)."""
        cfg = self.cfg
        B, S = x.shape[0], x.shape[1]
        H, dc = cfg.num_heads, cfg.kv_lora_rank
        dn, dr, dv = (cfg.qk_nope_head_dim, cfg.qk_rope_head_dim,
                      cfg.v_head_dim)
        scale = cfg.softmax_scale
        cos, sin = self._rope
        q = _mm(x, self.q_proj._value).reshape(B, S, H, dn + dr)
        if cfg.use_qk_norm:
            q = _rms(q, self.q_norm._value, cfg.rms_norm_eps)
        q_n, q_r = q[..., :dn], _apply_rope(q[..., dn:], cos, sin, offset)
        ckr = _mm(x, self.kv_a_proj._value)
        c = _rms(ckr[..., :dc], self.kv_a_norm._value, cfg.rms_norm_eps)
        k_r = _apply_rope(ckr[..., None, dc:], cos, sin, offset)  # [B,S,1,dr]
        w_k, w_v = self._kv_b()
        lanes = ((0, 0),) * 3 + ((0, cfg.rope_cache_width - dr),)

        paged = cache is not None and len(cache) >= 3
        if paged:
            from ..ops.pallas.decode_attention import paged_kv_write

            c_pool, r_pool, tables = cache[:3]
            c_pool, r_pool = paged_kv_write(
                c_pool, r_pool, c[:, :, None, :], jnp.pad(k_r, lanes),
                tables, offset)
            new_cache = (c_pool, r_pool, tables) + tuple(cache[3:])
        elif cache is not None:         # static [B, 1, M, d] caches
            off = jnp.broadcast_to(
                jnp.asarray(offset, jnp.int32).reshape(-1), (B,))
            dus = lambda buf, new, o: lax.dynamic_update_slice_in_dim(
                buf, new, o, axis=1)
            c_pool = jax.vmap(dus)(cache[0], jnp.swapaxes(
                c[:, :, None, :], 1, 2).astype(cache[0].dtype), off)
            r_pool = jax.vmap(dus)(cache[1], jnp.swapaxes(
                jnp.pad(k_r, lanes), 1, 2).astype(cache[1].dtype), off)
            new_cache = (c_pool, r_pool)
        else:
            new_cache = None

        if cache is None or _concrete_zero(offset):
            # unabsorbed: per-head keys and values from the latent
            kv_n = jnp.einsum("bsc,chd->bshd", c, w_k,
                              preferred_element_type=jnp.float32
                              ).astype(x.dtype)
            v = jnp.einsum("bsc,chd->bshd", c, w_v,
                           preferred_element_type=jnp.float32
                           ).astype(x.dtype)
            k = jnp.concatenate(
                [kv_n, jnp.broadcast_to(k_r, (B, S, H, dr))], axis=-1)
            o = _causal_attention(jnp.concatenate([q_n, q_r], -1), k, v,
                                  scale)
        else:
            from ..ops.pallas import mla_attention as _ma

            off = jnp.broadcast_to(
                jnp.asarray(offset, jnp.int32).reshape(-1), (B,))
            q_lat = jnp.einsum("bshd,chd->bshc", q_n, w_k,
                               preferred_element_type=jnp.float32
                               ).astype(x.dtype)
            q_r = jnp.pad(q_r, lanes)
            if paged:
                u = _dispatch_kernel(
                    "mla_paged_decode_attention",
                    lambda: S == 1 and _ma.mla_paged_supported(
                        (B, H, dc), c_pool.shape, r_pool.shape),
                    lambda: _ma.mla_paged_decode_attention(
                        q_lat[:, 0], q_r[:, 0], c_pool, r_pool, tables,
                        off, scale)[:, None],
                    lambda: _ma.mla_paged_attention_dense(
                        q_lat, q_r, c_pool, r_pool, tables, off, scale))
            else:
                u = _ma.mla_attention_dense(q_lat, q_r, c_pool[:, 0],
                                            r_pool[:, 0], off, scale)
            o = jnp.einsum("bshc,chd->bshd", u, w_v,
                           preferred_element_type=jnp.float32
                           ).astype(x.dtype)
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


class MLAMoEDecoderLayer(Layer):
    def __init__(self, cfg: MLAMoEConfig, index: int):
        super().__init__()
        self.cfg = cfg
        ones = ParamAttr(initializer=I.Constant(1.0))
        self.input_layernorm = self.create_parameter((cfg.hidden_size,),
                                                     attr=ones)
        self.self_attn = LatentAttention(cfg)
        self.post_attention_layernorm = self.create_parameter(
            (cfg.hidden_size,), attr=ones)
        self.is_moe = index >= cfg.first_k_dense_replace
        if self.is_moe:
            std = cfg.initializer_range
            self.mlp = GatedMoELayer(
                cfg.hidden_size, cfg.moe_intermediate_size,
                cfg.num_experts, cfg.num_local_experts, cfg.expert_offset,
                top_k=cfg.num_experts_per_tok,
                routed_scaling_factor=cfg.routed_scaling_factor,
                num_shared_experts=cfg.num_shared_experts,
                weight_attr=_attr(std),
                down_attr=_attr(std / math.sqrt(2 * cfg.num_layers)))
        else:
            self.mlp = DenseSwiGLU(cfg)

    def forward(self, x, cache=None, offset=0):
        eps = self.cfg.rms_norm_eps
        with _annotate("attention"):
            a, cache = self.self_attn(
                _rms(x, self.input_layernorm._value, eps), cache=cache,
                offset=offset)
        x = x + a
        with _annotate("mlp"):
            h = _rms(x, self.post_attention_layernorm._value, eps)
            if not self.is_moe:
                y = self.mlp(h)
            elif cache is not None and len(cache) == 4:   # routing counter
                y, counts = self.mlp(h, counts=cache[3])
                y, cache = y._value, cache[:3] + (counts,)
            else:
                y = self.mlp(h)._value
        return x + y, cache


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
        self.layers = LayerList([MLAMoEDecoderLayer(cfg, i)
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
        """Per layer, the shapes of the two pooled arrays: the latent
        and the rotated shared key, ONE cache head each."""
        cfg = self.config
        return [((P, 1, page, cfg.kv_lora_rank),
                 (P, 1, page, cfg.rope_cache_width))
                for _ in range(cfg.num_layers)]

    def moe_counter_shape(self):
        """[layers, held experts + 3] routing counters (``GatedMoELayer``);
        rows of dense layers stay 0."""
        return (self.config.num_layers, self.config.num_local_experts + 3)

    def _empty_caches(self, B: int, max_len: int, dtype):
        cfg = self.config
        return [(jnp.zeros((B, 1, max_len, cfg.kv_lora_rank), dtype),
                 jnp.zeros((B, 1, max_len, cfg.rope_cache_width), dtype))
                for _ in range(cfg.num_layers)]

    def forward(self, input_ids, caches=None, offset=0):
        ids = input_ids._value if isinstance(input_ids, Tensor) \
            else jnp.asarray(input_ids)
        with _annotate("mla_moe"):
            with _annotate("embed"):
                x = self.embed_tokens._value[ids]
            new_caches = []
            for i, layer in enumerate(self.layers):
                with _annotate(f"layer{i}"):
                    x, nc = layer(x, cache=None if caches is None
                                  else caches[i], offset=offset)
                new_caches.append(nc)
            x = _rms(x, self.norm._value, self.config.rms_norm_eps)
            logits = Tensor(jnp.dot(x, self.lm_head._value,
                                    preferred_element_type=jnp.float32
                                    ).astype(x.dtype), stop_gradient=True)
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
