"""A decoder whose layers differ by KIND, for serving.

Attention is ``"full"`` (every earlier position) or ``"window"`` (the
last ``sliding_window`` positions, the current one included), each kind
with its own number of KV heads and rotary base; keys and queries are
``qk_head_dim`` wide against ``v_head_dim``-wide values, rotary on the
first ``rotary_dim`` dims of a head of the kinds in ``rotary_kinds`` (a
kind left out turns nothing: its scores carry no position), the values
scaled by ``value_scale``, and a kind may carry one learned SINK logit a
query head that takes weight in the softmax and gives no value. The
feed-forward is ``"dense"`` (SwiGLU) or ``"experts"`` (``GatedMoELayer``:
a sigmoid top-k router over ``num_experts``, of which this holder has
``num_local_experts`` from ``expert_offset`` on, beside
``num_shared_experts`` that every holder runs whole).

Switches, all data of ``HybridMoEConfig`` and all off by default:
``qk_norm`` (an RMSNorm on every q and k head before the rotation),
``attention_gate`` (the attention result times ``sigmoid(u W_g)``, as
wide as the result, before ``o_proj``), ``sandwich_norm`` (a norm on
each branch's OUTPUT as well as on its input: four a layer),
``embedding_multiplier`` (the embedding's rows scaled once, at entry)
and ``head_on_last_row`` (below). With the defaults this is the block
of the MiMo-V2 line (``model_type`` ``mimo_v2_flash``); with window-only
rotary, q/k norms, the gate, sandwich norms, a multiplier of
``sqrt(hidden_size)`` and a shared expert it is the block of the AFMoE
line (``model_type`` ``afmoe``). Nothing asks a model's name.

It honours the serving contract of ``LlamaForCausalLM``:
``forward(input_ids, caches, offset)`` with per-layer paged tuples
``(k_pool, v_pool, table[, counts])``. Beside ``kv_pool_shapes`` and
``moe_counter_shape`` it tells the engine the PAGE CLASS of each layer
(``kv_page_classes``): a window layer only ever reads the pages that
hold its last ``sliding_window`` positions, so ``PagedKVCache`` gives it
a ring of pages a row instead of the whole context, and its table is
that ring. The forward takes no ``valid``: the unified ragged step
(chunked prefill, and with it the prefix cache, host spill and
speculative decoding) is refused by the engine at construction.

THE HEAD ON THE LAST ROW. A prefill needs one row of logits a prompt.
With ``head_on_last_row`` the model says so (``model.head_on_last_row``)
and ``Predictor._prefill_fn`` hands ``forward`` the rows' ``lengths``:
the final norm and the head then run on ``x[b, lengths[b] - 1]`` alone
and the program returns ``[B, vocab]``; no ``[B, S, vocab]`` array
exists (6.6 GB in float32 for 8,192 positions of a 200,192-row
vocabulary). Off, the program computes every position's logits and the
caller gathers one row, as it always did.

Attention forms, chosen at trace time:

- prefill (``offset`` a concrete 0, or no cache): blockwise causal
  self-attention over the new positions (``ops/blockwise_attention.py``:
  no ``[H, S, S]`` array, the band alone on a window layer). K and V of
  the prompt are written by the table: a window layer's PREFILL table is
  logical, one column a page, and the cache has sent every page but the
  prompt's last ``ring`` to the trash page;
- decode (one new position a row, paged): the row is written at
  ``(pos // page) % ring`` of a window layer's ring, then
  ``paged_decode_attention`` (``window=`` on a window layer) on TPU, its
  dense twin elsewhere;
- the static caches of ``Predictor.generate``: the dense function.

Inference only: parameters are plain arrays, nothing records a tape.
"""
from __future__ import annotations

import math
from dataclasses import dataclass, field
from typing import List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from ..core.enforce import enforce
from ..framework.param_attr import ParamAttr
from ..incubate.distributed.models.moe import GatedMoELayer
from ..nn import initializer as I
from ..nn.container import LayerList
from ..nn.layer import Layer
from ..observability import annotate as _annotate
from ..ops.blockwise_attention import blockwise_causal_attention
from ..ops.pallas import decode_attention as _da
from ..tensor import Tensor
from .llama import _apply_rope, _dispatch_kernel
from .mla_moe import DenseSwiGLU, _attr, _mm, _rms

__all__ = ["HybridMoEConfig", "HybridMoEForCausalLM", "hybrid_moe_tiny",
           "afmoe_tiny"]


@dataclass
class HybridMoEConfig:
    vocab_size: int = 32000
    hidden_size: int = 4096
    # one entry a layer: "full" | "window", and "dense" | "experts"
    attention_kinds: List[str] = field(default_factory=lambda: [
        "full", "window", "window", "window", "window", "full", "window"])
    ffn_kinds: List[str] = field(default_factory=lambda: [
        "dense"] + ["experts"] * 6)
    num_heads: int = 64
    num_kv_heads: int = 4                    # full layers
    window_num_kv_heads: int = 8             # window layers
    qk_head_dim: int = 192
    v_head_dim: int = 128
    rotary_dim: int = 64                     # leading dims of a head
    rope_theta: float = 5000000.0            # full layers
    window_rope_theta: float = 10000.0       # window layers
    sliding_window: int = 128
    full_sink: bool = False
    window_sink: bool = True
    value_scale: float = 0.707
    intermediate_size: int = 16384           # a dense layer
    moe_intermediate_size: int = 2048        # one expert
    num_experts: int = 256                   # the router's width
    num_local_experts: Optional[int] = None  # held here; None = all
    expert_offset: int = 0
    num_experts_per_tok: int = 8
    routed_scaling_factor: float = 1.0
    max_position_embeddings: int = 4096
    rms_norm_eps: float = 1e-5
    initializer_range: float = 0.02
    attention_block: int = 512               # prefill's rows a block
    # -- switches (module docstring); the defaults are MiMo-V2's block ----
    rotary_kinds: Tuple[str, ...] = ("full", "window")
    qk_norm: bool = False
    attention_gate: bool = False
    sandwich_norm: bool = False
    embedding_multiplier: float = 1.0
    num_shared_experts: int = 0
    head_on_last_row: bool = False
    dtype: str = "float32"

    def __post_init__(self):
        if self.num_local_experts is None:
            self.num_local_experts = self.num_experts
        enforce(len(self.attention_kinds) == len(self.ffn_kinds)
                and set(self.attention_kinds) <= {"full", "window"}
                and set(self.ffn_kinds) <= {"dense", "experts"},
                "attention_kinds (full | window) and ffn_kinds (dense | "
                "experts) name every layer once")
        enforce(self.rotary_dim % 2 == 0
                and self.rotary_dim <= self.qk_head_dim,
                "rotary_dim is an even number of a head's leading dims")
        self.rotary_kinds = tuple(self.rotary_kinds)
        enforce(set(self.rotary_kinds) <= {"full", "window"},
                "rotary_kinds names attention kinds (full | window)")

    @property
    def num_layers(self) -> int:
        return len(self.attention_kinds)

    def kv_heads(self, kind: str) -> int:
        return self.num_kv_heads if kind == "full" \
            else self.window_num_kv_heads

    def theta(self, kind: str) -> float:
        return self.rope_theta if kind == "full" else self.window_rope_theta

    def sink(self, kind: str) -> bool:
        return self.full_sink if kind == "full" else self.window_sink

    @property
    def k_cache_width(self) -> int:
        """Columns of the pooled key array: ``qk_head_dim`` rounded up to
        whole 128-wide lanes, the rest zeros. The chip tiles the last
        dim to the lanes anyway, and an array that does not fill them
        gets a layout of XLA's choosing and a copy of the whole pool on
        either side of a kernel call (``MLAMoEConfig.rope_cache_width``
        met it at 64)."""
        return -(-self.qk_head_dim // 128) * 128

    @property
    def softmax_scale(self) -> float:
        return self.qk_head_dim ** -0.5


def _rope_tables(dim: int, theta: float, max_len: int):
    inv = 1.0 / theta ** (np.arange(0, dim, 2, dtype=np.float64) / dim)
    f = np.outer(np.arange(max_len, dtype=np.float64), inv)
    emb = np.concatenate([f, f], axis=-1)           # rotate-half pairing
    return (jnp.asarray(np.cos(emb), jnp.float32),
            jnp.asarray(np.sin(emb), jnp.float32))


class HybridAttention(Layer):
    def __init__(self, cfg: HybridMoEConfig, kind: str):
        super().__init__()
        self.cfg, self.kind = cfg, kind
        h, H, KV = cfg.hidden_size, cfg.num_heads, cfg.kv_heads(kind)
        std = cfg.initializer_range
        self.kv = KV
        self.window = cfg.sliding_window if kind == "window" else None
        self.q_proj = self.create_parameter((h, H * cfg.qk_head_dim),
                                            attr=_attr(std))
        self.k_proj = self.create_parameter((h, KV * cfg.qk_head_dim),
                                            attr=_attr(std))
        self.v_proj = self.create_parameter((h, KV * cfg.v_head_dim),
                                            attr=_attr(std))
        self.o_proj = self.create_parameter(
            (H * cfg.v_head_dim, h),
            attr=_attr(std / math.sqrt(2 * cfg.num_layers)))
        self.has_sink = cfg.sink(kind)
        if self.has_sink:
            self.sinks = self.create_parameter((H,), attr=_attr(1.0))
        if cfg.qk_norm:
            ones = ParamAttr(initializer=I.Constant(1.0))
            self.q_norm = self.create_parameter((cfg.qk_head_dim,),
                                                attr=ones)
            self.k_norm = self.create_parameter((cfg.qk_head_dim,),
                                                attr=ones)
        if cfg.attention_gate:
            self.gate_proj = self.create_parameter(
                (h, H * cfg.v_head_dim), attr=_attr(std))
        self.rotates = kind in cfg.rotary_kinds
        if self.rotates:
            self._rope = _rope_tables(cfg.rotary_dim, cfg.theta(kind),
                                      cfg.max_position_embeddings)

    def _heads(self, x, proj, heads, norm, offset):
        """One of q / k: the projection split into ``heads``, each head
        normed (``qk_norm``) and turned (a kind in ``rotary_kinds``)."""
        cfg = self.cfg
        y = _mm(x, proj._value).reshape(
            x.shape[0], x.shape[1], heads, cfg.qk_head_dim)
        if cfg.qk_norm:
            y = _rms(y, norm._value, cfg.rms_norm_eps)
        return self._rotate(y, offset) if self.rotates else y

    def _rotate(self, x, offset):
        r = self.cfg.rotary_dim
        cos, sin = self._rope
        return jnp.concatenate(
            [_apply_rope(x[..., :r], cos, sin, offset), x[..., r:]], axis=-1)

    def forward(self, x, cache=None, offset=0):
        """x: values [B, S, hidden]. Returns (values [B, S, hidden],
        the cache tuple with its two arrays updated)."""
        cfg = self.cfg
        B, S = x.shape[0], x.shape[1]
        H, KV, dk, dv = cfg.num_heads, self.kv, cfg.qk_head_dim, \
            cfg.v_head_dim
        scale, window = cfg.softmax_scale, self.window
        sinks = self.sinks._value if self.has_sink else None
        q = self._heads(x, self.q_proj, H,
                        self.q_norm if cfg.qk_norm else None, offset)
        k = self._heads(x, self.k_proj, KV,
                        self.k_norm if cfg.qk_norm else None, offset)
        v = (_mm(x, self.v_proj._value) * cfg.value_scale).astype(
            x.dtype).reshape(B, S, KV, dv)
        lanes = ((0, 0),) * 3 + ((0, cfg.k_cache_width - dk),)
        prefill = cache is None or _da._concrete_zero(offset)

        paged = cache is not None and len(cache) >= 3
        if paged:
            k_pool, v_pool, table = cache[:3]
            # a window layer's decode table is its ring; its prefill
            # table is logical (see the module docstring)
            k_pool, v_pool = _da.paged_kv_write(
                k_pool, v_pool, jnp.pad(k, lanes), v, table, offset,
                ring=window is not None and not prefill)
            new_cache = (k_pool, v_pool, table) + tuple(cache[3:])
        elif cache is not None:         # static [B, KV, M, d] caches
            off = jnp.broadcast_to(
                jnp.asarray(offset, jnp.int32).reshape(-1), (B,))
            dus = lambda buf, new, o: lax.dynamic_update_slice_in_dim(
                buf, new, o, axis=1)
            k_pool = jax.vmap(dus)(cache[0], jnp.swapaxes(
                jnp.pad(k, lanes), 1, 2).astype(cache[0].dtype), off)
            v_pool = jax.vmap(dus)(cache[1], jnp.swapaxes(
                v, 1, 2).astype(cache[1].dtype), off)
            new_cache = (k_pool, v_pool)
        else:
            new_cache = None

        if prefill:
            with _annotate("blockwise_attention"):
                o = blockwise_causal_attention(
                    q, k, v, scale, window, sinks, cfg.attention_block)
        else:
            off = jnp.broadcast_to(
                jnp.asarray(offset, jnp.int32).reshape(-1), (B,))
            qp = jnp.pad(q, lanes)
            if paged:
                o = _dispatch_kernel(
                    "paged_decode_attention" if window is None
                    else "paged_window_decode_attention",
                    lambda: S == 1 and _da.paged_supported(
                        qp.shape, k_pool.shape, v_pool.shape),
                    lambda: _da.paged_decode_attention(
                        qp, k_pool, v_pool, table, off, scale=scale,
                        sinks=sinks, window=window),
                    lambda: _da.paged_attention_dense(
                        qp, k_pool, v_pool, table, off, scale, sinks,
                        window))
            else:
                M = k_pool.shape[2]
                pos = jnp.broadcast_to(jnp.arange(M, dtype=jnp.int32),
                                       (B, M))
                o = _da.attention_dense_masked(qp, k_pool, v_pool, pos,
                                               off, scale, sinks, window)
        o = o.reshape(B, S, H * dv)
        if cfg.attention_gate:
            g = _mm(x, self.gate_proj._value).astype(jnp.float32)
            o = (o.astype(jnp.float32) * jax.nn.sigmoid(g)).astype(x.dtype)
        return _mm(o, self.o_proj._value), new_cache


class HybridMoEDecoderLayer(Layer):
    def __init__(self, cfg: HybridMoEConfig, index: int):
        super().__init__()
        self.cfg, self.index = cfg, index
        self.attn_kind = cfg.attention_kinds[index]
        ones = ParamAttr(initializer=I.Constant(1.0))
        self.input_layernorm = self.create_parameter((cfg.hidden_size,),
                                                     attr=ones)
        self.self_attn = HybridAttention(cfg, self.attn_kind)
        self.post_attention_layernorm = self.create_parameter(
            (cfg.hidden_size,), attr=ones)      # the feed-forward's INPUT
        if cfg.sandwich_norm:                   # and each branch's output
            self.attention_out_layernorm = self.create_parameter(
                (cfg.hidden_size,), attr=ones)
            self.mlp_out_layernorm = self.create_parameter(
                (cfg.hidden_size,), attr=ones)
        self.is_moe = cfg.ffn_kinds[index] == "experts"
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
        eps, i = self.cfg.rms_norm_eps, self.index
        sandwich = self.cfg.sandwich_norm
        with _annotate(f"layer{i}.attn.{self.attn_kind}"):
            a, cache = self.self_attn(
                _rms(x, self.input_layernorm._value, eps), cache=cache,
                offset=offset)
            if sandwich:
                a = _rms(a, self.attention_out_layernorm._value, eps)
        x = x + a
        with _annotate(f"layer{i}.moe" if self.is_moe else f"layer{i}.mlp"):
            h = _rms(x, self.post_attention_layernorm._value, eps)
            if not self.is_moe:
                y = self.mlp(h)
            elif cache is not None and len(cache) == 4:   # routing counter
                y, counts = self.mlp(h, counts=cache[3])
                y, cache = y._value, cache[:3] + (counts,)
            else:
                y = self.mlp(h)._value
            if sandwich:
                y = _rms(y, self.mlp_out_layernorm._value, eps)
        return x + y, cache


class HybridMoEForCausalLM(Layer):
    """The decoder with an untied output head, over ``vocab_size`` rows
    (one holder's slice of the vocabulary is a smaller vocabulary)."""

    def __init__(self, config: HybridMoEConfig):
        super().__init__()
        self.config = config
        cfg = config
        std = cfg.initializer_range
        self.embed_tokens = self.create_parameter(
            (cfg.vocab_size, cfg.hidden_size), attr=_attr(std))
        self.layers = LayerList([HybridMoEDecoderLayer(cfg, i)
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
        """Per layer, the shapes of the pooled K and V: the layer's own
        KV heads, K at ``k_cache_width``."""
        cfg = self.config
        return [((P, cfg.kv_heads(kind), page, cfg.k_cache_width),
                 (P, cfg.kv_heads(kind), page, cfg.v_head_dim))
                for kind in cfg.attention_kinds]

    def kv_page_classes(self):
        """Per layer ``"full"`` (a row holds a page for every page of
        its context) or ``("window", n)`` (a row holds a ring of pages
        that covers its last ``n`` positions)."""
        cfg = self.config
        return ["full" if kind == "full" else ("window", cfg.sliding_window)
                for kind in cfg.attention_kinds]

    def moe_counter_shape(self):
        """[layers, held experts + 3] routing counters (``GatedMoELayer``);
        rows of dense layers stay 0."""
        return (self.config.num_layers, self.config.num_local_experts + 3)

    def _empty_caches(self, B: int, max_len: int, dtype):
        return [(jnp.zeros((B,) + a[1:2] + (max_len,) + a[3:], dtype),
                 jnp.zeros((B,) + b[1:2] + (max_len,) + b[3:], dtype))
                for a, b in self.kv_pool_shapes(1, 1)]

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
        with _annotate("hybrid_moe"):
            with _annotate("embed"):
                x = self.embed_tokens._value[ids]
                if self.config.embedding_multiplier != 1.0:
                    x = x * self.config.embedding_multiplier
            new_caches = []
            for i, layer in enumerate(self.layers):
                x, nc = layer(x, cache=None if caches is None
                              else caches[i], offset=offset)
                new_caches.append(nc)
            if lengths is None:
                logits = self._head(x)
            else:
                with _annotate("head"):
                    last = jnp.asarray(lengths, jnp.int32) - 1
                    logits = self._head(jnp.take_along_axis(
                        x, last[:, None, None], axis=1)[:, 0])
        return logits if caches is None else (logits, new_caches)


def hybrid_moe_tiny(**kw) -> HybridMoEConfig:
    """CPU-test size: every mechanism present (both attention kinds with
    their own KV heads and bases, a sink on the window kind, a partial
    rotary, keys wider than values, a leading dense layer, held experts
    a strict share of the router's; a context of several windows and
    pages at ``page_size`` 8)."""
    base = dict(vocab_size=256, hidden_size=64,
                attention_kinds=["full", "window", "window", "full"],
                ffn_kinds=["dense", "experts", "experts", "experts"],
                num_heads=8, num_kv_heads=2, window_num_kv_heads=4,
                qk_head_dim=24, v_head_dim=16, rotary_dim=8,
                rope_theta=50000.0, window_rope_theta=100.0,
                sliding_window=12, intermediate_size=128,
                moe_intermediate_size=32, num_experts=16,
                num_local_experts=4, expert_offset=4,
                num_experts_per_tok=4, max_position_embeddings=128,
                attention_block=16)
    base.update(kw)
    return HybridMoEConfig(**base)


def afmoe_tiny(**kw) -> HybridMoEConfig:
    """CPU-test size with every switch on: window layers that turn the
    whole head beside full layers that turn nothing, q/k norms, the
    output gate, sandwich norms, the embedding multiplier, one head size
    and the same KV heads on both kinds, no sink, two leading dense
    layers, a shared expert beside held experts that are a strict share
    of the router's, the head on a prefill's last row; a window of 24
    over pages of 8 is a ring of 4 pages."""
    base = dict(vocab_size=256, hidden_size=64,
                attention_kinds=["window", "window", "window", "full",
                                 "window", "full"],
                ffn_kinds=["dense", "dense"] + ["experts"] * 4,
                num_heads=8, num_kv_heads=2, window_num_kv_heads=2,
                qk_head_dim=16, v_head_dim=16, rotary_dim=16,
                window_rope_theta=100.0, rotary_kinds=("window",),
                sliding_window=24, window_sink=False, value_scale=1.0,
                intermediate_size=128, moe_intermediate_size=32,
                num_experts=16, num_local_experts=4, expert_offset=8,
                num_experts_per_tok=4, routed_scaling_factor=2.826,
                num_shared_experts=1, qk_norm=True, attention_gate=True,
                sandwich_norm=True, embedding_multiplier=8.0,
                head_on_last_row=True, max_position_embeddings=160,
                attention_block=16)
    base.update(kw)
    return HybridMoEConfig(**base)
