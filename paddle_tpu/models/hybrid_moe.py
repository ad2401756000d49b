"""A decoder whose layers differ by KIND, for serving.

Attention is ``"full"`` (every earlier position), ``"window"`` (the
last ``sliding_window`` positions, the current one included) or
``"sparse"`` (below: a full-class layer that attends to the keys a
learned index chooses), each kind with its own number of KV heads and
rotary base (a sparse layer takes the full kind's); keys and queries are
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
``embedding_multiplier`` (the embedding's rows scaled once, at entry),
``router_score_func`` (``"softmax"``: the router scores by a softmax
over all experts and has no selection bias) and ``head_on_last_row``
(below). With the defaults this is the block
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

A SPARSE LAYER (``ops/sparse_attention.py`` has the equations) projects,
beside q, k and v, ``index_heads`` index queries of ``index_head_dim``,
ONE index key a position (a LayerNorm, then rotary over the whole index
head at the layer's base) and a weight an index head; a query attends to
the ``index_topk`` positions of largest index score alone, exactly, and
to every earlier one while there are no more than that. Its index keys
are a THIRD pooled array of the layer (``[P, 1, page,
index_cache_width]``) under the same table and page class as K and V:
its cache tuple is ``(k_pool, v_pool, index_pool, table[, counts])``.
``collect_selection()`` hands a check the kept sets of a forward.

THE HEAD ON THE LAST ROW. A prefill needs one row of logits a prompt.
With ``head_on_last_row`` the model says so (``model.head_on_last_row``)
and ``Predictor._prefill_fn`` hands ``forward`` the rows' ``lengths``:
the final norm and the head then run on ``x[b, lengths[b] - 1]`` alone
and the program returns ``[B, vocab]``; no ``[B, S, vocab]`` array
exists (6.6 GB in float32 for 8,192 positions of a 200,192-row
vocabulary). Off, the program computes every position's logits and the
caller gathers one row, as it always did.

Attention forms, chosen at trace time:

- prefill (``offset`` a concrete 0, or no cache): causal self-attention
  over the new positions, the band alone on a window layer and no ``[H,
  S, S]`` array either way: ``flash_attention_gqa`` (``window=`` on a
  window layer) where the kernels are on, the layer has no sink, keys
  and values are one width and ``flash_gqa_supported`` admits the
  shapes; else ``ops/blockwise_attention.py`` in plain ``lax``. The form
  is recorded (``ServingEngine.prefill_attention_forms``). K and V of
  the prompt are written by the table: a window layer's PREFILL table is
  logical, one column a page, and the cache has sent every page but the
  prompt's last ``ring`` to the trash page;
- decode (one new position a row, paged): the row is written at
  ``(pos // page) % ring`` of a window layer's ring, then
  ``paged_decode_attention`` (``window=`` on a window layer) on TPU, its
  dense twin elsewhere; a sparse layer first scores the row's index
  pages and selects, then the same kernel walks every page of the row
  with the kept positions as a mask (``keep=``);
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
from ..observability import moestats as _moestats
from ..ops.blockwise_attention import blockwise_causal_attention
from ..ops.pallas import decode_attention as _da
from ..ops.pallas import flash_attention as _fa
from ..ops.sparse_attention import (collect_selection, count_kept,
                                    select_rows, selection_sink,
                                    sparse_causal_attention)
from ..tensor import Tensor
from . import llama as _llama
from .llama import _apply_rope, _dispatch_kernel
from .mla_moe import DenseSwiGLU, _attr, _mm, _rms

__all__ = ["HybridMoEConfig", "HybridMoEForCausalLM", "hybrid_moe_tiny",
           "afmoe_tiny", "sparse_moe_tiny", "collect_selection"]

# the attention kinds a layer may name
_KINDS = ("full", "window", "sparse")

@dataclass
class HybridMoEConfig:
    vocab_size: int = 32000
    hidden_size: int = 4096
    # one entry a layer: "full" | "window" | "sparse", and
    # "dense" | "experts"
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
    router_score_func: str = "sigmoid"       # | "softmax" (no bias)
    # -- a "sparse" layer's index (module docstring); unused otherwise ----
    index_heads: int = 0
    index_head_dim: int = 0
    index_topk: int = 0
    dtype: str = "float32"

    def __post_init__(self):
        if self.num_local_experts is None:
            self.num_local_experts = self.num_experts
        enforce(len(self.attention_kinds) == len(self.ffn_kinds)
                and set(self.attention_kinds) <= set(_KINDS)
                and set(self.ffn_kinds) <= {"dense", "experts"},
                "attention_kinds (full | window | sparse) and ffn_kinds "
                "(dense | experts) name every layer once")
        enforce(self.rotary_dim % 2 == 0
                and self.rotary_dim <= self.qk_head_dim,
                "rotary_dim is an even number of a head's leading dims")
        self.rotary_kinds = tuple(self.rotary_kinds)
        enforce(set(self.rotary_kinds) <= set(_KINDS),
                "rotary_kinds names attention kinds (full | window | "
                "sparse)")
        if "sparse" in self.attention_kinds:
            enforce(self.index_heads >= 1 and self.index_topk >= 1
                    and self.index_head_dim >= 2
                    and self.index_head_dim % 2 == 0,
                    "a sparse layer needs index_heads, an even "
                    "index_head_dim and index_topk")

    @property
    def num_layers(self) -> int:
        return len(self.attention_kinds)

    def kv_heads(self, kind: str) -> int:
        return self.window_num_kv_heads if kind == "window" \
            else self.num_kv_heads

    def theta(self, kind: str) -> float:
        return self.window_rope_theta if kind == "window" \
            else self.rope_theta

    def sink(self, kind: str) -> bool:
        return self.window_sink if kind == "window" else self.full_sink

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
    def index_cache_width(self) -> int:
        """Columns of a sparse layer's pooled index key: whole lanes,
        as ``k_cache_width``."""
        return -(-self.index_head_dim // 128) * 128

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
    def __init__(self, cfg: HybridMoEConfig, kind: str,
                 scope: str = "attn"):
        super().__init__()
        self.cfg, self.kind, self.scope = cfg, kind, scope
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
        self.sparse = kind == "sparse"
        self.n_pools = 3 if self.sparse else 2   # arrays it pools
        if self.sparse:
            Hi, di = cfg.index_heads, cfg.index_head_dim
            self.index_q_proj = self.create_parameter((h, Hi * di),
                                                      attr=_attr(std))
            self.index_k_proj = self.create_parameter((h, di),
                                                      attr=_attr(std))
            self.index_w_proj = self.create_parameter((h, Hi),
                                                      attr=_attr(std))
            self.index_k_norm = self.create_parameter(
                (di,), attr=ParamAttr(initializer=I.Constant(1.0)))
            self.index_k_norm_bias = self.create_parameter(
                (di,), attr=ParamAttr(initializer=I.Constant(0.0)))
            self._index_rope = _rope_tables(di, cfg.theta(kind),
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

    def _index(self, x, offset):
        """A sparse layer's index of the new positions: queries
        [B, S, Hi, di] and the ONE key [B, S, 1, di], both turned over
        the whole index head, and the heads' weights [B, S, Hi] float32
        (scaled by ``Hi ** -0.5 * di ** -0.5``)."""
        cfg = self.cfg
        B, S = x.shape[0], x.shape[1]
        Hi, di = cfg.index_heads, cfg.index_head_dim
        cos, sin = self._index_rope
        iq = _mm(x, self.index_q_proj._value).reshape(B, S, Hi, di)
        ik = _mm(x, self.index_k_proj._value).astype(jnp.float32)
        ik = ik - ik.mean(-1, keepdims=True)            # LayerNorm
        ik = ik * lax.rsqrt((ik * ik).mean(-1, keepdims=True)
                            + cfg.rms_norm_eps)
        ik = (ik * self.index_k_norm._value.astype(jnp.float32)
              + self.index_k_norm_bias._value.astype(jnp.float32)
              ).astype(x.dtype).reshape(B, S, 1, di)
        iw = _mm(x, self.index_w_proj._value).astype(jnp.float32) \
            * (Hi ** -0.5 * di ** -0.5)
        return (_apply_rope(iq, cos, sin, offset),
                _apply_rope(ik, cos, sin, offset), iw)

    def forward(self, x, cache=None, offset=0):
        """x: values [B, S, hidden]. Returns (values [B, S, hidden],
        the cache tuple with its pooled arrays updated)."""
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
        n = self.n_pools
        more = ()
        if self.sparse:
            with _annotate(f"{self.scope}.index"):
                iq, ik, iw = self._index(x, offset)
            if cache is not None:       # the index key joins K and V
                more = ((cache[2], jnp.pad(ik, ((0, 0),) * 3 + ((
                    0, cfg.index_cache_width - ik.shape[-1]),))),)

        paged = cache is not None and len(cache) > n
        if paged:
            table = cache[n]
            # a window layer's decode table is its ring; its prefill
            # table is logical (see the module docstring)
            pools = _da.paged_kv_write(
                cache[0], cache[1], jnp.pad(k, lanes), v, table, offset,
                ring=window is not None and not prefill, more=more)
            new_cache = pools + tuple(cache[n:])
        elif cache is not None:         # static [B, KV, M, d] caches
            off = jnp.broadcast_to(
                jnp.asarray(offset, jnp.int32).reshape(-1), (B,))
            dus = lambda buf, new, o: lax.dynamic_update_slice_in_dim(
                buf, new, o, axis=1)
            pools = tuple(
                jax.vmap(dus)(buf, jnp.swapaxes(new, 1, 2).astype(
                    buf.dtype), off)
                for buf, new in ((cache[0], jnp.pad(k, lanes)),
                                 (cache[1], v)) + more)
            new_cache = pools
        else:
            new_cache = pools = None
        if pools is not None:
            k_pool, v_pool = pools[:2]

        if prefill and self.sparse:
            kept = selection_sink()
            o = sparse_causal_attention(
                q, k, v, iq, ik[:, :, 0], iw, scale, cfg.index_topk,
                cfg.attention_block,
                scopes=tuple(f"{self.scope}.{part}" for part in
                             ("index", "select", "attend")),
                want_mask=kept is not None)
            if kept is not None:
                o, mask = o
                kept.append(mask)
        elif prefill:
            # one head size, no sink: the flash kernel's shapes (padding
            # rows lie after every real row, so they need no mask)
            flash = (_llama._kernels_on() and sinks is None and dv == dk
                     and _fa.flash_gqa_supported(q.shape, k.shape))
            if cache is not None:       # a serving program's prefill
                _moestats.record(
                    {"attention": "flash" if flash else "blockwise"})
            if flash:
                with _annotate("flash_attention"):
                    o = _fa.flash_attention_gqa(q, k, v, scale=scale,
                                                window=window)
            else:
                with _annotate("blockwise_attention"):
                    o = blockwise_causal_attention(
                        q, k, v, scale, window, sinks, cfg.attention_block)
        elif self.sparse:
            off = jnp.broadcast_to(
                jnp.asarray(offset, jnp.int32).reshape(-1), (B,))
            qp = jnp.pad(q, lanes)
            di = cfg.index_head_dim
            if paged:
                with _annotate(f"{self.scope}.index"):
                    keys = _da.gather_pages(pools[2], table)[:, 0, :, :di]
                keep = select_rows(iq, iw, keys, off, cfg.index_topk,
                                   self.scope)
                if len(cache) == n + 2:     # the step's device counter
                    with _annotate(f"{self.scope}.select"):
                        new_cache = new_cache[:-1] + (count_kept(
                            keep, off, cfg.index_topk, new_cache[-1]),)
                with _annotate(f"{self.scope}.attend"):
                    o = _dispatch_kernel(
                        "paged_sparse_decode_attention",
                        lambda: S == 1 and _da.paged_supported(
                            qp.shape, k_pool.shape, v_pool.shape),
                        lambda: _da.paged_decode_attention(
                            qp, k_pool, v_pool, table, off, scale=scale,
                            sinks=sinks, keep=keep[:, 0]),
                        lambda: _da.paged_attention_dense(
                            qp, k_pool, v_pool, table, off, scale, sinks,
                            None, keep))
            else:
                keep = select_rows(iq, iw, pools[2][:, 0, :, :di], off,
                                   cfg.index_topk, self.scope)
                M = k_pool.shape[2]
                pos = jnp.broadcast_to(jnp.arange(M, dtype=jnp.int32),
                                       (B, M))
                with _annotate(f"{self.scope}.attend"):
                    o = _da.attention_dense_masked(
                        qp, k_pool, v_pool, pos, off, scale, sinks, None,
                        keep)
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
        self.self_attn = HybridAttention(
            cfg, self.attn_kind, f"layer{index}.attn.{self.attn_kind}")
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
                down_attr=_attr(std / math.sqrt(2 * cfg.num_layers)),
                score_func=cfg.router_score_func)
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
            elif cache is not None and \
                    len(cache) == self.self_attn.n_pools + 2:
                # the routing counter follows the pools and their table;
                # a selecting model's two slots follow it
                counts, m = cache[-1], self.cfg.num_local_experts + 3
                more = counts.shape[0] > m
                y, moe = self.mlp(h, counts=counts[:m] if more else counts)
                if more:
                    moe = jnp.concatenate([moe, counts[m:]])
                y, cache = y._value, cache[:-1] + (moe,)
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
        KV heads, K at ``k_cache_width``; a sparse layer pools a third
        array, its one index key a position."""
        cfg = self.config
        return [((P, cfg.kv_heads(kind), page, cfg.k_cache_width),
                 (P, cfg.kv_heads(kind), page, cfg.v_head_dim))
                + (((P, 1, page, cfg.index_cache_width),)
                   if kind == "sparse" else ())
                for kind in cfg.attention_kinds]

    def kv_page_classes(self):
        """Per layer ``"full"`` (a row holds a page for every page of
        its context: a full or a sparse layer) or ``("window", n)`` (a
        row holds a ring of pages that covers its last ``n``
        positions)."""
        cfg = self.config
        return [("window", cfg.sliding_window) if kind == "window"
                else "full" for kind in cfg.attention_kinds]

    @property
    def key_selection(self) -> Optional[int]:
        """How many keys a query of a sparse layer keeps; None where no
        layer selects. The serving engine asks: it states its refusals
        for such a model and reports the share of keys kept."""
        return self.config.index_topk \
            if "sparse" in self.config.attention_kinds else None

    def moe_counter_shape(self):
        """[layers, held experts + 3] routing counters (``GatedMoELayer``);
        rows of dense layers stay 0. A model that selects keys has two
        slots more a layer: the decode step's rows whose kept count was
        not ``min(t + 1, index_topk)``, and its rows."""
        return (self.config.num_layers, self.config.num_local_experts + 3
                + (2 if self.key_selection else 0))

    def _empty_caches(self, B: int, max_len: int, dtype):
        return [tuple(jnp.zeros((B,) + a[1:2] + (max_len,) + a[3:], dtype)
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


def sparse_moe_tiny(**kw) -> HybridMoEConfig:
    """CPU-test size of a decoder whose every layer is SPARSE: 2 index
    heads of 8 that keep 8 keys over pages of 8 (contexts several times
    that), q/k norms, rotary over the whole head, a softmax router with
    no bias and no shared expert, held experts a strict share of the
    router's, the head on a prefill's last row."""
    base = dict(vocab_size=256, hidden_size=64,
                attention_kinds=["sparse"] * 3, ffn_kinds=["experts"] * 3,
                num_heads=8, num_kv_heads=2, window_num_kv_heads=2,
                qk_head_dim=16, v_head_dim=16, rotary_dim=16,
                rope_theta=10000.0, rotary_kinds=("sparse",),
                full_sink=False, window_sink=False, value_scale=1.0,
                moe_intermediate_size=32, num_experts=16,
                num_local_experts=4, expert_offset=4,
                num_experts_per_tok=4, router_score_func="softmax",
                qk_norm=True, head_on_last_row=True, index_heads=2,
                index_head_dim=8, index_topk=8,
                max_position_embeddings=128, attention_block=16)
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
