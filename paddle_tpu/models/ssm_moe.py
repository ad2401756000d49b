"""A decoder whose layer is ONE MIXER, of three kinds, for serving.

``x <- x + Mixer_l(RMSNorm(x))`` a layer, and ``mixer_kinds`` names each
layer's mixer: ``"ssm"`` (a Mamba-2 state-space mixer), ``"attention"``
(grouped-query attention that turns nothing: ``HybridAttention``'s full
kind with no rotary) or ``"experts"`` (``GatedMoELayer``: a sigmoid
top-k router over ``num_experts`` on the hidden state, LATENT experts of
``moe_latent_size -> moe_intermediate_size -> moe_latent_size`` between
two projections, ``expert_activation`` ``"relu2"`` or ``"swiglu"``, one
shared expert of its own width). The untied head follows a last
RMSNorm. All sizes are data of ``SSMMoEConfig``; this is the block of
the Nemotron-H line (``model_type`` ``nemotron_h``). Nothing asks a
model's name.

THE STATE-SPACE MIXER (``ops/ssm.py`` has the recurrence's forms), with
``inner = ssm_num_heads * ssm_head_dim`` and ``conv = inner + 2 *
ssm_groups * ssm_state_size``::

    [z | c | dt] = u W_in                    # inner | conv | heads
    c  = silu(b_c + causal depthwise conv of c, conv_kernel wide)
    [xs | B | C] = c                         # head h reads group h // (heads / groups)
    D  = softplus(dt + dt_bias);  H <- exp(D A) H + D (xs (x) B),  A = -exp(A_log)
    y  = H C + Dskip * xs
    out = group_norm(y * silu(z)) W_out      # ssm_groups groups, then a weight

It keeps, a row, the state ``H [heads, head_dim, state]`` in
``ssm_state_dtype`` and the convolution's TAIL, its last ``conv_kernel -
1`` inputs side by side ``[(conv_kernel - 1) * conv]``: FIXED size
whatever the context.
Two forms, chosen at trace time:

- prefill (``offset`` a concrete 0, or no cache): the chunked form at
  ``chunk_size``. A bucketed prompt is RIGHT-PADDED and a recurrence
  walks through its pad, so the mixer takes the rows' ``lengths``: a pad
  position's step is 0 (it changes no state) and the tail is the last
  real inputs, so what decode is handed is the state after the prompt's
  LAST REAL position. The row's slot is written WHOLE, from ``H = 0``:
  nothing of the slot's last request outlives an admission.
- decode (one new position a row): one step on the slot in place. The
  state arrays are ``[rows, ...]`` and a row's slot is its table row, so
  the step is elementwise over the donated arrays: no gather, no copy.

It honours the serving contract: ``forward(input_ids, caches, offset,
lengths)``, a cache tuple a layer of ``(arrays.., table[, counter])``:
an attention layer ``(k_pool, v_pool, table)``, a state-space layer
``(H, tail, slots)`` (``slots``: the table rows a prefill writes), an
expert layer ``(table[, counter])``: it keeps nothing and only its
routing counter rides. ``kv_page_classes`` names the classes (``"full"``
| ``"state"`` | ``"none"``), ``state_shapes`` the fixed-size arrays,
``moe_counter_layers`` the layers that carry a counter. The forward
takes no ``valid``, and the engine refuses what needs a state at a
position the slot no longer holds (chunked prefill, the prefix cache,
host spill, speculation, the disaggregated phases). ``head_on_last_row``
is always on: the prefill program is handed the lengths anyway.

Inference only: parameters are plain arrays, nothing records a tape.
"""
from __future__ import annotations

import math
from dataclasses import dataclass, field
from typing import List, Optional

import jax
import jax.numpy as jnp
from jax import lax

from ..core.enforce import enforce
from ..framework.param_attr import ParamAttr
from ..incubate.distributed.models.moe import GatedMoELayer
from ..nn import initializer as I
from ..nn.container import LayerList
from ..nn.layer import Layer
from ..observability import annotate as _annotate
from ..ops import ssm as _ssm
from ..ops.pallas import decode_attention as _da
from ..tensor import Tensor
from .hybrid_moe import HybridAttention, HybridMoEConfig
from .mla_moe import _attr, _mm, _rms

__all__ = ["SSMMoEConfig", "SSMMoEForCausalLM", "ssm_moe_tiny"]

_MIXERS = ("ssm", "attention", "experts")


@dataclass
class SSMMoEConfig:
    vocab_size: int = 32000
    hidden_size: int = 4096
    # one entry a layer: "ssm" | "attention" | "experts"
    mixer_kinds: List[str] = field(default_factory=lambda: [
        "ssm", "experts", "ssm", "attention", "experts"])
    # -- an attention layer ----------------------------------------------
    num_heads: int = 32
    num_kv_heads: int = 2
    head_dim: int = 128
    # -- a state-space layer ---------------------------------------------
    ssm_num_heads: int = 128
    ssm_head_dim: int = 64
    ssm_groups: int = 8
    ssm_state_size: int = 128
    conv_kernel: int = 4
    conv_bias: bool = True
    chunk_size: int = 128
    ssm_state_dtype: str = "float32"
    # -- an expert layer -------------------------------------------------
    num_experts: int = 512                   # the router's width
    num_local_experts: Optional[int] = None  # held here; None = all
    expert_offset: int = 0
    num_experts_per_tok: int = 22
    routed_scaling_factor: float = 1.0
    norm_topk_prob: bool = True
    moe_intermediate_size: int = 2688        # one expert
    moe_latent_size: Optional[int] = 1024    # None: experts read hidden
    expert_activation: str = "relu2"         # | "swiglu"
    shared_expert_intermediate_size: int = 5376
    max_position_embeddings: int = 4096
    rms_norm_eps: float = 1e-5
    initializer_range: float = 0.02
    attention_block: int = 512               # prefill's rows a block
    dtype: str = "float32"

    def __post_init__(self):
        if self.num_local_experts is None:
            self.num_local_experts = self.num_experts
        enforce(self.mixer_kinds and set(self.mixer_kinds) <= set(_MIXERS),
                "mixer_kinds (ssm | attention | experts) names every "
                "layer once")
        enforce(self.ssm_num_heads % self.ssm_groups == 0
                and self.conv_kernel >= 2,
                "a state-space layer's heads divide into its groups, and "
                "its convolution is two or more positions wide")

    @property
    def num_layers(self) -> int:
        return len(self.mixer_kinds)

    @property
    def ssm_inner(self) -> int:
        return self.ssm_num_heads * self.ssm_head_dim

    @property
    def conv_dim(self) -> int:
        return self.ssm_inner + 2 * self.ssm_groups * self.ssm_state_size

    def attention_config(self) -> HybridMoEConfig:
        """What ``HybridAttention`` reads: every layer its full kind,
        nothing turned, one head size, no sink, no gate."""
        return HybridMoEConfig(
            vocab_size=self.vocab_size, hidden_size=self.hidden_size,
            attention_kinds=["full"] * self.num_layers,
            ffn_kinds=["dense"] * self.num_layers,
            num_heads=self.num_heads, num_kv_heads=self.num_kv_heads,
            window_num_kv_heads=self.num_kv_heads,
            qk_head_dim=self.head_dim, v_head_dim=self.head_dim,
            rotary_dim=0, rotary_kinds=(), full_sink=False,
            window_sink=False, value_scale=1.0, num_experts=1,
            max_position_embeddings=self.max_position_embeddings,
            rms_norm_eps=self.rms_norm_eps,
            initializer_range=self.initializer_range,
            attention_block=self.attention_block, dtype=self.dtype)


class SSMMixer(Layer):
    """The state-space mixer (module docstring). ``forward(u, cache,
    offset, lengths)`` -> (values, the cache tuple with its two state
    arrays updated)."""

    def __init__(self, cfg: SSMMoEConfig, scope: str):
        super().__init__()
        self.cfg, self.scope = cfg, scope
        h, nh, std = cfg.hidden_size, cfg.ssm_num_heads, \
            cfg.initializer_range
        inner, conv = cfg.ssm_inner, cfg.conv_dim
        const = lambda v: ParamAttr(initializer=I.Constant(v))
        self.in_proj = self.create_parameter((h, inner + conv + nh),
                                             attr=_attr(std))
        self.conv_weight = self.create_parameter(
            (conv, cfg.conv_kernel), attr=_attr(std))
        if cfg.conv_bias:
            self.conv_bias = self.create_parameter((conv,),
                                                   attr=const(0.0))
        # stored in the model's type like every weight, read as float32
        self.A_log = self.create_parameter((nh,), attr=const(0.0))
        self.dt_bias = self.create_parameter((nh,), attr=const(0.0))
        self.D = self.create_parameter((nh,), attr=const(1.0))
        self.norm = self.create_parameter((inner,), attr=const(1.0))
        self.out_proj = self.create_parameter(
            (inner, h), attr=_attr(std / math.sqrt(2 * cfg.num_layers)))

    def forward(self, u, cache=None, offset=0, lengths=None):
        cfg = self.cfg
        B, S = u.shape[0], u.shape[1]
        nh, P, G, N = (cfg.ssm_num_heads, cfg.ssm_head_dim,
                       cfg.ssm_groups, cfg.ssm_state_size)
        inner, conv, K = cfg.ssm_inner, cfg.conv_dim, cfg.conv_kernel
        proj = _mm(u, self.in_proj._value)
        z, c, dt = (proj[..., :inner], proj[..., inner:inner + conv],
                    proj[..., inner + conv:])
        prefill = cache is None or _da._concrete_zero(offset)
        enforce(prefill or S == 1,
                "a state-space layer is fed a whole prompt (offset 0) or "
                "one position a row: a chunk at an offset would need the "
                "state at that position, which the slot no longer holds")
        w = self.conv_weight._value
        b = self.conv_bias._value if cfg.conv_bias else None
        A = -jnp.exp(self.A_log._value.astype(jnp.float32))
        with _annotate(f"{self.scope}.scan"):
            step = jax.nn.softplus(
                dt.astype(jnp.float32)
                + self.dt_bias._value.astype(jnp.float32))
            if prefill:
                if lengths is not None:     # a pad position's step is 0
                    real = jnp.arange(S, dtype=jnp.int32)[None] \
                        < jnp.asarray(lengths, jnp.int32)[:, None]
                    step = jnp.where(real[:, :, None], step, 0.0)
                tail = _ssm.conv_tail(c, lengths, K).reshape(B, -1)
                c = _ssm.causal_conv(c, w, b)
                xs = c[..., :inner].reshape(B, S, nh, P)
                Bm = c[..., inner:inner + G * N].reshape(B, S, G, N)
                Cm = c[..., inner + G * N:].reshape(B, S, G, N)
                y, H = _ssm.ssd_chunked(xs, step, A, Bm, Cm,
                                        cfg.chunk_size)
            else:
                # row b of the arrays IS batch row b
                c, tail = _ssm.conv_step(cache[1], c[:, 0], w, b)
                xs = c[:, :inner].reshape(B, nh, P)
                y, H = _ssm.ssd_step(
                    cache[0].astype(jnp.float32), xs, step[:, 0], A,
                    c[:, inner:inner + G * N].reshape(B, G, N),
                    c[:, inner + G * N:].reshape(B, G, N))
                y, xs = y[:, None], xs[:, None]
            y = y + self.D._value.astype(jnp.float32)[:, None] \
                * xs.astype(jnp.float32)
            n = _ssm.gated_group_norm(y.reshape(B, S, inner), z,
                                      self.norm._value, G,
                                      cfg.rms_norm_eps)
            if cache is not None:
                cache = self._keep(cache, H, tail, prefill)
        return _mm(n, self.out_proj._value), cache

    @staticmethod
    def _keep(cache, H, tail, prefill: bool):
        """The cache tuple with the rows' new state. Decode: row b of the
        arrays IS row b of the batch, replaced whole. Prefill: the rows
        ``cache[2]`` names (``slots``; without it, a static cache, the
        arrays' own rows in order) are written whole."""
        Hs, ts = cache[0], cache[1]
        H, tail = H.astype(Hs.dtype), tail.astype(ts.dtype)
        if not prefill or len(cache) == 2:
            return (H, tail) + tuple(cache[2:])
        slots = jnp.asarray(cache[2], jnp.int32).reshape(-1)
        if H.shape[0] == 1:     # the engine's prefill: one row, no scatter
            return (lax.dynamic_update_slice_in_dim(Hs, H, slots[0], 0),
                    lax.dynamic_update_slice_in_dim(ts, tail, slots[0], 0)
                    ) + tuple(cache[2:])
        return (Hs.at[slots].set(H), ts.at[slots].set(tail)) \
            + tuple(cache[2:])


class SSMMoEDecoderLayer(Layer):
    def __init__(self, cfg: SSMMoEConfig, index: int,
                 acfg: HybridMoEConfig):
        super().__init__()
        self.cfg, self.index = cfg, index
        self.kind = kind = cfg.mixer_kinds[index]
        self.norm = self.create_parameter(
            (cfg.hidden_size,), attr=ParamAttr(initializer=I.Constant(1.0)))
        std = cfg.initializer_range
        if kind == "ssm":
            self.mixer = SSMMixer(cfg, f"layer{index}.ssm")
        elif kind == "attention":
            self.mixer = HybridAttention(acfg, "full",
                                         f"layer{index}.attn.full")
        else:
            self.mixer = GatedMoELayer(
                cfg.hidden_size, cfg.moe_intermediate_size,
                cfg.num_experts, cfg.num_local_experts, cfg.expert_offset,
                top_k=cfg.num_experts_per_tok,
                routed_scaling_factor=cfg.routed_scaling_factor,
                norm_topk_prob=cfg.norm_topk_prob,
                weight_attr=_attr(std),
                down_attr=_attr(std / math.sqrt(2 * cfg.num_layers)),
                latent_size=cfg.moe_latent_size,
                activation=cfg.expert_activation,
                shared_hidden=cfg.shared_expert_intermediate_size,
                latent_scope=f"layer{index}.moe.latent")

    def forward(self, x, cache=None, offset=0, lengths=None):
        i, kind = self.index, self.kind
        scope = {"ssm": "ssm", "attention": "attn.full",
                 "experts": "moe"}[kind]
        with _annotate(f"layer{i}.{scope}"):    # the norm with its mixer
            u = _rms(x, self.norm._value, self.cfg.rms_norm_eps)
            if kind == "ssm":
                y, cache = self.mixer(u, cache=cache, offset=offset,
                                      lengths=lengths)
            elif kind == "attention":
                y, cache = self.mixer(u, cache=cache, offset=offset)
            elif cache is not None and len(cache) == 2:
                # (table, counter): the layer keeps nothing else
                y, counts = self.mixer(u, counts=cache[1])
                y, cache = y._value, (cache[0], counts)
            else:
                y = self.mixer(u)._value
        return x + y, cache


class SSMMoEForCausalLM(Layer):
    """The decoder with an untied output head, over ``vocab_size`` rows
    (one holder's slice of the vocabulary is a smaller vocabulary)."""

    head_on_last_row = True     # a prefill is handed the rows' lengths

    def __init__(self, config: SSMMoEConfig):
        super().__init__()
        self.config = cfg = config
        std = cfg.initializer_range
        self._acfg = acfg = cfg.attention_config()
        self.embed_tokens = self.create_parameter(
            (cfg.vocab_size, cfg.hidden_size), attr=_attr(std))
        self.layers = LayerList([SSMMoEDecoderLayer(cfg, i, acfg)
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
        """Per layer, the PAGED arrays: K (whole 128-wide lanes:
        ``HybridMoEConfig.k_cache_width``) and V of an attention layer,
        nothing of the others."""
        cfg = self.config
        kv = ((P, cfg.num_kv_heads, page, self._acfg.k_cache_width),
              (P, cfg.num_kv_heads, page, cfg.head_dim))
        return [kv if kind == "attention" else ()
                for kind in cfg.mixer_kinds]

    def kv_page_classes(self):
        """Per layer ``"full"`` (attention: a page for every page of a
        row's context), ``"state"`` (state-space: arrays of fixed size a
        row, ``state_shapes``) or ``"none"`` (experts keep nothing)."""
        return [{"attention": "full", "ssm": "state", "experts": "none"}
                [kind] for kind in self.config.mixer_kinds]

    def state_shapes(self):
        """Per layer, ``((shape a row, dtype), ..)`` of a state-space
        layer's two arrays, H and the convolution's tail; ``()`` of the
        others. The tail is in the model's type (None: the cache's)."""
        cfg = self.config
        ssm = (((cfg.ssm_num_heads, cfg.ssm_head_dim, cfg.ssm_state_size),
                cfg.ssm_state_dtype),
               (((cfg.conv_kernel - 1) * cfg.conv_dim,), None))
        return [ssm if kind == "ssm" else () for kind in cfg.mixer_kinds]

    def moe_counter_layers(self) -> List[int]:
        """The layers that carry a routing counter: the expert layers."""
        return [i for i, kind in enumerate(self.config.mixer_kinds)
                if kind == "experts"]

    def moe_counter_shape(self):
        """[expert layers, held experts + 3] (``GatedMoELayer``), or
        None for a model without expert layers."""
        return (len(self.moe_counter_layers()),
                self.config.num_local_experts + 3)

    def _empty_caches(self, B: int, max_len: int, dtype):
        """Static caches of ``Predictor.generate``: K and V ``[B, KV,
        max_len, d]`` of an attention layer, ``(H, tail)`` of a
        state-space layer (row b is batch row b), ``()`` of an expert
        layer."""
        out = []
        for pools, state in zip(self.kv_pool_shapes(1, 1),
                                self.state_shapes()):
            out.append(tuple(
                jnp.zeros((B,) + a[1:2] + (max_len,) + a[3:], dtype)
                for a in pools) + tuple(
                jnp.zeros((B,) + tuple(s), d or dtype) for s, d in state))
        return out

    def _head(self, x):
        x = _rms(x, self.norm._value, self.config.rms_norm_eps)
        return Tensor(jnp.dot(x, self.lm_head._value,
                              preferred_element_type=jnp.float32
                              ).astype(x.dtype), stop_gradient=True)

    def forward(self, input_ids, caches=None, offset=0, lengths=None):
        """Logits ``[B, S, vocab]``; with ``lengths`` ``[B]`` (a prefill)
        ``[B, vocab]``, of row b's position ``lengths[b] - 1`` alone, and
        the state-space layers stop at that position."""
        ids = input_ids._value if isinstance(input_ids, Tensor) \
            else jnp.asarray(input_ids)
        with _annotate("ssm_moe"):
            with _annotate("embed"):
                x = self.embed_tokens._value[ids]
            new_caches = []
            for i, layer in enumerate(self.layers):
                x, nc = layer(x, cache=None if caches is None
                              else caches[i], offset=offset,
                              lengths=lengths)
                new_caches.append(nc)
            if lengths is None:
                logits = self._head(x)
            else:
                with _annotate("head"):
                    last = jnp.asarray(lengths, jnp.int32) - 1
                    logits = self._head(jnp.take_along_axis(
                        x, last[:, None, None], axis=1)[:, 0])
        return logits if caches is None else (logits, new_caches)


def ssm_moe_tiny(**kw) -> SSMMoEConfig:
    """CPU-test size with every mechanism present: the three mixers in
    one period's order, a chunk shorter than a prompt, heads that share
    groups, a convolution bias, latent relu² experts held as a strict
    share of the router's beside a wider shared expert, two KV heads."""
    base = dict(vocab_size=256, hidden_size=64,
                mixer_kinds=["ssm", "experts", "ssm", "attention",
                             "experts", "ssm"],
                num_heads=8, num_kv_heads=2, head_dim=16,
                ssm_num_heads=8, ssm_head_dim=8, ssm_groups=2,
                ssm_state_size=16, conv_kernel=4, chunk_size=8,
                num_experts=16, num_local_experts=4, expert_offset=4,
                num_experts_per_tok=4, routed_scaling_factor=2.5,
                moe_intermediate_size=48, moe_latent_size=32,
                shared_expert_intermediate_size=96,
                max_position_embeddings=128, attention_block=16)
    base.update(kw)
    return SSMMoEConfig(**base)
