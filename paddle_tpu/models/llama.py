"""Llama model family + compiled KV-cache generation.

TPU-native redesign of the reference's Llama/fused-decode stack
(reference: the inference fast path fluid/operators/fused/
fused_multi_transformer_op.cu.h — a 2,023-LoC CUDA decoder loop with
cache-KV attention — plus masked_multihead_attention_kernel.cu per
decode step; python surface incubate/nn/layer/fused_transformer.py:1025
FusedMultiTransformer).

Architecture: RMSNorm (Pallas on TPU), rotary embeddings, GQA
(num_kv_heads < num_heads), SwiGLU MLP — all projections are
Column/RowParallelLinear so the model tensor-parallelizes over 'mp'
exactly like GPT.

Generation redesign: instead of a hand-written CUDA decoder, the decode
step is ONE jitted XLA program with *static-shape* preallocated KV
caches (head-major [B, KV, max_len, D]) updated in place via donated buffers —
the XLA-idiomatic equivalent of the paged cache-KV loop. Prefill and
decode share a single forward path (offset + sequence masking), so the
program compiles twice (prefill shape, decode shape) and never again.
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from typing import List, Optional, Tuple

import jax
import numpy as np
import jax.numpy as jnp
from jax import lax

from .. import ops
from ..autograd import no_grad
from ..core.dispatch import def_op
from ..nn import functional as F
from ..nn.layer import Layer
from ..nn.container import LayerList
from ..nn.norm import RMSNorm
from ..framework.param_attr import ParamAttr
from ..nn import initializer as I
from ..ops.attention import flash_attention
from ..distributed.fleet.layers.mpu import (ColumnParallelLinear,
                                            RowParallelLinear,
                                            VocabParallelEmbedding,
                                            parallel_cross_entropy)
from ..observability import annotate as _annotate
from ..tensor import Tensor

__all__ = ["LlamaConfig", "LlamaModel", "LlamaForCausalLM",
           "LlamaPretrainingCriterion", "LlamaRMSNorm", "llama_tiny",
           "llama_7b", "llama_13b"]


@dataclass
class LlamaConfig:
    vocab_size: int = 32000
    hidden_size: int = 4096
    num_layers: int = 32
    num_heads: int = 32
    num_kv_heads: int = 0               # 0 -> num_heads (MHA)
    intermediate_size: int = 11008
    max_position_embeddings: int = 4096
    rope_theta: float = 10000.0
    rms_norm_eps: float = 1e-5
    initializer_range: float = 0.02
    tie_word_embeddings: bool = False
    use_flash_attention: bool = True
    dtype: str = "float32"

    def __post_init__(self):
        if not self.num_kv_heads:
            self.num_kv_heads = self.num_heads

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_heads

    def num_params(self) -> int:
        h, L, V = self.hidden_size, self.num_layers, self.vocab_size
        kv = self.num_kv_heads * self.head_dim
        per_layer = (h * h + 2 * h * kv + h * h
                     + 3 * h * self.intermediate_size + 2 * h)
        head = 0 if self.tie_word_embeddings else V * h
        return V * h + L * per_layer + h + head


def _init_attr(std):
    return ParamAttr(initializer=I.Normal(mean=0.0, std=std))


def _rope_tables(cfg: LlamaConfig, dtype=jnp.float32):
    D = cfg.head_dim
    inv = 1.0 / cfg.rope_theta ** (np.arange(0, D, 2, dtype=np.float64) / D)
    t = np.arange(cfg.max_position_embeddings, dtype=np.float64)
    freqs = np.outer(t, inv)
    emb = np.concatenate([freqs, freqs], axis=-1)
    return (jnp.asarray(np.cos(emb), dtype), jnp.asarray(np.sin(emb), dtype))


from ..ops.nn_ops import rotate_half as _rot_half  # noqa: E402


def _apply_rope(x, cos, sin, offset):
    """x: [B, S, H, D] values; cos/sin: [max, D]; offset: traced or int,
    or a PER-ROW vector [B] (ragged batches: each row rotates at its own
    absolute positions)."""
    S = x.shape[1]
    off = jnp.asarray(offset, jnp.int32)
    if off.ndim:
        pos = off[:, None] + jnp.arange(S, dtype=jnp.int32)[None]  # [B,S]
        c = cos[pos][:, :, None, :]                          # [B,S,1,D]
        s = sin[pos][:, :, None, :]
    else:
        c = lax.dynamic_slice_in_dim(cos, offset, S, axis=0)[None, :,
                                                             None, :]
        s = lax.dynamic_slice_in_dim(sin, offset, S, axis=0)[None, :,
                                                             None, :]
    return x * c.astype(x.dtype) + _rot_half(x) * s.astype(x.dtype)


def _kernels_on() -> bool:
    """The flag and the platform: whether a dispatch site may run a
    Pallas kernel at all."""
    from ..core import flags as _flags
    from ..ops.pallas import is_tpu_platform

    return bool(_flags._get("use_pallas_kernels", True)) and is_tpu_platform()


def _dispatch_kernel(name, supported, kernel, dense):
    """Pallas-kernel dispatch policy, shared by the cache/paged
    attention paths: the flag, the platform and the kernel's shape gate
    choose the lowering; the chosen one is called bare, so a kernel the
    gate admitted and Mosaic refuses fails the run."""
    use_kernel = _kernels_on() and supported()
    # the semantic scope names BOTH lowerings after the kernel, so
    # device traces show e.g. `decode_attention` over whichever ran
    with _annotate(name):
        return kernel() if use_kernel else dense()


@def_op("llama_rms_norm")
def _rms_norm_dispatch(x, weight, epsilon=1e-5):
    from ..ops.pallas.rms_norm import (rms_norm_dense, rms_norm_fused,
                                       rms_norm_supported)

    return _dispatch_kernel(
        "rms_norm",
        lambda: rms_norm_supported(x.shape),
        lambda: rms_norm_fused(x, weight, float(epsilon)),
        lambda: rms_norm_dense(x, weight, float(epsilon)))


class LlamaRMSNorm(RMSNorm):
    """RMSNorm routed through the shared Pallas dispatch policy: the
    fused one-VMEM-pass kernel (ops/pallas/rms_norm.py) when the Mosaic
    shape gate admits the geometry on TPU, the dense XLA path otherwise
    (both accumulate in f32 with the same formula)."""

    def forward(self, x):
        return _rms_norm_dispatch(x, self.weight,
                                  epsilon=float(self._epsilon))


def _cache_attention(q, k_cache, v_cache, offset, S):
    """Attention of q [B,S,H,D] against static caches [B,KV,M,D]; valid
    kv positions are <= offset + row (the fused_multi_transformer
    cache-KV attention). On TPU this is the Pallas decode kernel —
    cache streamed in blocks, DMA stops at the valid frontier, GQA
    grouped natively (ops/pallas/decode_attention.py); the portable
    path is a full-cache matmul + length mask in XLA."""
    from ..ops.pallas import decode_attention as _da

    return _dispatch_kernel(
        "decode_attention",
        lambda: _da.supported(q.shape, k_cache.shape),
        lambda: _da.decode_attention(q, k_cache, v_cache, offset),
        lambda: _cache_attention_dense(q, k_cache, v_cache, offset, S))


def _paged_attention(q, k_pool, v_pool, tables, lengths, S, fresh=None,
                     offset=None):
    """Paged-cache attention dispatch: Pallas block-table kernel on TPU
    (reference capability: block_multi_head_attention_kernel.cu), XLA
    gather + ragged dense mask elsewhere. ``fresh`` = the ``(k, v)``
    ``[B, S, KV, D]`` this call has just written at ``offset``: a
    prefill at a concrete offset 0 attends to those directly, as causal
    flash attention (``decode_attention.paged_attention_form`` has the
    rule). One scope name over whichever lowering ran; the form is
    recorded for whoever listens (``ServingEngine``)."""
    from ..observability import moestats as _moestats
    from ..ops.pallas import decode_attention as _da
    from ..ops.pallas.flash_attention import flash_attention_gqa

    form = "dense"
    if _kernels_on():
        form = _da.paged_attention_form(
            q.shape, k_pool.shape,
            None if fresh is None else fresh[0].shape, offset)
    _moestats.record({"attention": form})
    with _annotate("paged_decode_attention"):
        if form == "flash":
            k, v = fresh      # in the pool's type: what a page would hold
            return flash_attention_gqa(q, k.astype(k_pool.dtype),
                                       v.astype(v_pool.dtype))
        if form == "paged":
            return _da.paged_decode_attention(q, k_pool, v_pool, tables,
                                              lengths)
        return _da.paged_attention_dense(q, k_pool, v_pool, tables, lengths)


def _unified_paged_attention(q, k_pool, v_pool, tables, starts, valid):
    """Unified mixed prefill-chunk/decode attention dispatch over the
    page pool (the Ragged Paged Attention design): Pallas ragged kernel
    on TPU, gathered doubly-ragged dense mask elsewhere."""
    from ..ops.pallas import ragged_paged_attention as _ra

    return _dispatch_kernel(
        "ragged_paged_attention",
        lambda: _ra.ragged_supported(q.shape, k_pool.shape),
        lambda: _ra.ragged_paged_attention(q, k_pool, v_pool, tables,
                                           starts, valid),
        lambda: _ra.ragged_paged_attention_dense(q, k_pool, v_pool,
                                                 tables, starts, valid))


def _cache_attention_dense(q, k_cache, v_cache, offset, S):
    """Caches are head-major [B, KV, M, D]; offset scalar or [B]. The
    math lives in ops/pallas/decode_attention._dense_ragged (shared
    with the paged fallback)."""
    from ..ops.pallas.decode_attention import _dense_ragged

    B = q.shape[0]
    off = jnp.broadcast_to(jnp.asarray(offset, jnp.int32).reshape(-1),
                           (B,))
    return _dense_ragged(q, k_cache, v_cache, off)


class LlamaAttention(Layer):
    """GQA attention with rotary embeddings; qkv column-, out row-parallel."""

    def __init__(self, config: LlamaConfig):
        super().__init__()
        self.config = config
        std = config.initializer_range
        h, D = config.hidden_size, config.head_dim
        kv = config.num_kv_heads * D
        from ..core.enforce import enforce

        self.q_proj = ColumnParallelLinear(h, h, weight_attr=_init_attr(std),
                                           has_bias=False,
                                           gather_output=False)
        enforce(config.num_heads % self.q_proj.world_size == 0
                and config.num_kv_heads % self.q_proj.world_size == 0,
                f"num_heads {config.num_heads} and num_kv_heads "
                f"{config.num_kv_heads} must divide mp degree "
                f"{self.q_proj.world_size} (GQA TP sharding)")
        self.k_proj = ColumnParallelLinear(h, kv, weight_attr=_init_attr(std),
                                           has_bias=False,
                                           gather_output=False)
        self.v_proj = ColumnParallelLinear(h, kv, weight_attr=_init_attr(std),
                                           has_bias=False,
                                           gather_output=False)
        self.o_proj = RowParallelLinear(
            h, h, weight_attr=_init_attr(std / math.sqrt(2 * config.num_layers)),
            has_bias=False, input_is_parallel=True)
        # built eagerly: creating constants inside a jit trace and caching
        # them on the layer would leak tracers
        self._rope = _rope_tables(config, jnp.float32)

    def _tables(self, dtype):
        return self._rope

    def forward(self, x, cache=None, offset=0, valid=None):
        cfg = self.config
        B, S = x.shape[0], x.shape[1]
        D = cfg.head_dim
        q = self.q_proj(x)
        k = self.k_proj(x)
        v = self.v_proj(x)
        n_local = q.shape[-1] // D
        nkv_local = k.shape[-1] // D
        qv = q._value.reshape(B, S, n_local, D)
        kv_ = k._value.reshape(B, S, nkv_local, D)
        vv = v._value.reshape(B, S, nkv_local, D)
        cos, sin = self._tables(jnp.float32)
        qv = _apply_rope(qv, cos, sin, offset)
        kv_ = _apply_rope(kv_, cos, sin, offset)

        if cache is not None:
            if len(cache) == 3:         # paged: (k_pool, v_pool, tables)
                k_pool, v_pool, tables = cache
                off = jnp.broadcast_to(
                    jnp.asarray(offset, jnp.int32).reshape(-1), (B,))
                nv = None if valid is None \
                    else jnp.asarray(valid, jnp.int32).reshape(B)
                # the new [B,S,KV,D] kv rows land in their physical
                # pages, in place (rows a row does not own are mapped
                # to the trash page by the table, see inference paged
                # allocator). With ``valid`` (the unified mixed
                # prefill-chunk/decode step) only the first valid[b]
                # slots of row b are real tokens. CONTRACT: the
                # caller's table then carries ONE EXTRA trailing column
                # that always maps to the trash page
                # (inference/serving.py builds it) — dead slots' kv
                # writes are redirected there instead of clobbering the
                # row's own future cache slots
                from ..ops.pallas.decode_attention import paged_kv_write

                k_pool, v_pool = paged_kv_write(
                    k_pool, v_pool, kv_, vv, tables, offset, nv)
                if valid is not None:
                    # the trailing trash column is a write-side device
                    # only: attention sees the canonical [B, npages]
                    # table, so the key space (and the compiled
                    # attention shape) matches the two-program path
                    ov = _unified_paged_attention(
                        qv, k_pool, v_pool, tables[:, :-1], off, nv)
                else:
                    ov = _paged_attention(qv, k_pool, v_pool, tables,
                                          off, S, (kv_, vv), offset)
                out = Tensor(ov.reshape(B, S, n_local * D),
                             stop_gradient=True)
                return self.o_proj(out), (k_pool, v_pool, tables)
            from ..core.enforce import enforce

            enforce(valid is None, "valid (unified ragged metadata) is "
                    "only served over the paged KV cache")
            k_cache, v_cache = cache    # head-major [B, KV, M, D]
            off = jnp.asarray(offset, jnp.int32)
            k_new = jnp.swapaxes(kv_, 1, 2).astype(k_cache.dtype)
            v_new = jnp.swapaxes(vv, 1, 2).astype(v_cache.dtype)
            if off.ndim:                # ragged: per-row write positions
                dus = lambda c, u, o: lax.dynamic_update_slice_in_dim(
                    c, u, o, axis=1)    # [KV,M,D] <- [KV,S,D] @ row off
                k_cache = jax.vmap(dus)(k_cache, k_new, off)
                v_cache = jax.vmap(dus)(v_cache, v_new, off)
            else:
                k_cache = lax.dynamic_update_slice_in_dim(
                    k_cache, k_new, offset, axis=2)
                v_cache = lax.dynamic_update_slice_in_dim(
                    v_cache, v_new, offset, axis=2)
            ov = _cache_attention(qv, k_cache, v_cache, offset, S)
            out = Tensor(ov.reshape(B, S, n_local * D), stop_gradient=True)
            return self.o_proj(out), (k_cache, v_cache)

        # training path: tape-tracked rope + flash attention. GQA heads
        # pass through as-is — flash_attention groups q per kv head by
        # broadcast (no repeated K/V copies on the XLA path)
        q_r = _rope_op(q, B, S, n_local, D, cos, sin)
        k_r = _rope_op(k, B, S, nkv_local, D, cos, sin)
        v_r = ops.reshape(v, (B, S, nkv_local, D))
        o = flash_attention(q_r, k_r, v_r, causal=True)
        o = ops.reshape(o, (B, S, n_local * D))
        return self.o_proj(o)


def _rope_op(x, B, S, n, D, cos, sin):
    """Tape-differentiable rope on a [B,S,n*D] projection output."""
    x4 = ops.reshape(x, (B, S, n, D))
    from ..ops.nn_ops import fused_rope

    out, _ = fused_rope(x4, x4, cos[:S], sin[:S])
    return out


class LlamaMLP(Layer):
    """SwiGLU MLP: gate/up column-parallel, down row-parallel."""

    def __init__(self, config: LlamaConfig):
        super().__init__()
        std = config.initializer_range
        h, m = config.hidden_size, config.intermediate_size
        self.gate_proj = ColumnParallelLinear(
            h, m, weight_attr=_init_attr(std), has_bias=False,
            gather_output=False)
        self.up_proj = ColumnParallelLinear(
            h, m, weight_attr=_init_attr(std), has_bias=False,
            gather_output=False)
        self.down_proj = RowParallelLinear(
            m, h, weight_attr=_init_attr(std / math.sqrt(2 * config.num_layers)),
            has_bias=False, input_is_parallel=True)

    def forward(self, x):
        return self.down_proj(F.silu(self.gate_proj(x)) * self.up_proj(x))


class LlamaDecoderLayer(Layer):
    def __init__(self, config: LlamaConfig):
        super().__init__()
        self.input_layernorm = LlamaRMSNorm(config.hidden_size,
                                            epsilon=config.rms_norm_eps)
        self.self_attn = LlamaAttention(config)
        self.post_attention_layernorm = LlamaRMSNorm(
            config.hidden_size, epsilon=config.rms_norm_eps)
        self.mlp = LlamaMLP(config)

    def forward(self, x, cache=None, offset=0, valid=None):
        if cache is not None:
            with _annotate("attention"):
                a, new_cache = self.self_attn(self.input_layernorm(x),
                                              cache=cache, offset=offset,
                                              valid=valid)
            x = x + a
            with _annotate("mlp"):
                x = x + self.mlp(self.post_attention_layernorm(x))
            return x, new_cache
        with _annotate("attention"):
            x = x + self.self_attn(self.input_layernorm(x))
        with _annotate("mlp"):
            x = x + self.mlp(self.post_attention_layernorm(x))
        return x


class LlamaModel(Layer):
    def __init__(self, config: LlamaConfig):
        super().__init__()
        self.config = config
        self.embed_tokens = VocabParallelEmbedding(
            config.vocab_size, config.hidden_size,
            weight_attr=_init_attr(config.initializer_range))
        self.layers = LayerList([LlamaDecoderLayer(config)
                                 for _ in range(config.num_layers)])
        self.norm = LlamaRMSNorm(config.hidden_size,
                                 epsilon=config.rms_norm_eps)

    def forward(self, input_ids, caches=None, offset=0, valid=None):
        # named scopes per layer: XLA metadata (and thus the Perfetto /
        # TensorBoard device trace) reads `llama/layer3/attention`
        # instead of bare fusions
        with _annotate("llama"):
            with _annotate("embed"):
                x = self.embed_tokens(input_ids)
            if caches is not None:
                new_caches = []
                for i, (layer, cache) in enumerate(zip(self.layers,
                                                       caches)):
                    with _annotate(f"layer{i}"):
                        x, nc = layer(x, cache=cache, offset=offset,
                                      valid=valid)
                    new_caches.append(nc)
                return self.norm(x), new_caches
            for i, layer in enumerate(self.layers):
                with _annotate(f"layer{i}"):
                    x = layer(x)
            return self.norm(x)


class LlamaForCausalLM(Layer):
    """Llama with (untied by default) vocab-parallel LM head + compiled
    KV-cache generation (the fused_multi_transformer decode path)."""

    def __init__(self, config: LlamaConfig):
        super().__init__()
        self.config = config
        self.llama = LlamaModel(config)
        if not config.tie_word_embeddings:
            self.lm_head = ColumnParallelLinear(
                config.hidden_size, config.vocab_size,
                weight_attr=_init_attr(config.initializer_range),
                has_bias=False, gather_output=False)
        if config.dtype not in ("float32", None):
            self.astype(config.dtype)
        self._decode_fns = {}

    def _logits(self, x):
        if self.config.tie_word_embeddings:
            from ..distributed.fleet.layers.mpu.mp_ops import (_c_identity,
                                                               mp_active)

            w = self.llama.embed_tokens.weight
            if mp_active():
                x = _c_identity(x)
            return ops.matmul(x, w, transpose_y=True)
        return self.lm_head(x)

    def forward(self, input_ids, caches=None, offset=0, valid=None):
        if caches is not None:
            x, new_caches = self.llama(input_ids, caches=caches,
                                       offset=offset, valid=valid)
            return self._logits(x), new_caches
        return self._logits(self.llama(input_ids))

    # -- generation (compiled decode loop) ------------------------------
    def _empty_caches(self, B: int, max_len: int, dtype):
        # head-major [B, KV, M, D]: each head's [M, D] plane contiguous
        # (Mosaic-tileable for the Pallas decode kernel)
        cfg = self.config
        shape = (B, cfg.num_kv_heads, max_len, cfg.head_dim)
        return [(jnp.zeros(shape, dtype), jnp.zeros(shape, dtype))
                for _ in range(cfg.num_layers)]

    def _step_fn(self, B: int, S: int, max_len: int):
        """One jitted forward-with-cache step; compiled per (B, S)."""
        key = (B, S, max_len)
        if key in self._decode_fns:
            return self._decode_fns[key]
        params = list(self.parameters())
        from ..distributed.engine import bind_params

        def step(pvals, ids, caches, offset):
            with no_grad(), bind_params(params, pvals):
                logits, new_caches = self.forward(
                    Tensor(ids, stop_gradient=True), caches=caches,
                    offset=offset)
            return logits._value, new_caches

        self._decode_fns[key] = jax.jit(step, donate_argnums=(2,))
        return self._decode_fns[key]

    def generate(self, input_ids, max_new_tokens: int = 32,
                 temperature: float = 0.0, top_k: int = 0,
                 seed: int = 0, max_length: Optional[int] = None):
        """Greedy (or temperature/top-k) generation with static caches.

        Returns a Tensor [B, S_prompt + max_new_tokens]. Exactly two XLA
        programs run: prefill [B, S_prompt] and decode [B, 1] — the
        decode program is reused every token with donated cache buffers.
        """
        ids = input_ids._value if isinstance(input_ids, Tensor) else \
            jnp.asarray(input_ids)
        B, S0 = ids.shape
        M = max_length or min(self.config.max_position_embeddings,
                              S0 + max_new_tokens)
        from ..core.enforce import enforce

        enforce(S0 + max_new_tokens <= M,
                f"prompt ({S0}) + max_new_tokens ({max_new_tokens}) "
                f"exceeds the cache length {M} "
                f"(max_position_embeddings="
                f"{self.config.max_position_embeddings}); writes past the "
                "cache would silently clamp")
        p_dtype = self.parameters()[0]._value.dtype
        caches = self._empty_caches(B, M, p_dtype)
        pvals = tuple(p._value for p in self.parameters())

        prefill = self._step_fn(B, S0, M)
        logits, caches = prefill(pvals, ids, caches, 0)
        key = jax.random.PRNGKey(seed)

        def pick(logits_last, key):
            if temperature and temperature > 0:
                lg = logits_last / temperature
                if top_k:
                    kth = jnp.sort(lg, axis=-1)[:, -top_k][:, None]
                    lg = jnp.where(lg < kth, -1e30, lg)
                return jax.random.categorical(key, lg, axis=-1)
            return jnp.argmax(logits_last, axis=-1)

        toks = [ids]
        step = self._step_fn(B, 1, M)
        nxt = pick(logits[:, -1].astype(jnp.float32), key)
        pos = S0
        for i in range(max_new_tokens - 1):
            toks.append(nxt[:, None])
            logits, caches = step(pvals, nxt[:, None], caches, pos)
            key, sub = jax.random.split(key)
            nxt = pick(logits[:, -1].astype(jnp.float32), sub)
            pos += 1
        toks.append(nxt[:, None])
        return Tensor(jnp.concatenate(toks, axis=1), stop_gradient=True)


class LlamaPretrainingCriterion(Layer):
    """Vocab-parallel LM loss (same contract as GPTPretrainingCriterion)."""

    def __init__(self, config: Optional[LlamaConfig] = None, mp_group=None):
        super().__init__()
        self._mp_group = mp_group

    def forward(self, logits, labels, loss_mask=None):
        loss = parallel_cross_entropy(logits, labels, self._mp_group)
        loss = ops.squeeze(loss, axis=-1)
        if loss_mask is not None:
            from .gpt import _masked_mean_over_splits

            m = ops.cast(loss_mask, str(loss.dtype))
            return _masked_mean_over_splits(ops.sum(loss * m), ops.sum(m))
        return ops.mean(loss)


def llama_tiny(**kw) -> LlamaConfig:
    return LlamaConfig(vocab_size=256, hidden_size=64, num_layers=2,
                       num_heads=4, num_kv_heads=2, intermediate_size=128,
                       max_position_embeddings=128, **kw)


def llama_tiny_draft(**kw) -> LlamaConfig:
    """Draft-sized companion to ``llama_tiny`` for speculative
    decoding: same vocabulary and position range (the serving engine
    requires both), roughly a quarter of the compute — one layer,
    half the width."""
    kw.setdefault("vocab_size", 256)
    kw.setdefault("max_position_embeddings", 128)
    return LlamaConfig(hidden_size=32, num_layers=1, num_heads=2,
                       num_kv_heads=1, intermediate_size=64, **kw)


def llama_7b(**kw) -> LlamaConfig:
    return LlamaConfig(**kw)


def llama_13b(**kw) -> LlamaConfig:
    kw.setdefault("hidden_size", 5120)
    kw.setdefault("num_layers", 40)
    kw.setdefault("num_heads", 40)
    kw.setdefault("intermediate_size", 13824)
    return LlamaConfig(**kw)
