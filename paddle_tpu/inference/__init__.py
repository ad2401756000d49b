"""Inference / serving API (paddle.inference analog).

TPU-native redesign of the reference's AnalysisPredictor stack
(reference: paddle/fluid/inference/api/analysis_predictor.h:100
AnalysisPredictor::Run, paddle_inference_api.h Config/CreatePredictor,
api/api_impl.cc NativePaddlePredictor). The reference predictor loads a
static Program, runs IR passes and executes on a Scope; every knob
about IR/memory optimization is owned here by XLA, so the TPU predictor
is: load params → jit-compile → run.

Serving design (the fused_multi_transformer decode loop, XLA style):

- ``Predictor.run`` — generic compiled forward, cached per input shape.
- ``Predictor.generate`` — LLM serving path over any model exposing the
  KV-cache protocol (``_empty_caches``/``forward(ids, caches, offset)``,
  e.g. LlamaForCausalLM, FusedMultiTransformer wrappers):
  * PREFILL: the prompt is right-padded to a power-of-two bucket so one
    compiled program serves every prompt length in the bucket (the
    garbage cache rows past the longest true length are never attended —
    decode masks by absolute position — and are overwritten as decoding
    advances); last-token logits are gathered at each row's true length.
  * RAGGED batches decode at PER-ROW offsets: each row's rope
    positions, cache-write slot, and attention frontier advance from
    its own true length, with optional per-row EOS stopping
    (GenerationConfig.eos_token_id) — the continuous-batching
    decode semantics of the reference's block_multi_head_attention.
  * PAGED KV (Config.enable_paged_kv): physical [page, D] pages in a
    shared pool + per-row block tables; pages are allocated per row
    for len+new tokens only, so a ragged batch pays sum(len_i), not
    B*max_len, of HBM (reference: phi/kernels/fusion/gpu/
    block_multi_head_attention_kernel.cu — there CUDA threads chase
    the table; here the Pallas BlockSpec index map does).
  * DECODE: the WHOLE token loop is ONE compiled XLA program — a
    ``lax.scan`` over steps carrying (token, caches, rng) with donated
    cache buffers, sampling (greedy/temperature/top-k/top-p) fused in.
    Zero host round-trips per token; the cache-KV attention inside is
    the Pallas decode kernel on TPU (ops/pallas/decode_attention.py).
"""
from __future__ import annotations

import json
import os
from dataclasses import dataclass
from typing import Any, Callable, Dict, List, Optional

import jax
import numpy as np
import jax.numpy as jnp
from jax import lax

from ..tensor import Tensor

__all__ = ["Config", "Predictor", "create_predictor", "GenerationConfig",
           "CompileStats", "ServingEngine", "ServingRequest",
           "Router", "RouterServer", "Replica", "KVMigrator",
           "MigrationCorruptError"]


# one lattice definition for the whole tree (serving S/P buckets, MoE
# expert capacity): core/bucketing.py
from ..core.bucketing import bucket as _bucket  # noqa: E402


# shared with the training engine (ParallelEngine.stats); the class
# lives in core so distributed/engine.py can import it without pulling
# the whole inference stack
from ..core.compile_stats import CompileStats  # noqa: E402,F401


def _sample(logits, key, gen: "GenerationConfig"):
    """Greedy / temperature / top-k / top-p sampling (traceable; used by
    both the first-token host step and the compiled decode loop)."""
    lg = logits.astype(jnp.float32)
    if gen.temperature and gen.temperature > 0:
        lg = lg / gen.temperature
        if gen.top_k:
            kth = jax.lax.top_k(lg, gen.top_k)[0][:, -1][:, None]
            lg = jnp.where(lg < kth, -1e30, lg)
        if gen.top_p < 1.0:
            srt = jnp.sort(lg, axis=-1)[:, ::-1]
            probs = jax.nn.softmax(srt, axis=-1)
            cum = jnp.cumsum(probs, axis=-1)
            # smallest set with cumulative prob >= top_p
            cutoff_idx = jnp.sum(cum < gen.top_p, axis=-1)
            cutoff = jnp.take_along_axis(srt, cutoff_idx[:, None], axis=-1)
            lg = jnp.where(lg < cutoff, -1e30, lg)
        return jax.random.categorical(key, lg, axis=-1)
    return jnp.argmax(lg, axis=-1)


@dataclass
class GenerationConfig:
    max_new_tokens: int = 128
    temperature: float = 0.0       # 0 = greedy
    top_k: int = 0                 # 0 = off
    top_p: float = 1.0             # 1 = off
    seed: int = 0
    eos_token_id: Optional[int] = None  # per-row stop; post-EOS tokens
    #                                     are filled with eos_token_id


class Config:
    """Predictor configuration (reference: paddle_inference_api.h Config).

    The TPU predictor takes either a live Layer (``set_model``) or a
    params file saved with ``paddle.save(model.state_dict(), path)``
    plus a model factory. The reference's IR/pass/memory knobs are
    accepted as no-ops for API compatibility — XLA owns those choices.
    """

    def __init__(self, model_dir: Optional[str] = None,
                 params_file: Optional[str] = None):
        self.model_dir = model_dir
        self.params_file = params_file
        self._model = None
        self._model_factory: Optional[Callable[[], Any]] = None
        self.dtype: Optional[str] = None
        self.max_batch_size = 8
        self.max_length: Optional[int] = None
        self.generation = GenerationConfig()
        self._mem_optim = True
        self._ir_optim = True
        self._weight_only_algo: Optional[str] = None
        self._weight_only_skip = ("lm_head",)
        self._kv_page_size: Optional[int] = None

    # -- model sources --------------------------------------------------
    def set_model(self, model) -> "Config":
        """Serve a live Layer instance."""
        self._model = model
        return self

    def set_model_factory(self, factory: Callable[[], Any]) -> "Config":
        """Factory building the (uninitialized) model; combined with
        ``params_file`` / ``model_dir`` for weight loading."""
        self._model_factory = factory
        return self

    def set_params_file(self, path: str) -> "Config":
        self.params_file = path
        return self

    def enable_weight_only(self, algo: str = "weight_only_int8",
                           skip=("lm_head",)) -> "Config":
        """Serve with int8/int4 weights resident in HBM
        (nn.quant.quantize_for_serving): decode is weight-bandwidth
        bound, so tokens/s scales with the byte shrink. ``skip`` keeps
        named layers (default: the LM head) in full precision."""
        if algo not in ("weight_only_int8", "weight_only_int4"):
            raise ValueError(
                f"enable_weight_only supports weight_only_int8/int4, got "
                f"{algo!r} (llm.int8 is the functional nn.quant."
                f"llm_int8_linear, not a serving swap)")
        self._weight_only_algo = algo
        self._weight_only_skip = tuple(skip)
        return self

    def enable_paged_kv(self, page_size: int = 64) -> "Config":
        """Serve with a paged (block-table) KV cache (reference:
        block_multi_head_attention / enable_block_attn): physical pages
        are allocated per row for ceil((len+new)/page) tokens instead of
        B*max_len rows, so ragged batches don't pay max-length HBM. The
        attention is the block-table Pallas kernel on TPU
        (ops/pallas/decode_attention.py paged_decode_attention)."""
        if page_size < 8 or page_size % 8:
            raise ValueError("page_size must be a multiple of 8 (TPU "
                             f"sublane tiling), got {page_size}")
        self._kv_page_size = int(page_size)
        return self

    # -- reference-compat knobs (XLA owns these; kept as recorded flags)
    def enable_memory_optim(self, flag: bool = True) -> None:
        self._mem_optim = flag

    def switch_ir_optim(self, flag: bool = True) -> None:
        self._ir_optim = flag

    def set_cpu_math_library_num_threads(self, n: int) -> None:
        pass

    def enable_use_gpu(self, *a, **k) -> None:  # pragma: no cover
        raise ValueError("paddle_tpu serves on TPU; there is no GPU path")


def create_predictor(config: Config) -> "Predictor":
    """(reference: paddle_infer::CreatePredictor)"""
    return Predictor(config)


class Predictor:
    def __init__(self, config: Config):
        self.config = config
        self._model = self._build_model(config)
        self._model.eval()
        self._params = list(self._model.parameters())
        self._run_fns: Dict[Any, Any] = {}
        self._decode_fns: Dict[Any, Any] = {}
        self._prefill_fns: Dict[Any, Any] = {}
        self._last_outputs: List[np.ndarray] = []
        self._input_names = ["input_ids"]
        self.stats = CompileStats()

    @staticmethod
    def _build_model(config: Config):
        model = config._model
        if model is None:
            if config._model_factory is None:
                raise ValueError(
                    "Config needs set_model(layer) or set_model_factory "
                    "(+ params_file/model_dir) before create_predictor")
            model = config._model_factory()
        path = config.params_file
        if path is None and config.model_dir:
            for cand in ("model.pdparams", "params"):
                p = os.path.join(config.model_dir, cand)
                if os.path.exists(p):
                    path = p
                    break
        if path:
            from ..framework.io import load

            model.set_state_dict(load(path))
        if config.dtype:
            model.astype(config.dtype)
        if config._weight_only_algo:
            from ..nn.quant import quantize_for_serving

            quantize_for_serving(model, config._weight_only_algo,
                                 config._weight_only_skip)
        return model

    # ------------------------------------------------------------------
    # generic forward serving (AnalysisPredictor::Run)
    # ------------------------------------------------------------------
    def get_input_names(self) -> List[str]:
        return list(self._input_names)

    def get_output_names(self) -> List[str]:
        return [f"output_{i}" for i in range(len(self._last_outputs) or 1)]

    def run(self, inputs: List[Any]) -> List[np.ndarray]:
        """Compiled forward on a list of inputs; one XLA program per
        input-shape signature (the predictor analog of shape-keyed
        retrace in jit/__init__.py)."""
        vals = [x._value if isinstance(x, Tensor) else jnp.asarray(x)
                for x in inputs]
        key = tuple((v.shape, str(v.dtype)) for v in vals)
        self.stats.note("run", key)
        if key not in self._run_fns:
            model, params = self._model, self._params
            from ..autograd import no_grad
            from ..distributed.engine import bind_params

            def fwd(pvals, *xs):
                with no_grad(), bind_params(params, pvals):
                    out = model(*[Tensor(x, stop_gradient=True)
                                  for x in xs])
                outs = out if isinstance(out, (list, tuple)) else (out,)
                return [o._value if isinstance(o, Tensor) else o
                        for o in outs]

            self._run_fns[key] = jax.jit(fwd)
        pvals = tuple(p._value for p in self._params)
        outs = self._run_fns[key](pvals, *vals)
        self._last_outputs = [np.asarray(o) for o in outs]
        return self._last_outputs

    # ------------------------------------------------------------------
    # LLM serving (fused_multi_transformer decode loop)
    # ------------------------------------------------------------------
    def _max_len(self, S0: int, n_new: int) -> int:
        if self.config.max_length:
            return self.config.max_length
        cap = getattr(getattr(self._model, "config", None),
                      "max_position_embeddings", None)
        need = _bucket(S0) + n_new
        return min(cap, _bucket(need)) if cap else _bucket(need)

    def _prefill_fn(self, B, Sb, M):
        key = (B, Sb, M, self.config._kv_page_size)
        if key in self._prefill_fns:
            return self._prefill_fns[key]
        model, params = self._model, self._params
        from ..autograd import no_grad
        from ..distributed.engine import bind_params

        # a model may run its head on each row's last prompt token alone
        # (``head_on_last_row``: its forward takes the lengths and
        # returns [B, vocab]); the others compute every position's
        # logits and one row of them is gathered here
        last_row = bool(getattr(model, "head_on_last_row", False))

        def prefill(pvals, ids, caches, lengths):
            with no_grad(), bind_params(params, pvals):
                logits, caches = model.forward(
                    Tensor(ids, stop_gradient=True), caches=caches,
                    offset=0, **({"lengths": lengths} if last_row else {}))
            lv = logits._value if isinstance(logits, Tensor) else logits
            if last_row:
                return lv, caches
            # gather each row's logits at its true last prompt token
            last = jnp.take_along_axis(
                lv, (lengths - 1)[:, None, None], axis=1)[:, 0]
            return last, caches

        self._prefill_fns[key] = jax.jit(prefill, donate_argnums=(2,))
        return self._prefill_fns[key]

    def _decode_fn(self, B, M, n_new, gen: GenerationConfig, ragged,
                   paged):
        key = (B, M, n_new, gen.temperature, gen.top_k, gen.top_p,
               gen.eos_token_id, ragged, paged)
        if key in self._decode_fns:
            return self._decode_fns[key]
        model, params = self._model, self._params
        eos = gen.eos_token_id
        from ..autograd import no_grad
        from ..distributed.engine import bind_params

        def decode(pvals, tok0, caches, pos0, rng):
            done0 = (tok0 == eos) if eos is not None \
                else jnp.zeros((B,), bool)

            def body(carry, _):
                tok, caches, pos, rng, done = carry
                with no_grad(), bind_params(params, pvals):
                    logits, caches = model.forward(
                        Tensor(tok[:, None], stop_gradient=True),
                        caches=caches, offset=pos)
                lv = (logits._value if isinstance(logits, Tensor)
                      else logits)
                rng, sub = jax.random.split(rng)
                nxt = _sample(lv[:, -1], sub, gen)
                if eos is not None:  # per-row stop: freeze at eos
                    nxt = jnp.where(done, jnp.asarray(eos, nxt.dtype),
                                    nxt)
                    done = done | (nxt == eos)
                return (nxt, caches, pos + 1, rng, done), nxt

            (tok, caches, _, _, _), toks = lax.scan(
                body, (tok0, caches, pos0, rng, done0), None,
                length=n_new)
            return jnp.swapaxes(toks, 0, 1), caches  # [B, n_new]

        self._decode_fns[key] = jax.jit(decode, donate_argnums=(2,))
        return self._decode_fns[key]

    # -- paged KV-cache pool (reference: block_multi_head_attention's
    #    block tables; here a PagedKVCache sized for the call) ---
    def _paged_caches(self, lengths, n_new, M, page, dtype):
        """Allocate per-row physical pages for len+n_new tokens from a
        ``PagedKVCache`` sized for this call. Logical pages a row does
        not own map to one shared TRASH page, so prefill's right-pad
        writes land harmlessly (they are never attended: the mask stops
        at each row's frontier).

        The physical pool size P is BUCKETED to a power of two exactly
        like S: jax.jit keys compiled programs on the pool shape, so an
        exact ``sum(need)+1`` pool would recompile prefill AND the fused
        decode scan on nearly every distinct batch length-mix. On the
        bucket lattice, every mix whose page demand lands in the same
        bucket reuses the same compiled programs (the extra pages are
        never referenced by any table entry below the trash id)."""
        from ..core.enforce import enforce
        from .kv_cache import PagedKVCache, page_classes

        enforce(not page_classes(self._model)[1],
                "Predictor.generate over the paged cache hands prefill "
                "and decode one table a layer, and a model with window "
                "layers needs two (a logical one to prefill through, its "
                "ring to decode through): serve it through "
                "ServingEngine, or generate over the static cache")
        need = [-(-(int(l) + n_new) // page) for l in lengths]
        cache = PagedKVCache(self._model, page, M, len(lengths), dtype,
                             pool_pages=sum(need) + 1)  # +1 trash page
        # the call owns its whole pool: page ids in order, from 0
        ids = sorted(cache.allocate(cache.usable))
        for b, nb in enumerate(need):
            cache.set_row(b, ids[:nb])
            del ids[:nb]
        return cache.bind(cache.rows()), cache.P

    def generate(self, input_ids, max_new_tokens: Optional[int] = None,
                 lengths=None, **overrides):
        """Batched generation; one compiled prefill + ONE compiled
        decode program for the whole token loop. ``lengths`` gives the
        true per-row prompt lengths for right-padded ragged batches;
        ragged rows decode at per-row offsets (own rope positions,
        cache slots, and attention frontier), stopping per row at
        ``eos_token_id`` when set (later slots filled with eos)."""
        gen = GenerationConfig(**{
            **self.config.generation.__dict__,
            **({"max_new_tokens": max_new_tokens}
               if max_new_tokens is not None else {}),
            **overrides})
        ids = np.asarray(input_ids._value if isinstance(input_ids, Tensor)
                         else input_ids)
        B, S0 = ids.shape
        if lengths is None:
            lengths = np.full((B,), S0, np.int32)
        lengths = np.asarray(lengths, np.int32)
        n_new = gen.max_new_tokens
        M = self._max_len(S0, n_new)
        # bucket never past the cache: a 90-token prompt with
        # max_length=100 must prefill at Sb=100, not bucket 128
        Sb = min(_bucket(S0), M)
        ragged = int(lengths.min()) != int(lengths.max())
        from ..core.enforce import enforce

        enforce(int(lengths.max()) + n_new <= M,
                f"prompt ({int(lengths.max())}) + max_new_tokens ({n_new}) "
                f"exceeds cache length {M}; raise config.max_length")
        model = self._model
        p_dtype = self._params[0]._value.dtype
        pvals = tuple(p._value for p in self._params)
        page = self.config._kv_page_size
        if page:
            caches, P = self._paged_caches(lengths, n_new, M, page,
                                           p_dtype)
        else:
            caches = model._empty_caches(B, M, p_dtype)
            P = 0

        ids_p = np.zeros((B, Sb), ids.dtype)
        ids_p[:, :S0] = ids
        # B is the caller's batch by contract (one program per batch
        # size); the ServingEngine pins B for traffic-grade serving
        # tpulint: disable=recompile-hazard
        prefill = self._prefill_fn(B, Sb, M)
        self.stats.note("prefill", (B, Sb, M, page, P, str(ids_p.dtype),
                                    str(p_dtype)))
        last, caches = prefill(pvals, jnp.asarray(ids_p), caches,
                               jnp.asarray(lengths))

        rng = jax.random.PRNGKey(gen.seed)
        rng, sub = jax.random.split(rng)
        # first sampled token (same rule as the compiled loop)
        # B: static per-call batch, same contract as prefill above
        # tpulint: disable=recompile-hazard
        decode = self._decode_fn(B, M, n_new - 1, gen, ragged,
                                 bool(page)) if n_new > 1 else None
        if decode is not None:
            self.stats.note("decode", (B, M, n_new - 1, gen.temperature,
                                       gen.top_k, gen.top_p,
                                       gen.eos_token_id, ragged, page, P,
                                       str(p_dtype)))
        self.stats.count_tokens(("generate", B, Sb, P), B * n_new)
        tok0 = _sample(last, sub, gen)
        # ragged rows decode at PER-ROW offsets: each row's rope
        # positions, cache-write slot, and attention frontier advance
        # from its own true length (no lockstep from max(lengths))
        pos0 = jnp.asarray(lengths) if ragged else int(lengths.max())
        if decode is not None:
            toks, caches = decode(pvals, tok0, caches, pos0, rng)
            all_new = jnp.concatenate([tok0[:, None], toks], axis=1)
        else:
            all_new = tok0[:, None]
        out = jnp.concatenate([jnp.asarray(ids), all_new], axis=1)
        return Tensor(out, stop_gradient=True)


from .serving import ServingEngine, ServingRequest  # noqa: E402
from .disagg import KVMigrator, MigrationCorruptError  # noqa: E402
from .router import Replica, Router, RouterServer  # noqa: E402
