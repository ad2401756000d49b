"""chip_smoke.py — does the system still start on the chip?

Drives the two hot paths once each through the entry points a user
calls, at the full width of models the repo supports, on one TPU chip:

  kernels  every Pallas kernel compiled by Mosaic (never interpreted) at
           the shapes the next two phases use, against the dense function
           that sits beside it, flash backward included;
  train    GPT-3 1.3B (S=1024, B=4, bf16 params and moments) through
           ``fleet.init`` -> ``ParallelEngine.train_step``;
  serve    Llama-7B widths, depth cut to fit one chip, through
           ``Config.enable_paged_kv`` -> ``create_predictor`` ->
           ``ServingEngine`` in both of its modes.

Every serving phase is one body, ``serve_case``, over an entry of
``SERVE_CASES``, which holds what differs. Four run only when named in
``--phases``: ``serve_latent`` (latent attention + routed experts; two
shapes: sarvam's block and the shortcut-connected double layer),
``serve_hybrid`` (window and full attention layers: MiMo-V2's block, then
the AFMoE block), ``serve_sparse`` and ``serve_sparse_mla`` (attention
over the keys, or the latent cache rows, a learned index keeps). Each
holds its decode kernels against their dense twins, its pooled arrays
written in place and its kernel by name in the decode program; ``Sizes``
says what each is cut to and why.

``--four-chips`` adds the train step over a real 2x2 mesh in two layouts
(mp2 x dp2 on ParallelEngine; pp2 x mp2 on GPTForCausalLMPipe via
``fleet.distributed_model(...).train_batch``), or fails under four TPUs.

    python chip_smoke.py                        # one chip
    python chip_smoke.py --four-chips           # one four-chip host
    python -m paddle_tpu.distributed.launch chip_smoke.py --four-chips
    python chip_smoke.py --phases kernels       # a subset, in order

A chip belongs to one process at a time and HBM is only reliably released
at process exit, so every phase is a child process and this parent never
touches JAX. A child that finds no TPU exits non-zero. The last line of
stdout on success is ``{"ok": true, "device": {"platform": "tpu", "kind":
..., "count": ...}}`` with the device as JAX reported it to the children;
any failed check, in any phase, is a non-zero exit and no such line.

``--rehearsal`` is for whoever types it: the same phases at toy sizes on
the CPU (kernels in interpret mode, four virtual devices), to debug this
script without chip time. Its output says REHEARSAL on every phase and in
the result line; the program never chooses it. The times and rates
printed name their device and are not metrics.
"""
from __future__ import annotations

import argparse
import dataclasses
import importlib
import json
import math
import os
import subprocess
import sys
import threading
import time
from typing import Callable, Optional

ONE_CHIP_PHASES = ("kernels", "train", "serve")
FOUR_CHIP_PHASES = ("mp2dp2", "pp2mp2")
# run only when named in --phases: the default three fill their time limit
EXTRA_PHASES = ("serve_latent", "serve_hybrid", "serve_sparse",
                "serve_sparse_mla", "serve_ssm")
ALL_PHASES = ONE_CHIP_PHASES + FOUR_CHIP_PHASES + EXTRA_PHASES
# seconds per child, compilation included. The one-chip three sum to 1100,
# inside that run's 1200 s; cold on a v5e they took 72, 122 and 106 s (PR 21)
PHASE_TIMEOUT = {"kernels": 200, "train": 400, "serve": 500,
                 "mp2dp2": 400, "pp2mp2": 400, "serve_latent": 700,
                 "serve_hybrid": 900,    # two shapes since PR 35
                 "serve_sparse": 400, "serve_sparse_mla": 400,
                 "serve_ssm": 500}
RESULT_TAG = "CHIP_SMOKE_PHASE_RESULT "

# Tolerances, each with its reason. Every comparison is
#   max|kernel - ref| / max(1, max|ref|)
# with the kernel fed bf16 and the dense twin fed the SAME values in f32.
# bf16 keeps 8 significant bits (relative step 2^-8 = 3.9e-3).
# - attention forward: probabilities are rounded to bf16 before the PV
#   matmul and the output is rounded to bf16 once more; outputs are O(1)
#   convex combinations of |v| <= ~4.5, so a few times 3.9e-3 * 4.5.
TOL_ATTN = 3e-2
# - rms_norm: f32 math on both sides, the kernel rounds its output to
#   bf16 once: half a bf16 step of the largest output.
TOL_RMS = 1e-2
# - flash backward: ds and p are rounded to bf16 before their matmuls
#   and 1024 rows accumulate in f32, so the error random-walks to about
#   sqrt(N) * 3.9e-3 of a term against a sum of about sqrt(N) terms.
TOL_BWD = 5e-2
# - first training loss: ln(V) plus half the logit variance at init
#   (tied embedding std 0.02 over a unit-variance hidden state: 0.41 at
#   hidden 2048), computed through bf16 weights.
LOSS_BAND = 1.0
# - one chip against four: same seed, batch and math; only the reduction
#   order (mp splits each contraction, dp/pp split the batch mean) and
#   where bf16 rounding lands differ. A mean over 4096 tokens averages
#   that out; a missing or doubled collective moves it by tenths.
TOL_LOSS_4CHIP = 2e-2
# - serving: every token served for the first request must score within
#   this of the best logit of an independent full forward over the same
#   context. Random weights make near-ties common, so tokens are not
#   compared; logits have std ~1.3, a wrong token sits units below the best
TOL_LOGIT = 0.5


class Sizes:
    """The full-width sizes, and the toy ones ``--rehearsal`` swaps in."""

    def __init__(self, rehearsal: bool):
        self.rehearsal = rehearsal
        self.state_dtype = "bfloat16"
        kinds = dict(ffn_kinds=["dense", "experts", "experts"],
                     dtype="bfloat16")
        # the AFMoE block: every switch of HybridMoEConfig on
        switches = dict(
            kinds, attention_kinds=["window", "full", "window"],
            rotary_kinds=("window",), window_sink=False, value_scale=1.0,
            routed_scaling_factor=2.826, num_shared_experts=1,
            qk_norm=True, attention_gate=True, sandwich_norm=True,
            head_on_last_row=True)
        # the shortcut-connected block: MLAMoEConfig's switches for it
        shortcut = dict(
            shortcut_moe=True, use_qk_norm=False, router_score_func="softmax",
            router_bias=True, norm_topk_prob=False, mla_scale_q_lora=True,
            mla_scale_kv_lora=True, num_shared_experts=0,
            first_k_dense_replace=0, rope_scaling=None)
        if not rehearsal:
            # train: GPT-3 1.3B, the widths of benchmarks/configs/gpt3-1.3b
            self.gpt = dict(vocab_size=50304, hidden_size=2048, num_layers=24,
                            num_heads=16, max_position_embeddings=1024,
                            dtype="bfloat16")
            self.B, self.S, self.steps = 4, 1024, 4
            # serve: Llama-7B widths. Depth 8 of 32: 1.88B parameters =
            # 3.8 GB in bf16, and the default page pool (8 rows x 18 pages
            # + 1 on the power-of-two lattice: 256 pages x 16.8 MB) is 4.3
            # GB, so both + a 2048-token prefill's activations fit 16 GB
            self.llama = dict(hidden_size=4096, num_heads=32,
                              intermediate_size=11008, vocab_size=32000,
                              max_position_embeddings=2304, num_layers=8,
                              dtype="bfloat16")
            self.page, self.max_batch = 128, 8
            self.decode_chunk, self.prefill_chunk = 8, 256
            self.new_tokens = 32
            # one prompt per prefill bucket (64 .. 2048), then a dozen;
            # the first of the mix is checked against a forward
            self.warm_lens = (40, 100, 200, 400, 900, 1500)
            self.mix_lens = (128, 168, 191, 528, 611, 723, 725, 881, 1037,
                             1093, 1290, 1324)
            # serve_latent: published widths, the dense layer and one
            # expert layer holding 32 of the router's 128 experts (1.76B)
            self.latent = dict(
                vocab_size=65536, hidden_size=4096, num_layers=2,
                num_heads=64, kv_lora_rank=512, qk_nope_head_dim=128,
                qk_rope_head_dim=64, v_head_dim=128,
                intermediate_size=16384, moe_intermediate_size=2048,
                num_experts=128, num_local_experts=32,
                num_experts_per_tok=8, max_position_embeddings=1152,
                dtype="bfloat16")
            self.latent_batch = 32
            # serve_latent's second shape, the shortcut-connected double
            # layer at longcat-flash-omni's published widths: two layers
            # (four latent attentions off a query latent, four dense
            # parts, two expert branches holding 16 of 512 real experts
            # beside 256 identity ones, top-12 of a softmax), an eighth
            # of the vocabulary (2.69B parameters)
            self.shortcut = dict(
                vocab_size=16384, hidden_size=6144, num_layers=2,
                num_heads=64, q_lora_rank=1536, kv_lora_rank=512,
                qk_nope_head_dim=128, qk_rope_head_dim=64, v_head_dim=128,
                intermediate_size=12288, moe_intermediate_size=2048,
                num_experts=512, num_local_experts=16,
                num_experts_per_tok=12, zero_expert_num=256,
                routed_scaling_factor=6.0, rope_theta=1e7,
                rms_norm_eps=1e-5, max_position_embeddings=1152,
                dtype="bfloat16", **shortcut)
            # serve_hybrid: published widths (64 heads, keys 192 against
            # values 128, 8 and 4 KV heads, a window of 128 with a sink),
            # the dense layer and two expert layers holding 16 of the
            # router's 256 experts (1.44B parameters)
            self.hybrid = dict(
                kinds, vocab_size=19072, hidden_size=4096,
                attention_kinds=["full", "window", "window"],
                num_local_experts=16, max_position_embeddings=1152)
            self.hybrid_batch = 32
            # prompts ending just short of a ring's wrap (256), 40 rounds
            self.hybrid_lens, self.hybrid_new = (250, 240, 200, 100), 40
            # its second shape, the AFMoE block at published widths: 32
            # heads on 4 KV heads of 128, a window of 2048 (a ring of 17
            # pages), a dense layer and two expert layers holding 16 of
            # 128 experts beside a shared one, the whole vocabulary
            # (1.15B); the longest prompt ends just short of the ring's
            # wrap at position 2176
            self.afmoe = dict(
                switches, vocab_size=200192, hidden_size=2048, num_heads=32,
                num_kv_heads=4, window_num_kv_heads=4, qk_head_dim=128,
                v_head_dim=128, rotary_dim=128, window_rope_theta=10000.0,
                sliding_window=2048, intermediate_size=6144,
                moe_intermediate_size=1024, num_experts=128,
                num_local_experts=16, embedding_multiplier=2048 ** 0.5,
                max_position_embeddings=2304)
            self.afmoe_lens = (2150, 2040, 1000, 100)
            # the flash prefill kernel under a window at that block's
            # shapes and the benchmark cell's three longest buckets
            self.window_prefill = dict(heads=32, kv=4, window=2048,
                                       rows=(2048, 4096, 8192))
            # serve_sparse: sparse_moe_tiny (three sparse layers) at head
            # widths the chip tiles; the 256 best keys of contexts to 1,000
            self.sparse = dict(
                qk_head_dim=128, v_head_dim=128, rotary_dim=128,
                index_head_dim=64, index_topk=256, attention_block=128,
                max_position_embeddings=1152, dtype="bfloat16")
            self.sparse_lens, self.sparse_new = (900, 700, 300, 100), 40
            # the pool the engine would size itself (512 pages, 33 MB an
            # array) fits the chip's VMEM and XLA stages the whole V pool
            # there around the decode step's scatter: a copy no
            # deployment's pool (gigabytes) can get. 2,048 pages (134 MB
            # an array) keep the check on layout changes
            self.sparse_pool = 2048
            # serve_sparse_mla: sparse_mla_tiny (a dense and two expert
            # layers) at widths the chip tiles, serve_sparse's lengths.
            # Its pooled arrays are ONE head of 128 columns: at 2,048
            # pages (67 MB) XLA still stages one in VMEM (a `copy` into
            # S(1), seen on the chip and in an AOT compile, PR 41), so
            # 4,096 pages (134 MB)
            self.sparse_mla_pool = 4096
            # serve_ssm: one mixer a layer at nemotron-3-super's published
            # widths: two state-space layers (a float32 slot of 4 MiB a
            # row each), two latent relu2 expert layers holding 32 of the
            # router's 512 experts, one attention layer of 2 KV heads, a
            # quarter of the vocabulary (0.98B parameters)
            self.ssm = dict(
                vocab_size=32768, hidden_size=4096,
                mixer_kinds=["ssm", "experts", "attention", "ssm",
                             "experts"],
                num_heads=32, num_kv_heads=2, head_dim=128,
                ssm_num_heads=128, ssm_head_dim=64, ssm_groups=8,
                ssm_state_size=128, conv_kernel=4, chunk_size=128,
                num_experts=512, num_local_experts=32,
                num_experts_per_tok=22, routed_scaling_factor=5.0,
                moe_intermediate_size=2688, moe_latent_size=1024,
                shared_expert_intermediate_size=5376,
                max_position_embeddings=1152, dtype="bfloat16")
            self.sparse_mla = dict(
                hidden_size=256, num_heads=8, q_lora_rank=128,
                kv_lora_rank=128, qk_nope_head_dim=64, qk_rope_head_dim=64,
                v_head_dim=128, intermediate_size=512,
                moe_intermediate_size=128, index_head_dim=128,
                index_topk=256, attention_block=128,
                max_position_embeddings=1152, dtype="bfloat16")
        else:
            self.gpt = dict(vocab_size=1024, hidden_size=128, num_layers=2,
                            num_heads=4, max_position_embeddings=64,
                            dtype="bfloat16")
            self.B, self.S, self.steps = 4, 64, 4
            self.llama = dict(hidden_size=128, num_heads=4,
                              intermediate_size=256, vocab_size=512,
                              max_position_embeddings=288, num_layers=2,
                              dtype="bfloat16")
            self.page, self.max_batch = 16, 4
            self.decode_chunk, self.prefill_chunk = 4, 32
            self.new_tokens = 6
            self.warm_lens = (20, 100, 200)
            self.mix_lens = (64, 57, 127, 77, 113, 19)
            self.latent = dict(
                vocab_size=512, hidden_size=128, num_layers=2, num_heads=8,
                kv_lora_rank=128, qk_nope_head_dim=32, qk_rope_head_dim=16,
                v_head_dim=32, intermediate_size=256,
                moe_intermediate_size=64, num_experts=16,
                num_local_experts=4, num_experts_per_tok=4,
                max_position_embeddings=288, dtype="bfloat16")
            self.latent_batch = 4
            self.shortcut = dict(
                vocab_size=512, hidden_size=128, num_layers=2, num_heads=8,
                q_lora_rank=64, kv_lora_rank=128, qk_nope_head_dim=32,
                qk_rope_head_dim=16, v_head_dim=32, intermediate_size=256,
                moe_intermediate_size=64, num_experts=16,
                num_local_experts=4, num_experts_per_tok=4,
                zero_expert_num=8, routed_scaling_factor=6.0,
                max_position_embeddings=288, dtype="bfloat16", **shortcut)
            self.hybrid = dict(
                kinds, vocab_size=512, hidden_size=128,
                attention_kinds=["full", "window", "window"], num_heads=8,
                num_kv_heads=2, window_num_kv_heads=4, qk_head_dim=48,
                v_head_dim=32, rotary_dim=16, sliding_window=16,
                intermediate_size=256, moe_intermediate_size=64,
                num_experts=16, num_local_experts=4,
                num_experts_per_tok=4, max_position_embeddings=288,
                attention_block=32)
            self.hybrid_batch = 4
            self.hybrid_lens, self.hybrid_new = (30, 28, 20, 10), 12
            self.afmoe = dict(
                switches, vocab_size=512, hidden_size=128, num_heads=8,
                num_kv_heads=2, window_num_kv_heads=2, qk_head_dim=32,
                v_head_dim=32, rotary_dim=32, window_rope_theta=100.0,
                sliding_window=48, intermediate_size=256,
                moe_intermediate_size=64, num_experts=16,
                num_local_experts=4, num_experts_per_tok=4,
                embedding_multiplier=128 ** 0.5,
                max_position_embeddings=288, attention_block=32)
            self.afmoe_lens = (60, 50, 20, 10)
            self.window_prefill = dict(heads=8, kv=1, window=150,
                                       rows=(128, 384))
            self.sparse = dict(max_position_embeddings=288, dtype="bfloat16")
            self.sparse_mla = dict(self.sparse)
            self.sparse_lens, self.sparse_new = (100, 60, 30, 10), 12
            self.sparse_pool = self.sparse_mla_pool = None
            self.ssm = dict(
                vocab_size=512, hidden_size=128,
                mixer_kinds=["ssm", "experts", "attention", "ssm",
                             "experts"],
                num_heads=8, num_kv_heads=2, head_dim=32, ssm_num_heads=8,
                ssm_head_dim=32, ssm_groups=2, ssm_state_size=16,
                conv_kernel=4, chunk_size=16, num_experts=16,
                num_local_experts=4, num_experts_per_tok=4,
                routed_scaling_factor=5.0, moe_intermediate_size=64,
                moe_latent_size=64, shared_expert_intermediate_size=128,
                max_position_embeddings=288, attention_block=32,
                dtype="bfloat16")


# ---------------------------------------------------------------------------
# what every child does first and last
# ---------------------------------------------------------------------------
class Failed(Exception):
    """A check did not hold."""


def check(cond: bool, what: str) -> None:
    print(("  ok   " if cond else "  FAIL ") + what, flush=True)
    if not cond:
        raise Failed(what)


class JaxEvents:
    """Counts of XLA compile requests and persistent-cache traffic, from
    JAX's own monitoring events (nothing here changes what JAX does)."""

    COMPILE = "/jax/core/compile/backend_compile_duration"
    REQ = "/jax/compilation_cache/compile_requests_use_cache"
    HIT = "/jax/compilation_cache/cache_hits"
    WRITE = "/jax/compilation_cache/cache_misses"

    def __init__(self):
        import jax.monitoring as mon

        self.n = {self.COMPILE: 0, self.REQ: 0, self.HIT: 0, self.WRITE: 0}
        self.compile_secs = 0.0
        mon.register_event_listener(self._event)
        mon.register_event_duration_secs_listener(self._duration)

    def _event(self, name, **kw):
        if name in self.n:
            self.n[name] += 1

    def _duration(self, name, secs, **kw):
        if name == self.COMPILE:
            self.n[name] += 1
            self.compile_secs += secs

    @property
    def compiles(self) -> int:
        return self.n[self.COMPILE]

    def cache_line(self) -> dict:
        req, hit = self.n[self.REQ], self.n[self.HIT]
        return {"xla_compile_requests": self.compiles,
                "xla_compile_or_fetch_s": round(self.compile_secs, 1),
                "cache_requests": req, "cache_hits": hit,
                "cache_misses": req - hit,
                "cache_entries_written": self.n[self.WRITE]}


def start_child(rehearsal: bool, need_devices: int = 1):
    """Import JAX and the package, say what we run on, refuse a machine
    without a TPU. Returns (jax, device dict, JaxEvents)."""
    import jax

    import paddle_tpu  # noqa: F401  (first: places the compile cache)

    events = JaxEvents()
    devs = jax.devices()
    d = devs[0]
    try:
        import libtpu

        libtpu_version = getattr(libtpu, "__version__", "unknown")
    except ImportError:
        libtpu_version = "not installed"
    device = {"platform": d.platform, "kind": d.device_kind,
              "count": len(devs)}
    import jaxlib

    print(f"  device: platform={d.platform} device_kind={d.device_kind!r} "
          f"count={len(devs)} jax={jax.__version__} "
          f"jaxlib={jaxlib.__version__} libtpu={libtpu_version}",
          flush=True)
    cache_dir = jax.config.jax_compilation_cache_dir
    where = ("JAX_COMPILATION_CACHE_DIR, set from outside"
             if os.environ.get("JAX_COMPILATION_CACHE_DIR")
             else "the fixed path in the checkout" if cache_dir
             else "a process pinned to the CPU keeps none")
    print(f"  compile cache: {cache_dir} ({where})", flush=True)
    if rehearsal:
        print("  REHEARSAL: toy sizes on the CPU, asked for by the caller; "
              "proves nothing about the chip", flush=True)
    elif d.platform != "tpu":
        print(f"chip_smoke: no accelerator: jax found platform "
              f"{d.platform!r} ({d.device_kind}); this script only "
              "passes on a TPU", file=sys.stderr, flush=True)
        sys.exit(3)
    if not rehearsal and len(devs) < need_devices:
        print(f"chip_smoke: this phase needs {need_devices} TPU devices, "
              f"jax found {len(devs)}", file=sys.stderr, flush=True)
        sys.exit(3)
    return jax, device, events


def kernel_names(text: str) -> dict:
    """kernel_name -> count over the Mosaic custom calls of a lowered
    program (each pallas_call lowers to one ``tpu_custom_call``)."""
    import re

    names = re.findall(r'kernel_name = "([^"]+)"', text)
    out = {}
    for n in names:
        out[n] = out.get(n, 0) + 1
    if text.count("tpu_custom_call") < len(names):
        raise Failed("kernel_name attributes without tpu_custom_call")
    return out


def check_flash_calls(found: dict, eng, num_layers: int, pipe: bool) -> None:
    """The compiled train step's flash kernels, from ``kernel_names`` of
    its lowered text. Every layout holds the forward and both backward
    kernels. Where the tape differentiates the model (not ``pipe``) its
    backward reuses the forward's out and lse: one forward a layer in the
    text (the generic jax.vjp ran it twice), one explicit grad kernel a
    layer on the tape. The pipeline's stages run under no_grad() and scan
    over the stacked layers: one call for all of them in its text, the
    remat's beside it, and no op node on its tape."""
    check(all(found.get(n, 0) >= 1 for n in
              ("flash_attention_fwd", "flash_attention_dq",
               "flash_attention_dkv")),
          f"compiled step holds flash fwd and bwd as Mosaic calls {found}")
    if pipe:
        return
    check(found.get("flash_attention_fwd", 0) == num_layers,
          f"exactly {num_layers} flash_attention_fwd calls in the compiled "
          f"step, one a layer (got {found.get('flash_attention_fwd', 0)})")
    check(eng.backward_nodes[0] == num_layers,
          f"the tape took {num_layers} explicit grad kernels "
          f"(explicit, generic = {eng.backward_nodes})")


def finish_child(phase: str, device: dict, events: JaxEvents,
                 extra: dict) -> None:
    out = {"phase": phase, "ok": True, "device": device}
    out.update(events.cache_line())
    out.update(extra)
    print(RESULT_TAG + json.dumps(out), flush=True)


# ---------------------------------------------------------------------------
# phase: kernels
# ---------------------------------------------------------------------------
def kernel_cases(sz: Sizes):
    """(name, kernel names, build, tolerance) for every Pallas kernel at
    the shapes the train and serve phases use. ``build(normal)`` returns
    (kernel_fn, dense_fn, args): ``normal(shape)`` makes each big bf16
    operand, so the smoke passes seeded random arrays and
    tests/test_chip_bringup.py passes shapes only and lowers the same
    table. dense_fn takes the same args cast to f32."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from paddle_tpu.core.bucketing import bucket

    g, L = sz.gpt, sz.llama
    Hd = g["hidden_size"] // g["num_heads"]
    Ld = L["hidden_size"] // L["num_heads"]
    H, hid = L["num_heads"], L["hidden_size"]
    page, B = sz.page, sz.max_batch
    M = L["max_position_embeddings"]
    npages = -(-M // page)
    P = B * npages + 1
    interpret = sz.rehearsal
    r = np.random.RandomState(1)      # block tables and lengths only
    cases = []

    def flash(normal):
        from paddle_tpu.ops.attention import _sdpa_raw
        from paddle_tpu.ops.pallas.flash_attention import \
            flash_attention_fwd

        shape = (sz.B, sz.S, g["num_heads"], Hd)
        return (lambda q, k, v, w: flash_attention_fwd(
                    q, k, v, True, None, interpret),
                lambda q, k, v, w: _sdpa_raw(
                    q, k, v, attn_mask=None, dropout_p=0.0,
                    is_causal=True),
                tuple(normal(shape) for _ in range(4)))

    cases.append(("flash_attention fwd", ("flash_attention_fwd",), flash,
                  TOL_ATTN))

    def flash_bwd(normal):
        kern, dense, args = flash(normal)

        def grads(f):
            return lambda q, k, v, w: jnp.stack(jax.grad(
                lambda q, k, v: (f(q, k, v, w).astype(jnp.float32)
                                 * w.astype(jnp.float32)).sum(),
                argnums=(0, 1, 2))(q, k, v))

        return grads(kern), grads(dense), args

    cases.append(("flash_attention bwd (dq, dk, dv)",
                  ("flash_attention_fwd", "flash_attention_dq",
                   "flash_attention_dkv"), flash_bwd, TOL_BWD))

    # rms_norm at the row counts serving feeds it: a decode step
    # [B, 1], the widest prefill bucket [1, Sb], a unified chunk [B, Sc]
    buckets = sorted({min(bucket(n), M) for n in sz.warm_lens})
    for shp in ((B, 1, hid), (1, buckets[-1], hid),
                (B, sz.prefill_chunk, hid)):
        def rms(normal, shp=shp):
            from paddle_tpu.ops.pallas.rms_norm import (rms_norm_dense,
                                                        rms_norm_fused)

            return (lambda x, w: rms_norm_fused(x, w, 1e-5, interpret),
                    lambda x, w: rms_norm_dense(x, w, 1e-5),
                    (normal(shp), normal(shp[-1:])))

        cases.append((f"rms_norm_fused {shp}", ("rms_norm_fused",), rms,
                      TOL_RMS))

    def ragged_lens():
        return r.randint(1, M - 1, (B,)).astype("int32")

    def decode(normal):
        from paddle_tpu.models.llama import _cache_attention_dense
        from paddle_tpu.ops.pallas.decode_attention import \
            decode_attention

        off = jnp.asarray(ragged_lens())
        return (lambda q, kc, vc: decode_attention(
                    q, kc, vc, off, interpret=interpret),
                lambda q, kc, vc: _cache_attention_dense(
                    q, kc, vc, off, 1),
                (normal((B, 1, H, Ld)), normal((B, H, M, Ld)),
                 normal((B, H, M, Ld))))

    cases.append((f"decode_attention Sq=1 B={B} M={M}",
                  ("decode_attention",), decode, TOL_ATTN))

    def table(rows):
        return jnp.asarray(r.permutation(P - 1)[:rows * npages].reshape(
            rows, npages), jnp.int32)

    pool = (P, H, page, Ld)

    def paged(rows, Sq, lens):
        def build(normal):
            from paddle_tpu.ops.pallas.decode_attention import (
                paged_attention_dense, paged_decode_attention)

            tbl = table(rows)
            return (lambda q, kp, vp: paged_decode_attention(
                        q, kp, vp, tbl, lens, interpret=interpret),
                    lambda q, kp, vp: paged_attention_dense(
                        q, kp, vp, tbl, lens),
                    (normal((rows, Sq, H, Ld)), normal(pool),
                     normal(pool)))

        return build

    # a batch as the engine holds it: ragged rows, one that ends on a
    # page's last slot, and free slots (length 0) between them. The
    # kernel walks each row's own pages and one page of a free slot
    lens = ragged_lens()
    lens[0] = 2 * page - 1
    lens[1::3] = 0
    cases.append((f"paged_decode_attention Sq=1 B={B} page={page} "
                  f"lengths={lens.tolist()}",
                  ("paged_decode_attention",),
                  paged(B, 1, jnp.asarray(lens)), TOL_ATTN))
    # the bucketed prefill runs the SAME paged kernel at Sq = the bucket,
    # up to the rows over which a prompt attends to the K/V its layer has
    # just written instead (decode_attention.paged_attention_form)
    from paddle_tpu.ops.pallas.decode_attention import FLASH_OVER_ROWS

    for Sb in [b for b in buckets if b <= FLASH_OVER_ROWS]:
        cases.append((f"paged_decode_attention prefill Sq={Sb}",
                      ("paged_decode_attention",),
                      paged(1, Sb, jnp.zeros((1,), jnp.int32)), TOL_ATTN))

    def fresh(Sb, KV):
        def build(normal):
            from paddle_tpu.ops.attention import _sdpa_raw
            from paddle_tpu.ops.pallas.flash_attention import \
                flash_attention_gqa

            wide = lambda a: jnp.repeat(a, H // KV, axis=2)
            return (lambda q, k, v: flash_attention_gqa(
                        q, k, v, interpret=interpret),
                    lambda q, k, v: _sdpa_raw(
                        q, wide(k), wide(v), attn_mask=None,
                        dropout_p=0.0, is_causal=True),
                    (normal((1, Sb, H, Ld)), normal((1, Sb, KV, Ld)),
                     normal((1, Sb, KV, Ld))))

        return build

    # every query head its own KV head (this phase's model) and four to
    # one (the benchmark's serving cells)
    for Sb in [b for b in buckets if b > FLASH_OVER_ROWS]:
        for KV in (H, H // 4):
            cases.append((f"flash_attention_gqa prefill Sq={Sb} KV={KV}",
                          ("flash_attention_fwd_gqa",), fresh(Sb, KV),
                          TOL_ATTN))

    def windowed(Sb, heads, KV, window):
        def build(normal):
            from paddle_tpu.ops.blockwise_attention import \
                blockwise_causal_attention
            from paddle_tpu.ops.pallas.flash_attention import \
                flash_attention_gqa

            scale = 128 ** -0.5
            return (lambda q, k, v: flash_attention_gqa(
                        q, k, v, scale=scale, window=window,
                        interpret=interpret),
                    lambda q, k, v: blockwise_causal_attention(
                        q, k, v, scale, window),
                    (normal((1, Sb, heads, 128)), normal((1, Sb, KV, 128)),
                     normal((1, Sb, KV, 128))))

        return build

    # a hybrid model's prefill (models/hybrid_moe.py): eight query heads
    # under one K/V tile, a window layer's band at each bucket and a full
    # layer's triangle at the longest, against the lax blocks they replace
    wp = sz.window_prefill
    for Sb, window in [(Sb, wp["window"]) for Sb in wp["rows"]] + [
            (wp["rows"][-1], None)]:
        cases.append((f"flash_attention_gqa prefill Sq={Sb} "
                      f"{wp['heads']} heads on {wp['kv']} window={window}",
                      ("flash_attention_fwd_gqa",),
                      windowed(Sb, wp["heads"], wp["kv"], window),
                      TOL_ATTN))

    def ragged(normal):
        from paddle_tpu.ops.pallas.ragged_paged_attention import (
            ragged_paged_attention, ragged_paged_attention_dense)

        Sc = sz.prefill_chunk
        # a full chunk mid-prompt, a first chunk, a partial last chunk,
        # a dead row, and decode rows for the rest
        starts = ragged_lens()
        nv = [1] * B
        starts[0], nv[0] = 3 * Sc, Sc
        starts[1], nv[1] = 0, Sc
        starts[2], nv[2] = Sc, max(Sc // 3, 1)
        starts[3], nv[3] = 0, 0
        starts = jnp.asarray(starts)
        nv = jnp.asarray(nv, jnp.int32)
        tbl = table(B)
        return (lambda q, kp, vp: ragged_paged_attention(
                    q, kp, vp, tbl, starts, nv, interpret=interpret),
                lambda q, kp, vp: ragged_paged_attention_dense(
                    q, kp, vp, tbl, starts, nv),
                (normal((B, Sc, H, Ld)), normal(pool), normal(pool)))

    cases.append((f"ragged_paged_attention Sc={sz.prefill_chunk} B={B}",
                  ("ragged_paged_attention",), ragged, TOL_ATTN))

    def latent(normal):
        from paddle_tpu.models.mla_moe import MLAMoEConfig
        from paddle_tpu.ops.pallas.mla_attention import (
            mla_paged_attention_dense, mla_paged_decode_attention)

        cfg = MLAMoEConfig(**sz.latent)
        Hl, dc, dr = cfg.num_heads, cfg.kv_lora_rank, cfg.rope_cache_width
        tbl, lens = table(B), jnp.asarray(ragged_lens())
        scale = cfg.softmax_scale
        return (lambda ql, qr, cp, rp: mla_paged_decode_attention(
                    ql, qr, cp, rp, tbl, lens, scale, interpret=interpret),
                lambda ql, qr, cp, rp: mla_paged_attention_dense(
                    ql[:, None], qr[:, None], cp, rp, tbl, lens,
                    scale)[:, 0],
                (normal((B, Hl, dc)), normal((B, Hl, dr)),
                 normal((P, 1, page, dc)), normal((P, 1, page, dr))))

    cases.append((f"mla_paged_decode_attention B={B} page={page}",
                  ("mla_paged_decode_attention",), latent, TOL_ATTN))
    return cases


def phase_kernels(sz: Sizes) -> None:
    jax, device, events = start_child(sz.rehearsal)
    import jax.numpy as jnp
    import numpy as np

    results = {}
    rng = np.random.default_rng(0)

    def normal(shape):
        return jnp.asarray(rng.standard_normal(shape, dtype=np.float32),
                           jnp.bfloat16)

    for name, expect, build, tol in kernel_cases(sz):
        kern, dense, args = build(normal)
        lowered = jax.jit(kern).lower(*args)
        found = kernel_names(lowered.as_text())
        check(sz.rehearsal or all(found.get(n, 0) >= 1 for n in expect),
              f"{name}: lowered to Mosaic tpu_custom_call {found}")
        t0 = time.perf_counter()
        out = jax.block_until_ready(lowered.compile()(*args))
        t_first = time.perf_counter() - t0
        ref = jax.block_until_ready(jax.jit(dense)(
            *[a.astype(jnp.float32) for a in args]))
        out32 = np.asarray(out.astype(jnp.float32))
        ref32 = np.asarray(ref.astype(jnp.float32))
        check(out32.shape == ref32.shape and np.isfinite(out32).all(),
              f"{name}: finite, shape {out32.shape}")
        err = float(np.abs(out32 - ref32).max()
                    / max(1.0, np.abs(ref32).max()))
        check(err <= tol, f"{name}: err {err:.2e} <= {tol:.0e} of its "
                          f"dense twin (compile + run {t_first:.1f}s)")
        results[name] = round(err, 5)
    finish_child("kernels", device, events, {"errors": results})


# ---------------------------------------------------------------------------
# phase: train (and the two four-chip layouts of the same step)
# ---------------------------------------------------------------------------
LAYOUTS = {
    "train": {"dp_degree": 1, "mp_degree": 1},
    "mp2dp2": {"dp_degree": 2, "mp_degree": 2},
    "pp2mp2": {"pp_degree": 2, "mp_degree": 2},
}


def phase_train(sz: Sizes, layout: str) -> None:
    degrees = LAYOUTS[layout]
    n_dev = 1
    for v in degrees.values():
        n_dev *= v
    jax, device, events = start_child(sz.rehearsal, need_devices=n_dev)
    import numpy as np

    import paddle_tpu as paddle
    from paddle_tpu.distributed import fleet
    from paddle_tpu.distributed.engine import ParallelEngine, param_spec
    from paddle_tpu.models import (GPTConfig, GPTForCausalLM,
                                   GPTForCausalLMPipe,
                                   GPTPretrainingCriterion)

    cfg = GPTConfig(**sz.gpt)
    pipe = degrees.get("pp_degree", 1) > 1
    strategy = fleet.DistributedStrategy()
    strategy.hybrid_configs = dict(degrees)
    if pipe:
        strategy.pipeline_configs = {"accumulate_steps": 2,
                                     "micro_batch_size": sz.B // 2}
    hcg = fleet.init(is_collective=True, strategy=strategy)
    print(f"  layout {layout}: {degrees}, mesh {dict(hcg.mesh.shape)} on "
          f"{[d.id for d in hcg.mesh.devices.flat]}", flush=True)

    t_setup = time.perf_counter()
    paddle.seed(0)
    model = GPTForCausalLMPipe(cfg) if pipe else GPTForCausalLM(cfg)
    opt = paddle.optimizer.AdamW(learning_rate=1e-4,
                                 parameters=model.parameters(),
                                 state_dtype=sz.state_dtype)
    ids = np.random.RandomState(0).randint(0, cfg.vocab_size,
                                           (sz.B, sz.S + 1))
    x, y = paddle.to_tensor(ids[:, :-1]), paddle.to_tensor(ids[:, 1:])
    if pipe:
        dist_model = fleet.distributed_model(model)
        dopt = fleet.distributed_optimizer(opt)

        def step():
            return dist_model.train_batch([x, y], dopt)
    else:
        crit = GPTPretrainingCriterion(cfg)
        eng = ParallelEngine(model, opt, hcg.mesh)
        train_step = eng.train_step(lambda m, b: crit(m(b["x"]), b["y"]))

        def step():
            return train_step({"x": x, "y": y})

    losses = [float(step())]          # warm-up: trace + compile + run
    t_setup = time.perf_counter() - t_setup
    compiles_after_warmup = events.compiles
    t0 = time.perf_counter()
    for _ in range(sz.steps):
        losses.append(float(step()))  # float() waits for the device
    t_run = time.perf_counter() - t0
    if pipe:
        eng = dist_model.engine
    step_s = t_run / sz.steps
    print(f"  set-up (build + compile + first step) {t_setup:.1f}s, then "
          f"{sz.steps} steps in {t_run:.2f}s: {step_s * 1e3:.0f} ms/step, "
          f"{sz.B * sz.S / step_s:.0f} tokens/s on {n_dev} x "
          f"{device['kind']} (not a metric)", flush=True)
    print(f"  losses {[round(v, 4) for v in losses]}", flush=True)

    lnv = math.log(cfg.vocab_size)
    check(all(math.isfinite(v) for v in losses), "every loss finite")
    check(abs(losses[0] - lnv) < LOSS_BAND,
          f"first loss {losses[0]:.3f} within {LOSS_BAND} of "
          f"ln({cfg.vocab_size})={lnv:.2f}")
    check(losses[-1] < losses[0],
          f"loss fell: {losses[0]:.3f} -> {losses[-1]:.3f}")
    check(eng.stats.compiles == 1,
          f"eng.stats.compiles == 1 (got {eng.stats.compiles})")
    check(events.compiles == compiles_after_warmup,
          f"no XLA compile after the warm-up step "
          f"({events.compiles - compiles_after_warmup} seen)")
    # the model state the step carries, per device, as the engine
    # measures it: params plus two moments in the dtype that was asked for
    acct = eng.state_accounting().components
    print(f"  state per device: params {acct['params'] / 2**30:.2f} GiB, "
          f"optimizer {acct['optimizer_state'] / 2**30:.2f} GiB, masters "
          f"{acct.get('master_weights', 0) / 2**30:.2f} GiB", flush=True)
    ratio = (np.dtype(sz.state_dtype).itemsize
             / np.dtype(cfg.dtype).itemsize)
    check(acct["optimizer_state"] <= 2 * ratio * acct["params"] * 1.01,
          f"optimizer moments are stored in {sz.state_dtype} as asked "
          f"(two moments, {ratio:g}x the {cfg.dtype} parameter bytes each)")
    if not sz.rehearsal:
        check_flash_calls(kernel_names(eng.lowered_text()), eng,
                          cfg.num_layers, pipe)

    # every device of the mesh holds memory, and each parameter's shards
    # have the shape its PartitionSpec says
    from jax.sharding import NamedSharding

    mesh_devs = list(hcg.mesh.devices.flat)
    holders = set()
    bad = []
    for p in eng.params:
        want = NamedSharding(hcg.mesh, param_spec(p)).shard_shape(
            tuple(p._value.shape))
        for sh in p._value.addressable_shards:
            holders.add(sh.device)
            if tuple(sh.data.shape) != tuple(want):
                bad.append((getattr(p, "name", "?"),
                            tuple(sh.data.shape), tuple(want)))
    check(not bad, f"every parameter shard has its param_spec shape "
                   f"({len(eng.params)} params; first bad: {bad[:1]})")
    check(holders == set(mesh_devs),
          f"parameter shards live on all {len(mesh_devs)} mesh devices "
          f"(found {sorted(d.id for d in holders)})")
    mem = {}
    for d in mesh_devs:
        stats = d.memory_stats() or {}
        mem[d.id] = stats.get("bytes_in_use", 0)
    print("  bytes_in_use per device: "
          + ", ".join(f"{i}: {b / 2**30:.2f} GiB" for i, b in mem.items()),
          flush=True)
    if not sz.rehearsal:       # the CPU backend reports no memory stats
        check(all(b > 0 for b in mem.values()),
              "every mesh device reports bytes_in_use > 0")
    finish_child(layout, device, events, {
        "losses": losses, "setup_s": round(t_setup, 1),
        "step_ms": round(step_s * 1e3, 1), "devices_used": n_dev})


# ---------------------------------------------------------------------------
# phases: serve, serve_latent, serve_hybrid, serve_sparse, serve_sparse_mla
# one body (serve_case); SERVE_CASES holds what differs between them
# ---------------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class ServeCase:
    """What one served configuration hands ``serve_case``: a new one is one
    more entry of ``SERVE_CASES``, a check all share goes into the body."""

    label: str
    # (module of paddle_tpu.models, config maker, model class, the Sizes
    # attribute with the maker's arguments)
    model: tuple
    # sz -> {"warm": lengths, "mix": lengths, "new": tokens a request,
    # "batch": max_batch}; no warm-up, no word on compiles
    traffic: Callable
    # sz -> ServingEngine keyword arguments, one engine a member, each
    # over the same predictor and the same traffic, in turn
    engines: Callable
    # cfg -> (heads, width) of each array a layer pools; None: no claim
    pools: Optional[Callable]
    decode_kernels: Callable    # cfg -> {Mosaic kernel: calls in ('decode',)}
    # arrays the decode step writes in place: a layer (int), or cfg -> all
    # of them where the layers differ
    donated: object
    # (sz, cfg): the decode kernels alone against their dense twins at
    # this model's shapes, before the model takes the memory
    kernels: Optional[Callable] = None
    # (ctx, ids) -> float32 logits [len(ids), vocab], one forward with no
    # cache; ``Predictor.run`` (the flash-attention path) where None
    forward: Optional[Callable] = None
    # (ctx): this family's own checks, after the shared ones; ctx holds
    # sz cfg model eng warm mix new full, and warm_first after a warm-up
    extra: Optional[Callable] = None

    def build(self, sz: Sizes):
        """(config, model class); the module imported when a child asks."""
        module, make, model, sizes = self.model
        mod = importlib.import_module("paddle_tpu.models." + module)
        return getattr(mod, make)(**getattr(sz, sizes)), getattr(mod, model)


def serve_case(sz: Sizes, case: ServeCase, events: JaxEvents,
               device: dict) -> None:
    """One configuration through ``Config.enable_paged_kv`` ->
    ``create_predictor`` -> ``ServingEngine``: the checks every served
    configuration shares, then the case's own."""
    import gc
    import types

    import jax
    import numpy as np

    import paddle_tpu as paddle
    from paddle_tpu.incubate.distributed.models.moe.moe_layer import (
        routed_form)
    from paddle_tpu.inference import (Config, ServingEngine,
                                      create_predictor)

    cfg, model_cls = case.build(sz)
    traffic = case.traffic(sz)
    new, batch = traffic["new"], traffic["batch"]
    if case.kernels:
        case.kernels(sz, cfg)
    t0 = time.perf_counter()
    paddle.set_default_dtype(cfg.dtype)
    paddle.seed(0)
    model = model_cls(cfg)
    n_par = sum(int(np.prod(p.shape)) for p in model.parameters())
    pred = create_predictor(
        Config().set_model(model).enable_paged_kv(page_size=sz.page))
    r = np.random.RandomState(0)
    warm, mix = ([r.randint(0, cfg.vocab_size, (int(n),)).astype("int32")
                  for n in traffic[k]] for k in ("warm", "mix"))
    print(f"  {model_cls.__name__}: hidden {cfg.hidden_size}, vocabulary "
          f"{cfg.vocab_size}, {cfg.num_layers} layers, {n_par / 1e9:.2f}B "
          f"params, built in {time.perf_counter() - t0:.1f}s; pages of "
          f"{sz.page}; prompts of {list(traffic['warm'])} tokens to warm up, "
          f"then {list(traffic['mix'])}, {new} new tokens each", flush=True)
    for kw in case.engines(sz):
        print(f"  -- ServingEngine max_batch={batch} {kw}", flush=True)
        eng = ServingEngine(pred, max_batch=batch, **kw)
        ctx = types.SimpleNamespace(sz=sz, cfg=cfg, model=model, eng=eng,
                                    warm=warm, mix=mix, new=new)
        # pooled tuples: one a layer, two a layer of two attentions
        tuples = cfg.num_layers * getattr(cfg, "attention_sublayers", 1)
        if case.pools:
            want = [(h, sz.page, w) for h, w in case.pools(cfg)]
            check(eng.cache.arrays == [len(want)] * tuples
                  and all([a.shape[1:] for a in layer] == want
                          for layer in eng.pools),
                  f"a layer pools {len(want)} arrays of {eng.P} pages: "
                  f"{' | '.join('x'.join(map(str, w)) for w in want)}")
        t0 = time.perf_counter()
        if warm:
            wrids = [eng.submit(p, max_new_tokens=new) for p in warm]
            done = eng.run()
            check(len(done) == len(warm),
                  f"warm-up mix drained ({len(done)})")
            ctx.warm_first = [int(done[rid].new_tokens[0])
                              for rid in wrids]
            compiles0, xla0 = eng.stats.compiles, events.compiles
        t_setup, t0 = time.perf_counter() - t0, time.perf_counter()
        rids = [eng.submit(p, max_new_tokens=new) for p in mix]
        done = eng.run()
        t_run = time.perf_counter() - t0
        outs = [np.asarray(done[rid].new_tokens) for rid in rids
                if rid in done]
        print(f"    compile + warm-up mix {t_setup:.1f}s; {len(mix)} requests "
              f"in {t_run:.2f}s, {sum(len(o) for o in outs) / t_run:.0f} "
              f"generated tokens/s on {device['kind']} (not a metric); pool "
              f"{eng.P} pages", flush=True)
        check(len(outs) == len(rids)
              and not any(done[rid].shed for rid in rids)
              and all(len(o) == new for o in outs)
              and all(((o >= 0) & (o < cfg.vocab_size)).all() for o in outs),
              f"every request returned {new} tokens of the vocabulary")
        if warm:
            check(eng.stats.compiles == compiles0 and events.compiles == xla0,
                  f"no compile after warm-up (eng.stats.compiles "
                  f"{compiles0}, {events.compiles - xla0} XLA compiles seen)")
        # the reference: one forward with no cache over the first request's
        # prompt and the tokens served for it (row t scores the token after)
        seq = np.concatenate([mix[0], outs[0][:-1]])
        full = ctx.full = case.forward(ctx, seq) if case.forward else \
            pred.run([seq[None, :]])[0][0].astype("float32")
        check(full.shape == (len(seq), cfg.vocab_size)
              and np.isfinite(full).all(),
              f"reference forward: finite logits of shape {full.shape}")
        at = full[len(mix[0]) - 1:]
        gaps = at.max(-1) - at[np.arange(new), outs[0]]
        check(float(gaps[0]) <= TOL_LOGIT,
              f"first token {int(outs[0][0])} scores within {TOL_LOGIT} "
              f"of the reference forward's best logit (gap "
              f"{float(gaps[0]):.3f})")
        check(float(gaps.max()) <= TOL_LOGIT,
              f"every served token of the first request scores within "
              f"{TOL_LOGIT} of the reference forward's best (widest "
              f"{float(gaps.max()):.3f})")
        sites = eng.program_sites()
        st = eng.moe_stats()
        if st is not None:              # a model with routed experts
            check(st["dropped"] == 0 and st["tokens"].max() > 0,
                  f"expert layers dropped {st['dropped']} routed pairs of "
                  f"{int(st['tokens'].sum()) * cfg.num_experts_per_tok}")
            # their products: batched over the held experts in the decode
            # program (no grouped matmul in it), sorted and grouped in a
            # prefill bucket over 128 tokens (the token count alone decides)
            buckets = sorted(s[1] for s in sites if s[0] == "prefill")
            forms = {"decode": routed_form(eng.B), "prefill": "+".join(
                sorted({routed_form(Sb) for Sb in buckets}))}
            check(st["forms"] == forms and forms["decode"] == "batched"
                  and (sz.rehearsal or "sorted" in forms["prefill"]),
                  f"expert products' forms {st['forms']} (decode at "
                  f"{eng.B} rows, prefill buckets {buckets})")
            # a sorted bucket's grouped product is the one the layers
            # recorded: ours ("grouped_matmul") at the published widths,
            # XLA's ("ragged_dot") where the kernel's gate refuses them
            grouped = {site: [n for n in ("ragged_dot", "grouped_matmul")
                              if n in eng.lowered_text(site)]
                       for site in [("decode",), ("prefill", buckets[-1])]}
            said = st["grouped"]
            check(sz.rehearsal or (
                said == {"decode": None, "prefill": "pallas"}
                and list(grouped.values()) == [[], ["grouped_matmul"]]),
                f"grouped products in the lowered programs: {grouped}, "
                f"traced as {said}")
        found = kernel_names(eng.lowered_text(("decode",)))
        asked = case.decode_kernels(cfg)
        check(sz.rehearsal or all(found.get(name, 0) == calls
                                  for name, calls in asked.items()),
              f"program ('decode',) holds Mosaic calls {found} "
              f"(asked: {asked})")
        # the page pools are written in place: no copy of a whole pool
        # (a layout change around the write) in the compiled decode or
        # unified program, nor in the largest prefill program
        shapes = sorted({a.shape for layer in eng.pools for a in layer})
        for site in [s for s in sites if s[0] in ("decode", "unified")] \
                + sorted(s for s in sites if s[0] == "prefill")[-1:]:
            text = eng.compiled_text(site)
            n = sum(eng.pool_copies(text, s) for s in shapes)
            check(sz.rehearsal or n == 0,
                  f"compiled program {site}: {n} copies of a whole pool "
                  f"{' | '.join('x'.join(map(str, s)) for s in shapes)}")
            if site == ("decode",):
                # it writes in place what the cache lent it (pools, an
                # expert model's counters), never its round array (tables,
                # pos, token, mask), which every layer reads
                donated = eng.donated_params(text)
                lent = case.donated(cfg) if callable(case.donated) \
                    else case.donated * tuples
                check(len(donated) == lent
                      and all(name.startswith("state") for name in donated),
                      f"compiled program ('decode',) donates the "
                      f"{len(donated)} arrays it was lent and not its "
                      f"round array: {donated}")
        # one decode round in flight: rounds were launched before the one
        # ahead was read (all but the first of a run() in the default mode;
        # chunked mode overlaps only pure-decode rounds), none left unread
        ov = eng.overlap_stats()
        check(ov["in_flight"] == 0 and ov["rounds"] > 0
              and (eng.chunked or ov["overlapped"] * 2 >= ov["rounds"]),
              f"decode rounds launched with the one before unretired: "
              f"{ov['overlapped']} of {ov['rounds']}, {ov['in_flight']} in "
              f"flight after run()")
        if case.extra:
            case.extra(ctx)
        eng.release_pools()     # before the next engine builds its own
        del eng, ctx
    for p in model.parameters():        # the next case needs the memory
        p._value = None
    del pred, model
    gc.collect()
    jax.clear_caches()


def phase_serve(sz: Sizes, phase: str) -> None:
    _, device, events = start_child(sz.rehearsal)
    for case in SERVE_CASES[phase]:
        print(f"  -- {case.label} --", flush=True)
        serve_case(sz, case, events, device)
    finish_child(phase, device, events, {})


def check_twin(what: str, got, want) -> None:
    import jax.numpy as jnp

    err = float(jnp.abs(got.astype(jnp.float32)
                        - want.astype(jnp.float32)).max())
    check(err <= TOL_ATTN, f"{what} within {TOL_ATTN} of its dense twin "
                           f"(max err {err:.2e})")


def paged_batch(sz: Sizes, r, ncols: int):
    """``hybrid_batch`` rows of ``ncols`` pages scattered over a pool:
    (pages in the pool, block table, a maker of random bf16 arrays)."""
    import jax.numpy as jnp

    B = sz.hybrid_batch
    P = B * ncols + 1
    tbl = r.permutation(P - 1)[:B * ncols].reshape(B, ncols).astype("int32")
    return P, tbl, lambda *shape: jnp.asarray(r.standard_normal(shape),
                                              jnp.bfloat16)


def paged_twin(sz: Sizes, cfg, r, what: str, KV: int, ncols: int, lens,
               **kw) -> None:
    """``paged_decode_attention`` alone against its dense twin over such a
    batch at the decode program's shapes, where its gate admits them."""
    from paddle_tpu.ops.pallas import decode_attention as da

    P, tbl, rnd = paged_batch(sz, r, ncols)
    kp = rnd(P, KV, sz.page, cfg.k_cache_width)
    vp = rnd(P, KV, sz.page, cfg.v_head_dim)
    q = rnd(sz.hybrid_batch, 1, cfg.num_heads, cfg.k_cache_width)
    kw["scale"] = cfg.softmax_scale
    if sz.rehearsal or da.paged_supported(q.shape, kp.shape, vp.shape):
        check_twin(f"{what} ({cfg.num_heads // KV} query heads a KV head, "
                   f"{ncols} pages a row)",
                   da.paged_decode_attention(q, kp, vp, tbl, lens,
                                             interpret=sz.rehearsal, **kw),
                   da.paged_attention_dense(q, kp, vp, tbl, lens, **kw))


# -- serve: Llama-7B widths, both engine modes ------------------------------
def llama_extra(ctx) -> None:
    """The prefill and unified programs' attention forms and kernels; the
    long prompts' first tokens against a forward with every kernel off."""
    import paddle_tpu as paddle
    from paddle_tpu.core.bucketing import bucket
    from paddle_tpu.inference import Config, create_predictor
    from paddle_tpu.ops.pallas import decode_attention as da

    sz, cfg, eng = ctx.sz, ctx.cfg, ctx.eng

    warm_buckets = {bucket(len(p)) for p in ctx.warm}
    # the prompts whose first tokens are checked against the dense path:
    # the two largest buckets (1,024 and 2,048 rows on the chip)
    long_buckets = set(sorted(warm_buckets)[-2:])
    # a prompt over FLASH_OVER_ROWS tokens attends to its layer's fresh
    # K/V as flash attention, a shorter one through the pool (the
    # rehearsal's programs are all "dense")
    forms = eng.prefill_attention_forms()
    if not eng.chunked:
        check(forms == {b: "dense" if sz.rehearsal
                        else da.paged_attention_form(
                            (1, b, cfg.num_heads, cfg.head_dim),
                            (1, cfg.num_kv_heads, sz.page, cfg.head_dim),
                            (1, b, cfg.num_kv_heads, cfg.head_dim), 0)
                        for b in warm_buckets | set(forms)}
              and (sz.rehearsal or
                   {"flash", "paged"} <= set(forms.values())
                   and all(forms[b] == "flash" for b in long_buckets)),
              f"prefill programs attend as {forms}")
    attend = {"flash": "flash_attention_fwd_gqa",
              "paged": "paged_decode_attention"}
    sites = eng.program_sites()
    for site in [s for s in sites if s[0] in ("prefill", "unified")]:
        found = kernel_names(eng.lowered_text(site))
        names = ("ragged_paged_attention", "rms_norm_fused")
        if site[0] == "prefill":
            # each kernel is jitted on its own: the text holds it
            # once however many layers call it
            mine = attend.get(forms[site[1]])
            names = (mine, "rms_norm_fused")
            check(sz.rehearsal or not any(
                found.get(n, 0) for n in attend.values() if n != mine),
                f"program {site} holds no other attention kernel")
        check(sz.rehearsal or all(found.get(n, 0) >= 1 for n in names),
              f"program {site} holds Mosaic calls {found}")
    kinds = {s[0] for s in sites}
    check(("unified" in kinds) if eng.chunked else
          ({"prefill", "decode"} <= kinds),
          f"the programs that ran: {sorted(map(str, sites))}")
    # the long prompts' first tokens against the dense path: a forward with
    # every kernel off (plain XLA attention over the whole prompt, no cache)
    paddle.set_flags({"use_pallas_kernels": False})
    dense = create_predictor(Config().set_model(ctx.model))
    gaps = {}
    for p, tok in zip(ctx.warm, ctx.warm_first):
        if bucket(len(p)) in long_buckets:
            row = dense.run([p[None, :]])[0][0, -1].astype("float32")
            gaps[len(p)] = (float(row.max() - row[tok]),
                            tok == int(row.argmax()))
    paddle.set_flags({"use_pallas_kernels": True})
    check(len(gaps) == len(long_buckets)
          and all(g <= TOL_LOGIT for g, _ in gaps.values()),
          f"first tokens of the prompts in the buckets "
          f"{sorted(long_buckets)} score within {TOL_LOGIT} of the dense "
          f"path's best logit (prompt length: (gap, same token) = {gaps})")


# -- serve_latent's second shape: the shortcut-connected double layer -------
def shortcut_extra(ctx) -> None:
    """Every pick of the decode steps is a held real expert's, an absent
    one's or an identity expert's, and identity picks are about their
    share of the router's width."""
    cfg, st = ctx.cfg, ctx.eng.moe_stats()
    k, live = cfg.num_experts_per_tok, st["tokens"] > 0
    check((st["pairs"].sum(1) + st["absent_pairs"] + st["zero_pairs"]
           == st["tokens"] * k).all()
          and live.tolist() == [True, False] * cfg.num_layers,
          f"tokens x {k} = held + absent + identity pairs in every expert "
          f"branch; its counter rides the first attention's tuple (tokens "
          f"{st['tokens'].tolist()})")
    share = st["zero_pairs"].sum() / (st["tokens"].sum() * k)
    even = cfg.zero_expert_num / (cfg.num_experts + cfg.zero_expert_num)
    check(even / 2 <= share <= even * 2,
          f"identity experts took {share:.3f} of the decode steps' picks "
          f"({even:.3f} of the router's outputs are theirs)")


# -- serve_hybrid: window and full attention layers, two shapes -------------
def hybrid_kernels(sz, cfg) -> None:
    """Both decode kernels alone: rows below, at and past the window, and
    at the ring's seam."""
    import jax.numpy as jnp
    import numpy as np

    page, W = sz.page, cfg.sliding_window
    ring = -(-W // page) + 1
    r = np.random.RandomState(0)
    lens = np.resize([0, W - 1, W, W + 1, 2 * page - 1, 2 * page,
                      5 * page + 3, ring * page + 5],
                     sz.hybrid_batch).astype("int32")
    for kind, ncols, window in (("full", int(lens.max()) // page + 1, None),
                                ("window", ring, W)):
        sk = jnp.asarray(r.standard_normal(cfg.num_heads), jnp.float32) \
            if cfg.sink(kind) else None
        paged_twin(sz, cfg, r, f"{kind} decode kernel, keys "
                   f"{cfg.k_cache_width} against values {cfg.v_head_dim}, "
                   f"sink {sk is not None}, window {window}",
                   cfg.kv_heads(kind), ncols, lens, sinks=sk, window=window)


def hybrid_extra(ctx) -> None:
    """Decode ran past a ring's wrap and still agrees with a forward over
    the whole context; both classes of pages came back."""
    eng, B, page = ctx.eng, ctx.sz.hybrid_batch, ctx.sz.page
    ring = -(-ctx.cfg.sliding_window // page) + 1
    longest = len(ctx.mix[0]) + ctx.new
    check((eng.cache.ring, eng.cache.Pw) == (ring, B * ring + 1)
          and [a.shape[0] for a, _ in eng.pools]
          == [eng.cache.Pw if w else eng.P for w in eng.cache.window_layers],
          f"two classes of pages: {eng.P} full, {eng.cache.Pw} window (a "
          f"ring of {ring} a row)")
    check(longest > ring * page,
          f"the longest context {longest} wrapped its ring at {ring * page}")
    c = eng.cache.counts()["classes"]
    check(c["full"]["used"] == 0 and c["window"]["used"] == 0,
          f"both classes back to free: {c}")
    # a prompt's own attention is the flash kernel on the layers whose q,
    # k and v are one width of whole lanes with no sink (afmoe's), lax
    # blocks on the others (mimo's 192 against 128; the rehearsal's CPU)
    from paddle_tpu.ops.pallas.flash_attention import flash_gqa_supported

    sz, cfg = ctx.sz, ctx.cfg

    def form(b):
        words = set()
        for kind in cfg.attention_kinds:
            KV = cfg.window_num_kv_heads if kind == "window" \
                else cfg.num_kv_heads
            words.add("flash" if not (
                sz.rehearsal or cfg.sink(kind)
                or cfg.v_head_dim != cfg.qk_head_dim)
                and flash_gqa_supported(
                    (1, b, cfg.num_heads, cfg.qk_head_dim),
                    (1, b, KV, cfg.qk_head_dim)) else "blockwise")
        return "+".join(sorted(words))

    forms = eng.prefill_attention_forms()
    check(forms and forms == {b: form(b) for b in forms},
          f"prefill programs attend as {forms}")
    for site in [s for s in eng.program_sites() if s[0] == "prefill"]:
        found = kernel_names(eng.lowered_text(site))
        # jitted on its own: in the text once a kind of layer at most
        check(("flash" in forms[site[1]])
              == (found.get("flash_attention_fwd_gqa", 0) >= 1),
              f"program {site} holds Mosaic calls {found}")


# -- serve_sparse, serve_sparse_mla: attention over the keys (the latent
# cache rows) a learned index keeps ------------------------------------------
def kept_rows(sz, topk: int):
    """A decode batch for a kernel under a kept mask, at the decode
    program's shapes: (r, pages a row, lengths, mask). A row keeps its
    newest key and a quarter of the others, and of its second page the
    newest key alone."""
    import jax.numpy as jnp
    import numpy as np

    page, B = sz.page, sz.hybrid_batch
    r = np.random.RandomState(0)
    ncols = -(-(max(sz.sparse_lens) + sz.sparse_new) // page)
    lens = np.resize([0, topk - 1, topk, topk + 1, 2 * page - 1,
                      ncols * page - 2], B).astype("int32")
    cols = np.arange(ncols * page)[None]
    newest = cols == lens[:, None]
    keep = (cols <= lens[:, None]) & (
        (r.random_sample((B, ncols * page)) < 0.25) | newest)
    keep[:, page:2 * page] &= newest[:, page:2 * page]
    return r, ncols, lens, jnp.asarray(keep)


def sparse_kernels(sz, cfg) -> None:
    r, ncols, lens, keep = kept_rows(sz, cfg.index_topk)
    paged_twin(sz, cfg, r, "decode kernel under a kept mask",
               cfg.num_kv_heads, ncols, lens, keep=keep)


def sparse_mla_kernels(sz, cfg) -> None:
    import jax.numpy as jnp
    import numpy as np

    from paddle_tpu.ops.pallas import kept_attention as ka
    from paddle_tpu.ops.pallas import mla_attention as ma

    r, ncols, lens, keep = kept_rows(sz, cfg.index_topk)
    P, tbl, rnd = paged_batch(sz, r, ncols)
    H, dc, B = cfg.num_heads, cfg.kv_lora_rank, sz.hybrid_batch
    cp = rnd(P, 1, sz.page, dc)
    rp = rnd(P, 1, sz.page, cfg.rope_cache_width)
    ql, qr = rnd(B, H, dc), rnd(B, H, cfg.rope_cache_width)
    if sz.rehearsal or ma.mla_paged_supported(ql.shape, cp.shape, rp.shape):
        check_twin(
            f"latent decode kernel under a kept mask ({H} heads, {ncols} "
            f"pages a row)",
            ma.mla_paged_decode_attention(
                ql, qr, cp, rp, tbl, lens, cfg.softmax_scale, keep=keep,
                interpret=sz.rehearsal),
            ma.mla_paged_attention_dense(
                ql[:, None], qr[:, None], cp, rp, tbl, lens,
                cfg.softmax_scale, keep)[:, 0])
    # the prefill's attention over kept sets, keys wider than values
    Sk, blk = (64, 16) if sz.rehearsal else (1024, 512)
    kq, kk, kv = rnd(1, Sk, 8, 192), rnd(1, Sk, 8, 192), rnd(1, Sk, 8, 128)
    tri = np.tril(np.ones((Sk, Sk), bool))
    kmask = tri & ((r.random_sample((Sk, Sk)) < 0.3) | np.eye(Sk, dtype=bool))
    kmask[:blk] = tri[:blk]
    kmask = jnp.asarray(kmask[None])
    if sz.rehearsal or ka.kept_flash_supported(kq.shape, kv.shape, blk):
        check_twin(
            f"prefill attention over kept sets ({Sk} rows, 8 heads of 192 "
            f"against 128)",
            ka.kept_flash_attention(kq, kk, kv, kmask, 0.07, blk,
                                    interpret=sz.rehearsal),
            ka.kept_attention_dense(kq, kk, kv, kmask, 0.07))


def selecting_forward(ctx, seq):
    """One jitted forward over the whole context (an eager one compiles
    a program a length): the logits, and into ``ctx`` what every row kept
    a layer and the passes its sorted expert layers took."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from paddle_tpu.autograd import no_grad
    from paddle_tpu.distributed.engine import bind_params
    from paddle_tpu.observability import moestats
    from paddle_tpu.ops.sparse_attention import collect_selection

    params = list(ctx.model.parameters())

    def whole(pvals, ids):
        with no_grad(), bind_params(params, pvals), \
                collect_selection() as sets:
            moestats.begin()
            try:
                logits = ctx.model.forward(ids)
            finally:
                recs = moestats.drain()
        return (logits._value, [k[0].sum(-1) for k in sets],
                [r["passes"] for r in recs if "passes" in r])

    full, kept, passes = jax.jit(whole)(tuple(p._value for p in params),
                                        jnp.asarray(seq[None, :]))
    ctx.kept = [np.asarray(k) for k in kept]
    ctx.passes = [int(p) for p in passes]
    return np.asarray(full[0].astype(jnp.float32))


def sparse_extra(ctx) -> None:
    """Every row of a full forward, and every row the decode steps
    selected for, kept ``min(t + 1, top-k)`` keys, at contexts several
    times the top-k; every page came back."""
    import numpy as np

    sz, cfg, eng = ctx.sz, ctx.cfg, ctx.eng
    topk, longest = cfg.index_topk, len(ctx.mix[0]) + ctx.new
    check(longest > 2 * topk,
          f"the longest context {longest} is {longest / topk:.1f} times "
          f"the {topk} keys a query keeps")
    want_kept = np.minimum(np.arange(len(ctx.full)) + 1, topk)
    wrong = sum(int((k != want_kept).sum()) for k in ctx.kept)
    check(len(ctx.kept) == cfg.num_layers and wrong == 0,
          f"kept_keys_wrong == {wrong}: every row of {len(ctx.kept)} "
          f"layers of a full forward keeps min(t + 1, {topk}) keys")
    sel = eng.selection_stats()
    check(sel["rows"] > 0 and sel["kept_keys_wrong"] == 0,
          f"the decode steps' own count on the device: kept_keys_wrong "
          f"== {sel['kept_keys_wrong']} of {sel['rows']} (row, layer) "
          f"pairs")
    c = eng.cache.counts()
    check(c["free"] == eng.cache.usable, f"every page back to free: {c}")


def sparse_rows(ctx) -> None:
    sparse_extra(ctx)
    # the sorted rows each prefill bucket's expert layers hold at a time,
    # and the passes a full forward's layers took over them (a rehearsal's
    # few tokens take the batched form: no rows, no pass)
    rows, passes = ctx.eng.moe_stats()["rows"], ctx.passes
    check(all(m <= n for m, n in rows.values())
          and passes == [1] * len(passes)
          and (ctx.sz.rehearsal or (
              len(passes) == ctx.cfg.num_layers and rows
              and all(m < n for m, n in rows.values()))),
          f"sorted rows a prefill bucket (bound, routed pairs) {rows}; "
          f"passes of a full forward over {len(ctx.full)} tokens, layer "
          f"by layer: {passes}")


def sparse_mla_extra(ctx) -> None:
    sparse_extra(ctx)
    sz, cfg, eng = ctx.sz, ctx.cfg, ctx.eng
    check(eng.moe_stats()["tokens"][0] == 0,
          "the dense layer routed no pair")
    site = max(s for s in eng.program_sites() if s[0] == "prefill")
    found = kernel_names(eng.lowered_text(site))
    check(sz.rehearsal or site[1] < cfg.attention_block
          or found.get("kept_flash_attention", 0) == cfg.num_layers,
          f"program {site} holds Mosaic calls {found}")


def cold_traffic(lens: str, new: str):
    """The named ``Sizes`` lengths as the mix, no warm-up."""
    return lambda sz: {"warm": (), "mix": getattr(sz, lens),
                       "new": getattr(sz, new), "batch": sz.hybrid_batch}


def hybrid_case(label: str, sizes: str, lens: str) -> ServeCase:
    return ServeCase(
        label=label, model=("hybrid_moe", "HybridMoEConfig",
                            "HybridMoEForCausalLM", sizes),
        traffic=cold_traffic(lens, "hybrid_new"),
        engines=lambda sz: [{"debug_invariants": True}],
        pools=None, kernels=hybrid_kernels,
        # each jitted on its own: one call site in the text for all layers
        decode_kernels=lambda cfg: {"paged_decode_attention": 1,
                                    "paged_window_decode_attention": 1},
        donated=3,      # K, V, the routing counters
        extra=hybrid_extra)


def ssm_extra(ctx) -> None:
    """A model with state layers: every slot taken was given back, the
    recurrent state is float32 beside a bf16 model, what the cache
    accounts is what the arrays take, and the engine says why it refuses
    a prefix cache."""
    from paddle_tpu.inference import ServingEngine

    eng, cfg = ctx.eng, ctx.cfg
    n = cfg.mixer_kinds.count("ssm")
    row = n * (cfg.ssm_num_heads * cfg.ssm_head_dim * cfg.ssm_state_size
               * 4 + (cfg.conv_kernel - 1) * cfg.conv_dim * 2)
    mem = eng.memory_summary()["state"]
    kept = sorted({str(layer[0].dtype) for layer, st in zip(
        eng.pools, eng.cache.state_layers) if st})
    check(mem["state_row_bytes"] == row and kept == ["float32"]
          and mem["state_bytes"] == eng.B * row,
          f"a row's slot over {n} state layers is {row} bytes, H kept in "
          f"{kept}; {eng.B} slots hold {mem['state_bytes']} bytes")
    eng.check_invariants()
    classes = eng.cache.counts()["classes"]
    check(classes["state"] == {"used": 0, "free": eng.B}
          and classes["full"]["used"] == 0,
          f"every slot and page back to free: {classes}")
    try:
        ServingEngine(eng.pred, max_batch=eng.B, prefill_chunk=ctx.sz.page,
                      prefix_cache=True)
        why = ""
    except Exception as e:      # the refusal, with its reason
        why = str(e)
    check("state layers" in why and "one slot a row" in why,
          f"a chunked engine is refused with its reason: {why[:60]}")


SERVE_CASES = {
    "serve": (ServeCase(
        label="llama",
        model=("llama", "LlamaConfig", "LlamaForCausalLM", "llama"),
        # one prompt per prefill bucket (64 .. 2048), then a dozen
        traffic=lambda sz: {"warm": sz.warm_lens, "mix": sz.mix_lens,
                            "new": sz.new_tokens, "batch": sz.max_batch},
        # bucketed prefill + fused decode scan, then the chunked unified step
        engines=lambda sz: [{"decode_chunk": sz.decode_chunk},
                            {"decode_chunk": sz.decode_chunk,
                             "prefill_chunk": sz.prefill_chunk}],
        pools=lambda cfg: [(cfg.num_kv_heads, cfg.head_dim)] * 2,
        decode_kernels=lambda cfg: {"paged_decode_attention": 1,
                                    "rms_norm_fused": 2 * cfg.num_layers + 1},
        donated=2, extra=llama_extra),),
    "serve_latent": (ServeCase(
        label="latent",
        model=("mla_moe", "MLAMoEConfig", "MLAMoEForCausalLM", "latent"),
        # the prompts of up to 900 tokens (prefill buckets to 1,024)
        traffic=lambda sz: {
            "warm": [n for n in sz.warm_lens if n <= 900],
            "mix": [n for n in sz.mix_lens if n <= 900],
            "new": sz.new_tokens, "batch": sz.latent_batch},
        engines=lambda sz: [{}],
        # one latent and one rotated key a position
        pools=lambda cfg: [(1, cfg.kv_lora_rank), (1, cfg.rope_cache_width)],
        # jitted on its own since PR 47: lowered once, called a layer
        decode_kernels=lambda cfg: {"mla_paged_decode_attention": 1},
        donated=3),         # latents, rotated keys, the routing counters
        ServeCase(
        label="shortcut",
        model=("mla_moe", "MLAMoEConfig", "MLAMoEForCausalLM", "shortcut"),
        traffic=lambda sz: {
            "warm": [n for n in sz.warm_lens if n <= 900],
            "mix": [n for n in sz.mix_lens if n <= 900],
            "new": sz.new_tokens, "batch": sz.latent_batch},
        engines=lambda sz: [{}],
        pools=lambda cfg: [(1, cfg.kv_lora_rank), (1, cfg.rope_cache_width)],
        # two attentions a layer, each over its own pooled pair, all
        # calls of the one lowered kernel
        decode_kernels=lambda cfg: {"mla_paged_decode_attention": 1},
        donated=3, extra=shortcut_extra)),
    "serve_hybrid": (hybrid_case("mimo", "hybrid", "hybrid_lens"),
                     hybrid_case("afmoe", "afmoe", "afmoe_lens")),
    "serve_sparse": (ServeCase(
        label="sparse", model=("hybrid_moe", "sparse_moe_tiny",
                               "HybridMoEForCausalLM", "sparse"),
        traffic=cold_traffic("sparse_lens", "sparse_new"),
        engines=lambda sz: [{"debug_invariants": True,
                             "pool_pages": sz.sparse_pool}],
        pools=lambda cfg: [(cfg.num_kv_heads, cfg.k_cache_width),
                           (cfg.num_kv_heads, cfg.v_head_dim),
                           (1, cfg.index_cache_width)],
        kernels=sparse_kernels,
        decode_kernels=lambda cfg: {"paged_sparse_decode_attention": 1},
        donated=4,      # K, V, index keys, the counters
        forward=selecting_forward, extra=sparse_rows),),
    "serve_sparse_mla": (ServeCase(
        label="sparse_mla", model=("mla_moe", "sparse_mla_tiny",
                                   "MLAMoEForCausalLM", "sparse_mla"),
        traffic=cold_traffic("sparse_lens", "sparse_new"),
        engines=lambda sz: [{"debug_invariants": True,
                             "pool_pages": sz.sparse_mla_pool}],
        pools=lambda cfg: [(1, cfg.kv_lora_rank), (1, cfg.rope_cache_width),
                           (1, cfg.index_cache_width)],
        kernels=sparse_mla_kernels,
        decode_kernels=lambda cfg: {
            "mla_paged_sparse_decode_attention": 1},
        donated=4, forward=selecting_forward, extra=sparse_mla_extra),),
    "serve_ssm": (ServeCase(
        label="ssm", model=("ssm_moe", "SSMMoEConfig", "SSMMoEForCausalLM",
                            "ssm"),
        traffic=lambda sz: {
            "warm": [n for n in sz.warm_lens if n <= 900],
            "mix": [n for n in sz.mix_lens if n <= 900],
            "new": sz.new_tokens, "batch": sz.latent_batch},
        engines=lambda sz: [{"debug_invariants": True}],
        pools=None,     # the layers differ: state, pages, nothing
        decode_kernels=lambda cfg: {"paged_decode_attention": 1},
        # H and the tail of a state layer, K and V of an attention
        # layer, the routing counter of an expert layer
        donated=lambda cfg: sum(
            {"ssm": 2, "attention": 2, "experts": 1}[k]
            for k in cfg.mixer_kinds),
        extra=ssm_extra),),
}


# ---------------------------------------------------------------------------
# parent: children, in order, one at a time; never imports JAX
# ---------------------------------------------------------------------------
def run_phase_child(phase: str, rehearsal: bool, env: dict):
    """Run one phase as a child, passing its output through line by
    line. Returns its result dict, or None if it failed, timed out or
    printed none. The child is dead when this returns."""
    cmd = [sys.executable, os.path.abspath(__file__), "--phase", phase]
    if rehearsal:
        cmd.append("--rehearsal")
    print(f"== phase {phase}" + (" (REHEARSAL)" if rehearsal else ""),
          flush=True)
    t0 = time.perf_counter()
    proc = subprocess.Popen(cmd, stdout=subprocess.PIPE,
                            stderr=subprocess.STDOUT, text=True, env=env)
    timer = threading.Timer(PHASE_TIMEOUT[phase], proc.kill)
    timer.daemon = True
    timer.start()
    result = None
    try:
        for line in proc.stdout:
            if line.startswith(RESULT_TAG):
                result = json.loads(line[len(RESULT_TAG):])
            else:
                sys.stdout.write(line)
                sys.stdout.flush()
        rc = proc.wait()
    finally:
        timer.cancel()
        if proc.poll() is None:
            proc.kill()
            proc.wait()
    dt = time.perf_counter() - t0
    if rc != 0 or result is None:
        why = (f"killed after {PHASE_TIMEOUT[phase]}s" if rc in (-9, 137)
               else f"exit code {rc}")
        print(f"== phase {phase} FAILED ({why}, {dt:.0f}s)", flush=True)
        return None
    keys = ("xla_compile_requests", "xla_compile_or_fetch_s",
            "cache_hits", "cache_misses", "cache_entries_written")
    print(f"== phase {phase} ok in {dt:.0f}s; "
          + ", ".join(f"{k}={result[k]}" for k in keys), flush=True)
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="also run the train step over a 2x2 mesh in two "
                         "layouts; fewer than four TPU devices is an error")
    ap.add_argument("--phases", default=None,
                    help="comma-separated subset, run in the given order: "
                         + ",".join(ALL_PHASES))
    ap.add_argument("--rehearsal", action="store_true",
                    help="toy sizes on the CPU; output says REHEARSAL")
    ap.add_argument("--phase", default=None, help=argparse.SUPPRESS)
    args = ap.parse_args(argv)
    sz = Sizes(args.rehearsal)

    if args.phase:                       # a child: one phase, this process
        try:
            if args.phase == "kernels":
                phase_kernels(sz)
            elif args.phase in SERVE_CASES:
                phase_serve(sz, args.phase)
            else:
                phase_train(sz, args.phase)
        except Failed as e:
            print(f"chip_smoke: phase {args.phase}: check failed: {e}",
                  file=sys.stderr, flush=True)
            return 1
        return 0

    if args.phases:
        phases = tuple(args.phases.split(","))
        unknown = set(phases) - set(ALL_PHASES)
        if unknown:
            ap.error(f"unknown phases {sorted(unknown)}")
    else:
        phases = ONE_CHIP_PHASES + (FOUR_CHIP_PHASES if args.four_chips
                                    else ())
    if set(phases) & set(FOUR_CHIP_PHASES) and "train" not in phases:
        phases = ("train",) + phases    # their one-chip reference loss

    env = dict(os.environ)
    if args.rehearsal:
        env["JAX_PLATFORMS"] = "cpu"
        env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "") +
                            " --xla_force_host_platform_device_count=4")
    t0 = time.perf_counter()
    results = {}
    for phase in phases:
        res = run_phase_child(phase, args.rehearsal, env)
        if res is None:
            print(f"chip_smoke: FAILED in phase {phase}; no result",
                  file=sys.stderr, flush=True)
            return 1
        results[phase] = res
    ok = True
    for phase in FOUR_CHIP_PHASES:
        if phase in results:
            a, b = results["train"]["losses"][0], results[phase]["losses"][0]
            good = abs(a - b) <= TOL_LOSS_4CHIP
            ok &= good
            print(("  ok   " if good else "  FAIL ")
                  + f"{phase} step-0 loss {b:.4f} vs one chip {a:.4f}: "
                    f"|diff| {abs(a - b):.4f} <= {TOL_LOSS_4CHIP}",
                  flush=True)
    if not ok:
        print("chip_smoke: FAILED: four-chip loss differs from one chip; "
              "no result", file=sys.stderr, flush=True)
        return 1
    if not set(phases) & set(FOUR_CHIP_PHASES):
        print("four-chip phase: not run (ask with --four-chips on a "
              "four-chip host)", flush=True)
    device = results[phases[-1]]["device"]
    print(f"chip_smoke: {len(phases)} phases ok in "
          f"{time.perf_counter() - t0:.0f}s: {', '.join(phases)}",
          flush=True)
    final = {"ok": True, "device": device}
    if args.rehearsal:
        final["rehearsal"] = True
    print(json.dumps(final), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
