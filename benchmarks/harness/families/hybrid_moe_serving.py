"""A decoder with window and full attention layers and routed experts
(here ``mimo-v2-flash``: ``HybridMoEConfig`` takes every one of its sizes
as data) through ``Config.enable_paged_kv`` -> ``create_predictor`` ->
``ServingEngine`` in its default mode, as ``mla_moe_serving`` builds a
latent-attention decoder. Serves ``closed_loop`` and ``open_loop``
traffic (the interface is in ``traffic/serving_common.py``; the
loop-driving methods are ``llama_serving.System``'s, the routing
counters and the program's expert choices ``mla_moe_serving.System``'s).

The configuration is one holder's share of an expert-parallel layer
(``configs/mimo-v2-flash.json``: ``n_routed_experts`` held of
``router_experts``, from ``expert_offset``; a slice of the vocabulary).
``check`` compares the requests with the LONGEST contexts (their decode
steps are the ones that wrap a window layer's ring most often and walk
the most pages of a full layer), holds the expert layers to 0 dropped
pairs and the decode program to both kernels by name, and lists the
share of expert choices that differ from the reference's.

The model is imported when this module is: a tree without it fails
here, before anything is allocated.
"""
from __future__ import annotations

import json
import re
import time
from typing import Dict, List

import numpy as np

from paddle_tpu.models.hybrid_moe import (HybridMoEConfig,
                                          HybridMoEForCausalLM)

from .. import weights
from ..laps import Laps
from ...references import mimo as ref
from . import llama_serving as base
from . import mla_moe_serving as moe

KERNELS = ("paged_decode_attention", "paged_window_decode_attention")
_LEAF = {"input_layernorm": "in_norm", "self_attn.q_proj": "q",
         "self_attn.k_proj": "k", "self_attn.v_proj": "v",
         "self_attn.o_proj": "o", "self_attn.sinks": "sinks",
         "post_attention_layernorm": "post_norm",
         "mlp.gate_proj": "gate", "mlp.up_proj": "up",
         "mlp.down_proj": "down", "mlp.gate.weight": "router",
         "mlp.gate.bias": "router_bias"}
_STACK = {"mlp.w_gate": "gate", "mlp.w_up": "up", "mlp.w_down": "down"}
KV_GAUGE = "paddle_tpu_serving_kv_bytes_per_context_token"


def names_of(pname: str, cfg: Dict):
    """The reference's leaf (or stack of leaves) behind one parameter."""
    m = re.match(r"layers\.(\d+)\.(.+)$", pname)
    if not m:
        return {"embed_tokens": "embed", "norm": "norm",
                "lm_head": "lm_head"}[pname]
    i, rest = m.groups()
    if rest in _STACK:
        return [f"l.{i}.e.{j}.{_STACK[rest]}"
                for j in ref.held_experts(cfg)]
    return f"l.{i}.{_LEAF[rest]}"


def model_config(cfg: Dict, max_len: int) -> HybridMoEConfig:
    n = cfg["num_hidden_layers"]
    return HybridMoEConfig(
        vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
        attention_kinds=["window" if w else "full"
                         for w in cfg["hybrid_layer_pattern"][:n]],
        ffn_kinds=["experts" if e else "dense"
                   for e in cfg["moe_layer_freq"][:n]],
        num_heads=cfg["num_attention_heads"],
        num_kv_heads=cfg["num_key_value_heads"],
        window_num_kv_heads=cfg["swa_num_key_value_heads"],
        qk_head_dim=cfg["head_dim"], v_head_dim=cfg["v_head_dim"],
        rotary_dim=ref.rotary_dim(cfg), rope_theta=cfg["rope_theta"],
        window_rope_theta=cfg["swa_rope_theta"],
        sliding_window=cfg["sliding_window"],
        full_sink=cfg["add_full_attention_sink_bias"],
        window_sink=cfg["add_swa_attention_sink_bias"],
        value_scale=cfg["attention_value_scale"],
        intermediate_size=cfg["intermediate_size"],
        moe_intermediate_size=cfg["moe_intermediate_size"],
        num_experts=cfg["router_experts"],
        num_local_experts=cfg["n_routed_experts"],
        expert_offset=cfg["expert_offset"],
        num_experts_per_tok=cfg["num_experts_per_tok"],
        routed_scaling_factor=cfg["routed_scaling_factor"] or 1.0,
        max_position_embeddings=max_len,
        rms_norm_eps=cfg["layernorm_epsilon"],
        initializer_range=cfg["initializer_range"],
        dtype=cfg["torch_dtype"])


class System(moe.System):
    def __init__(self, cfg: Dict, traffic: Dict, plan: Dict, seed: int,
                 devices):
        import paddle_tpu as paddle
        from paddle_tpu.inference import (Config, ServingEngine,
                                          create_predictor)

        self.cfg, self.traffic, self.seed = cfg, traffic, seed
        srv = cfg["serving"]
        self.max_batch = int(traffic["max_batch"])
        self.M = int(srv["max_length"])
        mcfg = model_config(cfg, self.M)
        if (cfg["swa_head_dim"], cfg["swa_v_head_dim"],
                cfg["swa_num_attention_heads"]) != (
                mcfg.qk_head_dim, mcfg.v_head_dim, mcfg.num_heads):
            raise ValueError("the model takes one query head count and "
                             "one head size for both attention kinds; "
                             "the file's differ")
        laps = Laps()
        paddle.set_default_dtype(cfg["torch_dtype"])
        paddle.seed(seed % (2 ** 31))
        with paddle.LazyGuard():
            model = HybridMoEForCausalLM(mcfg)
        named = list(model.named_parameters())
        table = ref.leaf_table(cfg)
        # a layer at a time: the generator's float32 temporaries for a
        # stack of experts, and all layers at once would hold several
        # beside the weights
        groups: Dict[str, List] = {}
        for n, p in named:
            m = re.match(r"layers\.(\d+)\.", n)
            groups.setdefault(m.group(1) if m else n, []).append((n, p))
        for part in groups.values():
            weights.load(part, {n: names_of(n, cfg) for n, _ in part},
                         table, seed, cfg["torch_dtype"])
        laps.mark("model_and_weights")
        pred = create_predictor(Config().set_model(model).enable_paged_kv(
            page_size=srv["page_size"]))
        laps.mark("predictor")
        self.eng = ServingEngine(
            pred, max_batch=self.max_batch,
            decode_chunk=srv["decode_chunk"], pool_pages=srv["pool_pages"],
            prefill_chunk=srv["prefill_chunk"],
            prefix_cache=srv["prefix_cache"], trace_ring=1 << 16)
        laps.mark("engine")
        self.build_seconds = laps.seconds
        self.n_chips = 1
        lens = plan["prompt_lens"]
        lo, hi = base.bucket(min(lens)), min(base.bucket(max(lens)), self.M)
        self.warm_buckets = [b for b in (lo << k for k in range(12))
                             if b <= hi]
        self._model, self._pred = model, pred
        # the engine's gauge of pool bytes held a context token (made by
        # the engine above; get-or-create hands the same one back)
        from paddle_tpu.observability import get_registry

        self._kv_gauge = get_registry().gauge(KV_GAUGE)
        self._kv_ratio: List[float] = []

    def kernels_present(self) -> Dict[tuple, bool]:
        """(kernel, program) -> is the kernel's call in its text."""
        out = {}
        for site in self.eng.program_sites():
            if site[0] in ("decode", "prefill"):
                text = self.eng.lowered_text(site) or ""
                for k in KERNELS:
                    out[k, "_".join(map(str, site))] = \
                        f'kernel_name = "{k}"' in text
        return out

    def decode_rows(self) -> List[int]:
        """As ``llama_serving``; called once a step of the traced
        stretch, so it also reads the engine's gauge of pool bytes held
        a context token there (the last retired round's)."""
        v = self._kv_gauge.value()
        if v:
            self._kv_ratio.append(float(v))
        return super().decode_rows()

    def kv_host(self) -> Dict:
        if not self._kv_ratio:
            return {}
        return {"kv_bytes_per_context_token":
                float(np.mean(self._kv_ratio))}


def build(cfg, traffic, plan, seed, devices) -> System:
    return System(cfg, traffic, plan, seed, devices)


def sample(finished, k: int):
    """The ``k`` finished requests with the longest contexts."""
    order = sorted(finished, key=lambda r: (len(r.prompt) + len(r.tokens),
                                            r.idx))
    return order[-k:] if k else []


def check(system: System, result: Dict) -> List[Dict]:
    """As ``mla_moe_serving.check`` over the longest requests: both
    decode kernels in the decode program (a prefill program holds
    neither: listed, never judged), 0 dropped pairs, token counts, the
    logit gap, the share of expert choices that differ from the
    reference's (listed, never judged: limit 1). Puts the routing
    counters and the pool bytes a context token among the host readings
    for the reducers, which run after this."""
    import jax

    cfg, seed = system.cfg, system.seed
    vocab = cfg["vocab_size"]
    kernels = system.kernels_present() \
        if jax.devices()[0].platform == "tpu" else {}
    host = system.moe_host()
    host.update(system.kv_host())
    result["host"].update(host)
    print("host: " + json.dumps({k: v for k, v in result["host"].items()
                                 if k != "decode_rows"}), flush=True)
    picks = sample(result["finished"], system.traffic["check_requests"])
    bad = [r for r in result["finished"]
           if len(r.tokens) != r.n_out
           or not ((r.tokens >= 0) & (r.tokens < vocab)).all()]
    t0 = time.perf_counter()
    prog = system.program_choices(moe._sequences(picks))
    system.free()
    print(f"the program's expert choices on {len(picks)} requests took "
          f"{time.perf_counter() - t0:.1f}s", flush=True)
    out = [{"name": f"kernel {k} missing from program {prog}",
            "value": int(not v), "limit": 0 if prog == "decode" else 1}
           for (k, prog), v in kernels.items()]
    out.append({"name": "routed pairs the expert layers dropped",
                "value": host["moe_dropped_pairs"], "limit": 0})
    out.append({"name": "finished requests with a wrong token count or a "
                        "token outside the vocabulary",
                "value": len(bad), "limit": 0})
    r = ref.ServeReference(cfg, seed)
    logits = r.logits([(q.prompt, q.tokens) for q in picks])
    gaps = [ref.served_gap(lg, q.tokens) for lg, q in zip(logits, picks)]
    out.append(base._gap_number(gaps, picks,
                                cfg["limits"]["served_logit_gap"]))
    out.append({"name": "share of (position, layer) expert choices of an "
                        "untimed full forward of the program's model that "
                        "differ from the reference's",
                "value": moe._flip_share(prog, r.chosen()), "limit": 1.0})
    return out


def control(system: System, result: Dict) -> Dict:
    """As ``mla_moe_serving.control``: the reference in the program's
    place in fp8, beside the program, over the same longest requests."""
    cfg, seed = system.cfg, system.seed
    limit = cfg["limits"]["served_logit_gap"]
    picks = sample(result["finished"], system.traffic["check_requests"])
    prog_choices = system.program_choices(moe._sequences(picks))
    system.free()
    reqs = [(q.prompt, q.tokens) for q in picks]
    r = ref.ServeReference(cfg, seed)
    logits = r.logits(reqs)
    want = r.chosen()
    lo = ref.ServeReference(cfg, seed, "fp8")
    low = lo.logits(reqs)
    prog = [ref.served_gap(lg, q.tokens) for lg, q in zip(logits, picks)]
    ctl = [ref.served_gap(lg, lw.argmax(-1))
           for lg, lw in zip(logits, low)]
    q = (50, 90, 99, 100)
    return {"program": [base._gap_number(prog, picks, limit)],
            "control": [base._gap_number(ctl, picks, limit)],
            "program_gap_quantiles": dict(zip(map(str, q), np.percentile(
                np.concatenate(prog), q).tolist())),
            "control_gap_quantiles": dict(zip(map(str, q), np.percentile(
                np.concatenate(ctl), q).tolist())),
            "program_choice_flips": moe._flip_share(prog_choices, want),
            "control_choice_flips": moe._flip_share(lo.chosen(), want)}
