"""A latent-attention decoder of SHORTCUT-CONNECTED double layers (here
``longcat-flash-omni``: two latent attentions off a query latent and two
dense SwiGLUs a layer around one expert branch, a 768-wide softmax
router whose last 256 outputs are identity experts, 12 a token, weights
not renormalised) through ``Config.enable_paged_kv`` ->
``create_predictor`` -> ``ServingEngine`` in its default mode. The model
is ``MLAMoEForCausalLM`` with the fields of ``FIELDS`` set; this module
maps the source's key names (``ffn_hidden_size``, ``moe_topk``,
``zero_expert_num``, ...) to ``MLAMoEConfig`` and reuses
``mla_moe_serving.System`` for everything that drives and reads the
engine. The reference is ``references/longcat.py``.

The configuration is one holder's share of an expert-parallel layer
(``configs/longcat-flash-omni.json``: ``n_routed_experts`` real experts
held of ``router_experts``, from ``expert_offset``, ALL identity experts;
a slice of the vocabulary), cut in depth. ``check``: the logits gap of
prefill-then-decode through the paged cache against the reference's
full forward on ``check_requests`` finished requests, 0 dropped pairs
(tokens x 12 = held + absent + identity), the kernel
``mla_paged_decode_attention`` in the decode program, finished requests
well-formed, and, listed and never judged (limit 1), the share of
(position, layer) router choices of an untimed full forward of the
program's model that differ from the reference's.

Host readings for the reducers, beside ``mla_moe_serving``'s two (pairs
a HELD REAL expert a step, the most loaded of them over their mean):
``moe_zero_pick_share`` (identity picks over all picks of the decode
steps, the engine's gauge ``paddle_tpu_moe_zero_pick_share`` as
``moe_stats()`` set it, percent: a property of the weights, watched for
drift),
``kv_bytes_per_context_token`` (the engine's gauge, a step of the traced
stretch, mean) and ``prefill_padding_share`` (1 - prompt tokens / bucket
tokens of the window's prefills, percent). A second ``host:`` line, for
people, holds the histogram of REAL experts a token chose (0..12) on the
probe's positions.

The model's fields are checked when this module is imported: a tree
whose ``MLAMoEConfig`` lacks them fails here, before anything is
allocated.
"""
from __future__ import annotations

import dataclasses
import json
import re
import time
from typing import Dict, List

import numpy as np

from paddle_tpu.models.mla_moe import MLAMoEConfig, MLAMoEForCausalLM

from .. import weights
from ..laps import Laps
from ...references import longcat as ref
from . import llama_serving as base
from . import mla_moe_serving as moe

FIELDS = ("shortcut_moe", "zero_expert_num", "router_score_func",
          "router_bias", "norm_topk_prob", "mla_scale_q_lora",
          "mla_scale_kv_lora")
_missing = sorted(set(FIELDS)
                  - {f.name for f in dataclasses.fields(MLAMoEConfig)})
if _missing:
    raise ImportError("this tree's MLAMoEConfig lacks " +
                      ", ".join(_missing) + ": it cannot build a "
                      "shortcut-connected layer of two latent attentions "
                      "around one expert branch, identity experts, a "
                      "softmax router with a selection bias and weights "
                      "that are not renormalised, nor the latents' scale "
                      "factors")

KERNEL = moe.KERNEL
KV_GAUGE = "paddle_tpu_serving_kv_bytes_per_context_token"
ZERO_GAUGE = "paddle_tpu_moe_zero_pick_share"
PREFILL_TOKENS = "paddle_tpu_serving_prefill_tokens_total"
_ATTN = {"q_a_proj": "q_a", "q_a_norm": "q_a_norm", "q_b_proj": "q_b",
         "kv_a_proj": "kva", "kv_a_norm": "kv_norm", "kv_b_proj": "kvb",
         "o_proj": "o"}
_FFN = {"gate_proj": "gate", "up_proj": "up", "down_proj": "down"}
_STACK = {"mlp.w_gate": "gate", "mlp.w_up": "up", "mlp.w_down": "down"}
_PAIR = {"input_layernorm": "in_norm",
         "post_attention_layernorm": "post_norm"}


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
    if rest in _PAIR:                   # [2, hidden]: a row a sublayer
        return [f"l.{i}.{_PAIR[rest]}.{j}" for j in (0, 1)]
    if rest.startswith("mlp.gate."):
        return f"l.{i}." + {"weight": "router",
                            "bias": "router_bias"}[rest[9:]]
    part, j, leaf = rest.split(".")
    return f"l.{i}.a.{j}.{_ATTN[leaf]}" if part == "self_attn" \
        else f"l.{i}.f.{j}.{_FFN[leaf]}"


def model_config(cfg: Dict, max_len: int) -> MLAMoEConfig:
    if cfg["zero_expert_type"] != "identity" or cfg["attention_bias"] \
            or cfg["attention_method"] != "MLA" \
            or cfg["num_hidden_layers"] != 2 * cfg["num_layers"]:
        raise ValueError("the model's zero experts are identities, its "
                         "attention is latent and has no bias, and "
                         "num_hidden_layers counts its 2 x num_layers "
                         "attention sublayers; the file says otherwise")
    return MLAMoEConfig(
        vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
        num_layers=cfg["num_layers"],
        num_heads=cfg["num_attention_heads"],
        q_lora_rank=cfg["q_lora_rank"], kv_lora_rank=cfg["kv_lora_rank"],
        qk_nope_head_dim=cfg["qk_nope_head_dim"],
        qk_rope_head_dim=cfg["qk_rope_head_dim"],
        v_head_dim=cfg["v_head_dim"],
        intermediate_size=cfg["ffn_hidden_size"],
        moe_intermediate_size=cfg["expert_ffn_hidden_size"],
        num_experts=cfg["router_experts"],
        num_local_experts=cfg["n_routed_experts"],
        expert_offset=cfg["expert_offset"],
        num_experts_per_tok=cfg["moe_topk"],
        num_shared_experts=0, first_k_dense_replace=0,
        routed_scaling_factor=cfg["routed_scaling_factor"],
        use_qk_norm=False, shortcut_moe=True,
        zero_expert_num=cfg["zero_expert_num"],
        router_score_func="softmax", router_bias=True,
        norm_topk_prob=False,
        mla_scale_q_lora=cfg["mla_scale_q_lora"],
        mla_scale_kv_lora=cfg["mla_scale_kv_lora"],
        max_position_embeddings=max_len, rope_theta=cfg["rope_theta"],
        rope_scaling=None, rms_norm_eps=cfg["rms_norm_eps"],
        initializer_range=cfg["initializer_range"],
        dtype=cfg["torch_dtype"])


class System(moe.System):
    def __init__(self, cfg: Dict, traffic: Dict, plan: Dict, seed: int,
                 devices):
        import paddle_tpu as paddle
        from paddle_tpu.inference import (Config, ServingEngine,
                                          create_predictor)
        from paddle_tpu.observability import get_registry

        self.cfg, self.traffic, self.seed = cfg, traffic, seed
        srv = cfg["serving"]
        self.max_batch = int(traffic["max_batch"])
        self.M = int(srv["max_length"])
        mcfg = model_config(cfg, self.M)
        laps = Laps()
        paddle.set_default_dtype(cfg["torch_dtype"])
        paddle.seed(seed % (2 ** 31))
        with paddle.LazyGuard():
            model = MLAMoEForCausalLM(mcfg)
        table = ref.leaf_table(cfg)
        # a layer at a time, as mla_moe_serving: the generator's float32
        # temporaries for one layer's two dense parts are 1.8 GB
        groups: Dict[str, List] = {}
        for n, p in model.named_parameters():
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
        # the engine's own instruments (made by the engine above;
        # get-or-create hands the same ones back)
        reg = get_registry()
        self._kv_gauge = reg.gauge(KV_GAUGE)
        self._zero_gauge = reg.gauge(ZERO_GAUGE)
        self._kv_ratio: List[float] = []
        self._prefill_tokens = reg.counter(PREFILL_TOKENS,
                                           labelnames=("kind",))
        self._prefill_base = self._prefill_counts()

    def _prefill_counts(self) -> Dict[str, float]:
        return {k: self._prefill_tokens.value(kind=k)
                for k in ("prompt", "bucket")}

    def warm(self) -> Dict:
        out = super().warm()
        self._prefill_base = self._prefill_counts()   # the warm-up's out
        return out

    def decode_rows(self) -> List[int]:
        """As ``llama_serving``; called once a step of the traced
        stretch, so it also reads the engine's gauge of pool bytes held
        a context token there (the last retired round's)."""
        v = self._kv_gauge.value()
        if v:
            self._kv_ratio.append(float(v))
        return super().decode_rows()

    def moe_host(self) -> Dict:
        """``mla_moe_serving``'s readings over the layers that decoded
        (a layer's second attention keeps a counter that stays 0), the
        identity picks' share of all picks of the decode steps, the
        pool bytes a context token and the prefills' padding; each left
        out where the engine counted nothing."""
        out = super().moe_host()    # fetches: moe_stats() sets the gauge
        if "moe_pairs_per_expert" in out:       # some layer decoded
            out["moe_zero_pick_share"] = 100.0 * self._zero_gauge.value()
        if self._kv_ratio:
            out["kv_bytes_per_context_token"] = float(
                np.mean(self._kv_ratio))
        now = self._prefill_counts()
        prompt, bkt = (now[k] - self._prefill_base[k]
                       for k in ("prompt", "bucket"))
        if bkt > 0:
            out["prefill_padding_share"] = 100.0 * (1.0 - prompt / bkt)
        return out


def build(cfg, traffic, plan, seed, devices) -> System:
    return System(cfg, traffic, plan, seed, devices)


def _real_picks_hist(choices: List[np.ndarray], cfg: Dict) -> List[int]:
    """How many (position, layer) pairs chose 0, 1, .. k REAL experts."""
    real = np.concatenate([(c < cfg["router_experts"]).sum(-1)
                           for c in choices])
    return np.bincount(real, minlength=cfg["moe_topk"] + 1).tolist()


def check(system: System, result: Dict) -> List[Dict]:
    """``mla_moe_serving.check``'s numbers against
    ``references/longcat.py``. Puts the routing counters among the host
    readings for the reducers, which run after this."""
    import jax

    cfg, seed = system.cfg, system.seed
    vocab = cfg["vocab_size"]
    kernels = system.kernels_present() \
        if jax.devices()[0].platform == "tpu" else {}
    host = system.moe_host()
    result["host"].update(host)
    print("host: " + json.dumps({k: v for k, v in result["host"].items()
                                 if k != "decode_rows"}), flush=True)
    picks = base.sample(result["finished"], seed,
                        system.traffic["check_requests"])
    bad = [r for r in result["finished"]
           if len(r.tokens) != r.n_out
           or not ((r.tokens >= 0) & (r.tokens < vocab)).all()]
    t0 = time.perf_counter()
    prog = system.program_choices(moe._sequences(picks))
    system.free()
    print(f"the program's router choices on {len(picks)} requests took "
          f"{time.perf_counter() - t0:.1f}s", flush=True)
    print("host: " + json.dumps({"real_experts_a_token_hist":
                                 _real_picks_hist(prog, cfg)}), flush=True)
    out = [{"name": f"kernel {KERNEL} missing from program {k}",
            "value": int(not v), "limit": 0 if k == "decode" else 1}
           for k, v in kernels.items()]
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
    out.append({"name": "share of (position, layer) router choices of an "
                        "untimed full forward of the program's model that "
                        "differ from the reference's",
                "value": moe._flip_share(prog, r.chosen()), "limit": 1.0})
    return out


def control(system: System, result: Dict) -> Dict:
    """As ``mla_moe_serving.control``: the reference in the program's
    place in fp8, beside the program."""
    cfg, seed = system.cfg, system.seed
    limit = cfg["limits"]["served_logit_gap"]
    picks = base.sample(result["finished"], seed,
                        system.traffic["check_requests"])
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
