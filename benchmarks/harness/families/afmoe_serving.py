"""The AFMoE block (here ``trinity-mini``: rotated window layers beside
full layers that turn nothing, q/k norms, a gated attention result,
sandwich norms, a shared expert beside the routed ones) through
``Config.enable_paged_kv`` -> ``create_predictor`` -> ``ServingEngine``
in its default mode. The model is ``HybridMoEForCausalLM`` with its
switches on; this module maps the source's key names (``layer_types``,
``num_dense_layers``, ``num_experts``, ``route_scale``, ...) to
``HybridMoEConfig`` and reuses ``hybrid_moe_serving.System`` for
everything that drives and reads the engine. The reference is
``references/trinity.py``.

The configuration is one holder's share of an expert-parallel layer
(``configs/trinity-mini.json``: ``num_experts`` held of
``router_experts``, from ``expert_offset``; the vocabulary whole), cut in
depth to the published layers ``layers_run``. ``check`` is
``hybrid_moe_serving.check`` against this reference: the requests with
the LONGEST contexts (their rings wrap most often and their full layers
walk the most pages), 0 dropped pairs, both decode kernels by name; the
logit comparison is judged by its mean gap as well as its widest.

Beside mimo's host readings it reports two that read what the engine
counts since PR 35: ``window_ring_fill`` (the gauge
``paddle_tpu_serving_window_ring_fill``, read once a step of the traced
stretch, mean, in %) and ``prefill_padding_share`` (1 - prompt tokens /
bucket tokens of ``paddle_tpu_serving_prefill_tokens_total`` over every
prefill after the warm-up: ramp, window and traced tail, all the same
stratified traffic, in %).

The model's switches are checked when this module is imported: a tree
whose ``HybridMoEConfig`` lacks them fails here, before anything is
allocated.
"""
from __future__ import annotations

import dataclasses
import json
import re
import time
from typing import Dict, List

import numpy as np

from paddle_tpu.models.hybrid_moe import (HybridMoEConfig,
                                          HybridMoEForCausalLM)

from .. import weights
from ..laps import Laps
from ...references import trinity as ref
from . import hybrid_moe_serving as hybrid
from . import llama_serving as base
from . import mla_moe_serving as moe

SWITCHES = ("rotary_kinds", "qk_norm", "attention_gate", "sandwich_norm",
            "embedding_multiplier", "num_shared_experts",
            "head_on_last_row")
_missing = sorted(set(SWITCHES)
                  - {f.name for f in dataclasses.fields(HybridMoEConfig)})
if _missing:
    raise ImportError("this tree's HybridMoEConfig lacks " +
                      ", ".join(_missing) + ": it cannot build the AFMoE "
                      "block")

_LEAF = {"input_layernorm": "in_norm", "self_attn.q_proj": "q",
         "self_attn.k_proj": "k", "self_attn.v_proj": "v",
         "self_attn.gate_proj": "attn_gate", "self_attn.o_proj": "o",
         "self_attn.q_norm": "q_norm", "self_attn.k_norm": "k_norm",
         "attention_out_layernorm": "attn_out_norm",
         "post_attention_layernorm": "pre_mlp_norm",
         "mlp_out_layernorm": "mlp_out_norm",
         "mlp.gate_proj": "gate", "mlp.up_proj": "up",
         "mlp.down_proj": "down", "mlp.gate.weight": "router",
         "mlp.gate.bias": "router_bias", "mlp.shared_gate": "sh_gate",
         "mlp.shared_up": "sh_up", "mlp.shared_down": "sh_down"}
_STACK = {"mlp.w_gate": "gate", "mlp.w_up": "up", "mlp.w_down": "down"}
RING_GAUGE = "paddle_tpu_serving_window_ring_fill"
PREFILL_TOKENS = "paddle_tpu_serving_prefill_tokens_total"


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
    if len(cfg["layers_run"]) != n:
        raise ValueError("layers_run names num_hidden_layers published "
                         "layers")
    return HybridMoEConfig(
        vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
        attention_kinds=["window" if ref.is_window(cfg, i) else "full"
                         for i in range(n)],
        ffn_kinds=["experts" if ref.is_moe(cfg, i) else "dense"
                   for i in range(n)],
        num_heads=cfg["num_attention_heads"],
        num_kv_heads=cfg["num_key_value_heads"],
        window_num_kv_heads=cfg["num_key_value_heads"],
        qk_head_dim=cfg["head_dim"], v_head_dim=cfg["head_dim"],
        rotary_dim=cfg["head_dim"], rope_theta=cfg["rope_theta"],
        window_rope_theta=cfg["rope_theta"], rotary_kinds=("window",),
        sliding_window=cfg["sliding_window"], full_sink=False,
        window_sink=False, value_scale=1.0,
        intermediate_size=cfg["intermediate_size"],
        moe_intermediate_size=cfg["moe_intermediate_size"],
        num_experts=cfg["router_experts"],
        num_local_experts=cfg["num_experts"],
        expert_offset=cfg["expert_offset"],
        num_experts_per_tok=cfg["num_experts_per_tok"],
        routed_scaling_factor=cfg["route_scale"],
        num_shared_experts=cfg["num_shared_experts"],
        qk_norm=True, attention_gate=True, sandwich_norm=True,
        embedding_multiplier=ref.embedding_multiplier(cfg),
        head_on_last_row=True, max_position_embeddings=max_len,
        rms_norm_eps=cfg["rms_norm_eps"],
        initializer_range=cfg["initializer_range"],
        dtype=cfg["torch_dtype"])


class System(hybrid.System):
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
            model = HybridMoEForCausalLM(mcfg)
        table = ref.leaf_table(cfg)
        # a layer at a time, as hybrid_moe_serving: the generator's
        # float32 temporaries (the embedding's are 1.6 GB) one group at
        # a time
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
        self._kv_gauge = reg.gauge(hybrid.KV_GAUGE)
        self._kv_ratio: List[float] = []
        self._ring_gauge = reg.gauge(RING_GAUGE)
        self._ring_fill: List[float] = []
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

    def kernels_present(self) -> Dict[tuple, bool]:
        """As ``hybrid_moe_serving``, over the decode program (judged)
        and the largest prefill program alone (listed): each program's
        text carries 64 MB of rotary tables, and lowering all seven
        again costs the check a quarter of a minute a run."""
        sites = self.eng.program_sites()
        prefill = sorted(s for s in sites if s[0] == "prefill")[-1:]
        out = {}
        for site in [s for s in sites if s[0] == "decode"] + prefill:
            text = self.eng.lowered_text(site) or ""
            for k in hybrid.KERNELS:
                out[k, "_".join(map(str, site))] = \
                    f'kernel_name = "{k}"' in text
        return out

    def decode_rows(self) -> List[int]:
        v = self._ring_gauge.value()
        if v:
            self._ring_fill.append(float(v))
        return super().decode_rows()

    def kv_host(self) -> Dict:
        """mimo's reading, the ring's fill and the prefills' padding;
        each left out where the engine counted nothing."""
        out = super().kv_host()
        if self._ring_fill:
            out["window_ring_fill"] = 100.0 * float(
                np.mean(self._ring_fill))
        now = self._prefill_counts()
        prompt, bkt = (now[k] - self._prefill_base[k]
                       for k in ("prompt", "bucket"))
        if bkt > 0:
            out["prefill_padding_share"] = 100.0 * (1.0 - prompt / bkt)
        return out


def build(cfg, traffic, plan, seed, devices) -> System:
    return System(cfg, traffic, plan, seed, devices)


sample = hybrid.sample


def _gap_numbers(gaps, picks, limits: Dict) -> List[Dict]:
    """The two judged numbers of the logit comparison: the WIDEST gap
    (held against a fault: an altered token, a wrong page) and the MEAN
    gap (held against a lower precision: this block's sandwich norms
    widen every rounding, and its widest gap in bf16 lies within 1.4x
    of the fp8 control's, its mean 12x under it; PERF.md section 2)."""
    widest = base._gap_number(gaps, picks, limits["served_logit_gap"])
    return [widest, {
        "name": "mean gap of a served token's logit below the "
                "reference's best, the same tokens",
        "value": widest["mean_gap"] if gaps else 1e9,
        "limit": limits["served_logit_mean_gap"]}]


def check(system: System, result: Dict) -> List[Dict]:
    """``hybrid_moe_serving.check`` against ``references/trinity.py``,
    with the mean gap judged beside the widest."""
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
    out.extend(_gap_numbers(gaps, picks, cfg["limits"]))
    out.append({"name": "share of (position, layer) expert choices of an "
                        "untimed full forward of the program's model that "
                        "differ from the reference's",
                "value": moe._flip_share(prog, r.chosen()), "limit": 1.0})
    return out


def control(system: System, result: Dict) -> Dict:
    """``hybrid_moe_serving.control`` against ``references/trinity.py``:
    the reference in the program's place in fp8, beside the program,
    over the same longest requests."""
    cfg, seed = system.cfg, system.seed
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
    return {"program": _gap_numbers(prog, picks, cfg["limits"]),
            "control": _gap_numbers(ctl, picks, cfg["limits"]),
            "program_gap_quantiles": dict(zip(map(str, q), np.percentile(
                np.concatenate(prog), q).tolist())),
            "control_gap_quantiles": dict(zip(map(str, q), np.percentile(
                np.concatenate(ctl), q).tolist())),
            "program_choice_flips": moe._flip_share(prog_choices, want),
            "control_choice_flips": moe._flip_share(lo.chosen(), want)}
