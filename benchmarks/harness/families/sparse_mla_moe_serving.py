"""A latent-attention expert decoder whose queries attend to the rows of
the LATENT cache a learned index chooses (here ``deepseek-v3.2-exp``: a
query latent, 64 index heads of 128 off it that keep 2,048 rows, a
group-limited sigmoid router beside a shared expert), through
``Config.enable_paged_kv`` -> ``create_predictor`` -> ``ServingEngine``
in its default mode. The model is ``MLAMoEForCausalLM`` with the fields
of ``FIELDS`` set; this module maps the source's key names
(``q_lora_rank``, ``index_n_heads``, ``n_group``, ...) to
``MLAMoEConfig`` and reuses ``sparse_moe_serving.System`` for everything
that drives and reads the engine (the routing counters, the pool bytes a
context token, the share of rows kept, the prefills' padding, the probe
of the program's model), and its ``check``'s numbers. The reference is
``references/deepseek_v32.py``.

The configuration is one holder's share of an expert-parallel layer
(``configs/deepseek-v3.2-exp.json``: ``n_routed_experts`` held of
``router_experts``, from ``expert_offset``; a slice of the vocabulary),
cut in depth. ``check`` is ``sparse_moe_serving.check``'s list against
this reference over the requests with the LONGEST contexts: the widest
and the mean logit gap, 0 dropped pairs, the decode kernel by name in
the decode program, ``kept_keys_wrong`` twice (the served decode steps'
own count on the device, and the probe's), ``selection_agreement``; and,
listed and never judged, the share of expert choices and of GROUP
choices (the groups the router kept) of the probe that differ from the
reference's.

The model's fields are checked when this module is imported: a tree
whose ``MLAMoEConfig`` lacks them fails here, before anything is
allocated.
"""
from __future__ import annotations

import dataclasses
import json
import re
import time
from typing import Dict, List, Tuple

import numpy as np

from paddle_tpu.models.mla_moe import MLAMoEConfig, MLAMoEForCausalLM

from .. import weights
from ..laps import Laps
from ...references import deepseek_v32 as ref
from . import llama_serving as base
from . import mla_moe_serving as moe
from . import sparse_moe_serving as sparse

FIELDS = ("q_lora_rank", "index_heads", "index_head_dim", "index_topk",
          "n_group", "topk_group", "head_on_last_row")
_missing = sorted(set(FIELDS)
                  - {f.name for f in dataclasses.fields(MLAMoEConfig)})
if _missing:
    raise ImportError("this tree's MLAMoEConfig lacks " +
                      ", ".join(_missing) + ": it cannot build a latent-"
                      "attention layer with a query latent whose index "
                      "selects rows of the latent cache, nor a router "
                      "that keeps groups of experts")

KERNEL = "mla_paged_sparse_decode_attention"     # in the decode program
_LEAF = {"input_layernorm": "in_norm", "self_attn.q_a_proj": "q_a",
         "self_attn.q_a_norm": "q_a_norm", "self_attn.q_b_proj": "q_b",
         "self_attn.kv_a_proj": "kva", "self_attn.kv_a_norm": "kv_norm",
         "self_attn.kv_b_proj": "kvb", "self_attn.o_proj": "o",
         "self_attn.index_q_proj": "iq", "self_attn.index_k_proj": "ik",
         "self_attn.index_w_proj": "iw",
         "self_attn.index_k_norm": "ik_norm",
         "self_attn.index_k_norm_bias": "ik_norm_bias",
         "post_attention_layernorm": "post_norm",
         "mlp.gate_proj": "gate", "mlp.up_proj": "up",
         "mlp.down_proj": "down", "mlp.gate.weight": "router",
         "mlp.gate.bias": "router_bias", "mlp.shared_gate": "sh_gate",
         "mlp.shared_up": "sh_up", "mlp.shared_down": "sh_down"}
_STACK = {"mlp.w_gate": "gate", "mlp.w_up": "up", "mlp.w_down": "down"}


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


def model_config(cfg: Dict, max_len: int) -> MLAMoEConfig:
    if cfg["scoring_func"] != "sigmoid" or not cfg["norm_topk_prob"] \
            or cfg["topk_method"] != "noaux_tc" \
            or cfg["moe_layer_freq"] != 1 or cfg["attention_bias"]:
        raise ValueError("the model scores experts by a sigmoid, chooses "
                         "them within kept groups on score + bias, "
                         "renormalises the chosen weights, has an expert "
                         "layer in every layer after the dense ones and "
                         "no biases; the file says otherwise")
    return MLAMoEConfig(
        vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
        num_layers=cfg["num_hidden_layers"],
        num_heads=cfg["num_attention_heads"],
        q_lora_rank=cfg["q_lora_rank"], kv_lora_rank=cfg["kv_lora_rank"],
        qk_nope_head_dim=cfg["qk_nope_head_dim"],
        qk_rope_head_dim=cfg["qk_rope_head_dim"],
        v_head_dim=cfg["v_head_dim"],
        intermediate_size=cfg["intermediate_size"],
        moe_intermediate_size=cfg["moe_intermediate_size"],
        num_experts=cfg["router_experts"],
        num_local_experts=cfg["n_routed_experts"],
        expert_offset=cfg["expert_offset"],
        num_experts_per_tok=cfg["num_experts_per_tok"],
        n_group=cfg["n_group"], topk_group=cfg["topk_group"],
        num_shared_experts=cfg["n_shared_experts"],
        first_k_dense_replace=cfg["first_k_dense_replace"],
        routed_scaling_factor=cfg["routed_scaling_factor"],
        use_qk_norm=False, index_heads=cfg["index_n_heads"],
        index_head_dim=cfg["index_head_dim"],
        index_topk=cfg["index_topk"], head_on_last_row=True,
        max_position_embeddings=max_len, rope_theta=cfg["rope_theta"],
        rope_scaling={k: v for k, v in cfg["rope_scaling"].items()
                      if k != "type"},
        rms_norm_eps=cfg["rms_norm_eps"],
        initializer_range=cfg["initializer_range"],
        dtype=cfg["torch_dtype"])


class System(sparse.System):
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
        # temporaries for a stack of 16 experts one group at a time
        groups: Dict[str, List] = {}
        for n, p in model.named_parameters():
            m = re.match(r"layers\.(\d+)\.", n)
            groups.setdefault(m.group(1) if m else n, []).append((n, p))
        for part in groups.values():
            weights.load(part, {n: names_of(n, cfg) for n, _ in part},
                         table, seed, cfg["torch_dtype"])
        laps.mark("model_and_weights")
        conf = Config().set_model(model).enable_paged_kv(
            page_size=srv["page_size"])
        conf.max_length = self.M
        pred = create_predictor(conf)
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
        # the engine's own instruments, as sparse_moe_serving reads them
        reg = get_registry()
        self._kv_gauge = reg.gauge(sparse.hybrid.KV_GAUGE)
        self._kv_ratio: List[float] = []
        self._share_gauge = reg.gauge(sparse.SHARE_GAUGE)
        self._share: List[float] = []
        self._prefill_tokens = reg.counter(sparse.PREFILL_TOKENS,
                                           labelnames=("kind",))
        self._prefill_base = self._prefill_counts()

    def kernels_present(self) -> Dict[tuple, bool]:
        """(kernel, program) -> is its call in the program's text: the
        decode program alone holds a kernel of this model's own."""
        out = {}
        for site in self.eng.program_sites():
            if site[0] == "decode":
                text = self.eng.lowered_text(site) or ""
                out[KERNEL, "_".join(map(str, site))] = \
                    f'kernel_name = "{KERNEL}"' in text
        return out

    def program_probe(self, picks) -> Tuple[List[np.ndarray],
                                            List[List[np.ndarray]]]:
        """``sparse_moe_serving.System.program_probe`` (per expert layer
        the probe's chosen experts, and ``kept[layer][request]``), and
        per expert layer the groups the probe's router kept, all
        requests in order, in ``self.probe_groups``."""
        import jax
        import jax.numpy as jnp

        from paddle_tpu.autograd import no_grad
        from paddle_tpu.distributed.engine import bind_params
        from paddle_tpu.observability import moestats
        from paddle_tpu.ops.sparse_attention import collect_selection

        self.eng.release_pools()
        model, params = self._model, list(self._model.parameters())
        seqs = moe._sequences(picks)
        # every sequence at the engine's longest context, every probe as
        # many rows: one program, the same in every run
        S = self.M
        rows = max(len(q.tokens) for q in picks)

        def fwd(pvals, ids, lo):
            with no_grad(), bind_params(params, pvals), \
                    collect_selection() as kept:
                moestats.begin()
                try:
                    model.forward(ids)
                finally:
                    recs = moestats.drain()
            recs = [r for r in recs if "choices" in r]
            return ([r["choices"] for r in recs],
                    [r["groups"] for r in recs],
                    [jax.lax.dynamic_slice_in_dim(k[0], lo, rows, 0)
                     for k in kept])

        f = jax.jit(fwd)
        pvals = tuple(p._value for p in params)
        choices, groups, kept = [], [], []
        for seq, q in zip(seqs, picks):
            ids = jnp.asarray(np.pad(seq, (0, S - len(seq)))[None]
                              .astype(np.int32))
            # the last prompt row, then every served token but the last;
            # a probe that would pass the end starts earlier
            lo = min(len(q.prompt) - 1, S - rows)
            c, g, k = f(pvals, ids, jnp.int32(lo))
            skip = len(q.prompt) - 1 - lo
            choices.append([np.asarray(x)[:len(seq)] for x in c])
            groups.append([np.asarray(x)[:len(seq)] for x in g])
            kept.append([np.asarray(x)[skip:skip + len(q.tokens)]
                         for x in k])
        self.probe_groups = [np.concatenate(layer)
                             for layer in zip(*groups)]
        return ([np.concatenate(layer) for layer in zip(*choices)],
                [list(layer) for layer in zip(*kept)])


def build(cfg, traffic, plan, seed, devices) -> System:
    return System(cfg, traffic, plan, seed, devices)


sample = sparse.sample


def check(system: System, result: Dict) -> List[Dict]:
    """``sparse_moe_serving.check``'s numbers against
    ``references/deepseek_v32.py``, and the share of group choices that
    differ (listed, never judged: limit 1)."""
    import jax

    cfg, seed = system.cfg, system.seed
    vocab, topk = cfg["vocab_size"], cfg["index_topk"]
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
    prog, kept = system.program_probe(picks)
    system.free()
    print(f"the program's expert choices and kept rows on {len(picks)} "
          f"requests took {time.perf_counter() - t0:.1f}s", flush=True)
    out = [{"name": f"kernel {k} missing from program {prog}",
            "value": int(not v), "limit": 0}
           for (k, prog), v in kernels.items()]
    out.append({"name": "routed pairs the expert layers dropped",
                "value": host["moe_dropped_pairs"], "limit": 0})
    out.append({"name": "finished requests with a wrong token count or a "
                        "token outside the vocabulary",
                "value": len(bad), "limit": 0})
    r = ref.ServeReference(cfg, seed)
    logits = r.logits([(q.prompt, q.tokens) for q in picks])
    gaps = [ref.served_gap(lg, q.tokens) for lg, q in zip(logits, picks)]
    out.extend(sparse._gap_numbers(gaps, picks, cfg["limits"]))
    rows = host.get("decode_selecting_rows", 0)
    out.append({"name": f"kept_keys_wrong, the decode steps' own count: "
                        f"(row, layer) pairs of {rows} the served steps "
                        f"selected for whose kept count is not min(t + 1, "
                        f"{topk}) (1 where none was counted)",
                "value": host["decode_kept_keys_wrong"] if rows else 1,
                "limit": cfg["limits"]["kept_keys_wrong"]})
    out.extend(sparse.selection_numbers(kept, r.kept, picks, topk,
                                        cfg["limits"]))
    for what, mine, want in (
            ("expert", prog, r.chosen()),
            ("group", system.probe_groups, r.kept_groups())):
        out.append({"name": f"share of (position, layer) {what} choices "
                            "of an untimed full forward of the program's "
                            "model that differ from the reference's",
                    "value": moe._flip_share(mine, want), "limit": 1.0})
    return out


def control(system: System, result: Dict) -> Dict:
    """``sparse_moe_serving.control``'s two controls against this
    reference: fp8, and the selection SKIPPED."""
    cfg, seed = system.cfg, system.seed
    topk, limits = cfg["index_topk"], cfg["limits"]
    picks = sample(result["finished"], system.traffic["check_requests"])
    prog_choices, prog_kept = system.program_probe(picks)
    system.free()
    reqs = [(q.prompt, q.tokens) for q in picks]
    r = ref.ServeReference(cfg, seed)
    logits = r.logits(reqs)
    want, want_kept = r.chosen(), r.kept
    prog = [ref.served_gap(lg, q.tokens) for lg, q in zip(logits, picks)]
    q = (50, 90, 99, 100)
    out = {"program": sparse._gap_numbers(prog, picks, limits)
           + sparse.selection_numbers(prog_kept, want_kept, picks, topk,
                                      limits),
           "program_gap_quantiles": dict(zip(map(str, q), np.percentile(
               np.concatenate(prog), q).tolist())),
           "program_choice_flips": moe._flip_share(prog_choices, want),
           "program_group_flips": moe._flip_share(system.probe_groups,
                                                  r.kept_groups())}
    for name, precision in (("control", "fp8"), ("dense_control", "dense")):
        lo = ref.ServeReference(cfg, seed, precision)
        low = lo.logits(reqs)
        ctl = [ref.served_gap(lg, lw.argmax(-1))
               for lg, lw in zip(logits, low)]
        out[name] = sparse._gap_numbers(ctl, picks, limits) \
            + sparse.selection_numbers(lo.kept, want_kept, picks, topk,
                                       limits)
        out[f"{name}_gap_quantiles"] = dict(zip(map(str, q), np.percentile(
            np.concatenate(ctl), q).tolist()))
        out[f"{name}_choice_flips"] = moe._flip_share(lo.chosen(), want)
    return out
