"""A latent-attention mixture-of-experts decoder (here ``sarvam-105b``:
``MLAMoEConfig`` takes every one of its sizes as data) through
``Config.enable_paged_kv`` -> ``create_predictor`` -> ``ServingEngine``
in its default mode, as ``llama_serving`` builds a dense decoder. Serves
``closed_loop`` and ``open_loop`` traffic (the interface is in
``traffic/serving_common.py``; the loop-driving methods are
``llama_serving.System``'s).

The configuration is one holder's share of an expert-parallel layer
(``configs/sarvam-105b.json``: ``num_experts`` held of ``router_experts``,
from ``expert_offset``; a slice of the vocabulary). Beside the logits
gap, ``check`` holds the expert layer to 0 dropped pairs, the decode
program to the kernel ``mla_paged_decode_attention``, and lists the
share of (position, layer) expert choices on the checked requests that
differ from the float32 reference's: the program's are read from one
full forward of ITS model over the same tokens (bf16, the router in
float32), after the window. A near-tie between the 8th and 9th score
flips a choice under bf16 rounding of the layer's input; the number says
how often.

The model is imported when this module is: a tree without it fails
here, before anything is allocated.
"""
from __future__ import annotations

import json
import re
import time
from typing import Dict, List

import numpy as np

from paddle_tpu.models.mla_moe import MLAMoEConfig, MLAMoEForCausalLM

from .. import weights
from ..laps import Laps
from ...references import sarvam as ref
from . import llama_serving as base

KERNEL = "mla_paged_decode_attention"
_ATTN = {"input_layernorm": "in_norm", "self_attn.q_proj": "q",
         "self_attn.q_norm": "q_norm", "self_attn.kv_a_proj": "kva",
         "self_attn.kv_a_norm": "kv_norm", "self_attn.kv_b_proj": "kvb",
         "self_attn.o_proj": "o", "post_attention_layernorm": "post_norm",
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
    return f"l.{i}.{_ATTN[rest]}"


def model_config(cfg: Dict, max_len: int) -> MLAMoEConfig:
    return MLAMoEConfig(
        vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
        num_layers=cfg["num_hidden_layers"],
        num_heads=cfg["num_attention_heads"],
        kv_lora_rank=cfg["kv_lora_rank"],
        qk_nope_head_dim=cfg["qk_nope_head_dim"],
        qk_rope_head_dim=cfg["qk_rope_head_dim"],
        v_head_dim=cfg["v_head_dim"],
        intermediate_size=cfg["intermediate_size"],
        moe_intermediate_size=cfg["moe_intermediate_size"],
        num_experts=cfg["router_experts"],
        num_local_experts=cfg["num_experts"],
        expert_offset=cfg["expert_offset"],
        num_experts_per_tok=cfg["num_experts_per_tok"],
        num_shared_experts=cfg["num_shared_experts"],
        first_k_dense_replace=cfg["first_k_dense_replace"],
        routed_scaling_factor=cfg["routed_scaling_factor"],
        use_qk_norm=cfg["use_qk_norm"], max_position_embeddings=max_len,
        rope_theta=cfg["rope_theta"],
        rope_scaling={k: v for k, v in cfg["rope_scaling"].items()
                      if k != "type"},
        rms_norm_eps=cfg["rms_norm_eps"],
        initializer_range=cfg["initializer_range"],
        dtype=cfg["torch_dtype"])


class System(base.System):
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
        if (mcfg.q_head_dim != cfg["q_head_dim"] or mcfg.kv_lora_rank
                + mcfg.qk_rope_head_dim != cfg["head_dim"]):
            raise ValueError("q_head_dim / head_dim of the file and of "
                             "the model differ")
        laps = Laps()
        paddle.set_default_dtype(cfg["torch_dtype"])
        paddle.seed(seed % (2 ** 31))
        with paddle.LazyGuard():
            model = MLAMoEForCausalLM(mcfg)
        named = list(model.named_parameters())
        table = ref.leaf_table(cfg)
        # a layer at a time: the generator's float32 temporaries for a
        # stack of 32 experts are 1 GiB, and all layers at once would
        # hold several beside 10 GiB of weights
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

    def kernels_present(self) -> Dict[str, bool]:
        out = {}
        for site in self.eng.program_sites():
            if site[0] in ("decode", "prefill"):
                text = self.eng.lowered_text(site) or ""
                out["_".join(map(str, site))] = \
                    f'kernel_name = "{KERNEL}"' in text
        return out

    def moe_host(self) -> Dict:
        """The routing counters of the decode steps so far, as host
        readings (``harness/reducers``): one fetch, after the window.
        The decode program routes every row of its batch, live or not,
        so ``moe_pairs_per_expert`` is pairs per held expert per step of
        ``max_batch`` rows: how evenly the router spreads its pairs
        over the holders (8 = this holder's quarter of 128 x 8), and
        the work an expert gets only while the batch is full (the cell
        runs at 99.6-99.8% occupancy)."""
        st = self.eng.moe_stats()
        out = {"moe_dropped_pairs": st["dropped"]}
        live = st["tokens"] > 0         # the expert layers that decoded
        if live.any():
            pairs = st["pairs"][live].astype(np.float64)
            steps = st["tokens"][live] / float(self.max_batch)
            mean = pairs.mean(axis=1)
            out["moe_pairs_per_expert"] = float(np.mean(mean / steps))
            out["moe_load_max_over_mean"] = float(np.mean(
                pairs.max(axis=1) / np.maximum(mean, 1e-9)))
        return out

    def program_choices(self, seqs: List[np.ndarray]) -> List[np.ndarray]:
        """Per expert layer, the experts the PROGRAM's model chooses at
        every position of ``seqs`` [rows, k]: one full forward a
        sequence (no cache, the unabsorbed form: NOT the timed decode
        path), the router's choices collected through
        ``observability.moestats``. Frees the engine's pools first."""
        import jax
        import jax.numpy as jnp

        from paddle_tpu.autograd import no_grad
        from paddle_tpu.distributed.engine import bind_params
        from paddle_tpu.observability import moestats

        self.eng.release_pools()
        model, params = self._model, list(self._model.parameters())

        def fwd(pvals, ids):
            with no_grad(), bind_params(params, pvals):
                moestats.begin()
                try:
                    model.forward(ids)
                finally:
                    recs = moestats.drain()
            return [r["choices"] for r in recs]

        f = jax.jit(fwd)
        pvals = tuple(p._value for p in params)
        per_seq = []
        for seq in seqs:
            # every sequence at the context's length: one program
            ids = jnp.asarray(np.pad(seq, (0, self.M - len(seq)))[None]
                              .astype(np.int32))
            per_seq.append([np.asarray(c)[:len(seq)] for c in f(pvals, ids)])
        return [np.concatenate(layer) for layer in zip(*per_seq)]


def build(cfg, traffic, plan, seed, devices) -> System:
    return System(cfg, traffic, plan, seed, devices)


def _sequences(picks) -> List[np.ndarray]:
    return [np.concatenate([q.prompt, q.tokens[:-1]]) for q in picks]


def _flip_share(prog: List[np.ndarray], want: List[np.ndarray]) -> float:
    """Share of (position, layer) pairs whose chosen SET differs."""
    differ = [np.any(np.sort(a, -1) != np.sort(b, -1), axis=-1)
              for a, b in zip(prog, want)]
    return float(np.mean(np.concatenate(differ))) if differ else 1.0


def check(system: System, result: Dict) -> List[Dict]:
    """As ``llama_serving.check``, and: 0 dropped pairs, the kernel in
    the decode program, the share of expert choices that differ from
    the reference's (listed, never judged: limit 1). Puts the routing
    counters among the host readings for the reducers, which run after
    this."""
    import jax

    cfg, seed = system.cfg, system.seed
    vocab = cfg["vocab_size"]
    kernels = system.kernels_present() \
        if jax.devices()[0].platform == "tpu" else {}
    moe = system.moe_host()
    result["host"].update(moe)
    print("host: " + json.dumps({k: v for k, v in result["host"].items()
                                 if k != "decode_rows"}), flush=True)
    picks = base.sample(result["finished"], seed,
                        system.traffic["check_requests"])
    bad = [r for r in result["finished"]
           if len(r.tokens) != r.n_out
           or not ((r.tokens >= 0) & (r.tokens < vocab)).all()]
    t0 = time.perf_counter()
    prog = system.program_choices(_sequences(picks))
    system.free()
    print(f"the program's expert choices on {len(picks)} requests took "
          f"{time.perf_counter() - t0:.1f}s", flush=True)
    out = [{"name": f"kernel {KERNEL} missing from program {k}",
            "value": int(not v), "limit": 0 if k == "decode" else 1}
           for k, v in kernels.items()]
    out.append({"name": "routed pairs the expert layers dropped",
                "value": moe["moe_dropped_pairs"], "limit": 0})
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
                "value": _flip_share(prog, r.chosen()), "limit": 1.0})
    return out


def control(system: System, result: Dict) -> Dict:
    """As ``llama_serving.control``: the reference in the program's
    place in fp8, beside the program."""
    cfg, seed = system.cfg, system.seed
    limit = cfg["limits"]["served_logit_gap"]
    picks = base.sample(result["finished"], seed,
                        system.traffic["check_requests"])
    prog_choices = system.program_choices(_sequences(picks))
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
            "program_choice_flips": _flip_share(prog_choices, want),
            "control_choice_flips": _flip_share(lo.chosen(), want)}
