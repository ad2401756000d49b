"""A decoder whose attention keeps the keys a learned index chooses,
with routed experts behind a softmax router (here
``keye-vl-2.0-30b-a3b``'s language model), through
``Config.enable_paged_kv`` -> ``create_predictor`` -> ``ServingEngine``
in its default mode. The model is ``HybridMoEForCausalLM`` with every
layer of the ``"sparse"`` kind; this module maps the source's key names
(``sa_config``, ``num_experts``, ``norm_topk_prob``, ...) to
``HybridMoEConfig`` and reuses ``hybrid_moe_serving.System`` for
everything that drives and reads the engine. The reference is
``references/keye.py``.

The configuration is one holder's share of an expert-parallel layer
(``configs/keye-vl-2.0-30b-a3b.json``: ``num_experts`` held of
``router_experts``, from ``expert_offset``; the vocabulary whole), cut in
depth. ``check`` is ``hybrid_moe_serving.check`` over the requests with
the LONGEST contexts (their rows select among the most keys), with the
mean gap judged beside the widest as ``afmoe_serving`` does, the decode
kernel by name in the decode program, and three numbers of the
SELECTION. One comes from the timed path itself:

- ``kept_keys_wrong``, the decode steps' own count: every decode step
  the engine ran adds, on the device beside the routing counters, the
  (row, layer) pairs whose kept count is not ``min(t + 1, topk)``
  (``eng.selection_stats()``, fetched once after the window; limit 0).

Two come from the PROBE, an untimed full forward of the program's model
over the checked requests (no cache, the prefill form at one length;
``models.hybrid_moe.collect_selection``) at the rows that produced the
served tokens, and are named as the probe's. They hold the model and the
prefill form's selection, not the timed programs, which the logit gaps
hold:

- the probe's ``kept_keys_wrong`` (limit 0), counted as above from the
  sets it kept;
- ``selection_agreement``: |probe's set and reference's| / |either|,
  mean over (row, layer); a near-tie at the topk-th index score flips a
  key as one at the 8th router score flips an expert, so it is under 1
  and judged against a limit from readings.

Beside mimo's host readings it reports ``sparse_selected_share`` (the
gauge ``paddle_tpu_serving_sparse_selected_share``, read once a step of
the traced stretch, mean, in %) and ``prefill_padding_share`` (as
``afmoe_serving``).

The model's fields are checked when this module is imported: a tree
whose ``HybridMoEConfig`` lacks them fails here, before anything is
allocated.
"""
from __future__ import annotations

import dataclasses
import json
import re
import time
from typing import Dict, List, Tuple

import numpy as np

from paddle_tpu.models.hybrid_moe import (HybridMoEConfig,
                                          HybridMoEForCausalLM)

from .. import weights
from ..laps import Laps
from ...references import keye as ref
from . import hybrid_moe_serving as hybrid
from . import llama_serving as base
from . import mla_moe_serving as moe

FIELDS = ("index_heads", "index_head_dim", "index_topk",
          "router_score_func", "qk_norm", "head_on_last_row")
_missing = sorted(set(FIELDS)
                  - {f.name for f in dataclasses.fields(HybridMoEConfig)})
if _missing:
    raise ImportError("this tree's HybridMoEConfig lacks " +
                      ", ".join(_missing) + ": it cannot build a layer "
                      "whose attention selects keys by a learned index")

KERNEL = "paged_sparse_decode_attention"     # in the decode program
_LEAF = {"input_layernorm": "in_norm", "self_attn.q_proj": "q",
         "self_attn.k_proj": "k", "self_attn.v_proj": "v",
         "self_attn.o_proj": "o", "self_attn.q_norm": "q_norm",
         "self_attn.k_norm": "k_norm", "self_attn.index_q_proj": "iq",
         "self_attn.index_k_proj": "ik", "self_attn.index_w_proj": "iw",
         "self_attn.index_k_norm": "ik_norm",
         "self_attn.index_k_norm_bias": "ik_norm_bias",
         "post_attention_layernorm": "post_norm",
         "mlp.gate.weight": "router"}
_STACK = {"mlp.w_gate": "gate", "mlp.w_up": "up", "mlp.w_down": "down"}
SHARE_GAUGE = "paddle_tpu_serving_sparse_selected_share"
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
    n, sa = cfg["num_hidden_layers"], cfg["sa_config"]
    if sa["indexer_num_kv_heads"] != 1 or not cfg["norm_topk_prob"] \
            or cfg["mlp_only_layers"] or cfg["decoder_sparse_step"] != 1:
        raise ValueError("the model holds one index key a position, "
                         "renormalises its chosen experts' weights and "
                         "has an expert layer in every layer; the file "
                         "says otherwise")
    return HybridMoEConfig(
        vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
        attention_kinds=["sparse"] * n, ffn_kinds=["experts"] * n,
        num_heads=cfg["num_attention_heads"],
        num_kv_heads=cfg["num_key_value_heads"],
        window_num_kv_heads=cfg["num_key_value_heads"],
        qk_head_dim=cfg["head_dim"], v_head_dim=cfg["head_dim"],
        rotary_dim=cfg["head_dim"], rope_theta=cfg["rope_theta"],
        rotary_kinds=("sparse",), full_sink=False, window_sink=False,
        value_scale=1.0, intermediate_size=cfg["intermediate_size"],
        moe_intermediate_size=cfg["moe_intermediate_size"],
        num_experts=cfg["router_experts"],
        num_local_experts=cfg["num_experts"],
        expert_offset=cfg["expert_offset"],
        num_experts_per_tok=cfg["num_experts_per_tok"],
        routed_scaling_factor=1.0, router_score_func="softmax",
        num_shared_experts=0, qk_norm=True, head_on_last_row=True,
        index_heads=sa["indexer_num_heads"],
        index_head_dim=sa["indexer_head_dim"], index_topk=sa["topk"],
        attention_block=sa["q_chunk_size"],
        max_position_embeddings=max_len,
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
        # float32 temporaries (the embedding's are 1.2 GB) one group at
        # a time
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
        # the engine's own instruments (made by the engine above;
        # get-or-create hands the same ones back)
        reg = get_registry()
        self._kv_gauge = reg.gauge(hybrid.KV_GAUGE)
        self._kv_ratio: List[float] = []
        self._share_gauge = reg.gauge(SHARE_GAUGE)
        self._share: List[float] = []
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
        """(kernel, program) -> is its call in the program's text: the
        decode program alone holds a kernel of this model's own."""
        out = {}
        for site in self.eng.program_sites():
            if site[0] == "decode":
                text = self.eng.lowered_text(site) or ""
                out[KERNEL, "_".join(map(str, site))] = \
                    f'kernel_name = "{KERNEL}"' in text
        return out

    def decode_rows(self) -> List[int]:
        """As ``hybrid_moe_serving`` reads its gauge: once a step of the
        traced stretch, the last retired round's."""
        v = self._share_gauge.value()
        if v:
            self._share.append(float(v))
        return super().decode_rows()

    def kv_host(self) -> Dict:
        """mimo's reading, the share of keys kept, the prefills' padding
        and the decode steps' own count of rows that kept a wrong number
        of keys (``eng.selection_stats()``: read before the pools go);
        each left out where the engine counted nothing."""
        out = super().kv_host()
        sel = self.eng.selection_stats()
        if sel:
            out["decode_kept_keys_wrong"] = sel["kept_keys_wrong"]
            out["decode_selecting_rows"] = sel["rows"]
        if self._share:
            out["sparse_selected_share"] = 100.0 * float(
                np.mean(self._share))
        now = self._prefill_counts()
        prompt, bkt = (now[k] - self._prefill_base[k]
                       for k in ("prompt", "bucket"))
        if bkt > 0:
            out["prefill_padding_share"] = 100.0 * (1.0 - prompt / bkt)
        return out

    def program_probe(self, picks) -> Tuple[List[np.ndarray],
                                            List[List[np.ndarray]]]:
        """What the PROGRAM's model chooses over the checked requests,
        from one full forward a request (no cache, the prefill form: NOT
        the timed decode path): per layer the chosen experts at every
        position, all requests in order, as ``program_choices``; and
        ``kept[layer][request]``, the kept sets ``[served rows, padded
        length]`` of the rows that produced the served tokens. Frees the
        engine's pools first."""
        import jax
        import jax.numpy as jnp

        from paddle_tpu.autograd import no_grad
        from paddle_tpu.distributed.engine import bind_params
        from paddle_tpu.models.hybrid_moe import collect_selection
        from paddle_tpu.observability import moestats

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
            return ([r["choices"] for r in recs if "choices" in r],
                    [jax.lax.dynamic_slice_in_dim(k[0], lo, rows, 0)
                     for k in kept])

        f = jax.jit(fwd)
        pvals = tuple(p._value for p in params)
        choices, kept = [], []
        for seq, q in zip(seqs, picks):
            ids = jnp.asarray(np.pad(seq, (0, S - len(seq)))[None]
                              .astype(np.int32))
            # the last prompt row, then every served token but the last;
            # a probe that would pass the end starts earlier
            lo = min(len(q.prompt) - 1, S - rows)
            c, k = f(pvals, ids, jnp.int32(lo))
            skip = len(q.prompt) - 1 - lo
            choices.append([np.asarray(x)[:len(seq)] for x in c])
            kept.append([np.asarray(x)[skip:skip + len(q.tokens)]
                         for x in k])
        return ([np.concatenate(layer) for layer in zip(*choices)],
                [list(layer) for layer in zip(*kept)])


def build(cfg, traffic, plan, seed, devices) -> System:
    return System(cfg, traffic, plan, seed, devices)


sample = hybrid.sample


def _gap_numbers(gaps, picks, limits: Dict) -> List[Dict]:
    """The widest gap (held against a fault) and the mean gap (held
    against a lower precision), as ``afmoe_serving._gap_numbers``."""
    widest = base._gap_number(gaps, picks, limits["served_logit_gap"])
    return [widest, {
        "name": "mean gap of a served token's logit below the "
                "reference's best, the same tokens",
        "value": widest["mean_gap"] if gaps else 1e9,
        "limit": limits["served_logit_mean_gap"]}]


def selection_numbers(kept, want, picks, topk: int, limits: Dict
                      ) -> List[Dict]:
    """The probe's two judged numbers of the selection (``check`` adds
    the decode steps' own count). ``kept`` and ``want``:
    ``[layer][request]`` bool ``[served rows, >= context]``, the
    program's (or a control's) kept sets and the reference's, at the
    rows that produced the served tokens (row r of a request is position
    ``len(prompt) - 1 + r``)."""
    wrong, rows, agree = 0, 0, []
    for kl, wl in zip(kept, want):
        for k, w, q in zip(kl, wl, picks):
            n = min(k.shape[1], w.shape[1])
            k, w = k[:, :n], w[:, :n]
            t = len(q.prompt) - 1 + np.arange(len(k))
            wrong += int(np.sum(k.sum(-1) != np.minimum(t + 1, topk)))
            rows += len(k)
            agree.append((k & w).sum(-1) / np.maximum((k | w).sum(-1), 1))
    agreement = float(np.mean(np.concatenate(agree))) if agree else 0.0
    return [{"name": f"probe's kept_keys_wrong: (row, layer) pairs of "
                     f"{rows} of an untimed full forward whose kept count "
                     f"is not min(t + 1, {topk})",
             "value": wrong, "limit": limits["kept_keys_wrong"]},
            {"name": "1 - selection_agreement (|the probe's kept set and "
                     "the reference's| / |either|, mean over the same "
                     "pairs)",
             "value": 1.0 - agreement,
             "limit": 1.0 - limits["selection_agreement"]}]


def check(system: System, result: Dict) -> List[Dict]:
    """``hybrid_moe_serving.check`` against ``references/keye.py``, with
    the mean gap and the selection's three numbers judged as well."""
    import jax

    cfg, seed = system.cfg, system.seed
    vocab, topk = cfg["vocab_size"], cfg["sa_config"]["topk"]
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
    print(f"the program's expert choices and kept keys on {len(picks)} "
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
    out.extend(_gap_numbers(gaps, picks, cfg["limits"]))
    rows = host.get("decode_selecting_rows", 0)
    out.append({"name": f"kept_keys_wrong, the decode steps' own count: "
                        f"(row, layer) pairs of {rows} the served steps "
                        f"selected for whose kept count is not min(t + 1, "
                        f"{topk}) (1 where none was counted)",
                "value": host["decode_kept_keys_wrong"] if rows else 1,
                "limit": cfg["limits"]["kept_keys_wrong"]})
    out.extend(selection_numbers(kept, r.kept, picks, topk, cfg["limits"]))
    out.append({"name": "share of (position, layer) expert choices of an "
                        "untimed full forward of the program's model that "
                        "differ from the reference's",
                "value": moe._flip_share(prog, r.chosen()), "limit": 1.0})
    return out


def control(system: System, result: Dict) -> Dict:
    """Two controls beside the program, over the same longest requests:
    the reference in the program's place in fp8 (the nearest precision
    below the configuration's), and the reference with the selection
    SKIPPED (every earlier key attended to). Each gives the judged
    numbers the program gives."""
    cfg, seed = system.cfg, system.seed
    topk, limits = cfg["sa_config"]["topk"], cfg["limits"]
    picks = sample(result["finished"], system.traffic["check_requests"])
    prog_choices, prog_kept = system.program_probe(picks)
    system.free()
    reqs = [(q.prompt, q.tokens) for q in picks]
    r = ref.ServeReference(cfg, seed)
    logits = r.logits(reqs)
    want, want_kept = r.chosen(), r.kept
    prog = [ref.served_gap(lg, q.tokens) for lg, q in zip(logits, picks)]
    q = (50, 90, 99, 100)
    out = {"program": _gap_numbers(prog, picks, limits)
           + selection_numbers(prog_kept, want_kept, picks, topk, limits),
           "program_gap_quantiles": dict(zip(map(str, q), np.percentile(
               np.concatenate(prog), q).tolist())),
           "program_choice_flips": moe._flip_share(prog_choices, want)}
    for name, precision in (("control", "fp8"), ("dense_control", "dense")):
        lo = ref.ServeReference(cfg, seed, precision)
        low = lo.logits(reqs)
        ctl = [ref.served_gap(lg, lw.argmax(-1))
               for lg, lw in zip(logits, low)]
        out[name] = _gap_numbers(ctl, picks, limits) + selection_numbers(
            lo.kept, want_kept, picks, topk, limits)
        out[f"{name}_gap_quantiles"] = dict(zip(map(str, q), np.percentile(
            np.concatenate(ctl), q).tolist()))
        out[f"{name}_choice_flips"] = moe._flip_share(lo.chosen(), want)
    return out
