"""A Llama-shaped decoder (here Mistral-7B: ``LlamaConfig`` takes every
one of its sizes as data) through ``Config.enable_paged_kv`` ->
``create_predictor`` -> ``ServingEngine`` in its default mode, the way
``chip_smoke.py`` builds it. Serves ``open_loop`` and ``closed_loop``
traffic (the interface is in ``traffic/serving_common.py``).
"""
from __future__ import annotations

import gc
import re
from typing import Dict, List

import numpy as np

from .. import weights
from ..laps import Laps
from ...references import mistral as ref
from ..traffic.lengths import seeded

KERNEL = "paged_decode_attention"
_LAYER = {"input_layernorm.weight": "in_norm",
          "self_attn.q_proj.weight": "q", "self_attn.k_proj.weight": "k",
          "self_attn.v_proj.weight": "v", "self_attn.o_proj.weight": "o",
          "post_attention_layernorm.weight": "post_norm",
          "mlp.gate_proj.weight": "gate", "mlp.up_proj.weight": "up",
          "mlp.down_proj.weight": "down"}


def _names_of(pname: str) -> str:
    m = re.match(r"llama\.layers\.(\d+)\.(.+)$", pname)
    if m:
        return f"l.{m.group(1)}.{_LAYER[m.group(2)]}"
    return {"llama.embed_tokens.weight": "embed",
            "llama.norm.weight": "norm", "lm_head.weight": "lm_head"}[pname]


def bucket(n: int, lo: int = 64) -> int:
    """The engine's prefill lattice (``paddle_tpu/core/bucketing.py``):
    the smallest power-of-two multiple of ``lo`` that holds ``n``."""
    b = lo
    while b < n:
        b *= 2
    return b


class System:
    def __init__(self, cfg: Dict, traffic: Dict, plan: Dict, seed: int,
                 devices):
        import paddle_tpu as paddle
        from paddle_tpu.inference import (Config, ServingEngine,
                                          create_predictor)
        from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM

        self.cfg, self.traffic, self.seed = cfg, traffic, seed
        srv = cfg["serving"]
        self.max_batch = int(traffic["max_batch"])
        self.M = int(srv["max_length"])
        lcfg = LlamaConfig(
            vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
            num_layers=cfg["num_hidden_layers"],
            num_heads=cfg["num_attention_heads"],
            num_kv_heads=cfg["num_key_value_heads"],
            intermediate_size=cfg["intermediate_size"],
            max_position_embeddings=self.M,
            rope_theta=cfg["rope_theta"], rms_norm_eps=cfg["rms_norm_eps"],
            initializer_range=cfg["initializer_range"],
            tie_word_embeddings=cfg["tie_word_embeddings"],
            dtype=cfg["torch_dtype"])
        if lcfg.head_dim != cfg["head_dim"]:
            raise ValueError("head_dim of the file and of the model differ")
        laps = Laps()
        paddle.set_default_dtype(cfg["torch_dtype"])
        paddle.seed(seed % (2 ** 31))
        with paddle.LazyGuard():
            model = LlamaForCausalLM(lcfg)
        named = list(model.named_parameters())
        weights.load(named, {n: _names_of(n) for n, _ in named},
                     ref.leaf_table(cfg), seed, cfg["torch_dtype"])
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
        lo, hi = bucket(min(lens)), min(bucket(max(lens)), self.M)
        self.warm_buckets = [b for b in (lo << k for k in range(12))
                             if b <= hi]
        self._model, self._pred = model, pred

    def warm(self) -> Dict:
        """One prompt per prefill bucket the traffic can reach, and the
        decode program: every shape the window will use, and no other."""
        rng = seeded(self.seed, 9)
        for b in self.warm_buckets:
            n = min(b, self.M - 4)
            self.eng.submit(rng.integers(0, self.cfg["vocab_size"], n,
                                         dtype="int32"), max_new_tokens=3)
        self.eng.run()
        self.pop_finished()
        return {"pool_pages": self.eng.P, "warm_buckets": self.warm_buckets,
                "build_seconds": self.build_seconds}

    # -- what the serving loops drive -----------------------------------------
    def submit(self, prompt, n_out: int) -> int:
        return self.eng.submit(prompt, max_new_tokens=n_out)

    def step(self) -> None:
        self.eng.step()

    def busy(self) -> bool:
        return bool(self.eng.queue) or self.eng.num_active > 0

    def active(self) -> int:
        return self.eng.num_active

    def queued(self) -> int:
        return len(self.eng.queue)

    def pop_finished(self):
        fin = self.eng.finished
        out = [(rid, r.t_first_token, r.t_finish,
                np.asarray(r.new_tokens, np.int64))
               for rid, r in fin.items()]
        fin.clear()
        return out

    def decode_rows(self) -> List[int]:
        """Context length, after this step's write, of every row that
        will decode in the next step."""
        return [s.pos + len(s.req.new_tokens) for s in self.eng.slots
                if s is not None and s.state == "decode"]

    def admit_times(self) -> Dict[int, float]:
        out = {}
        for tr in self.eng.request_traces():
            for sp in tr["spans"]:
                if sp["name"] == "queued" and sp["t1"] is not None:
                    out[tr["rid"]] = sp["t1"]
        return out

    # -- after the window ---------------------------------------------------------
    def kernels_present(self) -> Dict[str, bool]:
        out = {}
        for site in self.eng.program_sites():
            if site[0] in ("decode", "prefill"):
                text = self.eng.lowered_text(site) or ""
                out["_".join(map(str, site))] = \
                    f'kernel_name = "{KERNEL}"' in text
        return out

    def compiles(self) -> int:
        return self.eng.stats.compiles

    def free(self) -> None:
        import jax

        self.eng.pools = None
        for p in self._model.parameters():
            p._value = None
        self.eng = self._model = self._pred = None
        gc.collect()
        jax.clear_caches()
        gc.collect()


def build(cfg, traffic, plan, seed, devices) -> System:
    return System(cfg, traffic, plan, seed, devices)


def sample(finished, seed: int, k: int):
    """The requests the reference is run over: the longest, and others
    drawn from the seed."""
    if not finished:
        return []
    order = sorted(finished, key=lambda r: (len(r.prompt) + len(r.tokens),
                                            r.idx))
    pick = [order[-1]]
    rest = order[:-1]
    rng = seeded(seed, 11)
    for i in rng.permutation(len(rest))[:max(k - 1, 0)]:
        pick.append(rest[int(i)])
    return pick


def _gap_number(gaps, picks, limit) -> Dict:
    n_tok = sum(len(g) for g in gaps)
    return {"name": f"widest gap of a served token's logit below the "
                    f"reference's best ({len(picks)} requests, {n_tok} "
                    f"tokens)",
            "value": float(max((g.max() for g in gaps), default=1e9)),
            "limit": limit,
            "mean_gap": float(np.mean(np.concatenate(gaps)))
            if gaps else None}


def check(system: System, result: Dict) -> List[Dict]:
    """The widest gap by which a served token's logit lies below the
    reference's best, over a seeded sample of the window's finished
    requests. Frees the engine first: the reference needs the memory,
    and ``memory_peak_bytes`` has been read."""
    import jax

    cfg, seed = system.cfg, system.seed
    vocab = cfg["vocab_size"]
    kernels = system.kernels_present() \
        if jax.devices()[0].platform == "tpu" else {}
    picks = sample(result["finished"], seed,
                   system.traffic["check_requests"])
    bad = [r for r in result["finished"]
           if len(r.tokens) != r.n_out
           or not ((r.tokens >= 0) & (r.tokens < vocab)).all()]
    system.free()
    # the decode program must hold the kernel or this is not the cell; a
    # prefill program may not (the kernel's gate sends long prompts to the
    # dense path): listed, never judged
    out = [{"name": f"kernel {KERNEL} missing from program {k}",
            "value": int(not v), "limit": 0 if k == "decode" else 1}
           for k, v in kernels.items()]
    out.append({"name": "finished requests with a wrong token count or a "
                        "token outside the vocabulary",
                "value": len(bad), "limit": 0})
    logits = ref.ServeReference(cfg, seed).logits(
        [(q.prompt, q.tokens) for q in picks])
    gaps = [ref.served_gap(lg, q.tokens) for lg, q in zip(logits, picks)]
    out.append(_gap_number(gaps, picks, cfg["limits"]["served_logit_gap"]))
    return out


def control(system: System, result: Dict) -> Dict:
    """The reference in the program's place in fp8 (the nearest
    precision below the configuration's bf16): at each position of the
    same prompts and served tokens, the gap of the token the fp8
    forward puts first, beside the program's. Run by
    ``tools/control.py``, never by a benchmark run."""
    cfg, seed = system.cfg, system.seed
    limit = cfg["limits"]["served_logit_gap"]
    picks = sample(result["finished"], seed,
                   system.traffic["check_requests"])
    system.free()
    reqs = [(q.prompt, q.tokens) for q in picks]
    logits = ref.ServeReference(cfg, seed).logits(reqs)
    low = ref.ServeReference(cfg, seed, "fp8").logits(reqs)
    prog = [ref.served_gap(lg, q.tokens) for lg, q in zip(logits, picks)]
    ctl = [ref.served_gap(lg, lo.argmax(-1))
           for lg, lo in zip(logits, low)]
    return {"program": [_gap_number(prog, picks, limit)],
            "control": [_gap_number(ctl, picks, limit)]}
