"""A decoder whose layer is ONE mixer of three kinds (here
``nemotron-3-super-120b-a12b``: Mamba-2 state-space layers whose
recurrent state rides beside the paged keys as one slot a row, one
grouped-query attention layer in eleven, latent relu² experts behind a
sigmoid router) through ``Config.enable_paged_kv`` -> ``create_predictor``
-> ``ServingEngine`` in its default mode. The model is
``SSMMoEForCausalLM``; this module maps the source's key names
(``hybrid_override_pattern``, ``mamba_*``, ``moe_latent_size``,
``mlp_hidden_act``, ...) to ``SSMMoEConfig`` and reuses
``afmoe_serving.System`` for everything that drives and reads the
engine. The reference is ``references/nemotron_h.py``.

The configuration is one holder's share of a four-chip expert-parallel
layer (``configs/nemotron-3-super-120b-a12b.json``: ``n_routed_experts``
held of ``router_experts``, from ``expert_offset``; a slice of the
vocabulary), cut in depth to the published layers ``layers_run``.

``check``: the logits of prefill-then-decode THROUGH THE SLOT AND THE
PAGES against the reference's full forward over the requests with the
longest contexts (widest gap and mean gap, two limits, as
``afmoe_serving``), 0 dropped pairs, the decode kernel by name, the
state arrays' dtype (``float32``: judged), finished requests
well-formed; the share of expert choices that differ is listed.
``control``: the reference in fp8 in the program's place, and, listed
beside it, the reference with H rounded to bfloat16 after every
position.

Host readings beside ``afmoe_serving``'s (``moe_pairs_per_expert``,
``moe_load_max_over_mean``, ``prefill_padding_share``,
``kv_bytes_per_context_token``): ``ssm_state_bytes_per_row``, what the
cache says a row's slot takes over the state layers.

The model is imported when this module is: a tree without it fails
here, before anything is allocated.
"""
from __future__ import annotations

import json
import re
import time
from typing import Dict, List

import jax
import jax.numpy as jnp
import numpy as np

from paddle_tpu.models.ssm_moe import SSMMoEConfig, SSMMoEForCausalLM

from ..laps import Laps
from ...references import nemotron_h as ref
from . import afmoe_serving as afmoe
from . import hybrid_moe_serving as hybrid
from . import llama_serving as base
from . import mla_moe_serving as moe

KERNEL = "paged_decode_attention"
_KINDS = {"M": "ssm", "E": "experts", "*": "attention"}
_LEAF = {"norm": "norm", "mixer.in_proj": "in_proj",
         "mixer.conv_weight": "conv_w", "mixer.conv_bias": "conv_b",
         "mixer.A_log": "A_log", "mixer.dt_bias": "dt_bias",
         "mixer.D": "D", "mixer.norm": "gn", "mixer.out_proj": "out_proj",
         "mixer.q_proj": "q", "mixer.k_proj": "k", "mixer.v_proj": "v",
         "mixer.o_proj": "o", "mixer.gate.weight": "router",
         "mixer.gate.bias": "router_bias", "mixer.latent_down": "lat_dn",
         "mixer.latent_up": "lat_up", "mixer.shared_up": "sh_up",
         "mixer.shared_down": "sh_down"}
_STACK = {"mixer.w_up": "up", "mixer.w_down": "down"}


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


def model_config(cfg: Dict, max_len: int) -> SSMMoEConfig:
    n = cfg["num_hidden_layers"]
    pattern = cfg["hybrid_override_pattern"]
    if len(cfg["layers_run"]) != n or len(pattern) != n:
        raise ValueError("layers_run and hybrid_override_pattern name "
                         "num_hidden_layers published layers")
    if cfg["mamba_num_heads"] * cfg["mamba_head_dim"] \
            != cfg["expand"] * cfg["hidden_size"]:
        raise ValueError("mamba_num_heads x mamba_head_dim is not expand "
                         "x hidden_size")
    return SSMMoEConfig(
        vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
        mixer_kinds=[_KINDS[c] for c in pattern],
        num_heads=cfg["num_attention_heads"],
        num_kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        ssm_num_heads=cfg["mamba_num_heads"],
        ssm_head_dim=cfg["mamba_head_dim"], ssm_groups=cfg["n_groups"],
        ssm_state_size=cfg["ssm_state_size"],
        conv_kernel=cfg["conv_kernel"], conv_bias=cfg["use_conv_bias"],
        chunk_size=cfg["chunk_size"],
        ssm_state_dtype=cfg["ssm_state_dtype"],
        num_experts=cfg["router_experts"],
        num_local_experts=cfg["n_routed_experts"],
        expert_offset=cfg["expert_offset"],
        num_experts_per_tok=cfg["num_experts_per_tok"],
        routed_scaling_factor=cfg["routed_scaling_factor"],
        norm_topk_prob=cfg["norm_topk_prob"],
        moe_intermediate_size=cfg["moe_intermediate_size"],
        moe_latent_size=cfg["moe_latent_size"],
        expert_activation=cfg["mlp_hidden_act"],
        shared_expert_intermediate_size=cfg[
            "moe_shared_expert_intermediate_size"] * cfg["n_shared_experts"],
        max_position_embeddings=max_len, rms_norm_eps=cfg["norm_eps"],
        initializer_range=cfg["initializer_range"], dtype=cfg["torch_dtype"])


def load(params, cfg: Dict, table: Dict, seed: int) -> None:
    """``harness/weights.py::load`` with the reference's OWN ``leaf``
    (two kinds of a state-space layer that ``references/gpt.py`` has
    not): ONE jitted call a group, each value in the type it is stored
    in, from the seed."""
    dtype = jnp.dtype(cfg["torch_dtype"])
    entries = []
    for pname, p in params:
        names = names_of(pname, cfg)
        spec = table[names if isinstance(names, str) else names[0]]
        shape = tuple(spec[0]) if isinstance(names, str) \
            else (len(names),) + tuple(spec[0])
        if shape != tuple(p._value.shape):
            raise ValueError(f"{pname}: the program holds "
                             f"{tuple(p._value.shape)}, the reference's "
                             f"table says {shape}")
        entries.append((names, spec))

    def gen(key):
        out = []
        for names, spec in entries:
            if isinstance(names, str):
                out.append(ref.leaf(key, ref.name_id(names), spec, dtype))
            else:
                nids = jnp.asarray([ref.name_id(n) for n in names],
                                   jnp.int32)
                out.append(jax.vmap(
                    lambda nid: ref.leaf(key, nid, spec, dtype))(nids))
        return tuple(out)

    key = jax.random.wrap_key_data(jnp.asarray(ref.key_data(seed)))
    for (_, p), v in zip(params, jax.jit(gen)(key)):
        p._value = v


class System(afmoe.System):
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
            model = SSMMoEForCausalLM(mcfg)
        table = ref.leaf_table(cfg)
        # a layer at a time: the generator's float32 temporaries for a
        # stack of 128 experts are 1.4 GB
        groups: Dict[str, List] = {}
        for n, p in model.named_parameters():
            m = re.match(r"layers\.(\d+)\.", n)
            groups.setdefault(m.group(1) if m else n, []).append((n, p))
        for part in groups.values():
            load(part, cfg, table, seed)
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
        reg = get_registry()
        self._kv_gauge = reg.gauge(hybrid.KV_GAUGE)
        self._kv_ratio: List[float] = []
        self._ring_gauge = reg.gauge(afmoe.RING_GAUGE)   # never set here
        self._ring_fill: List[float] = []
        self._prefill_tokens = reg.counter(afmoe.PREFILL_TOKENS,
                                           labelnames=("kind",))
        self._prefill_base = self._prefill_counts()

    def kernels_present(self) -> Dict[str, bool]:
        """program -> is the decode kernel's call in its text: the
        decode program (judged) and the largest prefill program
        (listed)."""
        sites = self.eng.program_sites()
        prefill = sorted(s for s in sites if s[0] == "prefill")[-1:]
        return {"_".join(map(str, site)):
                f'kernel_name = "{KERNEL}"' in (
                    self.eng.lowered_text(site) or "")
                for site in [s for s in sites if s[0] == "decode"]
                + prefill}

    def state_host(self) -> Dict:
        """What the cache keeps a row over the state layers, and the
        types it keeps it in (the first array of a state layer is H)."""
        cache = self.eng.cache
        return {"ssm_state_bytes_per_row": int(cache.state_row_bytes),
                "state_dtypes": sorted({
                    str(layer[0].dtype) for layer, st in zip(
                        cache.pools, cache.state_layers) if st})}


def build(cfg, traffic, plan, seed, devices) -> System:
    return System(cfg, traffic, plan, seed, devices)


sample = hybrid.sample


def check(system: System, result: Dict) -> List[Dict]:
    """``afmoe_serving.check`` against ``references/nemotron_h.py``, the
    one decode kernel, and the state arrays' dtype."""
    cfg, seed = system.cfg, system.seed
    vocab = cfg["vocab_size"]
    kernels = system.kernels_present() \
        if jax.devices()[0].platform == "tpu" else {}
    host = system.moe_host()
    host.update(system.kv_host())
    state = system.state_host()
    dtypes = state.pop("state_dtypes")
    host.update(state)
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
    out = [{"name": f"kernel {KERNEL} missing from program {k}",
            "value": int(not v), "limit": 0 if k == "decode" else 1}
           for k, v in kernels.items()]
    out.append({"name": "routed pairs the expert layers dropped",
                "value": host["moe_dropped_pairs"], "limit": 0})
    out.append({"name": "state layers whose recurrent state is not kept "
                        f"in {cfg['ssm_state_dtype']} (kept: "
                        f"{', '.join(dtypes) or 'none'})",
                "value": int(dtypes != [cfg["ssm_state_dtype"]]),
                "limit": 0})
    out.append({"name": "finished requests with a wrong token count or a "
                        "token outside the vocabulary",
                "value": len(bad), "limit": 0})
    r = ref.ServeReference(cfg, seed)
    logits = r.logits([(q.prompt, q.tokens) for q in picks])
    gaps = [ref.served_gap(lg, q.tokens) for lg, q in zip(logits, picks)]
    out.extend(afmoe._gap_numbers(gaps, picks, cfg["limits"]))
    out.append({"name": "share of (position, layer) expert choices of an "
                        "untimed full forward of the program's model that "
                        "differ from the reference's",
                "value": moe._flip_share(prog, r.chosen()), "limit": 1.0})
    return out


def control(system: System, result: Dict) -> Dict:
    """``afmoe_serving.control`` against ``references/nemotron_h.py``:
    the reference in the program's place in fp8, beside the program,
    over the same longest requests; and, listed, the reference with H
    rounded to bfloat16 after every position."""
    cfg, seed = system.cfg, system.seed
    picks = sample(result["finished"], system.traffic["check_requests"])
    prog_choices = system.program_choices(moe._sequences(picks))
    system.free()
    reqs = [(q.prompt, q.tokens) for q in picks]
    r = ref.ServeReference(cfg, seed)
    logits = r.logits(reqs)
    want = r.chosen()
    prog = [ref.served_gap(lg, q.tokens) for lg, q in zip(logits, picks)]
    q = (50, 90, 99, 100)
    out = {"program": afmoe._gap_numbers(prog, picks, cfg["limits"]),
           "program_gap_quantiles": dict(zip(map(str, q), np.percentile(
               np.concatenate(prog), q).tolist())),
           "program_choice_flips": moe._flip_share(prog_choices, want)}
    for name, precision in (("control", "fp8"),
                            ("state_bf16", "state_bf16")):
        lo = ref.ServeReference(cfg, seed, precision)
        gaps = [ref.served_gap(lg, lw.argmax(-1))
                for lg, lw in zip(logits, lo.logits(reqs))]
        out[name] = afmoe._gap_numbers(gaps, picks, cfg["limits"])
        out[f"{name}_gap_quantiles"] = dict(zip(
            map(str, q), np.percentile(np.concatenate(gaps), q).tolist()))
        out[f"{name}_choice_flips"] = moe._flip_share(lo.chosen(), want)
    return out
