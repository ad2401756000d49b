"""Plain reference for ``trinity-mini``
(huggingface.co/arcee-ai/Trinity-Mini ``config.json``, ``model_type``
``afmoe``, 26B-A3B): three window layers of 2,048 keys to one full
layer, rotary on the window layers ONLY, an RMSNorm on every q and k
head, a sigmoid output gate on the attention result, a norm on each
branch's output as well as on its input, the embedding times
``sqrt(hidden_size)``, two leading dense SwiGLU layers, then layers of
128 routed SwiGLU experts (8 per token, sigmoid scores, a selection
bias, scale 2.826) beside one shared expert, untied output head.

Straightforward ``jax.numpy`` in float32 under
``jax.default_matmul_precision("highest")``: a full causal forward over
the whole sequence, layer by layer, expert by expert, the scores of a
block of rows against EVERY key with the masks written out, no kernels,
no cache, no batching. It imports nothing of the program and takes
nothing the program made: weights come from ``leaf``, from the seed, in
the type the configuration stores them in.

The equations (config keys in backticks), eps = ``rms_norm_eps``:
``h_0 = E[ids] * sqrt(hidden_size)`` (``mup_enabled``). Run layer i is
published layer ``layers_run[i]``; its kind is
``layer_types[layers_run[i]]`` and it is dense where ``layers_run[i] <
num_dense_layers``. With input x [S, 2048]:

1. u = RMSNorm(x; w_in); q = u W_q -> [S, 32, 128]; k = u W_k ->
   [S, 4, 128]; v = u W_v -> [S, 4, 128]; g = u W_g -> [S, 4096]; no
   biases.
2. q <- RMSNorm_128(q; w_qn), k <- RMSNorm_128(k; w_kn), each head alone.
3. ``sliding_attention``: q and k turn over all 128 dims, base
   ``rope_theta``, rotate-half pairing, no scaling (``rope_scaling``
   null). ``full_attention``: no rotation at all.
4. s_ij = q_i . k_j / sqrt(128) for j <= i, on a sliding layer also
   i - j < ``sliding_window`` (the current key counts);
   a = softmax_j(s) v, 8 query heads a KV head (query head h reads KV
   head h // 8); no sink.
5. a <- a * sigmoid(g) over the 4,096 columns; y = a W_o.
6. x <- x + RMSNorm(y; w_post_attn).
7. u = RMSNorm(x; w_pre_mlp). Dense: f = (silu(u W_gate) * (u W_up))
   W_down, ``intermediate_size`` wide. Else s = sigmoid(u W_r) in
   float32, ``router_experts`` wide (``score_func``); chosen = the
   ``num_experts_per_tok`` largest of s + b (one group: ``n_group`` 1);
   w_e = ``route_scale`` * s_e / (sum over the chosen of s + 1e-20)
   (``route_norm``); f = SwiGLU_shared(u) + sum over the chosen AND
   HELD e of w_e SwiGLU_e(u), each ``moe_intermediate_size`` wide
   (``num_shared_experts`` 1).
8. x <- x + RMSNorm(f; w_post_mlp).

logits = RMSNorm(x_L; w_norm) W_head, untied.

THE SHARE. The configuration is one chip's share of an 8-chip
expert-parallel layer: experts ``expert_offset .. expert_offset +
num_experts - 1`` of the router's ``router_experts`` are held (the
file's ``num_experts`` counts the HELD ones). w is normalised over all
chosen experts; only the chosen AND held are summed; the shared expert
runs whole on every holder; what the absent experts would add is left
out, here as in the program, and that partial sum goes through the
output norm and on to the next layer. The vocabulary is whole.

ASSUMED (the config file repeats these, with where each comes from):
steps 2, 3 (no rotation on full layers), 5, 6, 8 and the multiplier's
value are the family's published modelling code (``modeling_afmoe.py``)
and the catalog's description of the sibling Trinity-Large ("SWA gated",
"sandwich norm"); ``config.json`` has no key for them. Rotate-half
pairing (i with i + 64). The selection bias b is N(0, 0.02) from the
seed (``references/sarvam.py`` on why 0.02), weights N(0, 0.02) by
``references/gpt.py::leaf``'s recipe, down-projections (o, dense, shared,
expert) N(0, 0.02 / sqrt(2 x 32)), norms 1; ``load_balance_coeff`` is a
training term. The 1e-20 of step 7 is below float32's resolution of a
sum of eight sigmoids: the program's gate leaves it out.

The control (``precision="fp8"``) rounds the operands of every linear
layer to fp8; the router's product stays float32, as the configuration
states it.
"""
from __future__ import annotations

import math
from typing import Dict, List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .gpt import fp8, key_data, leaf, name_id  # the seeded-leaf recipe
from .mistral import served_gap  # noqa: F401  (the families' import)

ATTN_LEAVES = ("in_norm", "q", "k", "v", "attn_gate", "o", "q_norm",
               "k_norm", "attn_out_norm")
DENSE_LEAVES = ("pre_mlp_norm", "gate", "up", "down", "mlp_out_norm")
MOE_LEAVES = ("pre_mlp_norm", "router", "router_bias", "sh_gate", "sh_up",
              "sh_down", "mlp_out_norm")
EXPERT_LEAVES = ("gate", "up", "down")
PAD = 256     # sequences are padded to a multiple, to bound the shapes
ROWS = 256    # attention: query rows a block, against every key
BIAS_STD = 0.02
PUBLISHED_LAYERS = 32     # down-projections: N(0, std / sqrt(2 * 32))


def held_experts(cfg: Dict) -> range:
    return range(cfg["expert_offset"],
                 cfg["expert_offset"] + cfg["num_experts"])


def is_window(cfg: Dict, i: int) -> bool:
    return cfg["layer_types"][cfg["layers_run"][i]] == "sliding_attention"


def is_moe(cfg: Dict, i: int) -> bool:
    return cfg["layers_run"][i] >= cfg["num_dense_layers"]


def embedding_multiplier(cfg: Dict) -> float:
    return math.sqrt(cfg["hidden_size"]) if cfg["mup_enabled"] else 1.0


def leaf_table(cfg: Dict) -> Dict[str, Tuple[Tuple[int, ...], str, float]]:
    h = cfg["hidden_size"]
    H, KV, d = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                cfg["head_dim"])
    ff, fe = cfg["intermediate_size"], cfg["moe_intermediate_size"]
    fs = fe * cfg["num_shared_experts"]
    E = cfg["router_experts"]
    std = cfg["initializer_range"]
    out_std = std / math.sqrt(2 * PUBLISHED_LAYERS)
    t = {"embed": ((cfg["vocab_size"], h), "normal", std),
         "norm": ((h,), "ones", 0.0),
         "lm_head": ((h, cfg["vocab_size"]), "normal", std)}
    for i in range(cfg["num_hidden_layers"]):
        for n in ("in_norm", "attn_out_norm", "pre_mlp_norm",
                  "mlp_out_norm"):
            t[f"l.{i}.{n}"] = ((h,), "ones", 0.0)
        t[f"l.{i}.q"] = ((h, H * d), "normal", std)
        t[f"l.{i}.k"] = ((h, KV * d), "normal", std)
        t[f"l.{i}.v"] = ((h, KV * d), "normal", std)
        t[f"l.{i}.attn_gate"] = ((h, H * d), "normal", std)
        t[f"l.{i}.o"] = ((H * d, h), "normal", out_std)
        t[f"l.{i}.q_norm"] = ((d,), "ones", 0.0)
        t[f"l.{i}.k_norm"] = ((d,), "ones", 0.0)
        if not is_moe(cfg, i):
            t[f"l.{i}.gate"] = ((h, ff), "normal", std)
            t[f"l.{i}.up"] = ((h, ff), "normal", std)
            t[f"l.{i}.down"] = ((ff, h), "normal", out_std)
            continue
        t[f"l.{i}.router"] = ((h, E), "normal", std)
        t[f"l.{i}.router_bias"] = ((E,), "normal", BIAS_STD)
        t[f"l.{i}.sh_gate"] = ((h, fs), "normal", std)
        t[f"l.{i}.sh_up"] = ((h, fs), "normal", std)
        t[f"l.{i}.sh_down"] = ((fs, h), "normal", out_std)
        for j in held_experts(cfg):
            t[f"l.{i}.e.{j}.gate"] = ((h, fe), "normal", std)
            t[f"l.{i}.e.{j}.up"] = ((h, fe), "normal", std)
            t[f"l.{i}.e.{j}.down"] = ((fe, h), "normal", out_std)
    return t


def _mm(a, w, precision: str):
    if precision == "fp8":          # the control: see references/gpt.py
        a, w = fp8(a), fp8(w)
    return a @ w


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _rope(x, theta: float):
    """x: [S, heads, D]; position = row index; every dim of a head turns
    (rotate-half: i with i + D/2). The angles in float64 (a position of
    thousands times a frequency), their cosines and sines in float32."""
    S, D = x.shape[0], x.shape[-1]
    inv = 1.0 / theta ** (np.arange(0, D, 2, dtype=np.float64) / D)
    f = np.arange(S, dtype=np.float64)[:, None] * inv[None]
    cos = jnp.asarray(np.concatenate([np.cos(f), np.cos(f)], -1)[:, None],
                      jnp.float32)
    sin = jnp.asarray(np.concatenate([np.sin(f), np.sin(f)], -1)[:, None],
                      jnp.float32)
    x1, x2 = x[..., :D // 2], x[..., D // 2:]
    return x * cos + jnp.concatenate([-x2, x1], -1) * sin


def attention(p, x, cfg: Dict, i: int, precision: str):
    """Steps 1-6 of layer ``i``'s kind, one sequence x: [S, hidden]."""
    S = x.shape[0]
    H, KV, d = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                cfg["head_dim"])
    eps, window = cfg["rms_norm_eps"], is_window(cfg, i)
    u = _rms(x, p["in_norm"], eps)
    q = _rms(_mm(u, p["q"], precision).reshape(S, H, d), p["q_norm"], eps)
    k = _rms(_mm(u, p["k"], precision).reshape(S, KV, d), p["k_norm"], eps)
    v = _mm(u, p["v"], precision).reshape(S, KV, d)
    g = _mm(u, p["attn_gate"], precision)
    if window:                      # a full layer turns nothing
        q, k = _rope(q, cfg["rope_theta"]), _rope(k, cfg["rope_theta"])
    k = jnp.repeat(k, H // KV, axis=1)       # query head h reads h // G
    v = jnp.repeat(v, H // KV, axis=1)
    outs = []
    j = jnp.arange(S)[None, :]
    for a in range(0, S, ROWS):              # rows a block, every key
        qi = q[a:a + ROWS]
        ii = jnp.arange(a, a + qi.shape[0])[:, None]
        s = jnp.einsum("qhd,khd->hqk", qi, k) / math.sqrt(d)
        keep = j <= ii
        if window:
            keep = keep & (ii - j < cfg["sliding_window"])
        s = jnp.where(keep, s, -jnp.inf)
        e = jnp.where(keep, jnp.exp(s - jnp.max(s, -1, keepdims=True)), 0.0)
        outs.append(jnp.einsum("hqk,khd->qhd",
                               e / e.sum(-1, keepdims=True), v))
    a = jnp.concatenate(outs, 0).reshape(S, H * d) * jax.nn.sigmoid(g)
    return x + _rms(_mm(a, p["o"], precision), p["attn_out_norm"], eps)


def swiglu(x, gate, up, down, precision: str):
    return _mm(jax.nn.silu(_mm(x, gate, precision))
               * _mm(x, up, precision), down, precision)


def route(u, router, bias, cfg: Dict):
    """Chosen experts [T, k] (numbered over the router's width) and
    their weights [T, k], normalised over all k chosen. Float32."""
    s = jax.nn.sigmoid(u @ router)
    _, idx = jax.lax.top_k(s + bias, cfg["num_experts_per_tok"])
    sel = jnp.take_along_axis(s, idx, axis=-1)
    return idx, cfg["route_scale"] * sel / (
        sel.sum(-1, keepdims=True) + 1e-20)


def expert_part(u, idx, w, j: int, gate, up, down, precision: str):
    """w_j * E_j(u) on the tokens that chose expert j, 0 elsewhere."""
    wj = jnp.sum(jnp.where(idx == j, w, 0.0), axis=-1)          # [T]
    return wj[:, None] * swiglu(u, gate, up, down, precision)


class ServeReference:
    """Logits of a full forward over ``prompt + served tokens``, and the
    experts each position chose in each expert layer."""

    def __init__(self, cfg: Dict, seed: int, precision: str = "float32"):
        self.cfg = cfg
        self.key = jax.random.wrap_key_data(jnp.asarray(key_data(seed)))
        self.table = leaf_table(cfg)
        self.store = jnp.dtype(cfg["torch_dtype"])
        self.precision = precision
        self._jit: Dict = {}
        self.choices: List[np.ndarray] = []

    def _params(self, names: Sequence[str]) -> Dict[str, jax.Array]:
        specs = tuple(self.table[n] for n in names)
        fn = self._jit.get(specs)
        if fn is None:
            def make(key, nids):
                return tuple(leaf(key, nids[i], s, self.store).astype(
                    jnp.float32) for i, s in enumerate(specs))
            fn = self._jit[specs] = jax.jit(make)
        nids = jnp.asarray([name_id(n) for n in names], jnp.int32)
        return dict(zip([n.split(".")[-1] for n in names],
                        fn(self.key, nids)))

    def _mlp(self, i: int, xs: List[jax.Array]) -> List[jax.Array]:
        """Steps 7-8 for every sequence of ``xs``, layer ``i``: rows of
        all sequences side by side (the MLP acts on single rows)."""
        cfg, prec = self.cfg, self.precision
        eps = cfg["rms_norm_eps"]
        rows = jnp.concatenate(xs, axis=0)
        f_norm = self._jit.setdefault(
            "norm", jax.jit(lambda x, w: _rms(x, w, eps)))
        f_ffn = self._jit.setdefault(
            "ffn", jax.jit(lambda u, gate, up, down: swiglu(
                u, gate, up, down, prec)))
        moe = is_moe(cfg, i)
        p = self._params([f"l.{i}.{k}"
                          for k in (MOE_LEAVES if moe else DENSE_LEAVES)])
        u = f_norm(rows, p["pre_mlp_norm"])
        if not moe:
            f = f_ffn(u, p["gate"], p["up"], p["down"])
            self.choices.append(None)
        else:
            f_route = self._jit.setdefault(
                "route", jax.jit(lambda u, r, b: route(u, r, b, cfg)))
            idx, w = f_route(u, p["router"], p["router_bias"])
            self.choices.append(np.asarray(idx))
            f = f_ffn(u, p["sh_gate"], p["sh_up"], p["sh_down"])
            f_exp = self._jit.setdefault("expert", jax.jit(
                lambda u, idx, w, j, pe: expert_part(
                    u, idx, w, j, pe["gate"], pe["up"], pe["down"], prec)))
            for j in held_experts(cfg):        # expert by expert
                pe = self._params([f"l.{i}.e.{j}.{k}"
                                   for k in EXPERT_LEAVES])
                f = f + f_exp(u, idx, w, jnp.int32(j), pe)
        out = rows + f_norm(f, p["mlp_out_norm"])
        cuts = np.cumsum([x.shape[0] for x in xs])[:-1]
        return list(jnp.split(out, cuts, axis=0))

    def forward(self, seqs: List[np.ndarray]) -> List[jax.Array]:
        """The final hidden rows [padded length, hidden] of each whole
        sequence. Afterwards ``self.choices[i]`` holds layer i's chosen
        experts for the rows of all (padded) sequences side by side,
        None for a dense layer; ``self.row_spans`` each sequence's
        (first row, length)."""
        cfg, prec = self.cfg, self.precision
        self.choices, self.row_spans = [], []
        emb = self._params(["embed"])["embed"]
        mult = embedding_multiplier(cfg)
        xs, first = [], 0
        # one padded length for all: one attention program a kind
        longest = max(len(seq) for seq in seqs)
        longest += -longest % PAD
        for seq in seqs:
            pad = longest - len(seq)
            xs.append(emb[jnp.asarray(np.pad(
                np.asarray(seq, np.int32), (0, pad)))] * mult)
            self.row_spans.append((first, len(seq)))
            first += len(seq) + pad
        del emb
        for i in range(cfg["num_hidden_layers"]):
            # a layer's index enters attention by its kind alone
            f_attn = self._jit.setdefault(
                ("attn", is_window(cfg, i)), jax.jit(
                    lambda p, x, i=i: attention(p, x, cfg, i, prec)))
            p = self._params([f"l.{i}.{k}" for k in ATTN_LEAVES])
            xs = [f_attn(p, x) for x in xs]
            xs = self._mlp(i, xs)
        return xs

    def logits(self, requests: List[Tuple[np.ndarray, np.ndarray]]
               ) -> List[np.ndarray]:
        """For each (prompt, served) pair: float32 logits [n, vocab] at
        the n positions that produced the served tokens (the last prompt
        position, then each served token but the last)."""
        cfg, prec = self.cfg, self.precision

        def head(p, x):
            return _mm(_rms(x, p["norm"], cfg["rms_norm_eps"]),
                       p["lm_head"], prec)

        f_head = self._jit.setdefault("head", jax.jit(head))
        with jax.default_matmul_precision("highest"):
            xs = self.forward([np.concatenate([prompt, served[:-1]])
                               for prompt, served in requests])
            p = self._params(["norm", "lm_head"])
            out = []
            for (prompt, served), x in zip(requests, xs):
                lo = len(prompt) - 1
                rows = x[lo:lo + len(served)]
                rpad = -rows.shape[0] % 64
                lg = f_head(p, jnp.pad(rows, ((0, rpad), (0, 0))))
                out.append(np.asarray(lg[:len(served)], np.float32))
        return out

    def chosen(self) -> List[np.ndarray]:
        """Per expert layer, the chosen experts [real rows, k] of the
        last ``logits`` call, the requests' real positions in order."""
        keep = np.concatenate([np.arange(a, a + n)
                               for a, n in self.row_spans])
        return [c[keep] for c in self.choices if c is not None]
