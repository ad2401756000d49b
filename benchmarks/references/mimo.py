"""Plain reference for ``mimo-v2-flash``
(huggingface.co/XiaomiMiMo/MiMo-V2-Flash ``config.json``, ``model_type``
``mimo_v2_flash``): layers of two attention kinds in one model — full
causal attention, and window attention over the last 128 positions with
a learned sink logit a head — with 192-wide queries and keys against
128-wide values, a leading dense SwiGLU layer, then layers of 256 routed
SwiGLU experts (8 per token, sigmoid scores, a selection bias, no shared
expert), untied output head.

Straightforward ``jax.numpy`` in float32 under
``jax.default_matmul_precision("highest")``: a full causal forward over
the whole sequence, layer by layer, expert by expert, the scores of a
block of rows against EVERY key with the masks written out, no kernels,
no cache, no batching. It imports nothing of the program and takes
nothing the program made: weights come from ``leaf``, from the seed, in
the type the configuration stores them in.

The equations, with x^ = RMSNorm(x), eps = ``layernorm_epsilon``:

- attention of kind k (``hybrid_layer_pattern[i]``: 0 full, 1 window):
  q = x^ W_q -> [S, 64, 192]; K = x^ W_k -> [S, KV_k, 192];
  V = x^ W_v -> [S, KV_k, 128]; KV_full = ``num_key_value_heads``,
  KV_window = ``swa_num_key_value_heads``; rotary on the first
  ``rotary_dim`` = 64 dims of every q and K head (``partial_rotary_factor``
  x 192 rounded down to an even number), base ``rope_theta`` on full
  layers and ``swa_rope_theta`` on window layers, the other dims pass
  through; s_ij = q_i . K_j / sqrt(192) for j <= i, on window layers
  also i - j < ``sliding_window``; full: p = softmax_j(s); window
  (``add_swa_attention_sink_bias``): p_ij = exp(s_ij) / (sum_j exp(s_ij)
  + exp(b_h)), one learned logit b_h a query head, which takes weight
  and gives no value; o_i = sum_j p_ij (``attention_value_scale`` V_j);
  x <- x + concat_h(o) W_o. Query head h reads KV head h // (64 / KV_k).
- feed-forward (``moe_layer_freq[i]``: 0 dense, 1 experts): u =
  RMSNorm(x); dense: x <- x + (silu(u W_g) * (u W_u)) W_d; experts:
  z = sigmoid(u W_r) in float32, chosen = the 8 largest of z + bias
  (``noaux_tc``, one group), w_e = z_e / sum_chosen z
  (``norm_topk_prob``; ``routed_scaling_factor`` null = 1),
  x <- x + sum_{e chosen AND held} w_e E_e(u), no shared expert.

THE SHARE. The configuration is one chip's share of a 16-chip
expert-parallel layer: experts ``expert_offset .. expert_offset +
n_routed_experts - 1`` of the router's ``router_experts`` are held. w is
normalised over all chosen experts; only the chosen AND held are
summed; what the absent experts would add is left out, here as in the
program, and that partial sum goes on to the next layer. Logits are over
the ``vocab_size`` rows held here.

ASSUMED (the config file repeats these):

- rotary pairing is rotate-half over the 64 rotated dims (i with
  i + 32); an interleaved checkpoint is a fixed permutation of the
  columns of W_q and W_k: the same function class with seeded weights.
- the value scale is applied to V (linear, so where it sits does not
  change o).
- the window keeps ``sliding_window`` keys, the current one included.
- no q/k norm (the config has no such key).
- the sinks b are N(0, 1) and the selection bias N(0, 0.02) from the
  seed (the checkpoint's are learned; ``references/sarvam.py`` on why
  0.02).
- weights by ``references/gpt.py::leaf``'s recipe, N(0, 0.02),
  down-projections (o, dense, expert) N(0, 0.02 / sqrt(2 x 48)), norms 1.
- the 3 multi-token-prediction layers of the model card are not in
  ``config`` and are left out.

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

DENSE_LEAVES = ("gate", "up", "down")
MOE_LEAVES = ("router", "router_bias")
EXPERT_LEAVES = ("gate", "up", "down")
PAD = 256     # sequences are padded to a multiple, to bound the shapes
ROWS = 256    # attention: query rows a block, against every key
BIAS_STD = 0.02
SINK_STD = 1.0
PUBLISHED_LAYERS = 48     # down-projections: N(0, std / sqrt(2 * 48))


def held_experts(cfg: Dict) -> range:
    return range(cfg["expert_offset"],
                 cfg["expert_offset"] + cfg["n_routed_experts"])


def is_window(cfg: Dict, i: int) -> bool:
    return bool(cfg["hybrid_layer_pattern"][i])


def is_moe(cfg: Dict, i: int) -> bool:
    return bool(cfg["moe_layer_freq"][i])


def kv_heads(cfg: Dict, i: int) -> int:
    return cfg["swa_num_key_value_heads"] if is_window(cfg, i) \
        else cfg["num_key_value_heads"]


def has_sink(cfg: Dict, i: int) -> bool:
    return bool(cfg["add_swa_attention_sink_bias"] if is_window(cfg, i)
                else cfg["add_full_attention_sink_bias"])


def rotary_dim(cfg: Dict) -> int:
    return int(cfg["partial_rotary_factor"] * cfg["head_dim"]) // 2 * 2


def attn_leaves(cfg: Dict, i: int) -> Tuple[str, ...]:
    return ("in_norm", "q", "k", "v", "o", "post_norm") \
        + (("sinks",) if has_sink(cfg, i) else ())


def leaf_table(cfg: Dict) -> Dict[str, Tuple[Tuple[int, ...], str, float]]:
    h = cfg["hidden_size"]
    H, dk, dv = cfg["num_attention_heads"], cfg["head_dim"], cfg["v_head_dim"]
    ff, fe = cfg["intermediate_size"], cfg["moe_intermediate_size"]
    E = cfg["router_experts"]
    std = cfg["initializer_range"]
    out_std = std / math.sqrt(2 * PUBLISHED_LAYERS)
    t = {"embed": ((cfg["vocab_size"], h), "normal", std),
         "norm": ((h,), "ones", 0.0),
         "lm_head": ((h, cfg["vocab_size"]), "normal", std)}
    for i in range(cfg["num_hidden_layers"]):
        KV = kv_heads(cfg, i)
        t[f"l.{i}.in_norm"] = ((h,), "ones", 0.0)
        t[f"l.{i}.q"] = ((h, H * dk), "normal", std)
        t[f"l.{i}.k"] = ((h, KV * dk), "normal", std)
        t[f"l.{i}.v"] = ((h, KV * dv), "normal", std)
        t[f"l.{i}.o"] = ((H * dv, h), "normal", out_std)
        t[f"l.{i}.post_norm"] = ((h,), "ones", 0.0)
        if has_sink(cfg, i):
            t[f"l.{i}.sinks"] = ((H,), "normal", SINK_STD)
        if not is_moe(cfg, i):
            t[f"l.{i}.gate"] = ((h, ff), "normal", std)
            t[f"l.{i}.up"] = ((h, ff), "normal", std)
            t[f"l.{i}.down"] = ((ff, h), "normal", out_std)
            continue
        t[f"l.{i}.router"] = ((h, E), "normal", std)
        t[f"l.{i}.router_bias"] = ((E,), "normal", BIAS_STD)
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


def _rope(x, r: int, theta: float):
    """x: [S, heads, D]; position = row index; the first ``r`` dims of a
    head turn (rotate-half: i with i + r/2), the rest pass through. The
    angles in float64 (a position of thousands times a frequency), their
    cosines and sines in float32."""
    S = x.shape[0]
    inv = 1.0 / theta ** (np.arange(0, r, 2, dtype=np.float64) / r)
    f = np.arange(S, dtype=np.float64)[:, None] * inv[None]
    cos = jnp.asarray(np.concatenate([np.cos(f), np.cos(f)], -1)[:, None],
                      jnp.float32)
    sin = jnp.asarray(np.concatenate([np.sin(f), np.sin(f)], -1)[:, None],
                      jnp.float32)
    t = x[..., :r]
    t1, t2 = t[..., :r // 2], t[..., r // 2:]
    return jnp.concatenate(
        [t * cos + jnp.concatenate([-t2, t1], -1) * sin, x[..., r:]], -1)


def attention(p, x, cfg: Dict, i: int, precision: str):
    """x + attention of layer ``i``'s kind, one sequence x: [S, hidden]."""
    S = x.shape[0]
    H, dk, dv = cfg["num_attention_heads"], cfg["head_dim"], cfg["v_head_dim"]
    KV, window = kv_heads(cfg, i), is_window(cfg, i)
    theta = cfg["swa_rope_theta"] if window else cfg["rope_theta"]
    h = _rms(x, p["in_norm"], cfg["layernorm_epsilon"])
    r = rotary_dim(cfg)
    q = _rope(_mm(h, p["q"], precision).reshape(S, H, dk), r, theta)
    k = _rope(_mm(h, p["k"], precision).reshape(S, KV, dk), r, theta)
    v = cfg["attention_value_scale"] \
        * _mm(h, p["v"], precision).reshape(S, KV, dv)
    k = jnp.repeat(k, H // KV, axis=1)       # query head h reads h // G
    v = jnp.repeat(v, H // KV, axis=1)
    outs = []
    j = jnp.arange(S)[None, :]
    for a in range(0, S, ROWS):              # rows a block, every key
        qi = q[a:a + ROWS]
        ii = jnp.arange(a, a + qi.shape[0])[:, None]
        s = jnp.einsum("qhd,khd->hqk", qi, k) / math.sqrt(dk)
        keep = j <= ii
        if window:
            keep = keep & (ii - j < cfg["sliding_window"])
        e = jnp.where(keep, jnp.exp(
            s - jnp.max(jnp.where(keep, s, -jnp.inf), -1, keepdims=True)),
            0.0)
        den = e.sum(-1, keepdims=True)
        if "sinks" in p:                     # weight, and no value
            m = jnp.max(jnp.where(keep, s, -jnp.inf), -1, keepdims=True)
            den = den + jnp.exp(p["sinks"][:, None, None] - m)
        outs.append(jnp.einsum("hqk,khd->qhd", e / den, v))
    o = jnp.concatenate(outs, 0).reshape(S, H * dv)
    return x + _mm(o, p["o"], precision)


def swiglu(x, gate, up, down, precision: str):
    return _mm(jax.nn.silu(_mm(x, gate, precision))
               * _mm(x, up, precision), down, precision)


def route(h, router, bias, cfg: Dict):
    """Chosen experts [T, k] (numbered over the router's width) and
    their weights [T, k], normalised over all k chosen. Float32."""
    s = jax.nn.sigmoid(h @ router)
    _, idx = jax.lax.top_k(s + bias, cfg["num_experts_per_tok"])
    sel = jnp.take_along_axis(s, idx, axis=-1)
    scaling = cfg["routed_scaling_factor"] or 1.0
    return idx, scaling * sel / sel.sum(-1, keepdims=True)


def expert_part(h, idx, g, j: int, gate, up, down, precision: str):
    """g_j * E_j(x^) on the tokens that chose expert j, 0 elsewhere."""
    w = jnp.sum(jnp.where(idx == j, g, 0.0), axis=-1)          # [T]
    return w[:, None] * swiglu(h, gate, up, down, precision)


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
        """x + MLP(x^) for every sequence of ``xs``, layer ``i``: rows
        of all sequences side by side (the MLP acts on single rows)."""
        cfg, prec = self.cfg, self.precision
        eps = cfg["layernorm_epsilon"]
        rows = jnp.concatenate(xs, axis=0)
        f_norm = self._jit.setdefault(
            "norm", jax.jit(lambda x, w: _rms(x, w, eps)))
        f_ffn = self._jit.setdefault(
            "ffn", jax.jit(lambda h, p: swiglu(h, p["gate"], p["up"],
                                               p["down"], prec)))
        h = f_norm(rows, self._params([f"l.{i}.post_norm"])["post_norm"])
        if not is_moe(cfg, i):
            y = f_ffn(h, self._params([f"l.{i}.{k}"
                                       for k in DENSE_LEAVES]))
            self.choices.append(None)
        else:
            p = self._params([f"l.{i}.{k}" for k in MOE_LEAVES])
            f_route = self._jit.setdefault(
                "route", jax.jit(lambda h, r, b: route(h, r, b, cfg)))
            idx, g = f_route(h, p["router"], p["router_bias"])
            self.choices.append(np.asarray(idx))
            y = jnp.zeros_like(rows)
            f_exp = self._jit.setdefault("expert", jax.jit(
                lambda h, idx, g, j, p: expert_part(
                    h, idx, g, j, p["gate"], p["up"], p["down"], prec)))
            for j in held_experts(cfg):        # expert by expert
                pe = self._params([f"l.{i}.e.{j}.{k}"
                                   for k in EXPERT_LEAVES])
                y = y + f_exp(h, idx, g, jnp.int32(j), pe)
        out = rows + y
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
        xs, first = [], 0
        # one padded length for all: one attention program a kind
        longest = max(len(seq) for seq in seqs)
        longest += -longest % PAD
        for seq in seqs:
            pad = longest - len(seq)
            xs.append(emb[jnp.asarray(np.pad(
                np.asarray(seq, np.int32), (0, pad)))])
            self.row_spans.append((first, len(seq)))
            first += len(seq) + pad
        del emb
        for i in range(cfg["num_hidden_layers"]):
            # a layer's index enters attention by its kind alone
            f_attn = self._jit.setdefault(
                ("attn", is_window(cfg, i)), jax.jit(
                    lambda p, x, i=i: attention(p, x, cfg, i, prec)))
            p = self._params([f"l.{i}.{k}" for k in attn_leaves(cfg, i)])
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
            return _mm(_rms(x, p["norm"], cfg["layernorm_epsilon"]),
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
