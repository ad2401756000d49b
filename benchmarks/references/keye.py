"""Plain reference for the language model of ``keye-vl-2.0-30b-a3b``
(huggingface.co/Kwai-Keye/Keye-VL-2.0-30B-A3B ``config.json``,
``model_type`` ``KeyeVL2``, 30B-A3B): 48 identical layers of
grouped-query attention (32 query heads on 4 KV heads of 128, an RMSNorm
on every q and k head, rotary at base 1e7) that attends to the 2,048
keys a learned INDEXER chooses (``sa_config``: 16 index heads of 64 on
ONE 64-wide index key a position), then 128 routed SwiGLU experts of
768 behind a softmax router (8 per token, renormalised, no bias, no
shared expert), untied output head. Text only: the vision tower is not
here (the catalog's ``config`` gives none of its widths).

Straightforward ``jax.numpy`` in float32 under
``jax.default_matmul_precision("highest")``: a full causal forward over
the whole sequence, layer by layer, expert by expert, the index scores
and the attention scores of a block of rows against EVERY key with the
masks written out, ``lax.top_k`` for the selection, no kernels, no
cache, no batching. It imports nothing of the program and takes nothing
the program made: weights come from ``leaf``, from the seed, in the type
the configuration stores them in.

The block (config keys in backticks), eps = ``rms_norm_eps``, for a
layer with input x_t at position t, u_t = RMSNorm(x_t):

1. q_{t,h} = R_t(RMSNorm_128((u_t W_q)_h)), h = 0..31;
   k_{t,g} = R_t(RMSNorm_128((u_t W_k)_g)), v_{t,g} = (u_t W_v)_g,
   g = 0..3; no biases; R_t = rotary over all 128 dims, base
   ``rope_theta``, rotate-half pairing.
2. Index: a_{t,j} = R'_t((u_t W_qI)_j) in R^64, j = 0..15;
   b_s = R'_s(LayerNorm_64(u_s W_kI)) (ONE index key a position);
   c_t = (u_t W_wI) * 16^-0.5 * 64^-0.5;
   I_{t,s} = sum_j c_{t,j} * ReLU(a_{t,j} . b_s) for s <= t; R' = rotary
   over the whole 64-wide index head at the same base.
3. S_t = the ``sa_config.topk`` positions s <= t of largest I_{t,s}
   (ties to the lower position); all of them while t + 1 <= topk.
4. o_{t,h} = sum_{s in S_t} softmax_{s in S_t}(q_{t,h} . k_{s,g(h)} /
   sqrt(128)) v_{s,g(h)}, query head h reads KV head h // 8;
   x' = x + concat_h(o_{t,h}) W_o.
5. h = RMSNorm(x'); p = softmax_128(h W_r) in float32; E = top-8 of p;
   w_e = p_e / sum_E p (``norm_topk_prob``); y = sum over the chosen AND
   HELD e of w_e W2_e(silu(W1_e h) * W3_e h), ``moe_intermediate_size``
   wide; x'' = x' + y.

logits = RMSNorm(x_L) W_head, untied.

THE SHARE. The configuration is one chip's share of an 8-chip
expert-parallel layer: experts ``expert_offset .. expert_offset +
num_experts - 1`` of the router's ``router_experts`` are held (the
file's ``num_experts`` counts the HELD ones). w is normalised over all
chosen experts; only the chosen AND held are summed; what the absent
experts would add is left out, here as in the program. The vocabulary
is whole.

ASSUMED (the config file repeats these, with where each comes from): the
q/k head norms of step 1 (the Qwen3-MoE lineage whose keys the config
carries); ``mrope_section``: a text position is the same on its three
axes, which is ordinary rotary; all of step 2 beyond the sizes (the
published DSA indexer of DeepSeek-V3.2-Exp, which the catalog's
description names: queries from the layer's normed input, a LayerNorm
with weight 1 and bias 0 on the key, the two scale factors; no FP8, no
Hadamard rotation); weights N(0, ``initializer_range``) by
``references/gpt.py::leaf``'s recipe, down-projections (o, expert)
N(0, 0.02 / sqrt(2 x 48)), norms 1.

Two controls. ``precision="fp8"`` rounds the operands of every linear
layer (the index's three projections among them) to fp8; the router's
product stays float32, as the configuration states it.
``precision="dense"`` skips step 3: every earlier key is attended to.
"""
from __future__ import annotations

import math
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .gpt import fp8, key_data, leaf, name_id  # the seeded-leaf recipe
from .mistral import served_gap  # noqa: F401  (the families' import)

ATTN_LEAVES = ("in_norm", "q", "k", "v", "o", "q_norm", "k_norm", "iq",
               "ik", "iw", "ik_norm", "ik_norm_bias")
MOE_LEAVES = ("post_norm", "router")
EXPERT_LEAVES = ("gate", "up", "down")
PAD = 256     # sequences are padded to a multiple, to bound the shapes
ROWS = 256    # query rows a block, against every key
PUBLISHED_LAYERS = 48     # down-projections: N(0, std / sqrt(2 * 48))


def held_experts(cfg: Dict) -> range:
    return range(cfg["expert_offset"],
                 cfg["expert_offset"] + cfg["num_experts"])


def leaf_table(cfg: Dict) -> Dict[str, Tuple[Tuple[int, ...], str, float]]:
    h = cfg["hidden_size"]
    H, KV, d = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                cfg["head_dim"])
    sa = cfg["sa_config"]
    Hi, di = sa["indexer_num_heads"], sa["indexer_head_dim"]
    fe, E = cfg["moe_intermediate_size"], cfg["router_experts"]
    std = cfg["initializer_range"]
    out_std = std / math.sqrt(2 * PUBLISHED_LAYERS)
    t = {"embed": ((cfg["vocab_size"], h), "normal", std),
         "norm": ((h,), "ones", 0.0),
         "lm_head": ((h, cfg["vocab_size"]), "normal", std)}
    for i in range(cfg["num_hidden_layers"]):
        for n in ("in_norm", "post_norm"):
            t[f"l.{i}.{n}"] = ((h,), "ones", 0.0)
        t[f"l.{i}.q"] = ((h, H * d), "normal", std)
        t[f"l.{i}.k"] = ((h, KV * d), "normal", std)
        t[f"l.{i}.v"] = ((h, KV * d), "normal", std)
        t[f"l.{i}.o"] = ((H * d, h), "normal", out_std)
        t[f"l.{i}.q_norm"] = ((d,), "ones", 0.0)
        t[f"l.{i}.k_norm"] = ((d,), "ones", 0.0)
        t[f"l.{i}.iq"] = ((h, Hi * di), "normal", std)
        t[f"l.{i}.ik"] = ((h, di * sa["indexer_num_kv_heads"]), "normal",
                          std)
        t[f"l.{i}.iw"] = ((h, Hi), "normal", std)
        t[f"l.{i}.ik_norm"] = ((di,), "ones", 0.0)
        t[f"l.{i}.ik_norm_bias"] = ((di,), "zeros", 0.0)
        t[f"l.{i}.router"] = ((h, E), "normal", std)
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


def _layer_norm(x, w, b, eps):
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), -1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * w + b


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


def selected(scores, seen, topk: int):
    """Step 3 for a block of rows: scores [rows, S], seen [rows, S]
    (s <= t). The ``topk`` largest seen scores of each row, ties to the
    lower position (``lax.top_k`` lists equal values by rising index);
    every seen key where there are no more than ``topk``."""
    S = scores.shape[-1]
    if topk >= S:
        return seen
    _, idx = jax.lax.top_k(jnp.where(seen, scores, -jnp.inf), topk)
    rows = jnp.arange(scores.shape[0])[:, None]
    return jnp.zeros(scores.shape, bool).at[rows, idx].set(True) & seen


def attention(p, x, cfg: Dict, precision: str):
    """Steps 1-4 for one sequence x: [S, hidden]. Returns (x', the kept
    sets [S, S] bool: row t, key s)."""
    S = x.shape[0]
    H, KV, d = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                cfg["head_dim"])
    sa = cfg["sa_config"]
    Hi, di, topk = (sa["indexer_num_heads"], sa["indexer_head_dim"],
                    sa["topk"])
    eps, theta = cfg["rms_norm_eps"], cfg["rope_theta"]
    u = _rms(x, p["in_norm"], eps)
    q = _rope(_rms(_mm(u, p["q"], precision).reshape(S, H, d),
                   p["q_norm"], eps), theta)
    k = _rope(_rms(_mm(u, p["k"], precision).reshape(S, KV, d),
                   p["k_norm"], eps), theta)
    v = _mm(u, p["v"], precision).reshape(S, KV, d)
    a = _rope(_mm(u, p["iq"], precision).reshape(S, Hi, di), theta)
    b = _rope(_layer_norm(_mm(u, p["ik"], precision), p["ik_norm"],
                          p["ik_norm_bias"], eps)[:, None], theta)[:, 0]
    c = _mm(u, p["iw"], precision) * (Hi ** -0.5 * di ** -0.5)
    k = jnp.repeat(k, H // KV, axis=1)       # query head h reads h // G
    v = jnp.repeat(v, H // KV, axis=1)
    j = jnp.arange(S)[None, :]

    def rows_block(r):                       # ROWS rows, every key
        cut = lambda z: jax.lax.dynamic_slice_in_dim(z, r, ROWS, 0)
        ii = (r + jnp.arange(ROWS))[:, None]
        seen = j <= ii
        if precision == "dense":             # the control: no selection
            keep = seen
        else:
            idx = jnp.einsum("qjd,kd->jqk", cut(a), b)
            idx = jnp.sum(jnp.maximum(idx, 0.0) * cut(c).T[:, :, None],
                          axis=0)
            keep = selected(idx, seen, topk)
        s = jnp.einsum("qhd,khd->hqk", cut(q), k) / math.sqrt(d)
        s = jnp.where(keep, s, -jnp.inf)
        e = jnp.where(keep, jnp.exp(s - jnp.max(s, -1, keepdims=True)), 0.0)
        return jnp.einsum("hqk,khd->qhd", e / e.sum(-1, keepdims=True),
                          v), keep

    # block after block (S is a multiple of ROWS: ``PAD``)
    o, kept = jax.lax.map(rows_block, jnp.arange(0, S, ROWS))
    return (x + _mm(o.reshape(S, H * d), p["o"], precision),
            kept.reshape(S, S))


def swiglu(x, gate, up, down, precision: str):
    return _mm(jax.nn.silu(_mm(x, gate, precision))
               * _mm(x, up, precision), down, precision)


def route(h, router, cfg: Dict):
    """Chosen experts [T, k] (numbered over the router's width) and
    their weights [T, k], normalised over all k chosen. Float32."""
    p = jax.nn.softmax(h @ router, axis=-1)
    sel, idx = jax.lax.top_k(p, cfg["num_experts_per_tok"])
    return idx, sel / sel.sum(-1, keepdims=True)


def expert_part(u, idx, w, j: int, gate, up, down, precision: str):
    """w_j * E_j(u) on the tokens that chose expert j, 0 elsewhere."""
    wj = jnp.sum(jnp.where(idx == j, w, 0.0), axis=-1)          # [T]
    return wj[:, None] * swiglu(u, gate, up, down, precision)


class ServeReference:
    """Logits of a full forward over ``prompt + served tokens``, the
    experts each position chose in each layer, and the keys each of the
    rows that produced a served token kept in each layer."""

    def __init__(self, cfg: Dict, seed: int, precision: str = "float32"):
        self.cfg = cfg
        self.key = jax.random.wrap_key_data(jnp.asarray(key_data(seed)))
        self.table = leaf_table(cfg)
        self.store = jnp.dtype(cfg["torch_dtype"])
        self.precision = precision
        self._jit: Dict = {}
        self.choices: List[np.ndarray] = []
        # [layer][sequence] -> bool [probed rows, padded length]
        self.kept: List[List[np.ndarray]] = []

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
        """Step 5 for every sequence of ``xs``, layer ``i``: rows of all
        sequences side by side (the experts act on single rows)."""
        cfg, prec = self.cfg, self.precision
        eps = cfg["rms_norm_eps"]
        rows = jnp.concatenate(xs, axis=0)
        f_norm = self._jit.setdefault(
            "norm", jax.jit(lambda x, w: _rms(x, w, eps)))
        p = self._params([f"l.{i}.{k}" for k in MOE_LEAVES])
        h = f_norm(rows, p["post_norm"])
        f_route = self._jit.setdefault(
            "route", jax.jit(lambda h, r: route(h, r, cfg)))
        idx, w = f_route(h, p["router"])
        self.choices.append(np.asarray(idx))
        f_exp = self._jit.setdefault("expert", jax.jit(
            lambda u, idx, w, j, pe: expert_part(
                u, idx, w, j, pe["gate"], pe["up"], pe["down"], prec)))
        y = jnp.zeros_like(rows)
        for j in held_experts(cfg):        # expert by expert
            pe = self._params([f"l.{i}.e.{j}.{k}" for k in EXPERT_LEAVES])
            y = y + f_exp(h, idx, w, jnp.int32(j), pe)
        cuts = np.cumsum([x.shape[0] for x in xs])[:-1]
        return list(jnp.split(rows + y, cuts, axis=0))

    def forward(self, seqs: List[np.ndarray],
                probes: Optional[List[Tuple[int, int]]] = None
                ) -> List[jax.Array]:
        """The final hidden rows [padded length, hidden] of each whole
        sequence. Afterwards ``self.choices[i]`` holds layer i's chosen
        experts for the rows of all (padded) sequences side by side,
        ``self.row_spans`` each sequence's (first row, length), and,
        with ``probes`` = a (first row, rows) a sequence, ``self.kept[i]
        [n]`` the kept sets of those rows of sequence n in layer i."""
        cfg, prec = self.cfg, self.precision
        self.choices, self.row_spans, self.kept = [], [], []
        emb = self._params(["embed"])["embed"]
        xs, first = [], 0
        # one padded length for all: one attention program
        longest = max(len(seq) for seq in seqs)
        longest += -longest % PAD
        for seq in seqs:
            pad = longest - len(seq)
            xs.append(emb[jnp.asarray(np.pad(
                np.asarray(seq, np.int32), (0, pad)))])
            self.row_spans.append((first, len(seq)))
            first += len(seq) + pad
        del emb
        f_attn = self._jit.setdefault("attn", jax.jit(
            lambda p, x: attention(p, x, cfg, prec)))
        for i in range(cfg["num_hidden_layers"]):
            p = self._params([f"l.{i}.{k}" for k in ATTN_LEAVES])
            kept = []
            for n, x in enumerate(xs):
                xs[n], keep = f_attn(p, x)
                if probes is not None:
                    lo, rows = probes[n]
                    kept.append(np.asarray(keep[lo:lo + rows]))
                del keep
            self.kept.append(kept)
            xs = self._mlp(i, xs)
        return xs

    def logits(self, requests: List[Tuple[np.ndarray, np.ndarray]]
               ) -> List[np.ndarray]:
        """For each (prompt, served) pair: float32 logits [n, vocab] at
        the n positions that produced the served tokens (the last prompt
        position, then each served token but the last). Those rows'
        kept sets are in ``self.kept`` afterwards."""
        cfg, prec = self.cfg, self.precision

        def head(p, x):
            return _mm(_rms(x, p["norm"], cfg["rms_norm_eps"]),
                       p["lm_head"], prec)

        f_head = self._jit.setdefault("head", jax.jit(head))
        with jax.default_matmul_precision("highest"):
            xs = self.forward(
                [np.concatenate([prompt, served[:-1]])
                 for prompt, served in requests],
                [(len(prompt) - 1, len(served))
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
        """Per layer, the chosen experts [real rows, k] of the last
        ``logits`` call, the requests' real positions in order."""
        keep = np.concatenate([np.arange(a, a + n)
                               for a, n in self.row_spans])
        return [c[keep] for c in self.choices]
