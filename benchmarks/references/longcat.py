"""Plain reference for ``longcat-flash-omni``
(huggingface.co/meituan-longcat/LongCat-Flash-Omni ``config.json``; the
language model is the block of the LongCat-Flash technical report): a
SHORTCUT-CONNECTED double layer, two multi-head latent attentions (each
off a query latent, both latents scaled) and two dense SwiGLUs around
ONE expert branch of 512 routed SwiGLU experts and 256 identity
("zero-computation") experts, 12 a token by a softmax router with a
selection bias and weights that are NOT renormalised, no shared expert,
no leading dense layer, plain rotary positions, untied output head.

Straightforward ``jax.numpy`` in float32 under
``jax.default_matmul_precision("highest")``: a full causal forward over
the whole sequence in the UNABSORBED form (per-head keys and values
built from the latent), sublayer by sublayer, expert by expert, no
kernels, no cache, no batching. It imports nothing of the program and
takes nothing the program made: weights come from ``leaf``, from the
seed, in the type the configuration stores them in.

The equations, x the layer's input [S, hidden], eps from the config, no
bias anywhere:

    x1 = x  + MLA_0(RMSNorm(x))
    h  = RMSNorm(x1);  s = MoE(h)            # the shortcut branch
    x2 = x1 + FFN_0(h)
    x3 = x2 + MLA_1(RMSNorm(x2))
    x4 = x3 + FFN_1(RMSNorm(x3)) + s         # the layer's output

- MLA_j(u): cq = RMSNorm(u W_dq); q = (cq W_uq) -> heads x (nope +
  rope), times (hidden / q_lora_rank)^1/2; [c' | kr'] = u W_dkv;
  c = RMSNorm(c'); q_r, k_r <- RoPE (one k_r a position, every head's);
  [k_n,h | v_h] = (c * (hidden / kv_lora_rank)^1/2) W_ukv,h;
  score_h(t, s) = (q_n,h(t) . k_n,h(s) + q_r,h(t) . k_r(s)) *
  (nope + rope)^-1/2, causal softmax; out = concat_h(P v_h) W_o.
- FFN_j(u) = (silu(u W_g) * (u W_u)) W_d at ``ffn_hidden_size``.
- MoE(h): p = softmax(h W_r) over ``router_experts + zero_expert_num``
  in float32; chosen = top-k of p + b (b steers the CHOICE only);
  w_e = routed_scaling_factor * p_e, not divided by anything;
  s = sum_{chosen AND held e < router_experts} w_e E_e(h)
    + (sum_{chosen e >= router_experts} w_e) * h, E_e a SwiGLU at
  ``expert_ffn_hidden_size``.

THE SHARE. The configuration is one chip's share of a 32-chip
expert-parallel layer: real experts ``expert_offset .. expert_offset +
n_routed_experts - 1`` of the router's ``router_experts`` are held; ALL
identity experts are computed here (they cost nothing and live where the
token lives); what the absent real experts would add is left out, here
as in the program, and that partial sum goes on. Logits are over the
``vocab_size`` rows held here.

DEPARTURES from the source and ASSUMPTIONS (the config file repeats
these; the published ``modeling_longcat_flash.py`` was not on this
machine, so the first three are its author's as the issue's writer knew
them):

- the order of the five lines of the block above;
- the router: bias added to the softmax's PROBABILITIES for the choice
  alone, weights ``scaling * p_e`` with no renormalisation over the
  chosen, all in float32;
- identity experts are the router's LAST ``zero_expert_num`` outputs;
- rotary pairing is rotate-half over the rope dims (i with i + d/2);
  the source interleaves (2i with 2i + 1), a fixed permutation of the
  columns of W_uq and W_dkv: the same function class under seeded
  weights. Base ``rope_theta``, no scaling (the config has no
  ``rope_scaling`` key);
- weights are random from the seed, N(0, 0.02); down-projections
  (attention output, dense, expert) N(0, 0.02 / sqrt(2 x 28)); norms 1;
- the selection bias b is N(0, ``BIAS_STD``) from the seed (the
  checkpoint's is learned, to even the load out): the probabilities of
  a 768-wide softmax are about 1e-3 and neighbours near the 12th
  largest lie ``BIAS_STD`` apart, so a bias that wide flips choices
  without emptying experts; the N(0, 0.02) of the other references
  would choose the same 12 experts for every token.

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

ATTN_LEAVES = ("q_a", "q_a_norm", "q_b", "kva", "kv_norm", "kvb", "o")
FFN_LEAVES = ("gate", "up", "down")
PAD = 256     # sequences are padded to a multiple, to bound the shapes
# between the 12th and the 13th largest probability of the router on
# seeded unit-RMS rows at the published widths (6144 -> 768, N(0, 0.02)
# weights; 4,096 rows, two seeds, on the CPU): median 4.0e-4 and
# 4.1e-4, mean 6.0e-4 and 6.1e-4; the 12th itself 1.16e-2. A bias of
# N(0, 5e-4) changes 0.43 of a token's 12 picks and leaves the least
# loaded of the 768 outputs 35-41 picks of a mean 64 (N(0, 0.02): 10.1
# picks change and outputs go empty)
BIAS_STD = 5e-4
PUBLISHED_LAYERS = 28     # down-projections: N(0, std / sqrt(2 * 28))


def held_experts(cfg: Dict) -> range:
    return range(cfg["expert_offset"],
                 cfg["expert_offset"] + cfg["n_routed_experts"])


def router_width(cfg: Dict) -> int:
    return cfg["router_experts"] + cfg["zero_expert_num"]


def leaf_table(cfg: Dict) -> Dict[str, Tuple[Tuple[int, ...], str, float]]:
    h = cfg["hidden_size"]
    H = cfg["num_attention_heads"]
    dq, dc, dn, dr, dv = (cfg["q_lora_rank"], cfg["kv_lora_rank"],
                          cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                          cfg["v_head_dim"])
    ff, fe = cfg["ffn_hidden_size"], cfg["expert_ffn_hidden_size"]
    W = router_width(cfg)
    std = cfg["initializer_range"]
    out_std = std / math.sqrt(2 * PUBLISHED_LAYERS)
    t = {"embed": ((cfg["vocab_size"], h), "normal", std),
         "norm": ((h,), "ones", 0.0),
         "lm_head": ((h, cfg["vocab_size"]), "normal", std)}
    attn = {"q_a": ((h, dq), "normal", std),
            "q_a_norm": ((dq,), "ones", 0.0),
            "q_b": ((dq, H * (dn + dr)), "normal", std),
            "kva": ((h, dc + dr), "normal", std),
            "kv_norm": ((dc,), "ones", 0.0),
            "kvb": ((dc, H * (dn + dv)), "normal", std),
            "o": ((H * dv, h), "normal", out_std)}
    for i in range(cfg["num_layers"]):
        for j in (0, 1):
            t[f"l.{i}.in_norm.{j}"] = ((h,), "ones", 0.0)
            t[f"l.{i}.post_norm.{j}"] = ((h,), "ones", 0.0)
            for k, v in attn.items():
                t[f"l.{i}.a.{j}.{k}"] = v
            t[f"l.{i}.f.{j}.gate"] = ((h, ff), "normal", std)
            t[f"l.{i}.f.{j}.up"] = ((h, ff), "normal", std)
            t[f"l.{i}.f.{j}.down"] = ((ff, h), "normal", out_std)
        t[f"l.{i}.router"] = ((h, W), "normal", std)
        t[f"l.{i}.router_bias"] = ((W,), "normal", BIAS_STD)
        for e in held_experts(cfg):
            t[f"l.{i}.e.{e}.gate"] = ((h, fe), "normal", std)
            t[f"l.{i}.e.{e}.up"] = ((h, fe), "normal", std)
            t[f"l.{i}.e.{e}.down"] = ((fe, h), "normal", out_std)
    return t


def _mm(a, w, precision: str):
    if precision == "fp8":          # the control: see references/gpt.py
        a, w = fp8(a), fp8(w)
    return a @ w


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _rope(x, theta: float):
    """x: [S, heads, d_r]; position = row index; rotate-half."""
    S, _, D = x.shape
    inv = 1.0 / theta ** (jnp.arange(0, D, 2, dtype=jnp.float32) / D)
    f = jnp.arange(S, dtype=jnp.float32)[:, None] * inv[None]
    cos = jnp.concatenate([jnp.cos(f), jnp.cos(f)], -1)[:, None]
    sin = jnp.concatenate([jnp.sin(f), jnp.sin(f)], -1)[:, None]
    x1, x2 = x[..., :D // 2], x[..., D // 2:]
    return x * cos + jnp.concatenate([-x2, x1], -1) * sin


def lora_scales(cfg: Dict) -> Tuple[float, float]:
    """(the query's factor, the keys' and values')."""
    h = cfg["hidden_size"]
    return ((h / cfg["q_lora_rank"]) ** 0.5
            if cfg["mla_scale_q_lora"] else 1.0,
            (h / cfg["kv_lora_rank"]) ** 0.5
            if cfg["mla_scale_kv_lora"] else 1.0)


def attention(p, u, cfg: Dict, precision: str):
    """MLA(u), one sequence u: [S, hidden] (already normed), unabsorbed."""
    S = u.shape[0]
    H = cfg["num_attention_heads"]
    dc, dn, dr, dv = (cfg["kv_lora_rank"], cfg["qk_nope_head_dim"],
                      cfg["qk_rope_head_dim"], cfg["v_head_dim"])
    eps, theta = cfg["rms_norm_eps"], cfg["rope_theta"]
    qs, kvs = lora_scales(cfg)
    cq = _rms(_mm(u, p["q_a"], precision), p["q_a_norm"], eps)
    q = _mm(cq, p["q_b"], precision).reshape(S, H, dn + dr) * qs
    q_n, q_r = q[..., :dn], _rope(q[..., dn:], theta)
    ckr = _mm(u, p["kva"], precision)
    c = _rms(ckr[:, :dc], p["kv_norm"], eps)
    k_r = _rope(ckr[:, None, dc:], theta)[:, 0]                  # [S, dr]
    kv = _mm(c * kvs, p["kvb"], precision).reshape(S, H, dn + dv)
    k_n, v = kv[..., :dn], kv[..., dn:]
    s = (jnp.einsum("qhd,khd->hqk", q_n, k_n)
         + jnp.einsum("qhd,kd->hqk", q_r, k_r)) * (dn + dr) ** -0.5
    s = jnp.where(jnp.tril(jnp.ones((S, S), bool)), s, -jnp.inf)
    a = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("hqk,khd->qhd", a, v).reshape(S, H * dv)
    return _mm(o, p["o"], precision)


def swiglu(x, gate, up, down, precision: str):
    return _mm(jax.nn.silu(_mm(x, gate, precision))
               * _mm(x, up, precision), down, precision)


def route(h, router, bias, cfg: Dict):
    """Chosen router outputs [T, k] (ids past ``router_experts`` are
    identity experts) and their weights [T, k] = scaling * p, NOT
    renormalised. Float32."""
    p = jax.nn.softmax(h @ router, axis=-1)
    _, idx = jax.lax.top_k(p + bias, cfg["moe_topk"])
    return idx, cfg["routed_scaling_factor"] * jnp.take_along_axis(
        p, idx, axis=-1)


def expert_part(h, idx, g, j, gate, up, down, precision: str):
    """g_j * E_j(h) on the tokens that chose expert j, 0 elsewhere."""
    w = jnp.sum(jnp.where(idx == j, g, 0.0), axis=-1)          # [T]
    return w[:, None] * swiglu(h, gate, up, down, precision)


def identity_part(h, idx, g, cfg: Dict):
    """(the sum of a token's weights on identity experts) * h."""
    w = jnp.sum(jnp.where(idx >= cfg["router_experts"], g, 0.0), axis=-1)
    return w[:, None] * h


class ServeReference:
    """Logits of a full forward over ``prompt + served tokens``, and the
    router outputs each position chose in each layer."""

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

    def _fn(self, name: str, make):
        return self._jit.setdefault(name, jax.jit(make))

    def _attend(self, i: int, j: int, xs: List[jax.Array]
                ) -> List[jax.Array]:
        """x + MLA_j(RMSNorm(x)), a sequence at a time."""
        cfg, prec = self.cfg, self.precision
        p = self._params([f"l.{i}.a.{j}.{k}" for k in ATTN_LEAVES])
        w = self._params([f"l.{i}.in_norm.{j}"])[str(j)]
        f = self._fn("attn", lambda p, w, x: x + attention(
            p, _rms(x, w, cfg["rms_norm_eps"]), cfg, prec))
        return [f(p, w, x) for x in xs]

    def _normed(self, i: int, j: int, rows):
        eps = self.cfg["rms_norm_eps"]
        w = self._params([f"l.{i}.post_norm.{j}"])[str(j)]
        return self._fn("norm", lambda x, w: _rms(x, w, eps))(rows, w)

    def _ffn(self, i: int, j: int, h):
        prec = self.precision
        p = self._params([f"l.{i}.f.{j}.{k}" for k in FFN_LEAVES])
        return self._fn("ffn", lambda h, p: swiglu(
            h, p["gate"], p["up"], p["down"], prec))(h, p)

    def _shortcut(self, i: int, h):
        """MoE(h): the held real experts one by one, then the identity
        experts' part; records the layer's choices."""
        cfg, prec = self.cfg, self.precision
        p = self._params([f"l.{i}.router", f"l.{i}.router_bias"])
        idx, g = self._fn("route", lambda h, r, b: route(h, r, b, cfg))(
            h, p["router"], p["router_bias"])
        self.choices.append(np.asarray(idx))
        s = self._fn("identity", lambda h, idx, g: identity_part(
            h, idx, g, cfg))(h, idx, g)
        f_exp = self._fn("expert", lambda h, idx, g, j, p: expert_part(
            h, idx, g, j, p["gate"], p["up"], p["down"], prec))
        for j in held_experts(cfg):        # expert by expert
            pe = self._params([f"l.{i}.e.{j}.{k}" for k in FFN_LEAVES])
            s = s + f_exp(h, idx, g, jnp.int32(j), pe)
        return s

    def _layer(self, i: int, xs: List[jax.Array]) -> List[jax.Array]:
        """The double layer: the attentions a sequence at a time, the
        parts that act on single rows on all sequences side by side."""
        cuts = np.cumsum([x.shape[0] for x in xs])[:-1]
        xs = self._attend(i, 0, xs)                          # x1
        rows = jnp.concatenate(xs, axis=0)
        h = self._normed(i, 0, rows)
        s = self._shortcut(i, h)
        rows = rows + self._ffn(i, 0, h)                     # x2
        xs = self._attend(i, 1, list(jnp.split(rows, cuts, axis=0)))
        rows = jnp.concatenate(xs, axis=0)                   # x3
        rows = rows + self._ffn(i, 1, self._normed(i, 1, rows)) + s
        return list(jnp.split(rows, cuts, axis=0))

    def logits(self, requests: List[Tuple[np.ndarray, np.ndarray]]
               ) -> List[np.ndarray]:
        """For each (prompt, served) pair: float32 logits [n, vocab] at
        the n positions that produced the served tokens (the last prompt
        position, then each served token but the last). Afterwards
        ``self.choices[i]`` holds layer i's chosen router outputs for
        the rows of all (padded) sequences side by side;
        ``self.row_spans`` each request's (first row, length)."""
        cfg, prec = self.cfg, self.precision

        def head(p, x):
            return _mm(_rms(x, p["norm"], cfg["rms_norm_eps"]),
                       p["lm_head"], prec)

        f_head = self._fn("head", head)
        self.choices, self.row_spans = [], []
        with jax.default_matmul_precision("highest"):
            emb = self._params(["embed"])["embed"]
            xs, first = [], 0
            for prompt, served in requests:
                seq = np.concatenate([prompt, served[:-1]]).astype(np.int32)
                pad = -len(seq) % PAD
                xs.append(emb[jnp.asarray(np.pad(seq, (0, pad)))])
                self.row_spans.append((first, len(seq)))
                first += len(seq) + pad
            del emb
            for i in range(cfg["num_layers"]):
                xs = self._layer(i, xs)
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
        """Per layer, the chosen router outputs [real rows, k] of the
        last ``logits`` call, the requests' real positions in order."""
        keep = np.concatenate([np.arange(a, a + n)
                               for a, n in self.row_spans])
        return [c[keep] for c in self.choices]
