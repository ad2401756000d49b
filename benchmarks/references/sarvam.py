"""Plain reference for ``sarvam-105b``
(huggingface.co/sarvamai/sarvam-105b ``config.json``, ``model_type``
``sarvam_mla``): multi-head latent attention with YaRN-scaled rotary
positions, a leading dense SwiGLU layer, then layers of 128 routed
SwiGLU experts (8 per token, sigmoid scores, a selection bias, scaling
2.5) plus one shared expert, untied output head.

Straightforward ``jax.numpy`` in float32 under
``jax.default_matmul_precision("highest")``: a full causal forward over
the whole sequence in the UNABSORBED form (per-head keys and values
built from the latent), layer by layer, expert by expert, no kernels, no
cache, no batching. It imports nothing of the program and takes nothing
the program made: weights come from ``leaf``, from the seed, in the type
the configuration stores them in.

The equations, with x^ = RMSNorm(x), eps from the config:

- q = x^ W_q -> heads x (nope + rope); [c | k_r] = x^ W_kva;
  c <- RMSNorm_c(c); q_r, k_r <- RoPE; [k_n,h | v_h] = c W_kvb,h;
  score_h(t, s) = (q_n,h(t) . k_n,h(s) + q_r,h(t) . k_r(s)) * sigma,
  causal; sigma = q_head_dim^-1/2 * m^2, m = 0.1 * mscale_all_dim *
  ln(factor) + 1; cos/sin carry yarn(factor, mscale) / yarn(factor,
  mscale_all_dim). o = concat_h(softmax . v_h) W_o.
- s = sigmoid(x^ W_r) in float32; chosen = top-k of s + b; g_i = scaling
  * s_i / sum_{chosen} s_j; y = sum_{chosen AND held} g_i E_i(x^) +
  E_shared(x^), E(x) = (silu(x W_g) * (x W_u)) W_d. Layer 0: the same
  SwiGLU at the dense width, no router.

THE SHARE. The configuration is one chip's share of a four-chip
expert-parallel layer: experts ``expert_offset .. expert_offset +
num_experts - 1`` of the router's ``router_experts`` are held. g is
normalised over all chosen experts; only the chosen AND held are
summed; what the absent experts would add is left out, here as in the
program, and that partial sum goes on to the next layer. Logits are over
the ``vocab_size`` rows held here.

ASSUMED (the config file repeats these):

- ``use_qk_norm``: a learned RMSNorm on each head's q_head_dim-wide
  query before rotation; the key side is the RMSNorm on the latent
  (which keeps the cache latent). Weights 1.
- a direct W_q (the config has no ``q_lora_rank``).
- no expert groups (no ``n_group`` key), normalised top-k weights.
- rotary pairing is rotate-half over the rope dims (i with i + d/2).
  DeepSeek's published code de-interleaves q_r/k_r first, a fixed
  permutation of the columns of W_q and W_kva: the same function class
  with seeded weights.
- the selection bias b is N(0, 0.02) from the seed: as wide as the gap
  between neighbouring scores near the k-th, so it changes choices
  without emptying experts (the published checkpoint's is learned, to
  even the load out). At N(0, 0.1) a holder's experts took 22-28% of
  the pairs by the seed and several went without a pair in a step, so
  the bytes a decode step read, and the rate, followed the seed.

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

ATTN_LEAVES = ("in_norm", "q", "q_norm", "kva", "kv_norm", "kvb", "o",
               "post_norm")
DENSE_LEAVES = ("gate", "up", "down")
MOE_LEAVES = ("router", "router_bias", "sh_gate", "sh_up", "sh_down")
EXPERT_LEAVES = ("gate", "up", "down")
PAD = 256     # sequences are padded to a multiple, to bound the shapes
BIAS_STD = 0.02
PUBLISHED_LAYERS = 32     # down-projections: N(0, std / sqrt(2 * 32))


def held_experts(cfg: Dict) -> range:
    return range(cfg["expert_offset"],
                 cfg["expert_offset"] + cfg["num_experts"])


def leaf_table(cfg: Dict) -> Dict[str, Tuple[Tuple[int, ...], str, float]]:
    h = cfg["hidden_size"]
    H = cfg["num_attention_heads"]
    dc, dn, dr, dv = (cfg["kv_lora_rank"], cfg["qk_nope_head_dim"],
                      cfg["qk_rope_head_dim"], cfg["v_head_dim"])
    ff, fe = cfg["intermediate_size"], cfg["moe_intermediate_size"]
    fs = fe * cfg["num_shared_experts"]
    E = cfg["router_experts"]
    std = cfg["initializer_range"]
    out_std = std / math.sqrt(2 * PUBLISHED_LAYERS)
    t = {"embed": ((cfg["vocab_size"], h), "normal", std),
         "norm": ((h,), "ones", 0.0),
         "lm_head": ((h, cfg["vocab_size"]), "normal", std)}
    attn = {"in_norm": ((h,), "ones", 0.0),
            "q": ((h, H * (dn + dr)), "normal", std),
            "q_norm": ((dn + dr,), "ones", 0.0),
            "kva": ((h, dc + dr), "normal", std),
            "kv_norm": ((dc,), "ones", 0.0),
            "kvb": ((dc, H * (dn + dv)), "normal", std),
            "o": ((H * dv, h), "normal", std),
            "post_norm": ((h,), "ones", 0.0)}
    for i in range(cfg["num_hidden_layers"]):
        for k, v in attn.items():
            t[f"l.{i}.{k}"] = v
        if i < cfg["first_k_dense_replace"]:
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


def _yarn_mscale(factor: float, mscale: float) -> float:
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def yarn_inv_freq(dim: int, theta: float, rs: Dict) -> np.ndarray:
    """``deepseek_yarn``: plain inverse frequencies where a dimension
    turns more than ``beta_fast`` times over the original context,
    plain / factor where it turns fewer than ``beta_slow`` times, a
    linear ramp over the dimensions between."""
    plain = 1.0 / theta ** (np.arange(0, dim, 2, dtype=np.float64) / dim)
    orig = rs["original_max_position_embeddings"]

    def dim_of(rotations):
        return dim * math.log(orig / (rotations * 2 * math.pi)) \
            / (2 * math.log(theta))

    low = max(math.floor(dim_of(rs["beta_fast"])), 0)
    high = min(math.ceil(dim_of(rs["beta_slow"])), dim - 1)
    span = high - low if high != low else 0.001
    ramp = np.clip((np.arange(dim // 2) - low) / span, 0.0, 1.0)
    return plain * (1.0 - ramp) + plain / rs["factor"] * ramp


def _rope(x, cfg: Dict):
    """x: [S, heads, d_r]; position = row index."""
    S, _, D = x.shape
    rs = cfg["rope_scaling"]
    inv = jnp.asarray(yarn_inv_freq(D, cfg["rope_theta"], rs), jnp.float32)
    m = _yarn_mscale(rs["factor"], rs["mscale"]) \
        / _yarn_mscale(rs["factor"], rs["mscale_all_dim"])
    f = jnp.arange(S, dtype=jnp.float32)[:, None] * inv[None]
    cos = m * jnp.concatenate([jnp.cos(f), jnp.cos(f)], -1)[:, None]
    sin = m * jnp.concatenate([jnp.sin(f), jnp.sin(f)], -1)[:, None]
    x1, x2 = x[..., :D // 2], x[..., D // 2:]
    return x * cos + jnp.concatenate([-x2, x1], -1) * sin


def softmax_scale(cfg: Dict) -> float:
    rs = cfg["rope_scaling"]
    m = _yarn_mscale(rs["factor"], rs["mscale_all_dim"])
    return cfg["q_head_dim"] ** -0.5 * m * m


def attention(p, x, cfg: Dict, precision: str):
    """x + latent attention, one sequence x: [S, hidden], unabsorbed."""
    S = x.shape[0]
    H = cfg["num_attention_heads"]
    dc, dn, dr, dv = (cfg["kv_lora_rank"], cfg["qk_nope_head_dim"],
                      cfg["qk_rope_head_dim"], cfg["v_head_dim"])
    eps = cfg["rms_norm_eps"]
    h = _rms(x, p["in_norm"], eps)
    q = _rms(_mm(h, p["q"], precision).reshape(S, H, dn + dr),
             p["q_norm"], eps)
    q_n, q_r = q[..., :dn], _rope(q[..., dn:], cfg)
    ckr = _mm(h, p["kva"], precision)
    c = _rms(ckr[:, :dc], p["kv_norm"], eps)
    k_r = _rope(ckr[:, None, dc:], cfg)[:, 0]                 # [S, dr]
    kv = _mm(c, p["kvb"], precision).reshape(S, H, dn + dv)
    k_n, v = kv[..., :dn], kv[..., dn:]
    s = (jnp.einsum("qhd,khd->hqk", q_n, k_n)
         + jnp.einsum("qhd,kd->hqk", q_r, k_r)) * softmax_scale(cfg)
    s = jnp.where(jnp.tril(jnp.ones((S, S), bool)), s, -jnp.inf)
    a = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("hqk,khd->qhd", a, v).reshape(S, H * dv)
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
    return idx, cfg["routed_scaling_factor"] * sel / sel.sum(-1,
                                                             keepdims=True)


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
        eps = cfg["rms_norm_eps"]
        rows = jnp.concatenate(xs, axis=0)
        f_norm = self._jit.setdefault(
            "norm", jax.jit(lambda x, w: _rms(x, w, eps)))
        f_ffn = self._jit.setdefault(
            "ffn", jax.jit(lambda h, p: swiglu(h, p["gate"], p["up"],
                                               p["down"], prec)))
        h = f_norm(rows, self._params([f"l.{i}.post_norm"])["post_norm"])
        if i < cfg["first_k_dense_replace"]:
            y = f_ffn(h, self._params([f"l.{i}.{k}"
                                       for k in DENSE_LEAVES]))
            self.choices.append(None)
        else:
            p = self._params([f"l.{i}.{k}" for k in MOE_LEAVES])
            f_route = self._jit.setdefault(
                "route", jax.jit(lambda h, r, b: route(h, r, b, cfg)))
            idx, g = f_route(h, p["router"], p["router_bias"])
            self.choices.append(np.asarray(idx))
            y = f_ffn(h, {"gate": p["sh_gate"], "up": p["sh_up"],
                          "down": p["sh_down"]})
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

    def logits(self, requests: List[Tuple[np.ndarray, np.ndarray]]
               ) -> List[np.ndarray]:
        """For each (prompt, served) pair: float32 logits [n, vocab] at
        the n positions that produced the served tokens (the last prompt
        position, then each served token but the last). Afterwards
        ``self.choices[i]`` holds layer i's chosen experts for the rows
        of all (padded) sequences side by side, None for a dense
        layer; ``self.row_spans`` each request's (first row, length)."""
        cfg, prec = self.cfg, self.precision
        f_attn = self._jit.setdefault(
            "attn", jax.jit(lambda p, x: attention(p, x, cfg, prec)))

        def head(p, x):
            return _mm(_rms(x, p["norm"], cfg["rms_norm_eps"]),
                       p["lm_head"], prec)

        f_head = self._jit.setdefault("head", jax.jit(head))
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
            for i in range(cfg["num_hidden_layers"]):
                p = self._params([f"l.{i}.{k}" for k in ATTN_LEAVES])
                xs = [f_attn(p, x) for x in xs]
                xs = self._mlp(i, xs)
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
