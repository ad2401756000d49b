"""Plain reference for ``deepseek-v3.2-exp``
(huggingface.co/deepseek-ai/DeepSeek-V3.2-Exp ``config.json``,
``model_type`` ``deepseek_v32``, 671B-A37B): multi-head latent attention
with a QUERY latent and YaRN-scaled rotary positions, in which every
query attends to the 2,048 rows of the latent cache a learned INDEXER
chooses (64 index heads of 128 off the query latent, ONE 128-wide index
key a position); leading dense SwiGLU layers, then layers of 256 routed
SwiGLU experts (8 per token of sigmoid scores, chosen within the 4 of 8
groups the router keeps first, a selection bias, scaling 2.5) plus one
shared expert; untied output head.

Straightforward ``jax.numpy`` in float32 under
``jax.default_matmul_precision("highest")``: a full causal forward over
the whole sequence in the UNABSORBED form (per-head keys and values
built from the latent), layer by layer, expert by expert, the index
scores and the attention scores of a block of rows against EVERY key
with the masks written out, ``lax.top_k`` for the selection, the groups
and the experts, no kernels, no cache, no batching. It imports nothing
of the program and takes nothing the program made: weights come from
``leaf``, from the seed, in the type the configuration stores them in.

The block (config keys in backticks), eps = ``rms_norm_eps``, no biases
anywhere, for a layer with input x_t at position t, u_t = RMSNorm(x_t):

1. Query: cq_t = RMSNorm_``q_lora_rank``(u_t W_dq); q_{t,h} =
   (cq_t W_uq)_h in R^192, h = 0..127, split [qn | qr] = 128 + 64;
   qr <- R_t(qr). No norm on a head's query: the norm is on the latent.
2. Latent: [ckv_t | kr_t] = u_t W_dkv (512 + 64); c_t = RMSNorm_512(
   ckv_t); kr_t <- R_t(kr_t), one rotated key a position for all heads.
   k_{s,h} = [(c_s W_uk)_h | kr_s], v_{s,h} = (c_s W_uv)_h. R = rotary
   over the 64 rotated dims at ``deepseek_yarn`` inverse frequencies
   (factor 40 over 4,096, beta 32 / 1), rotate-half pairing.
3. Index: a_{t,j} = (cq_t W_qI)_j in R^128, j = 0..63; b_s =
   LayerNorm_128(u_s W_kI) (weight 1, bias 0; ONE index key a position);
   the FIRST 64 dims of every a_{t,j} and of b_s turn by the same R, the
   other 64 do not; w_t = (u_t W_wI) * 64^-0.5 * 128^-0.5;
   I_{t,s} = sum_j w_{t,j} * ReLU(a_{t,j} . b_s) for s <= t.
4. S_t = the ``index_topk`` positions s <= t of largest I_{t,s} (ties
   to the lower position); all of them while t + 1 <= ``index_topk``.
5. o_{t,h} = sum_{s in S_t} softmax_{s in S_t}(sigma q_{t,h} . k_{s,h})
   v_{s,h}, sigma = 192^-0.5 * (0.1 ln 40 + 1)^2;
   x' = x + concat_h(o_{t,h}) W_o.
6. The leading ``first_k_dense_replace`` layers: x'' = x' +
   W_down(silu(W_gate h) * W_up h), ``intermediate_size`` wide,
   h = RMSNorm(x').
7. Expert layers: s = sigmoid(h W_r) in float32 over the router's
   width; s' = s + bias; group g = that many neighbouring experts, its
   score the sum of its two largest s'; the ``topk_group`` groups of
   largest score are kept (ties to the lower group), every other
   expert's s' set to 0; E = the ``num_experts_per_tok`` experts of
   largest s' (all in kept groups unless a kept s' is negative); w_e =
   ``routed_scaling_factor`` * s_e / sum_E s (from s, not s');
   y = sum over the chosen AND HELD e of w_e W2_e(silu(W1_e h) * W3_e h)
   + shared(h); x'' = x' + y.

logits = RMSNorm(x_L) W_head, untied.

THE SHARE. The configuration is one chip's share of a 16-chip
expert-parallel layer: experts ``expert_offset .. expert_offset +
n_routed_experts - 1`` of the router's ``router_experts`` are held (the
file's ``n_routed_experts`` counts the HELD ones). w is normalised over
all chosen experts; only the chosen AND held are summed; what the absent
experts would add is left out, here as in the program, and that partial
sum plus the shared expert goes on. Logits are over the ``vocab_size``
rows held here.

ASSUMED (the config file repeats these, with where each comes from): all
of step 3 beyond the sizes (the published DSA indexer: queries off the
query latent, a LayerNorm on the key, the two scale factors); the split
64 turned + 64 plain (the config gives ``index_head_dim`` and no rotary
width of its own; ``qk_rope_head_dim`` is taken); rotate-half pairing (a
fixed permutation of the columns of W_uq / W_dkv / W_qI / W_kI against
the source's interleaved layout); no Hadamard rotation of index queries
and keys (an orthogonal map of both leaves every score as it was) and
no FP8; the multi-token-prediction module (``num_nextn_predict_layers``)
is not built: it does not enter the next-token logits; the selection
bias N(0, 0.02) from the seed; weights N(0, ``initializer_range``) by
``references/gpt.py::leaf``'s recipe, down-projections (W_o, dense,
expert, shared) N(0, 0.02 / sqrt(2 x 61)), norms 1.

Two controls. ``precision="fp8"`` rounds the operands of every linear
layer (the index's three projections among them) to fp8; the router's
product stays float32, as the configuration states it.
``precision="dense"`` skips step 4: every earlier row is attended to.
"""
from __future__ import annotations

import math
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .gpt import fp8, key_data, leaf, name_id  # the seeded-leaf recipe
from .mistral import served_gap  # noqa: F401  (the families' import)

ATTN_LEAVES = ("in_norm", "q_a", "q_a_norm", "q_b", "kva", "kv_norm",
               "kvb", "o", "iq", "ik", "iw", "ik_norm", "ik_norm_bias")
DENSE_LEAVES = ("gate", "up", "down")
MOE_LEAVES = ("router", "router_bias", "sh_gate", "sh_up", "sh_down")
EXPERT_LEAVES = ("gate", "up", "down")
PAD = 256     # sequences are padded to a multiple, to bound the shapes
ROWS = 128    # query rows a block, against every key
BIAS_STD = 0.02
PUBLISHED_LAYERS = 61     # down-projections: N(0, std / sqrt(2 * 61))


def held_experts(cfg: Dict) -> range:
    return range(cfg["expert_offset"],
                 cfg["expert_offset"] + cfg["n_routed_experts"])


def leaf_table(cfg: Dict) -> Dict[str, Tuple[Tuple[int, ...], str, float]]:
    h, H = cfg["hidden_size"], cfg["num_attention_heads"]
    dq, dc, dn, dr, dv = (cfg["q_lora_rank"], cfg["kv_lora_rank"],
                          cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                          cfg["v_head_dim"])
    Hi, di = cfg["index_n_heads"], cfg["index_head_dim"]
    ff, fe = cfg["intermediate_size"], cfg["moe_intermediate_size"]
    fs = fe * cfg["n_shared_experts"]
    E = cfg["router_experts"]
    std = cfg["initializer_range"]
    out_std = std / math.sqrt(2 * PUBLISHED_LAYERS)
    t = {"embed": ((cfg["vocab_size"], h), "normal", std),
         "norm": ((h,), "ones", 0.0),
         "lm_head": ((h, cfg["vocab_size"]), "normal", std)}
    attn = {"in_norm": ((h,), "ones", 0.0),
            "q_a": ((h, dq), "normal", std),
            "q_a_norm": ((dq,), "ones", 0.0),
            "q_b": ((dq, H * (dn + dr)), "normal", std),
            "kva": ((h, dc + dr), "normal", std),
            "kv_norm": ((dc,), "ones", 0.0),
            "kvb": ((dc, H * (dn + dv)), "normal", std),
            "o": ((H * dv, h), "normal", out_std),
            "iq": ((dq, Hi * di), "normal", std),
            "ik": ((h, di), "normal", std),
            "iw": ((h, Hi), "normal", std),
            "ik_norm": ((di,), "ones", 0.0),
            "ik_norm_bias": ((di,), "zeros", 0.0),
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


def _layer_norm(x, w, b, eps):
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), -1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * w + b


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
    """R of step 2. x: [S, heads, d_r]; position = row index. The angles
    in float64 (a position of thousands times a frequency), their
    cosines and sines in float32."""
    S, _, D = x.shape
    rs = cfg["rope_scaling"]
    inv = yarn_inv_freq(D, cfg["rope_theta"], rs)
    m = _yarn_mscale(rs["factor"], rs["mscale"]) \
        / _yarn_mscale(rs["factor"], rs["mscale_all_dim"])
    f = np.arange(S, dtype=np.float64)[:, None] * inv[None]
    cos = jnp.asarray(m * np.concatenate([np.cos(f), np.cos(f)], -1)
                      [:, None], jnp.float32)
    sin = jnp.asarray(m * np.concatenate([np.sin(f), np.sin(f)], -1)
                      [:, None], jnp.float32)
    x1, x2 = x[..., :D // 2], x[..., D // 2:]
    return x * cos + jnp.concatenate([-x2, x1], -1) * sin


def _turn_first(x, cfg: Dict):
    """Step 3's rotation: the first ``qk_rope_head_dim`` numbers of
    every head of x [S, heads, D] by R, the rest as they are."""
    dr = cfg["qk_rope_head_dim"]
    return jnp.concatenate([_rope(x[..., :dr], cfg), x[..., dr:]], -1)


def softmax_scale(cfg: Dict) -> float:
    rs = cfg["rope_scaling"]
    m = _yarn_mscale(rs["factor"], rs["mscale_all_dim"])
    return (cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]) ** -0.5 \
        * m * m


def selected(scores, seen, topk: int):
    """Step 4 for a block of rows: scores [rows, S], seen [rows, S]
    (s <= t). The ``topk`` largest seen scores of each row, ties to the
    lower position (``lax.top_k`` lists equal values by rising index);
    every seen row where there are no more than ``topk``."""
    S = scores.shape[-1]
    if topk >= S:
        return seen
    _, idx = jax.lax.top_k(jnp.where(seen, scores, -jnp.inf), topk)
    rows = jnp.arange(scores.shape[0])[:, None]
    return jnp.zeros(scores.shape, bool).at[rows, idx].set(True) & seen


def attention(p, x, cfg: Dict, precision: str):
    """Steps 1-5 for one sequence x: [S, hidden], unabsorbed. Returns
    (x', the kept sets [S, S] bool: row t, cache row s)."""
    S = x.shape[0]
    H = cfg["num_attention_heads"]
    dc, dn, dr, dv = (cfg["kv_lora_rank"], cfg["qk_nope_head_dim"],
                      cfg["qk_rope_head_dim"], cfg["v_head_dim"])
    Hi, di, topk = (cfg["index_n_heads"], cfg["index_head_dim"],
                    cfg["index_topk"])
    eps = cfg["rms_norm_eps"]
    u = _rms(x, p["in_norm"], eps)
    cq = _rms(_mm(u, p["q_a"], precision), p["q_a_norm"], eps)
    q = _mm(cq, p["q_b"], precision).reshape(S, H, dn + dr)
    q_n, q_r = q[..., :dn], _rope(q[..., dn:], cfg)
    ckr = _mm(u, p["kva"], precision)
    c = _rms(ckr[:, :dc], p["kv_norm"], eps)
    k_r = _rope(ckr[:, None, dc:], cfg)[:, 0]                 # [S, dr]
    kv = _mm(c, p["kvb"], precision).reshape(S, H, dn + dv)
    k_n, v = kv[..., :dn], kv[..., dn:]
    a = _turn_first(_mm(cq, p["iq"], precision).reshape(S, Hi, di), cfg)
    b = _turn_first(_layer_norm(_mm(u, p["ik"], precision), p["ik_norm"],
                                p["ik_norm_bias"], eps)[:, None], cfg)[:, 0]
    w = _mm(u, p["iw"], precision) * (Hi ** -0.5 * di ** -0.5)
    sigma = softmax_scale(cfg)
    j = jnp.arange(S)[None, :]

    def rows_block(r):                       # ROWS rows, every key
        cut = lambda z: jax.lax.dynamic_slice_in_dim(z, r, ROWS, 0)
        ii = (r + jnp.arange(ROWS))[:, None]
        seen = j <= ii
        if precision == "dense":             # the control: no selection
            keep = seen
        else:
            idx = jnp.einsum("qjd,kd->jqk", cut(a), b)
            idx = jnp.sum(jnp.maximum(idx, 0.0) * cut(w).T[:, :, None],
                          axis=0)
            keep = selected(idx, seen, topk)
        s = (jnp.einsum("qhd,khd->hqk", cut(q_n), k_n)
             + jnp.einsum("qhd,kd->hqk", cut(q_r), k_r)) * sigma
        s = jnp.where(keep, s, -jnp.inf)
        e = jnp.where(keep, jnp.exp(s - jnp.max(s, -1, keepdims=True)), 0.0)
        return jnp.einsum("hqk,khd->qhd", e / e.sum(-1, keepdims=True),
                          v), keep

    # block after block (S is a multiple of ROWS: ``PAD``)
    o, kept = jax.lax.map(rows_block, jnp.arange(0, S, ROWS))
    return (x + _mm(o.reshape(S, H * dv), p["o"], precision),
            kept.reshape(S, S))


def swiglu(x, gate, up, down, precision: str):
    return _mm(jax.nn.silu(_mm(x, gate, precision))
               * _mm(x, up, precision), down, precision)


def route(h, router, bias, cfg: Dict):
    """Step 7's choice: chosen experts [T, k] (numbered over the
    router's width), their weights [T, k], normalised over all k chosen,
    and the kept groups [T, topk_group]. Float32."""
    E, n = cfg["router_experts"], cfg["n_group"]
    s = jax.nn.sigmoid(h @ router)
    sb = s + bias
    T = sb.shape[0]
    by_group = sb.reshape(T, n, E // n)
    score = jax.lax.top_k(by_group, 2)[0].sum(-1)
    _, groups = jax.lax.top_k(score, cfg["topk_group"])
    kept = jnp.zeros((T, n), bool).at[jnp.arange(T)[:, None],
                                      groups].set(True)
    sb = jnp.where(jnp.repeat(kept, E // n, axis=1), sb, 0.0)
    _, idx = jax.lax.top_k(sb, cfg["num_experts_per_tok"])
    sel = jnp.take_along_axis(s, idx, axis=-1)
    return (idx, cfg["routed_scaling_factor"] * sel
            / sel.sum(-1, keepdims=True), groups)


def expert_part(h, idx, g, j: int, gate, up, down, precision: str):
    """g_j * E_j(h) on the tokens that chose expert j, 0 elsewhere."""
    w = jnp.sum(jnp.where(idx == j, g, 0.0), axis=-1)          # [T]
    return w[:, None] * swiglu(h, gate, up, down, precision)


class ServeReference:
    """Logits of a full forward over ``prompt + served tokens``, the
    experts and the groups each position chose in each expert layer, and
    the cache rows each of the rows that produced a served token kept in
    each layer."""

    def __init__(self, cfg: Dict, seed: int, precision: str = "float32"):
        self.cfg = cfg
        self.key = jax.random.wrap_key_data(jnp.asarray(key_data(seed)))
        self.table = leaf_table(cfg)
        self.store = jnp.dtype(cfg["torch_dtype"])
        self.precision = precision
        self._jit: Dict = {}
        self.choices: List[Optional[np.ndarray]] = []
        self.groups: List[Optional[np.ndarray]] = []
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
        """Step 6 or 7 for every sequence of ``xs``, layer ``i``: rows
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
            self.groups.append(None)
        else:
            p = self._params([f"l.{i}.{k}" for k in MOE_LEAVES])
            f_route = self._jit.setdefault(
                "route", jax.jit(lambda h, r, b: route(h, r, b, cfg)))
            idx, g, groups = f_route(h, p["router"], p["router_bias"])
            self.choices.append(np.asarray(idx))
            self.groups.append(np.asarray(groups))
            y = f_ffn(h, {"gate": p["sh_gate"], "up": p["sh_up"],
                          "down": p["sh_down"]})
            del p
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

    def forward(self, seqs: List[np.ndarray],
                probes: Optional[List[Tuple[int, int]]] = None
                ) -> List[jax.Array]:
        """The final hidden rows [padded length, hidden] of each whole
        sequence. Afterwards ``self.choices[i]`` / ``self.groups[i]``
        hold layer i's chosen experts and kept groups for the rows of
        all (padded) sequences side by side (None for a dense layer),
        ``self.row_spans`` each sequence's (first row, length), and,
        with ``probes`` = a (first row, rows) a sequence, ``self.kept[i]
        [n]`` the kept sets of those rows of sequence n in layer i."""
        cfg, prec = self.cfg, self.precision
        self.choices, self.groups, self.row_spans, self.kept = \
            [], [], [], []
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
            del p
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

    def _real_rows(self, per_layer) -> List[np.ndarray]:
        keep = np.concatenate([np.arange(a, a + n)
                               for a, n in self.row_spans])
        return [c[keep] for c in per_layer if c is not None]

    def chosen(self) -> List[np.ndarray]:
        """Per expert layer, the chosen experts [real rows, k] of the
        last ``logits`` call, the requests' real positions in order."""
        return self._real_rows(self.choices)

    def kept_groups(self) -> List[np.ndarray]:
        """Per expert layer, the kept groups [real rows, topk_group]."""
        return self._real_rows(self.groups)
