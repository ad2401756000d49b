"""Plain reference for ``nemotron-3-super-120b-a12b``
(huggingface.co/nvidia/NVIDIA-Nemotron-3-Super-120B-A12B-BF16
``config.json``, ``model_type`` ``nemotron_h``, 120B-A12B: the block of
the Nemotron-H report, arXiv:2504.03624, with the LatentMoE feed-forward
of the Nemotron 3 report): 88 layers of ONE mixer each, by
``hybrid_override_pattern`` (``M`` a Mamba-2 state-space mixer, ``E``
latent relu² experts, ``*`` grouped-query attention; 40 : 40 : 8), an
untied head.

Straightforward ``jax.numpy`` in float32 under
``jax.default_matmul_precision("highest")``: a full causal forward over
the whole sequence, layer by layer, expert by expert, the recurrence as
a plain ``lax.scan`` over POSITIONS (no chunks), the attention scores of
a block of rows against every key with the mask written out, no
kernels, no cache, no batching. It imports nothing of the program and
takes nothing the program made: weights come from ``leaf``, from the
seed, in the type the configuration stores them in.

The equations (config keys in backticks), eps = ``norm_eps``, no bias
but the convolution's, x ``[S, 4096]``. Run layer i is published layer
``layers_run[i]``, its kind the i-th character of
``hybrid_override_pattern``: ``x <- x + Mixer_i(RMSNorm(x; w_i))``;
after the last, ``logits = RMSNorm(x; w_norm) W_head``.

``M`` (``mamba_num_heads`` 128 x ``mamba_head_dim`` 64 = 8192 inner,
``n_groups`` 8, ``ssm_state_size`` 128, ``conv_kernel`` 4)::

    [z | c | dt] = u W_in               # 8192 | 8192 + 2*8*128 = 10240 | 128
    c_t = silu(b_c + sum_{j=0..3} w_c[:, j] c_{t-3+j})    # zeros before 0
    [xs | B | C] = c_t                  # xs [128, 64]; B, C [8, 128]; head h reads group h // 16
    D_t = softplus(dt_t + dt_bias);  a_t = exp(D_t A),  A = -exp(A_log)
    H_t = a_t H_{t-1} + D_t (xs_t (x) B_t)                # [128, 64, 128] float32, H_{-1} = 0
    y_t = H_t C_t + Dskip * xs_t
    g = y * silu(z);  n = g / rms(g over each of the 8 groups of 1024) * w_n
    Mixer = n W_out

``*``: q = u W_q [32 x 128]; k, v = u W_k, u W_v [2 x 128]; causal
softmax(q.k * 128^-0.5) in float32, query head h reads KV head h // 16;
Mixer = (P v) W_o. No rotation.

``E``: s = sigmoid(float32(u) W_r) over ``router_experts`` 512; chosen =
the ``num_experts_per_tok`` 22 largest of s + b (``n_group`` 1: no
groups); w_e = ``routed_scaling_factor`` * s_e / sum over the chosen of
s (``norm_topk_prob``); l = u W_dn [4096 -> ``moe_latent_size`` 1024];
r = sum over the chosen AND HELD e of w_e relu(l W1_e)^2 W2_e
[1024 -> ``moe_intermediate_size`` 2688 -> 1024] (``mlp_hidden_act``
``relu2``: no gate matrix); Mixer = r W_up [1024 -> 4096] + relu(u S1)^2
S2 (the shared expert, ``moe_shared_expert_intermediate_size`` 5376,
every token).

THE SHARE. The configuration is one chip's share of a 4-chip
expert-parallel layer: experts ``expert_offset .. expert_offset +
n_routed_experts - 1`` of the router's ``router_experts`` are held (the
file's ``n_routed_experts`` counts the HELD ones). w is normalised over
all chosen experts; only the chosen AND held are summed; ``W_dn`` and
``W_up`` are linear, so the holders' parts add up through them; the
shared expert runs whole on every holder; what the absent experts would
add is left out, here as in the program. The vocabulary is a slice:
``vocab_size`` rows.

DEPARTURES AND ASSUMPTIONS (the config file repeats these, with where
each comes from): the order inside the mixers, the gated GROUP norm
(after the gate, a group of 1024 at a time), the router reading the
hidden state and not the latent, ``W_up`` after the routed sum alone
and the missing rotation are ``modeling_nemotron_h.py``'s and the two
reports' as the issue's author knows them; ``config.json`` has no key
for them. The multi-token-prediction module
(``num_nextn_predict_layers`` 1) is not built. Weights N(0, ``initializer_range`` 0.02) by
``references/gpt.py::leaf``'s recipe, output projections (W_out, W_o,
W2_e, S2, W_up) N(0, 0.02 / sqrt(2 x 88)), norms 1; the convolution's
weight and bias N(0, 0.2887), the spread of the source's default U(-1/2,
1/2) for a depthwise kernel of 4; ``A_log = log U(1, 16)``; ``dt_bias``
the inverse softplus of a log-uniform draw in [``time_step_min``,
``time_step_max``] floored at ``time_step_floor``; ``Dskip = 1``; the
selection bias N(0, 0.02). ``A_log``, ``dt_bias`` and ``Dskip`` are
STORED in the configuration's type like every weight (a checkpoint keeps
them float32) and read as float32.

The controls: ``precision="fp8"`` rounds the operands of every linear
layer to fp8 (the router's product, the convolution and the recurrence
stay float32); ``precision="state_bf16"`` rounds H to bfloat16 after
every position and nothing else.
"""
from __future__ import annotations

import math
from typing import Dict, List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from . import gpt
from .gpt import fp8, key_data, name_id, stored
from .mistral import served_gap  # noqa: F401  (the family's import)

SSM_LEAVES = ("norm", "in_proj", "conv_w", "conv_b", "A_log", "dt_bias",
              "D", "gn", "out_proj")
ATTN_LEAVES = ("norm", "q", "k", "v", "o")
MOE_LEAVES = ("norm", "router", "router_bias", "lat_dn", "lat_up", "sh_up",
              "sh_down")
EXPERT_LEAVES = ("up", "down")
PAD = 256     # sequences are padded to a multiple, to bound the shapes
ROWS = 256    # attention: query rows a block, against every key
BIAS_STD = 0.02
CONV_STD = 0.2887         # of U(-1/2, 1/2): Conv1d's default at fan-in 4
PUBLISHED_LAYERS = 88     # output projections: N(0, std / sqrt(2 * 88))


def held_experts(cfg: Dict) -> range:
    return range(cfg["expert_offset"],
                 cfg["expert_offset"] + cfg["n_routed_experts"])


def kind(cfg: Dict, i: int) -> str:
    """'M' | 'E' | '*' of run layer i."""
    return cfg["hybrid_override_pattern"][i]


def inner(cfg: Dict) -> int:
    return cfg["mamba_num_heads"] * cfg["mamba_head_dim"]


def conv_dim(cfg: Dict) -> int:
    return inner(cfg) + 2 * cfg["n_groups"] * cfg["ssm_state_size"]


def state_bytes_per_row(cfg: Dict) -> int:
    """What a row keeps over the state-space layers run: H in float32
    and the convolution's tail in the configuration's type."""
    h = cfg["mamba_num_heads"] * cfg["mamba_head_dim"] \
        * cfg["ssm_state_size"] * 4
    tail = (cfg["conv_kernel"] - 1) * conv_dim(cfg) \
        * jnp.dtype(cfg["torch_dtype"]).itemsize
    return (h + tail) * cfg["hybrid_override_pattern"].count("M")


def leaf_table(cfg: Dict) -> Dict[str, Tuple[Tuple[int, ...], str, object]]:
    """name -> (shape, kind, std). Beside ``references/gpt.py``'s kinds
    (``normal`` | ``ones`` | ``zeros``): ``a_log`` (``log U(1, 16)``) and
    ``dt_bias`` (its third entry the (min, max, floor) of the step)."""
    h, V = cfg["hidden_size"], cfg["vocab_size"]
    H, KV, d = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                cfg["head_dim"])
    nh, inn, conv = cfg["mamba_num_heads"], inner(cfg), conv_dim(cfg)
    dl, fe = cfg["moe_latent_size"], cfg["moe_intermediate_size"]
    fs, E = cfg["moe_shared_expert_intermediate_size"], cfg["router_experts"]
    std = cfg["initializer_range"]
    out_std = std / math.sqrt(2 * PUBLISHED_LAYERS)
    steps = (cfg["time_step_min"], cfg["time_step_max"],
             cfg["time_step_floor"])
    t = {"embed": ((V, h), "normal", std), "norm": ((h,), "ones", 0.0),
         "lm_head": ((h, V), "normal", std)}
    for i in range(cfg["num_hidden_layers"]):
        t[f"l.{i}.norm"] = ((h,), "ones", 0.0)
        k = kind(cfg, i)
        if k == "M":
            t[f"l.{i}.in_proj"] = ((h, inn + conv + nh), "normal", std)
            t[f"l.{i}.conv_w"] = ((conv, cfg["conv_kernel"]), "normal",
                                  CONV_STD)
            t[f"l.{i}.conv_b"] = ((conv,), "normal", CONV_STD)
            t[f"l.{i}.A_log"] = ((nh,), "a_log", 0.0)
            t[f"l.{i}.dt_bias"] = ((nh,), "dt_bias", steps)
            t[f"l.{i}.D"] = ((nh,), "ones", 0.0)
            t[f"l.{i}.gn"] = ((inn,), "ones", 0.0)
            t[f"l.{i}.out_proj"] = ((inn, h), "normal", out_std)
        elif k == "*":
            t[f"l.{i}.q"] = ((h, H * d), "normal", std)
            t[f"l.{i}.k"] = ((h, KV * d), "normal", std)
            t[f"l.{i}.v"] = ((h, KV * d), "normal", std)
            t[f"l.{i}.o"] = ((H * d, h), "normal", out_std)
        else:
            t[f"l.{i}.router"] = ((h, E), "normal", std)
            t[f"l.{i}.router_bias"] = ((E,), "normal", BIAS_STD)
            t[f"l.{i}.lat_dn"] = ((h, dl), "normal", std)
            t[f"l.{i}.lat_up"] = ((dl, h), "normal", out_std)
            t[f"l.{i}.sh_up"] = ((h, fs), "normal", std)
            t[f"l.{i}.sh_down"] = ((fs, h), "normal", out_std)
            for j in held_experts(cfg):
                t[f"l.{i}.e.{j}.up"] = ((dl, fe), "normal", std)
                t[f"l.{i}.e.{j}.down"] = ((fe, dl), "normal", out_std)
    return t


def leaf(key, nid, spec, dtype) -> jax.Array:
    """``references/gpt.py::leaf`` and the two kinds of a state-space
    layer's own, in the type they are stored in."""
    shape, what, arg = spec
    if what not in ("a_log", "dt_bias"):
        return gpt.leaf(key, nid, spec, dtype)
    k = jax.random.fold_in(key, nid)
    if what == "a_log":
        v = jnp.log(jax.random.uniform(k, shape, jnp.float32, 1.0, 16.0))
    else:
        lo, hi, floor = arg
        step = jnp.maximum(jnp.exp(jax.random.uniform(
            k, shape, jnp.float32, math.log(lo), math.log(hi))), floor)
        v = step + jnp.log(-jnp.expm1(-step))      # softplus's inverse
    return stored(v, dtype).astype(dtype)


def _mm(a, w, precision: str):
    if precision == "fp8":          # the control: see references/gpt.py
        a, w = fp8(a), fp8(w)
    return a @ w


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def ssm_mixer(p, x, cfg: Dict, precision: str):
    """The ``M`` mixer of one sequence x: [S, hidden] (the residual
    included)."""
    S = x.shape[0]
    nh, P, G, N, K = (cfg["mamba_num_heads"], cfg["mamba_head_dim"],
                      cfg["n_groups"], cfg["ssm_state_size"],
                      cfg["conv_kernel"])
    inn, conv, eps = inner(cfg), conv_dim(cfg), cfg["norm_eps"]
    proj = _mm(_rms(x, p["norm"], eps), p["in_proj"], precision)
    z, c, dt = proj[:, :inn], proj[:, inn:inn + conv], proj[:, inn + conv:]
    cp = jnp.pad(c, ((K - 1, 0), (0, 0)))            # zeros before 0
    c = jax.nn.silu(p["conv_b"] + sum(cp[j:j + S] * p["conv_w"][:, j]
                                      for j in range(K)))
    xs = c[:, :inn].reshape(S, nh, P)
    Bm = jnp.repeat(c[:, inn:inn + G * N].reshape(S, G, N), nh // G, 1)
    Cm = jnp.repeat(c[:, inn + G * N:].reshape(S, G, N), nh // G, 1)
    step = jax.nn.softplus(dt + p["dt_bias"])                    # [S, nh]
    a = jnp.exp(step * -jnp.exp(p["A_log"]))

    def one(H, t):                       # a position at a time
        xt, Bt, Ct, at, st = t
        H = at[:, None, None] * H + (st[:, None] * xt)[:, :, None] \
            * Bt[:, None, :]
        if precision == "state_bf16":
            H = stored(H, jnp.bfloat16)
        return H, jnp.einsum("hpn,hn->hp", H, Ct)

    _, y = jax.lax.scan(one, jnp.zeros((nh, P, N), jnp.float32),
                        (xs, Bm, Cm, a, step))
    y = y + p["D"][:, None] * xs
    g = (y.reshape(S, inn) * jax.nn.silu(z)).reshape(S, G, inn // G)
    g = g * jax.lax.rsqrt(jnp.mean(g * g, -1, keepdims=True) + eps)
    return x + _mm(g.reshape(S, inn) * p["gn"], p["out_proj"], precision)


def attention(p, x, cfg: Dict, precision: str):
    """The ``*`` mixer of one sequence x: [S, hidden] (the residual
    included)."""
    S = x.shape[0]
    H, KV, d = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                cfg["head_dim"])
    u = _rms(x, p["norm"], cfg["norm_eps"])
    q = _mm(u, p["q"], precision).reshape(S, H, d)
    k = jnp.repeat(_mm(u, p["k"], precision).reshape(S, KV, d), H // KV, 1)
    v = jnp.repeat(_mm(u, p["v"], precision).reshape(S, KV, d), H // KV, 1)
    outs = []
    j = jnp.arange(S)[None, :]
    for a in range(0, S, ROWS):              # rows a block, every key
        qi = q[a:a + ROWS]
        ii = jnp.arange(a, a + qi.shape[0])[:, None]
        s = jnp.einsum("qhd,khd->hqk", qi, k) / math.sqrt(d)
        keep = j <= ii
        s = jnp.where(keep, s, -jnp.inf)
        e = jnp.where(keep, jnp.exp(s - jnp.max(s, -1, keepdims=True)), 0.0)
        outs.append(jnp.einsum("hqk,khd->qhd",
                               e / e.sum(-1, keepdims=True), v))
    return x + _mm(jnp.concatenate(outs, 0).reshape(S, H * d), p["o"],
                   precision)


def relu2(x, up, down, precision: str):
    return _mm(jnp.square(jax.nn.relu(_mm(x, up, precision))), down,
               precision)


def route(u, router, bias, cfg: Dict):
    """Chosen experts [T, k] (numbered over the router's width) and
    their weights [T, k], normalised over all k chosen. Float32."""
    s = jax.nn.sigmoid(u @ router)
    _, idx = jax.lax.top_k(s + bias, cfg["num_experts_per_tok"])
    sel = jnp.take_along_axis(s, idx, axis=-1)
    w = cfg["routed_scaling_factor"] * sel
    if cfg["norm_topk_prob"]:
        w = w / sel.sum(-1, keepdims=True)
    return idx, w


def expert_part(low, idx, w, j: int, up, down, precision: str):
    """w_j * E_j(l) on the tokens that chose expert j, 0 elsewhere."""
    wj = jnp.sum(jnp.where(idx == j, w, 0.0), axis=-1)          # [T]
    return wj[:, None] * relu2(low, up, down, precision)


class ServeReference:
    """Logits of a full forward over ``prompt + served tokens``, and the
    experts each position chose in each expert layer. ``precision``:
    ``"float32"`` | ``"fp8"`` | ``"state_bf16"`` (module docstring)."""

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

    def _experts(self, i: int, xs: List[jax.Array]) -> List[jax.Array]:
        """The ``E`` mixer for every sequence of ``xs``, layer ``i``:
        rows of all sequences side by side (it acts on single rows)."""
        cfg, prec = self.cfg, self.precision
        eps = cfg["norm_eps"]
        rows = jnp.concatenate(xs, axis=0)
        p = self._params([f"l.{i}.{k}" for k in MOE_LEAVES])
        f_norm = self._jit.setdefault(
            "norm", jax.jit(lambda x, w: _rms(x, w, eps)))
        f_route = self._jit.setdefault(
            "route", jax.jit(lambda u, r, b: route(u, r, b, cfg)))
        f_mm = self._jit.setdefault(
            "mm", jax.jit(lambda a, w: _mm(a, w, prec)))
        f_ffn = self._jit.setdefault("ffn", jax.jit(
            lambda u, up, down: relu2(u, up, down, prec)))
        f_exp = self._jit.setdefault("expert", jax.jit(
            lambda low, idx, w, j, pe: expert_part(
                low, idx, w, j, pe["up"], pe["down"], prec)))
        u = f_norm(rows, p["norm"])
        idx, w = f_route(u, p["router"], p["router_bias"])
        self.choices.append(np.asarray(idx))
        low = f_mm(u, p["lat_dn"])
        r = jnp.zeros_like(low)
        for j in held_experts(cfg):            # expert by expert
            pe = self._params([f"l.{i}.e.{j}.{k}" for k in EXPERT_LEAVES])
            r = r + f_exp(low, idx, w, jnp.int32(j), pe)
        out = rows + f_mm(r, p["lat_up"]) \
            + f_ffn(u, p["sh_up"], p["sh_down"])
        cuts = np.cumsum([x.shape[0] for x in xs])[:-1]
        return list(jnp.split(out, cuts, axis=0))

    def forward(self, seqs: List[np.ndarray]) -> List[jax.Array]:
        """The final hidden rows [padded length, hidden] of each whole
        sequence. Afterwards ``self.choices[i]`` holds layer i's chosen
        experts for the rows of all (padded) sequences side by side,
        None for a layer of another kind; ``self.row_spans`` each
        sequence's (first row, length)."""
        cfg, prec = self.cfg, self.precision
        self.choices, self.row_spans = [], []
        emb = self._params(["embed"])["embed"]
        xs, first = [], 0
        # one padded length for all: one program a kind of mixer
        longest = max(len(seq) for seq in seqs)
        longest += -longest % PAD
        for seq in seqs:
            pad = longest - len(seq)
            xs.append(emb[jnp.asarray(np.pad(
                np.asarray(seq, np.int32), (0, pad)))])
            self.row_spans.append((first, len(seq)))
            first += len(seq) + pad
        del emb
        f_ssm = self._jit.setdefault("ssm", jax.jit(
            lambda p, x: ssm_mixer(p, x, cfg, prec)))
        f_attn = self._jit.setdefault("attn", jax.jit(
            lambda p, x: attention(p, x, cfg, prec)))
        for i in range(cfg["num_hidden_layers"]):
            k = kind(cfg, i)
            if k == "E":
                xs = self._experts(i, xs)
                continue
            self.choices.append(None)
            p = self._params([f"l.{i}.{n}" for n in
                              (SSM_LEAVES if k == "M" else ATTN_LEAVES)])
            xs = [(f_ssm if k == "M" else f_attn)(p, x) for x in xs]
        return xs

    def logits(self, requests: List[Tuple[np.ndarray, np.ndarray]]
               ) -> List[np.ndarray]:
        """For each (prompt, served) pair: float32 logits [n, vocab] at
        the n positions that produced the served tokens (the last prompt
        position, then each served token but the last)."""
        cfg, prec = self.cfg, self.precision

        def head(p, x):
            return _mm(_rms(x, p["norm"], cfg["norm_eps"]), p["lm_head"],
                       prec)

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
