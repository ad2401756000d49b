"""Plain reference for Mistral-7B-v0.3
(huggingface.co/mistralai/Mistral-7B-v0.3 ``config.json`` and the
``MistralForCausalLM`` equations of the transformers library): RMSNorm,
rotary positions (rotate-half convention, theta from the config),
grouped-query attention without a sliding window, SwiGLU feed-forward,
untied output head.

Straightforward ``jax.numpy`` in float32 under
``jax.default_matmul_precision("highest")``: a full causal forward over
the whole sequence, no kernels, no cache, no batching. It imports nothing
of the program and takes nothing the program made: weights come from
``leaf``, from the seed, in the type the configuration stores them in,
and the benchmark loads the program with the same values.

Memory: one layer's weights at a time (regenerated from the seed), the
activations of the sampled requests kept between layers.
"""
from __future__ import annotations

import math
from typing import Dict, List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .gpt import fp8, key_data, leaf, name_id  # the seeded-leaf recipe

LAYER_LEAVES = ("in_norm", "q", "k", "v", "o", "post_norm", "gate", "up",
                "down")
PAD = 256     # sequences are padded to a multiple, to bound the shapes


def leaf_table(cfg: Dict) -> Dict[str, Tuple[Tuple[int, ...], str, float]]:
    h, ff = cfg["hidden_size"], cfg["intermediate_size"]
    D = cfg["head_dim"]
    nq, nkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    L = cfg["num_hidden_layers"]
    std = cfg["initializer_range"]
    out_std = std / math.sqrt(2 * L)
    t = {"embed": ((cfg["vocab_size"], h), "normal", std),
         "norm": ((h,), "ones", 0.0),
         "lm_head": ((h, cfg["vocab_size"]), "normal", std)}
    per = {"in_norm": ((h,), "ones", 0.0),
           "q": ((h, nq * D), "normal", std),
           "k": ((h, nkv * D), "normal", std),
           "v": ((h, nkv * D), "normal", std),
           "o": ((nq * D, h), "normal", std),
           "post_norm": ((h,), "ones", 0.0),
           "gate": ((h, ff), "normal", std),
           "up": ((h, ff), "normal", std),
           "down": ((ff, h), "normal", out_std)}
    for i in range(L):
        for k, v in per.items():
            t[f"l.{i}.{k}"] = v
    return t


def _mm(a, w, precision: str):
    if precision == "fp8":          # the control: see references/gpt.py
        a, w = fp8(a), fp8(w)
    return a @ w


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _rope(x, theta: float):
    """x: [S, H, D]; position = row index."""
    S, _, D = x.shape
    inv = 1.0 / theta ** (jnp.arange(0, D, 2, dtype=jnp.float32) / D)
    f = jnp.arange(S, dtype=jnp.float32)[:, None] * inv[None]
    cos = jnp.concatenate([jnp.cos(f), jnp.cos(f)], -1)[:, None]
    sin = jnp.concatenate([jnp.sin(f), jnp.sin(f)], -1)[:, None]
    x1, x2 = x[..., :D // 2], x[..., D // 2:]
    return x * cos + jnp.concatenate([-x2, x1], -1) * sin


def block(p, x, cfg: Dict, precision: str):
    """One decoder layer over one sequence x: [S, hidden]."""
    S = x.shape[0]
    D = cfg["head_dim"]
    nq, nkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    eps, theta = cfg["rms_norm_eps"], cfg["rope_theta"]
    h = _rms(x, p["in_norm"], eps)
    q = _rope(_mm(h, p["q"], precision).reshape(S, nq, D), theta)
    k = _rope(_mm(h, p["k"], precision).reshape(S, nkv, D), theta)
    v = _mm(h, p["v"], precision).reshape(S, nkv, D)
    k = jnp.repeat(k, nq // nkv, axis=1)
    v = jnp.repeat(v, nq // nkv, axis=1)
    s = jnp.einsum("qhd,khd->hqk", q, k) / math.sqrt(D)
    s = jnp.where(jnp.tril(jnp.ones((S, S), bool)), s, -jnp.inf)
    a = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("hqk,khd->qhd", a, v).reshape(S, nq * D)
    x = x + _mm(o, p["o"], precision)
    h = _rms(x, p["post_norm"], eps)
    g = _mm(h, p["gate"], precision)
    return x + _mm(jax.nn.silu(g) * _mm(h, p["up"], precision), p["down"],
                   precision)


class ServeReference:
    """Logits of a full forward over ``prompt + served tokens``."""

    def __init__(self, cfg: Dict, seed: int, precision: str = "float32"):
        self.cfg = cfg
        self.key = jax.random.wrap_key_data(jnp.asarray(key_data(seed)))
        self.table = leaf_table(cfg)
        self.store = jnp.dtype(cfg["torch_dtype"])
        self.precision = precision
        self._jit: Dict = {}

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

    def logits(self, requests: List[Tuple[np.ndarray, np.ndarray]]
               ) -> List[np.ndarray]:
        """For each (prompt, served) pair: float32 logits [n, vocab] at
        the n positions that produced the served tokens (the last prompt
        position, then each served token but the last)."""
        cfg, prec = self.cfg, self.precision
        f_block = self._jit.setdefault(
            "block", jax.jit(lambda p, x: block(p, x, cfg, prec)))

        def head(p, x):
            return _mm(_rms(x, p["norm"], cfg["rms_norm_eps"]),
                       p["lm_head"], prec)

        f_head = self._jit.setdefault("head", jax.jit(head))
        with jax.default_matmul_precision("highest"):
            emb = self._params(["embed"])["embed"]
            xs = []
            for prompt, served in requests:
                seq = np.concatenate([prompt, served[:-1]]).astype(np.int32)
                pad = -len(seq) % PAD
                xs.append(emb[jnp.asarray(np.pad(seq, (0, pad)))])
            del emb
            for i in range(cfg["num_hidden_layers"]):
                p = self._params([f"l.{i}.{k}" for k in LAYER_LEAVES])
                xs = [f_block(p, x) for x in xs]
            p = self._params(["norm", "lm_head"])
            out = []
            for (prompt, served), x in zip(requests, xs):
                lo = len(prompt) - 1
                rows = x[lo:lo + len(served)]
                rpad = -rows.shape[0] % 64
                lg = f_head(p, jnp.pad(rows, ((0, rpad), (0, 0))))
                out.append(np.asarray(lg[:len(served)], np.float32))
        return out


def served_gap(ref_logits: np.ndarray, tokens: np.ndarray) -> np.ndarray:
    """How far each token's logit lies below the reference's best, per
    position (0 where the token IS the reference's best)."""
    best = ref_logits.max(axis=-1)
    return best - ref_logits[np.arange(len(tokens)), np.asarray(tokens)]
