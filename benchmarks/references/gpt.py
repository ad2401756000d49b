"""Plain reference for the GPT-3 configurations (Brown et al. 2020,
arXiv:2005.14165, section 2.1 and table 2.1): the decoder block of GPT-2
with pre-layer-norm, learned positions, tanh-GELU feed-forward of 4 x
d_model, tied output embedding, next-token cross entropy; AdamW.

Straightforward ``jax.numpy`` in float32 under
``jax.default_matmul_precision("highest")``: no kernels, no cache, no
fused anything. It imports nothing of the program and takes nothing the
program made: weights come from ``leaf`` below, from the seed, and the
benchmark loads the program with the same values.

Departures from the paper, each because the configuration file says so:
dense attention in every layer (the paper alternates dense and locally
banded sparse layers; the program has no banded kernel), the vocabulary
padded to a multiple of 128, and the STORAGE types the configuration
states: parameters and Adam moments are rounded to ``dtype`` /
``state_dtype`` where they are stored between steps, while every
operation on them runs in float32.

Memory: one layer at a time. The forward keeps each layer's input,
the backward regenerates a layer's weights from the seed, takes that
layer's vjp, reduces the gradient to what is compared, and drops it.
The only state carried from step 1 to step 2 is the first gradient.
Rows are split over the devices given (``jax.sharding`` on plain
``jnp`` code); weights are replicated.
"""
from __future__ import annotations

import math
import zlib
from typing import Dict, List, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

SAMPLE_ROWS = 256      # rows of a sampled leaf's gradient kept whole
LAYER_LEAVES = ("ln1.w", "ln1.b", "qkv.w", "qkv.b", "proj.w", "proj.b",
                "ln2.w", "ln2.b", "fc1.w", "fc1.b", "fc2.w", "fc2.b")


# -- weights from the seed ----------------------------------------------------
def key_data(seed: int) -> np.ndarray:
    """Two uint32 words from any whole-number seed (they may be larger
    than 32 signed bits hold)."""
    return np.random.SeedSequence(int(seed)).generate_state(2).astype(
        np.uint32)


def leaf_table(cfg: Dict) -> Dict[str, Tuple[Tuple[int, ...], str, float]]:
    """name -> (shape, kind, std) of every parameter. kind is "normal",
    "ones" or "zeros". Initialisation as GPT-2/GPT-3: N(0, 0.02), output
    projections scaled by 1/sqrt(2 * n_layers), biases 0, norms 1."""
    d, ff, L = cfg["d_model"], cfg["d_ff"], cfg["n_layers"]
    std = cfg["initializer_range"]
    out_std = std / math.sqrt(2 * L)
    t = {"wte": ((cfg["vocab_size"], d), "normal", std),
         "wpe": ((cfg["n_ctx"], d), "normal", std),
         "lnf.w": ((d,), "ones", 0.0), "lnf.b": ((d,), "zeros", 0.0)}
    per = {"ln1.w": ((d,), "ones", 0.0), "ln1.b": ((d,), "zeros", 0.0),
           "qkv.w": ((d, 3 * d), "normal", std),
           "qkv.b": ((3 * d,), "zeros", 0.0),
           "proj.w": ((d, d), "normal", out_std),
           "proj.b": ((d,), "zeros", 0.0),
           "ln2.w": ((d,), "ones", 0.0), "ln2.b": ((d,), "zeros", 0.0),
           "fc1.w": ((d, ff), "normal", std),
           "fc1.b": ((ff,), "zeros", 0.0),
           "fc2.w": ((ff, d), "normal", out_std),
           "fc2.b": ((d,), "zeros", 0.0)}
    for i in range(L):
        for k, v in per.items():
            t[f"h.{i}.{k}"] = v
    return t


def sampled_leaves(cfg: Dict) -> List[str]:
    """Leaves whose first gradient is compared element by element (the
    first ``SAMPLE_ROWS`` rows): the widest matmul weights of the first,
    the middle and the last layer."""
    L = cfg["n_layers"]
    return [f"h.{i}.{k}" for i in sorted({0, L // 2, L - 1})
            for k in ("qkv.w", "fc2.w")]


def name_id(name: str) -> int:
    return zlib.crc32(name.encode()) & 0x7FFFFFFF


def stored(x, dtype):
    """Round to the type a value is stored in and come back to float32.
    ``reduce_precision`` and not a pair of casts: XLA may drop a cast
    down and up again (``xla_allow_excess_precision``), and then nothing
    was rounded."""
    dtype = jnp.dtype(dtype)
    if dtype == jnp.float32:
        return x.astype(jnp.float32)
    info = jnp.finfo(dtype)
    return jax.lax.reduce_precision(x.astype(jnp.float32), info.nexp,
                                    info.nmant)


def leaf(key, nid, spec, dtype) -> jax.Array:
    """One parameter, from the run's key and its name's id (``name_id``,
    may be traced), in the type it is stored in."""
    shape, kind, std = spec
    if kind == "ones":
        return jnp.ones(shape, dtype)
    if kind == "zeros":
        return jnp.zeros(shape, dtype)
    k = jax.random.fold_in(key, nid)
    return stored(std * jax.random.normal(k, shape, jnp.float32),
                  dtype).astype(dtype)


# -- the model ------------------------------------------------------------------
def fp8(x):
    """The control's precision, the nearest below bf16: fp8 (e4m3)
    operands. Three mantissa bits; the exponent keeps float32's range,
    which is what per-tensor scaling buys a real fp8 path, so only the
    precision is lower, not the range. Straight-through for the gradient."""
    q = jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=3)
    return x + jax.lax.stop_gradient(q - x)


def _mm(a, w, precision: str):
    if precision == "fp8":
        a, w = fp8(a), fp8(w)
    return a @ w


def _ln(x, w, b, eps):
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), -1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * w + b


def _gelu(x):
    return 0.5 * x * (1.0 + jnp.tanh(
        math.sqrt(2.0 / math.pi) * (x + 0.044715 * x ** 3)))


def block(p: Dict[str, jax.Array], x, n_heads: int, eps: float,
          precision: str):
    B, S, d = x.shape
    D = d // n_heads
    h = _ln(x, p["ln1.w"], p["ln1.b"], eps)
    qkv = _mm(h, p["qkv.w"], precision) + p["qkv.b"]
    # columns are laid out head by head, [q_h | k_h | v_h] within a head
    qkv = qkv.reshape(B, S, n_heads, 3 * D)
    q, k, v = qkv[..., :D], qkv[..., D:2 * D], qkv[..., 2 * D:]
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / math.sqrt(D)
    mask = jnp.tril(jnp.ones((S, S), bool))
    s = jnp.where(mask, s, -jnp.inf)
    a = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhqk,bkhd->bqhd", a, v).reshape(B, S, d)
    x = x + _mm(o, p["proj.w"], precision) + p["proj.b"]
    h = _ln(x, p["ln2.w"], p["ln2.b"], eps)
    h = _gelu(_mm(h, p["fc1.w"], precision) + p["fc1.b"])
    return x + _mm(h, p["fc2.w"], precision) + p["fc2.b"]


def embed(p, ids):
    return p["wte"][ids] + p["wpe"][jnp.arange(ids.shape[1])][None]


def head_loss(p, x, labels, eps: float, precision: str):
    h = _ln(x, p["lnf.w"], p["lnf.b"], eps)
    logits = _mm(h, p["wte"].T, precision)
    lse = jax.nn.logsumexp(logits, axis=-1)
    picked = jnp.take_along_axis(logits, labels[..., None], -1)[..., 0]
    return jnp.mean(lse - picked)


# -- two training steps, layer by layer -----------------------------------------
class TrainReference:
    """Follows the program's first two optimizer steps.

    ``run(batches)`` takes the first two (ids, labels) pairs the window's
    own feed produced and returns what is compared: each step's loss,
    the per-leaf norm of the first gradient, a block of the first gradient
    itself for a few leaves, and the per-leaf norm of the parameters'
    change after the two steps.
    """

    def __init__(self, cfg: Dict, seed: int, devices, precision="float32"):
        self.cfg = cfg
        self.key = jax.random.wrap_key_data(jnp.asarray(key_data(seed)))
        self.table = leaf_table(cfg)
        self.mesh = Mesh(np.asarray(devices), ("d",))
        self.rows = NamedSharding(self.mesh, P("d"))
        self.repl = NamedSharding(self.mesh, P())
        self.precision = precision
        self.store = jnp.dtype(cfg["dtype"])
        self.state = jnp.dtype(cfg["optimizer"]["state_dtype"])
        o = cfg["optimizer"]
        self.lr, self.b1, self.b2 = o["learning_rate"], o["beta1"], o["beta2"]
        self.eps, self.wd = o["epsilon"], o["weight_decay"]
        self.n_heads, self.ln_eps = cfg["n_heads"], cfg["layer_norm_eps"]
        self._g1: Dict[str, jax.Array] = {}
        self._jit: Dict = {}
        self._sampled = set(sampled_leaves(cfg))

    # names of the leaves of one group ("emb", layer index, "head")
    def _names(self, group) -> List[str]:
        if group == "emb":
            return ["wte", "wpe"]
        if group == "head":
            return ["lnf.w", "lnf.b", "wte"]
        return [f"h.{group}.{k}" for k in LAYER_LEAVES]

    @staticmethod
    def _short(name: str) -> str:
        return name.split(".", 2)[2] if name.startswith("h.") else name

    def _params(self, group, step: int) -> Dict[str, jax.Array]:
        """The group's parameters as stored before ``step`` (0 or 1), in
        float32: regenerated from the seed and, for step 1, moved by the
        first update and rounded to the storage type the configuration
        states. One compiled program serves every layer: the names enter
        as traced ids."""
        names = self._names(group)
        specs = tuple(self.table[n] for n in names)
        fn = self._jit.get(("params", specs, step))
        if fn is None:
            def make(key, nids, g1):
                out = []
                for i, spec in enumerate(specs):
                    p = leaf(key, nids[i], spec, self.store).astype(
                        jnp.float32)
                    if step:
                        p = self._apply(p, self._delta1(g1[i], p))
                    out.append(p)
                return tuple(out)
            fn = self._jit[("params", specs, step)] = jax.jit(
                make, out_shardings=self.repl)
        nids = jnp.asarray([name_id(n) for n in names], jnp.int32)
        g1 = tuple(self._g1[n] for n in names) if step else ()
        return dict(zip(map(self._short, names), fn(self.key, nids, g1)))

    def _apply(self, p, delta):
        return stored(p + delta, self.store)

    def _delta1(self, g, p0):
        """AdamW's first update. With bias correction m_hat = g and
        v_hat = g^2, each stored in ``state_dtype`` first."""
        m = stored((1 - self.b1) * g, self.state)
        v = stored((1 - self.b2) * g * g, self.state)
        upd = (m / (1 - self.b1)) / (jnp.sqrt(v / (1 - self.b2)) + self.eps)
        return -self.lr * (upd + self.wd * p0)

    def _delta2(self, g1, g2, p1):
        m1 = stored((1 - self.b1) * g1, self.state)
        v1 = stored((1 - self.b2) * g1 * g1, self.state)
        m = self.b1 * m1 + (1 - self.b1) * g2
        v = self.b2 * v1 + (1 - self.b2) * g2 * g2
        upd = (m / (1 - self.b1 ** 2)) / (
            jnp.sqrt(v / (1 - self.b2 ** 2)) + self.eps)
        return -self.lr * (upd + self.wd * p1)

    def _fwd_bwd(self):
        """The jitted pieces, built once: same code for every layer."""
        if "pieces" in self._jit:
            return self._jit["pieces"]
        prec, nh, eps = self.precision, self.n_heads, self.ln_eps

        @jax.jit
        def f_embed(p, ids):
            return embed(p, ids)

        @jax.jit
        def f_block(p, x):
            return block(p, x, nh, eps, prec)

        @jax.jit
        def b_block(p, x, ct):
            _, vjp = jax.vjp(lambda p, x: block(p, x, nh, eps, prec), p, x)
            return vjp(ct)

        @jax.jit
        def b_head(p, x, labels):
            loss, vjp = jax.vjp(
                lambda p, x: head_loss(p, x, labels, eps, prec), p, x)
            gp, gx = vjp(jnp.ones((), jnp.float32))
            return loss, gp, gx

        @jax.jit
        def b_embed(p, ids, ct):
            _, vjp = jax.vjp(lambda p: embed(p, ids), p)
            return vjp(ct)[0]

        self._jit["pieces"] = (f_embed, f_block, b_block, b_head, b_embed)
        return self._jit["pieces"]

    def _one_step(self, step: int, ids, labels, out: Dict):
        f_embed, f_block, b_block, b_head, b_embed = self._fwd_bwd()
        L = self.cfg["n_layers"]
        ids = jax.device_put(jnp.asarray(ids, jnp.int32), self.rows)
        labels = jax.device_put(jnp.asarray(labels, jnp.int32), self.rows)
        xs = [f_embed(self._params("emb", step), ids)]
        for i in range(L):
            xs.append(f_block(self._params(i, step), xs[-1]))
        hp = self._params("head", step)
        loss, g_head, ct = b_head(hp, xs.pop(), labels)
        out["loss"].append(float(loss))
        g_wte_head = g_head.pop("wte")
        self._reduce(step, "head", g_head, out)
        for i in reversed(range(L)):
            p = self._params(i, step)
            gp, ct = b_block(p, xs.pop(), ct)
            self._reduce(step, i, gp, out)
        ep = self._params("emb", step)
        g_emb = b_embed(ep, ids, ct)
        g_emb["wte"] = g_emb["wte"] + g_wte_head      # tied embedding
        self._reduce(step, "emb", g_emb, out)

    def _reduce(self, step, group, grads, out):
        """Turn one group's gradients into the numbers compared."""
        for short, g in grads.items():
            name = short if isinstance(group, str) else f"h.{group}.{short}"
            if step == 0:
                out["grad_norm"][name] = float(jnp.linalg.norm(g))
                if name in self._sampled:
                    out["grad_sample"][name] = np.asarray(g[:SAMPLE_ROWS])
                self._g1[name] = jax.device_put(g, self._spread(g))
            else:
                out["update_norm"][name] = float(self._update_norm(
                    name, self._g1.pop(name), g))

    def _spread(self, g):
        """Where the first gradient waits for step 2: rows over the
        devices when they divide, so four chips hold a quarter each."""
        n = self.mesh.devices.size
        if g.ndim and g.shape[0] % n == 0 and g.size >= 1 << 20:
            return self.rows
        return self.repl

    def _update_norm(self, name, g1, g2):
        spec = self.table[name]
        f = self._jit.get(("update_norm", spec))
        if f is None:
            def fn(key, nid, g1, g2):
                p0 = leaf(key, nid, spec, self.store).astype(jnp.float32)
                p1 = self._apply(p0, self._delta1(g1, p0))
                p2 = self._apply(p1, self._delta2(g1, g2, p1))
                return jnp.linalg.norm(p2 - p0)
            f = self._jit[("update_norm", spec)] = jax.jit(fn)
        return f(self.key, jnp.int32(name_id(name)), g1, g2)

    def run(self, batches) -> Dict:
        out = {"loss": [], "grad_norm": {}, "grad_sample": {},
               "update_norm": {}}
        with jax.default_matmul_precision("highest"):
            for step, (ids, labels) in enumerate(batches[:2]):
                self._one_step(step, ids, labels, out)
        return out
