"""Per-op SPMD (sharding) propagation rules.

(reference: paddle/phi/infermeta/spmd_rules/*.cc — matmul.cc,
elementwise.cc, reduction.cc, embedding.cc, reshape.cc, transpose.cc,
softmax.cc... — there each PHI op infers its outputs' TensorDistAttr
from the inputs' during static planning.)

TPU-native split of responsibilities: the HEAVY half of sharding
propagation (choosing collectives, partial-sum placement, resharding)
is owned by XLA's GSPMD when the auto-parallel Engine jit-compiles the
step — these rules only propagate the EAGER metadata (`Tensor.dist_attr`
PartitionSpecs) through the dispatch chokepoint so user code can ask
"how is this result distributed?" between ops, exactly like the
reference's eager DistTensor does.

Rules receive normalized input specs (tuples padded to each input's
rank) and return one spec tuple per output, or None when the rule
cannot say (the output is then left unannotated rather than wrongly
annotated).
"""
from __future__ import annotations

from typing import Callable, Dict, List, Optional, Sequence, Tuple

from jax.sharding import PartitionSpec as P

_RULES: Dict[str, Callable] = {}


def register_rule(*names):
    def deco(fn):
        for n in names:
            _RULES[n] = fn
        return fn
    return deco


def _spec_of(t) -> Optional[Tuple]:
    da = getattr(t, "dist_attr", None)
    if da is None:
        return None
    parts = tuple(da) if isinstance(da, P) else tuple(da)
    nd = getattr(t._value, "ndim", len(parts))
    return parts + (None,) * (nd - len(parts))


def _merge_entry(a, b):
    if a == b:
        return a
    if a is None:
        return b
    if b is None:
        return a
    return None  # conflicting shardings: give up on this dim


# ---------------------------------------------------------------------------
# rule implementations
# ---------------------------------------------------------------------------


def _elementwise(op, in_ts, out_vals, args, kwargs):
    """Broadcast elementwise: align specs right, merge per dim
    (reference elementwise.cc)."""
    out = out_vals[0]
    nd = out.ndim
    parts: List = [None] * nd
    for t in in_ts:
        s = _spec_of(t)
        if s is None:
            continue
        tnd = t._value.ndim
        for i, e in enumerate(s):
            # right-aligned broadcast: dim i of t maps to out dim
            oi = i + (nd - tnd)
            if t._value.shape[i] == out.shape[oi]:
                parts[oi] = _merge_entry(parts[oi], e)
    return [tuple(parts)]


def _passthrough_same_shape(op, in_ts, out_vals, args, kwargs):
    """Unary (or first-input-dominant) shape-preserving ops."""
    for t in in_ts:
        s = _spec_of(t)
        if s is not None and tuple(t._value.shape) == tuple(
                out_vals[0].shape):
            return [s]
    return None


@register_rule("matmul")
def _matmul(op, in_ts, out_vals, args, kwargs):
    """(reference matmul.cc) batch/m dims from x, n from y; the
    contracted dim's sharding is dropped (GSPMD realizes the partial
    sum; metadata-wise the output is unsharded there)."""
    x, y = in_ts[0], in_ts[1]
    sx, sy = _spec_of(x), _spec_of(y)
    tx = bool(kwargs.get("transpose_x", False) or
              (len(args) > 2 and args[2]))
    ty = bool(kwargs.get("transpose_y", False) or
              (len(args) > 3 and args[3]))
    out = out_vals[0]
    nd = out.ndim
    if x._value.ndim < 2 or y._value.ndim < 2 or nd < 2:
        # matrix-vector / vector products: stay unannotated rather
        # than risk assigning the m-dim sharding to a batch dim
        return None
    parts: List = [None] * nd
    if sx is not None:
        # batch dims + m
        for i in range(min(x._value.ndim - 2, nd - 2)):
            parts[i] = sx[i]
        parts[-2] = sx[-1] if tx else sx[-2]
    if sy is not None:
        parts[-1] = sy[-2] if ty else sy[-1]
    return [tuple(parts)]


@register_rule("linear", "fused_gemm_epilogue")
def _linear(op, in_ts, out_vals, args, kwargs):
    x, w = in_ts[0], in_ts[1]
    sx, sw = _spec_of(x), _spec_of(w)
    nd = out_vals[0].ndim
    parts: List = [None] * nd
    if sx is not None:
        for i in range(nd - 1):
            if i < len(sx) - 1:
                parts[i] = sx[i]
    if sw is not None:
        parts[-1] = sw[-1]
    return [tuple(parts)]


@register_rule("sum", "mean", "max", "min", "prod", "logsumexp")
def _reduction(op, in_ts, out_vals, args, kwargs):
    """(reference reduction.cc) drop reduced dims' entries."""
    t = in_ts[0]
    s = _spec_of(t)
    if s is None:
        return None
    axis = kwargs.get("axis", args[1] if len(args) > 1 else None)
    keepdim = bool(kwargs.get("keepdim", args[2] if len(args) > 2
                              else False))
    nd = t._value.ndim
    if axis is None:
        axes = set(range(nd))
    else:
        axes = {a % nd for a in
                (axis if isinstance(axis, (list, tuple)) else [axis])}
    parts = []
    for i, e in enumerate(s):
        if i in axes:
            if keepdim:
                parts.append(None)
        else:
            parts.append(e)
    return [tuple(parts)]


@register_rule("transpose")
def _transpose(op, in_ts, out_vals, args, kwargs):
    s = _spec_of(in_ts[0])
    if s is None:
        return None
    perm = kwargs.get("perm", args[1] if len(args) > 1 else None)
    if perm is None:
        return [tuple(reversed(s))]
    return [tuple(s[int(p)] for p in perm)]


@register_rule("reshape")
def _reshape(op, in_ts, out_vals, args, kwargs):
    """(reference reshape.cc) keep leading-dim entries while the
    cumulative products still match; anything past the first changed
    dim is conservatively unannotated."""
    t = in_ts[0]
    s = _spec_of(t)
    if s is None:
        return None
    ishape = tuple(t._value.shape)
    oshape = tuple(out_vals[0].shape)
    parts: List = [None] * len(oshape)
    for i in range(min(len(ishape), len(oshape))):
        if ishape[i] != oshape[i]:
            break
        parts[i] = s[i]
    return [tuple(parts)]


@register_rule("squeeze")
def _squeeze(op, in_ts, out_vals, args, kwargs):
    s = _spec_of(in_ts[0])
    if s is None:
        return None
    t = in_ts[0]
    ishape = tuple(t._value.shape)
    axis = kwargs.get("axis", args[1] if len(args) > 1 else None)
    nd = len(ishape)
    if axis is None:
        drop = {i for i, d in enumerate(ishape) if d == 1}
    else:
        drop = {a % nd for a in
                (axis if isinstance(axis, (list, tuple)) else [axis])}
    return [tuple(e for i, e in enumerate(s) if i not in drop)]


@register_rule("unsqueeze")
def _unsqueeze(op, in_ts, out_vals, args, kwargs):
    s = _spec_of(in_ts[0])
    if s is None:
        return None
    axis = kwargs.get("axis", args[1] if len(args) > 1 else 0)
    axes = sorted((a if a >= 0 else a + out_vals[0].ndim)
                  for a in (axis if isinstance(axis, (list, tuple))
                            else [axis]))
    parts = list(s)
    for a in axes:
        parts.insert(a, None)
    return [tuple(parts)]


@register_rule("embedding", "c_embedding")
def _embedding(op, in_ts, out_vals, args, kwargs):
    """(reference embedding.cc) out = ids dims + table's embed dim."""
    # signature embedding(x, weight) / c_embedding(w, ids)
    if op == "c_embedding":
        w, ids = in_ts[0], in_ts[1]
    else:
        ids, w = in_ts[0], in_ts[1]
    si = _spec_of(ids) or (None,) * ids._value.ndim
    sw = _spec_of(w)
    tail = sw[-1] if sw is not None else None
    return [tuple(si) + (tail,)]


@register_rule("flash_attention", "flash_attention_pallas",
               "scaled_dot_product_attention")
def _attention(op, in_ts, out_vals, args, kwargs):
    """(reference FlashAttInferSpmd) output follows q (the Pallas op's
    second output, the rows' logsumexp, is left unannotated)."""
    s = _spec_of(in_ts[0])
    return [s] if s is not None else None


@register_rule("softmax", "log_softmax")
def _softmax(op, in_ts, out_vals, args, kwargs):
    s = _spec_of(in_ts[0])
    if s is None:
        return None
    axis = kwargs.get("axis", args[1] if len(args) > 1 else -1)
    nd = in_ts[0]._value.ndim
    parts = list(s)
    parts[axis % nd] = None  # softmax dim must not stay sharded
    return [tuple(parts)]


@register_rule("concat_op", "concat")
def _concat(op, in_ts, out_vals, args, kwargs):
    axis = kwargs.get("axis", 0)
    specs = [_spec_of(t) for t in in_ts if t is not None]
    specs = [s for s in specs if s is not None]
    if not specs:
        return None
    nd = out_vals[0].ndim
    parts: List = [None] * nd
    for i in range(nd):
        vals = [s[i] for s in specs]
        e = vals[0]
        for v in vals[1:]:
            e = _merge_entry(e, v)
        parts[i] = e
    parts[axis % nd] = None
    return [tuple(parts)]


_ELEMENTWISE_OPS = {
    "add", "subtract", "multiply", "divide", "maximum", "minimum",
    "pow", "floor_divide", "mod", "remainder", "where", "clip",
    "add_n", "scale",
}

_UNARY_OPS = {
    "relu", "relu6", "gelu", "silu", "swish", "mish", "sigmoid", "tanh",
    "exp", "log", "sqrt", "rsqrt", "abs", "neg", "cast", "dropout",
    "erf", "floor", "ceil", "round", "sign", "square", "leaky_relu",
    "elu", "selu", "celu", "hardswish", "hardsigmoid", "softplus",
    "layer_norm", "rms_norm", "group_norm", "label_smooth",
    "fused_layer_norm_residual", "tril", "triu",
}


def infer(op_name: str, in_tensors: Sequence, out_tensors: Sequence,
          args, kwargs) -> None:
    """Annotate ``out_tensors``' dist_attr from inputs (best-effort; a
    missing/failed rule leaves outputs unannotated)."""
    ts = [t for t in in_tensors if t is not None]
    if not any(getattr(t, "dist_attr", None) is not None for t in ts):
        return
    rule = _RULES.get(op_name)
    if rule is None:
        if op_name in _ELEMENTWISE_OPS:
            rule = _elementwise
        elif op_name in _UNARY_OPS:
            rule = _passthrough_same_shape
        else:
            return
    try:
        out_vals = [o._value for o in out_tensors]
        specs = rule(op_name, ts, out_vals, args, kwargs)
    except Exception:
        return  # metadata only: never break the op over a rule bug
    if not specs:
        return
    for o, s in zip(out_tensors, specs):
        if s is not None and any(e is not None for e in s):
            o.dist_attr = P(*s)
