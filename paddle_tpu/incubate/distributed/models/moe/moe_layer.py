"""Mixture-of-Experts layer with expert parallelism over ICI.

TPU-native re-design of the reference's MoELayer
(reference: python/paddle/incubate/distributed/models/moe/moe_layer.py:263
— per-token expert indices + variable-length ``global_scatter`` /
``global_gather`` CUDA all-to-alls,
fluid/operators/collective/global_scatter_op.cu.cc).

XLA needs static shapes, so routing uses the dense GShard capacity-C
formulation instead of variable-length scatter: the gate builds
``dispatch``/``combine`` one-hot tensors [T, E, C] and the dispatch,
expert FFN, and combine are three einsums (MXU-bound) around a pair of
``lax.all_to_all`` collectives on the expert-parallel mesh axes — the
same math GShard/Switch run on TPU pods. Tokens beyond an expert's
capacity are dropped (gshard/switch) or capacity is set to T (naive gate,
no dropping).

Expert weights are *stacked*: one [E, d, h] tensor sharded over the
expert axes on dim 0, so each rank physically holds E/n experts and the
expert FFN is a single batched einsum rather than a Python loop over
expert modules (the reference loops over ``self.experts`` per rank).
"""
from __future__ import annotations

import contextlib
import math
from functools import partial
from typing import List, Optional

import jax
import numpy as np
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from .....autograd import engine as _engine
from .....core import flags as _flags
from .....core.enforce import enforce
from .....distributed import collective as C
from .....nn.layer import Layer
from .....observability import annotate as _annotate
from .....observability import moestats as _moestats
from .....ops import pallas as _pallas
from .....ops.pallas.grouped_matmul import (group_visits, grouped_matmul,
                                            grouped_matmul_supported)
from .....tensor import Tensor
from .gate import (BaseGate, GShardGate, NaiveGate, SigmoidTopKGate,
                   SwitchGate)

__all__ = ["MoELayer", "GatedMoELayer"]


def _topk_dispatch(probs, k: int, cap: int):
    """Dense top-k dispatch/combine [T, E, C] + switch-style aux loss."""
    T, E = probs.shape
    masks, gates = [], []
    remaining = probs
    for _ in range(k):
        idx = jnp.argmax(remaining, axis=-1)
        m = jax.nn.one_hot(idx, E, dtype=probs.dtype)
        masks.append(m)
        gates.append(jnp.sum(probs * m, axis=-1))
        remaining = remaining * (1.0 - m)
    # load-balance loss: E * sum_e fraction_tokens(e) * mean_prob(e)
    density = jnp.mean(masks[0], axis=0)
    density_proxy = jnp.mean(probs, axis=0)
    aux = jnp.sum(density * density_proxy) * E

    denom = sum(gates) + 1e-9
    combine = jnp.zeros((T, E, cap), probs.dtype)
    offset = jnp.zeros((E,), probs.dtype)
    for j, m in enumerate(masks):
        # queue position of each token at its chosen expert; later-k
        # choices queue behind all earlier-k traffic (GShard priority)
        pos = jnp.cumsum(m, axis=0) - m + offset[None, :]
        pos_t = jnp.sum(pos * m, axis=-1)
        keep = ((pos_t < cap) & (jnp.sum(m, axis=-1) > 0)).astype(
            probs.dtype)
        gate_j = gates[j] / denom * keep
        oh_c = jax.nn.one_hot(pos_t.astype(jnp.int32), cap,
                              dtype=probs.dtype)
        combine = combine + gate_j[:, None, None] * m[:, :, None] \
            * oh_c[:, None, :]
        offset = offset + jnp.sum(m, axis=0)
    dispatch = (combine > 0).astype(probs.dtype)
    return combine, dispatch, aux


@partial(jax.custom_vjp, nondiff_argnums=(1, 2, 3))
def _ledger_a2a(x, axes, split_axis, concat_axis):
    """t_all_to_all whose BACKWARD also routes through the traced-
    collective shim: jax's built-in all_to_all transpose calls lax
    directly, which would leave the bwd dispatch/combine exchanges out
    of the comm ledger. The transpose of a (split s, concat c) a2a is
    the (split c, concat s) a2a."""
    return C.t_all_to_all(x, axes, split_axis, concat_axis, tiled=True)


def _ledger_a2a_fwd(x, axes, split_axis, concat_axis):
    return _ledger_a2a(x, axes, split_axis, concat_axis), None


def _ledger_a2a_bwd(axes, split_axis, concat_axis, _, g):
    return (C.t_all_to_all(g, axes, concat_axis, split_axis, tiled=True),)


_ledger_a2a.defvjp(_ledger_a2a_fwd, _ledger_a2a_bwd)


def _moe_forward(x2d, gate_w, w1, b1, w2, b2, axes, k, cap, act_fn,
                 ring=False):
    """Pure function: tokens [T, d] → ((output [T, d], aux loss),
    routing stats). The stats dict (per-expert load, routed/dropped
    slot counts) is non-differentiated telemetry — callers take it
    through ``jax.vjp(..., has_aux=True)``."""
    dt = x2d.dtype
    T = x2d.shape[0]
    logits = x2d.astype(jnp.float32) @ gate_w.astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)
    combine, dispatch, aux = _topk_dispatch(probs, k, cap)
    routed = jnp.float32(T * k)
    kept = jnp.sum(dispatch.astype(jnp.float32))
    stats = {
        "load": lax.stop_gradient(
            jnp.sum(dispatch, axis=(0, 2)).astype(jnp.float32)),
        "routed": routed,
        "dropped": lax.stop_gradient(jnp.maximum(routed - kept, 0.0)),
        "aux": lax.stop_gradient(aux.astype(jnp.float32)),
    }
    # dispatch: [T,E,C] x [T,d] -> [E,C,d]
    expert_in = jnp.einsum("tec,td->ecd", dispatch.astype(dt), x2d)
    if axes and ring:
        # fused path (ep_async_dispatch): dispatch-a2a + expert FFN +
        # combine-a2a as one chunked ppermute ring, the ICI exchange
        # hidden behind the per-block expert GEMMs
        from .....distributed import collective_matmul as cm

        out = cm.moe_a2a_ffn(expert_in, w1, b1, w2, b2, axes, act_fn)
        y = jnp.einsum("ecd,tec->td", out, combine.astype(dt))
        return (y, aux), stats
    if axes:
        # [E, C, d] -> [E/n, n*C, d]: each rank keeps its experts, slots
        # from every source rank ride ICI
        expert_in = _ledger_a2a(expert_in, axes, 0, 1)
    h = act_fn(jnp.einsum("ecd,edf->ecf", expert_in, w1)
               + b1[:, None, :].astype(dt))
    out = jnp.einsum("ecf,efd->ecd", h, w2) + b2[:, None, :].astype(dt)
    if axes:
        out = _ledger_a2a(out, axes, 1, 0)
    y = jnp.einsum("ecd,tec->td", out, combine.astype(dt))
    return (y, aux), stats


def _extract_expert_weights(experts: List[Layer]):
    """Stack weights from a list of uniform FFN experts (reference
    ExpertLayer exposes htoh4/h4toh Linears; generic two-Linear experts
    also accepted)."""
    w1s, b1s, w2s, b2s = [], [], [], []
    for e in experts:
        if hasattr(e, "htoh4") and hasattr(e, "h4toh"):
            lin1, lin2 = e.htoh4, e.h4toh
        else:
            lins = [l for l in e.sublayers() if hasattr(l, "weight")
                    and getattr(l, "weight").ndim == 2]
            enforce(len(lins) == 2,
                    "stacked MoE needs uniform 2-linear experts (got "
                    f"{len(lins)} linears); use htoh4/h4toh naming or the "
                    "d_hidden constructor form")
            lin1, lin2 = lins
        w1s.append(np.asarray(lin1.weight._value))
        b1s.append(np.asarray(lin1.bias._value) if lin1.bias is not None
                   else np.zeros(lin1.weight.shape[1], "float32"))
        w2s.append(np.asarray(lin2.weight._value))
        b2s.append(np.asarray(lin2.bias._value) if lin2.bias is not None
                   else np.zeros(lin2.weight.shape[1], "float32"))
    return (np.stack(w1s), np.stack(b1s), np.stack(w2s), np.stack(b2s))


class MoELayer(Layer):
    """MoE layer (reference moe_layer.py:263 signature kept where it maps).

    Two construction forms::

        MoELayer(d_model, experts=[ExpertLayer(...), ...], gate=GShardGate(...))
        MoELayer(d_model, d_hidden=2048, num_experts=8, gate="gshard")

    ``group`` is the expert-parallel group (reference ``moe_group``);
    defaults to the fleet 'ep' group when ``ep_degree > 1`` (expert
    parallelism as a first-class hybrid axis), else to the dp group —
    the legacy "experts over dp" deployment. Stacked expert params are
    sharded over it on dim 0.
    """

    def __init__(self, d_model: int, experts=None, gate=None,
                 moe_group=None, mp_group=None, recompute_interval: int = 0,
                 d_hidden: Optional[int] = None,
                 num_experts: Optional[int] = None, group=None,
                 activation=None, **kw):
        super().__init__()
        if isinstance(experts, int) and d_hidden is None:
            d_hidden, experts = experts, None
        self.d_model = d_model
        group = group if group is not None else moe_group
        if group is False:  # explicit opt-out of expert parallelism
            group = None
        elif group is None:
            from .....distributed import fleet as _fleet

            hcg = _fleet.get_hybrid_communicate_group()
            if hcg is not None and \
                    hcg.get_expert_parallel_world_size() > 1:
                group = hcg.get_expert_parallel_group()
            elif hcg is not None and \
                    hcg.get_data_parallel_world_size() > 1:
                group = hcg.get_data_parallel_group()
        self._group = group
        self.world_size = group.nranks if group is not None else 1

        if experts is not None:
            experts = list(experts)
            num_experts = len(experts)
            w1, b1, w2, b2 = _extract_expert_weights(experts)
            d_hidden = w1.shape[2]
        enforce(num_experts is not None and d_hidden is not None,
                "need experts list or (d_hidden, num_experts)")
        enforce(num_experts % self.world_size == 0,
                f"num_experts {num_experts} must divide expert-parallel "
                f"degree {self.world_size}")
        self.num_experts = num_experts
        self.d_hidden = d_hidden

        if isinstance(gate, BaseGate):
            self.gate = gate
        else:
            name = gate or "gshard"
            cls = {"gshard": GShardGate, "switch": SwitchGate,
                   "naive": NaiveGate}[name]
            self.gate = cls(d_model, num_experts)

        if experts is not None:
            from .....nn import initializer as I

            self.w1 = self.create_parameter(
                w1.shape, default_initializer=I.Assign(w1))
            self.b1 = self.create_parameter(
                b1.shape, default_initializer=I.Assign(b1), is_bias=True)
            self.w2 = self.create_parameter(
                w2.shape, default_initializer=I.Assign(w2))
            self.b2 = self.create_parameter(
                b2.shape, default_initializer=I.Assign(b2), is_bias=True)
        else:
            E, d, h = num_experts, d_model, d_hidden
            self.w1 = self.create_parameter((E, d, h))
            self.b1 = self.create_parameter((E, h), is_bias=True)
            self.w2 = self.create_parameter((E, h, d))
            self.b2 = self.create_parameter((E, d), is_bias=True)
        if self.world_size > 1 and self._group is not None:
            axes = self._group.axis_names
            for p, nd in ((self.w1, 3), (self.b1, 2), (self.w2, 3),
                          (self.b2, 2)):
                p.dist_attr = P(*((axes,) + (None,) * (nd - 1)))
                p.is_distributed = True
        self._act = activation or jax.nn.gelu
        self.aux_loss = None

    def _capacity(self, T: int) -> int:
        cf = self.gate.capacity_factor
        if cf is None:
            return T  # naive gate: no token dropped
        raw = max(1, int(math.ceil(self.gate.top_k * cf * T
                                   / self.num_experts)))
        # bucket C onto the serving compile lattice (core/bucketing):
        # token-count / capacity-factor jitter lands on a handful of
        # power-of-two capacities instead of minting a new XLA program
        # per value. Rounding UP only ever keeps more tokens (effective
        # capacity factor >= requested); a cap above T is dead slots
        # (each expert queues at most T tokens), so clamp there.
        from .....core.bucketing import bucket

        return min(bucket(raw, lo=1), T)

    def forward(self, x: Tensor) -> Tensor:
        shape = list(x.shape)
        enforce(shape[-1] == self.d_model,
                f"last dim {shape[-1]} != d_model {self.d_model}")
        T = int(np.prod(shape[:-1]))
        cap = self._capacity(T)
        axes = (self._group.axis_names
                if self.world_size > 1 and C.in_spmd_region()
                and self._group is not None else ())

        from .....distributed import collective_matmul as _cm

        ring = bool(axes) and _cm.moe_overlap_available(axes)
        x2d = x._value.reshape(T, self.d_model)
        ins = (x2d, self.gate.weight._value, self.w1._value, self.b1._value,
               self.w2._value, self.b2._value)

        def pure(*vals):
            return _moe_forward(*vals, axes=axes, k=self.gate.top_k,
                                cap=cap, act_fn=self._act, ring=ring)

        in_tensors = [x, self.gate.weight, self.w1, self.b1, self.w2,
                      self.b2]
        need_grad = _engine.is_grad_enabled() and any(
            not t.stop_gradient for t in in_tensors)
        if need_grad:
            (y2d, aux), vjp_fn, stats = jax.vjp(pure, *ins, has_aux=True)
        else:  # inference: skip the linearization + residuals entirely
            (y2d, aux), stats = pure(*ins)
        _moestats.record(stats)
        y = Tensor(y2d.reshape(shape), stop_gradient=True)
        aux_t = Tensor(aux, stop_gradient=True)
        if need_grad:
            y.stop_gradient = aux_t.stop_gradient = False

            def bwd(gy, gaux):
                grads = vjp_fn((gy.reshape(T, self.d_model), gaux))
                # x's grad back to the caller's [..., d] layout
                return (grads[0].reshape(shape),) + tuple(grads[1:])

            _engine.record_custom("moe_layer", bwd, in_tensors,
                                  [y, aux_t], (y._value, aux_t._value))
        self.gate.set_loss(aux_t)
        self.aux_loss = aux_t
        return y

    def extra_repr(self):
        return (f"d={self.d_model}, h={self.d_hidden}, "
                f"E={self.num_experts}, ep={self.world_size}, "
                f"gate={type(self.gate).__name__}")


def swiglu(x, w_gate, w_up, w_down):
    """(silu(x W_g) * x W_u) W_d, products accumulated and returned in
    float32."""
    g = jnp.dot(x, w_gate, preferred_element_type=jnp.float32)
    u = jnp.dot(x, w_up, preferred_element_type=jnp.float32)
    return jnp.dot((jax.nn.silu(g) * u).astype(x.dtype), w_down,
                   preferred_element_type=jnp.float32)


def _activation(g, u):
    """What stands between an expert's first product(s) and its last:
    ``silu(g) * u`` of a gated expert, ``relu(u)^2`` of one that has no
    gate matrix (``g`` None)."""
    return jax.nn.silu(g) * u if g is not None \
        else jnp.square(jax.nn.relu(u))


def relu2_mlp(x, w_up, w_down):
    """``relu(x W_u)^2 W_d``: ``swiglu`` without a gate matrix."""
    u = jnp.dot(x, w_up, preferred_element_type=jnp.float32)
    return jnp.dot(_activation(None, u).astype(x.dtype), w_down,
                   preferred_element_type=jnp.float32)


# The held experts' products take one of two forms, chosen from the number
# of tokens T in the call (a static shape). Batched over the held experts,
# every token goes through every held expert and the combine selects: T
# multiply-adds, 2 T operations, per weight element, T operations per
# byte of a bf16 weight. A TPU v5e turns at 197e12 / 819e9 = 240
# operations a byte, so while T stays under ~240 the weights' bytes, which
# every form has to stream, set the time, and the unchosen products cost
# nothing. 128 is the largest size of the serving lattice (powers of two)
# under that ridge. Above it the batched form would be compute-bound and
# do El / (k x held share) times the work: the sorted form, whose work
# follows the pairs routed to the held experts, takes over. Which grouped
# matmul that form's products run on is ``grouped_product``'s to say: our
# Mosaic kernel on a TPU (every held expert's weights read once a call),
# XLA's ``ragged_dot`` off it.
_BATCHED_MAX_TOKENS = 128


def routed_form(T: int) -> str:
    """The form ``routed_swiglu`` takes for a call of ``T`` tokens."""
    return "batched" if T <= _BATCHED_MAX_TOKENS else "sorted"


# The sorted form works on M sorted rows at a time, a static bound from the
# call's shapes: the pairs a uniform router sends to the held experts
# (T k El / E) with half as many again for a router that is not, rounded up
# to _SORTED_TILE rows. What a prompt sends beyond M takes another pass of
# the same body; nothing is dropped. 256 is a multiple of both row tiles of
# the grouped product that runs on a TPU (``ops/pallas/grouped_matmul.py``:
# 128 rows a visit up to 128 rows a group, 256 above, both timed there:
# ``row_tile``'s docstring), so its gate admits every M this gives; the kernel's tiles start at each group's own first
# row, so the rounding buys no alignment and is kept for the programs'
# shapes (it was XLA's ``ragged_dot``'s row tile until PR 49).
_SORTED_SLACK = 1.5
_SORTED_TILE = 256


def sorted_rows(T: int, k: int, El: int, E: int) -> int:
    """The sorted rows ``routed_swiglu_sorted`` holds at a time for ``T``
    tokens of ``k`` choices over ``E`` experts, ``El`` of them held:
    never above ``T * k``, and ``T * k`` for a layer held whole."""
    want = math.ceil(T * k * El / E * _SORTED_SLACK)
    return min(T * k, -(-want // _SORTED_TILE) * _SORTED_TILE)


def routed_swiglu(x2d, idx, weights, w_gate, w_up, w_down,
                  expert_offset: int = 0, num_experts: Optional[int] = None):
    """The held experts' part of a routed SwiGLU layer. No capacity, no
    ``[T, E, C]`` tensor, no dropped pair, in either form
    (``routed_form(T)``: ``routed_swiglu_batched`` for a decode step's
    few tokens, ``routed_swiglu_sorted`` for a prefill's many).

    x2d      [T, d]       tokens
    idx      [T, k] int32 chosen experts, numbered over ALL experts
    weights  [T, k] f32   their weights (normalised over all k chosen)
    w_*      [El, ...]    the experts held here: expert_offset ..
                          expert_offset + El - 1; ``w_gate`` None: two
                          matrices an expert, ``relu(x W_u)^2 W_d``
    num_experts           the router's width E (None: the held El)

    Returns (y [T, d] float32: the sum over chosen AND held experts;
    sizes [El + 2] int32: the pairs of each held expert, the pairs whose
    expert is held elsewhere, and the pairs whose product was computed
    AND summed into y: counted from what was computed and not from the
    router's choice, so a held pair that the products missed shows as
    missing from the last; trace: what the call's form was as it was
    traced, ``form`` and, for the sorted one, ``rows`` = (the bound M,
    T * k), ``passes``, the windows of M rows it ran: a device value,
    and ``grouped``, the grouped product its calls took:
    ``grouped_product``'s "pallas" | "xla")."""
    form = routed_form(x2d.shape[0])
    if form == "batched":
        y, sizes = routed_swiglu_batched(x2d, idx, weights, w_gate, w_up,
                                         w_down, expert_offset)
        return y, sizes, {"form": form}
    (T, k), El = idx.shape, w_up.shape[0]
    E = El if num_experts is None else num_experts
    y, sizes, passes, grouped = routed_swiglu_sorted(
        x2d, idx, weights, w_gate, w_up, w_down, expert_offset, E)
    return y, sizes, {"form": form, "passes": passes, "grouped": grouped,
                      "rows": (sorted_rows(T, k, El, E), T * k)}


def grouped_product(M: int, dtype, *weights) -> str:
    """The grouped product the sorted form's calls over ``M`` rows of
    ``dtype`` take through the stacked ``weights`` (None: no such
    matrix): ``"pallas"`` (``ops/pallas/grouped_matmul.py``) on a TPU
    with kernels on (``FLAGS_use_pallas_kernels``, as every dispatch
    site) where that kernel's shape gate admits every one of them, else
    ``"xla"`` (``lax.ragged_dot``). A function of the call's static
    shapes and the platform alone."""
    if not (_flags._get("use_pallas_kernels", True)
            and _pallas.is_tpu_platform()):
        return "xla"
    ours = all(w.dtype == dtype and grouped_matmul_supported(
        (M, w.shape[1]), w.shape, dtype) for w in weights if w is not None)
    return "pallas" if ours else "xla"


def _group_sizes(e, El: int):
    """[El] int32: how many of the keys ``e`` name each held expert."""
    return (e[:, None] == jnp.arange(El, dtype=e.dtype)).sum(
        axis=0, dtype=jnp.int32)


def _sum_by_token(y, rows, tok):
    """``y [T, d]`` with the float32 ``rows [M, d]`` added, each to the
    token ``tok [M]`` it belongs to (a name of its own so that
    ``tools/routed_swiglu_timing.py`` can time other ways to)."""
    return y.at[tok].add(rows)


def routed_swiglu_sorted(x2d, idx, weights, w_gate, w_up, w_down,
                         expert_offset: int = 0,
                         num_experts: Optional[int] = None):
    """``routed_swiglu`` with work in proportion to the HELD pairs. The
    (token, expert) pairs' int32 keys are sorted by expert, held ones
    first; a window of ``M = sorted_rows(T, k, El, E)`` sorted rows is
    gathered (``[M, d]``), goes through the two or three products grouped
    over the held experts, is scaled and added to its tokens' rows of
    ``y``. The grouped product is ``grouped_product``'s: on a TPU
    ``ops/pallas/grouped_matmul.py``, which reads each held expert's
    weights once a call and runs at 1.16-1.28 times their stream where
    XLA's kernel took 2.5-4 times it at 16-100 rows a group and was
    5-30% behind at 400-800 (PERF.md, PR 49), one table of visits a
    window for its products; off the TPU, or at widths that kernel's
    gate refuses, ``lax.ragged_dot`` (XLA's grouped matmul; what the CPU
    tests and the benchmark's references run). Rows past the window's
    last group are nobody's: the select below drops them, not a
    product. A prompt that sends more than M pairs to the held experts
    runs the same body on the next M sorted rows, ``ceil(held / M)``
    passes in all (returned third; fourth, the grouped product the
    windows took): each pass's groups are the part of every group inside
    its window. Pairs of absent experts sort behind every group and are rows
    of nothing: no array has ``T * k`` rows of width d or h unless the
    layer is held whole. The computed pairs are counted, over the
    passes, from the sorted rows that lay inside a group."""
    T, k = idx.shape
    El = w_up.shape[0]
    pairs = T * k
    M = sorted_rows(T, k, El, El if num_experts is None else num_experts)
    local = idx - expert_offset
    held = (local >= 0) & (local < El)
    e = jnp.where(held, local, El).reshape(pairs)
    gs = _group_sizes(e, El)
    ends = jnp.cumsum(gs)
    starts = ends - gs
    # the sort moves 12 bytes a pair: its key, its number, its weight
    _, order, wf = lax.sort(
        (e, jnp.arange(pairs, dtype=jnp.int32), weights.reshape(pairs)),
        num_keys=1, is_stable=True)
    order, wf = (jnp.pad(a, (0, -pairs % M)) for a in (order, wf))
    n_held = held.sum()
    row = jnp.arange(M, dtype=jnp.int32)
    grouped = grouped_product(M, x2d.dtype, w_gate, w_up, w_down)

    def window(p, carry):
        y, summed = carry
        lo = p * M
        tok = lax.dynamic_slice(order, (lo,), (M,)) // k
        gw = jnp.clip(ends, lo, lo + M) - jnp.clip(starts, lo, lo + M)
        if grouped == "pallas":     # one table of visits a window
            dot = partial(grouped_matmul, group_sizes=gw,
                          visits=group_visits(gw, M))
        else:
            dot = partial(lax.ragged_dot, group_sizes=gw,
                          preferred_element_type=jnp.float32)
        xs = x2d[tok]                                          # [M, d]
        g = None if w_gate is None else dot(xs, w_gate)
        u = dot(xs, w_up)
        out = dot(_activation(g, u).astype(x2d.dtype), w_down)
        # a sorted row counts if its pair is held and a group covered it
        used = (lo + row < n_held) & (row < gw.sum())
        scale = lax.dynamic_slice(wf, (lo,), (M,))
        out = jnp.where(used[:, None], out * scale[:, None], 0.0)
        return (_sum_by_token(y, out, tok),
                summed + used.sum(dtype=jnp.int32))

    init = (jnp.zeros((T, w_down.shape[2]), jnp.float32), jnp.int32(0))
    if M == pairs:          # one window holds every pair: no loop
        passes = jnp.int32(1)
        y, summed = window(0, init)
    else:
        passes = lax.div(ends[-1] + (M - 1), jnp.int32(M))
        y, summed = lax.fori_loop(0, passes, window, init)
    sizes = jnp.concatenate([gs, jnp.stack([(~held).sum(), summed])
                             .astype(jnp.int32)])
    return y, sizes, passes, grouped


def _combine(idx, weights, expert_offset: int, El: int):
    """The ``[T, El]`` combine of the batched form, by comparison and
    without a sort: how many of a token's pairs chose each held expert
    (0 or 1 from a top-k) and the router's weight there, 0 elsewhere."""
    hit = (idx - expert_offset)[:, :, None] == jnp.arange(El,
                                                          dtype=idx.dtype)
    return (hit.sum(axis=1, dtype=jnp.int32),
            jnp.where(hit, weights[:, :, None], 0.0).sum(axis=1))


def routed_swiglu_batched(x2d, idx, weights, w_gate, w_up, w_down,
                          expert_offset: int = 0):
    """``routed_swiglu`` with work in proportion to the HELD EXPERTS: every
    token goes through every held expert, the expert a batch dimension
    of both operands (no weight array is transposed or copied), and the
    combine selects; the unchosen products are computed and discarded.
    The combine is folded into the activations, so the down product is
    one plain matmul over (expert, hidden) and no ``[El, T, d]`` array
    exists. No sort, no gather, no ``[T*k, d]`` operand. A select, not a
    product with 0, drops an unchosen activation: it may be non-finite.
    The computed pairs are counted from the combine that was applied."""
    T = x2d.shape[0]
    El, d, h = w_up.shape
    pairs, c = _combine(idx, weights, expert_offset, El)       # [T, El]
    xb = jnp.broadcast_to(x2d, (El, T, d))
    per_expert = (((2,), (1,)), ((0,), (0,)))
    g = None if w_gate is None else lax.dot_general(
        xb, w_gate, per_expert,
        preferred_element_type=jnp.float32)                    # [El, T, h]
    u = lax.dot_general(xb, w_up, per_expert,
                        preferred_element_type=jnp.float32)
    a = jnp.where((pairs > 0).T[:, :, None],
                  _activation(g, u) * c.T[:, :, None], 0.0)
    y = jnp.dot(a.astype(x2d.dtype).transpose(1, 0, 2).reshape(T, El * h),
                w_down.reshape(El * h, w_down.shape[2]),
                preferred_element_type=jnp.float32)
    local = idx - expert_offset
    absent = ((local < 0) | (local >= El)).sum()
    # what the select let in: ``pairs`` is the matrix it tested
    sizes = jnp.concatenate([pairs.sum(axis=0), jnp.stack(
        [absent, pairs.sum()]).astype(jnp.int32)])
    return y, sizes


class GatedMoELayer(Layer):
    """Routed SwiGLU experts plus shared experts, for one holder of an
    expert-parallel layer: it is TOLD which experts it holds
    (``expert_offset``, ``num_local_experts``), routes every token over
    all ``num_experts`` (``SigmoidTopKGate``, whose ``score_func`` is a
    sigmoid with a selection bias or a softmax, and which with
    ``n_group`` / ``topk_group`` keeps that many groups of experts
    first), and computes its own
    experts' part of the sum (``routed_swiglu``: batched over the held
    experts for a decode step's few tokens, where the weights' bytes set
    the time whatever is computed; sorted and grouped for a prefill's
    many, the held pairs alone gathered and multiplied, a bound of
    ``sorted_rows`` rows at a time that the router's width fixes). The
    shared experts run whole on every holder. Inference only (no tape
    backward); on one chip there is no exchange, and nothing stands in
    for the absent holders.

    ``zero_expert_num = Z`` > 0: the router is ``num_experts + Z`` wide
    and its last Z outputs are IDENTITY experts ("zero-computation
    experts"): a chosen id ``>= num_experts`` costs no product and adds
    ``weight * x``. They live where the token lives, so every holder
    computes them WHOLE for its own rows, whatever its
    ``expert_offset`` (as a shared expert; counted once when the
    holders' parts are added up), and they are "absent" nowhere.
    ``router_bias`` / ``norm_topk_prob`` are the gate's
    ``bias_on_choice`` / ``norm_topk_prob``.

    LATENT EXPERTS (``latent_size = dl``): the ROUTER reads the
    ``d_model``-wide token, the EXPERTS read ``l = x W_dn`` (``[d_model,
    dl]``) and are ``dl -> d_hidden -> dl``, and ``W_up`` (``[dl,
    d_model]``) stands after the ROUTED sum alone: the two projections
    are linear, so the holders' parts still add up, and the shared
    expert reads and writes ``d_model``. With ``latent_scope`` that part
    (``W_dn``, the routed products, ``W_up``) runs under that
    ``observability.annotate`` scope. ``activation="relu2"``: an expert
    is TWO matrices, ``relu(x W_u)^2 W_d``, routed and shared alike (no
    ``w_gate`` / ``shared_gate`` parameter exists). ``shared_hidden``:
    the shared expert's own width (default ``d_hidden *
    num_shared_experts``).

    ``forward(x, counts=None)``: ``counts`` is an optional
    ``[num_local_experts + 3]`` int32 routing counter (``routed_swiglu``'s
    sizes: pairs per held expert, pairs of absent experts, pairs
    computed and summed; then the tokens seen; with identity experts
    ``[num_local_experts + 4]``: their pairs stand before the tokens,
    and tokens x k = held + absent + identity); when given the updated
    counter is returned beside the output. While a collection of
    ``observability.moestats`` is open the layer records, as it is
    traced, ``choices``, ``load`` (those sizes), ``zero`` (the call's
    identity pairs, with identity experts), under a gate with groups
    ``groups`` (the kept groups' ids [T, topk_group]) and ``group_load``
    (the call's routed pairs by group, [n_group]), ``form`` and, in the
    sorted form, ``rows`` (the bound, the call's routed pairs),
    ``passes`` (a device value: the windows of that many rows the call
    took; 1 unless a prompt sends the held experts more than the bound)
    and ``grouped`` (``grouped_product``: "pallas" | "xla").
    """

    def __init__(self, d_model: int, d_hidden: int, num_experts: int,
                 num_local_experts: Optional[int] = None,
                 expert_offset: int = 0, top_k: int = 8,
                 routed_scaling_factor: float = 1.0,
                 num_shared_experts: int = 1, weight_attr=None,
                 down_attr=None, score_func: str = "sigmoid",
                 n_group: int = 0, topk_group: int = 0,
                 zero_expert_num: int = 0,
                 router_bias: Optional[bool] = None,
                 norm_topk_prob: bool = True,
                 latent_size: Optional[int] = None,
                 activation: str = "swiglu",
                 shared_hidden: Optional[int] = None,
                 latent_scope: Optional[str] = None):
        super().__init__()
        El = num_experts if num_local_experts is None \
            else int(num_local_experts)
        enforce(activation in ("swiglu", "relu2"),
                f"an expert is 'swiglu' or 'relu2', not {activation!r}")
        enforce(not (latent_size and zero_expert_num),
                "an identity expert returns the token, which a latent "
                "expert never sees: latent_size and zero_expert_num "
                "exclude each other")
        self.gated = activation == "swiglu"
        self.latent_size = int(latent_size) if latent_size else None
        self.latent_scope = latent_scope
        enforce(0 <= expert_offset and expert_offset + El <= num_experts,
                f"held experts {expert_offset}..{expert_offset + El - 1} "
                f"lie outside the router's {num_experts}")
        self.zero_expert_num = int(zero_expert_num)
        enforce(self.zero_expert_num >= 0
                and not (self.zero_expert_num and n_group > 1),
                "identity experts belong to no group of experts: "
                "zero_expert_num and n_group exclude each other")
        self.d_model, self.d_hidden = d_model, d_hidden
        self.num_experts, self.num_local_experts = num_experts, El
        self.expert_offset = int(expert_offset)
        self.gate = SigmoidTopKGate(
            d_model, num_experts + self.zero_expert_num, topk=top_k,
            routed_scaling_factor=routed_scaling_factor,
            score_func=score_func, n_group=n_group, topk_group=topk_group,
            bias_on_choice=router_bias, norm_topk_prob=norm_topk_prob)
        d, h = self.latent_size or d_model, d_hidden
        hs = d_hidden * num_shared_experts if shared_hidden is None \
            else int(shared_hidden)
        down_attr = down_attr if down_attr is not None else weight_attr
        if self.gated:
            self.w_gate = self.create_parameter((El, d, h),
                                                attr=weight_attr)
        self.w_up = self.create_parameter((El, d, h), attr=weight_attr)
        self.w_down = self.create_parameter((El, h, d), attr=down_attr)
        self.shared = hs > 0
        if self.shared:
            if self.gated:
                self.shared_gate = self.create_parameter(
                    (d_model, hs), attr=weight_attr)
            self.shared_up = self.create_parameter((d_model, hs),
                                                   attr=weight_attr)
            self.shared_down = self.create_parameter((hs, d_model),
                                                     attr=down_attr)
        if self.latent_size:
            self.latent_down = self.create_parameter(
                (d_model, d), attr=weight_attr)
            self.latent_up = self.create_parameter((d, d_model),
                                                   attr=down_attr)

    def _routed(self, x2d, idx, w):
        """The held experts' part over ``x2d``: ``routed_swiglu`` as it
        is, or between the two latent projections."""
        gate = self.w_gate._value if self.gated else None
        if not self.latent_size:
            return routed_swiglu(
                x2d, idx, w, gate, self.w_up._value, self.w_down._value,
                self.expert_offset, self.gate.num_experts)
        with _annotate(self.latent_scope) if self.latent_scope \
                else contextlib.nullcontext():
            low = jnp.dot(x2d, self.latent_down._value,
                          preferred_element_type=jnp.float32
                          ).astype(x2d.dtype)
            y, sizes, trace = routed_swiglu(
                low, idx, w, gate, self.w_up._value, self.w_down._value,
                self.expert_offset, self.gate.num_experts)
            y = jnp.dot(y.astype(x2d.dtype), self.latent_up._value,
                        preferred_element_type=jnp.float32)
        return y, sizes, trace

    def forward(self, x, counts=None):
        xv = x._value if isinstance(x, Tensor) else x
        shape = xv.shape
        x2d = xv.reshape(-1, self.d_model)
        idx, w, groups = self.gate.route_groups(x2d)
        # the sorted form's bound counts on the ROUTER's width: an
        # identity pair is no row of the held experts' products
        y, sizes, trace = self._routed(x2d, idx, w)
        if self.zero_expert_num:
            # ids past the real experts: weight * x for every row here;
            # routed_swiglu counted them absent, they are nobody's
            zero = idx >= self.num_experts
            y = y + jnp.where(zero, w, 0.0).sum(-1)[:, None] \
                * x2d.astype(jnp.float32)
            n_zero = zero.sum(dtype=jnp.int32)
            sizes = jnp.concatenate([
                sizes.at[self.num_local_experts].add(-n_zero), n_zero[None]])
            trace = dict(trace, zero=n_zero)
        if _moestats.active():
            if groups is not None:
                n = self.gate.n_group
                trace = dict(trace, groups=groups, group_load=jnp.sum(
                    (idx // (self.num_experts // n))[..., None]
                    == jnp.arange(n), axis=(0, 1), dtype=jnp.int32))
            _moestats.record({"choices": idx, "load": sizes, **trace})
        if self.shared and self.gated:
            y = y + swiglu(x2d, self.shared_gate._value,
                            self.shared_up._value, self.shared_down._value)
        elif self.shared:
            y = y + relu2_mlp(x2d, self.shared_up._value,
                               self.shared_down._value)
        out = Tensor(y.astype(xv.dtype).reshape(shape),
                     stop_gradient=True)
        if counts is None:
            return out
        seen = jnp.asarray([x2d.shape[0]], jnp.int32)
        return out, counts + jnp.concatenate([sizes, seen])

    def extra_repr(self):
        return (f"d={self.d_model}, h={self.d_hidden}, "
                f"E={self.num_experts}, held={self.expert_offset}.."
                f"{self.expert_offset + self.num_local_experts - 1}, "
                f"k={self.gate.top_k}")
