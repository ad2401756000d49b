"""MoE gates: naive top-k, Switch (top-1), GShard (top-2), and the
top-k gate over a sigmoid score or a softmax, with or without a
selection bias, its weights renormalised over the chosen or left as the
scores are, over all experts or over the groups of experts it keeps
first (no capacity, no drops).

TPU-native re-design of the reference's gate zoo
(reference: python/paddle/incubate/distributed/models/moe/gate/
naive_gate.py, switch_gate.py, gshard_gate.py, base_gate.py).

The reference gates emit per-token expert indices consumed by the
variable-length ``global_scatter`` CUDA op. XLA needs static shapes, so
here a gate is a *policy object* — (top_k, capacity_factor, jitter,
aux-loss style) — and the dense capacity-C dispatch/combine tensors are
built inside the MoE kernel (moe_layer.py::_topk_dispatch), the standard
GShard einsum formulation that maps onto the MXU.

The gate projection weight lives in the gate (a Layer, reference parity)
and is replicated across expert-parallel ranks.
"""
from __future__ import annotations

from typing import Optional

from .....nn.layer import Layer

__all__ = ["BaseGate", "NaiveGate", "SwitchGate", "GShardGate",
           "SigmoidTopKGate"]


class BaseGate(Layer):
    """Holds the [d_model, num_experts] router projection + policy knobs."""

    top_k = 1
    capacity_factor: Optional[float] = None  # None → no token dropping
    jitter = 0.0

    def __init__(self, d_model: int, num_experts: int, weight_attr=None):
        super().__init__()
        self.d_model = d_model
        self.num_experts = num_experts
        self.weight = self.create_parameter((d_model, num_experts))
        self._loss = None

    def get_loss(self, clear: bool = True):
        """The auxiliary load-balancing loss of the last forward
        (reference base_gate.py:49 set_loss/get_loss)."""
        loss = self._loss
        if clear:
            self._loss = None
        return loss

    def set_loss(self, loss):
        self._loss = loss

    def extra_repr(self):
        return (f"d={self.d_model}, experts={self.num_experts}, "
                f"k={self.top_k}, cf={self.capacity_factor}")


class NaiveGate(BaseGate):
    """Plain top-k routing, generous capacity (reference naive_gate.py)."""

    def __init__(self, d_model, num_experts, topk: int = 2, **kw):
        super().__init__(d_model, num_experts)
        self.top_k = topk
        self.capacity_factor = None


class SwitchGate(BaseGate):
    """Switch-Transformer top-1 gate with capacity
    (reference switch_gate.py — topk=1, capacity via switch_capacity)."""

    def __init__(self, d_model, num_experts, topk: int = 1,
                 capacity: float = 1.25, **kw):
        super().__init__(d_model, num_experts)
        if topk != 1:
            raise ValueError("SwitchGate is top-1 by definition; use "
                             "GShardGate or NaiveGate for top-k routing")
        self.top_k = 1
        self.capacity_factor = capacity


class GShardGate(BaseGate):
    """GShard top-k gate with capacity and load-balance loss
    (reference gshard_gate.py — topk=2, capacity=(1.2, 2.4)).
    ``random_routing`` (probability-proportional 2nd-expert drop) is not
    implemented — routing is deterministic top-k."""

    def __init__(self, d_model, num_experts, topk: int = 2,
                 capacity: float = 2.0, random_routing: bool = False, **kw):
        super().__init__(d_model, num_experts)
        if random_routing:
            raise NotImplementedError(
                "GShardGate random_routing is not implemented; pass "
                "random_routing=False for deterministic top-k")
        self.top_k = topk
        self.capacity_factor = capacity


class SigmoidTopKGate(BaseGate):
    """Top-k on a score of ``num_experts``, weights ``scaling * score /
    sum(chosen scores)``. No capacity: every chosen pair is computed
    (``GatedMoELayer``). The score function is data (``score_func``):

    - ``"sigmoid"``: sigmoid scores, chosen on ``score + bias`` (the bias
      steers the CHOICE only: auxiliary-loss-free load balancing);
    - ``"softmax"``: a softmax over all experts, chosen on the
      probabilities themselves, renormalised over the chosen; no bias
      (the gate then has no such parameter).

    Two switches of their own: ``bias_on_choice`` (None = as above: a
    sigmoid gate has the bias, a softmax gate has not; True gives a
    softmax gate one too, added to the PROBABILITIES for the choice
    alone) and ``norm_topk_prob`` (False: the weights are ``scaling *
    score`` as the score function gave them, not divided by the chosen
    ones' sum, so a token's weights need not add up to ``scaling``).
    ``num_experts`` is the ROUTER's width: a layer with identity experts
    (``GatedMoELayer(zero_expert_num=)``) routes over more outputs than
    it has experts.

    So is the field the choice is made over (``n_group``,
    ``topk_group``; 0 = off, the top-k over ALL experts): with
    ``n_group`` > 1 the experts form that many groups of neighbours
    (expert ``e`` lies in group ``e // (num_experts // n_group)``), a
    group's score is the sum of its TWO largest choosing scores, the
    ``topk_group`` groups of largest score are kept (ties to the lower
    group), every other expert's choosing score reads 0, and the top-k
    is taken over that (DeepSeek-V3's ``noaux_tc``). The weights still
    come from the score itself.

    The router product, the score, the group sums and every top-k run in
    float32 whatever the model's type, because a near-tie between the
    k-th and the next score flips an expert under bf16 rounding. All of
    them are exact (``lax.top_k``)."""

    SCORE_FUNCS = ("sigmoid", "softmax")

    def __init__(self, d_model, num_experts, topk: int = 8,
                 routed_scaling_factor: float = 1.0,
                 score_func: str = "sigmoid", n_group: int = 0,
                 topk_group: int = 0, bias_on_choice: Optional[bool] = None,
                 norm_topk_prob: bool = True, **kw):
        super().__init__(d_model, num_experts)
        if score_func not in self.SCORE_FUNCS:
            raise ValueError(f"score_func is one of {self.SCORE_FUNCS}, "
                             f"not {score_func!r}")
        self.n_group, self.topk_group = int(n_group), int(topk_group)
        if self.n_group > 1 and not (
                num_experts % self.n_group == 0
                and 1 <= self.topk_group <= self.n_group
                and num_experts // self.n_group >= 2
                and self.topk_group * (num_experts // self.n_group)
                >= topk):
            raise ValueError(
                f"{num_experts} experts in n_group={n_group} groups of "
                f"which topk_group={topk_group} are kept: the groups "
                "must divide the experts, hold two or more each, and "
                f"the kept ones must hold the {topk} chosen")
        self.top_k = topk
        self.capacity_factor = None
        self.routed_scaling_factor = float(routed_scaling_factor)
        self.score_func = score_func
        self.norm_topk_prob = bool(norm_topk_prob)
        self.bias_on_choice = score_func == "sigmoid" \
            if bias_on_choice is None else bool(bias_on_choice)
        if self.bias_on_choice:
            self.bias = self.create_parameter((num_experts,), is_bias=True)

    def route(self, x2d):
        """Values in, values out: tokens [T, d] -> (expert ids [T, k]
        int32 over ALL ``num_experts``, weights [T, k] float32,
        normalised over the k chosen unless ``norm_topk_prob`` is
        off)."""
        return self.route_groups(x2d)[:2]

    def route_groups(self, x2d):
        """``route`` and the groups it kept: [T, topk_group] int32 group
        ids, None for a gate without groups."""
        import jax
        import jax.numpy as jnp
        from jax import lax

        logits = jnp.dot(
            x2d.astype(jnp.float32), self.weight._value.astype(jnp.float32),
            precision=lax.Precision.HIGHEST)
        if self.score_func == "sigmoid":
            s = jax.nn.sigmoid(logits)
        else:
            s = jax.nn.softmax(logits, axis=-1)
        choose = s + self.bias._value.astype(jnp.float32) \
            if self.bias_on_choice else s
        groups = None
        if self.n_group > 1:
            T, n = choose.shape[0], self.n_group
            by_group = choose.reshape(T, n, self.num_experts // n)
            score = lax.top_k(by_group, 2)[0].sum(-1)           # [T, n]
            _, groups = lax.top_k(score, self.topk_group)
            kept = jnp.any(groups[:, :, None] == jnp.arange(n)[None, None],
                           axis=1)                              # [T, n]
            choose = jnp.where(kept[:, :, None], by_group, 0.0).reshape(
                T, self.num_experts)
            groups = groups.astype(jnp.int32)
        _, idx = lax.top_k(choose, self.top_k)
        sel = jnp.take_along_axis(s, idx, axis=-1)
        w = self.routed_scaling_factor * sel
        if self.norm_topk_prob:
            w = w / jnp.sum(sel, -1, keepdims=True)
        return idx.astype(jnp.int32), w, groups
