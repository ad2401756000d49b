"""Tape-based reverse-mode autograd engine.

TPU-native analog of the reference's eager autograd
(reference: paddle/fluid/eager/grad_node_info.h:53,197 GradNodeBase/Edge;
paddle/fluid/eager/backward.cc egr::Backward — queue-based engine with
dependency counting; paddle/fluid/eager/autograd_meta.h:61).

Design differences from the reference, driven by XLA:
- Grad kernels are pure JAX functions; each node's backward is either an
  explicit registered grad kernel or a generic jax.vjp of the forward
  (jit-cached per op — see core/registry.py). Saved "TensorWrapper"s are
  simply the forward input/output jax.Arrays (no-copy, immutable).
- The same tape runs under an enclosing jax.jit trace: recording and
  replay happen at Python level on Tracers, so `loss.backward()` inside a
  traced train step emits the backward ops into the *same* XLA program —
  this is how whole-step compilation (jit.to_static) gets a single fused
  graph with no eager overhead.
"""
from __future__ import annotations

import contextlib
import threading
from collections import deque
from typing import Any, List, Optional, Sequence, Tuple

import jax.numpy as jnp

from ..core.registry import OpCall, run_grad

__all__ = [
    "GradNode",
    "backward",
    "no_grad",
    "enable_grad",
    "is_grad_enabled",
    "set_grad_enabled",
    "record_op",
    "last_backward_nodes",
    "register_backward_end_callback",
    "unregister_backward_end_callback",
]

# callbacks fired after every backward() completes (e.g. the bucketed
# DataParallel Reducer flushes leftover partial buckets here — the
# analog of the reference Reducer's finalize_backward)
_backward_end_callbacks: List = []


def register_backward_end_callback(cb) -> None:
    _backward_end_callbacks.append(cb)


def unregister_backward_end_callback(cb) -> None:
    try:
        _backward_end_callbacks.remove(cb)
    except ValueError:
        pass

_state = threading.local()


def is_grad_enabled() -> bool:
    return getattr(_state, "grad_enabled", True)


def set_grad_enabled(mode: bool) -> None:
    _state.grad_enabled = bool(mode)


class _GradModeGuard(contextlib.ContextDecorator):
    def __init__(self, mode: bool):
        self._mode = mode

    def __enter__(self):
        self._prev = is_grad_enabled()
        set_grad_enabled(self._mode)
        return self

    def __exit__(self, *exc):
        set_grad_enabled(self._prev)
        return False


def last_backward_nodes() -> Tuple[int, int]:
    """Of the op nodes this thread's last ``backward()`` ran: how many
    took their op's explicit grad kernel, and how many the generic
    ``jax.vjp`` that runs the forward again (core/registry.py
    ``run_grad``). Counted while the tape walks, so under a trace it
    costs the compiled program nothing."""
    return getattr(_state, "backward_nodes", (0, 0))


def no_grad():
    """Context manager / decorator disabling tape recording (paddle.no_grad)."""
    return _GradModeGuard(False)


def enable_grad():
    return _GradModeGuard(True)


class GradNode:
    """One recorded op on the tape (analog of GradNode<Op> in eager_gen).

    ``edges[i]`` routes the grad of tensor-input i to its producer:
      None                      — input does not require grad
      ("leaf", tensor)          — accumulate into tensor.grad
      ("node", node, out_idx)   — flows to producer node's output slot
    """

    __slots__ = ("name", "call", "in_values", "out_values", "edges", "n_outputs",
                 "_hooks")

    def __init__(self, call: OpCall, in_values, out_values, edges):
        self.name = call.opdef.name
        self.call = call
        self.in_values = in_values
        self.out_values = out_values if isinstance(out_values, tuple) else (out_values,)
        self.edges = edges
        self.n_outputs = len(self.out_values)
        self._hooks = None

    def apply(self, out_grads: List[Optional[Any]]) -> Tuple[Optional[Any], ...]:
        if self.call is None:
            raise RuntimeError(
                f"backward through {self.name} a second time: the graph was "
                "released after .backward(); pass retain_graph=True to keep it")
        full = tuple(
            g if g is not None else jnp.zeros_like(v)
            for g, v in zip(out_grads, self.out_values)
        )
        # Match the forward's output structure for jax.vjp (ops return a
        # single array or a tuple of >=2 — see core/registry.py convention).
        structured = full if self.n_outputs > 1 else full[0]
        return run_grad(self.call, self.in_values, _raw_out(self), structured)

    def release(self):
        self.call = None
        self.in_values = None
        self.out_values = None
        self.edges = ()

    def __repr__(self):
        return f"GradNode({self.name})"


def _raw_out(node: GradNode):
    return node.out_values if node.n_outputs > 1 else node.out_values[0]


class _CustomNode(GradNode):
    """Node whose backward is a user fn (PyLayer, collectives, recompute)."""

    __slots__ = ("backward_fn",)

    def __init__(self, name, backward_fn, out_values, edges):
        self.name = name
        self.call = None
        self.in_values = None
        self.out_values = out_values if isinstance(out_values, tuple) else (out_values,)
        self.edges = edges
        self.n_outputs = len(self.out_values)
        self.backward_fn = backward_fn
        self._hooks = None

    def apply(self, out_grads):
        if self.backward_fn is None:
            raise RuntimeError(
                f"backward through {self.name} a second time: the graph was "
                "released after .backward(); pass retain_graph=True to keep it")
        full = tuple(
            g if g is not None else jnp.zeros_like(v)
            for g, v in zip(out_grads, self.out_values)
        )
        grads = self.backward_fn(*full)
        if not isinstance(grads, (tuple, list)):
            grads = (grads,)
        return tuple(grads)

    def release(self):
        self.backward_fn = None
        self.out_values = None
        self.edges = ()


def record_op(call: OpCall, in_tensors, out_tensors, out_values) -> None:
    """Attach a GradNode to the outputs of an executed op (tape record)."""
    edges = []
    for t in in_tensors:
        if t is None or t.stop_gradient:
            edges.append(None)
        elif t._grad_node is not None:
            edges.append(("node", t._grad_node, t._out_idx))
        else:
            edges.append(("leaf", t))
    node = GradNode(call, call.in_values, out_values, edges)
    for i, t in enumerate(out_tensors):
        t._grad_node = node
        t._out_idx = i


def record_custom(name, backward_fn, in_tensors, out_tensors, out_values) -> None:
    """Record a custom-backward node (PyLayer / collective ops)."""
    edges = []
    for t in in_tensors:
        if t is None or t.stop_gradient:
            edges.append(None)
        elif t._grad_node is not None:
            edges.append(("node", t._grad_node, t._out_idx))
        else:
            edges.append(("leaf", t))
    node = _CustomNode(name, backward_fn, out_values, edges)
    for i, t in enumerate(out_tensors):
        t._grad_node = node
        t._out_idx = i


def backward(tensors: Sequence, grad_tensors: Optional[Sequence] = None,
             retain_graph: bool = False) -> None:
    """Run reverse accumulation from ``tensors`` (egr::Backward analog).

    Queue-based with per-node dependency counting, matching the engine
    strategy of backward.cc: a node runs only once all grads flowing into
    its output slots (from already-processed consumers) are accumulated.
    """
    from ..tensor import Tensor  # local import to avoid cycle

    if grad_tensors is None:
        grad_tensors = [None] * len(tensors)

    buffers = {}    # node -> per-output-slot accumulated grads
    pending = {}    # node -> number of unprocessed consumer edges
    roots = []

    def seed(t: Tensor, g):
        if g is None:
            g = jnp.ones_like(t._value)
        elif isinstance(g, Tensor):
            g = g._value
        if t._grad_node is None:
            if not t.stop_gradient:
                _accumulate_leaf(t, g)
            return
        node, idx = t._grad_node, t._out_idx
        buf = buffers.setdefault(node, [None] * node.n_outputs)
        buf[idx] = g if buf[idx] is None else buf[idx] + g
        roots.append(node)

    for t, g in zip(tensors, grad_tensors):
        seed(t, g)

    # Discover reachable graph + consumer counts.
    visited = set()
    stack = list(roots)
    while stack:
        node = stack.pop()
        if id(node) in visited:
            continue
        visited.add(id(node))
        pending.setdefault(node, 0)
        for e in node.edges:
            if e is not None and e[0] == "node":
                producer = e[1]
                pending[producer] = pending.get(producer, 0) + 1
                stack.append(producer)

    queue = deque(n for n in pending if pending[n] == 0)
    processed = []
    by_path = [0, 0]    # op nodes by explicit grad kernel | generic vjp
    while queue:
        node = queue.popleft()
        out_grads = buffers.pop(node, [None] * node.n_outputs)
        if node.call is not None:
            by_path[node.call.opdef.grad_fn is None] += 1
        in_grads = node.apply(out_grads)
        if node._hooks:
            for hook in node._hooks:
                hook()
        processed.append(node)
        for e, g in zip(node.edges, in_grads):
            if e is None or g is None:
                continue
            if e[0] == "leaf":
                _accumulate_leaf(e[1], g)
            else:
                producer, idx = e[1], e[2]
                buf = buffers.setdefault(producer, [None] * producer.n_outputs)
                buf[idx] = g if buf[idx] is None else buf[idx] + g
                pending[producer] -= 1
                if pending[producer] == 0:
                    queue.append(producer)

    _state.backward_nodes = tuple(by_path)
    for cb in list(_backward_end_callbacks):
        cb()

    if not retain_graph:
        for node in processed:
            node.release()


def _accumulate_leaf(t, g) -> None:
    from ..tensor import Tensor
    from ..framework.selected_rows import SelectedRows

    if isinstance(g, SelectedRows):
        # row-sparse leaf gradient (sparse embedding): stays sparse
        # while possible — concat on sparse+sparse, densify on mixing
        # with a dense grad or with grad hooks (hooks see dense Tensors)
        if t._grad_hooks:
            g = g.to_dense_value()
        elif t.grad is None:
            t.grad = g
            return
        elif isinstance(t.grad, SelectedRows):
            t.grad = SelectedRows(
                jnp.concatenate([t.grad.rows, g.rows]),
                jnp.concatenate([t.grad.values, g.values]), g.height)
            return
        else:
            t.grad = Tensor(t.grad._value + g.to_dense_value(),
                            stop_gradient=True)
            return
    elif isinstance(t.grad, SelectedRows):
        t.grad = Tensor(t.grad.to_dense_value(), stop_gradient=True)
    if t._grad_hooks:
        gt = Tensor(g, stop_gradient=True)
        for hook in t._grad_hooks:
            res = hook(gt)
            if res is not None:
                gt = res
        g = gt._value
    if t.grad is None:
        t.grad = Tensor(g, stop_gradient=True)
    else:
        t.grad = Tensor(t.grad._value + g, stop_gradient=True)


# ---------------------------------------------------------------------------
# Higher-order backward (create_graph)
# ---------------------------------------------------------------------------


class _TapedFnNode(GradNode):
    """A grad-of-grad node: stores a PURE fn + operand values, so it can
    be applied (first order) or re-taped (any higher order) — the
    replayable analog of the reference's generated double_grad nodes."""

    __slots__ = ("fn",)

    def __init__(self, name, fn, in_values, out_values, edges):
        self.name = name
        self.fn = fn
        self.call = None
        self.in_values = tuple(in_values)
        self.out_values = out_values if isinstance(out_values, tuple) \
            else (out_values,)
        self.edges = edges
        self.n_outputs = len(self.out_values)
        self._hooks = None

    def apply(self, out_grads):
        import jax

        if self.fn is None:
            raise RuntimeError(
                f"backward through {self.name} a second time: the graph "
                "was released; pass retain_graph=True to keep it")
        full = tuple(
            g if g is not None else jnp.zeros_like(v)
            for g, v in zip(out_grads, self.out_values))
        _, vjp_fn = jax.vjp(lambda *a: self.fn(*a), *self.in_values)
        grads = vjp_fn(full)
        return tuple(
            None if (g is None or g.dtype == jax.dtypes.float0) else g
            for g in grads)

    def release(self):
        self.fn = None
        self.in_values = None
        self.out_values = None
        self.edges = ()


def _tensor_view(val, edge):
    """A Tensor aliasing a recorded input value, wired back into the
    tape via its edge — gives the second-order graph a path to the
    original producers/leaves."""
    from ..tensor import Tensor

    if edge is None:
        return Tensor(val, stop_gradient=True)
    if edge[0] == "leaf":
        return edge[1]
    t = Tensor(val, stop_gradient=False)
    t._grad_node = edge[1]
    t._out_idx = edge[2]
    return t


def backward_create_graph(tensors: Sequence,
                          grad_tensors: Optional[Sequence] = None,
                          leaf_filter=None) -> None:
    """Reverse accumulation where the computed grads are THEMSELVES
    recorded on the tape, so further ``backward``/``grad`` calls
    differentiate through them to ANY order (reference: the double_grad
    node generation of eager_gen — grad ops recorded like forward ops).

    Per-node construction: the map (saved_inputs, out_grads) ->
    in_grads is a pure jax function (re-running the forward ties the
    saved outputs to the inputs), so each first-order grad is emitted
    as a replayable :class:`_TapedFnNode` whose own grads follow the
    same construction recursively. Supported for the registered-op
    tape; custom-backward nodes (PyLayer, collectives, pipeline) raise.

    ``leaf_filter``: optional set of tensor ids — only those leaves
    accumulate (paddle.grad's only-inputs semantics).
    """
    from ..tensor import Tensor

    if grad_tensors is None:
        grad_tensors = [None] * len(tensors)

    buffers = {}    # node -> per-output-slot accumulated grad TENSORS
    pending = {}
    roots = []

    def add_grad(buf, idx, gt):
        buf[idx] = gt if buf[idx] is None else buf[idx] + gt

    def leaf_acc(t, gt):
        if leaf_filter is not None and id(t) not in leaf_filter:
            return
        _accumulate_leaf_tensor(t, gt)

    def seed(t, g):
        if g is None:
            g = Tensor(jnp.ones_like(t._value), stop_gradient=True)
        elif not isinstance(g, Tensor):
            g = Tensor(jnp.asarray(g), stop_gradient=True)
        if t._grad_node is None:
            if not t.stop_gradient:
                leaf_acc(t, g)
            return
        node, idx = t._grad_node, t._out_idx
        buf = buffers.setdefault(node, [None] * node.n_outputs)
        add_grad(buf, idx, g)
        roots.append(node)

    for t, g in zip(tensors, grad_tensors):
        seed(t, g)

    visited = set()
    stack = list(roots)
    while stack:
        node = stack.pop()
        if id(node) in visited:
            continue
        visited.add(id(node))
        pending.setdefault(node, 0)
        for e in node.edges:
            if e is not None and e[0] == "node":
                pending[e[1]] = pending.get(e[1], 0) + 1
                stack.append(e[1])

    queue = deque(n for n in pending if pending[n] == 0)
    while queue:
        node = queue.popleft()
        out_gts = buffers.pop(node, [None] * node.n_outputs)
        in_gts = _apply_taped(node, out_gts)
        if node._hooks:
            for hook in node._hooks:
                hook()
        for e, gt in zip(node.edges, in_gts):
            if e is None or gt is None:
                continue
            if e[0] == "leaf":
                leaf_acc(e[1], gt)
            else:
                producer, idx = e[1], e[2]
                buf = buffers.setdefault(producer,
                                         [None] * producer.n_outputs)
                add_grad(buf, idx, gt)
                pending[producer] -= 1
                if pending[producer] == 0:
                    queue.append(producer)
    # create_graph implies the graph stays alive (no release)


def _node_pure_fn(node: GradNode):
    """The node's backward as a PURE function of (operand values,
    out-grad values) -> tuple of in-grads."""
    import jax

    from ..core.registry import run_grad as _run_grad

    if isinstance(node, _TapedFnNode):
        fn = node.fn

        def pure(ivals, ogs):
            _, vjp_fn = jax.vjp(lambda *a: fn(*a), *ivals)
            grads = vjp_fn(tuple(ogs))
            return tuple(
                jnp.zeros_like(iv) if (
                    g is None or g.dtype == jax.dtypes.float0) else g
                for iv, g in zip(ivals, grads))

        return pure

    call = node.call
    multi = node.n_outputs > 1

    def pure(ivals, ogs):
        outs = call.flat_fn(*ivals)  # re-tie outputs to inputs
        grads = _run_grad(call, ivals, outs,
                          tuple(ogs) if multi else ogs[0])
        return tuple(
            jnp.zeros_like(iv) if g is None else g
            for iv, g in zip(ivals, grads))

    return pure


def _apply_taped(node: GradNode, out_grad_tensors):
    """Compute a node's input grads as RECORDED Tensors whose own
    backward is a replayable _TapedFnNode (recursion-closed: works for
    grad-of-grad nodes too, enabling arbitrary order)."""
    import jax

    from ..tensor import Tensor

    if isinstance(node, _CustomNode):
        raise NotImplementedError(
            f"create_graph through '{node.name}': custom-backward nodes "
            "(PyLayer, collectives, pipeline) save value closures that "
            "cannot be re-differentiated w.r.t. the forward inputs; "
            "express the computation with registered ops for "
            "higher-order gradients")
    if node.call is None and not isinstance(node, _TapedFnNode):
        raise RuntimeError(
            f"backward through {node.name} a second time: the graph was "
            "released; use retain_graph/create_graph on the first pass")

    og_full = tuple(
        (g._value if isinstance(g, Tensor) else g)
        if g is not None else jnp.zeros_like(v)
        for g, v in zip(out_grad_tensors, node.out_values))
    ivals = tuple(node.in_values)
    n_in = len(ivals)
    pure = _node_pure_fn(node)

    def flat_fn(*a):
        return pure(a[:n_in], a[n_in:])

    out_vals = flat_fn(*(ivals + og_full))

    in_views = [_tensor_view(v, e) for v, e in zip(ivals, node.edges)]
    og_tensors = [
        g if isinstance(g, Tensor) else Tensor(v, stop_gradient=True)
        for g, v in zip(out_grad_tensors, og_full)]
    out_tensors = [Tensor(v, stop_gradient=False) for v in out_vals]

    # record the replayable grad-of-grad node (edges like record_custom)
    operand_tensors = in_views + og_tensors
    edges = []
    for t in operand_tensors:
        if t is None or t.stop_gradient:
            edges.append(None)
        elif t._grad_node is not None:
            edges.append(("node", t._grad_node, t._out_idx))
        else:
            edges.append(("leaf", t))
    gnode = _TapedFnNode(f"{node.name}_grad", flat_fn,
                         ivals + og_full, tuple(out_vals), edges)
    for i, t in enumerate(out_tensors):
        t._grad_node = gnode
        t._out_idx = i
    # inputs that don't require grad yield None (parity with apply())
    return [t if e is not None else None
            for t, e in zip(out_tensors, node.edges)]


def _accumulate_leaf_tensor(t, gt) -> None:
    if t._grad_hooks:
        for hook in t._grad_hooks:
            res = hook(gt)
            if res is not None:
                gt = res
    if t.grad is None:
        t.grad = gt
    else:
        t.grad = t.grad + gt
