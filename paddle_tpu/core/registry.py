"""Op registry and eager executor.

TPU-native re-design of the reference's PHI kernel registry/dispatch
(reference: paddle/phi/core/kernel_factory.h:58,240,316 — KernelKey/
Kernel/KernelFactory::SelectKernelOrThrowError; registration macro
paddle/phi/core/kernel_registry.h:196 PD_REGISTER_KERNEL).

Where the reference maps (op name, backend, dtype, layout) -> a C++ kernel
that launches CUDA, here every op is a *pure JAX function* and "kernel
selection" becomes: pick the op's jax/Pallas implementation and fetch (or
build) a cached XLA executable keyed by (op, static attrs) — jax.jit then
keys on shapes/dtypes, mirroring KernelKey. This addresses the reference's
per-op dispatch on a compiled device: each eager op call is one cached
PJRT executable launch, and under a whole-graph trace (to_static) the same
op functions inline into a single XLA program with no per-op overhead.

Attrs convention: tensor inputs are positional-or-keyword args holding
arrays; anything non-array (ints, floats passed as attrs, bools, strings,
tuples, None) is treated as a *static attribute* baked into the cache key,
exactly like the reference's op attributes on an OpDesc.
"""
from __future__ import annotations

import functools
import threading
from typing import Any, Callable, Dict, Optional, Sequence, Tuple

import jax
import numpy as np
import jax.numpy as jnp

from . import flags
from .enforce import AlreadyExistsError, NotFoundError

__all__ = ["OpDef", "register_op", "register_grad", "get_op", "OpCall", "run_op"]

Tracer = jax.core.Tracer


class OpDef:
    """A registered operator: forward jax fn + optional explicit grad fn."""

    __slots__ = ("name", "fn", "grad_fn", "differentiable")

    def __init__(self, name: str, fn: Callable, differentiable: bool = True):
        self.name = name
        self.fn = fn
        self.grad_fn: Optional[Callable] = None
        self.differentiable = differentiable

    def __repr__(self):
        return f"OpDef({self.name})"


_REGISTRY: Dict[str, OpDef] = {}
_lock = threading.Lock()


def register_op(name: str, fn: Callable, differentiable: bool = True) -> OpDef:
    """Register a forward kernel (analog of PD_REGISTER_KERNEL)."""
    with _lock:
        if name in _REGISTRY:
            raise AlreadyExistsError(f"op '{name}' already registered")
        opdef = OpDef(name, fn, differentiable)
        _REGISTRY[name] = opdef
        return opdef


def register_grad(name: str, grad_fn: Callable) -> None:
    """Attach an explicit grad kernel to an op.

    Signature: grad_fn(in_values, out_values, out_grads, **attrs)
      -> tuple of grads aligned with the op's tensor inputs (None allowed).
    Ops without an explicit grad use the generic jax.vjp path.
    """
    get_op(name).grad_fn = grad_fn


def get_op(name: str) -> OpDef:
    opdef = _REGISTRY.get(name)
    if opdef is None:
        raise NotFoundError(f"op '{name}' not registered")
    return opdef


def is_tensor_like(x: Any) -> bool:
    return isinstance(x, (jax.Array, Tracer, np.ndarray, np.generic))


def _canon_static(v: Any) -> Any:
    if isinstance(v, list):
        return tuple(_canon_static(x) for x in v)
    if isinstance(v, dict):
        return tuple(sorted((k, _canon_static(x)) for k, x in v.items()))
    return v


def _type_sig(v: Any) -> Any:
    """Type signature of a static arg, part of the executable-cache key:
    the cache is an ``==``-keyed lru_cache and ``1 == 1.0 == True`` hash
    alike in Python, but the closed-over scalar's TYPE changes jnp
    promotion (x + 1 is int32, x + 1.0 float32) — so same-valued,
    differently-typed statics must not share an executable."""
    if isinstance(v, (list, tuple)):
        return ("seq",) + tuple(_type_sig(x) for x in v)
    if isinstance(v, dict):
        return ("map",) + tuple(sorted((k, _type_sig(x))
                                       for k, x in v.items()))
    return type(v).__name__


class OpCall:
    """A fully-bound op invocation: tensor slots split from static attrs.

    ``key`` uniquely identifies the flat callable, so jitted executables and
    vjp executables can be cached across calls (the reference's KernelFactory
    cache role).
    """

    __slots__ = ("opdef", "key", "flat_fn", "in_values")

    def __init__(self, opdef: OpDef, args: Sequence[Any], kwargs: Dict[str, Any]):
        self.opdef = opdef
        spec = []          # per positional slot: "T" or ("S", value)
        in_values = []
        for a in args:
            if is_tensor_like(a):
                spec.append("T")
                in_values.append(a)
            else:
                spec.append(("S", _canon_static(a), _type_sig(a)))
        kw_spec = []
        for k in sorted(kwargs):
            v = kwargs[k]
            if is_tensor_like(v):
                kw_spec.append((k, "T"))
                in_values.append(v)
            else:
                kw_spec.append((k, ("S", _canon_static(v), _type_sig(v))))
        self.key = (opdef.name, tuple(spec), tuple(kw_spec))
        self.flat_fn = _flat_fn_cache(self.key, opdef.fn)
        self.in_values = in_values


@functools.lru_cache(maxsize=16384)
def _flat_fn_cache(key: Tuple, fn: Callable) -> Callable:
    """Build fn(*tensor_values) reconstructing the original call."""
    _, spec, kw_spec = key

    def flat_fn(*tvals):
        it = iter(tvals)
        args = [next(it) if s == "T" else s[1] for s in spec]
        kwargs = {k: (next(it) if s == "T" else s[1]) for k, s in kw_spec}
        return fn(*args, **kwargs)

    return flat_fn


@functools.lru_cache(maxsize=16384)
def _jitted(key: Tuple, flat_fn: Callable) -> Callable:
    return jax.jit(flat_fn)


@functools.lru_cache(maxsize=16384)
def _jitted_vjp(key: Tuple, flat_fn: Callable) -> Callable:
    """Generic grad executable: (in_values, out_grads) -> input grads."""

    def vjp_flat(in_values, out_grads):
        _, vjp_fn = jax.vjp(lambda *a: flat_fn(*a), *in_values)
        return vjp_fn(out_grads)

    return jax.jit(vjp_flat)


def _check_finite(name: str, outs) -> None:
    for o in jax.tree_util.tree_leaves(outs):
        if jnp.issubdtype(o.dtype, jnp.floating) and not bool(jnp.all(jnp.isfinite(o))):
            raise FloatingPointError(f"NaN/Inf detected in output of op '{name}'")


def run_op(call: OpCall):
    """Execute the forward kernel, using the executable cache when eager.

    Under an outer trace (values are Tracers) the raw function is called so
    the op inlines into the enclosing XLA program.
    """
    tracing = any(isinstance(v, Tracer) for v in call.in_values)
    if tracing or not flags._get("eager_op_jit_cache", True):
        outs = call.flat_fn(*call.in_values)
    else:
        outs = _jitted(call.key, call.flat_fn)(*call.in_values)
        if flags._get("check_nan_inf", False):
            _check_finite(call.opdef.name, outs)
    return outs


def run_grad(call: OpCall, in_values, out_values, out_grads):
    """Execute the backward kernel for a recorded forward call.

    Uses the op's explicit grad kernel when registered, otherwise the
    generic jax.vjp path (jit-cached). The generic path runs the forward
    again for its residuals. Under a whole-graph trace XLA removes that
    replay where it is plain HLO: it merges with the original or is dead
    code. It does not merge two custom calls, so an op whose forward
    computes residuals in one (a Pallas kernel) registers a grad kernel
    and returns those residuals as outputs (ops/attention.py).
    """
    opdef = call.opdef
    if opdef.grad_fn is not None:
        _, spec, kw_spec = call.key
        attrs = {k: s[1] for k, s in kw_spec if s != "T"}
        grads = opdef.grad_fn(in_values, out_values, out_grads, **attrs)
        if not isinstance(grads, (tuple, list)):
            grads = (grads,)
        return tuple(grads)
    tracing = any(isinstance(v, Tracer) for v in in_values) or any(
        isinstance(v, Tracer) for v in jax.tree_util.tree_leaves(out_grads)
    )
    if tracing or not flags._get("eager_op_jit_cache", True):
        _, vjp_fn = jax.vjp(lambda *a: call.flat_fn(*a), *in_values)
        grads = vjp_fn(out_grads)
    else:
        grads = _jitted_vjp(call.key, call.flat_fn)(tuple(in_values), out_grads)
    # jax returns float0 cotangents for non-differentiable (int) inputs.
    return tuple(
        None if (g is None or g.dtype == jax.dtypes.float0) else g for g in grads
    )
