"""Optimizers (reference: python/paddle/optimizer/*; fused kernels
phi/kernels/gpu/fused_adam_kernel.cu, adamw_kernel.cu, multi-tensor path
python/paddle/optimizer/adam.py:224-229).

TPU design: each optimizer's update rule is a pure function over the
pytree of (params, grads, states); ``step()`` runs ONE jitted multi-tensor
update for all parameters — the analog of the reference's FusedAdam — and
the whole thing inlines into a traced train step under jit.to_static.
"""
from __future__ import annotations

import functools
from typing import Dict, List, Optional

import jax
import numpy as np
import jax.numpy as jnp

from ..autograd import no_grad
from ..nn.clip import ClipGradBase, ClipGradByGlobalNorm
from ..tensor import Parameter, Tensor
from . import lr as lr_sched
from .lr import LRScheduler

__all__ = ["Optimizer", "SGD", "Momentum", "LarsMomentum", "Adam", "AdamW", "Adagrad",
           "Adadelta", "Adamax", "ASGD", "Rprop",
           "RMSProp", "Lamb", "lr"]

lr = lr_sched


class Optimizer:
    """Base optimizer with fused pytree updates."""

    def __init__(self, learning_rate=0.001, parameters=None, weight_decay=None,
                 grad_clip: Optional[ClipGradBase] = None, name=None,
                 multi_precision: bool = False, state_dtype=None):
        self._lr = learning_rate
        self._parameter_list = list(parameters) if parameters is not None else None
        self._grad_clip = grad_clip
        if isinstance(weight_decay, float) or isinstance(weight_decay, int):
            self._weight_decay = float(weight_decay)
            self._wd_mode = "l2"
        elif weight_decay is None:
            self._weight_decay = 0.0
            self._wd_mode = "l2"
        else:  # L1Decay/L2Decay-like object with a coeff (+ optional mode)
            self._weight_decay = float(getattr(weight_decay, "_coeff",
                                               getattr(weight_decay, "coeff", 0.0)))
            self._wd_mode = getattr(weight_decay, "mode", "l2")
        self._multi_precision = multi_precision
        # dtype of per-param moment buffers. f32 default (the reference's
        # AdamW); bf16 halves optimizer-state HBM on memory-bound chips
        # (the update math still runs in f32 — states are cast in/out).
        self._state_dtype = (jnp.dtype(state_dtype) if state_dtype
                             else jnp.float32)
        self._states: Dict[int, Dict[str, jnp.ndarray]] = {}
        self._step_count = 0
        self._jitted = None
        self._master_weights: Dict[int, jnp.ndarray] = {}

    def _decay_term(self, pf):
        """Weight-decay gradient term: wd*p for L2Decay, wd*sign(p) (the
        L1 subgradient) for L1Decay (reference: python/paddle/
        regularizer.py applied by the append_regularization_ops path)."""
        if self._wd_mode == "l1":
            return self._weight_decay * jnp.sign(pf)
        return self._weight_decay * pf

    # -- lr handling ---------------------------------------------------
    def get_lr(self) -> float:
        if isinstance(self._lr, LRScheduler):
            return self._lr()
        return float(self._lr)

    def set_lr(self, value: float):
        if isinstance(self._lr, LRScheduler):
            raise RuntimeError("cannot set_lr when using an LRScheduler")
        self._lr = float(value)

    def set_lr_scheduler(self, scheduler: LRScheduler):
        self._lr = scheduler

    # -- state ---------------------------------------------------------
    def _param_state(self, p: Parameter, shapes: Dict[str, tuple]):
        st = self._states.get(id(p))
        if st is None:
            st = {k: jnp.zeros(s if s is not None else p._value.shape,
                               self._state_dtype)
                  for k, s in shapes.items()}
            if self._multi_precision and p._value.dtype != jnp.float32:
                self._master_weights[id(p)] = p._value.astype(jnp.float32)
            self._states[id(p)] = st
        return st

    def _state_shapes(self) -> Dict[str, tuple]:
        """Per-param state slots: name -> shape (None = same as param)."""
        return {}

    def _update_rule(self, p, g, state, lr_value, step):
        """Pure: returns (new_p, new_state_dict)."""
        raise NotImplementedError

    # -- the fused step -------------------------------------------------
    def _collect(self):
        params = [p for p in self._parameter_list
                  if p is not None and p.grad is not None and p.trainable]
        return params

    # -- sparse (SelectedRows) gradients --------------------------------
    def _sparse_update(self, p, pf, sr, state, lr_value, step):
        """Apply a merged SelectedRows grad. Default: densify (exact,
        same numerics as a dense grad); SGD/Adam override with row-wise
        scatter updates (reference: the optimizers'
        *DenseParamSparseGradKernel family)."""
        return self._update_rule(pf, sr.to_dense_value(), state,
                                 lr_value, step)

    def _apply_sparse(self, p, sr, lr_value, step_value, shapes):
        state = self._param_state(p, shapes)
        pf = self._master_weights.get(id(p), p._value)
        new_p, new_s = self._sparse_update(p, pf, sr,
                                           self._cast_state_in(state),
                                           lr_value, step_value)
        if id(p) in self._master_weights:
            self._master_weights[id(p)] = new_p
            p._value = new_p.astype(p._value.dtype)
        else:
            p._value = new_p
        self._states[id(p)] = self._cast_state_out(new_s)

    @no_grad()
    def step(self):
        from ..framework.selected_rows import (SelectedRows,
                                               merge_selected_rows)

        all_params = self._collect()
        if not all_params:
            return
        self._step_count += 1
        sparse = [p for p in all_params
                  if isinstance(p.grad, SelectedRows)]
        params = [p for p in all_params
                  if not isinstance(p.grad, SelectedRows)]
        extra_sq = None
        if sparse:
            shapes = self._state_shapes()
            lr_v = jnp.asarray(self.get_lr(), jnp.float32)
            st_v = jnp.asarray(self._step_count, jnp.int32)
            merged = [merge_selected_rows(p.grad) for p in sparse]
            if isinstance(self._grad_clip, ClipGradByGlobalNorm):
                # reference semantics (ClipGradByGlobalNorm): merged
                # SelectedRows grads join the global norm, and their
                # values scale by the same coefficient as the dense
                # grads (whose jitted clip sees the sparse sum via
                # extra_sq)
                sparse_sq = sum(
                    jnp.sum(jnp.square(sr.values.astype(jnp.float32)))
                    for sr in merged)
                dense_sq = sum(
                    jnp.sum(jnp.square(p.grad._value.astype(jnp.float32)))
                    for p in params)
                coef = self._grad_clip.coefficient(
                    jnp.sqrt(sparse_sq + dense_sq))
                from ..framework.selected_rows import SelectedRows as _SR

                merged = [_SR(sr.rows,
                              (sr.values * coef).astype(sr.values.dtype),
                              sr.height)
                          for sr in merged]
                extra_sq = sparse_sq
            for p, sr in zip(sparse, merged):
                self._apply_sparse(p, sr, lr_v, st_v, shapes)
        if not params:
            return
        shapes = self._state_shapes()
        states = [self._param_state(p, shapes) for p in params]
        pvals = [self._master_weights.get(id(p), p._value) for p in params]
        gvals = [p.grad._value for p in params]
        lr_value = jnp.asarray(self.get_lr(), jnp.float32)
        step_value = jnp.asarray(self._step_count, jnp.int32)

        new_pvals, new_states = self._fused_update(
            tuple(pvals), tuple(gvals), tuple(states), lr_value, step_value,
            extra_sq)

        for p, nv, ns in zip(params, new_pvals, new_states):
            if id(p) in self._master_weights:
                self._master_weights[id(p)] = nv
                p._value = nv.astype(p._value.dtype)
            else:
                p._value = nv
            self._states[id(p)] = ns

    def _cast_state_in(self, s):
        """Moment buffers may be stored low-precision (state_dtype); the
        update math always runs f32."""
        if self._state_dtype == jnp.float32:
            return s
        return {k: v.astype(jnp.float32)
                if jnp.issubdtype(v.dtype, jnp.floating) else v
                for k, v in s.items()}

    def _cast_state_out(self, s):
        if self._state_dtype == jnp.float32:
            return s
        return {k: v.astype(self._state_dtype)
                if jnp.issubdtype(v.dtype, jnp.floating) else v
                for k, v in s.items()}

    def _fused_update(self, pvals, gvals, states, lr_value, step_value,
                      extra_sq=None):
        # One jitted executable updating every parameter (multi-tensor
        # fused path — FusedAdam analog). jax.jit caches on pytree
        # structure + shapes. extra_sq: squared norm of the merged
        # sparse grads, folded into the global-norm clip so dense and
        # sparse sides scale by the same coefficient.
        if extra_sq is None:
            extra_sq = jnp.asarray(0.0, jnp.float32)

        def _clipped(gvals, extra_sq):
            clip = self._grad_clip
            if clip is None:
                return gvals
            if isinstance(clip, ClipGradByGlobalNorm):
                return clip.apply_values(list(gvals), extra_sq)[0]
            return clip.apply_values(list(gvals))[0]

        if self._jitted is None:

            def update_all(pvals, gvals, states, lr_value, step_value,
                           extra_sq):
                gvals = _clipped(gvals, extra_sq)
                out_p, out_s = [], []
                for p, g, s in zip(pvals, gvals, states):
                    np_, ns_ = self._update_rule(
                        p, g, self._cast_state_in(s), lr_value, step_value)
                    out_p.append(np_)
                    out_s.append(self._cast_state_out(ns_))
                return tuple(out_p), tuple(out_s)

            self._jitted = jax.jit(update_all)
        if any(isinstance(v, jax.core.Tracer) for v in pvals) or any(
                isinstance(v, jax.core.Tracer) for v in gvals):
            # already inside an enclosing trace (to_static train step)
            gvals = _clipped(gvals, extra_sq)
            out = [(lambda np_, ns_: (np_, self._cast_state_out(ns_)))(
                *self._update_rule(p, g, self._cast_state_in(s), lr_value,
                                   step_value))
                   for p, g, s in zip(pvals, gvals, states)]
            return tuple(o[0] for o in out), tuple(o[1] for o in out)
        return self._jitted(pvals, gvals, states, lr_value, step_value,
                            extra_sq)

    def clear_grad(self, set_to_zero: bool = False):
        if self._parameter_list:
            for p in self._parameter_list:
                if p is not None:
                    p.clear_grad()

    clear_gradients = clear_grad

    def minimize(self, loss, startup_program=None, parameters=None,
                 no_grad_set=None):
        """(reference: python/paddle/optimizer/optimizer.py minimize).
        On a static Variable, records the train objective into its
        Program — Executor.run then performs backward + the fused step;
        on an eager Tensor, runs backward/step/clear now."""
        if getattr(loss, "_is_static_var", False):
            loss._program._train_objective = (loss, self)
            return None, None
        loss.backward()
        self.step()
        self.clear_grad()
        return None, None

    # -- checkpointing ---------------------------------------------------
    def state_dict(self) -> Dict:
        out = {"step_count": self._step_count}
        if self._parameter_list:
            for i, p in enumerate(self._parameter_list):
                st = self._states.get(id(p))
                if st is not None:
                    key = p.name or f"param_{i}"
                    for k, v in st.items():
                        out[f"{key}.{k}"] = Tensor(v)
                    if id(p) in self._master_weights:
                        out[f"{key}.master_weight"] = Tensor(
                            self._master_weights[id(p)])
        if isinstance(self._lr, LRScheduler):
            out["LR_Scheduler"] = self._lr.state_dict()
        return out

    def set_state_dict(self, state: Dict):
        self._step_count = int(state.get("step_count", 0))
        if "LR_Scheduler" in state and isinstance(self._lr, LRScheduler):
            self._lr.set_state_dict(state["LR_Scheduler"])
        if self._parameter_list:
            shapes = self._state_shapes()
            for i, p in enumerate(self._parameter_list):
                key = p.name or f"param_{i}"
                st = {}
                for k in shapes:
                    sk = f"{key}.{k}"
                    if sk in state:
                        v = state[sk]
                        st[k] = v._value if isinstance(v, Tensor) else jnp.asarray(v)
                if st:
                    # preserve loaded master weights stored alongside
                    self._states[id(p)] = st
                mk = f"{key}.master_weight"
                if mk in state:
                    v = state[mk]
                    self._master_weights[id(p)] = (
                        v._value if isinstance(v, Tensor) else jnp.asarray(v)).astype(jnp.float32)


class SGD(Optimizer):
    def __init__(self, learning_rate=0.001, parameters=None, weight_decay=None,
                 grad_clip=None, name=None, **kwargs):
        super().__init__(learning_rate, parameters, weight_decay, grad_clip, name)

    def _update_rule(self, p, g, state, lr_value, step):
        g = g.astype(jnp.float32)
        if self._weight_decay:
            g = g + self._decay_term(p.astype(jnp.float32))
        return (p - (lr_value * g).astype(p.dtype)), state

    def _sparse_update(self, p, pf, sr, state, lr_value, step):
        """Row-wise SGD: touch only the looked-up rows (weight decay,
        when set, applies to those rows)."""
        rows = sr.rows
        g = sr.values.astype(jnp.float32)
        if self._weight_decay:
            g = g + self._decay_term(pf[rows].astype(jnp.float32))
        upd = (lr_value * g).astype(pf.dtype)
        return pf.at[rows].add(-upd), state


class Momentum(Optimizer):
    def __init__(self, learning_rate=0.001, momentum=0.9, parameters=None,
                 use_nesterov=False, weight_decay=None, grad_clip=None,
                 multi_precision=False, name=None, **kwargs):
        super().__init__(learning_rate, parameters, weight_decay, grad_clip,
                         name, multi_precision)
        self._momentum = momentum
        self._nesterov = use_nesterov

    def _state_shapes(self):
        return {"velocity": None}

    def _update_rule(self, p, g, state, lr_value, step):
        g = g.astype(jnp.float32)
        if self._weight_decay:
            g = g + self._decay_term(p.astype(jnp.float32))
        v = self._momentum * state["velocity"] + g
        if self._nesterov:
            upd = g + self._momentum * v
        else:
            upd = v
        return (p - (lr_value * upd).astype(p.dtype)), {"velocity": v}


class Adam(Optimizer):
    """(reference: python/paddle/optimizer/adam.py:38 → _C_ops.adam_ fused
    kernel at adam.py:331; here the fused kernel is the jitted pytree
    update in Optimizer._fused_update.)"""

    def __init__(self, learning_rate=0.001, beta1=0.9, beta2=0.999,
                 epsilon=1e-8, parameters=None, weight_decay=None,
                 grad_clip=None, lazy_mode=False, multi_precision=False,
                 state_dtype=None,
                 use_multi_tensor=True, name=None, **kwargs):
        super().__init__(learning_rate, parameters, weight_decay, grad_clip,
                         name, multi_precision, state_dtype)
        self._beta1 = beta1
        self._beta2 = beta2
        self._epsilon = epsilon
        self._decoupled = False
        self._lazy_mode = bool(lazy_mode)

    def _state_shapes(self):
        return {"moment1": None, "moment2": None}

    def _sparse_update(self, p, pf, sr, state, lr_value, step):
        """SelectedRows grad (reference: AdamDenseParamSparseGradKernel).
        lazy_mode=True updates moments/param ONLY at the touched rows
        (the reference's lazy path, exact for row-disjoint steps);
        lazy_mode=False keeps exact dense semantics by densifying."""
        if not self._lazy_mode:
            return super()._sparse_update(p, pf, sr, state, lr_value,
                                          step)
        rows = sr.rows
        g = sr.values.astype(jnp.float32)
        pf32 = pf.astype(jnp.float32)
        if self._weight_decay and not self._decoupled:
            g = g + self._decay_term(pf32[rows])
        m_r = self._beta1 * state["moment1"][rows] + (1 - self._beta1) * g
        v_r = self._beta2 * state["moment2"][rows] \
            + (1 - self._beta2) * jnp.square(g)
        t = step.astype(jnp.float32)
        mhat = m_r / (1 - self._beta1 ** t)
        vhat = v_r / (1 - self._beta2 ** t)
        upd = mhat / (jnp.sqrt(vhat) + self._epsilon)
        if self._weight_decay and self._decoupled:
            upd = upd + self._decay_term(pf32[rows])
        new_p = pf.at[rows].add((-lr_value * upd).astype(pf.dtype))
        new_s = {"moment1": state["moment1"].at[rows].set(m_r),
                 "moment2": state["moment2"].at[rows].set(v_r)}
        return new_p, new_s

    def _update_rule(self, p, g, state, lr_value, step):
        pf = p.astype(jnp.float32)
        g = g.astype(jnp.float32)
        if self._weight_decay and not self._decoupled:
            g = g + self._decay_term(pf)
        m = self._beta1 * state["moment1"] + (1 - self._beta1) * g
        v = self._beta2 * state["moment2"] + (1 - self._beta2) * jnp.square(g)
        t = step.astype(jnp.float32)
        mhat = m / (1 - self._beta1 ** t)
        vhat = v / (1 - self._beta2 ** t)
        upd = mhat / (jnp.sqrt(vhat) + self._epsilon)
        if self._weight_decay and self._decoupled:
            upd = upd + self._decay_term(pf)
        new_p = pf - lr_value * upd
        return new_p.astype(p.dtype), {"moment1": m, "moment2": v}


class AdamW(Adam):
    """Decoupled weight decay (reference: python/paddle/optimizer/adamw.py)."""

    def __init__(self, learning_rate=0.001, beta1=0.9, beta2=0.999,
                 epsilon=1e-8, parameters=None, weight_decay=0.01,
                 lr_ratio=None, apply_decay_param_fun=None, grad_clip=None,
                 lazy_mode=False, multi_precision=False, name=None,
                 state_dtype=None, **kwargs):
        super().__init__(learning_rate, beta1, beta2, epsilon, parameters,
                         weight_decay, grad_clip, lazy_mode, multi_precision,
                         name=name, state_dtype=state_dtype)
        self._decoupled = True
        self._apply_decay_param_fun = apply_decay_param_fun
        # NOTE: apply_decay_param_fun is honored in step() by zeroing decay
        # for excluded params via per-param decay masks.
        self._decay_mask = None

    @no_grad()
    def step(self):
        if self._apply_decay_param_fun is not None and self._decay_mask is None:
            self._decay_mask = {
                id(p): bool(self._apply_decay_param_fun(p.name))
                for p in (self._parameter_list or [])}
        super().step()

    def _update_rule(self, p, g, state, lr_value, step):
        pf = p.astype(jnp.float32)
        g = g.astype(jnp.float32)
        m = self._beta1 * state["moment1"] + (1 - self._beta1) * g
        v = self._beta2 * state["moment2"] + (1 - self._beta2) * jnp.square(g)
        t = step.astype(jnp.float32)
        mhat = m / (1 - self._beta1 ** t)
        vhat = v / (1 - self._beta2 ** t)
        upd = mhat / (jnp.sqrt(vhat) + self._epsilon)
        new_p = pf - lr_value * (upd + self._decay_term(pf))
        return new_p.astype(p.dtype), {"moment1": m, "moment2": v}


class Adagrad(Optimizer):
    def __init__(self, learning_rate=0.001, epsilon=1e-6, parameters=None,
                 weight_decay=None, grad_clip=None, name=None,
                 initial_accumulator_value=0.0, **kwargs):
        super().__init__(learning_rate, parameters, weight_decay, grad_clip, name)
        self._epsilon = epsilon
        self._init_acc = initial_accumulator_value

    def _state_shapes(self):
        return {"moment": None}

    def _param_state(self, p, shapes):
        st = self._states.get(id(p))
        if st is None:
            st = {"moment": jnp.full(p._value.shape, self._init_acc, jnp.float32)}
            self._states[id(p)] = st
        return st

    def _update_rule(self, p, g, state, lr_value, step):
        g = g.astype(jnp.float32)
        if self._weight_decay:
            g = g + self._decay_term(p.astype(jnp.float32))
        acc = state["moment"] + jnp.square(g)
        new_p = p.astype(jnp.float32) - lr_value * g / (jnp.sqrt(acc) + self._epsilon)
        return new_p.astype(p.dtype), {"moment": acc}


class RMSProp(Optimizer):
    def __init__(self, learning_rate=0.001, rho=0.95, epsilon=1e-6,
                 momentum=0.0, centered=False, parameters=None,
                 weight_decay=None, grad_clip=None, name=None, **kwargs):
        super().__init__(learning_rate, parameters, weight_decay, grad_clip, name)
        self._rho = rho
        self._epsilon = epsilon
        self._momentum = momentum
        self._centered = centered

    def _state_shapes(self):
        return {"mean_square": None, "mean_grad": None, "momentum": None}

    def _update_rule(self, p, g, state, lr_value, step):
        g = g.astype(jnp.float32)
        if self._weight_decay:
            g = g + self._decay_term(p.astype(jnp.float32))
        ms = self._rho * state["mean_square"] + (1 - self._rho) * jnp.square(g)
        if self._centered:
            mg = self._rho * state["mean_grad"] + (1 - self._rho) * g
            denom = jnp.sqrt(ms - jnp.square(mg) + self._epsilon)
        else:
            mg = state["mean_grad"]
            denom = jnp.sqrt(ms + self._epsilon)
        mom = self._momentum * state["momentum"] + lr_value * g / denom
        new_p = p.astype(jnp.float32) - mom
        return new_p.astype(p.dtype), {"mean_square": ms, "mean_grad": mg,
                                       "momentum": mom}


class Adadelta(Optimizer):
    """(reference: python/paddle/optimizer/adadelta.py over the phi
    adadelta kernel — accumulated-gradient/accumulated-update rule.)"""

    def __init__(self, learning_rate=0.001, epsilon=1e-6, rho=0.95,
                 parameters=None, weight_decay=None, grad_clip=None,
                 name=None, **kwargs):
        super().__init__(learning_rate, parameters, weight_decay,
                         grad_clip, name)
        self._epsilon = epsilon
        self._rho = rho

    def _state_shapes(self):
        return {"avg_squared_grad": None, "avg_squared_update": None}

    def _update_rule(self, p, g, state, lr_value, step):
        g = g.astype(jnp.float32)
        if self._weight_decay:
            g = g + self._decay_term(p.astype(jnp.float32))
        asg = self._rho * state["avg_squared_grad"] \
            + (1 - self._rho) * jnp.square(g)
        upd = g * jnp.sqrt(
            (state["avg_squared_update"] + self._epsilon)
            / (asg + self._epsilon))
        asu = self._rho * state["avg_squared_update"] \
            + (1 - self._rho) * jnp.square(upd)
        new_p = p.astype(jnp.float32) - lr_value * upd
        return new_p.astype(p.dtype), {"avg_squared_grad": asg,
                                       "avg_squared_update": asu}


class Adamax(Optimizer):
    """(reference: python/paddle/optimizer/adamax.py — infinity-norm
    Adam variant.)"""

    def __init__(self, learning_rate=0.001, beta1=0.9, beta2=0.999,
                 epsilon=1e-8, parameters=None, weight_decay=None,
                 grad_clip=None, name=None, **kwargs):
        super().__init__(learning_rate, parameters, weight_decay,
                         grad_clip, name)
        self._beta1 = beta1
        self._beta2 = beta2
        self._epsilon = epsilon

    def _state_shapes(self):
        return {"moment": None, "inf_norm": None}

    def _update_rule(self, p, g, state, lr_value, step):
        g = g.astype(jnp.float32)
        if self._weight_decay:
            g = g + self._decay_term(p.astype(jnp.float32))
        m = self._beta1 * state["moment"] + (1 - self._beta1) * g
        u = jnp.maximum(self._beta2 * state["inf_norm"], jnp.abs(g))
        t = step.astype(jnp.float32)
        lr_t = lr_value / (1 - self._beta1 ** t)
        new_p = p.astype(jnp.float32) - lr_t * m / (u + self._epsilon)
        return new_p.astype(p.dtype), {"moment": m, "inf_norm": u}


class ASGD(Optimizer):
    """(reference: python/paddle/optimizer/asgd.py over the phi asgd
    kernel — averaged SGD: keeps a running window-mean of the last N
    gradients; here the mean is the standard exponential form d/N.)"""

    def __init__(self, learning_rate=0.001, batch_num=1, parameters=None,
                 weight_decay=None, grad_clip=None, multi_precision=False,
                 name=None, **kwargs):
        super().__init__(learning_rate, parameters, weight_decay,
                         grad_clip, name, multi_precision)
        self._n = max(int(batch_num), 1)

    def _state_shapes(self):
        return {}  # shapes built directly in _param_state (hist is 3-D)

    def _param_state(self, p, shapes):
        st = self._states.get(id(p))
        if st is None:
            st = {"d": jnp.zeros(p._value.shape, jnp.float32),
                  "hist": jnp.zeros((self._n,) + tuple(p._value.shape),
                                    jnp.float32)}
            if self._multi_precision and p._value.dtype != jnp.float32:
                self._master_weights[id(p)] = p._value.astype(jnp.float32)
            self._states[id(p)] = st
        return st

    def _update_rule(self, p, g, state, lr_value, step):
        g = g.astype(jnp.float32)
        if self._weight_decay:
            g = g + self._decay_term(p.astype(jnp.float32))
        # d holds the sum of the last n gradients: rotate out the
        # oldest history slot, rotate in g (the reference's d/y buffers)
        idx = (step.astype(jnp.int32) - 1) % self._n
        oldest = state["hist"][idx]
        d = state["d"] - oldest + g
        hist = state["hist"].at[idx].set(g)
        new_p = p.astype(jnp.float32) - lr_value * d / self._n
        return new_p.astype(p.dtype), {"d": d, "hist": hist}


class Rprop(Optimizer):
    """(reference: python/paddle/optimizer/rprop.py — resilient
    backprop: per-weight step sizes grown/shrunk by gradient sign
    agreement; gradients' magnitudes are ignored.)"""

    def __init__(self, learning_rate=0.001, learning_rate_range=(1e-5, 50.0),
                 parameters=None, etas=(0.5, 1.2), grad_clip=None,
                 multi_precision=False, name=None, **kwargs):
        super().__init__(learning_rate, parameters, None, grad_clip,
                         name, multi_precision)
        self._lr_min, self._lr_max = learning_rate_range
        self._eta_neg, self._eta_pos = etas

    def _state_shapes(self):
        return {"prev_grad": None, "lr_w": None}

    def _param_state(self, p, shapes):
        st = self._states.get(id(p))
        if st is None:
            st = {"prev_grad": jnp.zeros(p._value.shape, jnp.float32),
                  "lr_w": jnp.full(p._value.shape, float(self.get_lr()),
                                   jnp.float32)}
            if self._multi_precision and p._value.dtype != jnp.float32:
                self._master_weights[id(p)] = p._value.astype(jnp.float32)
            self._states[id(p)] = st
        return st

    def _update_rule(self, p, g, state, lr_value, step):
        g = g.astype(jnp.float32)
        sign = jnp.sign(g * state["prev_grad"])
        lr_w = jnp.clip(
            jnp.where(sign > 0, state["lr_w"] * self._eta_pos,
                      jnp.where(sign < 0, state["lr_w"] * self._eta_neg,
                                state["lr_w"])),
            self._lr_min, self._lr_max)
        # sign-disagreement steps are skipped (grad treated as 0)
        g_eff = jnp.where(sign < 0, 0.0, g)
        new_p = p.astype(jnp.float32) - lr_w * jnp.sign(g_eff)
        return new_p.astype(p.dtype), {"prev_grad": g_eff, "lr_w": lr_w}


class Lamb(Optimizer):
    """(reference: python/paddle/optimizer/lamb.py + DistributedFusedLamb
    fusion kernels — layerwise-adaptive large-batch optimizer.)"""

    def __init__(self, learning_rate=0.001, lamb_weight_decay=0.01, beta1=0.9,
                 beta2=0.999, epsilon=1e-6, parameters=None, grad_clip=None,
                 exclude_from_weight_decay_fn=None, multi_precision=False,
                 name=None, **kwargs):
        super().__init__(learning_rate, parameters, lamb_weight_decay,
                         grad_clip, name, multi_precision)
        self._beta1 = beta1
        self._beta2 = beta2
        self._epsilon = epsilon
        self._exclude_fn = exclude_from_weight_decay_fn

    def _state_shapes(self):
        return {"moment1": None, "moment2": None}

    def _update_rule(self, p, g, state, lr_value, step):
        pf = p.astype(jnp.float32)
        g = g.astype(jnp.float32)
        m = self._beta1 * state["moment1"] + (1 - self._beta1) * g
        v = self._beta2 * state["moment2"] + (1 - self._beta2) * jnp.square(g)
        t = step.astype(jnp.float32)
        mhat = m / (1 - self._beta1 ** t)
        vhat = v / (1 - self._beta2 ** t)
        r = mhat / (jnp.sqrt(vhat) + self._epsilon) + self._decay_term(pf)
        w_norm = jnp.linalg.norm(pf)
        r_norm = jnp.linalg.norm(r)
        trust = jnp.where((w_norm > 0) & (r_norm > 0), w_norm / r_norm, 1.0)
        new_p = pf - lr_value * trust * r
        return new_p.astype(p.dtype), {"moment1": m, "moment2": v}


class LarsMomentum(Momentum):
    """LARS: layer-wise adaptive rate scaling over momentum
    (reference: fleet/meta_optimizers/lars_optimizer.py over the phi
    lars_momentum kernel — local_lr = lr * coeff * ||w|| /
    (||g|| + wd * ||w|| + eps), the large-batch training rule)."""

    def __init__(self, learning_rate=0.001, momentum=0.9, parameters=None,
                 lars_coeff=0.001, lars_weight_decay=0.0005,
                 epsilon=1e-8, exclude_from_weight_decay=None,
                 grad_clip=None, multi_precision=False, name=None,
                 **kwargs):
        super().__init__(learning_rate, momentum, parameters,
                         weight_decay=None, grad_clip=grad_clip,
                         multi_precision=multi_precision, name=name,
                         **kwargs)
        self._lars_coeff = float(lars_coeff)
        self._lars_wd = float(lars_weight_decay)
        self._eps = float(epsilon)
        self._exclude = list(exclude_from_weight_decay or [])

    def _param_state(self, p, shapes):
        st = super()._param_state(p, shapes)
        if "lars_skip" not in st:
            # per-param exclusion travels IN the state so the fused
            # positional update stays identity-free (name matching like
            # the reference's exclude_from_weight_decay)
            name = p.name or ""
            skip = any(tok in name for tok in self._exclude)
            st["lars_skip"] = jnp.float32(1.0 if skip else 0.0)
        return st

    def _update_rule(self, p, g, state, lr_value, step):
        g = g.astype(jnp.float32)
        pf = p.astype(jnp.float32)
        skip = state.get("lars_skip", jnp.float32(0.0)) > 0
        w_norm = jnp.sqrt(jnp.sum(pf * pf))
        g_norm = jnp.sqrt(jnp.sum(g * g))
        local = jnp.where(
            (~skip) & (w_norm > 0) & (g_norm > 0),
            self._lars_coeff * w_norm
            / (g_norm + self._lars_wd * w_norm + self._eps),
            jnp.float32(1.0))
        g = g + jnp.where(skip, 0.0, self._lars_wd) * pf
        v = self._momentum * state["velocity"] + lr_value * local * g
        new_state = {"velocity": v}
        if "lars_skip" in state:
            new_state["lars_skip"] = state["lars_skip"]
        return (p - v.astype(p.dtype)), new_state
