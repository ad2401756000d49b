"""Automatic mixed precision.

(reference: python/paddle/amp/auto_cast.py:856 auto_cast,
amp/grad_scaler.py:41,619 GradScaler; AMP insertion point in generated
eager code eager_gen.py:515. Here the insertion point is the dispatch
chokepoint core/dispatch.py::_amp_hook.)

TPU notes: bf16 is the native fast dtype (MXU) and needs NO loss scaling;
GradScaler keeps the fp16 semantics for API parity but becomes a no-op
pass-through when enable=False or dtype=bfloat16 with use_dynamic=False.
"""
from __future__ import annotations

import contextlib
from typing import Optional, Set

import jax
import numpy as np
import jax.numpy as jnp

from ..core import dispatch as _dispatch
from ..core.dtype import convert_dtype
from ..tensor import Tensor

__all__ = ["auto_cast", "amp_guard", "decorate", "GradScaler",
           "white_list", "black_list"]

# ops that benefit from low precision (MXU-bound)
WHITE_LIST: Set[str] = {
    "matmul", "linear", "conv2d", "conv1d", "conv2d_transpose", "bmm",
    "fused_gemm_epilogue", "einsum_op", "flash_attention",
    "flash_attention_pallas", "scaled_dot_product_attention", "addmm",
}
# ops that must stay fp32 (numerically sensitive)
BLACK_LIST: Set[str] = {
    "softmax_with_cross_entropy", "cross_entropy_loss", "log_softmax",
    "exp", "log", "logsumexp", "pow", "square", "sum", "mean",
    "layer_norm", "rms_norm", "batch_norm", "group_norm", "instance_norm",
    "norm", "cumsum",
}


def white_list():
    return set(WHITE_LIST)


def black_list():
    return set(BLACK_LIST)


class _AmpState:
    enabled = False
    dtype = jnp.bfloat16
    level = "O1"
    custom_white = set()
    custom_black = set()


_state = _AmpState()


def _amp_hook(op_name, conv_args, conv_kwargs):
    if not _state.enabled:
        return conv_args, conv_kwargs
    white = (WHITE_LIST | _state.custom_white) - _state.custom_black
    if op_name not in white:
        return conv_args, conv_kwargs
    target = _state.dtype

    def cast(v):
        if isinstance(v, (jax.Array, jax.core.Tracer)) and \
                v.dtype == jnp.float32:
            return v.astype(target)
        return v

    return [cast(a) for a in conv_args], {k: cast(v)
                                          for k, v in conv_kwargs.items()}


@contextlib.contextmanager
def auto_cast(enable: bool = True, custom_white_list=None,
              custom_black_list=None, level: str = "O1", dtype="bfloat16",
              use_promote: bool = True):
    prev = (_state.enabled, _state.dtype, _state.level,
            _state.custom_white, _state.custom_black)
    _state.enabled = enable
    _state.dtype = convert_dtype(dtype)
    _state.level = level
    _state.custom_white = set(custom_white_list or ())
    _state.custom_black = set(custom_black_list or ())
    _dispatch._amp_hook = _amp_hook if enable else None
    try:
        yield
    finally:
        (_state.enabled, _state.dtype, _state.level,
         _state.custom_white, _state.custom_black) = prev
        _dispatch._amp_hook = _amp_hook if _state.enabled else None


amp_guard = auto_cast


def decorate(models, optimizers=None, level: str = "O2", dtype="bfloat16",
             master_weight=None, save_dtype=None):
    """O2: cast model params to the low dtype (keeping master fp32 weights
    in the optimizer when multi_precision)."""
    single = not isinstance(models, (list, tuple))
    model_list = [models] if single else list(models)
    if level == "O2":
        for m in model_list:
            m.to(dtype=dtype)
        if optimizers is not None:
            opts = [optimizers] if not isinstance(optimizers, (list, tuple)) \
                else list(optimizers)
            for o in opts:
                o._multi_precision = True
    if optimizers is None:
        return models if single else model_list
    return (models if single else model_list), optimizers


class GradScaler:
    """(reference: python/paddle/amp/grad_scaler.py:619 — dynamic loss
    scaling with found_inf sync; hybrid-parallel variant syncs found_inf
    across groups.)"""

    def __init__(self, enable: bool = True, init_loss_scaling: float = 2.0**15,
                 incr_ratio: float = 2.0, decr_ratio: float = 0.5,
                 incr_every_n_steps: int = 1000,
                 decr_every_n_nan_or_inf: int = 2, use_dynamic_loss_scaling=True):
        self._enable = enable
        self._scale = float(init_loss_scaling)
        self._incr_ratio = incr_ratio
        self._decr_ratio = decr_ratio
        self._incr_every = incr_every_n_steps
        self._decr_every = decr_every_n_nan_or_inf
        self._dynamic = use_dynamic_loss_scaling
        self._good_steps = 0
        self._bad_steps = 0
        self._found_inf = False
        # device-resident state when driven by ParallelEngine (traced
        # protocol): (scale f32, good i32, bad i32, applied-step i32)
        self._dev = None
        self._dev_global = False  # True once _dev is a committed global
        self._found_inf_dev = None
        self._applied_steps = 0

    def _to_eager(self):
        """Hand device-resident scaler state back to the eager protocol:
        sync the host mirrors, then drop the device copy so subsequent
        engine steps reseed from the (possibly eager-updated) host
        values instead of clobbering them with stale device state."""
        self._sync_from_dev()
        self._dev = None
        self._found_inf_dev = None

    def scale(self, var: Tensor) -> Tensor:
        if not self._enable:
            return var
        self._to_eager()
        from ..ops import math as M

        return M.scale(var, scale=self._scale)

    def unscale_(self, optimizer):
        if not self._enable:
            return
        self._to_eager()
        inv = 1.0 / self._scale
        found = False
        for p in (optimizer._parameter_list or []):
            if p is not None and p.grad is not None:
                g = p.grad._value * inv
                p.grad._value = g
        self._found_inf = self._check_found_inf(optimizer)

    def _check_found_inf(self, optimizer) -> bool:
        # all-finite test (not abs-sum: summing many f16 grads can
        # overflow on its own). Eager-only — inside a compiled step the
        # engine runs the traced protocol below instead.
        finite = True
        for p in (optimizer._parameter_list or []):
            if p is not None and p.grad is not None:
                finite = finite & jnp.all(jnp.isfinite(
                    p.grad._value.astype(jnp.float32)))
        return not bool(finite)

    # -- traced protocol (ParallelEngine.train_step(scaler=...)) ---------
    def _traced_state(self, fallback_step: int = 0):
        """Scaler state as device scalars, carried through the compiled
        step (reference: hybrid_parallel_gradscaler.py keeps these as
        host floats and syncs found_inf with a blocking allreduce; here
        the whole protocol stays on device — no host round-trip).

        ``fallback_step`` seeds the applied-step counter (used for Adam
        bias correction) when no checkpointed value exists — the engine
        passes the optimizer's step count so a resumed run does not
        restart bias correction at t=1."""
        if self._dev is None:
            self._dev = (jnp.float32(self._scale),
                         jnp.int32(self._good_steps),
                         jnp.int32(self._bad_steps),
                         jnp.int32(self._applied_steps or fallback_step))
            self._dev_global = False
        return self._dev

    def _store_traced(self, out):
        self._dev = tuple(out[:4])
        self._dev_global = True  # jit outputs are committed global arrays
        self._found_inf_dev = out[4]

    @property
    def last_found_inf(self):
        """Whether the most recent engine step hit inf/nan (host sync)."""
        if self._found_inf_dev is not None:
            return bool(self._found_inf_dev > 0)
        return self._found_inf

    def step(self, optimizer):
        if not self._enable:
            optimizer.step()
            return
        self.unscale_(optimizer)
        if not self._found_inf:
            optimizer.step()
        self.update()

    def minimize(self, optimizer, scaled_loss):
        self.step(optimizer)
        optimizer.clear_grad()

    def update(self):
        if not (self._enable and self._dynamic):
            return
        if self._found_inf:
            self._bad_steps += 1
            self._good_steps = 0
            if self._bad_steps >= self._decr_every:
                self._scale = max(self._scale * self._decr_ratio, 1.0)
                self._bad_steps = 0
        else:
            self._good_steps += 1
            self._bad_steps = 0
            if self._good_steps >= self._incr_every:
                self._scale *= self._incr_ratio
                self._good_steps = 0
        self._found_inf = False

    def is_enable(self) -> bool:
        return self._enable

    def is_use_dynamic_loss_scaling(self) -> bool:
        return self._dynamic

    def _sync_from_dev(self):
        if self._dev is not None:
            self._scale = float(self._dev[0])
            self._good_steps = int(self._dev[1])
            self._bad_steps = int(self._dev[2])
            self._applied_steps = int(self._dev[3])

    def get_loss_scaling(self) -> float:
        self._sync_from_dev()
        return self._scale

    def set_init_loss_scaling(self, v: float):
        self._sync_from_dev()  # keep counters; only the scale resets
        self._scale = float(v)
        self._dev = None

    def state_dict(self):
        self._sync_from_dev()
        return {"scale": self._scale, "incr_ratio": self._incr_ratio,
                "decr_ratio": self._decr_ratio,
                "good_steps": self._good_steps, "bad_steps": self._bad_steps,
                "applied_steps": self._applied_steps}

    def load_state_dict(self, state):
        self._scale = state.get("scale", self._scale)
        self._good_steps = state.get("good_steps", 0)
        self._bad_steps = state.get("bad_steps", 0)
        self._applied_steps = state.get("applied_steps", 0)
        self._dev = None


from . import debugging  # noqa: E402,F401


def is_bfloat16_supported(device=None):
    """bf16 is the native TPU matmul dtype (always true here)."""
    return True


def is_float16_supported(device=None):
    """fp16 compute is emulated on TPU; XLA supports the dtype."""
    return True
