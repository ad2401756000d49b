"""Pallas TPU fused RMSNorm.

(reference: phi/kernels/gpu/rms_norm_kernel.cu + rms_norm_funcs.h —
warp-reduce CUDA kernel; SPMD rule infermeta/spmd_rules/rms_norm.cc.)

One VMEM pass: f32 mean-of-squares per row, rsqrt, scale — rows tiled
(block_t, H) so the reduction stays on the VPU. Backward is the analytic
VJP computed by XLA from the same formula (memory-bound op; recompute is
free relative to HBM traffic).
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import pick_block

__all__ = ["rms_norm_fused", "rms_norm_supported", "rms_norm_dense"]


def _kernel(x_ref, w_ref, o_ref, *, eps):
    xf = x_ref[:].astype(jnp.float32)
    ms = jnp.mean(xf * xf, axis=-1, keepdims=True)
    o_ref[:] = (xf * lax.rsqrt(ms + eps)
                * w_ref[:].astype(jnp.float32)).astype(o_ref.dtype)


# elements of one block of rows: 256 rows of 4,096. Its two copies in
# and out and the float32 pass over it fill the 16 MiB of scoped VMEM at
# 256 rows of 7,168 (21 MiB asked, the compiler's message), so wider
# rows take fewer of them
_BLOCK_ELEMENTS = 256 * 4096


def _pick_block(T: int, H: int) -> int:
    return pick_block(T, prefer=tuple(
        b for b in (256, 128, 512, 64, 32, 16, 8, 4, 2, 1)
        if b * H <= _BLOCK_ELEMENTS or b == 1))


def _rms_ref(x2, w, eps):
    xf = x2.astype(jnp.float32)
    ms = jnp.mean(xf * xf, axis=-1, keepdims=True)
    return (xf * lax.rsqrt(ms + eps) * w.astype(jnp.float32)).astype(
        x2.dtype)


def rms_norm_supported(shape) -> bool:
    """Mosaic gate for kernel-dispatch sites: True when the flattened
    row count and the hidden dim of ``shape`` tile cleanly on real TPU
    (see _mosaic_tileable).  Callers take rms_norm_dense when this
    returns False."""
    H = int(shape[-1])
    T = 1
    for d in shape[:-1]:
        T *= int(d)
    return _mosaic_tileable(T, _pick_block(T, H), H)


def rms_norm_dense(x, weight, eps=1e-6):
    """XLA reference path — identical f32 math to the kernel, so the
    fused and dense paths are numerically interchangeable."""
    H = x.shape[-1]
    return _rms_ref(x.reshape(-1, H), weight, eps).reshape(x.shape)


@partial(jax.custom_vjp, nondiff_argnums=(2, 3))
def rms_norm_fused(x, weight, eps=1e-6, interpret=False):
    """x: [..., H] (normalized over the last dim), weight: [H].
    Dispatch sites ask :func:`rms_norm_supported` first;
    ``interpret=True`` (tests) runs the Pallas interpreter."""
    out, _ = _fwd(x, weight, eps, interpret)
    return out


def _mosaic_tileable(T, bt, H) -> bool:
    """Mosaic shape gate: the second-minor block dim must divide by 8
    (or equal the array dim) per the Mosaic tiling rule, and H must
    fill whole 128-wide VPU lanes; other shapes take the XLA path."""
    return (bt % 8 == 0 or bt == T) and H % 128 == 0


def _fwd(x, weight, eps, interpret):
    H = x.shape[-1]
    x2 = x.reshape(-1, H)
    T = x2.shape[0]
    bt = _pick_block(T, H)
    kw = {"memory_space": pltpu.VMEM}
    out = pl.pallas_call(
        partial(_kernel, eps=eps),
        grid=(T // bt,),
        in_specs=[pl.BlockSpec((bt, H), lambda i: (i, 0), **kw),
                  pl.BlockSpec((H,), lambda i: (0,), **kw)],
        out_specs=pl.BlockSpec((bt, H), lambda i: (i, 0), **kw),
        out_shape=jax.ShapeDtypeStruct((T, H), x.dtype),
        interpret=interpret,
        name="rms_norm_fused",
    )(x2, weight)
    return out.reshape(x.shape), (x, weight)


def _bwd(eps, interpret, res, g):
    x, weight = res
    H = x.shape[-1]

    def ref(x_, w_):
        return _rms_ref(x_.reshape(-1, H), w_, eps).reshape(x_.shape)

    _, vjp_fn = jax.vjp(ref, x, weight)
    return vjp_fn(g)


rms_norm_fused.defvjp(lambda x, w, eps, interpret:
                      _fwd(x, w, eps, interpret), _bwd)
