"""Pallas TPU kernels for the hot fused ops.

(reference CUDA counterparts: phi/kernels/gpu/flash_attn_kernel.cu,
rms_norm_kernel.cu, fusion/gpu/fused_rope_kernel.cu,
fused_multi_transformer_op.cu.h — here each is a Mosaic kernel tiled for
MXU/VMEM.)

Who picks what: a dispatch site asks :func:`is_tpu_platform` and the
kernel's shape gate, then calls the kernel bare — a kernel the gate
admitted and Mosaic refuses fails the run with the compiler's message.
Off the TPU the dispatch sites take the dense XLA function. A kernel
never chooses interpret mode for itself: ``interpret=True`` is an
argument the tests pass.
"""
from __future__ import annotations

import jax
from jax.experimental.pallas import tpu as pltpu

__all__ = ["is_tpu_platform", "pick_block"]


def is_tpu_platform() -> bool:
    """The one "is this a TPU" predicate: the default backend's platform
    as JAX names it. Initialises the backend; an error there is the
    caller's error, not a reason to run something else."""
    return jax.devices()[0].platform == "tpu"


def pick_block(n: int, prefer=(128, 256, 512, 64, 32, 16, 8)) -> int:
    """Largest MXU/VPU-aligned block size that divides ``n`` (0 = none)."""
    for b in prefer:
        if b <= n and n % b == 0:
            return b
    return 0


_BLOCKS_LARGE = (512, 256, 128, 64, 32, 16, 8)


def compiler_params(n_parallel: int, interpret: bool = False,
                    vmem_limit_bytes: int | None = None) -> dict:
    """kwargs for pallas_call telling Mosaic which grid axes are
    parallel — the streaming axis is 'arbitrary' (it carries a scratch
    recurrence) — and, where a step holds more than the scoped default,
    how much VMEM it may take."""
    if interpret:
        return {}
    sem = ("parallel",) * n_parallel + ("arbitrary",)
    return {"compiler_params": pltpu.CompilerParams(
        dimension_semantics=sem, vmem_limit_bytes=vmem_limit_bytes)}
