"""Pallas TPU grouped matmul over sorted rows: ``[M, K] x [G, K, N] ->
[M, N]`` float32, row ``r`` multiplied by the matrix of the group it lies
in (``lax.ragged_dot``'s semantics: groups are consecutive runs of rows,
``group_sizes [G]``; rows past the last group are UNSPECIFIED here, where
``ragged_dot`` writes zeros: the caller drops them with a select).

Built for a prefill's sorted expert products (``moe_layer.
routed_swiglu_sorted``): 16-100 rows a group, where a call is bound by
the ``G * K * N`` weights it streams and not by its rows. Two things make
it run near that stream:

1. **A group's weights are read once a call.** The grid is (tile of N,
   visit); a visit is one row tile of one group, a group's visits are
   consecutive, and the weight block ``[K, tn]`` is indexed by the
   visit's group alone, so the pipeline fetches it when the group changes
   and never again (K is whole: no step of a contraction re-indexes it).
   An empty group has no visit and its weights are not read at all.
2. **Row tiles follow the groups.** A group's first tile starts at the
   group's own first row rounded DOWN to a sublane tile (16 rows), not at
   a multiple of the row tile: a group of up to ``tm - 15`` rows is ONE
   visit, one pass of its weights through the MXU, wherever it starts. (A
   grid of fixed tiles visits a 64-row group 1.5 times in the mean at
   ``tm`` 128.) The rows come through an ``Element``-indexed block, so the
   pipeline prefetches the next visit's rows like any block; the result
   tile ``[M, tn]`` stays in VMEM for a tile of N and a visit stores its
   group's rows into it under a mask, at its own row offset.

The visits (``group_visits``) are a few ``[G + M / tm]`` int32 arrays
made from ``group_sizes`` by XLA before the call and prefetched to SMEM;
the two or three products of one expert layer share them. The grid's
second extent is the NUMBER of visits, a device value.

``tm`` (``row_tile``) and ``tn`` (``col_tile``) are functions of the
call's static shapes; ``grouped_matmul_supported`` is the shape gate a
dispatch site asks first.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import compiler_params as _compiler_params

__all__ = ["grouped_matmul", "grouped_matmul_supported", "group_visits",
           "row_tile", "col_tile"]

# a group's first tile starts at a multiple of this: a bf16 sublane tile
# (and two float32 ones), so every dynamic row offset is tile-aligned
_ALIGN = 16
# VMEM a call may plan for, and what it asks Mosaic for (a v5e core has
# 128 MiB; the scoped default is 16)
_VMEM_PLAN = 80 * 2 ** 20
_VMEM_LIMIT = 100 * 2 ** 20


def row_tile(M: int, G: int) -> int:
    """Rows a visit multiplies: 128 (one pass of the MXU's 128 x 128
    weights a push) while the mean group is at most that, 256 above,
    where most groups would be two visits of 128 and are one of 256.
    Timed alone on a v5e, us a call of the whole sorted form at 128 |
    256 (``tools/routed_swiglu_timing.py``, ``sorted_tm_other``; PR 49):
    96 rows a group (sarvam, 1,024 tokens) 3,233 | 3,458; 192 (keye |
    trinity at 2,048) 867 | 799 and 984 | 889; 384 (at 4,096, and dsv32
    at 8,192) 1,542 | 1,537, 1,686 | 1,654 and 13,868 | 13,625; 768 (at
    8,192) 3,100 | 3,114 and 3,395 | 3,335: 256 loses 7% under the
    bound, wins 8-10% just over it and 0-2% further up."""
    return 128 if M <= 128 * G else 256


def _vmem_bytes(M, K, tm, tn, itemsize) -> int:
    """Two pipeline buffers each of the row tile, the weight block and
    the resident result, and the product's float32 tile twice (the
    product and the select)."""
    return (2 * tm * K * itemsize + 2 * K * tn * itemsize
            + 2 * M * tn * 4 + 2 * tm * tn * 4)


def col_tile(M: int, K: int, N: int, tm: int, itemsize: int) -> int:
    """Columns of a weight block: the largest multiple of 128 that
    divides N, is at most 1,024 and fits the plan (0: none does). The
    rows are read again for every tile of N (``tm / tn`` of the weights'
    bytes), the first block and the last result tile are not overlapped
    (``tn / N`` of a group's stream, of the result)."""
    for tn in range(min(N, 1024) // 128 * 128, 0, -128):
        if N % tn == 0 and _vmem_bytes(M, K, tm, tn, itemsize) <= _VMEM_PLAN:
            return tn
    return 0


def grouped_matmul_supported(lhs_shape, rhs_shape, dtype) -> bool:
    """Mosaic shape gate: lane-multiple K and N, rows a multiple of the
    row tile, bf16 or float32 operands of one type, and a column tile
    that fits."""
    if len(lhs_shape) != 2 or len(rhs_shape) != 3:
        return False
    (M, K), (G, K2, N) = lhs_shape, rhs_shape
    dtype = jnp.dtype(dtype)
    if dtype not in (jnp.dtype(jnp.bfloat16), jnp.dtype(jnp.float32)):
        return False
    tm = row_tile(M, G)
    if K != K2 or K % 128 or N % 128 or M % tm or M < tm:
        return False
    return col_tile(M, K, N, tm, dtype.itemsize) > 0


def group_visits(group_sizes, M: int):
    """The visits of a call over ``M`` rows in tiles of ``tm`` =
    ``row_tile(M, G)``:
    ``(group [V], row [V], start [G], end [G], n)`` int32. Group g's rows
    ``start[g] .. end[g] - 1`` take ``ceil((end - floor16(start)) / tm)``
    consecutive visits (none if empty), visit v multiplies rows ``row[v]
    .. row[v] + tm - 1`` (never past M: the last tile is moved back and
    the mask follows) by group ``group[v]``'s matrix; ``n`` visits in
    all, ``V = G + (M + 15 G) // tm`` at most."""
    G = group_sizes.shape[0]
    tm = row_tile(M, G)
    V = G + (M + (_ALIGN - 1) * G) // tm
    gs = group_sizes.astype(jnp.int32)
    end = jnp.cumsum(gs)
    start = end - gs
    base = start // _ALIGN * _ALIGN
    tiles = jnp.where(gs > 0, (end - base + (tm - 1)) // tm, 0)
    last = jnp.cumsum(tiles)
    v = jnp.arange(V, dtype=jnp.int32)
    # the group of visit v: how many groups' visits end at or before v
    group = jnp.minimum(
        (last[None, :] <= v[:, None]).sum(axis=1, dtype=jnp.int32), G - 1)
    row = jnp.minimum(base[group] + (v - (last - tiles)[group]) * tm,
                      M - tm)
    return group, row, start, end, last[-1]


def _kernel(group_ref, row_ref, start_ref, end_ref, x_ref, w_ref, o_ref, *,
            tm):
    v = pl.program_id(1)
    g = group_ref[v]
    r0 = pl.multiple_of(row_ref[v], _ALIGN)
    acc = jnp.dot(x_ref[...], w_ref[...],
                  preferred_element_type=jnp.float32)          # [tm, tn]
    rows = r0 + lax.broadcasted_iota(jnp.int32, (tm, 1), 0)
    mine = (rows >= start_ref[g]) & (rows < end_ref[g])
    at = pl.ds(r0, tm)
    o_ref[at, :] = jnp.where(mine, acc, o_ref[at, :])


def grouped_matmul(lhs, rhs, group_sizes, visits=None, interpret=False):
    """``lhs [M, K]`` sorted by group, ``rhs [G, K, N]``, ``group_sizes
    [G]`` int32 with ``sum <= M`` -> ``[M, N]`` float32: row r times the
    matrix of its group. Rows at and past ``sum(group_sizes)`` are
    unspecified. ``visits``: ``group_visits(group_sizes, M)`` where the
    caller has it already (several products over the same groups)."""
    (M, K), (G, _, N) = lhs.shape, rhs.shape
    tm = row_tile(M, G)
    if visits is None:
        visits = group_visits(group_sizes, M)
    tn = col_tile(M, K, N, tm, lhs.dtype.itemsize)
    return _call(lhs, rhs, *visits, tm=tm, tn=tn, interpret=interpret)


@partial(jax.jit, static_argnames=("tm", "tn", "interpret"))
def _call(lhs, rhs, group, row, start, end, n, tm, tn, interpret):
    (M, K), N = lhs.shape, rhs.shape[2]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(N // tn, n),
        in_specs=[
            pl.BlockSpec((pl.Element(tm), pl.Element(K)),
                         lambda j, v, group, row, start, end:
                         (pl.multiple_of(row[v], _ALIGN), 0)),
            pl.BlockSpec((None, K, tn),
                         lambda j, v, group, row, start, end:
                         (group[v], 0, j)),
        ],
        out_specs=pl.BlockSpec(
            (M, tn), lambda j, v, group, row, start, end: (0, j)),
    )
    return pl.pallas_call(
        partial(_kernel, tm=tm),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((M, N), jnp.float32),
        interpret=interpret,
        name="grouped_matmul",
        # the tiles of N are independent; a tile's visits run in order
        # (its result stays in VMEM, a group's visits follow each other)
        **_compiler_params(1, interpret, vmem_limit_bytes=_VMEM_LIMIT),
    )(group, row, start, end, lhs, rhs)
