"""Time the held experts' products of one expert layer alone on the chip
(PERF.md, PR 32).

At the two expert cells' shapes (``mimo``: 16 of 256 experts held;
``sarvam``: 32 of 128; both 128 tokens a decode step, top-8, d 4096, h
2048, bf16 weights) and at a prefill's (256, 512, 1,024 tokens: where
the two forms cross): ``STEPS`` calls in
one ``lax.scan``, each call's tokens made from the last call's output so
that they run one after the other and dispatch does not count. One JSON
line a reading: microseconds a call (three products), the bytes of expert
weights a call streams, and that over the time as a share of the chip's
HBM bandwidth. The forms:

- ``sorted`` / ``batched``: ``moe_layer.routed_swiglu_sorted`` /
  ``routed_swiglu_batched`` as the library has them;
- ``batched_unfolded``: the batched form with a down product per expert
  and the combine after it (an ``[El, T, d]`` float32 array);
- ``batched_plain``: the batched form without the expert as a batch
  dimension of the tokens (``td,edh->eth``);
- ``batched_pallas``: the batched form as one Mosaic kernel over
  (expert, tile of 256 of the hidden width; 512 read 1-5% slower).

    chiprun -- python tools/routed_swiglu_timing.py
"""
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from jax import lax  # noqa: E402
from jax.experimental import pallas as pl  # noqa: E402
from jax.experimental.pallas import tpu as pltpu  # noqa: E402

import paddle_tpu  # noqa: E402,F401  (places the compile cache)
from benchmarks.harness.peaks import peaks_for  # noqa: E402
from paddle_tpu.incubate.distributed.models.moe import moe_layer as ml  # noqa: E402

D, H, K = 4096, 2048, 8
STEPS, CALLS = 16, 4
CELLS = {"mimo": (16, 256), "sarvam": (32, 128)}     # held, routed over


def batched_unfolded(x, idx, w, wg, wu, wd, off=0):
    El = wg.shape[0]
    pairs, c = ml._combine(idx, w, off, El)
    xb = jnp.broadcast_to(x, (El,) + x.shape)
    dn = (((2,), (1,)), ((0,), (0,)))
    g = lax.dot_general(xb, wg, dn, preferred_element_type=jnp.float32)
    u = lax.dot_general(xb, wu, dn, preferred_element_type=jnp.float32)
    out = lax.dot_general((jax.nn.silu(g) * u).astype(x.dtype), wd, dn,
                          preferred_element_type=jnp.float32)
    y = jnp.where((pairs > 0).T[:, :, None], out * c.T[:, :, None],
                  0.0).sum(axis=0)
    return y, pairs.sum(axis=0)


def batched_plain(x, idx, w, wg, wu, wd, off=0):
    T, (El, _, h) = x.shape[0], wg.shape
    pairs, c = ml._combine(idx, w, off, El)
    g = jnp.einsum("td,edh->teh", x, wg,
                   preferred_element_type=jnp.float32)
    u = jnp.einsum("td,edh->teh", x, wu,
                   preferred_element_type=jnp.float32)
    a = jnp.where((pairs > 0)[:, :, None],
                  jax.nn.silu(g) * u * c[:, :, None], 0.0)
    y = jnp.dot(a.astype(x.dtype).reshape(T, El * h),
                wd.reshape(El * h, -1), preferred_element_type=jnp.float32)
    return y, pairs.sum(axis=0)


def _fused_kernel(x_ref, c_ref, m_ref, wg_ref, wu_ref, wd_ref, o_ref):
    @pl.when((pl.program_id(0) == 0) & (pl.program_id(1) == 0))
    def _():
        o_ref[...] = jnp.zeros_like(o_ref)

    x = x_ref[...]
    g = jnp.dot(x, wg_ref[...], preferred_element_type=jnp.float32)
    u = jnp.dot(x, wu_ref[...], preferred_element_type=jnp.float32)
    a = jnp.where(m_ref[...] > 0,
                  g * jax.nn.sigmoid(g) * u * c_ref[...], 0.0)
    o_ref[...] += jnp.dot(a.astype(x.dtype), wd_ref[...],
                          preferred_element_type=jnp.float32)


def batched_pallas(x, idx, w, wg, wu, wd, off=0, th=256, interpret=False):
    """One Mosaic kernel over (expert, tile of the hidden width): each
    step streams a [d, th] tile of W_g and of W_u and a [th, d] tile of
    W_d once, the tokens and the [T, d] float32 sum stay in VMEM."""
    T, d = x.shape
    El, _, h = wg.shape
    th = min(th, h)
    pairs, c = ml._combine(idx, w, off, El)
    y = pl.pallas_call(
        _fused_kernel,
        grid=(El, h // th),
        in_specs=[pl.BlockSpec((T, d), lambda e, j: (0, 0)),
                  pl.BlockSpec((None, T, 1), lambda e, j: (e, 0, 0)),
                  pl.BlockSpec((None, T, 1), lambda e, j: (e, 0, 0)),
                  pl.BlockSpec((None, d, th), lambda e, j: (e, 0, j)),
                  pl.BlockSpec((None, d, th), lambda e, j: (e, 0, j)),
                  pl.BlockSpec((None, th, d), lambda e, j: (e, j, 0))],
        out_specs=pl.BlockSpec((T, d), lambda e, j: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((T, d), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=64 * 2 ** 20),
        interpret=interpret, name="routed_swiglu_fused",
    )(x, c.T[:, :, None], pairs.T[:, :, None], wg, wu, wd)
    return y, pairs.sum(axis=0)


FORMS = {"sorted": ml.routed_swiglu_sorted,
         "batched": ml.routed_swiglu_batched,
         "batched_unfolded": batched_unfolded,
         "batched_plain": batched_plain,
         "batched_pallas": batched_pallas}


def reading(cell, T, form, ops, peak):
    x, idx, w, wg, wu, wd = ops

    @jax.jit
    def run(x, idx, w, wg, wu, wd):
        def step(x, _):
            y, _ = FORMS[form](x, idx, w, wg, wu, wd)
            return (x + 1e-3 * y.astype(x.dtype)), None
        return lax.scan(step, x, None, length=STEPS)[0]

    run(x, idx, w, wg, wu, wd).block_until_ready()
    t0 = time.perf_counter()
    for _ in range(CALLS):
        out = run(x, idx, w, wg, wu, wd)
    out.block_until_ready()
    us = (time.perf_counter() - t0) / (CALLS * STEPS) * 1e6
    nbytes = 3 * wg.size * wg.dtype.itemsize
    held = int(((np.asarray(idx) >= 0) & (np.asarray(idx) < wg.shape[0]))
               .sum())
    return {"cell": cell, "tokens": T, "form": form, "us_a_call": us,
            "us_a_product": us / 3, "weight_bytes": nbytes,
            "held_pairs": held, "sorted_rows": T * K,
            "hbm_share": nbytes / (us * 1e-6) / peak.hbm_bytes}


def main():
    dev = jax.devices()[0]
    peak = peaks_for(dev.device_kind)
    r = np.random.RandomState(0)
    out = []
    for cell, (El, E) in CELLS.items():
        keys = jax.random.split(jax.random.PRNGKey(El), 3)
        wg, wu, wd = (0.02 * jax.random.normal(k, s, jnp.bfloat16)
                      for k, s in zip(keys, ((El, D, H), (El, D, H),
                                             (El, H, D))))
        for T, forms in ((128, list(FORMS)),) + tuple(
                (T, ["sorted", "batched"]) for T in (256, 512, 1024)):
            x = jnp.asarray(r.randn(T, D), jnp.bfloat16)
            idx = jnp.asarray(np.stack([r.permutation(E)[:K]
                                        for _ in range(T)]), jnp.int32)
            w = jnp.asarray(r.uniform(0.05, 0.2, (T, K)), jnp.float32)
            ops = (x, idx, w, wg, wu, wd)
            want = np.asarray(jax.jit(ml.routed_swiglu_sorted)(*ops)[0])
            for form in forms:
                row = reading(cell, T, form, ops, peak)
                got = np.asarray(jax.jit(FORMS[form])(*ops)[0])
                row["max_gap_to_sorted"] = float(np.abs(got - want).max())
                row["max_abs"] = float(np.abs(want).max())
                print(json.dumps(row), flush=True)
                out.append(row)
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    with open(os.path.join(ROOT, "chiprun_out",
                           "routed_swiglu_timing.json"), "w") as f:
        json.dump({"device": dev.device_kind, "readings": out}, f)


if __name__ == "__main__":
    main()
