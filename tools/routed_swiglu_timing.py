"""Time the held experts' products of one expert layer alone on the chip
(PERF.md, PRs 32, 40 and 49).

Two sets of readings. ``decode``: at the two expert cells' decode shapes
(``mimo``: 16 of 256 experts held; ``sarvam``: 32 of 128; both 128 tokens
a decode step, top-8, d 4096, h 2048, bf16 weights) and at 256, 512 and
1,024 tokens, where the two forms cross. ``prefill``: at the prefill
buckets the expert cells run (``keye``: 16 of 128 held, d 2048, h 768,
and ``trinity``: h 1,024, at 2,048 / 4,096 / 8,192 tokens; ``mimo`` at
2,048; ``sarvam`` at 1,024; ``nemotron``: 128 of 512 held, top-22, latent
1,024 -> 2,688 -> 1,024 with no gate matrix, at 1,024; ``longcat``: 16
held of a 768-wide router, top-12, 6,144 -> 2,048, at 512; ``dsv32``: 16
of 256, 7,168 -> 2,048, at 8,192). ``STEPS`` calls in one ``lax.scan``,
each call's tokens made from the last call's output so that they run one
after the other and dispatch does not count. One JSON line a reading:
microseconds a call (its two or three products), the bytes of expert
weights a call streams, and that over the time as a share of the chip's
HBM bandwidth; every form's output is checked against ``sorted_full``'s.
``--forms a,b`` keeps the named forms. The forms:

- ``sorted`` / ``batched``: ``moe_layer.routed_swiglu_sorted`` /
  ``routed_swiglu_batched`` as the library has them (the sorted one on
  ``moe_layer.sorted_rows`` rows at a time since PR 40: ``rows`` and
  ``passes`` in its line; since PR 49 its products are
  ``ops/pallas/grouped_matmul.py`` where ``moe_layer.grouped_product``
  says ``"pallas"``: ``grouped``, ``tm`` and ``tn`` in its line);
- ``sorted_xla``: the same with kernels off: the products are
  ``lax.ragged_dot``, XLA's grouped matmul (the library's before PR 49);
- ``sorted_tm_other``: the library's form with the kernel's row tile
  swapped, 128 for 256 and 256 for 128 (``grouped_matmul.row_tile``'s
  docstring holds the readings);
- ``sorted_gmm``: the library's form with ``jax.experimental.pallas.
  ops.tpu.megablox.gmm`` for the product, K whole, row tile 256, column
  tile up to 512 (128 x 1,024 passed Mosaic's VMEM at nemotron's widths
  alone): a probe and a second opinion, fixed row tiles and a table of
  (tile, group) visits;
- ``sorted_full``: the sorted form as it was before PR 40, every routed
  pair a row of the ``[T*k, d]`` operand and of the float32 result;
- ``sorted_by_token`` / ``sorted_gather_k``: the library's sorted form
  with another way to sum a window's rows into their tokens: the rows
  put in token order first and a scatter-add told so (what XLA makes of
  the library's scatter-add by itself: the two read alike); ``k`` masked
  gathers from the window in token order, one a choice;
- ``batched_unfolded``: the batched form with a down product per expert
  and the combine after it (an ``[El, T, d]`` float32 array);
- ``batched_plain``: the batched form without the expert as a batch
  dimension of the tokens (``td,edh->eth``);
- ``batched_pallas``: the batched form as one Mosaic kernel over
  (expert, tile of 256 of the hidden width; 512 read 1-5% slower).

    chiprun -- python tools/routed_swiglu_timing.py [decode|prefill] [cell ...]
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
from paddle_tpu.ops.pallas import grouped_matmul as gm  # noqa: E402

STEPS, CALLS = 16, 4
# held, routed over, d, h, choices a token, a gate matrix
SHAPES = {"mimo": (16, 256, 4096, 2048, 8, True),
          "sarvam": (32, 128, 4096, 2048, 8, True),
          "keye": (16, 128, 2048, 768, 8, True),
          "trinity": (16, 128, 2048, 1024, 8, True),
          "nemotron": (128, 512, 1024, 2688, 22, False),
          "longcat": (16, 768, 6144, 2048, 12, True),
          "dsv32": (16, 256, 7168, 2048, 8, True)}
DECODE = ("mimo", "sarvam")
PREFILL = {"keye": (2048, 4096, 8192), "trinity": (2048, 4096, 8192),
           "mimo": (2048,), "sarvam": (1024,), "nemotron": (1024,),
           "longcat": (512,), "dsv32": (8192,)}


def sorted_full(x2d, idx, weights, w_gate, w_up, w_down, off=0):
    """``routed_swiglu_sorted`` before PR 40."""
    T, k = idx.shape
    El = w_up.shape[0]
    local = idx - off
    held = (local >= 0) & (local < El)
    e = jnp.where(held, local, El).reshape(T * k)
    order = jnp.argsort(e, stable=True)
    gs = jnp.bincount(e, length=El).astype(jnp.int32)
    xs = x2d[order // k]
    g = None if w_gate is None else lax.ragged_dot(
        xs, w_gate, gs, preferred_element_type=jnp.float32)
    u = lax.ragged_dot(xs, w_up, gs, preferred_element_type=jnp.float32)
    out = lax.ragged_dot(ml._activation(g, u).astype(x2d.dtype), w_down,
                         gs, preferred_element_type=jnp.float32)
    used = held.reshape(T * k)[order] & (jnp.arange(T * k) < gs.sum())
    wf = weights.reshape(T * k)[order]
    out = jnp.where(used[:, None], out * wf[:, None], 0.0)
    return out[jnp.argsort(order)].reshape(T, k, -1).sum(axis=1), gs


def by_token(y, rows, tok):
    """The window's rows in token order, then a scatter-add that is
    told its indices are sorted."""
    tok, at = lax.sort((tok, jnp.arange(tok.shape[0], dtype=jnp.int32)),
                       num_keys=1)
    return y.at[tok].add(rows[at], indices_are_sorted=True)


def gather_k(y, rows, tok):
    """``k`` masked gathers, one a choice: a token's j-th row of the
    window if it has one (a token's rows share its number, so ranking
    the window's rows by token finds them), summed in one pass."""
    M, T = tok.shape[0], y.shape[0]
    # stable: the zero rows that pad the last window sort behind token
    # 0's own
    tok, at = lax.sort((tok, jnp.arange(M, dtype=jnp.int32)), num_keys=1,
                       is_stable=True)
    first = jnp.searchsorted(tok, jnp.arange(T + 1, dtype=tok.dtype))
    for j in range(8):          # the four cells it was timed at: top-8
        i = first[:-1] + j
        y = y + jnp.where((i < first[1:])[:, None],
                          rows[at[jnp.minimum(i, M - 1)]], 0.0)
    return y


def with_swap(name, fn):
    """The library's sorted form with ``fn`` for ``moe_layer``'s
    ``name`` while it is traced."""
    def form(*a):
        keep = getattr(ml, name)
        setattr(ml, name, fn)
        try:
            return ml.routed_swiglu_sorted(*a)
        finally:
            setattr(ml, name, keep)
    return form


def sorted_tm_other(*a):
    """The library's sorted form with the kernel's OTHER row tile (256
    where ``row_tile`` says 128, 128 where 256)."""
    keep = gm.row_tile
    gm.row_tile = lambda M, G: 384 - keep(M, G)
    try:
        return ml.routed_swiglu_sorted(*a)
    finally:
        gm.row_tile = keep


def sorted_xla(*a):
    """The library's sorted form traced with kernels off."""
    paddle_tpu.set_flags({"use_pallas_kernels": False})
    try:
        return ml.routed_swiglu_sorted(*a)
    finally:
        paddle_tpu.set_flags({"use_pallas_kernels": True})


def megablox(lhs, rhs, group_sizes, visits=None):
    """``megablox.gmm`` in ``grouped_matmul``'s place: K whole, rows in
    fixed tiles of 256, columns in the largest lane-multiple tile of N
    that divides it, up to 512."""
    from jax.experimental.pallas.ops.tpu.megablox import gmm
    K, N = rhs.shape[1:]
    tn = max(t for t in range(128, min(N, 512) + 1, 128) if N % t == 0)
    return gmm(lhs, rhs, group_sizes, jnp.float32, (256, K, tn))


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


# every form takes (x, idx, w, wg, wu, wd, expert_offset, router's width)
FORMS = {"sorted": ml.routed_swiglu_sorted,
         "sorted_xla": sorted_xla,
         "sorted_tm_other": sorted_tm_other,
         "sorted_gmm": with_swap("grouped_matmul", megablox),
         "sorted_full": lambda *a: sorted_full(*a[:7]),
         "sorted_by_token": with_swap("_sum_by_token", by_token),
         "sorted_gather_k": with_swap("_sum_by_token", gather_k),
         "batched": lambda *a: ml.routed_swiglu_batched(*a[:7]),
         "batched_unfolded": lambda *a: batched_unfolded(*a[:7]),
         "batched_plain": lambda *a: batched_plain(*a[:7]),
         "batched_pallas": lambda *a: batched_pallas(*a[:7])}
BATCHED = [f for f in FORMS if f.startswith("batched")]
SORTED = [f for f in FORMS if f.startswith("sorted")]


def reading(cell, T, form, ops, E, peak):
    x, idx, w, wg, wu, wd = ops

    @jax.jit
    def run(x, idx, w, wg, wu, wd):
        def step(x, _):
            y = FORMS[form](x, idx, w, wg, wu, wd, 0, E)[0]
            return (x + 1e-3 * y.astype(x.dtype)), None
        return lax.scan(step, x, None, length=STEPS)[0]

    run(x, idx, w, wg, wu, wd).block_until_ready()
    t0 = time.perf_counter()
    for _ in range(CALLS):
        out = run(x, idx, w, wg, wu, wd)
    out.block_until_ready()
    us = (time.perf_counter() - t0) / (CALLS * STEPS) * 1e6
    products = 2 if wg is None else 3
    nbytes = products * wu.size * wu.dtype.itemsize
    held = int(((np.asarray(idx) >= 0) & (np.asarray(idx) < wu.shape[0]))
               .sum())
    return {"cell": cell, "tokens": T, "form": form, "us_a_call": us,
            "us_a_product": us / products, "weight_bytes": nbytes,
            "stream_us": nbytes / peak.hbm_bytes * 1e6,
            "held_pairs": held, "sorted_rows": idx.size,
            "hbm_share": nbytes / (us * 1e-6) / peak.hbm_bytes}


def cases(which, cells):
    """(cell, tokens, forms) of the readings asked for."""
    out = []
    if "decode" in which:
        out += [(c, T, BATCHED + ["sorted"] if T == 128
                 else ["sorted", "batched"])
                for c in DECODE for T in (128, 256, 512, 1024)]
    if "prefill" in which:
        out += [(c, T, SORTED) for c, Ts in PREFILL.items() for T in Ts]
    return [c for c in out if not cells or c[0] in cells]


def main():
    dev = jax.devices()[0]
    peak = peaks_for(dev.device_kind)
    r = np.random.RandomState(0)
    args = sys.argv[1:]
    sets = [a for a in args if a in ("decode", "prefill")]
    cells = [a for a in args if a in SHAPES]
    only = args[args.index("--forms") + 1].split(",") \
        if "--forms" in args else None
    out, made = [], {}
    for cell, T, forms in cases(sets or ["decode", "prefill"], cells):
        El, E, d, h, K, gated = SHAPES[cell]
        if cell not in made:       # one cell's weights at a time
            keys = jax.random.split(jax.random.PRNGKey(El), 3)
            made = {cell: tuple(
                0.02 * jax.random.normal(k, s, jnp.bfloat16)
                for k, s in zip(keys, ((El, d, h), (El, d, h),
                                       (El, h, d))))}
            if not gated:
                made[cell] = (None,) + made[cell][1:]
        x = jnp.asarray(r.randn(T, d), jnp.bfloat16)
        idx = jnp.asarray(np.argsort(r.random_sample((T, E)))[:, :K],
                          jnp.int32)
        w = jnp.asarray(r.uniform(0.05, 0.2, (T, K)), jnp.float32)
        ops = (x, idx, w) + made[cell]
        want = np.asarray(jax.jit(sorted_full)(*ops)[0])
        for form in forms if only is None else \
                [f for f in forms if f in only]:
            try:
                row = reading(cell, T, form, ops, E, peak)
                said = []     # what the form returned beside arrays

                def call(*a):
                    got = FORMS[form](*a, 0, E)
                    said[:] = got[3:]
                    return got[:3]
                got = jax.jit(call)(*ops)
            except Exception as err:    # a form the compiler refuses
                print(json.dumps({"cell": cell, "tokens": T, "form": form,
                                  "error": repr(err)[:300]}), flush=True)
                continue
            if form.startswith("sorted") and form != "sorted_full":
                M = row["rows"] = ml.sorted_rows(T, K, El, E)
                row["passes"] = int(got[2])
                row["grouped"], = said
                if form == "sorted":
                    tm = row["tm"] = gm.row_tile(M, El)
                    row["tn"] = [gm.col_tile(M, a, b, tm, 2)
                                 for a, b in ((d, h), (h, d))]
            row["max_gap_to_sorted_full"] = float(
                np.abs(np.asarray(got[0]) - want).max())
            row["max_abs"] = float(np.abs(want).max())
            print(json.dumps(row), flush=True)
            out.append(row)
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    with open(os.path.join(ROOT, "chiprun_out",
                           "routed_swiglu_timing.json"), "w") as f:
        json.dump({"device": dev.device_kind, "readings": out}, f)


if __name__ == "__main__":
    main()
