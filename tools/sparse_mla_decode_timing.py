"""Time the forms of decode attention over the cache rows an index KEPT,
alone on the chip (PERF.md, PR 41).

At ``serve-dsv32-longdoc-batch``'s shapes (48 rows of contexts
6.3k-8.4k, 128 heads, a 512 + 64 cache row in pools of 4,096 pages of
128, bf16, a table of 66 pages, 2,048 kept rows a query row chosen at
random): ``STEPS`` calls in one ``lax.scan``, each call's query made
from the last call's output so that they run one after the other and
dispatch does not count. One JSON line a form: microseconds a call, the
share of the roofline the KEPT rows' work would reach in that time
(``benchmarks/harness/sparse_mla_cost.py``), and the largest difference
from form (a)'s output.

- ``a_masked_walk``: ``mla_paged_sparse_decode_attention``, every
  referenced page walked with the kept positions as one more mask (the
  form the library ships);
- ``b_gather_rows``: the kept rows' ids by ``lax.top_k`` of the mask
  (2,048 of them), an XLA gather of those rows from the two pools, dense
  absorbed attention over them: what a kernel that fetches kept rows
  only would have to beat;
- ``dense_walk``: ``mla_paged_decode_attention`` over every row (no
  mask: ANOTHER result, the cost of the walk alone);
- ``index_select``: the decode step's index scores over the gathered
  index-key pages and ``keep_topk`` (what precedes any of the forms).

    chiprun -- python tools/sparse_mla_decode_timing.py

``prefill`` as the one argument times the PREFILL's attention of one
layer instead, at the cell's one bucket (8,192 rows, 128 heads of 192
against 128, 64 index heads of 128, top 2,048): ``loop`` =
``sparse_causal_attention`` (index, selection and the key-block loop in
plain ``lax``), ``kernel`` = ``kept_mask`` + ``kept_flash_attention``
(what the model runs on the chip), ``mask_only`` = ``kept_mask`` alone,
``kernel_only`` = ``kept_flash_attention`` alone under a mask built once
outside the timed scan: ms a layer, us a walked block a head (over the
``H n (n + 1) / 2`` blocks of 512 x 512 at or under the diagonal,
whatever blocks the kernel takes) and the kept pairs' roofline share
without the index.
"""
import json
import os
import sys
import time
from functools import partial

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from jax import lax  # noqa: E402

import paddle_tpu  # noqa: E402,F401  (places the compile cache)
from benchmarks.harness.kernel_cost import least_seconds  # noqa: E402
from benchmarks.harness.peaks import peaks_for  # noqa: E402
from benchmarks.harness.sparse_mla_cost import (  # noqa: E402
    sparse_latent_decode)
from paddle_tpu.ops.pallas import mla_attention as ma  # noqa: E402
from paddle_tpu.ops.pallas.decode_attention import gather_pages  # noqa: E402
from paddle_tpu.ops.sparse_attention import (index_scores,  # noqa: E402
                                             keep_topk)

B, H, DC, DR, PAGE, P, NPAGES, TOPK = 48, 128, 512, 64, 128, 4096, 66, 2048
HI, DI = 64, 128
STEPS, REPEATS = 8, 3
SCALE = 192 ** -0.5


def timed(fn, *args):
    """Seconds a call of ``fn`` inside a scan of STEPS dependent calls,
    best of REPEATS; and the first call's output."""
    def prog(q, *rest):
        def body(q, _):
            out = fn(q, *rest)
            # the next call's query depends on this call's output
            return q + (out.mean() * 0).astype(q.dtype), out
        q, outs = lax.scan(body, q, None, length=STEPS)
        return outs[0]

    f = jax.jit(prog)
    out = f(*args)
    out.block_until_ready()
    best = float("inf")
    for _ in range(REPEATS):
        t0 = time.perf_counter()
        f(*args).block_until_ready()
        best = min(best, (time.perf_counter() - t0) / STEPS)
    return best, out


def prefill(dev, pk):
    from benchmarks.harness.sparse_mla_cost import kept_prefill
    from paddle_tpu.ops.pallas.kept_attention import kept_flash_attention
    from paddle_tpu.ops.sparse_attention import (kept_mask,
                                                 sparse_causal_attention)

    S, D, DV = 8192, 192, 128
    rng = np.random.default_rng(41)
    f = lambda *s: jnp.asarray(rng.normal(size=s) * 0.3, jnp.bfloat16)
    q, k, v = f(1, S, H, D), f(1, S, H, D), f(1, S, H, DV)
    iq, ik = f(1, S, HI, DI), f(1, S, DI)
    iw = jnp.asarray(rng.normal(size=(1, S, HI)), jnp.float32)
    least = least_seconds(*kept_prefill(S, H, D, DV, TOPK), pk)
    n = S // 512
    blocks = H * n * (n + 1) // 2

    def loop(q, k, v, iq, ik, iw):
        return sparse_causal_attention(q, k, v, iq, ik, iw, SCALE, TOPK,
                                       head_block=16)

    def mask_only(q, iq, ik, iw):
        return kept_mask(iq + (q.mean() * 0).astype(q.dtype), ik, iw, TOPK,
                         head_block=16).astype(jnp.float32)[:, :, :128]

    def kernel(q, k, v, iq, ik, iw):
        keep = kept_mask(iq, ik, iw, TOPK, head_block=16)
        return kept_flash_attention(q, k, v, keep, SCALE)

    kernel_only = partial(kept_flash_attention, scale=SCALE)
    keep = jax.jit(partial(kept_mask, topk=TOPK, head_block=16))(iq, ik, iw)
    base = None
    for name, fn, args in (("kernel", kernel, (k, v, iq, ik, iw)),
                           ("loop", loop, (k, v, iq, ik, iw)),
                           ("mask_only", mask_only, (iq, ik, iw)),
                           ("kernel_only", kernel_only, (k, v, keep))):
        sec, out = timed(fn, q, *args)
        line = {"form": name, "ms_a_layer": sec * 1e3, "rows": S,
                "device": dev.device_kind}
        if name != "mask_only":
            out = np.asarray(out, np.float32)
            base = out if base is None else base
            line["max_diff_from_kernel"] = float(np.abs(out - base).max())
        if name in ("kernel", "loop"):
            line["kept_pairs_roofline_pct_with_index"] = 100 * least / sec
        if name == "kernel_only":
            line["us_a_walked_block_head"] = sec * 1e6 / blocks
            line["kept_pairs_roofline_pct"] = 100 * least / sec
        print(json.dumps(line), flush=True)
    return 0


def main():
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(json.dumps({"skipped": f"no TPU ({dev.platform})"}))
        return 3
    pk = peaks_for(dev.device_kind)
    if sys.argv[1:] == ["prefill"]:
        return prefill(dev, pk)
    rng = np.random.default_rng(41)
    bf = jnp.bfloat16
    f = lambda *s: jnp.asarray(rng.normal(size=s) * 0.3, bf)
    q_lat, q_r = f(B, H, DC), f(B, H, 128)
    cp, rp, ip = f(P, 1, PAGE, DC), f(P, 1, PAGE, 128), f(P, 1, PAGE, 128)
    iq, iw = f(B, 1, HI, DI), jnp.asarray(rng.normal(size=(B, 1, HI)),
                                          jnp.float32)
    tbl = jnp.asarray(rng.permutation(P - 1)[:B * NPAGES].reshape(
        B, NPAGES), jnp.int32)
    lens = np.linspace(6300, 8440, B).astype(np.int32)
    rng.shuffle(lens)
    M = NPAGES * PAGE
    keep = np.zeros((B, M), bool)
    for b, n in enumerate(lens):
        keep[b, rng.choice(n + 1, TOPK, replace=False)] = True
    keep, lens = jnp.asarray(keep), jnp.asarray(lens)
    rows = [(1, int(n) + 1) for n in np.asarray(lens)]
    least = least_seconds(*sparse_latent_decode(rows, H, DC, DR, TOPK), pk)

    def a(q):
        return ma.mla_paged_decode_attention(q, q_r, cp, rp, tbl, lens,
                                             SCALE, keep=keep)

    def b_gather(q):
        # the kept rows' positions (exactly TOPK a row), their physical
        # rows, one gather a pool, dense attention over what came
        _, pos = lax.top_k(keep.astype(jnp.int32), TOPK)        # [B, k]
        phys = jnp.take_along_axis(tbl, pos // PAGE, axis=1) * PAGE \
            + pos % PAGE
        c = cp.reshape(P * PAGE, DC)[phys]                      # [B,k,dc]
        r = rp.reshape(P * PAGE, 128)[phys]
        s = (jnp.einsum("bhc,bkc->bhk", q, c,
                        preferred_element_type=jnp.float32)
             + jnp.einsum("bhr,bkr->bhk", q_r, r,
                          preferred_element_type=jnp.float32)) * SCALE
        p = jax.nn.softmax(s, axis=-1).astype(c.dtype)
        return jnp.einsum("bhk,bkc->bhc", p, c,
                          preferred_element_type=jnp.float32).astype(bf)

    def dense(q):
        return ma.mla_paged_decode_attention(q, q_r, cp, rp, tbl, lens,
                                             SCALE)

    def index_select(q):
        keys = gather_pages(ip, tbl)[:, 0]
        sc = index_scores(iq + (q.mean() * 0).astype(bf), keys, iw)
        seen = jnp.arange(M)[None, None] <= lens[:, None, None]
        return keep_topk(sc, seen, TOPK).astype(jnp.float32)

    base = None
    for name, fn in (("a_masked_walk", a),
                     ("b_gather_rows", b_gather), ("dense_walk", dense),
                     ("index_select", index_select)):
        sec, out = timed(fn, q_lat)
        line = {"form": name, "us_a_call": sec * 1e6, "rows": B,
                "device": dev.device_kind}
        if name != "index_select":
            out = np.asarray(out, np.float32)
            base = out if base is None else base
            line["kept_rows_roofline_pct"] = 100.0 * least / sec
            line["max_diff_from_a"] = float(np.abs(out - base).max())
        else:
            line["kept_a_row"] = np.asarray(out).sum(-1).ravel().tolist()[:3]
        print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
