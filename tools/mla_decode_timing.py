"""Time ``mla_paged_decode_attention`` alone on the chip (PERF.md, PR 47).

At the decode shapes of the two cells that run the latent kernel without
a kept mask (``serve-longcat-dialoggen-batch``: 128 rows, 64 heads, a
512 + 128 cache row in pages of 128, a table of 10;
``serve-sarvam-longgen-batch``: the same with a table of 11), read from
their files under ``benchmarks/``: ``STEPS`` calls in one ``lax.scan``,
each call's query made from the last call's output so that they run one
after the other and dispatch does not count. One JSON line a reading:
microseconds a call, microseconds a visited page (a row visits
``context // page + 1``), and the share of the roofline by
``benchmarks/harness/mla_cost.latent_decode`` (the published widths: a
64-wide rotated key, the referenced pages read once), with the largest
difference from the dense twin.

- ``mix``: contexts drawn as the cell's closed loop holds them in steady
  state: a prompt from the traffic file's range, an output length drawn
  in proportion to itself (a longer answer holds its row longer), the
  row seen at a uniform point of its answer;
- ``one_page``: every row walks ONE page (what a row costs);
- ``full``: every row fills its table (what a page costs).

Uses nothing but the kernel's public signature, so a copy of this file
in an older tree times that tree in the same call:

    chiprun -- python tools/mla_decode_timing.py [longcat] [sarvam]

The kept walk (``keep=``) at the dsv32 cell's shapes is
``tools/sparse_mla_decode_timing.py``'s.
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

import paddle_tpu  # noqa: E402,F401  (places the compile cache)
from benchmarks.harness.kernel_cost import least_seconds  # noqa: E402
from benchmarks.harness.mla_cost import latent_decode  # noqa: E402
from benchmarks.harness.peaks import peaks_for  # noqa: E402
from paddle_tpu.ops.pallas import mla_attention as ma  # noqa: E402

CELLS = {"longcat": ("longcat-flash-omni", "dialoggen-batch"),
         "sarvam": ("sarvam-105b", "longgen-batch")}
STEPS, CALLS = 64, 5


def _load(kind, name):
    with open(os.path.join(ROOT, "benchmarks", kind, name + ".json")) as f:
        return json.load(f)


def steady_contexts(traffic, rows, r):
    """Tokens in cache a decoding row, as the closed loop holds them."""
    p, o = traffic["prompt"], traffic["output"]
    assert p["dist"] == o["dist"] == "uniform", (p, o)
    outs = np.arange(o["min"], o["max"] + 1)
    out = r.choice(outs, rows, p=outs / outs.sum())
    return (r.randint(p["min"], p["max"] + 1, rows)
            + (r.random_sample(rows) * out).astype(np.int64)).astype(np.int32)


def reading(cell, shape, lens, cfg, r, peaks):
    srv = cfg["serving"]
    B, H, dc = len(lens), cfg["num_attention_heads"], cfg["kv_lora_rank"]
    dr = cfg["qk_rope_head_dim"]
    width = -(-dr // 128) * 128         # the pooled key fills its lanes
    page, P = srv["page_size"], srv["pool_pages"]
    npages = srv["max_length"] // page
    scale = (cfg.get("qk_nope_head_dim", 128) + dr) ** -0.5
    bf = jnp.bfloat16
    f = lambda *s: jnp.asarray(r.standard_normal(s) * 0.3, bf)
    ql, qr = f(B, H, dc), f(B, H, width)
    cp, rp = f(P, 1, page, dc), f(P, 1, page, width)
    tbl = jnp.asarray(r.permutation(P - 1)[:B * npages].reshape(B, npages),
                      jnp.int32)
    lengths = jnp.asarray(lens)
    got = ma.mla_paged_decode_attention(ql, qr, cp, rp, tbl, lengths, scale)
    want = ma.mla_paged_attention_dense(ql[:, None], qr[:, None], cp, rp,
                                        tbl, lengths, scale)[:, 0]
    err = float(jnp.abs(got.astype(jnp.float32)
                        - want.astype(jnp.float32)).max())

    @jax.jit
    def prog(ql, qr, cp, rp):
        def body(ql, _):
            u = ma.mla_paged_decode_attention(ql, qr, cp, rp, tbl, lengths,
                                              scale)
            return (ql + u * 1e-3).astype(ql.dtype), None

        return lax.scan(body, ql, None, length=STEPS)[0]

    prog(ql, qr, cp, rp).block_until_ready()
    best = float("inf")
    for _ in range(CALLS):
        t0 = time.perf_counter()
        prog(ql, qr, cp, rp).block_until_ready()
        best = min(best, time.perf_counter() - t0)
    sec = best / STEPS
    pages = int((lens // page + 1).sum())
    least = least_seconds(*latent_decode(
        [(1, int(n) + 1) for n in lens], H, dc, dr, page), peaks)
    plan = getattr(ma, "_latent_plan", None)        # not on an older tree
    print(json.dumps({
        "cell": cell, "shape": shape, "rows": B, "heads": H,
        "table_pages": npages, "pages_visited": pages,
        "pages_a_row": round(pages / B, 2),
        "us_per_call": round(sec * 1e6, 1),
        "us_per_visited_page": round(sec * 1e6 / pages, 3),
        "roofline_pct": round(100 * least / sec, 1),
        "max_err_vs_dense": round(err, 4),
        # (pages a visit, VMEM slots a pool)
        "plan": plan and plan(H, page, dc, width, 2)}), flush=True)


def main():
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(json.dumps({"error": f"needs a TPU, found {dev.platform}"}))
        return 1
    peaks = peaks_for(dev.device_kind)
    print(json.dumps({"device": dev.device_kind, "steps": STEPS}),
          flush=True)
    r = np.random.RandomState(47)
    for cell in sys.argv[1:] or sorted(CELLS):
        config, traffic = (_load(k, n) for k, n in
                           zip(("configs", "traffic"), CELLS[cell]))
        B, srv = traffic["max_batch"], config["serving"]
        for shape, lens in (
                ("mix", steady_contexts(traffic, B, r)),
                ("one_page", r.randint(0, srv["page_size"], B)
                 .astype(np.int32)),
                ("full", np.full(B, srv["max_length"] - 2, np.int32))):
            reading(cell, shape, lens, config, r, peaks)
    return 0


if __name__ == "__main__":
    sys.exit(main())
