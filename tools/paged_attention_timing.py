"""Time ``paged_decode_attention`` alone on the chip (PERF.md, PR 28).

At the serving cells' shapes (512 pages of 128, 8 KV heads of 128, 32 q
heads, bf16, a table of 19 pages): ``STEPS`` calls in one ``lax.scan``,
each call's q made from the last call's output so that they run one
after the other and dispatch does not count. Prints one JSON line a
reading: microseconds a call, the pages the rows reference (what the
kernel has to read), and that over the time as a share of the chip's
HBM bandwidth. Uses nothing but the kernel's public signature, so the
same file times an older tree:

    chiprun -- python tools/paged_attention_timing.py

- ``chat``: 48 rows, 19 live with 256-1600 tokens, 29 free slots (the
  traced tail of ``serve-chat-steady``: 39% occupancy, ~920 tokens);
- ``longprompt``: 16 rows, all live, 1100-2100 tokens;
- ``full``: 48 rows, all live, 2200-2400 tokens (what a fixed grid is
  best at);
- ``one_page_half_free``: 48 rows that all walk ONE page, half of them
  free slots (the chat cell's shape of call: a lookahead that stopped
  at a row's last page would change nothing here);
- ``prefill_<Sb>``: one row of Sb new tokens at offset 0, the prefill
  programs' call, in every form a program may hold (PR 43;
  ``decode_attention.paged_attention_form``), one reading each, all
  checked against the dense one: ``paged`` (the kernel over the pool,
  where its gate admits the bucket), ``dense`` (the pages gathered,
  float32 scores in HBM), ``flash`` (``flash_attention_fwd`` over the
  fresh K/V widened to the query heads, layout copies and all) and
  ``flash_gqa`` (``flash_attention_gqa`` over the fresh K/V as they
  are; ``--gqa-block`` adds readings at other block sizes). A flash
  reading carries its share of the MXU's peak by the causal half of
  ``4 S^2 D H`` operations.

Every reading carries the kernel's plan for its shapes (``plan``: KV
heads a fetch, VMEM slots a pool, bytes in flight beside the page being
computed; ``null`` on a tree from before PR 36).

``--kv-width D`` pools keys ``D`` wide (256: a 192-wide key padded to
the lanes) against 128-wide values; ``--kv-heads`` / ``--q-heads`` set
the heads. ``--prefill [Sb ...] --window W`` times a hybrid model's
prompt to itself under the window and without one, as
``flash_attention_gqa`` and as ``blockwise_causal_attention`` (PR 46):

    chiprun -- python tools/paged_attention_timing.py --prefill \
        --window 2048 --kv-heads 4

``--window W`` alone times the WINDOW decode kernel instead
(``paged_window_decode_attention``: the table is a ring of
``ceil(W / 128) + 1`` columns): 64 rows whose windows intersect 1, 2, 8
and ``ring`` pages (``window_<n>p``), then the cell's mix of contexts and
the one-page case:

    chiprun -- python tools/paged_attention_timing.py --window 2048 \
        --kv-heads 4
"""
import argparse
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
from benchmarks.harness.peaks import peaks_for  # noqa: E402
from paddle_tpu.ops.pallas import decode_attention as da  # noqa: E402
from paddle_tpu.ops.pallas.decode_attention import (  # noqa: E402
    paged_attention_dense, paged_decode_attention)

P, KV, H, PAGE, D, NPAGES = 512, 8, 32, 128, 128, 19
DV = 128
STEPS, CALLS = 64, 5
ROWS = 64                      # the window cases' batch


def one_page_half_free(r, rows=48):
    """Rows that all walk one page, every second one a free slot."""
    lens = r.randint(16, PAGE - 1, rows).astype(np.int32)
    lens[::2] = 0
    return "one_page_half_free", 1, lens


def shapes(r):
    live = r.permutation(48)[:19]
    chat = np.zeros(48, np.int32)
    chat[live] = r.randint(256, 1600, 19)
    yield "chat", 1, chat
    yield one_page_half_free(r)
    yield "longprompt", 1, r.randint(1100, 2100, 16).astype(np.int32)
    yield "full", 1, r.randint(2200, 2400, 48).astype(np.int32)
    yield "prefill_64", 64, np.zeros(1, np.int32)


def pages_seen(ctx, window):
    """Pages a row with ``ctx`` cached tokens fetches for its next
    position: all up to its own page, or those its window intersects."""
    last = ctx // PAGE
    if window is None:
        return last + 1
    return last - np.maximum(ctx - (window - 1), 0) // PAGE + 1


def window_shapes(r, window):
    """64 rows that all fetch n pages, n = 1, 2, 8 and the whole ring;
    then contexts drawn like the mixed-length cell's (prompts lognormal
    around 2,048 clipped to 256..8,192, up to 512 tokens into their
    answers)."""
    ring = -(-window // PAGE) + 1
    for n in sorted({1, 2, min(8, ring - 1), ring}):
        # under the window a row sees every page so far; past it, a
        # context that ends mid-page sees ring pages
        ctx = n * PAGE - 28 if n < ring else 2 * window + PAGE - 28
        lens = np.full(ROWS, ctx, np.int32)
        assert (pages_seen(lens, window) == n).all(), (n, ctx)
        yield f"window_{n}p", 1, lens
    mix = np.clip(np.exp(r.normal(np.log(2048), 1.0, ROWS)), 256, 8192)
    yield "window_mix", 1, (mix + r.randint(0, 512, ROWS)).astype(np.int32)
    yield one_page_half_free(r, ROWS)


def _best(prog, *args):
    """Seconds a scan step: the best of ``CALLS`` calls of ``STEPS``."""
    prog(*args).block_until_ready()
    best = float("inf")
    for _ in range(CALLS):
        t0 = time.perf_counter()
        prog(*args).block_until_ready()
        best = min(best, time.perf_counter() - t0)
    return best / STEPS


def reading(name, Sq, lens, r, peak, window=None):
    B = len(lens)
    lengths = jnp.asarray(lens)
    ncols = NPAGES if window is None else -(-window // PAGE) + 1
    pool = max(P, B * ncols + 1)
    tbl = jnp.asarray(r.permutation(pool - 1)[:B * ncols].reshape(
        B, ncols), jnp.int32)
    q = jnp.asarray(r.randn(B, Sq, H, D), jnp.bfloat16)
    kp = jnp.asarray(r.randn(pool, KV, PAGE, D), jnp.bfloat16)
    vp = jnp.asarray(r.randn(pool, KV, PAGE, DV), jnp.bfloat16)
    kw = {} if window is None else {"window": window}
    if not da.paged_supported(q.shape, kp.shape, vp.shape):
        print(json.dumps({"shape": name, "rows": B, "Sq": Sq,
                          "skipped": "the kernel's gate refuses it"}),
              flush=True)
        return

    got = paged_decode_attention(q, kp, vp, tbl, lengths, **kw)
    want = paged_attention_dense(q, kp, vp, tbl, lengths, **kw)
    err = float(jnp.abs(got.astype(jnp.float32)
                        - want.astype(jnp.float32)).max())

    @jax.jit
    def prog(q, kp, vp):
        def body(q, _):
            o = paged_decode_attention(q, kp, vp, tbl, lengths, **kw)
            if DV != D:
                o = jnp.pad(o, ((0, 0),) * 3 + ((0, D - DV),))
            return (q + o * 1e-3).astype(q.dtype), None

        return lax.scan(body, q, None, length=STEPS)[0]

    us = _best(prog, q, kp, vp) * 1e6
    pages = int(pages_seen(lens + Sq - 1, window).sum())
    plan = getattr(da, "_paged_plan", None)     # not on an older tree
    if plan is not None:
        plan = plan(Sq, H // KV, KV, PAGE, D, 2, DV)._asdict()
    nbytes = pages * KV * PAGE * (D + DV) * 2
    print(json.dumps({
        "shape": name, "rows": B, "Sq": Sq, "kv_heads": KV, "q_heads": H,
        "kv_width": D, "window": window, "us_per_call": round(us, 1),
        "pages_referenced": pages, "us_per_page": round(us / pages, 3),
        "hbm_share_pct": round(100 * nbytes / (us * 1e-6) / peak, 1),
        "max_err_vs_dense": round(err, 4), "plan": plan}), flush=True)


def prefill_readings(Sb, r, peaks, blocks=()):
    """One row of ``Sb`` prompt tokens at offset 0 in each form a
    prefill program may hold. Every array is an ARGUMENT of the jitted
    program: closed over, a pool is a constant the compiler folds for a
    minute a form (PR 42)."""
    from paddle_tpu.ops.pallas import flash_attention as fa

    q = jnp.asarray(r.randn(1, Sb, H, D), jnp.bfloat16)
    k = jnp.asarray(r.randn(1, Sb, KV, D), jnp.bfloat16)
    v = jnp.asarray(r.randn(1, Sb, KV, D), jnp.bfloat16)
    tbl = jnp.asarray(r.permutation(P - 1)[:NPAGES].reshape(1, NPAGES),
                      jnp.int32)
    zero = jnp.zeros((1,), jnp.int32)
    kp, vp = da.paged_kv_write(
        jnp.asarray(r.randn(P, KV, PAGE, D), jnp.bfloat16),
        jnp.asarray(r.randn(P, KV, PAGE, D), jnp.bfloat16), k, v, tbl, 0)
    G = H // KV

    def widened(q, k, v, kp, vp, tbl):
        kk, vv = (jnp.repeat(a, G, axis=2) for a in (k, v))
        return fa.flash_attention_fwd(q, kk, vv, True, None, False)

    forms = {"dense": lambda q, k, v, kp, vp, tbl:
             paged_attention_dense(q, kp, vp, tbl, zero)}
    if da.paged_supported(q.shape, kp.shape):
        forms["paged"] = lambda q, k, v, kp, vp, tbl: \
            paged_decode_attention(q, kp, vp, tbl, zero)
    if fa.flash_supported(q.shape, (1, Sb, H, D)):
        forms["flash"] = widened
    gqa = getattr(fa, "flash_attention_gqa", None)  # not on an older tree
    if gqa is not None and fa.flash_gqa_supported(q.shape, k.shape):
        forms["flash_gqa"] = lambda q, k, v, kp, vp, tbl: gqa(q, k, v)
        for b in blocks:
            if b < Sb and Sb % b == 0:
                forms[f"flash_gqa_{b}"] = \
                    lambda q, k, v, kp, vp, tbl, b=b: gqa(q, k, v, block=b)
    args = (q, k, v, kp, vp, tbl)
    want = jax.jit(forms["dense"])(*args).astype(jnp.float32)
    for name, form in forms.items():
        got = jax.jit(form)(*args).astype(jnp.float32)

        @jax.jit
        def prog(q, k, v, kp, vp, tbl, form=form):
            def body(q, _):
                o = form(q, k, v, kp, vp, tbl)
                return (q + o * 1e-3).astype(q.dtype), None

            return lax.scan(body, q, None, length=STEPS)[0]

        us = _best(prog, *args) * 1e6
        line = {"shape": f"prefill_{Sb}", "form": name, "Sq": Sb,
                "kv_heads": KV, "q_heads": H, "table_pages": NPAGES,
                "us_per_call": round(us, 1),
                "max_err_vs_dense": round(
                    float(jnp.abs(got - want).max()), 4),
                "mean_err_vs_dense": round(
                    float(jnp.abs(got - want).mean()), 6)}
        if name.startswith("flash"):
            line["mxu_share_pct"] = round(
                100 * 2 * Sb * Sb * D * H / (us * 1e-6) / peaks.flops, 1)
        print(json.dumps(line), flush=True)


def window_prefill_readings(Sb, window, r, peaks):
    """A hybrid model's prompt of ``Sb`` rows to itself (PR 46), a window
    layer's band and a full layer's triangle, each as
    ``flash_attention_gqa`` and as the ``lax`` blocks it replaces
    (``blockwise_causal_attention``): us a call, us a head-pair (a 512 x
    512 block pair that holds a visible key, one query head), the flash
    form's share of the MXU by ``4 D H`` operations a visible (row, key).
    On a tree whose kernel knows no window only the full layer's flash
    form is timed."""
    import inspect

    from paddle_tpu.ops.blockwise_attention import \
        blockwise_causal_attention
    from paddle_tpu.ops.pallas import flash_attention as fa

    q = jnp.asarray(r.randn(1, Sb, H, D), jnp.bfloat16)
    k = jnp.asarray(r.randn(1, Sb, KV, D), jnp.bfloat16)
    v = jnp.asarray(r.randn(1, Sb, KV, D), jnp.bfloat16)
    scale = D ** -0.5
    windowed = "window" in inspect.signature(
        fa.flash_attention_gqa).parameters
    for w in (window, None):
        forms = {"lax": lambda q, k, v, w=w: blockwise_causal_attention(
            q, k, v, scale, w)}
        if w is None or windowed:
            kw = {} if w is None else {"window": w}
            forms["flash_gqa"] = lambda q, k, v, kw=kw: \
                fa.flash_attention_gqa(q, k, v, scale=scale, **kw)
        n = Sb // 512
        seen = [(i, j) for i in range(n) for j in range(i + 1)
                if w is None or j * 512 + 511 > i * 512 - w]
        t = np.arange(Sb)
        keys = int((np.minimum(t + 1, w) if w else t + 1).sum())
        want = jax.jit(forms["lax"])(q, k, v).astype(jnp.float32)
        for name, form in forms.items():
            got = jax.jit(form)(q, k, v).astype(jnp.float32)

            @jax.jit
            def prog(q, k, v, form=form):
                def body(q, _):
                    return (q + form(q, k, v) * 1e-3).astype(q.dtype), None

                return lax.scan(body, q, None, length=STEPS)[0]

            us = _best(prog, q, k, v) * 1e6
            line = {"shape": f"prefill_{Sb}", "form": name, "window": w,
                    "Sq": Sb, "kv_heads": KV, "q_heads": H,
                    "us_per_call": round(us, 1), "block_pairs": len(seen),
                    "us_per_head_pair": round(us / (len(seen) * H), 3),
                    "max_err_vs_lax": round(
                        float(jnp.abs(got - want).max()), 4)}
            if name != "lax":
                line["mxu_share_pct"] = round(
                    100 * 4 * keys * D * H / (us * 1e-6) / peaks.flops, 1)
            print(json.dumps(line), flush=True)


def main():
    global KV, H, D
    ap = argparse.ArgumentParser()
    ap.add_argument("--window", type=int, default=None)
    ap.add_argument("--kv-width", type=int, default=D)
    ap.add_argument("--kv-heads", type=int, default=KV)
    ap.add_argument("--q-heads", type=int, default=H)
    ap.add_argument("--prefill", type=int, nargs="*", default=None,
                    help="time only the prefill forms at these buckets "
                         "(none named: 128 256 512 1024 2048)")
    ap.add_argument("--gqa-block", type=int, nargs="*", default=(),
                    help="further block sizes of flash_attention_gqa")
    args = ap.parse_args()
    KV, H, D = args.kv_heads, args.q_heads, args.kv_width
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(json.dumps({"error": f"needs a TPU, found {dev.platform}"}))
        return 1
    peaks = peaks_for(dev.device_kind)
    print(json.dumps({"device": dev.device_kind, "steps": STEPS}),
          flush=True)
    r = np.random.RandomState(0)
    cases = shapes(r) if args.window is None \
        else window_shapes(r, args.window)
    if args.prefill is None:
        for name, Sq, lens in cases:
            reading(name, Sq, lens, r, peaks.hbm_bytes, args.window)
    if args.window is None and D == DV:
        for Sb in args.prefill or (128, 256, 512, 1024, 2048):
            prefill_readings(Sb, r, peaks, args.gqa_block)
    elif args.prefill is not None and D == DV:
        for Sb in args.prefill or (2048, 4096, 8192):
            window_prefill_readings(Sb, args.window, r, peaks)
    return 0


if __name__ == "__main__":
    sys.exit(main())
