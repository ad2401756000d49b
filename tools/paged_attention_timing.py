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
- ``prefill_<Sb>``: one row of Sb new tokens at offset 0, the prefill
  programs' call.
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
from benchmarks.harness.peaks import peaks_for  # noqa: E402
from paddle_tpu.ops.pallas.decode_attention import (  # noqa: E402
    paged_attention_dense, paged_decode_attention)

P, KV, H, PAGE, D, NPAGES = 512, 8, 32, 128, 128, 19
STEPS, CALLS = 64, 5


def shapes(r):
    live = r.permutation(48)[:19]
    chat = np.zeros(48, np.int32)
    chat[live] = r.randint(256, 1600, 19)
    yield "chat", 1, chat
    yield "longprompt", 1, r.randint(1100, 2100, 16).astype(np.int32)
    yield "full", 1, r.randint(2200, 2400, 48).astype(np.int32)
    for Sb in (64, 512):
        yield f"prefill_{Sb}", Sb, np.zeros(1, np.int32)


def reading(name, Sq, lens, r, peak):
    B = len(lens)
    lengths = jnp.asarray(lens)
    tbl = jnp.asarray(np.stack([r.permutation(P - 1)[:NPAGES]
                                for _ in range(B)]), jnp.int32)
    q = jnp.asarray(r.randn(B, Sq, H, D), jnp.bfloat16)
    kp = jnp.asarray(r.randn(P, KV, PAGE, D), jnp.bfloat16)
    vp = jnp.asarray(r.randn(P, KV, PAGE, D), jnp.bfloat16)

    got = paged_decode_attention(q, kp, vp, tbl, lengths)
    want = paged_attention_dense(q, kp, vp, tbl, lengths)
    err = float(jnp.abs(got.astype(jnp.float32)
                        - want.astype(jnp.float32)).max())

    @jax.jit
    def prog(q, kp, vp):
        def body(q, _):
            o = paged_decode_attention(q, kp, vp, tbl, lengths)
            return (q + o * 1e-3).astype(q.dtype), None

        return lax.scan(body, q, None, length=STEPS)[0]

    prog(q, kp, vp).block_until_ready()
    best = float("inf")
    for _ in range(CALLS):
        t0 = time.perf_counter()
        prog(q, kp, vp).block_until_ready()
        best = min(best, time.perf_counter() - t0)
    pages = int(((lens + Sq - 1) // PAGE + 1).sum())
    nbytes = 2 * pages * KV * PAGE * D * 2
    us = best / STEPS * 1e6
    print(json.dumps({
        "shape": name, "rows": B, "Sq": Sq, "us_per_call": round(us, 1),
        "pages_referenced": pages, "us_per_page": round(us / pages, 3),
        "hbm_share_pct": round(100 * nbytes / (us * 1e-6) / peak, 1),
        "max_err_vs_dense": round(err, 4)}), flush=True)


def main():
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(json.dumps({"error": f"needs a TPU, found {dev.platform}"}))
        return 1
    peak = peaks_for(dev.device_kind).hbm_bytes
    print(json.dumps({"device": dev.device_kind, "steps": STEPS}),
          flush=True)
    r = np.random.RandomState(0)
    for name, Sq, lens in shapes(r):
        reading(name, Sq, lens, r, peak)
    return 0


if __name__ == "__main__":
    sys.exit(main())
