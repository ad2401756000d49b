"""Time the forms of the page-pool write on the chip (PERF.md, PR 26).

At the serving cells' shapes (512 pages of 128, 8 KV heads of 128, bf16,
context 2432): ``LAYERS`` layers of K and V pools, donated, written
``STEPS`` times in one ``lax.scan`` so that dispatch does not count.
Prints one JSON line a reading: microseconds a pool write, and what that
makes of a 16-layer program.

    chiprun -- python tools/paged_write_timing.py

- ``helper``: ``paged_kv_write`` as the programs call it (rows at a
  traced offset for decode, whole pages at the static offset 0 for
  prefill);
- ``rows``: the row form on a prefill, which a traced zero offset takes;
- ``dus_loop``: one ``dynamic_update_slice`` a row in a ``fori_loop``;
- ``old``: the advanced-index scatter the helper replaced (the tests'
  reference), with its two whole-pool layout copies a pool.
"""
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "tests")]

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from jax import lax  # noqa: E402

import paddle_tpu  # noqa: E402,F401  (places the compile cache)
from paddle_tpu.ops.pallas.decode_attention import paged_kv_write  # noqa: E402
from test_paged_kv_write import scatter_reference  # noqa: E402

P, KV, PAGE, D, NPAGES = 512, 8, 128, 128, 19
LAYERS, STEPS, CALLS = 4, 32, 5


def old_write(kp, vp, kn, vn, tbl, off):
    return (scatter_reference(kp, kn, tbl, off),
            scatter_reference(vp, vn, tbl, off))


def dus_write(kp, vp, kn, vn, tbl, off):
    B, S = kn.shape[:2]
    off = jnp.broadcast_to(jnp.asarray(off, jnp.int32).reshape(-1), (B,))
    pos = off[:, None] + jnp.arange(S, dtype=jnp.int32)[None]
    pid = jnp.take_along_axis(tbl, pos // PAGE, axis=1).reshape(-1)
    slot = (pos % PAGE).reshape(-1)
    kn = kn.reshape(B * S, 1, KV, 1, D)
    vn = vn.reshape(B * S, 1, KV, 1, D)

    def body(i, pools):
        at = (pid[i], 0, slot[i], 0)
        return (lax.dynamic_update_slice(pools[0], kn[i], at),
                lax.dynamic_update_slice(pools[1], vn[i], at))

    return lax.fori_loop(0, B * S, body, (kp, vp))


def reading(form, write, B, S):
    prefill = S > 1

    def prog(pools, new, tbl, off):
        def body(carry, _):
            pools, off = carry
            at = off if not prefill else off * 0 if form == "rows" else 0
            pools = [write(kp, vp, new, new, tbl, at) for kp, vp in pools]
            return (pools, off + (not prefill)), None

        (pools, _), _ = lax.scan(body, (pools, off), None, length=STEPS)
        return pools

    fn = jax.jit(prog, donate_argnums=(0,))
    r = np.random.RandomState(0)
    pool = lambda: jnp.zeros((P, KV, PAGE, D), jnp.bfloat16)
    pools = [(pool(), pool()) for _ in range(LAYERS)]
    new = jnp.asarray(r.randn(B, S, KV, D), jnp.bfloat16)
    tbl = jnp.asarray(r.randint(0, P - 1, (B, NPAGES)), jnp.int32)
    off = jnp.asarray(r.randint(0, 2000, (B,)), jnp.int32)
    pools = jax.block_until_ready(fn(pools, new, tbl, off))     # compile
    t0 = time.perf_counter()
    for _ in range(CALLS):
        pools = fn(pools, new, tbl, off)
    jax.block_until_ready(pools)
    us = (time.perf_counter() - t0) / CALLS / (STEPS * LAYERS * 2) * 1e6
    print(json.dumps({"form": form, "B": B, "S": S,
                      "us_per_pool_write": round(us, 1),
                      "ms_per_16_layer_program": round(us * 32 / 1e3, 3)}),
          flush=True)


def main():
    print(json.dumps({"devices": [str(d) for d in jax.devices()]}))
    forms = (("helper", paged_kv_write), ("rows", paged_kv_write),
             ("dus_loop", dus_write), ("old", old_write))
    for form, write in forms:
        for B, S in ((48, 1), (16, 1), (1, 64), (1, 512), (1, 2048)):
            if (form, S > 1) in (("rows", False), ("dus_loop", True)):
                continue        # the helper's own form; 2048 loop trips
            reading(form, write, B, S)


if __name__ == "__main__":
    main()
