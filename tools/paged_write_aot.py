"""Does the page-pool write compile in place on a v5e? (no chip needed)

Compiles, with the TPU compiler installed beside JAX and a DESCRIBED
v5e:2x2 topology, two layers of ``paged_kv_write`` + the attention that
reads the pool, pools donated — the shape of every ``ServingEngine``
program — and counts the ops of the optimized HLO named ``copy`` whose
result has the pool's shape. Such a copy is a layout change of the whole
pool: its bytes scale with the pool, not with the rows written
(PERF.md, PR 26). Also lists the parameters the compiled program
aliases to its outputs: the pools, and nothing else. Prints one JSON line, ``{"cases": [...]}`` or
``{"skipped": why}`` where the topology cannot be described. Beside a
case that runs ``paged_decode_attention``: the KV heads one of its
fetches brings and the VMEM bytes the kernel counts for that block.

Run it in a process of its own (``tests/test_paged_kv_write.py`` does):
only one process at a time may load the TPU's library, and it keeps it.

    python tools/paged_write_aot.py            # the forms the program uses
    python tools/paged_write_aot.py --old      # the advanced-index scatter
"""
import json
import os
import sys

LAYERS = 2
# (name, B, S, page, dtype, attention): the benchmark's serving cells
# (512 pages of 128, 8 KV heads of 128, context 2432), then other pages
# and dtypes at the decode shape
CASES = [
    ("decode_48x1", 48, 1, 128, "bfloat16", "kernel"),
    # as the engine's decode program takes them: the tables and the
    # offsets are columns of ONE [B, npages + 3] array, read by every
    # layer and not donated
    ("decode_48x1_round", 48, 1, 128, "bfloat16", "kernel"),
    ("prefill_1x512", 1, 512, 128, "bfloat16", "kernel"),
    ("prefill_1x64", 1, 64, 128, "bfloat16", "kernel"),
    ("prefill_1x2048_dense", 1, 2048, 128, "bfloat16", "dense"),
    # what a prompt over 512 tokens runs since PR 43: the pages written,
    # the attention over the fresh K/V and not through the pool
    ("prefill_1x2048_flash", 1, 2048, 128, "bfloat16", "flash"),
    ("decode_page64", 48, 1, 64, "bfloat16", "kernel"),
    ("decode_page16_f32", 48, 1, 16, "float32", "kernel"),
    ("decode_page8_f32", 48, 1, 8, "float32", "kernel"),
    # the unified chunked step: ``valid`` and the trailing trash column
    ("unified_8x256_valid", 8, 256, 128, "bfloat16", "ragged"),
]
P, KV, H, D, CONTEXT = 512, 8, 32, 128, 2432


def main(argv):
    # what the TPU compiler needs to describe a chip that is not there
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    os.environ.setdefault("TPU_ACCELERATOR_TYPE", "v5litepod-4")
    os.environ.setdefault("TPU_WORKER_HOSTNAMES", "localhost")
    os.environ["JAX_PLATFORMS"] = "cpu"
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    sys.path.insert(0, root)
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no libtpu, or another process holds it
        print(json.dumps({"skipped": f"{type(e).__name__}: {e}"[:300]}))
        return 0
    from paddle_tpu.inference.serving import ServingEngine
    from paddle_tpu.ops.pallas import decode_attention as da
    from paddle_tpu.ops.pallas import flash_attention as fa
    from paddle_tpu.ops.pallas import ragged_paged_attention as ra

    write = da.paged_kv_write
    if "--old" in argv:     # the reference the tests compare with
        sys.path.insert(0, os.path.join(root, "tests"))
        from test_paged_kv_write import scatter_reference

        def write(kp, vp, kn, vn, *where):
            return (scatter_reference(kp, kn, *where),
                    scatter_reference(vp, vn, *where))
    dev = SingleDeviceSharding(topo.devices[0])
    out = []
    for name, B, S, page, dtype, attn in CASES:
        dt = jnp.dtype(dtype)
        npages = CONTEXT // page
        pools = P * 128 // page           # the same bytes at every page
        prefill = S > 1
        kernel = {"kernel": "paged_decode_attention",
                  "ragged": "ragged_paged_attention",
                  "flash": "flash_attention_fwd_gqa"}.get(attn)

        one_array = name.endswith("_round")

        def prog(q, new, pools_, tables, off, nv):
            if one_array:
                tables, off = tables[:, :npages], tables[:, npages]
            acc = jnp.zeros(q.shape, jnp.float32)
            done = []
            for kp, vp in pools_:
                if attn == "ragged":
                    kp, vp = write(kp, vp, new, new, tables, off, nv)
                    o = ra.ragged_paged_attention(q, kp, vp, tables[:, :-1],
                                                  off, nv)
                else:
                    kp, vp = write(kp, vp, new, new, tables,
                                   0 if prefill else off)
                    if attn == "flash":
                        o = fa.flash_attention_gqa(q, new, new)
                    else:
                        attend = (da.paged_decode_attention
                                  if attn == "kernel"
                                  else da.paged_attention_dense)
                        o = attend(q, kp, vp, tables, off)
                acc = acc + o.astype(jnp.float32)
                done.append((kp, vp))
            return acc, done

        sds = lambda shape, d: jax.ShapeDtypeStruct(shape, d, sharding=dev)
        pool = sds((pools, KV, page, D), dt)
        args = (sds((B, S, H, D), dt), sds((B, S, KV, D), dt),
                [(pool, pool)] * LAYERS,
                sds((B, npages + (attn == "ragged") + 3 * one_array),
                    jnp.int32),
                sds((B,), jnp.int32), sds((B,), jnp.int32))
        compiled = jax.jit(prog, donate_argnums=(2,)).lower(*args).compile()
        text = compiled.as_text()
        cost = compiled.cost_analysis() or {}
        if isinstance(cost, (list, tuple)):
            cost = cost[0] if cost else {}
        out.append({
            "case": name,
            "pool_copies": ServingEngine.pool_copies(
                text, (pools, KV, page, D)),
            "pool_bytes": pools * KV * page * D * dt.itemsize,
            "bytes_accessed": float(cost.get("bytes accessed", -1)),
            "kernel": kernel is not None and kernel in text,
            "donated": ServingEngine.donated_params(text),
        })
        if attn == "kernel":    # by the kernel's own count and plan
            plan = da._paged_plan(S, H // KV, KV, page, D, dt.itemsize)
            out[-1].update(
                head_block=plan.hb, depth=plan.depth,
                in_flight_bytes=plan.in_flight_bytes,
                vmem_bytes=da._paged_vmem_bytes(
                    plan.hb, S, H // KV, page, D, dt.itemsize,
                    depth=plan.depth))
    print(json.dumps({"cases": out, "old": "--old" in argv}))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
