"""Do the serving programs of an expert decoder compile for a v5e, in
place and with its kernels? (no chip needed)

Builds the model of ``--config`` (``sarvam-105b``: ``MLAMoEForCausalLM``,
the latent-attention decoder; ``mimo-v2-flash`` and ``trinity-mini``:
``HybridMoEForCausalLM``, window and full layers over two classes of
pages; ``keye-vl-2.0-30b-a3b``: the same model with layers that select
keys by a learned index and pool a third array; ``deepseek-v3.2-exp``:
``MLAMoEForCausalLM`` with a query latent and an index that selects rows
of the latent cache; ``longcat-flash-omni``: the same class as
shortcut-connected double layers, ``--layers`` of them, two pooled
tuples and two kernel calls each; ``nemotron-3-super-120b-a12b``:
``SSMMoEForCausalLM``, the first ``--layers`` mixers of its pattern,
whose state arrays count among the pools: a copy of one shows in
``pool_copies``) at the benchmark
configuration's widths (``benchmarks/configs/<config>.json``) with
``--layers`` layers (2: the
dense layer and one expert layer) and NO weights (``LazyGuard``), takes
``ServingEngine``'s own decode and prefill programs, and compiles them
with the TPU compiler installed beside JAX for a DESCRIBED v5e:2x2
topology, as ``tools/paged_write_aot.py`` does for the page-pool write.
Per program: pool-shaped ``copy`` ops in the optimized HLO (0 = the
pools of every page class are written in place), which of the decode
kernels (``mla_paged_decode_attention``,
``mla_paged_sparse_decode_attention``, the prefill's
``kept_flash_attention``, ``paged_decode_attention``,
``paged_window_decode_attention``, ``paged_sparse_decode_attention``,
and ``grouped_matmul``, the prefill's sorted expert products since PR 49)
and whether XLA's grouped matmul
(``ragged-dot``) are in it (the prefill programs' expert layers sort and
group, on our kernel where its gate admits the widths and on XLA's where
not; a decode step's are batched over the held experts and hold neither),
copies or transposes of a stacked expert weight array (0: the batched
products read ``[El, d, h]`` and ``[El, h, d]`` as they lie), the largest
float32 buffer (a prefill program
of the window/full decoder holds no ``[heads, S, S]`` one), and the compiler's
memory analysis, and the parameters it aliases to outputs: the pools and
the routing counters of the decode program, and not its round array. Prints one JSON line, ``{"programs": [...]}`` or
``{"skipped": why}``.

    python tools/mla_serving_aot.py [--config mimo-v2-flash] [--layers 6]
        [--batch 128] [--prefill 2048] [--dump DIR]
"""
import argparse
import json
import os
import sys


def main(argv):
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", default="sarvam-105b")
    ap.add_argument("--layers", type=int, default=2)
    ap.add_argument("--batch", type=int, default=128)
    ap.add_argument("--prefill", type=int, default=1024)
    ap.add_argument("--dump", help="write each program's optimized HLO "
                    "into this directory")
    args = ap.parse_args(argv)
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
    import importlib
    import math
    import re

    import paddle_tpu as paddle
    from paddle_tpu.inference import (Config, ServingEngine,
                                      create_predictor)
    from paddle_tpu.ops import pallas

    # trace the programs the chip would run: the kernels' own gates
    # still decide, the platform question is answered for the target
    pallas.is_tpu_platform = lambda: True
    cfg = json.load(open(os.path.join(
        root, "benchmarks", "configs", args.config + ".json")))
    if "num_layers" in cfg:     # the source's depth key; the file's
        # num_hidden_layers counts its attention sublayers (two a layer)
        per = cfg["num_hidden_layers"] // cfg["num_layers"]
        cfg["num_layers"] = args.layers
        cfg["num_hidden_layers"] = per * args.layers
    else:
        cfg["num_hidden_layers"] = args.layers
    if "layers_run" in cfg:     # a depth cut that names published layers
        cfg["layers_run"] = cfg["layers_run"][:args.layers]
    if "hybrid_override_pattern" in cfg:    # a mixer kind a character
        cfg["hybrid_override_pattern"] = \
            cfg["hybrid_override_pattern"][:args.layers]
    srv = cfg["serving"]
    fam = importlib.import_module(
        "benchmarks.harness.families." + cfg["family"])
    mcfg = fam.model_config(cfg, srv["max_length"])
    models = importlib.import_module("paddle_tpu.models")
    paddle.set_default_dtype(cfg["torch_dtype"])
    with paddle.LazyGuard():
        model = getattr(models, type(mcfg).__name__.replace(
            "Config", "ForCausalLM"))(mcfg)
    conf = Config().set_model(model).enable_paged_kv(
        page_size=srv["page_size"])
    conf.max_length = srv["max_length"]
    pred = create_predictor(conf)
    eng = ServingEngine(pred, max_batch=args.batch,
                        pool_pages=srv["pool_pages"])
    dev = SingleDeviceSharding(topo.devices[0])

    def sds(a, dtype=None):
        return jax.ShapeDtypeStruct(tuple(a.shape), dtype or a.dtype,
                                    sharding=dev)

    pvals = tuple(sds(p._value) for p in pred._params)
    B, npages, ring = eng.B, eng.cache.npages, eng.cache.ring

    i32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32,
                                              sharding=dev)
    rng = jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=dev)
    # the decode program: what the cache lends (pools and counters,
    # donated), one round array (tables, pos, host token, mask: not
    # donated), the token feed and the key
    state = jax.tree_util.tree_map(sds, eng.cache.lend())
    programs = {
        "decode": (eng._decode_step_fn(),
                   (pvals, state, i32(B, npages + ring + 3), i32(B), rng)),
        f"prefill_{args.prefill}": (
            pred._prefill_fn(1, args.prefill, eng.M),
            (pvals, i32(1, args.prefill),
             # a state layer is bound the slot it writes, not a table
             [tuple(map(sds, layer)) + (i32(1) if st else i32(1, npages),)
              for layer, st in zip(eng.pools, eng.cache.state_layers)],
             i32(1))),
    }
    kernels = ("mla_paged_decode_attention",
               "mla_paged_sparse_decode_attention", "kept_flash_attention",
               "paged_decode_attention", "paged_window_decode_attention",
               "paged_sparse_decode_attention", "grouped_matmul")
    pool_shapes = {s.shape for pair in eng.pools for s in pair}
    # the held experts' stacked weights, [El, d, h] and [El, h, d] (and
    # [El * h, d], as the batched down product reads them): an op named
    # for a copy or a transpose with such a result moves 268 or 537 MB
    expert_shapes = {tuple(p._value.shape) for p in pred._params
                     if len(p._value.shape) == 3}
    expert_shapes |= {(s[0] * s[1], s[2]) for s in expert_shapes}

    def weight_copies(text, dims):
        return len(re.findall(
            r"^\s*(?:ROOT\s+)?%?[\w.\-]*(?:copy|transpose)[\w.\-]*\s*=\s*"
            r"\w+\[" + ",".join(str(d) for d in dims) + r"\]", text, re.M))

    out = []
    for name, (fn, avals) in programs.items():
        compiled = fn.lower(*avals).compile()
        text = compiled.as_text()
        if args.dump:
            os.makedirs(args.dump, exist_ok=True)
            with open(os.path.join(args.dump, name + ".hlo"), "w") as f:
                f.write(text)
        mem = compiled.memory_analysis()
        out.append({
            "program": name,
            "pool_copies": sum(ServingEngine.pool_copies(text, s)
                               for s in pool_shapes),
            # by the op_name of its call, not by the function-name
            # table (a wrapper's name is there whatever was traced)
            "kernels": [k for k in kernels
                        if f"/{k}/pallas_call" in text],
            "ragged_dot": "ragged-dot" in text,
            "expert_weight_copies": sum(
                weight_copies(text, s) for s in expert_shapes),
            "largest_f32_elements": max(
                (math.prod(int(n) for n in d.split(",") if n)
                 for d in re.findall(r"f32\[([\d,]*)\]", text)),
                default=0),
            "argument_gib": mem.argument_size_in_bytes / 2 ** 30,
            "temp_gib": mem.temp_size_in_bytes / 2 ** 30,
            "alias_gib": mem.alias_size_in_bytes / 2 ** 30,
            # what the program writes in place: all of what was lent
            # and, in decode, nothing else
            "donated": ServingEngine.donated_params(text),
        })
    print(json.dumps({"layers": args.layers, "batch": B, "programs": out}))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
