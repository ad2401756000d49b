"""Benchmarks for the BASELINE.md configs, one JSON line each.

Covered rows (BASELINE.md):
  1. ResNet-50 single chip ............ imgs/sec            (train step)
  2. GPT-3 1.3B Fleet TP .............. tokens/sec/chip, MFU (headline,
     printed LAST so single-line parsers keep seeing it)
  4. ERNIE-MoE style GPT-MoE .......... tokens/sec/chip
  5. Llama-7B generation .............. decode tokens/sec, ms/token
     (compiled prefill + single-XLA-program scan decode, Pallas
     decode-attention kernel, ctx 2048)
Row 3 (13B hybrid TP*PP*DP) needs real multi-chip hardware - TBD.

MFU = 6*N*tok_s/peak (recompute FLOPs excluded, so remat lowers measured
MFU honestly); vs_baseline for the MFU line is measured/0.45 (the
north-star target — the reference publishes no absolute numbers,
BASELINE.md). The decode line's vs_baseline is the fraction of the
HBM-bandwidth roofline (params_bytes / BW per token) achieved.

Without a TPU every bench exits non-zero and says which platform it
found. ``BENCH_FORCE_CPU=1`` is the caller asking, by name, for the tiny
CPU configs (counts and parity lines, never a rate). A bench that
raises, times out or cannot start makes ``python bench.py`` exit
non-zero after the remaining benches have run.
"""
import json
import sys
import time

import numpy as np


def _chip(device):
    """(peak bf16 FLOP/s, HBM bytes/s) from the one peaks table; an
    unknown TPU kind raises, the CPU is explicit zeros."""
    from paddle_tpu.observability.flops import peak_flops_per_chip

    return peak_flops_per_chip(device)


def _emit(payload):
    print(json.dumps(payload), flush=True)


def _telemetry_section():
    """Compact snapshot of the unified observability registry
    (paddle_tpu/observability) — bench lines carry the SAME metrics a
    live scrape would see: histograms as count/p50/p99, counters and
    gauges as values. Each bench runs in its own process, so the
    registry holds exactly that bench's run."""
    try:
        from paddle_tpu.observability import get_registry

        snap = get_registry().snapshot()
    except Exception:
        return {}
    out = {}
    for name, entry in sorted(snap["metrics"].items()):
        short = name.replace("paddle_tpu_", "", 1)
        for row in entry["series"]:
            lbl = ",".join(f"{k}={v}"
                           for k, v in sorted(row["labels"].items()))
            key = short + (f"{{{lbl}}}" if lbl else "")
            if entry["type"] == "histogram":
                if row["count"]:
                    out[key] = {"count": row["count"],
                                "p50": round(row["p50"], 6),
                                "p99": round(row["p99"], 6)}
            else:
                v = row["value"]
                out[key] = round(v, 6) if isinstance(v, float) else v
    return out


# ---------------------------------------------------------------------------
# 1. ResNet-50 (BASELINE row 1)
# ---------------------------------------------------------------------------
def bench_resnet(on_tpu, dev):
    import paddle_tpu as paddle
    from paddle_tpu.distributed import fleet
    from paddle_tpu.distributed.engine import ParallelEngine
    from paddle_tpu import nn
    from paddle_tpu.vision.models import resnet18, resnet50

    if on_tpu:
        model_fn, B, steps = resnet50, 256, 5
    else:
        model_fn, B, steps = resnet18, 8, 2

    paddle.seed(0)
    model = model_fn(num_classes=1000 if on_tpu else 10)
    if on_tpu:
        model.astype("bfloat16")
    opt = paddle.optimizer.Momentum(learning_rate=0.1, momentum=0.9,
                                    parameters=model.parameters())
    strategy = fleet.DistributedStrategy()
    strategy.hybrid_configs = {"dp_degree": 1, "mp_degree": 1}
    hcg = fleet.init(is_collective=True, strategy=strategy)
    eng = ParallelEngine(model, opt, hcg.mesh)
    step = eng.train_step(
        lambda m, b: nn.functional.cross_entropy(m(b["x"]), b["y"]))

    r = np.random.RandomState(0)
    hw = 224 if on_tpu else 32
    batch = {
        "x": paddle.to_tensor(
            r.rand(B, 3, hw, hw).astype(
                "float32" if not on_tpu else "bfloat16")),
        "y": paddle.to_tensor(r.randint(0, 1000 if on_tpu else 10, (B,))),
    }
    loss = step(batch)
    float(loss)
    t0 = time.perf_counter()
    for _ in range(steps):
        loss = step(batch)
    float(loss)
    dt = time.perf_counter() - t0
    imgs_s = B * steps / dt
    _emit({
        "metric": "resnet50_train_imgs_per_sec" if on_tpu
        else "resnet_smoke_imgs_per_sec",
        "value": round(imgs_s, 2),
        "unit": "imgs/s",
        "vs_baseline": 0.0,  # reference publishes no number (BASELINE.md)
        "batch": B,
        "device": str(getattr(dev, "device_kind", dev.platform)),
    })


# ---------------------------------------------------------------------------
# 4. GPT-MoE (ERNIE-MoE style, BASELINE row 4)
# ---------------------------------------------------------------------------
def bench_moe(on_tpu, dev):
    import paddle_tpu as paddle
    from paddle_tpu.distributed import fleet
    from paddle_tpu.distributed.engine import ParallelEngine
    from paddle_tpu.models import (GPTConfig, GPTForCausalLM,
                                   GPTPretrainingCriterion)

    if on_tpu:
        cfg = GPTConfig(vocab_size=50304, hidden_size=1024, num_layers=12,
                        num_heads=16, max_position_embeddings=1024,
                        dtype="bfloat16", num_experts=8, moe_every=2)
        B, S, steps = 8, 1024, 5
    else:
        from paddle_tpu.models import gpt_moe_tiny

        cfg = gpt_moe_tiny()
        B, S, steps = 4, 16, 2

    paddle.seed(0)
    model = GPTForCausalLM(cfg)
    crit = GPTPretrainingCriterion(cfg)
    opt = paddle.optimizer.AdamW(learning_rate=1e-4,
                                 parameters=model.parameters(),
                                 state_dtype="bfloat16" if on_tpu else None)
    strategy = fleet.DistributedStrategy()
    strategy.hybrid_configs = {"dp_degree": 1, "mp_degree": 1}
    hcg = fleet.init(is_collective=True, strategy=strategy)
    eng = ParallelEngine(model, opt, hcg.mesh)
    step = eng.train_step(
        lambda m, b: crit(m(b["x"]), b["y"]) + m.aux_loss)

    r = np.random.RandomState(0)
    ids = r.randint(0, cfg.vocab_size, (B, S + 1))
    batch = {"x": paddle.to_tensor(ids[:, :-1]),
             "y": paddle.to_tensor(ids[:, 1:])}
    loss = step(batch)
    float(loss)
    t0 = time.perf_counter()
    for _ in range(steps):
        loss = step(batch)
    float(loss)
    dt = time.perf_counter() - t0
    tok_s = B * S * steps / dt
    _emit({
        "metric": "gpt_moe_train_tokens_per_sec" if on_tpu
        else "moe_smoke_tokens_per_sec",
        "value": round(tok_s, 2),
        "unit": "tokens/s",
        "vs_baseline": 0.0,  # reference publishes no number (BASELINE.md)
        "num_experts": cfg.num_experts,
        "device": str(getattr(dev, "device_kind", dev.platform)),
    })


# ---------------------------------------------------------------------------
# 5. Llama-7B generation (BASELINE row 5)
# ---------------------------------------------------------------------------
def bench_llama_decode(on_tpu, dev, weight_only=False):
    import paddle_tpu as paddle
    from paddle_tpu.inference import Config, create_predictor
    from paddle_tpu.models.llama import LlamaForCausalLM, llama_7b, \
        llama_tiny

    peak, hbm_bw = _chip(dev)
    old_dtype = paddle.get_default_dtype()
    if on_tpu:
        paddle.set_default_dtype("bfloat16")
        cfg = llama_7b(max_position_embeddings=2304, dtype="bfloat16")
        S_ctx, n_new = 2048, 128
    else:
        cfg = llama_tiny()
        S_ctx, n_new = 24, 8
    try:
        paddle.seed(0)
        model = LlamaForCausalLM(cfg)
        conf = Config().set_model(model)
        if weight_only:
            conf.enable_weight_only("weight_only_int8")
        pred = create_predictor(conf)
        r = np.random.RandomState(0)
        prompt = paddle.to_tensor(
            r.randint(0, cfg.vocab_size, (1, S_ctx)))

        # warm both programs, then time prefill-only and prefill+decode
        float(pred.generate(prompt, max_new_tokens=1)._value[0, -1])
        float(pred.generate(prompt, max_new_tokens=n_new)._value[0, -1])
        t0 = time.perf_counter()
        out = pred.generate(prompt, max_new_tokens=1)
        float(out._value[0, -1])
        t_prefill = time.perf_counter() - t0
        t0 = time.perf_counter()
        out = pred.generate(prompt, max_new_tokens=n_new)
        float(out._value[0, -1])
        t_full = time.perf_counter() - t0
        dec_s = max(t_full - t_prefill, 1e-4)
        tok_s = (n_new - 1) / dec_s
        ms_tok = dec_s / (n_new - 1) * 1e3
        # decode is HBM-bound: roofline = BW / bytes-touched-per-token.
        # vs_baseline is ALWAYS the bf16 (2-byte) roofline fraction, so
        # the int8 line shows its win as a fraction > the fp line's
        # (most weights are then 1 byte; the lm_head stays fp).
        n_params = cfg.num_params()
        roofline = (hbm_bw / (2.0 * n_params)) if hbm_bw else 0.0
        name = "llama7b_decode_tokens_per_sec" if on_tpu \
            else "llama_smoke_decode_tokens_per_sec"
        if weight_only:
            name += "_int8"
        _emit({
            "metric": name,
            "value": round(tok_s, 2),
            "unit": "tokens/s",
            "vs_baseline": round(tok_s / roofline, 4) if roofline else 0.0,
            "ms_per_token": round(ms_tok, 2),
            "prefill_s": round(t_prefill, 3),
            "context": S_ctx,
            "params": n_params,
            "device": str(getattr(dev, "device_kind", dev.platform)),
        })
    finally:
        paddle.set_default_dtype(old_dtype)


# ---------------------------------------------------------------------------
# 5b. Ragged serving: B=8 mixed prompt lengths, paged KV cache, per-row
# offsets (the continuous-batching decode the reference serves with
# block_multi_head_attention). int8 weights so 7B + the B=8 pool fits
# v5e HBM.
# ---------------------------------------------------------------------------
def bench_llama_decode_ragged(on_tpu, dev):
    import paddle_tpu as paddle
    from paddle_tpu.inference import Config, create_predictor
    from paddle_tpu.models.llama import LlamaForCausalLM, llama_7b, \
        llama_tiny

    peak, hbm_bw = _chip(dev)
    old_dtype = paddle.get_default_dtype()
    if on_tpu:
        paddle.set_default_dtype("bfloat16")
        cfg = llama_7b(max_position_embeddings=2304, dtype="bfloat16")
        lens = [1024, 896, 768, 640, 512, 384, 320, 256]
        n_new, page = 64, 128
    else:
        cfg = llama_tiny()
        lens = [24, 17, 11, 9]
        n_new, page = 8, 8
    B = len(lens)
    try:
        paddle.seed(0)
        model = LlamaForCausalLM(cfg)
        conf = Config().set_model(model).enable_paged_kv(page_size=page)
        if on_tpu:
            conf.enable_weight_only("weight_only_int8")
        pred = create_predictor(conf)
        r = np.random.RandomState(0)
        S0 = max(lens)
        ids = np.zeros((B, S0), np.int64)
        for b, L in enumerate(lens):
            ids[b, :L] = r.randint(1, cfg.vocab_size, (L,))
        prompt = paddle.to_tensor(ids)
        ln = np.asarray(lens, np.int32)

        float(pred.generate(prompt, max_new_tokens=1,
                            lengths=ln)._value[0, -1])       # warm prefill
        float(pred.generate(prompt, max_new_tokens=n_new,
                            lengths=ln)._value[0, -1])       # warm decode
        t0 = time.perf_counter()
        out = pred.generate(prompt, max_new_tokens=1, lengths=ln)
        float(out._value[0, -1])
        t_prefill = time.perf_counter() - t0
        t0 = time.perf_counter()
        out = pred.generate(prompt, max_new_tokens=n_new, lengths=ln)
        float(out._value[0, -1])
        dec_s = max(time.perf_counter() - t0 - t_prefill, 1e-4)
        tok_s = B * (n_new - 1) / dec_s
        n_params = cfg.num_params()
        # single-row bf16 weight roofline: batching + paging should put
        # aggregate tokens/s well ABOVE 1.0x of it
        roofline = (hbm_bw / (2.0 * n_params)) if hbm_bw else 0.0
        _emit({
            "metric": "llama7b_ragged_paged_decode_tokens_per_sec"
            if on_tpu else "llama_smoke_ragged_paged_decode_tokens_per_sec",
            "value": round(tok_s, 2),
            "unit": "tokens/s",
            "vs_baseline": round(tok_s / roofline, 4) if roofline else 0.0,
            "batch": B, "page_size": page,
            "mixed_lengths": [int(x) for x in lens],
            "prefill_s": round(t_prefill, 3),
            "device": str(getattr(dev, "device_kind", dev.platform)),
        })
    finally:
        paddle.set_default_dtype(old_dtype)


# ---------------------------------------------------------------------------
# 5c. Continuous-batching serving engine over the ragged paged KV cache:
# a mixed-length request stream through ServingEngine (admission /
# eviction / backfill, one shared decode program) vs the same stream
# served sequentially, one request per Predictor.generate. The JSON
# line carries the compile-cache counters: after warmup on one length
# mix, the streamed mixes must add ZERO compiles (program reuse is the
# tracked metric, not just tokens/s).
# ---------------------------------------------------------------------------
def bench_serving_mixed(on_tpu, dev):
    import tempfile

    import paddle_tpu as paddle
    from paddle_tpu.inference import Config, ServingEngine, \
        create_predictor
    from paddle_tpu.models.llama import LlamaForCausalLM, llama_7b, \
        llama_tiny
    from paddle_tpu.observability import timeseries as _ts

    old_dtype = paddle.get_default_dtype()
    if on_tpu:
        paddle.set_default_dtype("bfloat16")
        cfg = llama_7b(max_position_embeddings=2304, dtype="bfloat16")
        warm_mix = [512, 768]
        mixes = [[1024, 896, 640], [512, 384], [768, 320, 256, 640],
                 [896]]
        n_new, page, B, chunk = 64, 128, 8, 8
    else:
        cfg = llama_tiny()
        warm_mix = [7, 12]
        mixes = [[24, 17, 11], [9, 5], [30, 2, 14, 8], [13]]
        n_new, page, B, chunk = 8, 8, 4, 4
    try:
        paddle.seed(0)
        model = LlamaForCausalLM(cfg)
        conf = Config().set_model(model).enable_paged_kv(page_size=page)
        if on_tpu:
            conf.enable_weight_only("weight_only_int8")
        pred = create_predictor(conf)
        r = np.random.RandomState(0)

        def prompts(lens):
            return [r.randint(1, cfg.vocab_size, (L,)) for L in lens]

        # mem_ledger=True: per-executable HBM attribution (prefill per
        # bucket + the shared decode) rides the line below; the
        # recompiles_after_warmup field still gates at 0 with it on
        eng = ServingEngine(pred, max_batch=B, decode_chunk=chunk,
                            mem_ledger=True)
        # durable metrics journal riding alongside (observability/
        # timeseries): the background sampler snapshots the same
        # registry the scrape reads — host-side file IO only, so the
        # recompiles_after_warmup field below still gates at 0 with it
        # attached for the whole measured stream
        ts_smp = _ts.attach_dir(
            tempfile.mkdtemp(prefix="timeseries_serving_"),
            interval_s=0.5)
        for p in prompts(warm_mix):                      # warmup mix
            eng.submit(p, max_new_tokens=n_new)
        eng.run()
        compiles_warm = eng.stats.compiles
        stream = [p for mix in mixes for p in prompts(mix)]
        t0 = time.perf_counter()
        rids = [eng.submit(p, max_new_tokens=n_new) for p in stream]
        done = eng.run()
        dt = max(time.perf_counter() - t0, 1e-4)
        n_tok = sum(len(done[rid].new_tokens) for rid in rids)
        tok_s = n_tok / dt

        # sequential per-request Predictor baseline on the SAME stream
        seq_pred = create_predictor(conf)
        for p in prompts(warm_mix):                      # warm its programs
            seq_pred.generate(paddle.to_tensor(p[None]),
                              max_new_tokens=n_new)
        t0 = time.perf_counter()
        for p in stream:
            out = seq_pred.generate(paddle.to_tensor(p[None]),
                                    max_new_tokens=n_new)
        float(out._value[0, -1])
        seq_dt = max(time.perf_counter() - t0, 1e-4)
        seq_tok_s = len(stream) * n_new / seq_dt

        # the latency percentiles come from the SAME registry a live
        # scrape would read (ServingEngine's TTFT/TPOT histograms)
        snap = eng.metrics_snapshot()["metrics"]

        def _hist(name, q):
            rows = snap[name]["series"]
            return round(rows[0][q], 6) if rows else 0.0

        # per-request lifecycle span percentiles (queued / prefill /
        # decode / e2e) from the bounded trace ring's stage histogram
        spans = {
            row["labels"]["stage"]: {"count": row["count"],
                                     "p50": round(row["p50"], 6),
                                     "p99": round(row["p99"], 6)}
            for row in snap["paddle_tpu_serving_request_stage_seconds"]
            ["series"]}

        # HBM memory ledger + roofline verdict for the serving engine:
        # per-executable byte classes, resident state (params + KV
        # page pool), and the decode round's compute/HBM/ICI bound
        mem = eng.memory_summary()
        roof = eng.roofline_report()

        # close the journal with one guaranteed final sample, then pin
        # the per-sample overhead: bounded host-side cost (snapshot +
        # one flushed JSONL line), never device work
        ts_smp.sample_now()
        ts_stats = ts_smp.stats()
        ts_smp.close()
        assert ts_stats["samples"] >= 1, ts_stats
        assert (ts_stats["overhead_seconds"]
                <= 0.25 * ts_stats["samples"]), ts_stats

        _emit({
            "metric": "serving_mixed_traffic_tokens_per_sec" if on_tpu
            else "serving_smoke_mixed_traffic_tokens_per_sec",
            "value": round(tok_s, 2),
            "unit": "tokens/s",
            # the gate: continuous batching must beat sequential serving
            "vs_baseline": round(tok_s / seq_tok_s, 4),
            "sequential_tokens_per_sec": round(seq_tok_s, 2),
            "ttft_p50_s": _hist("paddle_tpu_serving_ttft_seconds", "p50"),
            "ttft_p99_s": _hist("paddle_tpu_serving_ttft_seconds", "p99"),
            "tpot_p50_s": _hist("paddle_tpu_serving_tpot_seconds", "p50"),
            "tpot_p99_s": _hist("paddle_tpu_serving_tpot_seconds", "p99"),
            "compiles": eng.stats.compiles,
            "cache_hits": eng.stats.cache_hits,
            "recompiles_after_warmup": eng.stats.compiles - compiles_warm,
            "batch": B, "page_size": page, "decode_chunk": chunk,
            "requests": len(stream), "tokens": n_tok,
            "request_spans": spans,
            "request_traces": len(eng.traces),
            "memory": mem,
            "timeseries": {
                "samples": ts_stats["samples"],
                "journal_bytes": ts_stats["journal_bytes"],
                "overhead_seconds": round(
                    ts_stats["overhead_seconds"], 6)},
            "roofline": roof.to_dict(),
            "telemetry": _telemetry_section(),
            "device": str(getattr(dev, "device_kind", dev.platform)),
        })
        # memory-ledger exact gate: the measured KV pool bytes (shard
        # accounting over the live pool arrays) must equal the closed
        # form page_bytes x pool_pages (bench_compare _EXACT)
        st = mem["state"]
        ok = st["kv_pool_bytes"] == st["page_bytes"] * st["pool_pages"]
        _emit({"metric": "serving_mem_pool_parity",
               "value": 1.0 if ok else 0.0, "unit": "pass",
               "vs_baseline": 1.0 if ok else 0.0,
               "kv_pool_bytes": st["kv_pool_bytes"],
               "page_bytes": st["page_bytes"],
               "pool_pages": st["pool_pages"]})
        # sampler cost headline for the serving line (lower-better in
        # bench_compare): the metrics journal rides the whole measured
        # stream, and its wall cost must stay near zero
        _emit({"metric": "serving_mixed_sampler_overhead_seconds",
               "value": round(ts_stats["overhead_seconds"], 6),
               "unit": "s", "vs_baseline": 0.0,
               "samples": ts_stats["samples"],
               "journal_bytes": ts_stats["journal_bytes"],
               "seconds_per_sample": round(
                   ts_stats["overhead_seconds"]
                   / max(ts_stats["samples"], 1), 6)})
    finally:
        paddle.set_default_dtype(old_dtype)


# ---------------------------------------------------------------------------
# 5d. Chunked prefill vs head-of-line prefill under a Poisson
# mixed-length stream (the serving_mixed_traffic line's latency axis):
# long prompts are injected mid-decode into a stream of short requests,
# and the SAME arrival schedule is served twice — chunked prefill ON
# (prompts folded into the unified ragged [B, Sc] step, decode rows
# advancing every round) vs OFF (each arrival's prefill runs as its own
# program, stalling every in-flight decode row). The JSON lines carry
# TPOT p99 for both, the ragged-kernel parity gate, and the memledger
# comparison of the unified program's HBM traffic against the old
# prefill+decode two-program sum.
# ---------------------------------------------------------------------------
def bench_serving_chunked(on_tpu, dev):
    import paddle_tpu as paddle
    from paddle_tpu.inference import Config, ServingEngine, \
        create_predictor
    from paddle_tpu.models.llama import (LlamaConfig, LlamaForCausalLM,
                                         llama_7b)

    old_dtype = paddle.get_default_dtype()
    if on_tpu:
        paddle.set_default_dtype("bfloat16")
        cfg = llama_7b(max_position_embeddings=2304, dtype="bfloat16")
        page, B, Sc = 128, 8, 256
        short_lens, long_len = (64, 96, 128), 1536
        n_short, n_long, new_s, new_l = 24, 3, 32, 16
        rate = 1.2                      # arrivals per decode round
    else:
        # the tiny smoke config with a longer position space so the
        # injected long prompts tower over the short stream (the HOL
        # contrast the line measures)
        cfg = LlamaConfig(vocab_size=256, hidden_size=64, num_layers=2,
                          num_heads=4, num_kv_heads=2,
                          intermediate_size=128,
                          max_position_embeddings=512)
        page, B, Sc = 8, 4, 32
        short_lens, long_len = (6, 9, 12, 15), 192
        n_short, n_long, new_s, new_l = 18, 3, 12, 8
        rate = 0.8
    try:
        paddle.seed(0)
        model = LlamaForCausalLM(cfg)
        conf = Config().set_model(model).enable_paged_kv(page_size=page)
        if on_tpu:
            conf.enable_weight_only("weight_only_int8")
        pred = create_predictor(conf)
        r = np.random.RandomState(7)

        # Poisson arrival schedule in decode-round time: short requests
        # stream steadily, long prompts land mid-decode (the HOL test)
        gaps = r.exponential(1.0 / rate, n_short)
        arrivals = [(float(t), int(r.choice(short_lens)), new_s)
                    for t in np.cumsum(gaps)]
        span = arrivals[-1][0]
        for k in range(n_long):
            arrivals.append((span * (k + 1.0) / (n_long + 1.0),
                             long_len, new_l))
        arrivals.sort()
        prompts = [(t, r.randint(1, cfg.vocab_size, (L,)), n)
                   for t, L, n in arrivals]

        def serve(chunked):
            eng = ServingEngine(
                pred, max_batch=B, mem_ledger=True,
                prefill_chunk=Sc if chunked else None)
            # warmup: one short + one long through every program shape
            for L in (short_lens[0], long_len):
                eng.submit(r.randint(1, cfg.vocab_size, (L,)),
                           max_new_tokens=2)
            eng.run()
            warm = eng.stats.compiles
            t0 = time.perf_counter()
            rnd, i = 0, 0
            while i < len(prompts) or eng.queue or eng.num_active:
                while i < len(prompts) and prompts[i][0] <= rnd:
                    _, ids, n = prompts[i]
                    eng.submit(ids, max_new_tokens=n)
                    i += 1
                eng.step()
                rnd += 1
            dt = max(time.perf_counter() - t0, 1e-4)
            tpots = [(q.t_finish - q.t_first_token)
                     / (len(q.new_tokens) - 1)
                     for q in eng.finished.values()
                     if len(q.new_tokens) > 1 and q.t_first_token]
            n_tok = sum(len(q.new_tokens) for q in eng.finished.values())
            return eng, {
                "tpot_p50_ms": round(float(np.percentile(tpots, 50))
                                     * 1e3, 3),
                "tpot_p99_ms": round(float(np.percentile(tpots, 99))
                                     * 1e3, 3),
                "tokens_per_sec": round(n_tok / dt, 2),
                "recompiles_after_warmup": eng.stats.compiles - warm,
                "rounds": rnd,
            }

        eng_on, on = serve(chunked=True)
        eng_off, off = serve(chunked=False)
        # the acceptance gate: the fixed lattice must absorb the whole
        # stream with ZERO post-warmup compiles in BOTH modes
        assert on["recompiles_after_warmup"] == 0, on
        assert off["recompiles_after_warmup"] == 0, off

        # memledger: the unified program's HBM traffic vs the old
        # prefill+decode two-program sum (measurable on chip; the CPU
        # backend has no memory_analysis and reports unknown)
        from paddle_tpu.core.bucketing import bucket as _bucket

        led_u = eng_on.memory_ledger(("unified", eng_on.Sc))
        led_p = eng_off.memory_ledger(
            ("prefill", min(_bucket(long_len), eng_off.M)))
        led_d = eng_off.memory_ledger(("decode",))
        if led_u is not None and led_u.available and \
                led_p is not None and led_p.available and \
                led_d is not None and led_d.available:
            two = led_p.traffic_bytes + led_d.traffic_bytes
            hbm = {"unified_traffic_bytes": int(led_u.traffic_bytes),
                   "two_program_traffic_bytes": int(two),
                   "unified_le_two_program":
                       bool(led_u.traffic_bytes <= two)}
        else:
            hbm = {"unified_le_two_program": "unknown (needs chips)"}

        _emit({
            "metric": "serving_mixed_traffic_tpot_p99_ms",
            "value": on["tpot_p99_ms"],
            "unit": "ms",
            # the gate: chunked prefill must hold the TPOT tail below
            # the head-of-line-blocking baseline on the same stream
            "vs_baseline": round(off["tpot_p99_ms"]
                                 / max(on["tpot_p99_ms"], 1e-9), 4),
            "chunked_on": on, "chunked_off": off,
            "prefill_chunk": Sc, "batch": B, "page_size": page,
            "long_prompt_len": long_len, "requests": len(prompts),
            "hbm": hbm,
            "telemetry": _telemetry_section(),
            "device": str(getattr(dev, "device_kind", dev.platform)),
        })

        # ragged-kernel parity gate (exact, bench_compare _EXACT): the
        # unified kernel vs its dense XLA twin on a mixed batch
        # whose chunk straddles page boundaries — interpret mode off
        # chip, Mosaic on chip
        import jax.numpy as jnp

        from paddle_tpu.ops.pallas.ragged_paged_attention import (
            ragged_paged_attention, ragged_paged_attention_dense)

        B2, Sq, H, KV, D, pg, npg = 4, 16, 8, 2, 128, 8, 16
        P2 = B2 * npg + 5
        q = jnp.asarray(r.randn(B2, Sq, H, D), jnp.float32)
        kp = jnp.asarray(r.randn(P2, KV, pg, D), jnp.float32)
        vp = jnp.asarray(r.randn(P2, KV, pg, D), jnp.float32)
        tb = jnp.asarray(r.permutation(P2)[:B2 * npg].reshape(B2, npg),
                         jnp.int32)
        st = jnp.asarray([5, 77, 0, 0], jnp.int32)    # straddles pages
        nv = jnp.asarray([16, 1, 16, 0], jnp.int32)   # chunk/decode/dead
        diff = float(jnp.abs(
            ragged_paged_attention(q, kp, vp, tb, st, nv,
                                   interpret=not on_tpu)
            - ragged_paged_attention_dense(q, kp, vp, tb, st, nv)).max())
        ok = diff < 1e-4
        _emit({"metric": "serving_ragged_kernel_parity",
               "value": 1.0 if ok else 0.0, "unit": "pass",
               "vs_baseline": 1.0 if ok else 0.0,
               "max_abs_diff": diff,
               "mode": "mosaic" if on_tpu else "interpret"})
    finally:
        paddle.set_default_dtype(old_dtype)


# ---------------------------------------------------------------------------
# 5e. Prefix-cache sharing + speculative decoding on the multi-tenant
# trace (the PR-16 serving lines): MANY users share a FEW long system
# prompts, so most arrivals' leading pages are already resident in the
# paged pool. The SAME Poisson trace is served three times — prefix
# cache ON, prefix cache OFF (the TTFT baseline), and prefix+spec ON
# (greedy draft-verify riding the unified [B, Sc] lattice) — and the
# JSON lines carry cache hit rate (ledger-exact fed+skipped
# accounting), TTFT p50/p99 on vs off, committed tokens per verify
# step, the exact three-way output-parity gate, and recompiles pinned
# at 0 for every mode (neither feature adds a program shape).
# ---------------------------------------------------------------------------
def bench_serving_prefix_spec(on_tpu, dev):
    import paddle_tpu as paddle
    from paddle_tpu.inference import Config, ServingEngine, \
        create_predictor
    from paddle_tpu.models.llama import (LlamaConfig, LlamaForCausalLM,
                                         llama_7b)

    old_dtype = paddle.get_default_dtype()
    if on_tpu:
        paddle.set_default_dtype("bfloat16")
        cfg = llama_7b(max_position_embeddings=1024, dtype="bfloat16")
        page, B, Sc, k = 128, 8, 256, 4
        n_sys, sys_pages = 3, 4          # 3 system prompts x 512 tok
        n_users, tail_lo, tail_hi, n_new = 24, 32, 96, 32
        rate = 1.0
    else:
        cfg = LlamaConfig(vocab_size=256, hidden_size=64, num_layers=2,
                          num_heads=4, num_kv_heads=2,
                          intermediate_size=128,
                          max_position_embeddings=256)
        page, B, Sc, k = 8, 4, 16, 3
        n_sys, sys_pages = 3, 4          # 3 system prompts x 32 tok
        n_users, tail_lo, tail_hi, n_new = 18, 4, 12, 8
        rate = 0.8
    try:
        paddle.seed(0)
        model = LlamaForCausalLM(cfg)
        conf = Config().set_model(model).enable_paged_kv(page_size=page)
        if on_tpu:
            conf.enable_weight_only("weight_only_int8")
        pred = create_predictor(conf)
        # self-speculation draft (draft == target): the acceptance
        # CEILING, so tokens/step approaches k+1 while the propose /
        # verify / commit machinery (and its latency) stays realistic;
        # a distilled draft plugs into the same knob on chip
        dpred = create_predictor(
            Config().set_model(model).enable_paged_kv(page_size=page))
        r = np.random.RandomState(16)

        # multi-tenant trace: every request = one of n_sys shared
        # system prompts + a short unique user tail, Poisson arrivals
        sys_prompts = [r.randint(1, cfg.vocab_size,
                                 (sys_pages * page,))
                       for _ in range(n_sys)]
        gaps = r.exponential(1.0 / rate, n_users)
        trace = []
        for t in np.cumsum(gaps):
            sysp = sys_prompts[r.randint(n_sys)]
            tail = r.randint(1, cfg.vocab_size,
                             (r.randint(tail_lo, tail_hi),))
            trace.append((float(t), np.concatenate([sysp, tail])))
        total_prompt_tok = sum(len(p) for _, p in trace)

        def serve(prefix, spec):
            eng = ServingEngine(
                pred, max_batch=B, prefill_chunk=Sc,
                prefix_cache=prefix,
                draft_predictor=dpred if spec else None,
                spec_tokens=k if spec else 0)
            # warmup: one multi-chunk + one sub-chunk prompt through
            # every program shape (chunk feed, decode verify, propose)
            for L in (sys_pages * page + tail_lo, page - 2):
                eng.submit(r.randint(1, cfg.vocab_size, (L,)),
                           max_new_tokens=3)
            eng.run()
            warm = eng.stats.compiles
            rids, i, rnd = [], 0, 0
            t0 = time.perf_counter()
            while i < len(trace) or eng.queue or eng.num_active:
                while i < len(trace) and trace[i][0] <= rnd:
                    rids.append(eng.submit(trace[i][1],
                                           max_new_tokens=n_new))
                    i += 1
                eng.step()
                rnd += 1
            dt = max(time.perf_counter() - t0, 1e-4)
            fin = [eng.finished[rid] for rid in rids]
            ttfts = [q.t_first_token - q.t_submit for q in fin
                     if q.t_first_token]
            n_tok = sum(len(q.new_tokens) for q in fin)
            return eng, [tuple(q.new_tokens) for q in fin], {
                "ttft_p50_ms": round(float(np.percentile(ttfts, 50))
                                     * 1e3, 3),
                "ttft_p99_ms": round(float(np.percentile(ttfts, 99))
                                     * 1e3, 3),
                "tokens_per_sec": round(n_tok / dt, 2),
                "recompiles_after_warmup": eng.stats.compiles - warm,
                "rounds": rnd,
            }

        eng_on, out_on, on = serve(prefix=True, spec=False)
        eng_off, out_off, off = serve(prefix=False, spec=False)
        eng_sp, out_sp, sp = serve(prefix=True, spec=True)
        # the compile gate: neither the cache (block-table surgery on
        # the host) nor spec decode (fixed propose/verify shapes) may
        # add a post-warmup program in ANY mode
        assert on["recompiles_after_warmup"] == 0, on
        assert off["recompiles_after_warmup"] == 0, off
        assert sp["recompiles_after_warmup"] == 0, sp

        pfx = eng_on.prefix_cache_stats()
        hit_rate = pfx["hits"] / max(pfx["lookups"], 1)
        # ledger-exact accounting: every prompt token was either FED
        # through a prefill chunk or SKIPPED via a cache hit — the two
        # ledgers must partition the trace exactly (warmup excluded:
        # stats are read before the measured phase only for fed/skip
        # deltas; here both ledgers include warmup's fed tokens, so
        # add them to the closed form)
        warm_tok = (sys_pages * page + tail_lo) + (page - 2)
        ledger_exact = (pfx["fed_tokens"] + pfx["skipped_tokens"]
                        == total_prompt_tok + warm_tok)
        _emit({
            "metric": "serving_prefix_ttft_p50_ms",
            "value": on["ttft_p50_ms"],
            "unit": "ms",
            # the gate: mapping cached pages must cut time-to-first-
            # token vs re-prefilling the shared prefix every arrival
            "vs_baseline": round(off["ttft_p50_ms"]
                                 / max(on["ttft_p50_ms"], 1e-9), 4),
            "prefix_on": on, "prefix_off": off,
            "cache_hit_rate": round(hit_rate, 4),
            "skipped_tokens": pfx["skipped_tokens"],
            "fed_tokens": pfx["fed_tokens"],
            "ledger_exact": bool(ledger_exact),
            "cow_copies": pfx["cow"], "pages_reclaimed": pfx["reclaimed"],
            "system_prompts": n_sys, "users": n_users,
            "prefix_pages": sys_pages, "page_size": page,
            "prefill_chunk": Sc, "batch": B,
            "telemetry": _telemetry_section(),
            "device": str(getattr(dev, "device_kind", dev.platform)),
        })
        _emit({
            "metric": "serving_prefix_cache_hit_rate",
            "value": round(hit_rate, 4), "unit": "ratio",
            # acceptance floor from the trace construction: with 3
            # system prompts over 18+ users, most lookups must hit
            "vs_baseline": round(hit_rate / 0.5, 4),
            "hits": pfx["hits"], "lookups": pfx["lookups"],
            "ledger_exact": bool(ledger_exact)})

        spec = eng_sp.spec_stats()
        _emit({
            "metric": "serving_spec_tokens_per_step",
            "value": round(spec["tokens_per_step"], 4),
            "unit": "tokens/step",
            # plain decode commits exactly 1 token per row-step; the
            # draft-verify lattice must beat that at its acceptance
            "vs_baseline": round(spec["tokens_per_step"], 4),
            "accept_rate": round(spec["accept_rate"], 4),
            "proposed": spec["proposed"], "accepted": spec["accepted"],
            "spec_tokens": k, "draft": "self (acceptance ceiling)",
            "spec_run": sp})

        # the exactness gate (bench_compare _EXACT): greedy spec decode
        # and prefix-cache sharing are both REORDERINGS of the same
        # computation, so all three serves of the same trace must emit
        # identical token streams, with the fed+skipped ledger closed
        ok = (out_on == out_off == out_sp) and ledger_exact \
            and hit_rate > 0.5
        _emit({"metric": "serving_prefix_spec_parity",
               "value": 1.0 if ok else 0.0, "unit": "pass",
               "vs_baseline": 1.0 if ok else 0.0,
               "outputs_equal": bool(out_on == out_off == out_sp),
               "ledger_exact": bool(ledger_exact),
               "hit_rate_gt_half": bool(hit_rate > 0.5)})
    finally:
        paddle.set_default_dtype(old_dtype)


# ---------------------------------------------------------------------------
# Disaggregated prefill/decode serving (ISSUE 20): a phase-split fleet
# (1 prefill replica streaming KV pages to 1 decode replica through
# inference/disagg.py, fronted by the inference/router.py front door)
# vs a unified 2-replica fleet on the SAME bursty Poisson trace.
# Same chip count on both sides, so goodput-per-chip is the headline;
# the exactness gates (bench_compare _EXACT): bit-identical token
# streams, migration wire bytes pinned to the pages x page_bytes +
# block-table-row closed form, zero post-warmup recompiles on BOTH
# replica kinds.
# ---------------------------------------------------------------------------
def bench_serving_disagg(on_tpu, dev):
    import paddle_tpu as paddle
    from paddle_tpu.inference import Config, Router, ServingEngine, \
        create_predictor
    from paddle_tpu.models.llama import (LlamaConfig, LlamaForCausalLM,
                                         llama_7b)

    old_dtype = paddle.get_default_dtype()
    if on_tpu:
        paddle.set_default_dtype("bfloat16")
        cfg = llama_7b(max_position_embeddings=1024, dtype="bfloat16")
        page, B, Sc = 128, 8, 256
        n_req, len_lo, len_hi, n_new, rate = 24, 128, 448, 48, 1.0
        pool = None                  # geometric default
    else:
        cfg = LlamaConfig(vocab_size=256, hidden_size=64, num_layers=2,
                          num_heads=4, num_kv_heads=2,
                          intermediate_size=128,
                          max_position_embeddings=256)
        page, B, Sc = 8, 4, 16
        n_req, len_lo, len_hi, n_new, rate = 14, 5, 30, 8, 0.8
        pool = 32
    try:
        paddle.seed(0)
        model = LlamaForCausalLM(cfg)
        conf = Config().set_model(model).enable_paged_kv(page_size=page)
        if on_tpu:
            conf.enable_weight_only("weight_only_int8")
        r = np.random.RandomState(20)
        # bursty Poisson arrivals on the router's step clock
        gaps = r.exponential(1.0 / rate, n_req)
        trace = [(float(t),
                  r.randint(1, cfg.vocab_size,
                            (int(r.randint(len_lo, len_hi)),)))
                 for t in np.cumsum(gaps)]

        def mk(phase=None):
            return ServingEngine(create_predictor(conf), max_batch=B,
                                 prefill_chunk=Sc, pool_pages=pool,
                                 phase=phase)

        def serve(disagg):
            if disagg:
                rt = Router([("prefill0", mk("prefill")),
                             ("decode0", mk("decode"))])
            else:
                rt = Router([("u0", mk()), ("u1", mk())])
            engs = [rep.engine for rep in rt.replicas]
            # warmup: one request PER FRONTDOOR REPLICA through every
            # program shape (prefill chunks, fused decode, page
            # read/write on the migration path) — least-loaded
            # placement spreads sequential submissions across the pool
            for _ in range(len(rt.frontdoor)):
                rt.submit(r.randint(1, cfg.vocab_size, (len_hi,)),
                          max_new_tokens=3)
            rt.run()
            warm = sum(e.stats.compiles for e in engs)
            gids, i, rnd = [], 0, 0
            t0 = time.perf_counter()
            while i < len(trace) or rt.pending:
                while i < len(trace) and trace[i][0] <= rnd:
                    gids.append(rt.submit(trace[i][1],
                                          max_new_tokens=n_new))
                    i += 1
                rt.step()
                rnd += 1
            dt = max(time.perf_counter() - t0, 1e-4)
            fin = [rt.result(g) for g in gids]
            ttfts = [q.t_first_token - q.t_submit for q in fin
                     if q.t_first_token]
            tpots = [(q.t_finish - q.t_first_token)
                     / (len(q.new_tokens) - 1) for q in fin
                     if q.t_first_token and len(q.new_tokens) > 1]
            n_tok = sum(len(q.new_tokens) for q in fin)
            return rt, [tuple(q.new_tokens) for q in fin], {
                "ttft_p99_ms": round(float(np.percentile(ttfts, 99))
                                     * 1e3, 3),
                "tpot_p99_ms": round(float(np.percentile(tpots, 99))
                                     * 1e3, 3),
                "goodput_tokens_per_sec_per_chip":
                    round(n_tok / dt / len(engs), 2),
                "recompiles_after_warmup":
                    sum(e.stats.compiles for e in engs) - warm,
                "rounds": rnd,
            }

        rt_d, out_d, dis = serve(disagg=True)
        rt_u, out_u, uni = serve(disagg=False)
        # the compile gate: a warmed fleet must serve the whole trace
        # (migrations included) without a single new XLA program
        assert dis["recompiles_after_warmup"] == 0, dis
        assert uni["recompiles_after_warmup"] == 0, uni

        # migration byte accounting: measured wire bytes (also booked
        # on the comm ledger's migrate axis and the migration_bytes
        # counter) == the closed form over the served requests,
        # warmup included
        peng = rt_d.replicas[0].engine
        mcfg = model.config
        page_bytes = (2 * mcfg.num_layers * mcfg.num_kv_heads * page
                      * mcfg.head_dim * np.dtype(peng._dtype).itemsize)
        lens = [len(p) for _, p in trace] \
            + [len_hi] * len(rt_d.frontdoor)
        closed = sum((-(-L // page)) * page_bytes + peng.cache.npages * 4
                     for L in lens)
        bytes_exact = rt_d.migrator.wire_bytes == closed
        parity = out_d == out_u

        _emit({
            "metric": "serving_disagg_ttft_p99_ms",
            "value": dis["ttft_p99_ms"], "unit": "ms",
            # chunked prefill at full MFU with decode offloaded: the
            # tail TTFT must not regress vs the co-located fleet
            "vs_baseline": round(uni["ttft_p99_ms"]
                                 / max(dis["ttft_p99_ms"], 1e-9), 4),
            "disagg": dis, "unified": uni,
            "requests": n_req, "page_size": page, "prefill_chunk": Sc,
            "batch": B,
            "telemetry": _telemetry_section(),
            "device": str(getattr(dev, "device_kind", dev.platform)),
        })
        _emit({
            "metric": "serving_disagg_tpot_p99_ms",
            "value": dis["tpot_p99_ms"], "unit": "ms",
            # the disagg pitch: decode rows never stall behind prefill
            # chunks, so the inter-token tail tightens
            "vs_baseline": round(uni["tpot_p99_ms"]
                                 / max(dis["tpot_p99_ms"], 1e-9), 4),
            "disagg": dis, "unified": uni})
        _emit({
            "metric": "serving_disagg_goodput_per_chip",
            "value": dis["goodput_tokens_per_sec_per_chip"],
            "unit": "tokens/s/chip",
            "vs_baseline": round(
                dis["goodput_tokens_per_sec_per_chip"]
                / max(uni["goodput_tokens_per_sec_per_chip"], 1e-9),
                4),
            "disagg": dis, "unified": uni})
        _emit({
            "metric": "serving_disagg_parity",
            "value": 1.0 if parity else 0.0, "unit": "pass",
            "vs_baseline": 1.0 if parity else 0.0,
            "outputs_equal": bool(parity),
            "migrated": rt_d.migrator.migrated})
        _emit({
            "metric": "serving_disagg_migration_bytes",
            "value": 1.0 if bytes_exact else 0.0, "unit": "pass",
            "vs_baseline": 1.0 if bytes_exact else 0.0,
            "wire_bytes": int(rt_d.migrator.wire_bytes),
            "closed_form": int(closed),
            "page_bytes": int(page_bytes),
            "block_table_row_bytes": int(peng.cache.npages * 4)})
    finally:
        paddle.set_default_dtype(old_dtype)


# ---------------------------------------------------------------------------
# 3. GPT-13B hybrid TP x PP x DP + GroupSharded stage2 (BASELINE row 3).
# Needs >= 8 chips; on one chip it reports the requirement cleanly, and
# on the CPU harness it runs the FULL hybrid code path on tiny shapes
# (correctness: the same strategy dryrun_multichip validates).
# ---------------------------------------------------------------------------
def bench_gpt13b_hybrid(on_tpu, dev):
    import os
    import shutil
    import tempfile

    import jax

    import paddle_tpu as paddle
    from paddle_tpu.distributed import fleet
    from paddle_tpu.models import GPTForCausalLMPipe
    from paddle_tpu.models.gpt import GPTConfig

    from paddle_tpu.observability import flops as _flops
    from paddle_tpu.observability import goodput as _gp
    from paddle_tpu.observability import memledger as _ml
    from paddle_tpu.observability import timeseries as _ts

    # HBM memory ledger on for every engine this bench builds (the
    # engines live behind fleet.distributed_model, so the env knob is
    # the plumbing): one extra AOT analysis per program, zero
    # recompiles of the live step (the recompiles_after_warmup field
    # below still gates at 0 with the ledger on)
    os.environ["PADDLE_TPU_MEM_LEDGER"] = "1"

    n = jax.device_count()
    if on_tpu and n < 8:
        _emit({"metric": "gpt13b_hybrid_train_tokens_per_sec",
               "value": 0.0, "unit": "needs_chips", "vs_baseline": 0.0,
               "needs_devices": 8, "have_devices": n,
               "note": "13B = TP4 x PP2 x sharding(n/8) stage2; "
                       "config compiled/validated on the 8-virtual-"
                       "device CPU mesh (dryrun + this bench on CPU)"})
        return
    if on_tpu:
        # GPT-13B: hidden 5120 x 40 layers x 40 heads (BASELINE row 3)
        cfg = GPTConfig(vocab_size=50304, hidden_size=5120,
                        num_layers=40, num_heads=40,
                        max_position_embeddings=1024, dtype="bfloat16")
        mp_deg, shard_deg = 4, max(n // 8, 1)
        B, S, steps, state_dtype = 4 * shard_deg, 1024, 5, "bfloat16"
        buf_mb = 64.0
    else:
        cfg = GPTConfig(vocab_size=512, hidden_size=64, num_layers=4,
                        num_heads=4, max_position_embeddings=64)
        # the smoke mesh carries a REAL sharding axis (mp2 x pp2 x
        # sharding2 = 8 vdevs) so the stage-2 grad reduce-scatter —
        # the tail comm_overlap exists to hide — is actually on the
        # wire and in the exposed-comm report
        mp_deg, shard_deg = 2, 2
        B, S, steps, state_dtype = 2 * shard_deg * 2, 16, 2, None
        buf_mb = 0.001        # tiny target -> several buckets at toy size

    # five lines, one knob apart each: vpp=1 (GPipe-family rotation),
    # vpp=2 (circular interleave), vpp=1 + comm_overlap (T3-style
    # bucketed backward: per-bucket grad reduce-scatter inside the
    # backward seam, distributed/grad_buckets.py), overlap +
    # quant_comm (int8 error-feedback quantized collectives,
    # distributed/quant_comm.py — the quant-vs-overlap pair isolates
    # the wire compression), and overlap + sharding_stage=3 (ZeRO-3
    # shard-only parameter storage with the bucketed just-in-time
    # gather — the stage3-vs-overlap pair isolates the storage
    # discipline: same grads, params stored at 1/sharding_degree and
    # re-gathered per signature bucket at forward entry). base vs
    # overlap is the same program shape, so the loss-parity and
    # profile_exposed_comm("sharding") comparison is one flag apart.
    quant_chunk = 256 if on_tpu else 64
    gp_base = tempfile.mkdtemp(prefix="goodput_gpt13b_")
    results = {}
    for tag, vpp, overlap, quant, stage, offload in (
            ("base", 1, False, False, 2, None),
            ("vpp2", 2, False, False, 2, None),
            ("overlap", 1, True, False, 2, None),
            ("quant", 1, True, True, 2, None),
            ("stage3", 1, True, False, 3, None),
            # the host tier rides the stage-3 line one knob apart:
            # optimizer state host-resident between steps, prefetched
            # per-bucket just in time (distributed/host_offload.py)
            ("offload", 1, True, False, 3,
             {"optimizer": True, "prefetch_buckets": 2})):
        # one goodput journal per tag (run-level wall attribution:
        # compile vs step_compute vs idle; observability/goodput.py)
        # plus the durable metrics journal beside it (observability/
        # timeseries): both are host-side file IO on fetched scalars,
        # so the recompiles_after_warmup gate below must hold at 0
        # with the sampler attached for the whole measured window
        gp_led = _gp.attach_dir(os.path.join(gp_base, tag))
        ts_smp = _ts.attach_dir(os.path.join(gp_base, tag),
                                interval_s=0.5)
        paddle.seed(0)
        strategy = fleet.DistributedStrategy()
        strategy.hybrid_configs = {
            "dp_degree": 1, "mp_degree": mp_deg,
            "pp_degree": 2,
            "sharding_degree": shard_deg,
            # collective-matmul overlap on the TP hot
            # path (distributed/collective_matmul.py)
            "mp_configs": {"mp_async_allreduce": True},
            "pp_configs": {"num_virtual_pipeline_stages": vpp},
            # T3-style bucketed grad sync (grad_buckets.py) + the ZeRO
            # stage knob (3 = shard-only params, just-in-time gather)
            "sharding_configs": {"comm_overlap": overlap,
                                 "comm_buffer_size_MB": buf_mb,
                                 "sharding_stage": stage,
                                 "offload": offload},
            # int8 quantized collectives with error feedback
            # (quant_comm.py): grad reduce-scatter buckets, TP rings +
            # activation allreduces, and the ZeRO param gather
            "quant_comm": {"dtype": "int8" if quant else "none",
                           "chunk": quant_chunk,
                           "error_feedback": True}}
        strategy.sharding_configs = {"stage": stage}
        strategy.pipeline_configs = {
            "accumulate_steps": 2,
            "micro_batch_size": B // (2 * shard_deg)}
        hcg = fleet.init(is_collective=True, strategy=strategy)
        model = GPTForCausalLMPipe(cfg)
        dist_model = fleet.distributed_model(model)
        opt = fleet.distributed_optimizer(
            paddle.optimizer.AdamW(learning_rate=1e-4,
                                   parameters=model.parameters(),
                                   state_dtype=state_dtype))
        r = np.random.RandomState(0)
        ids = r.randint(0, cfg.vocab_size, (B, S + 1))
        x = paddle.to_tensor(ids[:, :-1])
        y = paddle.to_tensor(ids[:, 1:])
        losses = [float(dist_model.train_batch([x, y], opt))]
        stats = dist_model._engine.stats
        compiles_warm = stats.compiles
        # host-offload steady state: cumulative transfer-ledger bytes
        # around the timed window pin the per-step cost exactly (one
        # h2d prefetch + one d2h page-out of every offloaded slot)
        tier = dist_model._engine._offload
        off_t0 = tier.transfer_bytes() if tier is not None else 0
        t0 = time.perf_counter()
        for _ in range(steps):
            losses.append(float(dist_model.train_batch([x, y], opt)))
        dt = time.perf_counter() - t0
        off_steady = (tier.transfer_bytes() - off_t0) \
            if tier is not None else 0
        tok_s = B * S * steps / dt
        # goodput summary BEFORE the offline exposed-comm replays (the
        # profiler suppresses goodput segments, so its wall time would
        # book as idle and dilute the percentage)
        gp_summary = gp_led.summary()
        # close the tag's metrics journal with one guaranteed final
        # sample and pin the per-sample overhead (snapshot + one
        # flushed JSONL line — bounded host cost, never device work)
        ts_smp.sample_now()
        ts_stats = ts_smp.stats()
        ts_smp.close()
        assert ts_stats["samples"] >= 1, ts_stats
        assert (ts_stats["overhead_seconds"]
                <= 0.25 * ts_stats["samples"]), ts_stats
        # exposed-comm attribution (observability/commledger): per-axis
        # overlapped-vs-exposed split + grad_sync_exposed_seconds. The
        # gauges land in the telemetry section below; the compact
        # report rides on the line itself. Offline pass — state is
        # restored and the compile counters above are not perturbed.
        prof = dist_model.profile_exposed_comm([x, y], repeats=2)
        exposed_comm = {
            "step_seconds": round(prof.step_seconds, 6),
            "exposed_seconds": {a: round(v, 6) for a, v in
                                prof.exposed_seconds.items()},
            "replay_seconds": {a: round(v, 6) for a, v in
                               prof.replay_seconds.items()},
            "exposed_fraction": {a: round(v, 4) for a, v in
                                 prof.exposed_fraction.items()},
            "grad_sync_exposed_seconds": round(
                prof.grad_sync_exposed_seconds, 6),
        }
        eng = dist_model._engine
        led = eng.comm_ledger()
        comm_bytes_per_step = {
            f"{a}/{o}": round(t["bytes"], 1)
            for (a, o), t in sorted(led.totals().items())} if led else {}
        plan = eng._bucket_plan
        # memory ledger + state accounting + roofline verdict: the
        # per-executable byte classes (XLA memory_analysis), the
        # measured model-state breakdown with the auto_tuner drift,
        # and the compute/HBM/ICI bound verdict joining flops + comm +
        # memory (observability/memledger.py)
        mem_led = eng.memory_ledger()
        acct = eng.state_accounting()
        roof = eng.roofline_report(exposed=prof)
        results[tag] = {"losses": losses, "prof": prof, "led": led,
                        "plan": plan, "eng": eng, "acct": acct,
                        "roof": roof, "goodput": gp_summary,
                        "off_steady": off_steady,
                        "ts_stats": ts_stats,
                        "recompiles": stats.compiles - compiles_warm}
        peak, _ = _chip(dev)
        n_params = cfg.num_params()
        mfu = (6.0 * n_params * tok_s / (peak * n)) if peak else 0.0
        base = ("gpt13b_hybrid_train_tokens_per_sec" if on_tpu
                else "gpt13b_hybrid_smoke_tokens_per_sec")
        line = {
            "metric": base if tag == "base" else
            base.replace("gpt13b_hybrid", f"gpt13b_hybrid_{tag}"),
            "value": round(tok_s, 2),
            "unit": "tokens/s",
            "vs_baseline": round(mfu / 0.45, 4) if peak else 0.0,
            "mfu": round(mfu, 4) if peak else 0.0,
            "mesh": f"sharding{shard_deg}xpp2xmp{mp_deg}", "devices": n,
            "mp_async_allreduce": True,
            "pp_vpp": vpp,
            "comm_overlap": overlap,
            "quant_comm": quant,
            "sharding_stage": stage,
            "comm_bytes_total": round(led.bytes_for(), 1) if led
            else 0.0,
            # engine compile-cache counters: steady state must be
            # recompile-free (overlap regressions keyed on traced shapes
            # would show here)
            "compiles": stats.compiles,
            "cache_hits": stats.cache_hits,
            "recompiles_after_warmup": stats.compiles - compiles_warm,
            # static comm ledger of the compiled step (bytes-on-wire
            # per participant per step, by axis/op) + the exposed-comm
            # attribution — the instrument panel quant_comm / T3
            # overlap / MoE a2a report through
            "comm_bytes_per_step": comm_bytes_per_step,
            "exposed_comm": exposed_comm,
            "memory": {
                "executable": mem_led.to_dict() if mem_led else {},
                "state": acct.to_dict(),
            },
            "roofline": roof.to_dict(),
            # run-level wall-clock attribution of THIS tag's run
            # (tools/run_report.py draws the waterfall;
            # tools/step_report.py columns + --strict gate ride on it)
            "goodput": gp_summary,
            # the durable metrics journal the same run wrote next to
            # the goodput ledger (tools/fleet_report.py reads these)
            "timeseries": {
                "samples": ts_stats["samples"],
                "journal_bytes": ts_stats["journal_bytes"],
                "overhead_seconds": round(
                    ts_stats["overhead_seconds"], 6)},
            "telemetry": _telemetry_section(),
            "device": str(getattr(dev, "device_kind", dev.platform)),
        }
        if overlap and plan is not None:
            summ = plan.summary()
            line["grad_buckets"] = summ["buckets"]
            line["bucket_payload_bytes"] = summ["bucket_payload_bytes"]
            line["grad_sync_floor_seconds"] = round(
                _flops.comm_seconds_lower_bound(
                    led.bytes_for(axis="sharding"), dev), 6) if led \
                else 0.0
        if tier is not None:
            line["offload"] = {
                "host_resident_bytes": tier.host_resident_bytes(),
                "transfer_bytes_d2h": tier.transfer_bytes(
                    direction="d2h"),
                "transfer_bytes_h2d": tier.transfer_bytes(
                    direction="h2d"),
                "steady_bytes_per_step": off_steady // max(steps, 1),
                "prefetch_seconds": round(tier._last_prefetch_s, 6),
            }
        if quant and led is not None:
            # realized per-axis wire compression (int8 payload + bf16
            # scale sidecars vs the uncompressed-equivalent bytes)
            line["quant_ratios"] = {a: round(v, 4) for a, v
                                    in led.quant_ratios().items()}
            line["quant_residual_buffers"] = len(eng._quant_residuals)
        _emit(line)

    # the T3 acceptance pair: knob-on vs knob-off on the same program —
    # loss parity (exact-gated in tools/bench_compare.py) and the
    # sharding axis's exposed seconds (direction-aware: lower is better)
    base_r, ov_r = results["base"], results["overlap"]
    parity = max(abs(a - b) for a, b in zip(base_r["losses"],
                                            ov_r["losses"]))
    _emit({"metric": "gpt13b_hybrid_overlap_loss_parity",
           "value": 1.0 if parity <= 1e-5 else 0.0, "unit": "pass",
           "vs_baseline": 1.0, "max_abs_loss_diff": parity,
           "grad_buckets": (ov_r["plan"].num_buckets
                            if ov_r["plan"] else 0)})
    exp_off = base_r["prof"].exposed_seconds.get("sharding", 0.0)
    exp_on = ov_r["prof"].exposed_seconds.get("sharding", 0.0)
    _emit({"metric": "gpt13b_hybrid_grad_sync_exposed_seconds",
           "value": round(exp_on, 6), "unit": "s", "vs_baseline": 0.0,
           "knob_off_exposed_seconds": round(exp_off, 6),
           "exposed_lower_than_knob_off": bool(exp_on < exp_off),
           "note": "CPU smoke proves parity + compile stability; the "
                   "realized overlap win is an on-TPU ROADMAP item"})
    # the quant_comm acceptance pair: quant vs overlap on the same
    # program — total comm-ledger wire bytes must drop to <= 0.30x
    # (int8 payload + bf16 scales closed forms; lower-better in
    # tools/bench_compare.py) and the deterministic-horizon loss gap
    # stays loose-bounded (the REAL convergence gate is the 200-step
    # parity test in tests/test_quant_comm.py — this line just tracks
    # drift on the flagship config)
    q_r = results["quant"]
    q_bytes = q_r["led"].bytes_for() if q_r["led"] else 0.0
    o_bytes = ov_r["led"].bytes_for() if ov_r["led"] else 0.0
    wire_ratio = (q_bytes / o_bytes) if o_bytes else 0.0
    _emit({"metric": "gpt13b_hybrid_quant_wire_ratio",
           "value": round(wire_ratio, 4), "unit": "x",
           "vs_baseline": 0.0,
           "quant_bytes_per_step": round(q_bytes, 1),
           "fp32_bytes_per_step": round(o_bytes, 1),
           "quant_ratios": {a: round(v, 4) for a, v in
                            (q_r["led"].quant_ratios().items()
                             if q_r["led"] else ())},
           "le_030": bool(wire_ratio <= 0.30)})
    q_gap = max(abs(a - b) for a, b in zip(ov_r["losses"],
                                           q_r["losses"]))
    _emit({"metric": "gpt13b_hybrid_quant_loss_gap",
           "value": round(q_gap, 6), "unit": "abs", "vs_baseline": 0.0,
           "losses_quant": [round(v, 5) for v in q_r["losses"]],
           "losses_fp32": [round(v, 5) for v in ov_r["losses"]]})
    # the ZeRO stage-3 acceptance pair: stage3 vs overlap on the same
    # program shape, one knob apart — loss parity (exact-gated in
    # tools/bench_compare.py: the gather is pure data movement, so
    # stage 3 must land bit-on the stage-2 trajectory) plus the
    # just-in-time gather's wire bytes pinned to the (p-1) x shard
    # closed form (scan_trips-exact on the stacked seam)
    s3_r = results["stage3"]
    s3_parity = max(abs(a - b) for a, b in zip(ov_r["losses"],
                                               s3_r["losses"]))
    s3_eng = s3_r["eng"]
    covered_shard_bytes = sum(
        _ml.shard_bytes(p._value) for p in s3_eng.trainable
        if s3_eng._zero.entry(p) is not None
        and s3_eng._zero.entry(p)[1])
    gather_closed = (shard_deg - 1) * covered_shard_bytes
    gather_bytes = (s3_r["led"].bytes_for(axis="sharding",
                                          op="all_gather")
                    if s3_r["led"] else 0.0)
    _emit({"metric": "gpt13b_hybrid_stage3_loss_parity",
           "value": 1.0 if (s3_parity <= 1e-5
                            and gather_bytes == gather_closed) else 0.0,
           "unit": "pass", "vs_baseline": 1.0,
           "max_abs_loss_diff": s3_parity,
           "gather_bytes_per_step": round(gather_bytes, 1),
           "gather_bytes_closed_form": round(float(gather_closed), 1),
           "gather_ops_per_step": (s3_r["led"].ops_for(
               axis="sharding", op="all_gather") if s3_r["led"] else 0)})
    # stage-3 memory exact gate: measured state accounting == closed
    # form byte-for-byte AND the params component sits at exactly
    # 1/sharding_degree of the stage-2 (replicated-storage) image —
    # the unlock that lets models outgrow one chip's HBM
    s3_acct = s3_r["acct"]
    s3_closed = _ml.closed_form_state_bytes(s3_eng)
    ov_params = results["overlap"]["acct"].components.get("params", 0)
    s3_params = s3_acct.components.get("params", 0)
    uncovered = sum(
        _ml.shard_bytes(p._value) for p in s3_eng.params
        if not (s3_eng._zero.entry(p) is not None
                and s3_eng._zero.entry(p)[1]))
    s3_ok = (all(s3_acct.components.get(k) == v
                 for k, v in s3_closed.items())
             and (s3_params - uncovered) * shard_deg
             == ov_params - uncovered)
    _emit({"metric": "gpt13b_hybrid_stage3_mem_state_parity",
           "value": 1.0 if s3_ok else 0.0, "unit": "pass",
           "vs_baseline": 1.0 if s3_ok else 0.0,
           "measured": {k: s3_acct.components.get(k) for k in s3_closed},
           "closed_form": s3_closed,
           "params_bytes_stage3": s3_params,
           "params_bytes_stage2": ov_params,
           "sharding_degree": shard_deg,
           "analytic_drift": round(s3_acct.drift, 4)})
    # the host-offload acceptance pair: offload vs stage3, one knob
    # apart — the tier is pure data movement (bytes copied, never
    # re-derived, outside the compiled step), so the loss trajectory
    # must land BIT-exactly on stage 3's with zero recompiles, and the
    # cumulative transfer ledger must pin to the closed form: every
    # offloaded slot's per-device shard bytes once per direction per
    # step (the steady-state window), with conservation d2h - h2d ==
    # bytes currently host-resident (exact-gated in bench_compare)
    from paddle_tpu.distributed import host_offload as _ho
    off_r = results["offload"]
    off_parity = max(abs(a - b) for a, b in zip(s3_r["losses"],
                                                off_r["losses"]))
    off_eng = off_r["eng"]
    tier = off_eng._offload
    slot_closed = sum(
        _ho.host_shard_bytes(tier._get(off_eng, key))
        for key, _c, _b in tier._iter_slots(off_eng))
    resident = tier.host_resident_bytes()
    conserved = (tier.transfer_bytes(direction="d2h")
                 - tier.transfer_bytes(direction="h2d"))
    steady_ok = off_r["off_steady"] == 2 * steps * slot_closed
    off_recompiles = off_r["recompiles"]
    _emit({"metric": "gpt13b_hybrid_offload_loss_parity",
           "value": 1.0 if (off_parity == 0.0 and resident == slot_closed
                            and conserved == resident and steady_ok
                            and off_recompiles == 0) else 0.0,
           "unit": "pass", "vs_baseline": 1.0,
           "max_abs_loss_diff": off_parity,
           "host_resident_bytes": resident,
           "host_resident_closed_form": slot_closed,
           "transfer_conservation_bytes": conserved,
           "steady_bytes_per_step": off_r["off_steady"] // max(steps, 1),
           "steady_closed_form_per_step": 2 * slot_closed,
           "recompiles_after_warmup": off_recompiles})
    # offload memory exact gate: the measured accounting (between
    # steps, i.e. with the tier paged OUT) books the offloaded slots
    # under host_state == the closed form, and the DEVICE-resident
    # image drops below stage 3's by exactly that amount
    off_acct = off_r["acct"]
    off_closed = _ml.closed_form_state_bytes(off_eng)
    s3_dev = s3_r["acct"].device_bytes
    off_ok = (all(off_acct.components.get(k) == v
                  for k, v in off_closed.items())
              and off_acct.components.get("host_state", 0) > 0
              and off_acct.device_bytes
              == s3_dev - off_acct.components.get("host_state", 0))
    _emit({"metric": "gpt13b_hybrid_offload_mem_state_parity",
           "value": 1.0 if off_ok else 0.0, "unit": "pass",
           "vs_baseline": 1.0 if off_ok else 0.0,
           "measured": {k: off_acct.components.get(k)
                        for k in off_closed},
           "closed_form": off_closed,
           "device_bytes_offload": off_acct.device_bytes,
           "device_bytes_stage3": s3_dev,
           "analytic_drift": round(off_acct.drift, 4)})
    # the capability line: the 13B flagship on its OWN 8-chip slice
    # (TP4 x PP2; sharding_degree = n // 8 = 1, so the fp32 optimizer
    # image has no axis left to shard away) priced by the auto_tuner
    # cost model — a 16 GB chip cannot hold it, and the SAME config
    # with the optimizer tier offloaded fits: the tier is the axis
    # past the last on-chip scale knob
    from paddle_tpu.distributed.auto_tuner.cost_model import (
        estimate_memory_gb)
    model_13b = {"hidden_size": 5120, "num_layers": 40,
                 "vocab_size": 50304}
    cfg_13b = {"dp_degree": 1, "mp_degree": 4, "pp_degree": 2,
               "sharding_degree": 1, "sharding_stage": 3,
               "micro_batch_size": 1}
    hbm_gb = 16.0
    m_s3 = estimate_memory_gb(model_13b, cfg_13b, global_batch=8,
                              seq_len=1024, recompute=True)
    m_off = estimate_memory_gb(
        model_13b, dict(cfg_13b, offload={"optimizer": True,
                                          "prefetch_buckets": 2}),
        global_batch=8, seq_len=1024, recompute=True)
    _emit({"metric": "gpt13b_hybrid_offload_overhbm_trainable",
           "value": 1.0 if (m_s3 > hbm_gb >= m_off) else 0.0,
           "unit": "pass", "vs_baseline": 1.0,
           "hbm_gb": hbm_gb,
           "stage3_image_gb": round(m_s3, 2),
           "offload_image_gb": round(m_off, 2)})
    # memory-ledger exact gate: the measured state accounting (shard_
    # shape path) must equal the closed form (global shape / sharding
    # degree path) byte-for-byte — incl. ZeRO stage-2 scattered state
    # and the pp x vpp stacked-chunk ownership (bench_compare _EXACT)
    acct = base_r["acct"]
    closed = _ml.closed_form_state_bytes(base_r["eng"])
    ok = all(acct.components.get(k) == v for k, v in closed.items())
    _emit({"metric": "gpt13b_hybrid_mem_state_parity",
           "value": 1.0 if ok else 0.0, "unit": "pass",
           "vs_baseline": 1.0 if ok else 0.0,
           "measured": {k: acct.components.get(k) for k in closed},
           "closed_form": closed,
           "analytic_drift": round(acct.drift, 4)})
    # HBM headroom of the roofline verdict (direction-aware in
    # bench_compare: higher = more slack before the memory wall; 0 on
    # CPU where peak tables are unknown and the verdict is "unknown")
    roof = base_r["roof"]
    _emit({"metric": "gpt13b_hybrid_hbm_headroom_pct",
           "value": round(roof.headroom_pct.get("hbm", 0.0), 2),
           "unit": "pct", "vs_baseline": 0.0, "bound": roof.bound,
           "roofline_seconds": {k: round(v, 6)
                                for k, v in roof.seconds.items()}})
    # run-level goodput headline (higher-better in bench_compare; the
    # CPU smoke number is dominated by compile at this toy scale — the
    # trajectory, not the absolute, is the signal) + the health
    # monitor's event count, which must be EXACTLY 0 on this
    # deterministic line (bench_compare _EXACT)
    gp = base_r["goodput"]
    _emit({"metric": "gpt13b_hybrid_goodput_pct",
           "value": gp["goodput_pct"], "unit": "pct",
           "vs_baseline": 0.0,
           "segment_pct": gp["segment_pct"],
           "wall_seconds": gp["wall_seconds"]})
    # sampler cost headline (lower-better in bench_compare): total
    # wall seconds the metrics-journal sampler spent across every tag
    # of this bench — the observability tax must stay near zero
    ts_total = sum(r["ts_stats"]["overhead_seconds"]
                   for r in results.values())
    ts_samples = sum(r["ts_stats"]["samples"] for r in results.values())
    _emit({"metric": "gpt13b_hybrid_sampler_overhead_seconds",
           "value": round(ts_total, 6), "unit": "s", "vs_baseline": 0.0,
           "samples": ts_samples,
           "journal_bytes": sum(r["ts_stats"]["journal_bytes"]
                                for r in results.values()),
           "seconds_per_sample": round(ts_total / max(ts_samples, 1),
                                       6)})
    # each tag's engine carries its OWN health monitor (per-run
    # windows); a deterministic fixed-seed bench must raise no event
    # on any of them
    n_events = sum(r["eng"]._health.event_count()
                   for r in results.values())
    _emit({"metric": "gpt13b_hybrid_health_spike_events",
           "value": float(n_events),
           "unit": "events", "vs_baseline": 0.0,
           "events": [e for r in results.values()
                      for e in r["eng"]._health.events()][-4:]})
    _gp.detach()
    _ts.detach()
    shutil.rmtree(gp_base, ignore_errors=True)


# ---------------------------------------------------------------------------
# 4a-bis. Checkpoint-save overlap: how much of a full-state crash-
# consistent checkpoint (params + ZeRO-2 moments + AMP + RNG, atomic
# commit protocol) the ASYNC path hides behind training steps on the
# gpt13b_hybrid smoke mesh (mp2 x pp2 x sharding2). The line's value is
# the async stall (lower better, registered direction-aware in
# tools/bench_compare.py); the acceptance bound rides along as
# async_stall_lt_step (< 1 step-time of stall).
# ---------------------------------------------------------------------------
def bench_ckpt_overlap(on_tpu, dev):
    import os
    import shutil
    import tempfile

    import jax

    import paddle_tpu as paddle
    from paddle_tpu.distributed import fleet
    from paddle_tpu.distributed.checkpoint import CheckpointManager
    from paddle_tpu.models import GPTForCausalLMPipe
    from paddle_tpu.models.gpt import GPTConfig
    from paddle_tpu.observability import goodput as _gp
    from paddle_tpu.observability.catalog import ckpt_metrics

    n = jax.device_count()
    if on_tpu and n < 8:
        _emit({"metric": "ckpt_save_overlap_stall_seconds",
               "value": 0.0, "unit": "needs_chips", "vs_baseline": 0.0,
               "needs_devices": 8, "have_devices": n})
        return
    # the gpt13b_hybrid smoke topology; on chip a fatter layer so the
    # snapshot/write actually move bytes worth hiding
    if on_tpu:
        cfg = GPTConfig(vocab_size=50304, hidden_size=1024,
                        num_layers=8, num_heads=8,
                        max_position_embeddings=512, dtype="bfloat16")
        B, S = 8, 512
    else:
        cfg = GPTConfig(vocab_size=512, hidden_size=64, num_layers=4,
                        num_heads=4, max_position_embeddings=64)
        B, S = 8, 16
    paddle.seed(0)
    strategy = fleet.DistributedStrategy()
    strategy.hybrid_configs = {
        "dp_degree": 1, "mp_degree": 2, "pp_degree": 2,
        "sharding_degree": 2}
    strategy.sharding_configs = {"stage": 2}
    strategy.pipeline_configs = {"accumulate_steps": 2,
                                 "micro_batch_size": B // 4}
    hcg = fleet.init(is_collective=True, strategy=strategy)
    model = GPTForCausalLMPipe(cfg)
    dist_model = fleet.distributed_model(model)
    opt = fleet.distributed_optimizer(paddle.optimizer.AdamW(
        learning_rate=1e-4, parameters=model.parameters()))
    r = np.random.RandomState(0)
    ids = r.randint(0, cfg.vocab_size, (B, S + 1))
    x = paddle.to_tensor(ids[:, :-1])
    y = paddle.to_tensor(ids[:, 1:])

    def run_steps(k):
        for _ in range(k):
            float(dist_model.train_batch([x, y], opt))

    # enough step-time behind the save for the write to hide in (the
    # CPU smoke's background writer contends with XLA's host threads,
    # so the window must comfortably exceed the write)
    N = 8
    run_steps(2)                      # warmup (compile)

    def timed(fn, repeats=2):
        """best-of-k: the smoke fights host-load noise, and the BEST
        run is the one where nothing external interfered."""
        best = None
        for _ in range(repeats):
            t0 = time.perf_counter()
            fn()
            dt = time.perf_counter() - t0
            best = dt if best is None else min(best, dt)
        return best

    _gp.detach()                 # baseline steps stay unattributed
    dt_base = timed(lambda: run_steps(N))

    base_dir = tempfile.mkdtemp(prefix="ckpt_overlap_")
    try:
        m = ckpt_metrics()
        # sync: the whole commit protocol stalls the step loop
        mgr_s = CheckpointManager(os.path.join(base_dir, "sync"),
                                  keep_last_k=1, async_save=False)
        save_no = [0]

        def sync_round():
            save_no[0] += 1
            dist_model.save_checkpoint(manager=mgr_s, step=save_no[0])
            run_steps(N)

        stall_sync = timed(sync_round) - dt_base
        save_bytes = m["save_bytes"].value()
        snap_s = m["save_seconds"].value(phase="snapshot")
        write_s = m["save_seconds"].value(phase="write")
        # async: only the device->host snapshot stalls; the file
        # protocol runs behind the next N steps (wait() joins the tail
        # that did NOT fit behind them)
        mgr_a = CheckpointManager(os.path.join(base_dir, "async"),
                                  keep_last_k=1, async_save=True)

        def async_round():
            save_no[0] += 1
            dist_model.save_checkpoint(manager=mgr_a, step=save_no[0])
            run_steps(N)
            mgr_a.wait()

        stall_async = timed(async_round) - dt_base
        mgr_a.close()
        # goodput attribution of the two phases: each manager attached
        # its own journal when constructed, so the sync phase's steps +
        # commit stalls landed in <base>/sync and the async phase's —
        # including the writer thread's OVERLAPPED ckpt_async
        # intervals — in <base>/async
        gp_sync = mgr_s._goodput.summary() if mgr_s._goodput else {}
        gp_async = mgr_a._goodput.summary() if mgr_a._goodput else {}
        _gp.detach()
    finally:
        shutil.rmtree(base_dir, ignore_errors=True)

    step_s = dt_base / N
    _emit({
        "metric": "ckpt_save_overlap_stall_seconds",
        "value": round(max(stall_async, 0.0), 6),
        "unit": "s", "vs_baseline": 0.0,
        "sync_stall_seconds": round(max(stall_sync, 0.0), 6),
        "hidden_seconds": round(max(stall_sync - stall_async, 0.0), 6),
        "hidden_fraction": round(
            max(stall_sync - stall_async, 0.0) / stall_sync, 4)
        if stall_sync > 0 else 0.0,
        "step_seconds": round(step_s, 6),
        # the acceptance bound: async save must cost < 1 step-time
        "async_stall_lt_step": bool(stall_async < step_s),
        "save_bytes": save_bytes,
        "snapshot_seconds": round(snap_s, 6),
        "write_seconds": round(write_s, 6),
        "mesh": "sharding2xpp2xmp2", "devices": n,
        "train_steps_behind": N,
        # run-level attribution of the ASYNC phase (the shipping
        # config): ckpt_stall = the snapshot the loop pays, ckpt_async
        # = the overlapped background commit; the sync phase rides
        # along for the contrast (its ckpt_stall carries the whole
        # commit protocol)
        "goodput": gp_async,
        "goodput_sync_phase": gp_sync,
        "telemetry": _telemetry_section(),
        "device": str(getattr(dev, "device_kind", dev.platform)),
    })
    _emit({"metric": "ckpt_overlap_goodput_pct",
           "value": gp_async.get("goodput_pct", 0.0), "unit": "pct",
           "vs_baseline": 0.0,
           "sync_phase_goodput_pct": gp_sync.get("goodput_pct", 0.0),
           "segment_pct": gp_async.get("segment_pct", {})})
    _emit({"metric": "ckpt_overlap_health_spike_events",
           "value": float(dist_model._engine._health.event_count()),
           "unit": "events", "vs_baseline": 0.0})


# ---------------------------------------------------------------------------
# 4b. GPT-MoE hybrid: expert parallelism as a first-class mesh axis.
# TP x EP x DP on 8 vdevs — stacked expert weights sharded over 'ep',
# token dispatch/combine all_to_alls inside the compiled step (fused
# into a ppermute ring behind the expert GEMMs: ep_async_dispatch).
# Gates carried on the line: loss parity <= 1e-5 vs the single-device
# dense-dispatch golden (computed per batch shard so capacity/drop
# decisions match exactly), 0 recompiles after warmup, and the
# expert-load / drop-rate gauges + comm_bytes_total{axis="ep"} in the
# telemetry snapshot.
# ---------------------------------------------------------------------------
def bench_gpt_moe_hybrid(on_tpu, dev):
    import jax

    import paddle_tpu as paddle
    from paddle_tpu.distributed import fleet
    from paddle_tpu.distributed.engine import ParallelEngine
    from paddle_tpu.models import (GPTConfig, GPTForCausalLM,
                                   GPTPretrainingCriterion)

    n = jax.device_count()
    if n < 8:
        _emit({"metric": "gpt_moe_hybrid_train_tokens_per_sec",
               "value": 0.0, "unit": "needs_chips", "vs_baseline": 0.0,
               "needs_devices": 8, "have_devices": n})
        return
    if on_tpu:
        cfg = GPTConfig(vocab_size=50304, hidden_size=1024, num_layers=12,
                        num_heads=16, max_position_embeddings=1024,
                        dtype="bfloat16", num_experts=16, moe_every=2)
        dp = max(n // 4, 1)
        B, S, steps, state_dtype = 4 * dp, 1024, 5, "bfloat16"
    else:
        cfg = GPTConfig(vocab_size=512, hidden_size=64, num_layers=4,
                        num_heads=4, max_position_embeddings=64,
                        num_experts=8, moe_every=2)
        dp = max(n // 4, 1)
        B, S, steps, state_dtype = 4 * dp, 16, 2, None

    # single-device dense-dispatch golden, built BEFORE fleet.init (no
    # hybrid mesh -> plain layers, MoE group None) from the same seed —
    # the mp/ep model below draws the same full-shape init sequence
    paddle.seed(0)
    golden = GPTForCausalLM(cfg)
    crit = GPTPretrainingCriterion(cfg)

    strategy = fleet.DistributedStrategy()
    strategy.hybrid_configs = {
        "dp_degree": dp, "mp_degree": 2, "ep_degree": 2,
        # dispatch/combine a2a fused into the chunked expert-GEMM ring
        # (distributed/collective_matmul.py moe_a2a_ffn)
        "moe_configs": {"ep_async_dispatch": True}}
    hcg = fleet.init(is_collective=True, strategy=strategy)
    paddle.seed(0)
    model = GPTForCausalLM(cfg)
    opt = paddle.optimizer.AdamW(learning_rate=1e-4,
                                 parameters=model.parameters(),
                                 state_dtype=state_dtype)
    eng = ParallelEngine(model, opt, hcg.mesh)

    def loss_fn(m, b):
        return crit(m(b["x"]), b["y"]) + m.aux_loss

    step = eng.train_step(loss_fn)
    r = np.random.RandomState(0)
    ids = r.randint(0, cfg.vocab_size, (B, S + 1))
    x, y = ids[:, :-1], ids[:, 1:]
    batch = {"x": paddle.to_tensor(x), "y": paddle.to_tensor(y)}

    # loss parity on the FIRST step (identical weights): the engine's
    # reported loss is the pmean of per-rank local losses, and each
    # (dp, ep) rank holds one contiguous batch shard — so the golden is
    # the mean of the dense model's loss over the same shards (same
    # per-shard token count -> same capacity buckets -> same drops)
    shards = dp * 2
    Bl = B // shards
    g_losses = []
    for i in range(shards):
        xb = paddle.to_tensor(x[i * Bl:(i + 1) * Bl])
        yb = paddle.to_tensor(y[i * Bl:(i + 1) * Bl])
        g_losses.append(float(loss_fn(golden, {"x": xb, "y": yb})))
    g_loss = float(np.mean(g_losses))
    loss0 = float(step(batch))
    parity_err = abs(loss0 - g_loss)
    parity_tol = 0.02 if on_tpu else 1e-5   # bf16 vs the f32 smoke gate
    compiles_warm = eng.stats.compiles

    t0 = time.perf_counter()
    for _ in range(steps):
        loss = step(batch)
    float(loss)
    dt = time.perf_counter() - t0
    tok_s = B * S * steps / dt

    led = eng.comm_ledger()
    comm_bytes_per_step = {
        f"{a}/{o}": round(t["bytes"], 1)
        for (a, o), t in sorted(led.totals().items())} if led else {}
    tel = _telemetry_section()
    load = {k.split("expert=")[1].split(",")[0].rstrip("}"): v
            for k, v in tel.items()
            if k.startswith("moe_expert_load") and "layer=layer0" in k}
    peak, _ = _chip(dev)
    n_params = cfg.num_params()
    mfu = (6.0 * n_params * tok_s / (peak * n)) if peak else 0.0
    _emit({
        "metric": "gpt_moe_hybrid_train_tokens_per_sec" if on_tpu
        else "gpt_moe_hybrid_smoke_tokens_per_sec",
        "value": round(tok_s, 2),
        "unit": "tokens/s",
        "vs_baseline": round(mfu / 0.45, 4) if peak else 0.0,
        "mesh": f"dp{dp}xep2xmp2", "devices": n,
        "num_experts": cfg.num_experts,
        "ep_async_dispatch": True,
        "loss_parity_err": round(parity_err, 8),
        "compiles": eng.stats.compiles,
        "cache_hits": eng.stats.cache_hits,
        "recompiles_after_warmup": eng.stats.compiles - compiles_warm,
        "comm_bytes_per_step": comm_bytes_per_step,
        "expert_load_layer0": load,
        "telemetry": tel,
        "device": str(getattr(dev, "device_kind", dev.platform)),
    })
    # the exact gates ride their own lines so bench_compare can pin them
    _emit({"metric": "gpt_moe_hybrid_loss_parity",
           "value": 1.0 if parity_err <= parity_tol else 0.0,
           "unit": "pass",
           "vs_baseline": 1.0 if parity_err <= parity_tol else 0.0,
           "err": round(parity_err, 8), "tol": parity_tol})


# ---------------------------------------------------------------------------
# 3b. Collective-matmul overlap microbench: the fused ring decompositions
# (distributed/collective_matmul.py — ag_matmul + matmul_rs, the TP/SP
# hot-path pair) vs the unfused all_gather -> GEMM -> psum_scatter chain
# on the same mesh. On TPU the fused rings hide the ICI transfer behind
# partial GEMMs; on the CPU harness the line still emits (correctness +
# plumbing smoke, speedup ~1x is expected there).
# ---------------------------------------------------------------------------
def bench_tp_overlap(on_tpu, dev):
    import jax
    import jax.numpy as jnp
    from jax import lax
    from jax.sharding import Mesh, PartitionSpec as P

    from paddle_tpu.distributed import collective_matmul as cm
    from paddle_tpu.distributed.engine import _shard_map

    n = jax.device_count()
    if n < 2:
        _emit({"metric": "tp_overlap_matmul_ms", "value": 0.0,
               "unit": "needs_chips", "vs_baseline": 0.0,
               "needs_devices": 2, "have_devices": n})
        return
    mesh = Mesh(np.array(jax.devices()).reshape(n), ("mp",))
    if on_tpu:
        S, B, K, N = 2048, 4, 4096, 4096
        dt, iters = jnp.bfloat16, 20
    else:
        S, B, K, N = 128, 2, 64, 128
        dt, iters = jnp.float32, 3
    r = np.random.RandomState(0)
    x = jnp.asarray(r.randn(S, B, K), dt)        # seq-major [s, b, h]
    w1 = jnp.asarray(r.randn(K, N), dt)          # column-sharded
    w2 = jnp.asarray(r.randn(N, K), dt)          # row-sharded

    def fused(xs, a, b):
        h = cm.ag_matmul(xs, a, ("mp",), 0)
        return cm.matmul_rs(h, b, ("mp",), 0)

    def unfused(xs, a, b):
        h = lax.all_gather(xs, ("mp",), axis=0, tiled=True) @ a
        return lax.psum_scatter(h @ b, "mp", scatter_dimension=0,
                                tiled=True)

    in_specs = (P("mp"), P(None, "mp"), P("mp"))

    def timed(fn):
        step = jax.jit(_shard_map(fn, mesh, in_specs, P("mp")))
        step(x, w1, w2).block_until_ready()      # compile + warm
        t0 = time.perf_counter()
        for _ in range(iters):
            out = step(x, w1, w2)
        out.block_until_ready()
        return (time.perf_counter() - t0) / iters * 1e3

    fused_ms = timed(fused)
    unfused_ms = timed(unfused)
    _emit({
        "metric": "tp_overlap_matmul_ms",
        "value": round(fused_ms, 3),
        "unit": "ms",
        # the gate on chip: fused must not be slower than unfused
        "vs_baseline": round(unfused_ms / fused_ms, 4) if fused_ms else 0.0,
        "unfused_ms": round(unfused_ms, 3),
        "shape": [S, B, K, N], "dtype": str(jnp.dtype(dt)),
        "devices": n,
        "device": str(getattr(dev, "device_kind", dev.platform)),
    })


# ---------------------------------------------------------------------------
# On-chip Pallas kernel parity at small shapes (CI runs the kernels in
# interpret mode on CPU only; chip_smoke.py is the full-width gate)
# ---------------------------------------------------------------------------
def bench_kernel_parity(on_tpu, dev):
    import jax
    import jax.numpy as jnp

    from paddle_tpu.models.llama import _cache_attention_dense
    from paddle_tpu.ops.pallas.decode_attention import decode_attention
    from paddle_tpu.ops.pallas.flash_attention import flash_attention_fwd

    interpret = not on_tpu
    r = np.random.RandomState(0)
    B, S, H, D = 2, 512, 4, 128
    dt = jnp.bfloat16 if on_tpu else jnp.float32
    q = jnp.asarray(r.randn(B, S, H, D), dt)
    k = jnp.asarray(r.randn(B, S, H, D), dt)
    v = jnp.asarray(r.randn(B, S, H, D), dt)

    def xla_ref(q, k, v):
        qf = jnp.swapaxes(q, 1, 2).astype(jnp.float32)
        kf = jnp.swapaxes(k, 1, 2).astype(jnp.float32)
        vf = jnp.swapaxes(v, 1, 2).astype(jnp.float32)
        s = jnp.einsum("bhsd,bhtd->bhst", qf, kf) / np.sqrt(D)
        keep = jnp.arange(S)[:, None] >= jnp.arange(S)[None, :]
        s = jnp.where(keep[None, None], s, -1e30)
        p = jax.nn.softmax(s, -1)
        return jnp.swapaxes(
            jnp.einsum("bhst,bhtd->bhsd", p, vf), 1, 2).astype(q.dtype)

    out = flash_attention_fwd(q, k, v, True, None, interpret)
    ref = xla_ref(q, k, v)
    fwd_err = float(jnp.abs(out.astype(jnp.float32)
                            - ref.astype(jnp.float32)).max())
    gk = jax.grad(lambda k: flash_attention_fwd(
        q, k, v, True, None, interpret).astype(jnp.float32).sum())(k)
    gr = jax.grad(lambda k: xla_ref(q, k, v).astype(
        jnp.float32).sum())(k)
    bwd_err = float(jnp.abs(gk.astype(jnp.float32)
                            - gr.astype(jnp.float32)).max())

    # decode kernel vs dense cache attention (serving shape)
    M, KV = 1024, 4
    qd = jnp.asarray(r.randn(1, 1, H, D), dt)
    kc = jnp.asarray(r.randn(1, KV, M, D), dt)
    vc = jnp.asarray(r.randn(1, KV, M, D), dt)
    dk = decode_attention(qd, kc, vc, 900, interpret=interpret)
    dd = _cache_attention_dense(qd, kc, vc, 900, 1)
    dec_err = float(jnp.abs(dk.astype(jnp.float32)
                            - dd.astype(jnp.float32)).max())

    # paged (block-table) kernel vs gathered dense, scrambled pages
    from paddle_tpu.ops.pallas.decode_attention import (
        paged_attention_dense, paged_decode_attention)

    page = 128
    npages = M // page
    P = npages + 3
    kp = jnp.asarray(r.randn(P, KV, page, D), dt)
    vp = jnp.asarray(r.randn(P, KV, page, D), dt)
    tbl = jnp.asarray(r.permutation(P)[:npages].reshape(1, npages),
                      jnp.int32)
    lens = jnp.asarray([900], jnp.int32)
    pk = paged_decode_attention(qd, kp, vp, tbl, lens,
                                interpret=interpret)
    pd = paged_attention_dense(qd, kp, vp, tbl, lens)
    paged_err = float(jnp.abs(pk.astype(jnp.float32)
                              - pd.astype(jnp.float32)).max())

    tol = 0.05 if on_tpu else 1e-4  # bf16 vs f32-ref on chip
    ok = (fwd_err < tol and bwd_err < 20 * tol and dec_err < tol
          and paged_err < tol)
    _emit({
        "metric": "pallas_kernel_parity_onchip" if on_tpu
        else "pallas_kernel_parity_interpret",
        "value": 1.0 if ok else 0.0,
        "unit": "pass",
        "vs_baseline": 1.0 if ok else 0.0,
        "flash_fwd_max_err": round(fwd_err, 5),
        "flash_bwd_max_err": round(bwd_err, 5),
        "decode_max_err": round(dec_err, 5),
        "paged_max_err": round(paged_err, 5),
        "device": str(getattr(dev, "device_kind", dev.platform)),
    })


# ---------------------------------------------------------------------------
# 2. GPT-3 1.3B training MFU (BASELINE row 2) - the headline, printed last
# ---------------------------------------------------------------------------
def bench_gpt(on_tpu, dev):
    import paddle_tpu as paddle
    from paddle_tpu.distributed import fleet
    from paddle_tpu.distributed.engine import ParallelEngine
    from paddle_tpu.models import GPTConfig, GPTForCausalLM, \
        GPTPretrainingCriterion

    peak, _ = _chip(dev)
    if on_tpu:
        # GPT-3 1.3B (BASELINE config: Fleet TP - degree 1 on one chip):
        # hidden 2048 x 24 layers, d_head 128. bf16 params + bf16 moments
        # (AdamW math in f32) to fit the 16GB HBM of a v5e chip.
        cfg = GPTConfig(vocab_size=50304, hidden_size=2048, num_layers=24,
                        num_heads=16, max_position_embeddings=1024,
                        dtype="bfloat16")
        B, S, steps = 4, 1024, 5
        state_dtype = "bfloat16"
    else:  # BENCH_FORCE_CPU=1 smoke config
        cfg = GPTConfig(vocab_size=1024, hidden_size=128, num_layers=2,
                        num_heads=4, max_position_embeddings=128)
        B, S, steps = 4, 64, 2
        state_dtype = None

    r = np.random.RandomState(0)
    paddle.seed(0)
    model = GPTForCausalLM(cfg)  # dtype casts params on TPU
    crit = GPTPretrainingCriterion(cfg)
    opt = paddle.optimizer.AdamW(learning_rate=1e-4,
                                 parameters=model.parameters(),
                                 state_dtype=state_dtype)
    strategy = fleet.DistributedStrategy()
    strategy.hybrid_configs = {"dp_degree": 1, "mp_degree": 1}
    hcg = fleet.init(is_collective=True, strategy=strategy)
    eng = ParallelEngine(model, opt, hcg.mesh)
    step = eng.train_step(lambda m, b: crit(m(b["x"]), b["y"]))
    ids = r.randint(0, cfg.vocab_size, (B, S + 1))
    batch = {"x": paddle.to_tensor(ids[:, :-1]),
             "y": paddle.to_tensor(ids[:, 1:])}
    loss = step(batch)  # compile + warmup
    float(loss)
    t0 = time.perf_counter()
    for _ in range(steps):
        loss = step(batch)
    float(loss)
    tok_s = B * S * steps / (time.perf_counter() - t0)

    n_params = cfg.num_params()
    mfu = (6.0 * n_params * tok_s / peak) if peak else 0.0
    if on_tpu:
        _emit({
            "metric": "gpt1p3b_train_mfu",
            "value": round(mfu, 4),
            "unit": "mfu",
            "vs_baseline": round(mfu / 0.45, 4),
            "tokens_per_sec_per_chip": round(tok_s, 2),
            "batch": B,
            "device": str(getattr(dev, "device_kind", dev.platform)),
            "params": n_params,
            "telemetry": _telemetry_section(),
        })
    else:
        _emit({
            "metric": "gpt_smoke_train_tokens_per_sec",
            "value": round(tok_s, 2),
            "unit": "tokens/s",
            "vs_baseline": 0.0,
            "telemetry": _telemetry_section(),
        })


_BENCHES = {}

# Per-bench subprocess timeouts. gpt (the headline) gets the largest
# budget; everything else is short so a single hang can't eat the
# driver's budget.
_TIMEOUTS = {"gpt": 900, "llama_decode": 420, "llama_decode_int8": 420,
             "llama_decode_ragged": 420, "serving": 420,
             "serving_chunked": 600, "serving_prefix_spec": 600,
             "serving_disagg": 600,
             "resnet": 300,
             "moe": 300, "gpt_moe_hybrid": 420, "gpt13b_hybrid": 900,
             "tp_overlap": 240, "kernel_parity": 240,
             "ckpt_overlap": 420}
_ORDER = ("gpt", "llama_decode", "llama_decode_int8",
          "llama_decode_ragged", "serving", "serving_chunked",
          "serving_prefix_spec", "serving_disagg", "resnet",
          "moe", "gpt_moe_hybrid", "gpt13b_hybrid", "ckpt_overlap",
          "tp_overlap", "kernel_parity")
# benches that need a virtual multi-device mesh under BENCH_FORCE_CPU=1
_NEEDS_VDEV = {"gpt13b_hybrid": 8, "tp_overlap": 8, "gpt_moe_hybrid": 8,
               "ckpt_overlap": 8}


def _run_one(name, deadline_s=None):
    """Run one bench in this process; the return value is the exit
    code. No TPU and no BENCH_FORCE_CPU=1 is a failure that names the
    platform found; so is a bench that raises."""
    import os
    import traceback

    # The watchdog is armed BEFORE any jax backend init so a hang
    # anywhere (client creation included) ends in a stack dump and a
    # machine-readable line instead of a silent parent kill.
    if deadline_s is None:  # explicit 0 disables the watchdog
        deadline_s = _TIMEOUTS.get(name, 600)
    if deadline_s > 0:
        import faulthandler
        import threading

        # Stack dump (to stderr; the parent re-prints stderr on
        # failure) fires BEFORE _die so the hang location is captured,
        # then _die emits the machine-readable line and exits.
        faulthandler.dump_traceback_later(max(deadline_s - 30, 3),
                                          exit=False)

        def _die():
            _emit({"metric": f"bench_{name}", "value": 0.0,
                   "unit": "error", "vs_baseline": 0.0,
                   "error": f"watchdog: exceeded {deadline_s - 15}s "
                            "(stack on stderr)"})
            os._exit(3)

        t = threading.Timer(max(deadline_s - 15, 5), _die)
        t.daemon = True
        t.start()

    force_cpu = bool(os.environ.get("BENCH_FORCE_CPU"))
    if force_cpu:
        os.environ["JAX_PLATFORMS"] = "cpu"
        nv = _NEEDS_VDEV.get(name)
        if nv:
            import re

            flags = re.sub(
                r"--xla_force_host_platform_device_count=\d+", "",
                os.environ.get("XLA_FLAGS", ""))
            os.environ["XLA_FLAGS"] = (
                flags + f" --xla_force_host_platform_device_count"
                        f"={nv}").strip()
    import jax

    dev = jax.devices()[0]
    on_tpu = dev.platform == "tpu"
    if not on_tpu and not force_cpu:
        _emit({"metric": f"bench_{name}", "value": 0.0, "unit": "error",
               "vs_baseline": 0.0,
               "error": f"no TPU: jax found platform {dev.platform!r} "
                        f"({dev.device_kind}); set BENCH_FORCE_CPU=1 to "
                        "ask for the CPU count-and-parity lines"})
        return 1
    fn = _BENCHES[name]
    try:
        fn(on_tpu, dev)
    except Exception as e:
        _emit({"metric": fn.__name__, "value": 0.0, "unit": "error",
               "vs_baseline": 0.0,
               "error": f"{type(e).__name__}: {e}",
               "trace": traceback.format_exc()[-400:]})
        return 1
    return 0


def bench_llama_decode_int8(on_tpu, dev):
    bench_llama_decode(on_tpu, dev, weight_only=True)


def main(argv):
    _BENCHES.update(resnet=bench_resnet, moe=bench_moe,
                    llama_decode=bench_llama_decode, gpt=bench_gpt,
                    kernel_parity=bench_kernel_parity,
                    llama_decode_int8=bench_llama_decode_int8,
                    llama_decode_ragged=bench_llama_decode_ragged,
                    serving=bench_serving_mixed,
                    serving_chunked=bench_serving_chunked,
                    serving_prefix_spec=bench_serving_prefix_spec,
                    serving_disagg=bench_serving_disagg,
                    gpt_moe_hybrid=bench_gpt_moe_hybrid,
                    gpt13b_hybrid=bench_gpt13b_hybrid,
                    ckpt_overlap=bench_ckpt_overlap,
                    tp_overlap=bench_tp_overlap)
    if len(argv) > 1 and argv[1] == "--only":
        dl = int(argv[3]) if len(argv) > 3 else None
        return _run_one(argv[2], dl)
    # Each bench runs in its OWN process: TPU HBM is only reliably
    # released at process exit (compiled executables pin buffers), and
    # the 7B decode + 1.3B train benches each need most of a v5e chip.
    # The parent NEVER imports jax: a chip belongs to one process.
    import subprocess

    failed = []
    headline_lines = []
    for name in _ORDER:
        tmo = _TIMEOUTS[name]
        out, err, synth = "", "", None
        try:
            r = subprocess.run(
                [sys.executable, __file__, "--only", name, str(tmo)],
                capture_output=True, text=True, timeout=tmo)
            out, err = r.stdout or "", r.stderr or ""
            if r.returncode != 0:
                failed.append(f"{name} (rc={r.returncode})")
        except subprocess.TimeoutExpired as e:
            def _s(x):
                return (x.decode() if isinstance(x, bytes) else x) or ""
            out, err = _s(e.stdout), _s(e.stderr)
            synth = {"metric": f"bench_{name}", "value": 0.0,
                     "unit": "error", "vs_baseline": 0.0,
                     "error": f"timeout after {tmo}s (parent kill)"}
        except Exception as e:  # a hung bench must not drop later lines
            synth = {"metric": f"bench_{name}", "value": 0.0,
                     "unit": "error", "vs_baseline": 0.0,
                     "error": f"{type(e).__name__}: {e}"}
        if synth is not None:
            failed.append(f"{name} ({synth['error']})")
            _emit(synth)
        if out:
            print(out, end="" if out.endswith("\n") else "\n", flush=True)
        if err.strip():  # watchdog stack dumps / crash tracebacks
            sys.stderr.write(err[-4000:])
            sys.stderr.flush()
        if name == "gpt":
            def _valid(ln):
                # a timed-out child can leave a truncated final line;
                # only well-formed JSON may become the headline
                try:
                    json.loads(ln)
                    return True
                except ValueError:
                    return False
            headline_lines = [ln for ln in out.splitlines()
                              if '"metric"' in ln and _valid(ln)]
            if not headline_lines and synth is not None:
                headline_lines = [json.dumps(synth)]
        # The headline runs FIRST (so a later hang can't kill it) but
        # single-line parsers take the LAST line - re-emit it after
        # EVERY bench (including right after gpt: its own stdout can
        # end in stray WARNING lines), so a driver-level kill at any
        # point leaves the headline as the last complete line.
        for ln in headline_lines:
            print(ln, flush=True)
    if failed:
        sys.stderr.write("bench.py: failed: " + "; ".join(failed) + "\n")
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
