"""HBM memory ledger + roofline step report (observability/memledger).

Under test:
- per-executable memory ledger: memory_analysis totals present and
  byte-identical across re-analyses of the same program; gauges
  published under the schema'd names; ZERO recompiles of the live
  step with the ledger on
- model-state accounting pinned against the closed form (global shape
  / sharding degree) for the gpt13b hybrid smoke config — incl. ZeRO
  stage-2 scattered optimizer state and pp x vpp stacked-chunk
  ownership — and for a plain dp engine
- roofline verdicts: the pure math (fake TPU device -> known peaks,
  bound selection, headroom/util percentages, CPU -> "unknown"), and
  the engine/serving report plumbing
- serving: per-site ledgers (prefill buckets + the shared decode),
  compile stability with the ledger on, KV-pool closed form,
  suggest_pool_pages / pool_pages="auto"
- /healthz on the metrics exporter: 200 + snapshot age that scrapes
  do NOT refresh
- flight records carry the memory context
- tpulint: memledger stays clean with ZERO baseline entries
"""
import json
import sys
import urllib.request
from pathlib import Path

import numpy as np
import pytest

import jax

import paddle_tpu as paddle
from paddle_tpu import observability as obs
from paddle_tpu.distributed.engine import ParallelEngine
from paddle_tpu.observability import memledger as ml

F32 = 4


# ---------------------------------------------------------------------------
# fixtures
# ---------------------------------------------------------------------------
@pytest.fixture(scope="module")
def dp_mem_engine():
    """dp8 tiny GPT with the memory ledger ON (ctor knob)."""
    from paddle_tpu.distributed import fleet
    from paddle_tpu.models import (GPTConfig, GPTForCausalLM,
                                   GPTPretrainingCriterion)

    obs.reset_registry()
    fleet._fleet_state.update(initialized=False, hcg=None, strategy=None)
    paddle.seed(0)
    cfg = GPTConfig(vocab_size=128, hidden_size=32, num_layers=2,
                    num_heads=2, max_position_embeddings=32)
    model = GPTForCausalLM(cfg)
    crit = GPTPretrainingCriterion(cfg)
    opt = paddle.optimizer.AdamW(learning_rate=1e-4,
                                 parameters=model.parameters())
    strategy = fleet.DistributedStrategy()
    strategy.hybrid_configs = {"dp_degree": 8, "mp_degree": 1}
    hcg = fleet.init(is_collective=True, strategy=strategy)
    eng = ParallelEngine(model, opt, hcg.mesh, mem_ledger=True)
    step = eng.train_step(lambda m, b: crit(m(b["x"]), b["y"]))
    r = np.random.RandomState(0)
    ids = r.randint(0, 128, (8, 17))
    batch = {"x": paddle.to_tensor(ids[:, :-1]),
             "y": paddle.to_tensor(ids[:, 1:])}
    for _ in range(3):
        float(step(batch))
    return eng, step, batch


@pytest.fixture(scope="module")
def hybrid_engine():
    """The GPT hybrid smoke topology: mp2 x pp2 x sharding2 stage-2,
    vpp=2 — the pinned target for chunk-aware state accounting."""
    from paddle_tpu.distributed import fleet
    from paddle_tpu.models import GPTForCausalLMPipe
    from paddle_tpu.models.gpt import GPTConfig

    fleet._fleet_state.update(initialized=False, hcg=None, strategy=None)
    paddle.seed(0)
    cfg = GPTConfig(vocab_size=512, hidden_size=64, num_layers=4,
                    num_heads=4, max_position_embeddings=64)
    strategy = fleet.DistributedStrategy()
    strategy.hybrid_configs = {
        "dp_degree": 1, "mp_degree": 2, "pp_degree": 2,
        "sharding_degree": 2,
        "pp_configs": {"num_virtual_pipeline_stages": 2}}
    strategy.sharding_configs = {"stage": 2}
    strategy.pipeline_configs = {"accumulate_steps": 2,
                                 "micro_batch_size": 2}
    hcg = fleet.init(is_collective=True, strategy=strategy)
    model = GPTForCausalLMPipe(cfg)
    dist_model = fleet.distributed_model(model)
    opt = fleet.distributed_optimizer(
        paddle.optimizer.AdamW(learning_rate=1e-4,
                               parameters=model.parameters()))
    r = np.random.RandomState(0)
    B, S = 8, 16
    ids = r.randint(0, cfg.vocab_size, (B, S + 1))
    x = paddle.to_tensor(ids[:, :-1])
    y = paddle.to_tensor(ids[:, 1:])
    float(dist_model.train_batch([x, y], opt))
    eng = dist_model._engine
    eng._mem_on = True          # knob after the fact: accessors only
    return eng, cfg, hcg


# ---------------------------------------------------------------------------
# per-executable ledger
# ---------------------------------------------------------------------------
class TestExecutableLedger:
    def test_totals_present(self, dp_mem_engine):
        eng, _, _ = dp_mem_engine
        led = eng.memory_ledger()
        assert led is not None and led.available
        assert led.argument_bytes > 0
        assert led.output_bytes > 0
        assert led.alias_bytes > 0          # donated params alias
        d = led.to_dict()
        for k in ("temp_bytes", "argument_bytes", "output_bytes",
                  "alias_bytes", "generated_code_bytes", "peak_bytes"):
            assert k in d
        # peak folds the donation alias out exactly once
        assert led.peak_bytes == (led.argument_bytes + led.output_bytes
                                  + led.temp_bytes
                                  + led.generated_code_bytes
                                  - led.alias_bytes)
        assert led.traffic_bytes == (led.argument_bytes
                                     + led.output_bytes
                                     + 2 * led.temp_bytes)

    def test_stable_across_reanalysis(self, dp_mem_engine):
        """Re-lowering the same program must reproduce the same byte
        classes (the 'stable across recompiles' contract)."""
        eng, _, _ = dp_mem_engine
        led1 = eng.memory_ledger()
        eng._mem_ledgers.pop(eng._last_key)
        led2 = eng.memory_ledger()
        assert led2 is not None and led1.same_totals(led2)

    def test_zero_recompiles_with_ledger_on(self, dp_mem_engine):
        eng, step, batch = dp_mem_engine
        c0 = eng.stats.compiles
        float(step(batch))
        float(step(batch))
        assert eng.stats.compiles == c0

    def test_gauges_published_inside_schema(self, dp_mem_engine):
        from paddle_tpu.observability import catalog

        eng, _, _ = dp_mem_engine
        snap = eng.metrics_snapshot()["metrics"]
        with open(catalog.SCHEMA_PATH) as f:
            schema = json.load(f)
        led = eng.memory_ledger()
        rows = {r["labels"]["program"]: r["value"] for r in
                snap["paddle_tpu_mem_temp_bytes"]["series"]}
        assert rows["train"] == led.temp_bytes
        for name in ("paddle_tpu_mem_temp_bytes",
                     "paddle_tpu_mem_argument_bytes",
                     "paddle_tpu_mem_output_bytes",
                     "paddle_tpu_mem_alias_bytes",
                     "paddle_tpu_mem_generated_code_bytes",
                     "paddle_tpu_mem_state_bytes",
                     "paddle_tpu_mem_analytic_drift",
                     "paddle_tpu_mem_live_bytes",
                     "paddle_tpu_mem_live_peak_bytes"):
            assert name in snap and name in schema
            for row in snap[name]["series"]:
                assert sorted(row["labels"]) == schema[name]["labels"]

    def test_unavailable_is_graceful(self):
        led = ml.analyze(object(), (), program="bogus")
        assert not led.available and led.note
        assert led.peak_bytes == 0

    def test_live_watermark_monotone(self, dp_mem_engine):
        eng, _, _ = dp_mem_engine
        m = eng._metrics
        assert m["mem_live_peak"].value() >= m["mem_live"].value() > 0


# ---------------------------------------------------------------------------
# model-state accounting
# ---------------------------------------------------------------------------
class TestStateAccounting:
    def test_dp_replicated_closed_form(self, dp_mem_engine):
        """dp-only: every param/state array is replicated, so one
        device holds the full bytes."""
        eng, _, _ = dp_mem_engine
        acct = eng.state_accounting()
        expect_params = sum(
            int(np.prod(p._value.shape)) * p._value.dtype.itemsize
            for p in eng.params)
        assert acct.components["params"] == expect_params
        assert acct.components["grads"] == expect_params
        # AdamW: two f32 moments per trainable param, replicated
        expect_state = 2 * sum(
            int(np.prod(p._value.shape)) * F32 for p in eng.trainable)
        assert acct.components["optimizer_state"] == expect_state
        assert acct.components == {
            **acct.components, **ml.closed_form_state_bytes(eng)}

    def test_hybrid_closed_form_zero2_vpp(self, hybrid_engine):
        """The pinned satellite: mp2 x pp2 x sharding2 stage-2, vpp=2.
        Param bytes = global / (spec degree); ZeRO-2 optimizer state
        additionally / sharding degree; the stacked block params carry
        the [vpp, L/(pp*vpp), ...] leading chunk axes sharded over
        'pp' — all of it must match the closed form byte-for-byte."""
        eng, cfg, hcg = hybrid_engine
        acct = eng.state_accounting()
        closed = ml.closed_form_state_bytes(eng)
        for k, v in closed.items():
            assert acct.components[k] == v, (k, acct.components[k], v)
        # independent sanity anchors, from first principles:
        # every param is stored at global_size / degree where degree
        # multiplies the axes in its spec (stage 2 leaves params
        # unscattered), so per-rank params < full model params
        full = sum(int(np.prod(p._value.shape))
                   * p._value.dtype.itemsize for p in eng.params)
        assert acct.components["params"] < full
        # the stacked decoder blocks: [vpp, L/(pp*vpp), ...] sharded
        # over pp on the chunk axis -> exactly half the rows per rank
        stacked = [p for n, p in eng.model.named_parameters()
                   if n.startswith("blocks__") and p._value.ndim >= 3]
        assert stacked, "expected stacked pp block params"
        for p in stacked:
            # global [vpp=2, L/vpp=2, ...]; axis 1 sharded over 'pp'
            # -> each rank owns exactly one K=1 row per circuit chunk
            assert tuple(p._value.shape)[:2] == (2, 2)
            local = p._value.sharding.shard_shape(
                tuple(p._value.shape))
            assert local[:2] == (2, 1)
            got = ml.shard_bytes(p._value)
            want = (int(np.prod(p._value.shape))
                    * p._value.dtype.itemsize
                    // ml._spec_degree(p, eng.mesh))
            assert got == want
        # ZeRO stage-2: eligible optimizer state is scattered over
        # 'sharding' — state bytes strictly below param bytes would
        # only hold without moments; instead pin: state of eligible
        # params == 2 x param shard bytes / sharding_degree (f32
        # moments over f32 params here)
        zero = eng._zero
        assert zero.axis == "sharding" and zero.n == 2
        assert zero.entries, "stage-2 plan should cover params"

    def test_drift_and_activation_term(self, hybrid_engine):
        eng, _, _ = hybrid_engine
        acct = eng.state_accounting()
        assert acct.components["activation_ckpt"] > 0
        assert acct.analytic_bytes > 0
        assert np.isfinite(acct.drift)
        d = acct.to_dict()
        assert set(d) == {"components", "groups", "measured_bytes",
                          "device_bytes", "analytic_bytes",
                          "analytic_drift"}
        # no offload on this engine: nothing host-resident
        assert d["device_bytes"] == d["measured_bytes"]
        json.dumps(d)     # the report must serialize

    def test_autotuner_crosscheck_matches_gauge_math(self):
        from paddle_tpu.distributed.auto_tuner import AutoTuner

        model = {"hidden_size": 64, "num_layers": 4, "vocab_size": 512,
                 "num_heads": 4}
        t = AutoTuner(model, num_devices=8, global_batch=8, seq_len=16)
        cfg = {"dp_degree": 1, "mp_degree": 2, "pp_degree": 2,
               "sharding_degree": 2, "micro_batch_size": 2}
        drift = t.crosscheck(cfg, measured_gb=0.001)
        from paddle_tpu.distributed.auto_tuner.cost_model import \
            estimate_memory_gb

        pred = estimate_memory_gb(model, cfg, 8, 16)
        assert drift == pytest.approx((pred - 0.001) / 0.001)


# ---------------------------------------------------------------------------
# roofline
# ---------------------------------------------------------------------------
class _FakeV5p:
    device_kind = "TPU v5p"
    platform = "tpu"


class TestRoofline:
    def test_hbm_bound_verdict(self):
        # v5p: 459e12 FLOPs, 2.765e12 HBM B/s, 600e9 ICI B/s
        rep = ml.roofline(step_seconds=0.01,
                          flops_per_step=459e12 * 1e-3,      # 1 ms
                          hbm_traffic_bytes=2.765e12 * 5e-3,  # 5 ms
                          wire_bytes=600e9 * 2e-3,            # 2 ms
                          device=_FakeV5p())
        assert rep.bound == "hbm-bound"
        assert rep.seconds["hbm"] == pytest.approx(5e-3)
        assert rep.headroom_pct["hbm"] == 0.0
        assert rep.headroom_pct["compute"] == pytest.approx(80.0)
        assert rep.headroom_pct["ici"] == pytest.approx(60.0)
        assert rep.util_pct["hbm"] == pytest.approx(50.0)

    def test_compute_bound_and_exposed_override(self):
        rep = ml.roofline(step_seconds=0.01,
                          flops_per_step=459e12 * 8e-3,
                          hbm_traffic_bytes=2.765e12 * 1e-3,
                          wire_bytes=600e9 * 100.0,   # huge analytic
                          exposed_ici_seconds=1e-3,   # ...but hidden
                          device=_FakeV5p())
        assert rep.bound == "compute-bound"
        assert rep.seconds["ici"] == pytest.approx(1e-3)

    def test_cpu_is_unknown(self):
        rep = ml.roofline(step_seconds=0.01, flops_per_step=1e12,
                          hbm_traffic_bytes=1e9, wire_bytes=1e9,
                          exposed_ici_seconds=0.5,
                          device=jax.devices()[0])
        assert rep.bound == "unknown"
        assert set(rep.headroom_pct) == set(ml.RESOURCES)
        json.dumps(rep.to_dict())

    def test_engine_report(self, hybrid_engine):
        eng, _, _ = hybrid_engine
        rep = eng.roofline_report()
        assert rep.bound == "unknown"          # CPU harness
        assert rep.program == "train"
        assert set(rep.seconds) == set(ml.RESOURCES)


# ---------------------------------------------------------------------------
# serving
# ---------------------------------------------------------------------------
@pytest.fixture(scope="module")
def serving_mem_engine():
    from paddle_tpu.inference import (Config, ServingEngine,
                                      create_predictor)
    from paddle_tpu.models.llama import LlamaForCausalLM, llama_tiny

    paddle.seed(0)
    cfg = llama_tiny()
    model = LlamaForCausalLM(cfg)
    conf = Config().set_model(model).enable_paged_kv(page_size=8)
    pred = create_predictor(conf)
    eng = ServingEngine(pred, max_batch=4, decode_chunk=2,
                        mem_ledger=True)
    r = np.random.RandomState(0)
    for L in (7, 12):                               # warmup mix
        eng.submit(r.randint(1, cfg.vocab_size, (L,)), max_new_tokens=6)
    eng.run()
    warm = eng.stats.compiles
    for L in (24, 17, 11, 9, 5):                    # streamed mixes
        eng.submit(r.randint(1, cfg.vocab_size, (L,)), max_new_tokens=6)
    eng.run()
    return eng, warm, cfg


class TestServingMemLedger:
    def test_sites_analyzed(self, serving_mem_engine):
        eng, _, _ = serving_mem_engine
        led = eng.memory_ledger(("decode",))
        assert led is not None and led.available
        assert led.argument_bytes > 0
        prefill = [s for s in eng._mem_ledgers if s[0] == "prefill"]
        assert prefill, "prefill site should be analyzed"

    def test_zero_recompiles_after_warmup(self, serving_mem_engine):
        eng, warm, _ = serving_mem_engine
        assert eng.stats.compiles == warm

    def test_pool_closed_form_and_summary(self, serving_mem_engine):
        eng, _, cfg = serving_mem_engine
        mem = eng.memory_summary()
        st = mem["state"]
        # measured pool arrays == page_bytes x pool_pages closed form
        assert st["kv_pool_bytes"] == st["page_bytes"] * st["pool_pages"]
        assert st["page_bytes"] == (2 * cfg.num_layers
                                    * cfg.num_kv_heads * 8
                                    * cfg.head_dim * F32)
        assert "decode" in mem["executables"]
        json.dumps(mem)
        rep = eng.roofline_report()
        assert rep.program == "decode"
        assert rep.bound == "unknown"          # CPU harness

    def test_suggest_pool_pages(self):
        class Dev:
            def memory_stats(self):
                return {"bytes_limit": 1000}

        # (1000 * 0.9 - 300) // 50 = 12
        assert ml.suggest_pool_pages(Dev(), 50, 300) == 12
        assert ml.suggest_pool_pages(Dev(), 50, 899) is None
        assert ml.suggest_pool_pages(jax.devices()[0], 50, 0) is None

        class NoStats:
            def memory_stats(self):
                return None

        assert ml.suggest_pool_pages(NoStats(), 50, 0) is None

    def test_auto_pool_falls_back_on_cpu(self, serving_mem_engine):
        from paddle_tpu.inference import ServingEngine

        eng, _, _ = serving_mem_engine
        auto = ServingEngine(eng.pred, max_batch=4, pool_pages="auto")
        assert auto.P == eng.P                  # geometric default


# ---------------------------------------------------------------------------
# /healthz
# ---------------------------------------------------------------------------
class TestHealthz:
    def test_healthz_age_and_scrape_independence(self):
        from paddle_tpu.observability.metrics import MetricsRegistry

        reg = MetricsRegistry()
        reg.gauge("g").set(1.0)
        with obs.serve_metrics(0, registry=reg) as srv:
            url = f"http://127.0.0.1:{srv.port}"

            def get(path):
                with urllib.request.urlopen(url + path, timeout=5) as r:
                    return r.status, r.read().decode()

            code, body = get("/healthz")
            assert code == 200
            doc = json.loads(body)
            assert doc["status"] == "ok"
            assert doc["snapshot_age_seconds"] is None   # never ticked
            # a scrape must NOT refresh the liveness age
            code, _ = get("/metrics")
            assert code == 200
            assert json.loads(get("/healthz")[1])[
                "snapshot_age_seconds"] is None
            reg.snapshot()                               # an engine tick
            age = json.loads(get("/healthz")[1])["snapshot_age_seconds"]
            assert age is not None and 0.0 <= age < 60.0
            with pytest.raises(urllib.error.HTTPError):
                get("/bogus")


# ---------------------------------------------------------------------------
# flight-record memory context
# ---------------------------------------------------------------------------
class TestFlightMemoryContext:
    def test_record_carries_memory(self, dp_mem_engine, tmp_path):
        eng, _, _ = dp_mem_engine
        eng.metrics_snapshot()          # mem gauges are live
        rec = obs.get_recorder().record(reason="test")
        assert "memory" in rec
        gauges = rec["memory"]["gauges"]
        assert any(k.startswith("paddle_tpu_mem_temp_bytes")
                   for k in gauges)
        assert "device_memory_stats" in rec["memory"]
        path = obs.get_recorder().dump(str(tmp_path / "f.json"),
                                       reason="test")
        with open(path) as f:
            assert "memory" in json.load(f)


# ---------------------------------------------------------------------------
# tpulint: the new modules must stay clean with ZERO baseline entries
# ---------------------------------------------------------------------------
def test_tpulint_memledger_surface_zero_baseline():
    repo = Path(__file__).resolve().parents[1]
    sys.path.insert(0, str(repo))
    try:
        from tools.tpulint import ALL_RULES, lint_paths

        findings = lint_paths(
            [repo / "paddle_tpu" / "observability" / "memledger.py"],
            ALL_RULES, root=repo)
    finally:
        sys.path.remove(str(repo))
    assert findings == [], [str(f) for f in findings]
