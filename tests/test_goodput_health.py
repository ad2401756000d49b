"""Run-level goodput ledger + training health monitor.

Under test:
- observability/goodput.py — the closed segment taxonomy, the
  crash-durable JSONL journal (dangling-tail close as
  recovery_restart), nesting pause/resume disjointness, the wall-sum
  identity, offline summarize(), the no-op-when-detached hook
- observability/healthmon.py — rolling median+MAD spike/stall events
  (failpoint-driven loss-spike injection, nonfinite loss, silence on
  smooth descent), flight-record dump, /healthz degraded component,
  single-process straggler gauges
- ParallelEngine wiring — compile vs step_compute attribution, zero
  recompiles and bit-identical losses with the instrumentation on,
  goodput/health gauges in the registry snapshot
- CompileStats across restore_checkpoint — restore books NO compile
  and NO recompile, on the engine counters AND the registry counters
- ServingEngine — shed decisions land in the span ring as zero-length
  "shed" events, exported as Chrome "i" instants
- tools/run_report.py — journal waterfall/timeline
- SIGKILL matrix (slow): a kill mid-segment leaves a parseable
  journal; the relaunch closes it as recovery_restart and the
  cross-restart goodput_pct matches the straight run
"""
import json
import os
import sys
import time
import urllib.request
from pathlib import Path

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import observability as obs
from paddle_tpu.distributed import failpoints as fp
from paddle_tpu.observability import goodput as gp
from paddle_tpu.observability import healthmon as hm


@pytest.fixture(autouse=True)
def _clean_goodput_and_failpoints():
    gp.detach()
    fp.clear()
    hm.reset_monitor()
    yield
    gp.detach()
    fp.clear()
    hm.reset_monitor()


def _journal(base):
    return os.path.join(str(base), gp.JOURNAL_NAME)


# ---------------------------------------------------------------------------
# the ledger itself (pure host-side)
# ---------------------------------------------------------------------------
class TestGoodputLedger:
    def test_segments_journal_and_summary(self, tmp_path):
        led = gp.attach_dir(str(tmp_path))
        with gp.segment("step_compute"):
            time.sleep(0.03)
        with gp.segment("input_wait"):
            time.sleep(0.01)
        s = led.summary()
        assert s["segments"]["step_compute"] >= 0.03
        assert s["segments"]["input_wait"] >= 0.01
        assert s["goodput_pct"] > 0
        # the journal holds begin AND end lines, parseable
        recs = gp.read_journal(_journal(tmp_path))
        assert any(r["ev"] == "b" and r["seg"] == "step_compute"
                   for r in recs)
        assert any(r["ev"] == "e" and r["seg"] == "input_wait"
                   for r in recs)

    def test_wall_sum_identity(self, tmp_path):
        led = gp.attach_dir(str(tmp_path))
        for seg in ("compile", "step_compute", "ckpt_stall"):
            with gp.segment(seg):
                time.sleep(0.01)
        time.sleep(0.02)                      # unattributed -> idle
        s = led.summary()
        fg = sum(s["segments"].values())      # incl. synthesized idle
        assert fg == pytest.approx(s["wall_seconds"],
                                   rel=0.01, abs=1e-6)
        assert s["segments"]["idle"] >= 0.015

    def test_nested_segment_pauses_outer(self, tmp_path):
        """An inner segment PAUSES the outer: closed foreground
        intervals are disjoint, so compile-inside-step never double
        counts."""
        led = gp.attach_dir(str(tmp_path))
        with gp.segment("step_compute"):
            time.sleep(0.02)
            with gp.segment("compile"):
                time.sleep(0.03)
            time.sleep(0.02)
        s = led.summary()
        assert s["segments"]["compile"] >= 0.03
        assert s["segments"]["step_compute"] >= 0.04
        # disjoint: totals never exceed wall
        assert sum(s["segments"].values()) <= s["wall_seconds"] + 1e-6
        # the journal shows the split: two step_compute intervals
        recs = [r for r in gp.read_journal(_journal(tmp_path))
                if r["ev"] == "e" and r["seg"] == "step_compute"]
        assert len(recs) == 2

    def test_overlapped_background_excluded_from_wall_sum(self,
                                                          tmp_path):
        led = gp.attach_dir(str(tmp_path))
        t0 = time.time()
        with gp.segment("step_compute"):
            time.sleep(0.02)
        led.record_overlapped("ckpt_async", t0, time.time())
        s = led.summary()
        assert s["overlapped_seconds"]["ckpt_async"] >= 0.02
        assert "ckpt_async" not in s["segments"]

    def test_detached_segment_is_noop(self, tmp_path):
        assert gp.current() is None
        with gp.segment("step_compute"):
            pass
        gp.note_event("nothing")
        assert not os.path.exists(_journal(tmp_path))

    def test_same_dir_reattach_is_not_a_restart(self, tmp_path):
        led = gp.attach_dir(str(tmp_path))
        with gp.segment("step_compute"):
            pass
        assert gp.attach_dir(str(tmp_path)) is led
        assert led.summary()["restarts"] == 0

    def test_dangling_segment_closed_as_recovery_restart(self,
                                                         tmp_path):
        """Crash mid-segment: the journal stays parseable and the next
        process (a fresh ledger object on the same path) closes the
        dangling tail as recovery_restart."""
        led = gp.attach_dir(str(tmp_path))
        with gp.segment("step_compute"):
            time.sleep(0.02)
        led.begin("ckpt_stall")               # ... SIGKILL here
        time.sleep(0.05)
        led2 = gp.GoodputLedger(_journal(tmp_path))
        s = led2.summary()
        assert s["restarts"] == 1
        assert s["segments"]["recovery_restart"] >= 0.045
        assert s["segments"]["step_compute"] >= 0.02
        recs = gp.read_journal(_journal(tmp_path))
        rr = [r for r in recs if r.get("seg") == "recovery_restart"
              and r["ev"] == "e"]
        assert len(rr) == 1
        # offline summarize agrees with the live view
        off = gp.summarize(recs)
        assert off["restarts"] == 1
        assert off["segments"]["recovery_restart"] == pytest.approx(
            s["segments"]["recovery_restart"], abs=0.05)

    def test_truncated_tail_line_tolerated(self, tmp_path):
        led = gp.attach_dir(str(tmp_path))
        with gp.segment("step_compute"):
            time.sleep(0.01)
        # a kill mid-write can truncate the last line
        with open(_journal(tmp_path), "a") as f:
            f.write('{"ev": "b", "seg": "ckpt_st')
        led2 = gp.GoodputLedger(_journal(tmp_path))
        s = led2.summary()
        assert s["restarts"] == 1
        assert s["segments"]["step_compute"] >= 0.01

    def test_events_journaled(self, tmp_path):
        led = gp.attach_dir(str(tmp_path))
        gp.note_event("loss_spike", step=7, value=123.0)
        recs = gp.read_journal(_journal(tmp_path))
        ev = [r for r in recs if r.get("ev") == "h"]
        assert len(ev) == 1 and ev[0]["kind"] == "loss_spike"
        assert ev[0]["step"] == 7
        assert led.summary()["events"] == 1


# ---------------------------------------------------------------------------
# health monitor
# ---------------------------------------------------------------------------
class TestHealthMonitor:
    def test_failpoint_injected_loss_spike(self, tmp_path, monkeypatch):
        """The acceptance path: a deliberately injected loss spike is
        detected within the window — event + flight record + degraded
        status — and the event is journaled to the goodput ledger."""
        monkeypatch.setenv("PADDLE_TPU_FLIGHT_DIR", str(tmp_path))
        gp.attach_dir(str(tmp_path))
        mon = hm.HealthMonitor(warmup=8, flight_min_interval_s=0.0)
        fired = []
        with fp.scoped("health.loss_spike=corrupt@12"):
            for i in range(12):
                fired += mon.observe(loss=2.0 + 0.01 * (i % 3),
                                     grad_norm=1.0, step=i)
        assert len(fired) == 1
        ev = fired[0]
        assert ev["kind"] == "loss_spike" and ev["step"] == 11
        assert ev["z"] > 6.0
        assert mon.status() == "degraded"
        assert mon.event_count("loss_spike") == 1
        # the flight record exists and names the spike
        assert os.path.isfile(ev["flight_record"])
        with open(ev["flight_record"]) as f:
            assert "loss_spike" in json.load(f)["reason"]
        # durable: the goodput journal carries it
        recs = gp.read_journal(_journal(tmp_path))
        assert any(r.get("ev") == "h" and r.get("kind") == "loss_spike"
                   for r in recs)
        # counters in the registry
        reg = obs.get_registry().snapshot()["metrics"]
        series = reg["paddle_tpu_health_events_total"]["series"]
        vals = {s["labels"]["kind"]: s["value"] for s in series}
        assert vals.get("loss_spike", 0) >= 1

    def test_silent_on_smooth_descent(self):
        mon = hm.HealthMonitor(warmup=8)
        for i in range(50):
            mon.observe(loss=5.0 * 0.95 ** i,
                        grad_norm=2.0 + 0.05 * (i % 5),
                        step_seconds=0.01 + 0.001 * (i % 4))
        assert mon.event_count() == 0
        assert mon.status() == "ok"

    def test_nonfinite_loss_always_fires(self):
        mon = hm.HealthMonitor(warmup=8, flight_on_spike=False)
        ev = mon.observe(loss=float("nan"), step=3)
        assert ev and ev[0]["kind"] == "loss_nonfinite"
        assert mon.status() == "degraded"

    def test_grad_norm_spike(self):
        mon = hm.HealthMonitor(warmup=8, flight_on_spike=False)
        for i in range(10):
            mon.observe(grad_norm=1.0 + 0.02 * (i % 4))
        ev = mon.observe(grad_norm=500.0, step=10)
        assert ev and ev[0]["kind"] == "grad_norm_spike"

    def test_unarmed_below_warmup(self):
        mon = hm.HealthMonitor(warmup=8, flight_on_spike=False)
        for i in range(4):
            mon.observe(loss=1.0)
        assert not mon.observe(loss=1e9)      # still warming up
        assert mon.event_count() == 0

    def test_healthz_degraded_component(self, tmp_path, monkeypatch):
        from paddle_tpu.observability.exporter import serve_metrics

        monkeypatch.setenv("PADDLE_TPU_FLIGHT_DIR", str(tmp_path))
        mon = hm.get_monitor()
        mon.flight_on_spike = False
        mon.observe(loss=float("inf"))        # degrade
        with serve_metrics(0) as srv:
            with urllib.request.urlopen(
                    f"http://127.0.0.1:{srv.port}/healthz") as resp:
                doc = json.loads(resp.read())
        assert doc["status"] == "degraded"
        comps = {c["component"]: c["status"]
                 for c in doc.get("components", [])}
        assert comps.get("healthmon") == "degraded"
        hm.reset_monitor()
        assert hm.get_monitor().status() == "ok"

    def test_single_process_skew(self):
        mon = hm.HealthMonitor()
        rep = mon.observe_pod_skew(0.25)
        assert rep["step_time_skew"] == 0.0
        assert rep["slowest_host"] == 0.0
        assert rep["host_step_seconds"] == [0.25]


# ---------------------------------------------------------------------------
# engine wiring (compile vs step_compute; zero perturbation)
# ---------------------------------------------------------------------------
def _tiny_engine(seed=3):
    from paddle_tpu.distributed import fleet
    from paddle_tpu.distributed.engine import ParallelEngine
    from paddle_tpu.models import (GPTConfig, GPTForCausalLM,
                                   GPTPretrainingCriterion)

    paddle.seed(seed)
    cfg = GPTConfig(vocab_size=64, hidden_size=16, num_layers=1,
                    num_heads=2, max_position_embeddings=16)
    model = GPTForCausalLM(cfg)
    crit = GPTPretrainingCriterion(cfg)
    opt = paddle.optimizer.AdamW(learning_rate=1e-4,
                                 parameters=model.parameters())
    strategy = fleet.DistributedStrategy()
    strategy.hybrid_configs = {"dp_degree": 1, "mp_degree": 1}
    hcg = fleet.init(is_collective=True, strategy=strategy)
    eng = ParallelEngine(model, opt, hcg.mesh)
    step = eng.train_step(lambda m, b: crit(m(b["x"]), b["y"]))
    r = np.random.RandomState(0)
    ids = r.randint(0, 64, (2, 9))
    batch = {"x": paddle.to_tensor(ids[:, :-1]),
             "y": paddle.to_tensor(ids[:, 1:])}
    return eng, step, batch


class TestEngineGoodputWiring:
    def test_compile_then_step_compute_attribution(self, tmp_path):
        obs.reset_registry()
        led = gp.attach_dir(str(tmp_path))
        eng, step, batch = _tiny_engine()
        losses = [float(step(batch)) for _ in range(3)]
        s = led.summary()
        # first call traced+compiled under "compile"; the rest are
        # productive step_compute
        assert s["segments"]["compile"] > 0
        assert s["segments"]["step_compute"] > 0
        assert eng.stats.compiles == 1
        recs = gp.read_journal(_journal(tmp_path))
        comp = [r for r in recs if r["ev"] == "e"
                and r["seg"] == "compile"]
        steps = [r for r in recs if r["ev"] == "e"
                 and r["seg"] == "step_compute"]
        assert len(comp) == 1
        assert len(steps) == 2
        # the step index rides on the begin records
        assert [r.get("step") for r in recs
                if r["ev"] == "b" and r["seg"] == "compile"] == [1]
        # goodput gauges in the snapshot
        m = eng.metrics_snapshot()["metrics"]
        assert m["paddle_tpu_goodput_pct"]["series"][0]["value"] > 0
        segs = {s_["labels"]["segment"]: s_["value"] for s_ in
                m["paddle_tpu_goodput_segment_seconds"]["series"]}
        assert segs["compile"] > 0 and segs["step_compute"] > 0
        assert losses[0] != losses[1]         # it actually trained

    def test_instrumentation_changes_nothing(self, tmp_path):
        """Bit-identical losses and an identical compile count with
        the ledger attached vs detached — the same discipline the
        comm/mem ledgers are held to."""
        obs.reset_registry()
        gp.detach()
        eng_a, step_a, batch_a = _tiny_engine(seed=5)
        gold = [float(step_a(batch_a)) for _ in range(3)]
        assert eng_a.stats.compiles == 1

        obs.reset_registry()
        gp.attach_dir(str(tmp_path))
        eng_b, step_b, batch_b = _tiny_engine(seed=5)
        got = [float(step_b(batch_b)) for _ in range(3)]
        assert got == gold
        assert eng_b.stats.compiles == 1
        assert eng_b.stats.cache_hits == 2

    def test_health_gauges_fed_by_engine(self):
        obs.reset_registry()
        eng, step, batch = _tiny_engine(seed=7)
        for _ in range(3):
            float(step(batch))
        m = eng.metrics_snapshot()["metrics"]
        assert "paddle_tpu_health_loss_zscore" in m
        assert "paddle_tpu_health_degraded" in m
        assert m["paddle_tpu_health_degraded"]["series"][0]["value"] \
            == 0.0
        assert eng._health.event_count() == 0
        rep = eng.pod_step_skew()
        assert rep["step_time_skew"] == 0.0

    def test_per_engine_windows_never_mix_runs(self):
        """A fresh model's first loss is judged against ITS OWN empty
        window, never another engine's converged baseline — two
        back-to-back runs raise zero events even though run B's first
        loss towers over run A's last."""
        obs.reset_registry()
        eng_a, step_a, batch_a = _tiny_engine(seed=5)
        for _ in range(10):
            float(step_a(batch_a))
        eng_b, step_b, batch_b = _tiny_engine(seed=6)
        for _ in range(3):
            float(step_b(batch_b))
        assert eng_a._health.event_count() == 0
        assert eng_b._health.event_count() == 0
        assert eng_a._health is not eng_b._health

    def test_scaler_absorbed_overflow_not_an_anomaly(self):
        """An AMP-skipped step (found_inf) is protocol: its inf loss
        never reaches the detector, so no loss_nonfinite event and no
        degraded /healthz for a routine scale-calibration step."""
        from paddle_tpu.distributed import fleet
        from paddle_tpu.distributed.engine import ParallelEngine

        obs.reset_registry()
        paddle.seed(4)
        model = paddle.nn.Linear(8, 8)
        opt = paddle.optimizer.Adam(learning_rate=0.05,
                                    parameters=model.parameters())
        strategy = fleet.DistributedStrategy()
        strategy.hybrid_configs = {"dp_degree": 1, "mp_degree": 1}
        hcg = fleet.init(is_collective=True, strategy=strategy)
        eng = ParallelEngine(model, opt, hcg.mesh)
        scaler = paddle.amp.GradScaler(init_loss_scaling=2.0 ** 8,
                                       decr_every_n_nan_or_inf=1)
        step = eng.train_step(
            lambda m, b: paddle.mean((m(b["x"]) - b["y"]) ** 2),
            scaler=scaler)
        r = np.random.RandomState(0)
        x = r.randn(4, 8).astype("float32")
        y = r.randn(4, 8).astype("float32")
        float(step({"x": paddle.to_tensor(x), "y": paddle.to_tensor(y)}))
        bad = x.copy()
        bad[0, 0] = np.inf
        step({"x": paddle.to_tensor(bad), "y": paddle.to_tensor(y)})
        float(step({"x": paddle.to_tensor(x),
                    "y": paddle.to_tensor(y)}))
        eng.metrics_snapshot()                # flush the lagged fetch
        assert scaler.last_found_inf is False
        assert eng._health.event_count() == 0
        assert eng._health.status() == "ok"


# ---------------------------------------------------------------------------
# CompileStats across restore (satellite: no double-counted compiles)
# ---------------------------------------------------------------------------
class TestCompileStatsAcrossRestore:
    def test_restore_books_no_compile_and_no_recompile(self, tmp_path):
        obs.reset_registry()
        eng, step, batch = _tiny_engine(seed=11)
        for _ in range(2):
            float(step(batch))
        eng.save_checkpoint(str(tmp_path / "ck"), step=2)
        assert eng.stats.compiles == 1
        # sync the registry counters, then restore into the SAME
        # already-compiled engine and step again
        eng.metrics_snapshot()
        reg_compiles = eng._metrics["compiles"].value(
            site="train_engine")
        hits_before = eng.stats.cache_hits
        eng.restore_checkpoint(str(tmp_path / "ck"))
        float(step(batch))
        # engine counters: no compile, exactly one more cache hit
        assert eng.stats.compiles == 1
        assert eng.stats.cache_hits == hits_before + 1
        # registry counters: the compile counter did NOT move (restore
        # must not book warmup compiles as steady-state recompiles)
        eng.metrics_snapshot()
        assert eng._metrics["compiles"].value(site="train_engine") \
            == reg_compiles == 1.0

    def test_fresh_engine_warmup_after_restore_books_once(self,
                                                          tmp_path):
        obs.reset_registry()
        eng, step, batch = _tiny_engine(seed=11)
        for _ in range(2):
            float(step(batch))
        eng.save_checkpoint(str(tmp_path / "ck"), step=2)
        # "relaunched process": fresh registry + fresh engine, restore
        # BEFORE the first step — the warmup compile books exactly
        # once, as a compile, never as a recompile-after-warmup
        obs.reset_registry()
        eng2, step2, batch2 = _tiny_engine(seed=11)
        eng2.restore_checkpoint(str(tmp_path / "ck"))
        assert eng2.stats.compiles == 0       # restore alone: nothing
        float(step2(batch2))
        warm = eng2.stats.compiles
        float(step2(batch2))
        assert warm == 1
        assert eng2.stats.compiles == 1       # 0 recompiles after warmup
        eng2.metrics_snapshot()
        assert eng2._metrics["compiles"].value(site="train_engine") \
            == 1.0


# ---------------------------------------------------------------------------
# serving: shed decisions in the span ring / Chrome export
# ---------------------------------------------------------------------------
class TestServingShedTraces:
    @pytest.fixture(scope="class")
    def tiny_model(self):
        from paddle_tpu.distributed import fleet as _fleet
        from paddle_tpu.models.llama import LlamaForCausalLM, llama_tiny

        _fleet._fleet_state.update(initialized=False, hcg=None,
                                   strategy=None)
        paddle.seed(11)
        return LlamaForCausalLM(llama_tiny())

    def _engine(self, tiny_model, **kw):
        from paddle_tpu.inference import (Config, ServingEngine,
                                          create_predictor)

        pred = create_predictor(
            Config().set_model(tiny_model).enable_paged_kv(page_size=8))
        return ServingEngine(pred, max_batch=2, **kw)

    def test_shed_span_in_ring_and_chrome_export(self, tiny_model,
                                                 tmp_path):
        eng = self._engine(tiny_model, max_queue=1)
        V = tiny_model.config.vocab_size
        r = np.random.RandomState(0)
        rids = [eng.submit(r.randint(1, V, (4,)), max_new_tokens=2)
                for _ in range(3)]
        shed = [rid for rid in rids if rid in eng.finished
                and eng.finished[rid].shed]
        assert len(shed) == 2
        # the ring holds the shed traces with a zero-length shed span
        by_rid = {t["rid"]: t for t in eng.request_traces()}
        for rid in shed:
            spans = {s["name"]: s for s in by_rid[rid]["spans"]}
            assert spans["shed"]["seconds"] == 0.0
            assert spans["shed"]["meta"]["reason"] == "queue_full"
            assert spans["queued"]["t1"] is not None
        # Chrome export: shed requests appear as "i" instant events
        doc = eng.export_request_traces(str(tmp_path / "t.json"))
        sheds = [e for e in doc["traceEvents"]
                 if e.get("name") == "shed"]
        assert len(sheds) == 2
        assert all(e["ph"] == "i" and e["args"]["reason"] ==
                   "queue_full" for e in sheds)
        assert {e["tid"] for e in sheds} == set(shed)
        with open(tmp_path / "t.json") as f:
            assert json.load(f)["traceEvents"]

    def test_deadline_shed_span_reason(self, tiny_model):
        eng = self._engine(tiny_model, admission_deadline_s=0.0)
        V = tiny_model.config.vocab_size
        rid = eng.submit(np.random.RandomState(1).randint(1, V, (4,)),
                        max_new_tokens=2)
        time.sleep(0.01)
        eng._admit()                          # sheds before prefill
        tr = {t["rid"]: t for t in eng.request_traces()}[rid]
        spans = {s["name"]: s for s in tr["spans"]}
        assert spans["shed"]["meta"]["reason"] == "deadline"
        assert spans["shed"]["meta"]["queued_seconds"] > 0


# ---------------------------------------------------------------------------
# tools: run_report
# ---------------------------------------------------------------------------
def _import_tools():
    repo = Path(__file__).resolve().parents[1]
    sys.path.insert(0, str(repo))
    try:
        from tools import run_report as rr
    finally:
        sys.path.remove(str(repo))
    return rr


class TestRunReportTool:
    def test_journal_report_and_timeline(self, tmp_path, capsys):
        rr = _import_tools()
        led = gp.attach_dir(str(tmp_path))
        with gp.segment("step_compute"):
            time.sleep(0.02)
        gp.note_event("loss_spike", step=4, value=9.0)
        led.begin("ckpt_stall")
        gp.GoodputLedger(_journal(tmp_path))  # the "relaunch"
        rep = rr.journal_report(str(tmp_path))
        assert rep is not None
        assert rep["summary"]["restarts"] == 1
        whats = [e["what"] for e in rep["timeline"]]
        assert "start" in whats and "resume" in whats
        assert "loss_spike" in whats and "recovery_restart" in whats
        assert rr.main(["--run-dir", str(tmp_path)]) == 0
        out = capsys.readouterr().out
        assert "goodput waterfall" in out
        assert "step_compute" in out

    def test_nothing_found_exit_code(self, tmp_path):
        rr = _import_tools()
        assert rr.main(["--run-dir", str(tmp_path / "none")]) == 2


# ---------------------------------------------------------------------------
# tpulint: the new modules must stay clean with ZERO baseline entries
# ---------------------------------------------------------------------------
def test_tpulint_goodput_surface_zero_baseline():
    repo = Path(__file__).resolve().parents[1]
    sys.path.insert(0, str(repo))
    try:
        from tools.tpulint import ALL_RULES, lint_paths

        findings = lint_paths(
            [repo / "paddle_tpu" / "observability" / "goodput.py",
             repo / "paddle_tpu" / "observability" / "healthmon.py",
             repo / "tools" / "run_report.py"],
            ALL_RULES, root=repo)
    finally:
        sys.path.remove(str(repo))
    assert findings == [], [str(f) for f in findings]


# ---------------------------------------------------------------------------
# SIGKILL matrix (subprocess; the real preemption)
# ---------------------------------------------------------------------------
@pytest.mark.slow
class TestGoodputSigkillMatrix:
    REPO = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))
    WORKER = os.path.join(REPO, "tests", "workers",
                          "goodput_crash_worker.py")

    def _run(self, extra_env, vdevs=1, timeout=600):
        import subprocess

        env = dict(os.environ)
        for k in list(env):
            if k.startswith(("PADDLE_", "JAX_", "XLA_")):
                del env[k]
        env["XLA_FLAGS"] = \
            f"--xla_force_host_platform_device_count={vdevs}"
        env["JAX_PLATFORMS"] = "cpu"
        env["OMP_NUM_THREADS"] = "1"
        env.update({k: str(v) for k, v in extra_env.items()})
        p = subprocess.run(
            [sys.executable, self.WORKER], env=env, cwd=self.REPO,
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
            timeout=timeout)
        return p.returncode, p.stdout.decode(errors="replace")[-3000:]

    def _check_journal_and_result(self, base, out, min_restarts=1):
        recs = gp.read_journal(os.path.join(base, gp.JOURNAL_NAME))
        assert recs, "journal missing or unparseable"
        summ = gp.summarize(recs)
        assert summ["restarts"] >= min_restarts
        assert summ["segments"].get("recovery_restart", 0) > 0
        # the wall identity: foreground segments + idle == wall (±1%)
        fg = sum(summ["segments"].values())
        assert fg == pytest.approx(summ["wall_seconds"], rel=0.01,
                                   abs=1e-3)
        with open(out + ".json") as f:
            doc = json.load(f)
        assert doc["start"] > 0               # genuinely resumed
        # the worker's live summary agrees with the offline journal
        assert doc["goodput"]["restarts"] == summ["restarts"]
        return doc

    @pytest.mark.parametrize("site,n", [
        ("ckpt.write_shard", 2),              # mid ckpt_stall segment
        ("ckpt.commit", 2),                   # later in the same stall
        ("engine.step_dispatch", 6),          # between step segments
    ])
    def test_sigkill_leaves_parseable_journal_resume_closes(
            self, tmp_path, site, n):
        base = str(tmp_path / "ck")
        out = str(tmp_path / "p")
        rc, log = self._run({
            "CKPT_BASE": base, "TOTAL_STEPS": 8, "SAVE_EVERY": 2,
            "TEST_OUT": out + "1",
            "PADDLE_TPU_FAILPOINTS": f"{site}=kill@{n}"})
        assert rc == -9, (site, rc, log)
        # the killed run's journal parses and has a run header
        recs = gp.read_journal(os.path.join(base, gp.JOURNAL_NAME))
        assert recs and recs[-1].get("ev") in ("b", "e", "run", "h")
        rc, log = self._run({"CKPT_BASE": base, "TOTAL_STEPS": 8,
                             "SAVE_EVERY": 2, "TEST_OUT": out})
        assert rc == 0, (site, log)
        self._check_journal_and_result(base, out)

    def test_hybrid_crash_goodput_matches_straight_run(self, tmp_path):
        """The acceptance line: on the gpt13b smoke topology,
        5 + SIGKILL + resume + 5 yields ONE journal whose segment sum
        equals wall time and whose goodput_pct lands within 5pp of the
        uninterrupted 10-step run."""
        gold_base = str(tmp_path / "gold_ck")
        rc, log = self._run({
            "CKPT_BASE": gold_base, "TOTAL_STEPS": 10, "SAVE_EVERY": 2,
            "TEST_OUT": str(tmp_path / "gold"), "HYBRID": 1},
            vdevs=8, timeout=900)
        assert rc == 0, log
        with open(str(tmp_path / "gold") + ".json") as f:
            gold = json.load(f)

        base = str(tmp_path / "ck")
        rc, log = self._run({
            "CKPT_BASE": base, "TOTAL_STEPS": 10, "SAVE_EVERY": 2,
            "TEST_OUT": str(tmp_path / "p1"), "HYBRID": 1,
            "PADDLE_TPU_FAILPOINTS": "engine.step_dispatch=kill@6"},
            vdevs=8, timeout=900)
        assert rc == -9, (rc, log)
        rc, log = self._run({
            "CKPT_BASE": base, "TOTAL_STEPS": 10, "SAVE_EVERY": 2,
            "TEST_OUT": str(tmp_path / "p2"), "HYBRID": 1},
            vdevs=8, timeout=900)
        assert rc == 0, log
        doc = self._check_journal_and_result(base,
                                             str(tmp_path / "p2"))
        # loss curve continues the straight run (the PR-10 guarantee,
        # re-checked here because the journal rides the same commit)
        gold_losses = open(str(tmp_path / "gold") + ".log").read()
        resumed = open(str(tmp_path / "p2") + ".log").read()
        assert gold_losses.splitlines()[doc["start"]:] == \
            resumed.splitlines()
        # goodput within 5 percentage points of the straight run
        assert doc["goodput"]["goodput_pct"] == pytest.approx(
            gold["goodput"]["goodput_pct"], abs=5.0)
