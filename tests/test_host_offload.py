"""Host-memory offload tier (distributed/host_offload.py).

The contract under test, end to end:
- HostState round trips are BIT-exact (bytes copied, never re-derived)
  at the original sharding — which is why every parity assertion below
  is ``==``, not allclose.
- The engine knob (``sharding_configs["offload"]``) moves optimizer
  moments / AMP masters / quant-comm EF residuals (optionally stored
  param shards) to host between steps and prefetches them per-bucket
  just in time: loss trajectories offload-on vs offload-off are
  identical, with ZERO recompiles after warmup (the tier lives outside
  the compiled step).
- Every transfer is booked at the closed form (per-device addressable-
  shard bytes per slot) into the ``paddle_tpu_offload_*`` gauges, with
  conservation: cumulative d2h - h2d == bytes currently host-resident.
- memledger's measured accounting books the offloaded split under a
  ``host_state`` component that the analytic closed form matches
  byte-for-byte, and the auto_tuner prices the tier (cheaper HBM,
  dearer step time) so over-HBM configs surface only with offload.
- The serving engine reuses the tier for cold prefix-cache KV pages:
  LRU-evicted pages spill to host and fault back through the normal
  admission accounting on a prefix hit, outputs bit-exact.
"""
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.distributed import fleet
from paddle_tpu.distributed import host_offload as ho
from paddle_tpu.distributed.engine import ParallelEngine
from paddle_tpu.observability import memledger as ml


def _reset_fleet():
    fleet._fleet_state.update(initialized=False, hcg=None, strategy=None)


# ---------------------------------------------------------------------------
# HostState: the round-trip primitive
# ---------------------------------------------------------------------------
class TestHostState:
    @pytest.mark.parametrize("dtype", ["float32", "bfloat16", "int32"])
    def test_round_trip_bit_exact(self, dtype):
        import jax.numpy as jnp

        r = np.random.RandomState(0)
        arr = jnp.asarray(r.randn(6, 10).astype("float32")).astype(dtype)
        hs = ho.page_out(arr)
        assert ho.is_host(hs)
        assert hs.shape == (6, 10) and hs.dtype == np.dtype(arr.dtype)
        assert hs.nbytes == arr.nbytes
        back = ho.place(hs)
        assert back.dtype == arr.dtype
        np.testing.assert_array_equal(
            np.asarray(back, dtype=np.float32),
            np.asarray(arr, dtype=np.float32))
        assert back.sharding == arr.sharding

    def test_sharded_round_trip_preserves_layout(self):
        import jax
        from jax.sharding import NamedSharding, PartitionSpec as P

        mesh = jax.make_mesh((4, 2), ("x", "y"))
        arr = jax.device_put(
            np.arange(64, dtype=np.float32).reshape(8, 8),
            NamedSharding(mesh, P("x", "y")))
        hs = ho.page_out(arr)
        # memledger prices a HostState like the live array it replaces
        assert ml.shard_bytes(hs) == ml.shard_bytes(arr)
        back = ho.place(hs)
        assert back.sharding == arr.sharding
        np.testing.assert_array_equal(np.asarray(back), np.asarray(arr))

    def test_make_config_normalization(self):
        assert ho.make_config(None) is None
        assert ho.make_config({}) is None
        assert ho.make_config(
            {"optimizer": False, "params": False}) is None
        cfg = ho.make_config(True)
        assert cfg.optimizer and not cfg.params
        cfg = ho.make_config({"params": True, "optimizer": False,
                              "prefetch_buckets": 3})
        assert cfg.params and not cfg.optimizer
        assert cfg.prefetch_buckets == 3
        assert ho.make_config(cfg) is cfg


# ---------------------------------------------------------------------------
# engine integration: parity, residency, ledger, recompiles
# ---------------------------------------------------------------------------
def _mlp():
    class MLP(paddle.nn.Layer):
        def __init__(self):
            super().__init__()
            self.fc1 = paddle.nn.Linear(16, 32)
            self.fc2 = paddle.nn.Linear(32, 16)

        def forward(self, x):
            return self.fc2(paddle.nn.functional.relu(self.fc1(x)))

    return MLP()


def _loss_fn(model, batch):
    return paddle.mean((model(batch["x"]) - batch["y"]) ** 2)


def _flat_engine(offload, quant="none", amp=False, stage=3):
    """dp2 x sharding4 ZeRO engine; offload rides the strategy knob
    (sharding_configs["offload"]) exactly like the reference dict."""
    strategy = fleet.DistributedStrategy()
    sc = {"comm_overlap": True, "comm_buffer_size_MB": 0.0005,
          "sharding_stage": stage}
    if offload is not None:
        sc["offload"] = offload
    strategy.hybrid_configs = {
        "dp_degree": 2, "sharding_degree": 4,
        "sharding_configs": sc,
        "quant_comm": {"dtype": quant, "chunk": 32}}
    _reset_fleet()
    hcg = fleet.init(is_collective=True, strategy=strategy)
    paddle.seed(3)
    model = _mlp()
    opt = paddle.optimizer.Adam(learning_rate=0.1,
                                parameters=model.parameters())
    eng = ParallelEngine(model, opt, hcg.mesh)
    scaler = paddle.amp.GradScaler(init_loss_scaling=2.0 ** 10) \
        if amp else None
    step = eng.train_step(_loss_fn, scaler=scaler)
    r = np.random.RandomState(0)
    batch = {"x": paddle.to_tensor(r.randn(8, 16).astype("float32")),
             "y": paddle.to_tensor(r.randn(8, 16).astype("float32"))}
    return eng, step, batch


class TestEngineOffload:
    def test_loss_parity_and_residency(self):
        _, step0, b0 = _flat_engine(None)
        gold = [float(step0(b0)) for _ in range(4)]
        eng, step, b = _flat_engine({"optimizer": True,
                                     "prefetch_buckets": 1})
        got = [float(step(b)) for _ in range(4)]
        assert got == gold  # bit-exact: the tier only copies bytes

        # between steps every moment leaf lives on the host tier
        tier = eng._offload
        assert tier is not None
        hosted = sum(
            1 for p in eng.trainable
            for v in (eng.optimizer._states.get(id(p)) or {}).values()
            if ho.is_host(v))
        assert hosted > 0
        assert tier.host_resident_bytes("optimizer_state") > 0

    def test_transfer_ledger_closed_form_and_gauges(self):
        from paddle_tpu.observability import get_registry

        eng, step, b = _flat_engine({"optimizer": True})
        float(step(b))
        # steady-state window: each step is one h2d prefetch + one d2h
        # page-out of every offloaded slot at shard_bytes granularity
        slot_closed = sum(
            ho.host_shard_bytes(tier_get)
            for tier_get in (eng._offload._get(eng, key) for key, _c, _b
                             in eng._offload._iter_slots(eng)))
        t0 = eng._offload.transfer_bytes()
        steps = 3
        for _ in range(steps):
            float(step(b))
        tier = eng._offload
        assert tier.transfer_bytes() - t0 == 2 * steps * slot_closed
        # conservation: everything sent down minus everything brought
        # back is exactly what the host currently holds
        resident = tier.host_resident_bytes()
        assert (tier.transfer_bytes(direction="d2h")
                - tier.transfer_bytes(direction="h2d")) == resident
        assert resident == slot_closed
        # the gauges carry the same cumulative closed forms
        snap = get_registry().snapshot()["metrics"]
        series = snap["paddle_tpu_offload_transfer_bytes"]["series"]
        vals = {(dict(s["labels"])["component"],
                 dict(s["labels"])["direction"]): s["value"]
                for s in series}
        for (c, d), v in tier._bytes.items():
            assert vals[(c, d)] == float(v)
        host = snap["paddle_tpu_offload_host_bytes"]["series"]
        assert sum(s["value"] for s in host
                   if dict(s["labels"])["component"]
                   != "kv_page") == float(resident)

    def test_zero_recompiles_after_warmup(self):
        eng, step, b = _flat_engine({"optimizer": True,
                                     "prefetch_buckets": 2})
        float(step(b))
        n = eng.stats.compiles
        for _ in range(3):
            float(step(b))
        assert eng.stats.compiles == n

    def test_amp_quant_params_offload_parity(self):
        """The full state surface at once: AMP scaler + int8 EF
        residuals + stored param shards all host-resident between
        steps — trajectory still bit-exact, eval still served."""
        _, step0, b0 = _flat_engine(None, quant="int8", amp=True)
        gold = [float(step0(b0)) for _ in range(5)]
        eng, step, b = _flat_engine(
            {"optimizer": True, "params": True, "prefetch_buckets": 2},
            quant="int8", amp=True)
        got = [float(step(b)) for _ in range(5)]
        assert got == gold
        tier = eng._offload
        assert tier.host_resident_bytes("quant_residual") > 0
        assert tier.host_resident_bytes("params") > 0
        # eval with params offloaded: restore_params pages them in
        ev = eng.eval_step(lambda mdl, bt: mdl(bt["x"]))
        v1 = np.asarray(ev(b))
        v2 = np.asarray(ev(b))
        np.testing.assert_array_equal(v1, v2)
        # and training resumes cleanly after the eval window
        float(step(b))

    def test_memledger_host_state_cross_check(self):
        eng, step, b = _flat_engine({"optimizer": True, "params": True},
                                    quant="int8", amp=True)
        for _ in range(2):
            float(step(b))
        acct = ml.account_engine(eng)
        closed = ml.closed_form_state_bytes(eng)
        assert "host_state" in acct.components
        for k, v in closed.items():
            assert acct.components.get(k) == v, (k, acct.components, closed)
        # host_state is exactly what the tier reports resident, and
        # device_bytes excludes it
        assert acct.components["host_state"] == \
            eng._offload.host_resident_bytes()
        assert acct.device_bytes == \
            acct.measured_bytes - acct.components["host_state"]

    def test_checkpoint_round_trip_under_offload(self, tmp_path):
        eng, step, b = _flat_engine({"optimizer": True,
                                     "prefetch_buckets": 1})
        for _ in range(2):
            float(step(b))
        ck = str(tmp_path / "ck")
        eng.save_checkpoint(ck)
        la = [float(step(b)) for _ in range(2)]
        eng.restore_checkpoint(ck)
        lb = [float(step(b)) for _ in range(2)]
        assert la == lb  # restore rebuilt the host tier bit-exactly
        # state is back on the host tier after the restore window
        assert eng._offload.host_resident_bytes() > 0


# ---------------------------------------------------------------------------
# the gpt13b smoke topology: mp2 x pp2 x sharding2, vpp2, AMP + int8
# ---------------------------------------------------------------------------
def _build_gpt_hybrid(offload):
    from paddle_tpu.models import GPTForCausalLMPipe
    from paddle_tpu.models.gpt import GPTConfig

    _reset_fleet()
    paddle.seed(0)
    strategy = fleet.DistributedStrategy()
    sc = {"comm_overlap": True, "comm_buffer_size_MB": 0.001,
          "sharding_stage": 3}
    if offload is not None:
        sc["offload"] = offload
    strategy.hybrid_configs = {
        "dp_degree": 1, "mp_degree": 2, "pp_degree": 2,
        "sharding_degree": 2,
        "pp_configs": {"num_virtual_pipeline_stages": 2},
        "sharding_configs": sc,
        "quant_comm": {"dtype": "int8", "chunk": 64,
                       "error_feedback": True}}
    strategy.sharding_configs = {"stage": 3}
    strategy.pipeline_configs = {"accumulate_steps": 2,
                                 "micro_batch_size": 2}
    fleet.init(is_collective=True, strategy=strategy)
    cfg = GPTConfig(vocab_size=128, hidden_size=32, num_layers=4,
                    num_heads=4, max_position_embeddings=32)
    model = GPTForCausalLMPipe(cfg)
    dm = fleet.distributed_model(model)
    opt = fleet.distributed_optimizer(paddle.optimizer.AdamW(
        learning_rate=1e-3, parameters=model.parameters()))
    scaler = paddle.amp.GradScaler(init_loss_scaling=2.0 ** 10)
    r = np.random.RandomState(0)
    ids = r.randint(0, 128, (8, 17))
    batch = [paddle.to_tensor(ids[:, :-1]), paddle.to_tensor(ids[:, 1:])]
    return dm, opt, scaler, batch


@pytest.fixture(scope="module")
def gpt_hybrid_runs():
    """Three steps of the topology with the tier off, then on: (losses,
    wrapped model, optimizer, scaler, batch) of each."""
    out = {}
    for name, offload in (("off", None),
                          ("on", {"optimizer": True, "prefetch_buckets": 2})):
        dm, opt, sc, b = _build_gpt_hybrid(offload)
        losses = [float(dm.train_batch(b, opt, scaler=sc))
                  for _ in range(3)]
        out[name] = (losses, dm, opt, sc, b)
    return out


class TestGpt13bSmokeParity:
    def test_hybrid_offload_bit_exact_and_recompile_free(
            self, gpt_hybrid_runs):
        gold = gpt_hybrid_runs["off"][0]
        got, dm, opt, sc, b = gpt_hybrid_runs["on"]
        assert got == gold  # bit-exact across mp x pp x sharding + vpp
        eng = dm._engine
        n = eng.stats.compiles
        float(dm.train_batch(b, opt, scaler=sc))
        assert eng.stats.compiles == n
        tier = eng._offload
        assert tier.host_resident_bytes("optimizer_state") > 0
        assert tier.host_resident_bytes("quant_residual") > 0
        # ledger == closed form on the hybrid mesh too
        slot_closed = sum(
            ho.host_shard_bytes(tier._get(eng, key))
            for key, _c, _b in tier._iter_slots(eng))
        assert tier.host_resident_bytes() == slot_closed
        assert (tier.transfer_bytes(direction="d2h")
                - tier.transfer_bytes(direction="h2d")) == slot_closed

    def test_hybrid_offload_memory_closed_form(self, gpt_hybrid_runs):
        """Between steps the measured accounting books the offloaded
        slots under ``host_state`` == the closed form, byte for byte, and
        the device-resident image is the tier-off run's less exactly
        that."""
        off = ml.account_engine(gpt_hybrid_runs["off"][1]._engine)
        eng = gpt_hybrid_runs["on"][1]._engine
        on = ml.account_engine(eng)
        for k, v in ml.closed_form_state_bytes(eng).items():
            assert on.components.get(k) == v, (k, on.components)
        host = on.components["host_state"]
        assert host == eng._offload.host_resident_bytes() > 0
        assert on.device_bytes == off.device_bytes - host


# ---------------------------------------------------------------------------
# auto_tuner: the tier is priced, gated, and surfaces when needed
# ---------------------------------------------------------------------------
class TestTunerPricing:
    MODEL = {"hidden_size": 5120, "num_layers": 40,
             "vocab_size": 50304, "num_heads": 40}

    def test_memory_and_time_ordering(self):
        from paddle_tpu.distributed.auto_tuner.cost_model import (
            estimate_memory_gb, estimate_step_time)

        cfg = {"dp_degree": 1, "mp_degree": 4, "pp_degree": 2,
               "sharding_degree": 1, "sharding_stage": 3,
               "micro_batch_size": 1}
        off = dict(cfg, offload={"optimizer": True,
                                 "prefetch_buckets": 2})
        m_s3 = estimate_memory_gb(self.MODEL, cfg, 8, 1024,
                                  recompute=True)
        m_off = estimate_memory_gb(self.MODEL, off, 8, 1024,
                                   recompute=True)
        t_s3 = estimate_step_time(self.MODEL, cfg, 8, 1024)
        t_off = estimate_step_time(self.MODEL, off, 8, 1024)
        # cheaper HBM, dearer step time — never a free lunch
        assert m_off < m_s3
        assert t_off > t_s3
        # prefetch overlap halves the DMA tax vs the blocking tier
        t_block = estimate_step_time(
            self.MODEL, dict(cfg, offload={"optimizer": True,
                                           "prefetch_buckets": 0}),
            8, 1024)
        assert t_s3 < t_off < t_block

    def test_candidates_gated_on_knob(self):
        from paddle_tpu.distributed.auto_tuner.tuner import (
            default_candidates)

        base = default_candidates(8, self.MODEL, 16)
        assert not any("offload" in c for c in base)
        cands = default_candidates(8, self.MODEL, 16, tune_offload=True)
        offs = [c for c in cands if "offload" in c]
        assert offs
        # offload rides stage 3, never replaces it
        assert all(c.get("sharding_stage") == 3
                   and c["sharding_degree"] > 1 for c in offs)

    def test_over_hbm_trainable_only_with_offload(self):
        from paddle_tpu.distributed.auto_tuner.tuner import AutoTuner

        # the flagship 8-chip slice: sharding_degree 1 leaves no axis
        # to shave the fp32 optimizer image — over a 16 GB chip without
        # the host tier, comfortably under it with the tier on
        cfg = {"dp_degree": 1, "mp_degree": 4, "pp_degree": 2,
               "sharding_degree": 1, "sharding_stage": 3,
               "micro_batch_size": 1}
        off = dict(cfg, offload={"optimizer": True,
                                 "prefetch_buckets": 2})
        kw = dict(num_devices=8, global_batch=8, seq_len=1024,
                  hbm_gb=16.0, recompute=True)
        bare = AutoTuner(self.MODEL, candidates=[dict(cfg)], **kw)
        assert bare.pruned() == []
        with pytest.raises(RuntimeError, match="no config fits"):
            bare.best_by_model()
        tuned = AutoTuner(self.MODEL, candidates=[dict(cfg), off], **kw)
        best = tuned.best_by_model()
        assert best.get("offload", {}).get("optimizer") is True
        assert best["sharding_stage"] == 3
        assert best["_pred_mem_gb"] <= 16.0


# ---------------------------------------------------------------------------
# serving: cold KV pages spill to host, fault back on a prefix hit
# ---------------------------------------------------------------------------
class TestServingSpill:
    PAGE = 8

    @pytest.fixture(scope="class")
    def tiny_model(self):
        from paddle_tpu.models.llama import LlamaForCausalLM, llama_tiny

        _reset_fleet()
        paddle.seed(11)
        return LlamaForCausalLM(llama_tiny())

    def _engine(self, model, **kw):
        from paddle_tpu.inference import (Config, ServingEngine,
                                          create_predictor)

        pred = create_predictor(
            Config().set_model(model).enable_paged_kv(
                page_size=self.PAGE))
        return ServingEngine(pred, max_batch=2, pool_pages=8,
                             prefill_chunk=16, prefix_cache=True,
                             debug_invariants=True, **kw)

    def _solo(self, model, prompt, n):
        from paddle_tpu.inference import Config, create_predictor

        pred = create_predictor(
            Config().set_model(model).enable_paged_kv(
                page_size=self.PAGE))
        return np.asarray(pred.generate(
            paddle.to_tensor(prompt[None]), max_new_tokens=n)._value)[0]

    def test_spill_fault_parity_and_ledger(self, tiny_model):
        eng = self._engine(tiny_model, host_spill_pages=8)
        prompts = [np.random.RandomState(20 + i).randint(
            1, 256, (3 * self.PAGE,)) for i in range(4)]
        done = {}
        for p in prompts:     # 4 x 3 pages through an 8-page pool
            eng.submit(p, max_new_tokens=4)
            done.update(eng.run())
        sp = eng.spill_stats()
        assert sp["spilled"] >= 1      # LRU evictions went to host
        assert sp["host_pages"] >= 1
        # payload closed form: page rows across every pool and layer
        k0 = eng.pools[0][0]
        item = np.dtype(k0.dtype).itemsize
        page_bytes = (2 * len(eng.pools) * k0.shape[1] * self.PAGE
                      * k0.shape[3] * item)
        assert sp["transfer_bytes"]["d2h"] == page_bytes * sp["spilled"]

        # resubmit the first prompt: its spilled pages fault back and
        # serve as ordinary prefix hits
        hits0 = eng.prefix_cache_stats()["hits"]
        eng.submit(prompts[0], max_new_tokens=4)
        done2 = eng.run()
        sp2 = eng.spill_stats()
        assert sp2["faulted"] >= 1
        assert sp2["transfer_bytes"]["h2d"] == \
            page_bytes * sp2["faulted"]
        assert eng.prefix_cache_stats()["hits"] > hits0

        # every output (through spill, fault, reuse) bit-matches a
        # fresh single-request predictor
        for rid, p in zip(sorted(done), prompts):
            np.testing.assert_array_equal(
                done[rid].output_ids, self._solo(tiny_model, p, 4))
        rid2 = sorted(done2)[-1]
        np.testing.assert_array_equal(
            done2[rid2].output_ids, self._solo(tiny_model, prompts[0], 4))
        eng.check_invariants()

    def test_spill_capacity_trims_oldest(self, tiny_model):
        eng = self._engine(tiny_model, host_spill_pages=2)
        for i in range(4):
            p = np.random.RandomState(40 + i).randint(
                1, 256, (3 * self.PAGE,))
            eng.submit(p, max_new_tokens=2)
            eng.run()
        sp = eng.spill_stats()
        assert sp["host_pages"] <= 2   # cap enforced
        assert sp["dropped"] >= 1      # overflow counted, not hoarded
        eng.check_invariants()

    def test_spill_requires_prefix_cache(self, tiny_model):
        from paddle_tpu.core.enforce import EnforceNotMet
        from paddle_tpu.inference import Config, ServingEngine, \
            create_predictor

        pred = create_predictor(
            Config().set_model(tiny_model).enable_paged_kv(
                page_size=self.PAGE))
        with pytest.raises(EnforceNotMet, match="prefix"):
            ServingEngine(pred, pool_pages=8, host_spill_pages=4)


# ---------------------------------------------------------------------------
# tpulint: the new host-tier paths stay clean, zero baseline
# ---------------------------------------------------------------------------
def test_tpulint_offload_surface_zero_baseline():
    import sys
    from pathlib import Path

    repo = Path(__file__).resolve().parents[1]
    sys.path.insert(0, str(repo))
    try:
        from tools.tpulint import ALL_RULES, lint_paths

        findings = lint_paths(
            [repo / "paddle_tpu" / "distributed" / "host_offload.py",
             repo / "paddle_tpu" / "inference" / "serving.py",
             repo / "paddle_tpu" / "inference" / "kv_cache.py"],
            ALL_RULES, root=repo)
    finally:
        sys.path.remove(str(repo))
    assert findings == [], [str(f) for f in findings]
