"""ZeRO stage-3 parameter sharding with the T3-style bucketed
just-in-time gather (distributed/grad_buckets.py BucketPlan.gather +
the engine integration).

Under test:
- the strategy knob surface: sharding_configs["sharding_stage"] = 3
  stores every plan entry's param shard-only (engine._ZeroPlan
  store_sharded) with no group_sharded_parallel call needed
- stage-3 vs stage-2 loss/param BIT-parity on the 8-vdev mesh: flat
  ZeRO MLP (dp2 x sharding4) and the gpt13b smoke topology
  (mp2 x pp2 x sharding2, vpp2), incl. AMP GradScaler and quant_comm
  int8 on — the gather is pure data movement, so the trajectories
  must coincide exactly
- per-device model-state bytes at EXACTLY 1/sharding_degree: measured
  accounting == closed form byte-for-byte (memledger)
- comm-ledger gather exactness: all_gather bytes on the sharding axis
  == (p-1) x stored shard bytes closed form; the seam gather rides
  the lax.scan with trips=nb (scan_trips); bucketed vs per-param
  gather (stage3_release_after_forward) moves identical bytes through
  a different node count
- zero steady-state recompiles on every stage-3 program
- checkpoint: stage-3 shard-only save + bit-exact resume, reshard
  across stage 2<->3 and across sharding degrees, and the flagship
  5+crash+5 == 10-straight gate on the gpt13b smoke topology
- auto_tuner: sharding_stage=3 in the search space, priced by the
  memory/cost models
- tpulint: grad_buckets + the stage-3 engine paths at zero baseline
  entries
"""
import numpy as np
import pytest

import paddle_tpu as paddle
import paddle_tpu.distributed as dist
from paddle_tpu.distributed import fleet
from paddle_tpu.distributed import grad_buckets as gb
from paddle_tpu.distributed.engine import ParallelEngine
from paddle_tpu.observability import memledger as ml


def _reset_fleet():
    fleet._fleet_state.update(initialized=False, hcg=None, strategy=None)


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


def _flat_engine(stage, overlap=True, release=True, quant="none",
                 amp=False, level="os_g", dp=2, sh=4, steps=3):
    """dp x sharding ZeRO MLP engine with the stage knob on the
    strategy (the reference hybrid_configs plumbing)."""
    strategy = fleet.DistributedStrategy()
    strategy.hybrid_configs = {
        "dp_degree": dp, "sharding_degree": sh,
        "sharding_configs": {"comm_overlap": overlap,
                             "comm_buffer_size_MB": 0.0005,
                             "sharding_stage": stage,
                             "stage3_release_after_forward": release},
        "quant_comm": {"dtype": quant, "chunk": 32}}
    _reset_fleet()
    hcg = fleet.init(is_collective=True, strategy=strategy)
    paddle.seed(3)
    model = _mlp()
    opt = paddle.optimizer.Adam(learning_rate=0.1,
                                parameters=model.parameters())
    if level:
        model, opt, _ = dist.group_sharded_parallel(model, opt, level)
    eng = ParallelEngine(model, opt, hcg.mesh)
    scaler = paddle.amp.GradScaler(init_loss_scaling=2.0 ** 10) \
        if amp else None
    step = eng.train_step(_loss_fn, scaler=scaler)
    np.random.seed(0)
    x = np.random.randn(8, 16).astype("float32")
    y = np.random.randn(8, 16).astype("float32")
    batch = {"x": paddle.to_tensor(x), "y": paddle.to_tensor(y)}
    losses = [float(step(batch)) for _ in range(steps)]
    eng._flush_pending_scalars()
    return eng, model, losses, batch, step


def _assert_same_trajectory(la, lb):
    """Two layouts of the same math are two XLA programs: step 0 (same
    params, same forward) is bit-equal, and from step 1 on the fused
    elementwise update may round differently by an f32 ulp (measured
    under jax 0.9: 6e-8 at loss 0.86, params within 2e-7, not growing).
    rtol 1e-6 is ~8 ulps — a real divergence is orders above it."""
    assert la[0] == lb[0]
    np.testing.assert_allclose(la, lb, rtol=1e-6, atol=0)


def _covered_shard_bytes(eng):
    return sum(ml.shard_bytes(p._value) for p in eng.trainable
               if eng._zero.entry(p) is not None
               and eng._zero.entry(p)[1])


# ---------------------------------------------------------------------------
# the strategy knob surface
# ---------------------------------------------------------------------------
def test_strategy_defaults_carry_stage_knobs():
    s = fleet.DistributedStrategy()
    sc = s.hybrid_configs["sharding_configs"]
    assert sc["sharding_stage"] == 2
    assert sc["stage3_release_after_forward"] is True
    s.hybrid_configs = {"sharding_configs": {"sharding_stage": 3}}
    sc = s.hybrid_configs["sharding_configs"]
    assert sc["sharding_stage"] == 3
    assert sc["stage3_release_after_forward"] is True
    assert gb.stage_config(s) == (3, True)


def test_knob_flips_storage_without_group_sharded_call():
    """sharding_stage=3 alone (no group_sharded_parallel) stores every
    plan entry's param scattered over 'sharding'."""
    eng, _, _, _, _ = _flat_engine(3, level=None)
    assert eng._sharding_stage == 3
    entries = [eng._zero.entry(p) for p in eng.trainable]
    assert entries and all(e is not None and e[1] for e in entries)
    for p in eng.trainable:
        assert "sharding" in str(eng._zero.storage_spec(p))


# ---------------------------------------------------------------------------
# flat parity: stage-3 == stage-2, bit-on
# ---------------------------------------------------------------------------
class TestFlatParity:
    def test_stage3_bit_parity_and_compile_stability(self):
        eng2, m2, l2, _, _ = _flat_engine(2)
        eng3, m3, l3, batch, step = _flat_engine(3)
        # the gather is exact data movement: the loss trajectory
        # coincides (same values through the same grad path)
        _assert_same_trajectory(l3, l2)
        # params: stage 2 and stage 3 are different XLA programs, so
        # elementwise-update fusion may differ by an ulp — the repo's
        # parity gate (<= 1e-5) applies
        for p2, p3 in zip(m2.parameters(), m3.parameters()):
            np.testing.assert_allclose(np.asarray(p3._value),
                                       np.asarray(p2._value),
                                       rtol=0, atol=1e-5)
        assert eng3.stats.compiles == 1
        float(step(batch))
        assert eng3.stats.compiles == 1

    def test_amp_scaler_parity(self):
        _, _, l2, _, _ = _flat_engine(2, amp=True)
        eng3, _, l3, _, _ = _flat_engine(3, amp=True)
        _assert_same_trajectory(l3, l2)
        assert eng3.stats.compiles == 1

    def test_p_g_os_level_uses_bucketed_gather(self):
        """group_sharded_parallel "p_g_os" rides the same bucketed
        gather when the comm_overlap plan exists."""
        eng, _, losses, _, _ = _flat_engine(2, level="p_g_os")
        assert all(np.isfinite(losses))
        led = eng.comm_ledger()
        plan = eng._bucket_plan
        rs_buckets = sum(len(g.buckets) for g in plan.groups
                        if g.kind == "rs")
        assert led.ops_for(axis="sharding", op="all_gather") == rs_buckets

    def test_memory_at_one_over_sharding_degree(self):
        eng2, _, _, _, _ = _flat_engine(2)
        eng3, _, _, _, _ = _flat_engine(3)
        a2 = ml.account_engine(eng2)
        a3 = ml.account_engine(eng3)
        c3 = ml.closed_form_state_bytes(eng3)
        # measured == closed form byte-for-byte (shard_shape path vs
        # global-shape/degree path)
        for k, v in c3.items():
            assert a3.components.get(k) == v, k
        # every MLP param is plan-covered: the whole params component
        # sits at exactly 1/sharding_degree of the stage-2 image
        assert a3.components["params"] * 4 == a2.components["params"]
        # optimizer state was already stage-2 scattered — unchanged
        assert a3.components["optimizer_state"] == \
            a2.components["optimizer_state"]


# ---------------------------------------------------------------------------
# ledger exactness: gather bytes + the release knob's node granularity
# ---------------------------------------------------------------------------
class TestGatherLedger:
    def test_gather_bytes_closed_form_and_bucketed_ops(self):
        eng, _, _, _, _ = _flat_engine(3)
        led = eng.comm_ledger()
        closed = (4 - 1) * _covered_shard_bytes(eng)
        assert led.bytes_for(axis="sharding", op="all_gather") == closed
        # bucketed: one coalesced gather per rs bucket, not per param
        plan = eng._bucket_plan
        rs_buckets = sum(len(g.buckets) for g in plan.groups
                        if g.kind == "rs")
        n_covered = sum(1 for p in eng.trainable
                        if eng._zero.entry(p) is not None
                        and eng._zero.entry(p)[1])
        assert led.ops_for(axis="sharding", op="all_gather") \
            == rs_buckets < n_covered

    def test_release_knob_off_gathers_per_param_same_bytes(self):
        eng_on, _, l_on, _, _ = _flat_engine(3, release=True)
        eng_off, _, l_off, _, _ = _flat_engine(3, release=False)
        # identical data movement -> identical trajectory
        _assert_same_trajectory(l_on, l_off)
        led_on, led_off = eng_on.comm_ledger(), eng_off.comm_ledger()
        assert led_on.bytes_for(axis="sharding", op="all_gather") == \
            led_off.bytes_for(axis="sharding", op="all_gather")
        n_covered = sum(1 for p in eng_off.trainable
                        if eng_off._zero.entry(p) is not None
                        and eng_off._zero.entry(p)[1])
        assert led_off.ops_for(axis="sharding", op="all_gather") \
            == n_covered
        assert led_on.ops_for(axis="sharding", op="all_gather") \
            < n_covered

    def test_no_overlap_plan_falls_back_per_param(self):
        eng, _, losses, _, _ = _flat_engine(3, overlap=False)
        assert eng._bucket_plan is None
        assert all(np.isfinite(losses))
        led = eng.comm_ledger()
        closed = (4 - 1) * _covered_shard_bytes(eng)
        assert led.bytes_for(axis="sharding", op="all_gather") == closed


# ---------------------------------------------------------------------------
# quant_comm composition: int8 wire + own-shard splice at bucket grain
# ---------------------------------------------------------------------------
class TestQuantComposition:
    def test_stage3_equals_stage2_under_quant(self):
        """With quant_comm's param_gather on, stage 2 already stores
        shards (PR-14 store_sharded) — stage 3 is the SAME program, so
        the trajectories must be identical floats."""
        eng2, _, l2, _, _ = _flat_engine(2, quant="int8")
        eng3, _, l3, _, _ = _flat_engine(3, quant="int8")
        assert l3 == l2
        assert eng3.stats.compiles == 1

    def test_quant_tracks_fp32_and_residuals_exist(self):
        _, _, l_fp, _, _ = _flat_engine(3)
        eng_q, _, l_q, _, _ = _flat_engine(3, quant="int8", steps=6)
        gap = max(abs(a - b) for a, b in zip(l_fp, l_q))
        # int8 wire vs f32 over three steps of lr 0.1: the gap is set by
        # where a few values fall on the int8 grid, so an ulp upstream
        # moves it by percents (5.06e-3 under jax 0.9, 4.9e-3 before);
        # the gate is "tracks", an order below the loss scale of 0.46
        assert gap < 1e-2
        assert eng_q._quant_residuals
        led = eng_q.comm_ledger()
        # the bucketed quantized gather stamps its compression ratio
        ag = [r for r in led.records
              if r.axis == "sharding" and r.op == "all_gather"]
        assert ag and all(r.payload_ratio < 1.0 for r in ag)


# ---------------------------------------------------------------------------
# the gpt13b smoke topology: mp2 x pp2 x sharding2, vpp2 (seam scan)
# ---------------------------------------------------------------------------
def _gpt_pipe(stage, quant="none", amp=False, vpp=2, lr=1e-3, steps=3):
    from paddle_tpu.models import GPTForCausalLMPipe
    from paddle_tpu.models.gpt import GPTConfig

    cfg = GPTConfig(vocab_size=128, hidden_size=32, num_layers=4,
                    num_heads=4, max_position_embeddings=32)
    strategy = fleet.DistributedStrategy()
    strategy.hybrid_configs = {
        "dp_degree": 1, "mp_degree": 2, "pp_degree": 2,
        "sharding_degree": 2,
        "mp_configs": {"mp_async_allreduce": True},
        "pp_configs": {"num_virtual_pipeline_stages": vpp},
        "sharding_configs": {"comm_overlap": True,
                             "comm_buffer_size_MB": 0.001,
                             "sharding_stage": stage},
        "quant_comm": {"dtype": quant, "chunk": 64}}
    strategy.sharding_configs = {"stage": stage}
    strategy.pipeline_configs = {"accumulate_steps": 2,
                                 "micro_batch_size": 2}
    _reset_fleet()
    fleet.init(is_collective=True, strategy=strategy)
    paddle.seed(0)
    model = GPTForCausalLMPipe(cfg)
    dm = fleet.distributed_model(model)
    opt = fleet.distributed_optimizer(
        paddle.optimizer.AdamW(learning_rate=lr,
                               parameters=model.parameters()))
    scaler = paddle.amp.GradScaler(init_loss_scaling=2.0 ** 10) \
        if amp else None
    r = np.random.RandomState(0)
    ids = r.randint(0, cfg.vocab_size, (8, 17))
    x = paddle.to_tensor(ids[:, :-1])
    y = paddle.to_tensor(ids[:, 1:])
    losses = [float(dm.train_batch([x, y], opt, scaler=scaler))
              for _ in range(steps)]
    return dm, model, opt, (x, y), losses


class TestGptSeamParity:
    def test_stage3_bit_parity_vpp2(self):
        _, m2, _, _, l2 = _gpt_pipe(2)
        dm3, m3, _, _, l3 = _gpt_pipe(3)
        assert l3 == l2
        for p2, p3 in zip(m2.parameters(), m3.parameters()):
            np.testing.assert_array_equal(np.asarray(p3._value),
                                          np.asarray(p2._value))
        eng = dm3._engine
        assert eng.stats.compiles == 1
        # the stacked decoder chunks gather through the seam scan:
        # trips=nb all_gather records on the sharding axis
        led = eng.comm_ledger()
        ag = [r for r in led.records
              if r.axis == "sharding" and r.op == "all_gather"]
        assert any(r.trips > 1 for r in ag)
        closed = (2 - 1) * _covered_shard_bytes(eng)
        assert led.bytes_for(axis="sharding", op="all_gather") == closed

    def test_stage3_memory_closed_form_gpt(self):
        dm2, _, _, _, _ = _gpt_pipe(2)
        dm3, _, _, _, _ = _gpt_pipe(3)
        e2, e3 = dm2._engine, dm3._engine
        a2 = ml.account_engine(e2, batch_tokens=8 * 16,
                               accumulate_steps=2)
        a3 = ml.account_engine(e3, batch_tokens=8 * 16,
                               accumulate_steps=2)
        c3 = ml.closed_form_state_bytes(e3)
        for k, v in c3.items():
            assert a3.components.get(k) == v, k
        # stage 2 stores the same plan entries REPLICATED over
        # 'sharding' — the stage-3 storage shrinks exactly those by
        # the sharding degree and leaves non-plan params untouched
        planned2 = sum(ml.shard_bytes(p._value) for p in e2.trainable
                       if e2._zero.entry(p) is not None)
        uncovered3 = a3.components["params"] - _covered_shard_bytes(e3)
        uncovered2 = a2.components["params"] - planned2
        assert uncovered3 == uncovered2
        assert _covered_shard_bytes(e3) * 2 == planned2

    @pytest.mark.slow
    def test_stage3_amp_and_quant_parity(self):
        _, _, _, _, l2a = _gpt_pipe(2, amp=True)
        _, _, _, _, l3a = _gpt_pipe(3, amp=True)
        assert l3a == l2a
        _, _, _, _, l2q = _gpt_pipe(2, quant="int8")
        dm3q, _, _, _, l3q = _gpt_pipe(3, quant="int8")
        assert l3q == l2q
        assert dm3q._engine.stats.compiles == 1


# ---------------------------------------------------------------------------
# checkpoint: shard-only save, reshard-on-load, crash+resume
# ---------------------------------------------------------------------------
class TestCheckpoint:
    def test_stage3_save_resume_bit_exact(self, tmp_path):
        _, _, straight, _, _ = _flat_engine(3, steps=6)
        eng1, _, first, batch, step = _flat_engine(3, steps=3)
        assert first == straight[:3]
        path = str(tmp_path / "ck")
        eng1.save_checkpoint(path)
        eng2, _, _, batch2, step2 = _flat_engine(3, steps=1)
        eng2.restore_checkpoint(path)
        rest = [float(step2(batch2)) for _ in range(3)]
        assert rest == straight[3:]

    def test_stage3_save_is_shard_only(self, tmp_path):
        """Every saved model-param shard is 1/sharding_degree of the
        global shape along its scatter dim — nobody writes (or holds)
        a full stage-3 parameter image."""
        import glob
        import json
        import os

        eng, model, _, _, _ = _flat_engine(3)
        path = str(tmp_path / "ck")
        eng.save_checkpoint(path)
        meta_file = glob.glob(os.path.join(path, "*.metadata"))[0]
        with open(meta_file) as f:
            md = json.load(f)
        dims = {id(p): eng._zero.entry(p)[0] for p in eng.trainable}
        names = {id(p): n for n, p in model.named_parameters()}
        for p in eng.trainable:
            key = f"model.{names[id(p)]}"
            gshape = md["global_shape"][key]
            d = dims[id(p)]
            for m in md["state_dict_metadata"][key]:
                assert m["local_shape"][d] == gshape[d] // 4

    def test_reshard_stage3_to_stage2_and_back(self, tmp_path):
        eng3, m3, _, _, _ = _flat_engine(3)
        p3 = str(tmp_path / "ck3")
        eng3.save_checkpoint(p3)
        # stage-3 shards load into a stage-2 (replicated-storage)
        # engine: the loader reassembles windows per target sharding
        eng2, m2, _, batch2, step2 = _flat_engine(2, steps=1)
        eng2.restore_checkpoint(p3)
        for pa, pb in zip(m3.parameters(), m2.parameters()):
            np.testing.assert_array_equal(np.asarray(pa._value),
                                          np.asarray(pb._value))
        float(step2(batch2))    # restored engine still steps
        # and a stage-2 checkpoint restores into stage-3 storage
        p2 = str(tmp_path / "ck2")
        eng2.save_checkpoint(p2)
        eng3b, m3b, _, batch3, step3 = _flat_engine(3, steps=1)
        eng3b.restore_checkpoint(p2)
        for pa, pb in zip(m2.parameters(), m3b.parameters()):
            np.testing.assert_array_equal(np.asarray(pa._value),
                                          np.asarray(pb._value))
        float(step3(batch3))

    def test_reshard_across_sharding_degrees(self, tmp_path):
        eng4, m4, _, _, _ = _flat_engine(3, dp=2, sh=4)
        path = str(tmp_path / "ck")
        eng4.save_checkpoint(path)
        eng2, m2, _, batch, step = _flat_engine(3, dp=4, sh=2, steps=1)
        eng2.restore_checkpoint(path)
        for pa, pb in zip(m4.parameters(), m2.parameters()):
            np.testing.assert_array_equal(np.asarray(pa._value),
                                          np.asarray(pb._value))
        float(step(batch))

    @pytest.mark.slow
    def test_5_crash_5_equals_10_straight_gpt(self, tmp_path):
        """The flagship gate on the gpt13b smoke topology: 5 steps +
        save + restore into a fresh stage-3 engine + 5 more == 10
        straight, bit-exactly — shard-only params, scattered moments,
        RNG and counters all round-trip in one commit unit."""
        dm, _, opt, (x, y), straight = _gpt_pipe(3, steps=10)
        dm1, _, opt1, (x1, y1), first = _gpt_pipe(3, steps=5)
        assert first == straight[:5]
        path = str(tmp_path / "ck")
        dm1.save_checkpoint(path)
        dm2, _, opt2, (x2, y2), _ = _gpt_pipe(3, steps=0)
        dm2.restore_checkpoint(path, optimizer=opt2)
        rest = [float(dm2.train_batch([x2, y2], opt2))
                for _ in range(5)]
        assert rest == straight[5:]


# ---------------------------------------------------------------------------
# auto_tuner: stage 3 in the search space, priced by the models
# ---------------------------------------------------------------------------
class TestAutoTuner:
    MODEL = {"hidden_size": 768, "num_layers": 12, "num_heads": 12,
             "vocab_size": 50304}

    def test_stage3_in_default_candidates(self):
        from paddle_tpu.distributed.auto_tuner import default_candidates

        cands = default_candidates(8, self.MODEL, global_batch=32)
        s3 = [c for c in cands if c.get("sharding_stage") == 3]
        assert s3 and all(c["sharding_degree"] > 1 for c in s3)
        # sharding-free configs never carry the stage knob
        assert all(c.get("sharding_stage") != 3 for c in cands
                   if c["sharding_degree"] == 1)

    def test_models_price_stage3(self):
        from paddle_tpu.distributed.auto_tuner import (
            estimate_memory_gb, estimate_step_time)

        base = {"dp_degree": 1, "mp_degree": 1, "pp_degree": 1,
                "sharding_degree": 8, "micro_batch_size": 4}
        s3 = dict(base, sharding_stage=3)
        # stage 3 trades HBM (params+grads / sh) for gather comm
        assert estimate_memory_gb(self.MODEL, s3, 32, 1024) < \
            estimate_memory_gb(self.MODEL, base, 32, 1024)
        assert estimate_step_time(self.MODEL, s3, 32, 1024) > \
            estimate_step_time(self.MODEL, base, 32, 1024)

    def test_crosscheck_prices_stage3_consistently(self):
        """AutoTuner.crosscheck on the measured stage-3 footprint: the
        stage-3 analytic estimate must sit BELOW the stage-2 one for
        the same measured bytes (params+grads / sharding_degree), so
        the measured-vs-analytic loop ranks the stages on their real
        trade instead of pruning stage 3 on stage-2 arithmetic."""
        from paddle_tpu.distributed.auto_tuner import AutoTuner

        dm3, _, _, _, _ = _gpt_pipe(3)
        eng = dm3._engine
        acct = ml.account_engine(eng, batch_tokens=8 * 16,
                                 accumulate_steps=2)
        assert acct.measured_bytes > 0 and acct.analytic_bytes > 0
        tuner = AutoTuner({"hidden_size": 32, "num_layers": 4,
                           "num_heads": 4, "vocab_size": 128},
                          num_devices=8, global_batch=4, seq_len=32)
        cfg = {"dp_degree": 1, "mp_degree": 2, "pp_degree": 2,
               "sharding_degree": 2, "micro_batch_size": 1}
        m_gb = acct.measured_bytes / 1e9
        d3 = tuner.crosscheck(dict(cfg, sharding_stage=3), m_gb)
        d2 = tuner.crosscheck(dict(cfg, sharding_stage=2), m_gb)
        assert d3 < d2
        # the live gauge's derivation (account_engine) uses the same
        # analytic model: stage-3 analytic bytes drop vs a stage-2
        # config of identical geometry
        from paddle_tpu.distributed.auto_tuner import estimate_memory_gb

        assert estimate_memory_gb(
            tuner.model, dict(cfg, sharding_stage=3), 4, 32) < \
            estimate_memory_gb(
                tuner.model, dict(cfg, sharding_stage=2), 4, 32)


# ---------------------------------------------------------------------------
# the stage-3 custom VJP: mirrored gather/reduce-scatter pairing
# ---------------------------------------------------------------------------
def test_stage3_gather_vjp_is_mirrored_reduce_scatter():
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, PartitionSpec as P

    from paddle_tpu.distributed.engine import _shard_map
    from paddle_tpu.observability import commledger as cl

    mesh = Mesh(np.array(jax.devices()[:8]).reshape(8), ("s",))

    def f(x):
        full = gb.stage3_gather(x, "s")
        return jnp.sum(full * full)

    def run(x):
        def body(xl):
            val, vjp = jax.vjp(f, xl)
            (g,) = vjp(jnp.float32(1.0))
            return g

        return jax.jit(_shard_map(body, mesh, (P("s"),), P("s")))(x)

    x = np.arange(16, dtype=np.float32)
    with cl.capture() as cap:
        g = run(x)
    # d/dx sum(gather(x)^2) = 2x on every rank summed -> 2*p*x
    np.testing.assert_allclose(np.asarray(g), 2 * 8 * x, rtol=1e-6)
    ops = {r.op for r in cap.records}
    assert "all_gather" in ops and "reduce_scatter" in ops


# ---------------------------------------------------------------------------
# tpulint: the bidirectional engine paths stay clean, zero baseline
# ---------------------------------------------------------------------------
def test_tpulint_stage3_surface_zero_baseline():
    import sys
    from pathlib import Path

    repo = Path(__file__).resolve().parents[1]
    sys.path.insert(0, str(repo))
    try:
        from tools.tpulint import ALL_RULES, lint_paths

        findings = lint_paths(
            [repo / "paddle_tpu" / "distributed" / "grad_buckets.py",
             repo / "paddle_tpu" / "distributed" / "engine.py"],
            ALL_RULES, root=repo)
    finally:
        sys.path.remove(str(repo))
    assert findings == [], [str(f) for f in findings]
