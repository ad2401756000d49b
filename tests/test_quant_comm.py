"""Quantized collectives (distributed/quant_comm.py) — codec, wire
exactness, error feedback, engine integration, checkpoint, and lint.

Under test:
- the int8/fp8 per-chunk codec: round-trip error bounds, zero chunks,
  nonfinite propagation (AMP found_inf must survive compression),
  stochastic rounding unbiasedness, the fixed chunk lattice
- quantized reduce-scatter / allreduce vs the full-precision
  collectives on the 8-vdev mesh, with ledger wire bytes pinned to the
  closed form (int8 payload + bf16 scale sidecar) EXACTLY
- knob-off byte-identity: quant_comm "none" leaves the engine's comm
  ledger byte-for-byte as before
- engine e2e (flat + pp seam scan): loss tracks fp32, zero steady-state
  recompiles, residual state carried, gauges published
- the convergence-parity gate: 200 deterministic steps int8+EF vs
  fp32 within a pinned tolerance AND the same test detects the
  divergence when error feedback is off (a harness that cannot see the
  failure it guards is no gate)
- crash/restore: the EF residual joins the checkpoint commit unit —
  save+restore+continue == straight run bit-exactly with the knob on
- collective-matmul rings: quantized ag_matmul/matmul_rs/
  matmul_allreduce fwd+bwd parity within quantization tolerance, int8
  ppermute payloads on the ledger
- auto_tuner: quant_comm in the search space, residual HBM in the
  analytic memory model
- tpulint: quant_comm pinned at zero baseline entries and
  vjp-ledger-symmetry green over the quantized rings
"""
import json
import os
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

import paddle_tpu as paddle
import paddle_tpu.distributed as dist
from paddle_tpu.distributed import collective as C
from paddle_tpu.distributed import fleet
from paddle_tpu.distributed import quant_comm as qc
from paddle_tpu.distributed.engine import ParallelEngine
from paddle_tpu.observability import commledger as cl

def _shard_map(f, mesh, in_specs, out_specs):
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


INT8 = qc.make_config({"dtype": "int8", "chunk": 16})
FP8 = qc.make_config({"dtype": "fp8", "chunk": 16})


def _reset_fleet():
    fleet._fleet_state.update(initialized=False, hcg=None, strategy=None)


# ---------------------------------------------------------------------------
# codec
# ---------------------------------------------------------------------------
class TestCodec:
    def test_roundtrip_error_bound(self):
        rng = np.random.RandomState(0)
        # wide per-chunk dynamic range: scales must adapt per chunk
        x = rng.randn(4, 64).astype(np.float32) * \
            np.array([1e3, 1.0, 1e-2, 0.0])[:, None]
        q, s = qc.encode(jnp.asarray(x), INT8)
        assert q.dtype == jnp.int8 and s.dtype == jnp.bfloat16
        assert q.shape == x.shape and s.shape == (4, 64 // 16)
        d = np.asarray(qc.decode(q, s, INT8))
        # error per element <= half a quantization step of ITS chunk
        # (bf16 scale rounding adds ~2^-8 relative slop)
        amax = np.abs(x).reshape(4, 4, 16).max(-1)
        bound = (amax / 127.0) * 0.5 * 1.02 + 1e-12
        err = np.abs(d - x).reshape(4, 4, 16).max(-1)
        assert (err <= bound + amax * 2 ** -7).all()

    def test_zero_chunk_exact_and_fp8(self):
        x = jnp.zeros((32,), jnp.float32)
        q, s = qc.encode(x, INT8)
        assert np.asarray(qc.decode(q, s, INT8)).max() == 0.0
        xr = jnp.asarray(np.random.RandomState(1).randn(64)
                         .astype(np.float32))
        q8, s8 = qc.encode(xr, FP8)
        assert q8.dtype == jnp.float8_e4m3fn
        d8 = np.asarray(qc.decode(q8, s8, FP8))
        assert np.abs(d8 - np.asarray(xr)).max() < 0.1

    def test_nonfinite_propagates(self):
        """A chunk holding inf must decode nonfinite — AMP's found_inf
        check runs on the SYNCED grads, so compression that silently
        finite-ized an overflow would break the scaler protocol."""
        for bad in (np.inf, np.nan):
            x = np.ones(16, np.float32)
            x[3] = bad
            q, s = qc.encode(jnp.asarray(x), INT8)
            d = np.asarray(qc.decode(q, s, INT8))
            assert not np.isfinite(d).all()

    def test_stochastic_rounding_unbiased(self):
        x = jnp.full((2048,), 0.3, jnp.float32)
        cfg = qc.make_config({"dtype": "int8", "chunk": 2048,
                              "stochastic_rounding": True})
        key = jax.random.key(0)
        q, s = qc.encode(x, cfg, key)
        d = np.asarray(qc.decode(q, s, cfg))
        vals = set(np.unique(np.asarray(q)).tolist())
        assert len(vals) == 2          # floor and floor+1 both hit
        assert abs(d.mean() - 0.3) < 0.005   # unbiased in expectation
        # same key -> same rounding (compile-stable determinism)
        q2, _ = qc.encode(x, cfg, key)
        assert (np.asarray(q) == np.asarray(q2)).all()

    def test_padding_lattice(self):
        assert qc.padded_len(40, 16) == 48
        assert qc.payload_wire_bytes(40, INT8) == 48 + 3 * 2
        cfg = qc.make_config({"dtype": "int8", "chunk": 64})
        assert qc.reduce_scatter_wire_bytes(4 * 40, 4, cfg) == \
            3 * (64 + 1 * 2)

    def test_make_config_validates(self):
        with pytest.raises(Exception):
            qc.make_config({"dtype": "int4"})
        with pytest.raises(Exception):
            qc.make_config({"nope": 1})
        with pytest.raises(Exception):
            qc.make_config({"chunk": 0})
        assert not qc.make_config(None).enabled
        assert qc.make_config({"dtype": "fp8"}).qmax == 448.0


# ---------------------------------------------------------------------------
# quantized collectives: parity + exact ledger bytes
# ---------------------------------------------------------------------------
class TestQuantizedCollectives:
    def _mesh(self, n=4):
        return Mesh(np.array(jax.devices()[:n]), ("s",))

    def test_reduce_scatter_parity_and_bytes(self):
        mesh = self._mesh(4)
        N = 4 * 40                      # L=40 pads to 48 on chunk 16
        v = np.random.RandomState(0).randn(4 * N).astype(np.float32)

        def f(x):
            with C.spmd_region():
                sh, deq = qc.quantized_reduce_scatter(
                    x.reshape(-1), ("s",), INT8)
                return sh, x.reshape(-1) - deq

        fn = jax.jit(_shard_map(f, mesh, P("s"), (P("s"), P("s"))))
        with cl.capture() as led:
            sh, resid = fn(jnp.asarray(v))
        ref = v.reshape(4, N).sum(0)
        scale_bound = np.abs(v).max() / 127.0 * 4 * 1.1
        assert np.abs(np.asarray(sh) - ref).max() <= scale_bound
        # wire bytes == the closed form EXACTLY (int8 + bf16 scales)
        assert led.bytes_for(op="all_to_all") == \
            qc.reduce_scatter_wire_bytes(N, 4, INT8)
        # records carry the quant stamps
        recs = [r for r in led.records if r.payload_ratio != 1.0]
        assert recs and {r.wire_dtype for r in recs} == \
            {"int8", "bfloat16"}
        # residual == v - decode(encode(v)) locally: adding it back to
        # the dequantized image reconstructs v exactly
        assert np.asarray(resid).shape == (4 * N,)

    def test_allreduce_parity_bytes_and_mean(self):
        mesh = self._mesh(4)
        N = 100                         # not divisible by p: pads
        v = np.random.RandomState(1).randn(4 * N).astype(np.float32)

        def f(x):
            with C.spmd_region():
                full, _ = qc.quantized_allreduce(
                    x.reshape(-1), ("s",), INT8, mean=True)
                return full

        fn = jax.jit(_shard_map(f, mesh, P("s"), P("s")))
        with cl.capture() as led:
            out = fn(jnp.asarray(v))
        ref = v.reshape(4, N).mean(0)
        got = np.asarray(out).reshape(4, N)
        bound = np.abs(v).max() / 127.0 * 2.2
        for r in range(4):              # every rank converged near ref
            assert np.abs(got[r] - ref).max() <= bound
        assert led.bytes_for() == qc.allreduce_wire_bytes(N, 4, INT8)
        ratios = led.quant_ratios()
        assert set(ratios) == {"s"} and 0 < ratios["s"] < 0.5

    def test_quant_ratio_math(self):
        """quant_ratios folds compressed records back to their
        uncompressed-equivalent bytes through the payload_ratio
        stamp."""
        led = cl.CommLedger()
        led.add(cl.CommRecord(op="all_to_all", axes=("s",), axis="s",
                              shape=(4, 64), dtype="int8", p=4,
                              payload_bytes=256, wire_bytes=192.0,
                              wire_dtype="int8", payload_ratio=0.25))
        led.add(cl.CommRecord(op="psum", axes=("s",), axis="s",
                              shape=(8,), dtype="float32", p=4,
                              payload_bytes=32, wire_bytes=48.0))
        r = led.quant_ratios()["s"]
        assert abs(r - (192.0 + 48.0) / (768.0 + 48.0)) < 1e-9

    def test_param_gather_own_shard_exact(self):
        mesh = self._mesh(4)
        shard = np.random.RandomState(2).randn(4, 8, 3) \
            .astype(np.float32)

        def f(x):
            with C.spmd_region():
                full = qc.quantized_param_gather(x, ("s",), 0, INT8)
                idx = jax.lax.axis_index("s")
                own = jax.lax.dynamic_slice_in_dim(full, idx * 8, 8,
                                                   axis=0)
                return own[None]

        fn = jax.jit(_shard_map(f, mesh, P("s"), P("s")))
        own = np.asarray(fn(jnp.asarray(shard.reshape(32, 3))))
        # every rank's own block survives the quantized gather EXACTLY
        assert (own == shard).all()


# ---------------------------------------------------------------------------
# engine integration (flat ZeRO-2 + knob-off byte identity)
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


def _flat_engine(quant_dtype="none", steps=6, error_feedback=True,
                 chunk=32, lr=0.01, seed=3, stochastic=False):
    strategy = fleet.DistributedStrategy()
    strategy.hybrid_configs = {
        "dp_degree": 2, "sharding_degree": 4,
        "sharding_configs": {"comm_overlap": True,
                             "comm_buffer_size_MB": 0.0005},
        "quant_comm": {"dtype": quant_dtype, "chunk": chunk,
                       "error_feedback": error_feedback,
                       "stochastic_rounding": stochastic}}
    _reset_fleet()
    hcg = fleet.init(is_collective=True, strategy=strategy)
    paddle.seed(seed)
    model = _mlp()
    opt = paddle.optimizer.Adam(learning_rate=lr,
                                parameters=model.parameters())
    model, opt, _ = dist.group_sharded_parallel(model, opt, "os_g")
    eng = ParallelEngine(model, opt, hcg.mesh)
    step = eng.train_step(lambda m, b: paddle.mean(
        (m(b["x"]) - b["y"]) ** 2))
    np.random.seed(0)
    x = np.random.randn(8, 16).astype("float32")
    y = np.random.randn(8, 16).astype("float32")
    batch = {"x": paddle.to_tensor(x), "y": paddle.to_tensor(y)}
    losses = [float(step(batch)) for _ in range(steps)]
    eng._flush_pending_scalars()
    return eng, losses, batch, step


class TestEngineFlat:
    def test_quant_tracks_fp32_zero_recompiles(self):
        eng_off, l_off, _, _ = _flat_engine("none")
        eng_on, l_on, _, _ = _flat_engine("int8")
        assert eng_on.stats.compiles == 1
        assert eng_on.stats.cache_hits == len(l_on) - 1
        gap = max(abs(a - b) for a, b in zip(l_off, l_on))
        assert gap < 5e-3
        # residual state exists and is finite
        assert eng_on._quant_residuals
        for v in eng_on._quant_residuals.values():
            assert np.isfinite(np.asarray(v)).all()

    def test_knob_off_ledger_byte_identical(self):
        """dtype "none" must leave the wire byte-for-byte as today."""
        eng, _, _, _ = _flat_engine("none")
        led = eng.comm_ledger()
        assert not led.quant_ratios()
        for r in led.records:
            assert r.payload_ratio == 1.0
            assert "int8" not in r.dtype
        # the exact closed forms the PR-8 tests pin still hold: every
        # record's wire bytes match the op's ring formula
        for r in led.records:
            assert r.wire_bytes == cl.wire_bytes(r.op, r.payload_bytes,
                                                 r.p)

    def test_quant_rs_bytes_closed_form(self):
        """The bucketed quantized reduce-scatter's a2a bytes on the
        sharding axis equal ceil(int8 payload + bf16 scales) exactly,
        summed over buckets (trips included)."""
        eng, _, _, _ = _flat_engine("int8")
        led = eng.comm_ledger()
        plan = eng._bucket_plan
        cfg = eng._quant_cfg
        expect = 0.0
        for g in plan.groups:
            if g.kind != "rs":
                continue
            for b in g.buckets:
                n = sum(int(np.prod(e.shape)) for e in b)
                expect += qc.reduce_scatter_wire_bytes(n, g.n, cfg)
        assert led.bytes_for(axis="sharding", op="all_to_all") == expect

    def test_gauges_published(self):
        _flat_engine("int8")
        from paddle_tpu.observability import get_registry

        snap = get_registry().snapshot()["metrics"]
        qr = snap["paddle_tpu_comm_quant_ratio"]["series"]
        assert any(s["labels"].get("axis") == "sharding" and
                   0 < s["value"] < 1 for s in qr)
        qn = snap["paddle_tpu_train_quant_residual_norm"]["series"]
        assert qn and qn[0]["value"] >= 0.0

    def test_stochastic_rounding_runs_compile_stable(self):
        eng, losses, _, _ = _flat_engine("int8", stochastic=True)
        assert eng.stats.compiles == 1
        assert all(np.isfinite(losses))

    def test_fp8_path(self):
        eng, losses, _, _ = _flat_engine("fp8")
        assert all(np.isfinite(losses))
        led = eng.comm_ledger()
        assert any("float8" in r.wire_dtype for r in led.records)


# ---------------------------------------------------------------------------
# convergence-parity gate (deterministic horizon)
# ---------------------------------------------------------------------------
class TestConvergenceGate:
    """int8 + error feedback must track the fp32 sync over a 300-step
    deterministic horizon; the SAME harness with error feedback off
    must show measurable divergence — proving the gate can detect the
    failure it guards.

    The task plants a ~200x dynamic-range spread inside ONE scale
    chunk (chunk >= bucket payload): two loud-but-irrelevant input
    features pin the int8 scale, so the target-relevant quiet
    gradients sit below one quantization step. Without error feedback
    they round to zero most steps and the model visibly stalls; with
    the residual carrying what each step failed to transmit, the
    quiet coordinates still receive their time-averaged gradient and
    the loss tracks the fp32 run. Everything is deterministic: fixed
    seeds, fixed batch, single XLA CPU backend — the tolerances are
    pins, not statistics."""

    def _run(self, dtype, error_feedback=True, steps=300):
        strategy = fleet.DistributedStrategy()
        strategy.hybrid_configs = {
            "dp_degree": 2, "sharding_degree": 4,
            "sharding_configs": {"comm_overlap": True,
                                 "comm_buffer_size_MB": 0.0005},
            # one scale chunk per bucket — the worst-case lattice
            "quant_comm": {"dtype": dtype, "chunk": 65536,
                           "error_feedback": error_feedback}}
        _reset_fleet()
        hcg = fleet.init(is_collective=True, strategy=strategy)
        paddle.seed(7)
        model = _mlp()
        opt = paddle.optimizer.Adam(learning_rate=0.03,
                                    parameters=model.parameters())
        model, opt, _ = dist.group_sharded_parallel(model, opt, "os_g")
        eng = ParallelEngine(model, opt, hcg.mesh)
        step = eng.train_step(lambda m, b: paddle.mean(
            (m(b["x"]) - b["y"]) ** 2))
        rng = np.random.RandomState(0)
        x = rng.randn(64, 16).astype("float32")
        x[:, :2] *= 200.0           # loud, target-irrelevant
        W = rng.randn(14, 16).astype("float32")
        y = (x[:, 2:] @ W * 0.1).astype("float32")
        batch = {"x": paddle.to_tensor(x), "y": paddle.to_tensor(y)}
        losses = [float(step(batch)) for _ in range(steps)]
        return float(np.mean(losses[-20:]))

    @pytest.mark.slow  # ~25s 300-step convergence horizon; 1-cpu tier-1 budget
    def test_int8_ef_matches_fp32_and_no_ef_diverges(self):
        ref = self._run("none")
        ef = self._run("int8", True)
        no_ef = self._run("int8", False)
        # pinned tolerance: EF lands within 4x of the fp32 tail loss
        # (observed ~2.3x; deterministic, so this is a pin with margin)
        assert ef <= 4.0 * ref, (ref, ef, no_ef)
        # and the harness DETECTS the EF-off failure: the no-EF tail
        # is at least 2x the EF tail (observed ~3.6x) — the quiet
        # coordinates demonstrably stop training
        assert no_ef >= 2.0 * ef, (ref, ef, no_ef)


# ---------------------------------------------------------------------------
# checkpoint: the EF residual is part of the commit unit
# ---------------------------------------------------------------------------
class TestCheckpointResidual:
    def test_save_restore_continue_bit_exact(self, tmp_path):
        # straight run: 6 steps
        _, straight, _, _ = _flat_engine("int8", steps=6)
        # interrupted run: 3 steps, save, restore into a FRESH engine,
        # 3 more — must equal the straight run bit-exactly, which
        # requires the residual to round-trip
        eng, first, batch, step = _flat_engine("int8", steps=3)
        path = str(tmp_path / "ck")
        eng.save_checkpoint(path)
        saved_res = {k: np.asarray(v)
                     for k, v in eng._quant_residuals.items()}
        assert saved_res
        eng2, _, batch2, step2 = _flat_engine("int8", steps=1)
        meta = eng2.restore_checkpoint(path)
        assert sorted(meta["quant_residual_keys"]) == \
            sorted(saved_res)
        for k, v in eng2._quant_residuals.items():
            assert (np.asarray(v) == saved_res[k]).all()
        rest = [float(step2(batch2)) for _ in range(3)]
        assert rest == straight[3:]

    def test_dropping_residual_changes_trajectory(self, tmp_path):
        """The negative control: a resume that zeroes the residual is
        NOT bit-exact — i.e. the state actually matters and the test
        above could catch a loader that silently dropped it."""
        _, straight, _, _ = _flat_engine("int8", steps=6)
        eng, _, _, _ = _flat_engine("int8", steps=3)
        path = str(tmp_path / "ck")
        eng.save_checkpoint(path)
        eng2, _, batch2, step2 = _flat_engine("int8", steps=1)
        eng2.restore_checkpoint(path)
        # sabotage: zero the residuals post-restore
        eng2._quant_residuals = {
            k: jnp.zeros_like(v)
            for k, v in eng2._quant_residuals.items()}
        rest = [float(step2(batch2)) for _ in range(3)]
        assert rest != straight[3:]


def _gpt_pipe(quant_dtype="int8", chunk=64):
    """The gpt13b smoke topology (mp2 x pp2 x sharding2, stage 2,
    comm_overlap, rings on) with quant_comm, tiny."""
    from paddle_tpu.models import GPTForCausalLMPipe
    from paddle_tpu.models.gpt import GPTConfig

    cfg = GPTConfig(vocab_size=128, hidden_size=32, num_layers=4,
                    num_heads=4, max_position_embeddings=32)
    strategy = fleet.DistributedStrategy()
    strategy.hybrid_configs = {
        "dp_degree": 1, "mp_degree": 2, "pp_degree": 2,
        "sharding_degree": 2,
        "mp_configs": {"mp_async_allreduce": True},
        "sharding_configs": {"comm_overlap": True,
                             "comm_buffer_size_MB": 0.001},
        "quant_comm": {"dtype": quant_dtype, "chunk": chunk}}
    strategy.sharding_configs = {"stage": 2}
    strategy.pipeline_configs = {"accumulate_steps": 2,
                                 "micro_batch_size": 2}
    _reset_fleet()
    fleet.init(is_collective=True, strategy=strategy)
    paddle.seed(0)
    model = GPTForCausalLMPipe(cfg)
    dm = fleet.distributed_model(model)
    opt = fleet.distributed_optimizer(
        paddle.optimizer.AdamW(learning_rate=1e-3,
                               parameters=model.parameters()))
    r = np.random.RandomState(0)
    ids = r.randint(0, cfg.vocab_size, (8, 17))
    x = paddle.to_tensor(ids[:, :-1])
    y = paddle.to_tensor(ids[:, 1:])
    return dm, opt, x, y


class TestCheckpointResidualGptTopology:
    @pytest.mark.slow
    def test_5_crash_5_equals_10_straight(self, tmp_path):
        """The flagship-topology acceptance: 5 steps + save + restore
        into a fresh engine + 5 more == 10 straight, bit-exactly, with
        quant_comm on — which holds ONLY if the seam-scan EF residuals
        (and the sharded stage-2 param shards the quantized gather
        stores) round-trip through the checkpoint."""
        dm, opt, x, y = _gpt_pipe()
        straight = [float(dm.train_batch([x, y], opt))
                    for _ in range(10)]
        dm1, opt1, x1, y1 = _gpt_pipe()
        first = [float(dm1.train_batch([x1, y1], opt1))
                 for _ in range(5)]
        assert first == straight[:5]
        path = str(tmp_path / "ck")
        dm1.save_checkpoint(path)
        assert dm1._engine._quant_residuals     # seam residuals exist
        dm2, opt2, x2, y2 = _gpt_pipe()
        dm2.restore_checkpoint(path, optimizer=opt2)
        rest = [float(dm2.train_batch([x2, y2], opt2))
                for _ in range(5)]
        assert rest == straight[5:]


# ---------------------------------------------------------------------------
# pp seam scan (pipelined stacked params)
# ---------------------------------------------------------------------------
class TestSeamScan:
    @pytest.mark.slow
    def test_pipelined_quant_seam(self):
        from paddle_tpu.models import GPTForCausalLMPipe
        from paddle_tpu.models.gpt import GPTConfig

        def run(dtype):
            cfg = GPTConfig(vocab_size=128, hidden_size=32,
                            num_layers=4, num_heads=4,
                            max_position_embeddings=32)
            strategy = fleet.DistributedStrategy()
            strategy.hybrid_configs = {
                "dp_degree": 1, "mp_degree": 2, "pp_degree": 2,
                "sharding_degree": 2,
                "mp_configs": {"mp_async_allreduce": True},
                "sharding_configs": {"comm_overlap": True,
                                     "comm_buffer_size_MB": 0.001},
                "quant_comm": {"dtype": dtype, "chunk": 64}}
            strategy.sharding_configs = {"stage": 2}
            strategy.pipeline_configs = {"accumulate_steps": 2,
                                         "micro_batch_size": 2}
            _reset_fleet()
            fleet.init(is_collective=True, strategy=strategy)
            paddle.seed(0)
            model = GPTForCausalLMPipe(cfg)
            dm = fleet.distributed_model(model)
            opt = fleet.distributed_optimizer(
                paddle.optimizer.AdamW(learning_rate=1e-4,
                                       parameters=model.parameters()))
            r = np.random.RandomState(0)
            ids = r.randint(0, cfg.vocab_size, (8, 17))
            x = paddle.to_tensor(ids[:, :-1])
            y = paddle.to_tensor(ids[:, 1:])
            losses = [float(dm.train_batch([x, y], opt))]
            cw = dm._engine.stats.compiles
            for _ in range(2):
                losses.append(float(dm.train_batch([x, y], opt)))
            return (losses, dm._engine,
                    dm._engine.stats.compiles - cw)

        l_off, _, _ = run("none")
        l_on, eng, rc = run("int8")
        assert rc == 0
        assert max(abs(a - b) for a, b in zip(l_off, l_on)) < 5e-2
        # seam residuals ride the scan: [nb, tick elems] buffers exist
        assert any(v.ndim == 2 for v in eng._quant_residuals.values())
        led = eng.comm_ledger()
        # scan-tick a2a records carry trips=nb
        assert any(r.trips > 1 and r.payload_ratio != 1.0
                   for r in led.records)


# ---------------------------------------------------------------------------
# quantized collective-matmul rings
# ---------------------------------------------------------------------------
class TestQuantRings:
    def _mesh(self, n=4):
        return Mesh(np.array(jax.devices()[:n]), ("mp",))

    def _with_ring_quant(self):
        return qc.override({"dtype": "int8", "chunk": 32,
                            "mp_rings": True})

    def test_ag_matmul_fwd_bwd_parity(self):
        from paddle_tpu.distributed import collective_matmul as cm

        mesh = self._mesh(4)
        rng = np.random.RandomState(0)
        x = rng.randn(16, 8).astype(np.float32)    # 4 ranks x 4 rows
        w = rng.randn(8, 8).astype(np.float32)

        def gold(xs, ws):
            def f(xl, wl):
                with C.spmd_region():
                    full = jax.lax.all_gather(xl, "mp", axis=0,
                                              tiled=True)
                    return jnp.sum(full @ wl)
            return jax.jit(_shard_map(f, mesh, (P("mp"), P()), P()))(
                xs, ws)

        def fused(xs, ws):
            def f(xl, wl):
                with C.spmd_region():
                    return jnp.sum(cm.ag_matmul(xl, wl, ("mp",), 0))
            return jax.jit(_shard_map(f, mesh, (P("mp"), P()), P()))(
                xs, ws)

        ref, (rgx, rgw) = jax.value_and_grad(gold, (0, 1))(
            jnp.asarray(x), jnp.asarray(w))
        with self._with_ring_quant():
            with cl.capture() as led:
                got, (ggx, ggw) = jax.value_and_grad(fused, (0, 1))(
                    jnp.asarray(x), jnp.asarray(w))
        scale = max(np.abs(np.asarray(ref)), 1.0)
        assert abs(float(got) - float(ref)) / scale < 0.05
        assert np.abs(np.asarray(ggx) - np.asarray(rgx)).max() / \
            max(np.abs(np.asarray(rgx)).max(), 1.0) < 0.1
        assert np.abs(np.asarray(ggw) - np.asarray(rgw)).max() / \
            max(np.abs(np.asarray(rgw)).max(), 1.0) < 0.1
        # the wire carried int8 + bf16 ppermutes, stamped
        pp = [r for r in led.records if r.op == "ppermute"]
        assert pp and all(r.payload_ratio != 1.0 for r in pp)
        assert {r.wire_dtype for r in pp} == {"int8", "bfloat16"}

    def test_matmul_allreduce_parity_and_gather_bytes(self):
        from paddle_tpu.distributed import collective_matmul as cm

        _reset_fleet()
        mesh = self._mesh(4)
        rng = np.random.RandomState(1)
        x = rng.randn(8, 16).astype(np.float32)    # k sharded: [8, 4]
        w = rng.randn(16, 8).astype(np.float32)    # [k_local 4, 8] x 4

        def gold(xl, wl):
            with C.spmd_region():
                return C.t_psum(xl @ wl, ("mp",))

        def fused(xl, wl):
            with C.spmd_region():
                return cm.matmul_allreduce(xl, wl, ("mp",), 0)

        gf = jax.jit(_shard_map(gold, mesh, (P(None, "mp"), P("mp")),
                                P()))
        ref = np.asarray(gf(jnp.asarray(x), jnp.asarray(w)))
        with self._with_ring_quant():
            ff = jax.jit(_shard_map(fused, mesh,
                                    (P(None, "mp"), P("mp")), P()))
            with cl.capture() as led:
                got = np.asarray(ff(jnp.asarray(x), jnp.asarray(w)))
        assert np.abs(got - ref).max() / max(np.abs(ref).max(), 1.0) \
            < 0.05
        ag = [r for r in led.records if r.op == "all_gather"]
        assert ag and all(r.payload_ratio != 1.0 for r in ag)

    def test_knob_off_rings_untouched(self):
        from paddle_tpu.distributed import collective_matmul as cm

        _reset_fleet()
        mesh = self._mesh(4)
        x = np.random.RandomState(2).randn(16, 8).astype(np.float32)
        w = np.random.RandomState(3).randn(8, 8).astype(np.float32)

        def f(xl, wl):
            with C.spmd_region():
                return cm.ag_matmul(xl, wl, ("mp",), 0)

        fn = jax.jit(_shard_map(f, mesh, (P("mp"), P()), P("mp")))
        with cl.capture() as led:
            fn(jnp.asarray(x), jnp.asarray(w))
        assert all(r.payload_ratio == 1.0 for r in led.records)
        assert all(r.dtype == "float32" for r in led.records
                   if r.op == "ppermute")


# ---------------------------------------------------------------------------
# auto_tuner + memory model
# ---------------------------------------------------------------------------
class TestTunerAndMemory:
    def test_search_space_grows_quant_variants(self):
        from paddle_tpu.distributed.auto_tuner import default_candidates

        model = {"hidden_size": 64, "num_layers": 4, "num_heads": 4,
                 "vocab_size": 128}
        base = default_candidates(8, model, 32)
        quant = default_candidates(8, model, 32, tune_quant_comm=True)
        q_cfgs = [c for c in quant if "quant_comm" in c]
        assert len(quant) > len(base) and q_cfgs
        assert all(c["quant_comm"]["dtype"] == "int8" for c in q_cfgs)

    def test_memory_model_prices_residual(self):
        from paddle_tpu.distributed.auto_tuner import estimate_memory_gb

        model = {"hidden_size": 512, "num_layers": 8,
                 "vocab_size": 1024}
        cfg = {"dp_degree": 2, "mp_degree": 2, "pp_degree": 1,
               "sharding_degree": 2}
        off = estimate_memory_gb(model, cfg, 32, 128)
        on = estimate_memory_gb(
            model, dict(cfg, quant_comm={"dtype": "int8"}), 32, 128)
        # the delta is exactly one local fp32 grad image
        P_local = (1024 * 512 + 8 * (4 * 512 * 512 + 2 * 512 * 2048)
                   + 2 * 512) / 2
        assert abs((on - off) * 1e9 - P_local * 4) < 1e3

    def test_step_time_model_discounts_quant_comm(self):
        from paddle_tpu.distributed.auto_tuner import estimate_step_time

        model = {"hidden_size": 512, "num_layers": 8,
                 "vocab_size": 1024}
        cfg = {"dp_degree": 4, "mp_degree": 2, "pp_degree": 1,
               "sharding_degree": 1}
        off = estimate_step_time(model, cfg, 32, 128)
        on = estimate_step_time(
            model, dict(cfg, quant_comm={"dtype": "int8"}), 32, 128)
        assert on < off

    def test_measured_accounting_reports_residual(self):
        from paddle_tpu.observability import memledger as ml

        eng, _, _, _ = _flat_engine("int8")
        acct = ml.account_engine(eng, batch_tokens=8)
        expect = sum(
            int(np.prod(v.shape)) * 4 // 8    # 8 vdevs share dim 0
            for v in eng._quant_residuals.values())
        assert acct.components.get("quant_residual") == expect


# ---------------------------------------------------------------------------
# static analysis
# ---------------------------------------------------------------------------
class TestLint:
    def test_quant_comm_zero_baseline(self):
        """quant_comm.py ships lint-clean: zero baseline entries."""
        base = json.loads(
            (Path(__file__).parent.parent / "tools" / "tpulint" /
             "baseline.json").read_text())
        for e in base.get("findings", []):
            assert "quant_comm" not in str(e), e

    def test_tree_clean_incl_vjp_symmetry(self):
        """Whole-tree tpulint exit 0 — in particular the quantized
        rings keep the mirrored-ring / psum-identity pairings
        recognizable (the quant_comm wrappers map to their LOGICAL
        collective kinds in the shim table)."""
        proc = subprocess.run(
            [sys.executable, "-m", "tools.tpulint", "paddle_tpu/",
             "--select", "vjp-ledger-symmetry,raw-collective"],
            cwd=str(Path(__file__).parent.parent),
            capture_output=True, text=True, timeout=300)
        assert proc.returncode == 0, proc.stdout + proc.stderr

    def test_quantized_wrapper_kinds_resolve(self):
        """Fixture: a custom_vjp whose fwd psums through
        quantized_allreduce still reads as the Megatron psum/identity
        pairing."""
        from tools.tpulint.project import COLLECTIVE_SHIMS

        assert COLLECTIVE_SHIMS["quantized_allreduce"] == "psum"
        assert COLLECTIVE_SHIMS["quantized_reduce_scatter"] == \
            "reduce_scatter"
        assert COLLECTIVE_SHIMS["quantized_param_gather"] == \
            "all_gather"
