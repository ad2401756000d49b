"""Communication ledger + exposed-comm attribution + serving spans.

Under test:
- closed-form wire-byte formulas (commledger.wire_bytes)
- trace-time capture: exact records for a hand-built shard_map program,
  empty capture on cached executions (the per-program ledger contract)
- ring closed forms: ag_matmul / matmul_rs / matmul_allreduce ledger
  bytes match the analytic ring costs EXACTLY on the 8-vdev mesh
- DP grad all-reduce: ParallelEngine's compiled step ledger matches the
  per-parameter closed form; comm counters accumulate per step; zero
  recompiles after warmup with the ledger enabled
- ablation: every collective's local stand-in preserves shape/dtype
- profile_exposed_comm: report shape, gauge publication, engine state
  restored bit-exactly, program cache intact (no recompile after)
- per-request serving spans: lifecycle stages, bounded ring, Chrome
  trace export, stage-latency histogram
- the stdlib /metrics HTTP exporter round-trips the exposition
"""
import json
import sys
import urllib.request
from pathlib import Path

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

import paddle_tpu as paddle
from paddle_tpu import observability as obs
from paddle_tpu.distributed import collective as C
from paddle_tpu.distributed import collective_matmul as cm
from paddle_tpu.distributed.engine import ParallelEngine, _shard_map
from paddle_tpu.observability import commledger as cl

F32 = 4  # bytes


def _mesh(n=8, axis="mp"):
    return Mesh(np.array(jax.devices()[:n]).reshape(n), (axis,))


# ---------------------------------------------------------------------------
# closed-form wire bytes
# ---------------------------------------------------------------------------
class TestWireBytes:
    def test_formulas(self):
        assert cl.wire_bytes("psum", 800, 8) == 2 * 7 / 8 * 800
        assert cl.wire_bytes("pmax", 800, 8) == 2 * 7 / 8 * 800
        assert cl.wire_bytes("all_gather", 100, 8) == 700
        assert cl.wire_bytes("reduce_scatter", 800, 8) == 700
        assert cl.wire_bytes("all_to_all", 800, 8) == 700
        assert cl.wire_bytes("ppermute", 256, 8) == 256
        # a group of one moves nothing
        for op in cl.OPS:
            assert cl.wire_bytes(op, 1234, 1) == 0.0
        with pytest.raises(ValueError):
            cl.wire_bytes("bogus", 1, 2)


# ---------------------------------------------------------------------------
# capture on a hand-built SPMD program
# ---------------------------------------------------------------------------
class TestCapture:
    def test_exact_records_and_cached_reuse(self):
        mesh = _mesh()

        def f(x):
            y = C.t_psum(x, ("mp",))
            z = C.t_all_gather(x, ("mp",), axis=0)
            w = C.t_psum_scatter(z, ("mp",), scatter_dimension=0)
            return y.sum() + z.sum() + w.sum()

        step = jax.jit(_shard_map(f, mesh, (P("mp"),), P()))
        x = jnp.ones((16, 4), jnp.float32)
        with cl.capture() as led:
            step(x)
        # local shard [2, 4] f32 = 32 bytes
        assert [(r.op, r.axis, r.shape) for r in led.records] == [
            ("psum", "mp", (2, 4)), ("all_gather", "mp", (2, 4)),
            ("reduce_scatter", "mp", (16, 4))]
        assert led.bytes_for(op="psum") == 2 * 7 / 8 * 32
        assert led.bytes_for(op="all_gather") == 7 * 32
        assert led.bytes_for(op="reduce_scatter") == 7 / 8 * 256
        # second execution hits the compiled program: nothing re-notes
        with cl.capture() as led2:
            step(x)
        assert len(led2) == 0

    def test_publish_increments_counters(self):
        reg = obs.MetricsRegistry()
        from paddle_tpu.observability.catalog import comm_metrics

        m = comm_metrics(reg)
        led = cl.CommLedger()
        cl._state.captures.append(led)
        try:
            cl.note("psum", ("dp",), (4, 4), np.dtype("float32"), 8)
            cl.note("ppermute", ("pp",), (2,), np.dtype("float32"), 2,
                    ((0, 1), (1, 0)))
        finally:
            cl._state.captures.remove(led)
        led.publish(m["comm_bytes"], m["comm_ops"])
        led.publish(m["comm_bytes"], m["comm_ops"])
        assert m["comm_bytes"].value(axis="dp", op="psum") == \
            2 * (2 * 7 / 8 * 64)
        assert m["comm_ops"].value(axis="pp", op="ppermute") == 2


# ---------------------------------------------------------------------------
# ring closed forms (the acceptance gate)
# ---------------------------------------------------------------------------
class TestRingClosedForms:
    S, K, N, p = 128, 8, 16, 8

    def _trace(self, fn, in_specs, out_specs, *args):
        mesh = _mesh(self.p)
        step = jax.jit(_shard_map(fn, mesh, in_specs, out_specs))
        with cl.capture() as led:
            out = step(*args)
        jax.block_until_ready(out)
        return led

    def test_ag_matmul_ring_bytes(self):
        r = np.random.RandomState(0)
        x = jnp.asarray(r.randn(self.S, self.K), jnp.float32)
        w = jnp.asarray(r.randn(self.K, self.N), jnp.float32)
        led = self._trace(lambda a, b: cm.ag_matmul(a, b, ("mp",), 0),
                          (P("mp"), P(None, "mp")), P("mp"), x, w)
        shard_bytes = (self.S // self.p) * self.K * F32
        # bidirectional ring: p-1 shard-sized ppermutes, nothing else
        assert led.ops_for(op="ppermute") == self.p - 1
        assert led.bytes_for(op="ppermute") == (self.p - 1) * shard_bytes
        assert led.bytes_for() == led.bytes_for(op="ppermute")

    def test_matmul_rs_ring_bytes(self):
        r = np.random.RandomState(1)
        x = jnp.asarray(r.randn(self.S, self.K), jnp.float32)
        w = jnp.asarray(r.randn(self.K, self.N), jnp.float32)
        led = self._trace(lambda a, b: cm.matmul_rs(a, b, ("mp",), 0),
                          (P("mp"), P(None, "mp")), P("mp"), x, w)
        # accumulator chunk: [S/p^2, N/p] partial sums (w is column-
        # sharded, so the local feature dim is N/p) shifted p-1 times
        acc_bytes = (self.S // self.p // self.p) \
            * (self.N // self.p) * F32
        assert led.ops_for(op="ppermute") == self.p - 1
        assert led.bytes_for(op="ppermute") == (self.p - 1) * acc_bytes
        assert led.bytes_for() == led.bytes_for(op="ppermute")

    def test_matmul_allreduce_ring_bytes(self):
        r = np.random.RandomState(2)
        x = jnp.asarray(r.randn(self.S, self.K), jnp.float32)
        w = jnp.asarray(r.randn(self.K, self.N), jnp.float32)
        led = self._trace(
            lambda a, b: cm.matmul_allreduce(a, b, ("mp",), 0),
            (P("mp"), P(None, "mp")), P("mp"), x, w)
        acc_bytes = (self.S // self.p // self.p) \
            * (self.N // self.p) * F32
        # rs-ring (p-1 shifts) + tiled all_gather of the acc chunk
        assert led.bytes_for(op="ppermute") == (self.p - 1) * acc_bytes
        assert led.ops_for(op="all_gather") == 1
        assert led.bytes_for(op="all_gather") == (self.p - 1) * acc_bytes
        assert led.bytes_for() == 2 * (self.p - 1) * acc_bytes


# ---------------------------------------------------------------------------
# DP grad all-reduce through the compiled train step
# ---------------------------------------------------------------------------
@pytest.fixture(scope="module")
def dp_engine():
    from paddle_tpu.distributed import fleet
    from paddle_tpu.models import (GPTConfig, GPTForCausalLM,
                                   GPTPretrainingCriterion)

    obs.reset_registry()
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
    eng = ParallelEngine(model, opt, hcg.mesh)
    step = eng.train_step(lambda m, b: crit(m(b["x"]), b["y"]))
    r = np.random.RandomState(0)
    ids = r.randint(0, 128, (8, 17))
    batch = {"x": paddle.to_tensor(ids[:, :-1]),
             "y": paddle.to_tensor(ids[:, 1:])}
    losses = [float(step(batch)) for _ in range(3)]
    return eng, step, batch, losses


class TestDpGradSyncLedger:
    def test_ledger_matches_closed_form(self, dp_engine):
        eng, _, _, _ = dp_engine
        led = eng.comm_ledger()
        p = 8
        # per trainable param: one grad pmean; plus one scalar loss
        # pmean — nothing else crosses 'dp' in this config
        expect = sum(
            2 * (p - 1) / p
            * int(np.prod(q._value.shape)) * q._value.dtype.itemsize
            for q in eng.trainable) + 2 * (p - 1) / p * F32
        assert led.bytes_for(axis="dp", op="psum") == expect
        assert led.ops_for(axis="dp", op="psum") == \
            len(eng.trainable) + 1
        assert led.axis_labels() == ["dp"]

    def test_counters_accumulate_per_step(self, dp_engine):
        eng, _, _, losses = dp_engine
        led = eng.comm_ledger()
        per_step = led.bytes_for(axis="dp", op="psum")
        got = eng._metrics["comm_bytes"].value(axis="dp", op="psum")
        assert got == len(losses) * per_step
        assert eng._metrics["comm_ops"].value(axis="dp", op="psum") \
            == len(losses) * led.ops_for(axis="dp", op="psum")

    def test_zero_recompiles_with_ledger_enabled(self, dp_engine):
        eng, step, batch, _ = dp_engine
        c0 = eng.stats.compiles
        float(step(batch))
        float(step(batch))
        assert eng.stats.compiles == c0      # ledger adds no signatures

    def test_snapshot_stays_inside_schema(self, dp_engine):
        from paddle_tpu.observability import catalog

        eng, _, _, _ = dp_engine
        with open(catalog.SCHEMA_PATH) as f:
            schema = json.load(f)
        m = eng.metrics_snapshot()["metrics"]
        for name in ("paddle_tpu_comm_bytes_total",
                     "paddle_tpu_comm_ops_total"):
            assert name in m and name in schema
            for row in m[name]["series"]:
                assert sorted(row["labels"]) == schema[name]["labels"]


# ---------------------------------------------------------------------------
# EP all-to-all through the compiled MoE step (the expert-parallel axis)
# ---------------------------------------------------------------------------
class TestEpA2aLedger:
    def _engine(self, async_dispatch):
        from paddle_tpu.distributed import fleet
        from paddle_tpu.incubate.distributed.models.moe import MoELayer

        strategy = fleet.DistributedStrategy()
        strategy.hybrid_configs = {
            "dp_degree": 1, "ep_degree": 8, "mp_degree": 1,
            "moe_configs": {"ep_async_dispatch": async_dispatch}}
        hcg = fleet.init(is_collective=True, strategy=strategy)
        paddle.seed(0)
        model = MoELayer(8, d_hidden=16, num_experts=8, gate="gshard")
        opt = paddle.optimizer.Adam(learning_rate=0.01,
                                    parameters=model.parameters())
        eng = ParallelEngine(model, opt, hcg.mesh)
        step = eng.train_step(
            lambda m, b: paddle.mean(m(b["x"]) ** 2) + 0.01 * m.aux_loss)
        r = np.random.RandomState(0)
        batch = {"x": paddle.to_tensor(
            r.randn(16, 8, 8).astype("float32"))}
        float(step(batch))
        # per-rank shapes of the dispatch tensor [E, C, d]
        T_local = 16 * 8 // 8
        C_cap = model._capacity(T_local)
        return eng, 8 * C_cap * 8 * F32

    def test_unfused_a2a_closed_form(self):
        """dispatch + combine, fwd + bwd = 4 all_to_alls of the full
        [E, C, d] dispatch tensor, each (p-1)/p x payload on the wire
        (the _ledger_a2a custom VJP keeps the backward pair visible)."""
        eng, payload = self._engine(False)
        led = eng.comm_ledger()
        p = 8
        assert led.ops_for(axis="ep", op="all_to_all") == 4
        assert led.bytes_for(axis="ep", op="all_to_all") == \
            4 * (p - 1) / p * payload
        assert led.ops_for(axis="ep", op="ppermute") == 0

    def test_fused_ring_same_wire_bytes(self):
        """ep_async_dispatch rides ppermutes: 2(p-1) per direction per
        pass = 4(p-1) block-sized shifts, totalling EXACTLY the a2a
        closed form (the ring re-chunks the exchange, it does not move
        more bytes)."""
        eng, payload = self._engine(True)
        led = eng.comm_ledger()
        p = 8
        block = payload // p                  # [E/p, C, d] per tick
        assert led.ops_for(axis="ep", op="all_to_all") == 0
        assert led.ops_for(axis="ep", op="ppermute") == 4 * (p - 1)
        assert led.bytes_for(axis="ep", op="ppermute") == \
            4 * (p - 1) * block == 4 * (p - 1) / p * payload


# ---------------------------------------------------------------------------
# bucketed grad sync (comm_overlap): exact wire bytes + scan trip counts
# ---------------------------------------------------------------------------
def _zero2_engine(overlap):
    """dp2 x sharding4 ZeRO stage-2 MLP engine (grad_buckets target)."""
    import paddle_tpu.distributed as dist
    from paddle_tpu.distributed import fleet

    strategy = fleet.DistributedStrategy()
    strategy.hybrid_configs = {
        "dp_degree": 2, "sharding_degree": 4,
        "sharding_configs": {"comm_overlap": overlap,
                             "comm_buffer_size_MB": 1e-6}}
    fleet._fleet_state.update(initialized=False, hcg=None, strategy=None)
    hcg = fleet.init(is_collective=True, strategy=strategy)
    paddle.seed(3)

    class MLP(paddle.nn.Layer):
        def __init__(self):
            super().__init__()
            self.fc1 = paddle.nn.Linear(16, 32)
            self.fc2 = paddle.nn.Linear(32, 16)

        def forward(self, x):
            return self.fc2(paddle.nn.functional.relu(self.fc1(x)))

    model = MLP()
    opt = paddle.optimizer.Adam(learning_rate=0.1,
                                parameters=model.parameters())
    model, opt, _ = dist.group_sharded_parallel(model, opt, "os_g")
    eng = ParallelEngine(model, opt, hcg.mesh)
    step = eng.train_step(
        lambda m, b: paddle.mean((m(b["x"]) - b["y"]) ** 2))
    x = np.zeros((8, 16), "float32")
    float(step({"x": paddle.to_tensor(x), "y": paddle.to_tensor(x)}))
    return eng


class TestBucketedGradSyncLedger:
    """The satellite pins: per-bucket collectives move EXACTLY the
    unbucketed closed-form bytes (coalescing re-chunks, it never moves
    more), and the ledger op count equals the bucket count."""

    def test_bucketed_bytes_match_unbucketed_closed_form(self):
        eng_on = _zero2_engine(True)
        eng_off = _zero2_engine(False)
        led_on, led_off = eng_on.comm_ledger(), eng_off.comm_ledger()
        plan = eng_on._bucket_plan
        p_sh, p_dp = 4, 2
        payload = sum(
            int(np.prod(q._value.shape)) * q._value.dtype.itemsize
            for q in eng_on.trainable)
        # stage-2 reduce-scatter over 'sharding': sum over buckets ==
        # (p-1)/p x total grad payload == the per-param closed form
        assert led_on.bytes_for(axis="sharding", op="reduce_scatter") \
            == (p_sh - 1) / p_sh * payload \
            == led_off.bytes_for(axis="sharding", op="reduce_scatter")
        # grad pmean over plain dp: 2(p-1)/p x total payload, same both
        # ways (the ledger books pmean under the "psum" kind); knob-off
        # adds nothing else on dp, knob-on adds nothing else on dp
        dp_grad = 2 * (p_dp - 1) / p_dp * payload
        assert led_on.bytes_for(axis="dp", op="psum") == dp_grad
        assert led_off.bytes_for(axis="dp", op="psum") == dp_grad
        # op count == bucket count (the tiny buffer forces one param
        # per bucket here), vs one op per parameter unbucketed
        nb = plan.num_buckets
        assert nb == len(eng_on.trainable)
        assert led_on.ops_for(axis="sharding", op="reduce_scatter") == nb
        assert led_on.ops_for(axis="dp", op="psum") == nb
        assert led_off.ops_for(axis="sharding", op="reduce_scatter") \
            == len(eng_off.trainable)
        # the folded grad-norm: ONE psum per signature group over
        # spec+zero axes, instead of one per parameter
        assert led_on.ops_for(axis="sharding", op="psum") \
            == len(plan.groups)

    def test_scan_trips_scales_ledger_and_survives_ablation(self):
        """A collective noted under scan_trips(nb) counts nb times —
        the bucket scan's exact accounting (plain scan bodies stay the
        documented once-counted lower bound)."""
        mesh = _mesh()
        nb = 4

        def prog(x):
            def tick(c, xt):
                return c + C.t_psum_scatter(
                    xt, ("mp",), scatter_dimension=0, tiled=True).sum(), \
                    None

            with cl.scan_trips(nb):
                out, _ = jax.lax.scan(tick, jnp.float32(0.0),
                                      x.reshape(nb, 16, 4))
            # an unmarked scan body still counts once (lower bound)
            def tick2(c, xt):
                return c + C.t_psum(xt, ("mp",)).sum(), None

            out2, _ = jax.lax.scan(tick2, jnp.float32(0.0),
                                   x.reshape(nb, 16, 4))
            return out + out2

        step = jax.jit(_shard_map(prog, mesh, (P(None, "mp"),), P()))
        x = jnp.ones((64, 32), jnp.float32)   # local shard [64, 4]
        with cl.capture() as led:
            step(x)
        tick_payload = 16 * 4 * F32
        assert led.ops_for(op="reduce_scatter") == nb
        assert led.bytes_for(op="reduce_scatter") == \
            nb * 7 / 8 * tick_payload
        assert led.ops_for(op="psum") == 1          # unmarked scan
        assert [r.trips for r in led.records] == [nb, 1]
        # the trip-scaled records replay trip-count times and the
        # ablated compile keeps shapes (the exposed-comm machinery
        # works unchanged over the bucket scan)
        rfn = cl.replay_callable(
            [r for r in led.records if r.op == "reduce_scatter"],
            mesh, _shard_map, jax.jit)
        assert float(rfn()) == 0.0
        with cl.ablate({"mp"}):
            abl = jax.jit(_shard_map(prog, mesh, (P(None, "mp"),),
                                     P()))(x)
        assert abl.shape == ()

    def test_trips_default_and_nesting(self):
        led = cl.CommLedger()
        cl._state.captures.append(led)
        try:
            cl.note("psum", ("dp",), (4,), np.dtype("float32"), 2)
            with cl.scan_trips(3):
                cl.note("psum", ("dp",), (4,), np.dtype("float32"), 2)
                with cl.scan_trips(2):
                    cl.note("psum", ("dp",), (4,), np.dtype("float32"),
                            2)
        finally:
            cl._state.captures.remove(led)
        assert [r.trips for r in led.records] == [1, 3, 6]
        assert led.ops_for(op="psum") == 10
        one = 2 * (2 - 1) / 2 * 16
        assert led.bytes_for(op="psum") == 10 * one
        assert led.totals()[("dp", "psum")]["ops"] == 10


# ---------------------------------------------------------------------------
# pipeline ring trips-exact accounting
# ---------------------------------------------------------------------------
class TestPipelineRingLedger:
    """The pp ring's per-tick ppermute rides _pipe_fn's lax.scan under
    ``scan_trips(E + S - 1)``: the ledger is trips-EXACT on the pp
    axis, pinned to the closed form trips x carry bytes. AD synthesizes
    the reverse ring outside the noting shim, so the forward schedule
    is the entire pp record set (the docstring caveat, asserted here)."""

    def test_ring_bytes_match_closed_form(self):
        from paddle_tpu.distributed import fleet
        from paddle_tpu.models import GPTForCausalLMPipe
        from paddle_tpu.models.gpt import GPTConfig

        S, V, M, sh = 2, 2, 2, 2        # pp, vpp, microbatches, sharding
        cfg = GPTConfig(vocab_size=128, hidden_size=32, num_layers=4,
                        num_heads=4, max_position_embeddings=32)
        strategy = fleet.DistributedStrategy()
        strategy.hybrid_configs = {
            "dp_degree": 1, "mp_degree": 2, "pp_degree": S,
            "sharding_degree": sh,
            "pp_configs": {"num_virtual_pipeline_stages": V}}
        strategy.pipeline_configs = {"accumulate_steps": M,
                                     "micro_batch_size": 2}
        fleet._fleet_state.update(initialized=False, hcg=None,
                                  strategy=None)
        fleet.init(is_collective=True, strategy=strategy)
        paddle.seed(0)
        model = GPTForCausalLMPipe(cfg)
        dm = fleet.distributed_model(model)
        opt = fleet.distributed_optimizer(
            paddle.optimizer.AdamW(learning_rate=1e-3,
                                   parameters=model.parameters()))
        r = np.random.RandomState(0)
        ids = r.randint(0, cfg.vocab_size, (8, 17))
        x, y = paddle.to_tensor(ids[:, :-1]), paddle.to_tensor(ids[:, 1:])
        float(dm.train_batch([x, y], opt))
        led = dm._engine.comm_ledger()
        pp_recs = [q for q in led.records
                   if q.op == "ppermute" and "pp" in q.axes]
        # ONE traced site, trips == E + S - 1 forward ticks
        assert len(pp_recs) == 1
        trips = V * M + S - 1
        assert pp_recs[0].trips == trips
        # carry payload: one microbatch of stage-boundary activations,
        # [B_local/M, seq, hidden] f32 (dp x sharding splits the batch)
        seq = ids.shape[1] - 1
        mb = ids.shape[0] // (1 * sh) // M
        payload = mb * seq * cfg.hidden_size * F32
        assert pp_recs[0].payload_bytes == payload
        # trips-exact totals: bytes == trips x payload (ppermute wire
        # == payload), ops counted once per tick
        assert led.bytes_for(axis="pp", op="ppermute") == trips * payload
        assert led.ops_for(axis="pp", op="ppermute") == trips
        # no reverse-ring record exists: the backward ppermute never
        # re-enters the noting shim (grad-norm psums etc. still cross
        # pp as part of wider axis groups — only the ring is pinned)
        assert [q.op for q in led.records if q.op == "ppermute"] \
            == ["ppermute"]


# ---------------------------------------------------------------------------
# ablation stand-ins
# ---------------------------------------------------------------------------
class TestAblation:
    def test_shape_and_dtype_parity(self):
        mesh = _mesh()

        def prog(x):
            a = C.t_psum(x, ("mp",))
            b = C.t_all_gather(x, ("mp",), axis=0)
            c = C.t_psum_scatter(b, ("mp",), scatter_dimension=0)
            d = C.t_all_to_all(b, ("mp",), split_axis=0, concat_axis=1)
            e = C.t_ppermute(x, ("mp",),
                             [(i, (i + 1) % 8) for i in range(8)])
            return a, b, c, d, e

        x = jnp.ones((16, 8), jnp.bfloat16)
        real = jax.jit(_shard_map(prog, mesh, (P("mp"),),
                                  (P("mp"), P(), P("mp"), P(),
                                   P("mp"))))(x)
        with cl.ablate({"mp"}):
            abl = jax.jit(_shard_map(prog, mesh, (P("mp"),),
                                     (P("mp"), P(), P("mp"), P(),
                                      P("mp"))))(x)
        for r, a in zip(real, abl):
            assert r.shape == a.shape and r.dtype == a.dtype

    def test_token_and_scoping(self):
        assert cl.ablation_token() is None
        with cl.ablate({"dp"}):
            assert cl.ablation_token() == frozenset({"dp"})
            assert cl.ablating("dp") and not cl.ablating("mp")
            with cl.ablate({"mp"}):
                assert cl.ablation_token() == frozenset({"dp", "mp"})
        assert cl.ablation_token() is None


# ---------------------------------------------------------------------------
# exposed-comm attribution
# ---------------------------------------------------------------------------
class TestExposedComm:
    def test_build_report_math(self):
        rep = cl.build_report(1.0, {"dp": 0.2, "mp": -0.05},
                              {"dp": 0.5, "mp": 0.1})
        assert rep.exposed_seconds == {"dp": 0.2, "mp": 0.0}
        assert rep.exposed_fraction["dp"] == pytest.approx(0.4)
        assert rep.exposed_fraction["mp"] == 0.0
        assert rep.grad_sync_exposed_seconds == pytest.approx(0.2)
        # exposed above replay: fraction clamps to 1
        rep2 = cl.build_report(1.0, {"sharding+dp": 0.4},
                               {"sharding+dp": 0.1})
        assert rep2.exposed_fraction["sharding+dp"] == 1.0
        assert rep2.grad_sync_exposed_seconds == pytest.approx(0.4)

    def test_profile_restores_state_and_cache(self, dp_engine):
        eng, step, batch, _ = dp_engine
        before_p = [np.asarray(p._value) for p in eng.params]
        before_sc = eng.optimizer._step_count
        c0 = eng.stats.compiles
        rep = eng.profile_exposed_comm(step, batch, repeats=2)
        assert set(rep.exposed_seconds) == {"dp"}
        assert 0.0 <= rep.exposed_fraction["dp"] <= 1.0
        assert rep.replay_seconds["dp"] > 0
        assert rep.step_seconds > 0
        # dp IS a grad-sync axis
        assert rep.grad_sync_exposed_seconds == \
            pytest.approx(rep.exposed_seconds["dp"])
        # engine state restored bit-exactly
        for b, p in zip(before_p, eng.params):
            assert (b == np.asarray(p._value)).all()
        assert eng.optimizer._step_count == before_sc
        # ablated replays are evicted from the cache; the next real
        # step reuses the original executable (and CompileStats never
        # saw the replays)
        assert eng.stats.compiles == c0
        assert all(k[-1] is None for k in eng._compiled)
        float(step(batch))
        assert eng.stats.compiles == c0
        # gauges published
        m = eng.metrics_snapshot()["metrics"]
        assert m["paddle_tpu_comm_exposed_fraction"]["series"][0][
            "labels"] == {"axis": "dp"}
        assert m["paddle_tpu_grad_sync_exposed_seconds"]["series"][0][
            "value"] == pytest.approx(rep.grad_sync_exposed_seconds)

    def test_pipeline_wrapper_requires_train_batch(self):
        from paddle_tpu.core.enforce import PreconditionNotMetError
        from paddle_tpu.distributed.fleet.meta_parallel import (
            pipeline_parallel as pp)

        class _Fake:
            _train_step = None

        with pytest.raises(PreconditionNotMetError):
            pp.PipelineParallel.profile_exposed_comm(_Fake(), [1, 2])


# ---------------------------------------------------------------------------
# serving request spans
# ---------------------------------------------------------------------------
@pytest.fixture(scope="module")
def span_engine():
    from paddle_tpu.inference import (Config, ServingEngine,
                                      create_predictor)
    from paddle_tpu.models.llama import LlamaForCausalLM, llama_tiny

    obs.reset_registry()
    paddle.seed(11)
    model = LlamaForCausalLM(llama_tiny())
    pred = create_predictor(
        Config().set_model(model).enable_paged_kv(page_size=8))
    eng = ServingEngine(pred, max_batch=2, decode_chunk=2)
    r = np.random.RandomState(0)
    V = model.config.vocab_size
    lens = [7, 12, 24, 9, 5]
    rids = [eng.submit(r.randint(1, V, (L,)), max_new_tokens=6)
            for L in lens]
    done = eng.run()
    return eng, rids, done


class TestServingSpans:
    def test_every_request_has_lifecycle_spans(self, span_engine):
        eng, rids, _ = span_engine
        traces = {t["rid"]: t for t in eng.request_traces()}
        assert set(traces) == set(rids)
        for t in traces.values():
            names = [s["name"] for s in t["spans"]]
            for stage in ("queued", "prefill", "decode", "e2e"):
                assert stage in names
            # the rounds are the engine's, kept once (eng.rounds), not a
            # span a request a round: the request names the ones it rode
            assert "decode_round" not in names
            dec = next(s for s in t["spans"] if s["name"] == "decode")
            kept = {r[0]: r for r in eng.rounds}
            first, last = (dec["meta"]["first_round"],
                           dec["meta"]["last_round"])
            assert first <= last and {first, last} <= set(kept)
            # 6 tokens: one from the prefill, five over rounds of 2
            assert last - first + 1 >= 3
            assert kept[first][1] >= dec["t0"] - 1e-3
            assert kept[last][2] <= dec["t1"] + 1e-3
            for s in t["spans"]:
                assert s["t1"] is not None and s["seconds"] >= 0
            e2e = next(s for s in t["spans"] if s["name"] == "e2e")
            assert e2e["seconds"] == max(
                s["seconds"] for s in t["spans"])
            assert t["meta"]["new_tokens"] == 6

    def test_stage_histogram_counts(self, span_engine):
        eng, rids, _ = span_engine
        m = eng.metrics_snapshot()["metrics"]
        rows = {s["labels"]["stage"]: s["count"]
                for s in m["paddle_tpu_serving_request_stage_seconds"]
                ["series"]}
        for stage in ("queued", "prefill", "decode", "e2e"):
            assert rows[stage] == len(rids)

    def test_chrome_trace_export(self, span_engine, tmp_path):
        eng, rids, _ = span_engine
        path = tmp_path / "trace.json"
        doc = eng.export_request_traces(str(path))
        back = json.load(open(path))
        assert back == doc
        evs = doc["traceEvents"]
        lanes = {e["tid"] for e in evs}
        assert set(rids) <= lanes
        xs = [e for e in evs if e["ph"] == "X"]
        assert all(e["ts"] >= 0 and e["dur"] >= 0 for e in xs)
        assert {"queued", "prefill", "decode", "decode_round",
                "e2e"} <= {e["name"] for e in xs}
        # lane names: one per request and ONE for the engine's rounds
        named = {e["tid"]: e["args"]["name"] for e in evs
                 if e["ph"] == "M"}
        engine = [tid for tid, n in named.items() if n == "engine"]
        assert len(engine) == 1 and engine[0] not in rids
        rounds = [e for e in xs if e["name"] == "decode_round"]
        assert {e["tid"] for e in rounds} == set(engine)
        assert [e["args"]["round"] for e in rounds] == \
            [r[0] for r in eng.rounds]
        assert all(1 <= e["args"]["rows"] <= 2 for e in rounds)

    def test_ring_is_bounded(self):
        ring = obs.SpanRing(maxlen=3)
        for i in range(7):
            tr = obs.RequestTrace(i)
            tr.add("e2e", 0.0, 1.0)
            ring.add(tr)
        assert len(ring) == 3
        assert [t["rid"] for t in ring.to_dicts()] == [4, 5, 6]

    def test_no_recompiles_with_spans_enabled(self, span_engine):
        eng, _, _ = span_engine
        # spans + ledger capture must not touch the program lattice
        c0 = eng.stats.compiles
        r = np.random.RandomState(3)
        eng.submit(r.randint(1, 64, (10,)), max_new_tokens=4)
        eng.run()
        assert eng.stats.compiles == c0


# ---------------------------------------------------------------------------
# /metrics HTTP exporter
# ---------------------------------------------------------------------------
class TestExporter:
    def test_scrape_round_trip(self):
        reg = obs.MetricsRegistry()
        reg.counter("scrape_tokens_total",
                    labelnames=("phase",)).inc(5, phase="decode")
        reg.gauge("scrape_depth").set(2)
        with obs.serve_metrics(0, registry=reg) as srv:
            assert srv.port > 0
            url = f"http://127.0.0.1:{srv.port}/metrics"
            body = urllib.request.urlopen(url, timeout=10).read()
            parsed = obs.parse_prometheus_text(body.decode())
            assert parsed["scrape_tokens_total"][
                (("phase", "decode"),)] == 5
            assert parsed["scrape_depth"][()] == 2
            with pytest.raises(Exception):
                urllib.request.urlopen(
                    f"http://127.0.0.1:{srv.port}/nope", timeout=10)

    def test_close_releases_port(self):
        reg = obs.MetricsRegistry()
        srv = obs.serve_metrics(0, registry=reg)
        port = srv.port
        srv.close()
        srv2 = obs.serve_metrics(port, registry=reg)   # rebindable
        assert srv2.port == port
        srv2.close()


# ---------------------------------------------------------------------------
# tpulint: the new modules must stay clean with ZERO baseline entries
# ---------------------------------------------------------------------------
def test_tpulint_commledger_surface_zero_baseline():
    repo = Path(__file__).resolve().parents[1]
    sys.path.insert(0, str(repo))
    try:
        from tools.tpulint import ALL_RULES, lint_paths

        findings = lint_paths(
            [repo / "paddle_tpu" / "observability"],
            ALL_RULES, root=repo)
    finally:
        sys.path.remove(str(repo))
    assert findings == [], [str(f) for f in findings]
