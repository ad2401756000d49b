"""Expert parallelism as a first-class hybrid axis.

Under test:
- 'ep' mesh axis: strategy/fleet plumbing, HCG degree/group/rank
  accessors, MoELayer defaulting to the ep group, custom-order guard
- gate correctness: GShard/Switch top-k dense dispatch parity vs a
  numpy reference (capacity overflow/drop behavior, tie handling)
- capacity-factor bucketing onto the core/bucketing lattice
- MoE-on-mesh loss/param parity <= 1e-5 vs the single-device
  dense-dispatch golden WITH capacity drops, 0 recompiles after warmup
- ep_async_dispatch: the fused dispatch->FFN->combine ppermute ring
  (collective_matmul.moe_a2a_ffn) is numerically identical to the
  unfused a2a path, fwd and bwd
- expert-load / drop-rate / aux-loss gauges through the compiled step
- moe_utils.global_scatter/global_gather: the named uniform-count
  error, and the gradient of the a2a round trip
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

import paddle_tpu as paddle
from paddle_tpu.distributed import collective as C
from paddle_tpu.distributed import fleet
from paddle_tpu.distributed.engine import ParallelEngine, _shard_map
from paddle_tpu.incubate.distributed.models.moe import MoELayer
from paddle_tpu.incubate.distributed.models.moe.moe_layer import \
    _topk_dispatch
from paddle_tpu.tensor import Tensor


def _init_ep(dp=2, ep=2, mp=2, moe_configs=None):
    strategy = fleet.DistributedStrategy()
    hc = {"dp_degree": dp, "ep_degree": ep, "mp_degree": mp}
    if moe_configs:
        hc["moe_configs"] = moe_configs
    strategy.hybrid_configs = hc
    return fleet.init(is_collective=True, strategy=strategy), strategy


# ---------------------------------------------------------------------------
# plumbing: strategy -> fleet.init -> HCG -> MoELayer
# ---------------------------------------------------------------------------
class TestEpPlumbing:
    def test_strategy_defaults(self):
        s = fleet.DistributedStrategy()
        assert s.hybrid_configs["ep_degree"] == 1
        assert s.hybrid_configs["moe_configs"]["ep_async_dispatch"] \
            is False
        assert "ep" in s.hybrid_configs["order"]
        # sub-config merge keeps unset keys at their defaults
        s.hybrid_configs = {"moe_configs": {}}
        assert s.hybrid_configs["moe_configs"]["ep_async_dispatch"] \
            is False

    def test_hcg_accessors_and_mesh(self):
        hcg, _ = _init_ep(dp=2, ep=2, mp=2)
        assert hcg.get_expert_parallel_world_size() == 2
        g = hcg.get_expert_parallel_group()
        assert g.axis_names == ("ep",) and g.nranks == 2
        assert hcg.mesh.shape["ep"] == 2
        assert "ep=2" in repr(hcg)

    def test_moe_layer_prefers_ep_group(self):
        hcg, _ = _init_ep(dp=2, ep=2, mp=2)
        paddle.seed(0)
        moe = MoELayer(8, d_hidden=16, num_experts=4)
        assert moe._group.axis_names == ("ep",)
        assert moe.world_size == 2
        # expert stack sharded over 'ep' on dim 0
        assert moe.w1.dist_attr == P(("ep",), None, None)

    def test_custom_order_without_ep_raises(self):
        from paddle_tpu.distributed.fleet.base.topology import \
            HybridCommunicateGroup

        with pytest.raises(ValueError, match="'ep' axis"):
            HybridCommunicateGroup(
                dp_degree=2, ep_degree=2,
                order=["dp", "pp", "sharding", "sep", "mp"])


# ---------------------------------------------------------------------------
# gate correctness vs a numpy reference
# ---------------------------------------------------------------------------
def _np_topk_dispatch(probs, k, cap):
    """Independent numpy re-derivation of the dense GShard dispatch."""
    T, E = probs.shape
    masks, gates = [], []
    remaining = probs.copy()
    for _ in range(k):
        idx = remaining.argmax(-1)
        m = np.zeros((T, E), probs.dtype)
        m[np.arange(T), idx] = 1.0
        masks.append(m)
        gates.append((probs * m).sum(-1))
        remaining = remaining * (1.0 - m)
    density = masks[0].mean(0)
    aux = float((density * probs.mean(0)).sum() * E)
    denom = sum(gates) + 1e-9
    combine = np.zeros((T, E, cap), probs.dtype)
    offset = np.zeros(E, probs.dtype)
    for m, gate in zip(masks, gates):
        pos = np.cumsum(m, axis=0) - m + offset[None, :]
        pos_t = (pos * m).sum(-1)
        keep = ((pos_t < cap) & (m.sum(-1) > 0)).astype(probs.dtype)
        gate_k = gate / denom * keep
        for t in range(T):
            if keep[t]:
                e = int(m[t].argmax())
                combine[t, e, int(pos_t[t])] += gate_k[t]
        offset = offset + m.sum(0)
    dispatch = (combine > 0).astype(probs.dtype)
    return combine, dispatch, aux


class TestGateNumpyParity:
    @pytest.mark.parametrize("k,cap", [(1, 3), (2, 4), (2, 64)])
    def test_topk_dispatch_matches_numpy(self, k, cap):
        r = np.random.RandomState(0)
        logits = r.randn(24, 6).astype("float32")
        probs = np.exp(logits) / np.exp(logits).sum(-1, keepdims=True)
        cj, dj, aj = _topk_dispatch(jnp.asarray(probs), k, cap)
        cn, dn, an = _np_topk_dispatch(probs, k, cap)
        np.testing.assert_allclose(np.asarray(cj), cn, rtol=1e-5,
                                   atol=1e-6)
        np.testing.assert_array_equal(np.asarray(dj) > 0, dn > 0)
        assert float(aj) == pytest.approx(an, rel=1e-5)

    def test_capacity_overflow_drops_in_arrival_order(self):
        # all tokens route to expert 0; cap=2 keeps the first two and
        # drops the rest (GShard queue position = cumulative count)
        probs = np.tile(np.asarray([[0.9, 0.1]], "float32"), (5, 1))
        combine, dispatch, _ = _topk_dispatch(jnp.asarray(probs), 1, 2)
        d = np.asarray(dispatch)
        assert d[:2, 0].sum() == 2          # first two tokens kept
        assert d[2:].sum() == 0             # later arrivals dropped
        # kept tokens occupy distinct capacity slots
        assert np.asarray(combine)[0, 0, 0] > 0
        assert np.asarray(combine)[1, 0, 1] > 0

    def test_tie_handling_matches_numpy_argmax(self):
        # exact ties pick the lowest expert index (argmax convention),
        # and the top-2 pick is the next tied expert, in both impls
        probs = np.asarray([[0.4, 0.4, 0.2],
                            [0.3, 0.3, 0.3]], "float32")
        cj, dj, _ = _topk_dispatch(jnp.asarray(probs), 2, 4)
        cn, dn, _ = _np_topk_dispatch(probs, 2, 4)
        np.testing.assert_array_equal(np.asarray(dj) > 0, dn > 0)
        d = np.asarray(dj)
        assert d[0, 0].sum() > 0 and d[0, 1].sum() > 0  # experts 0+1
        assert d[1, 0].sum() > 0 and d[1, 1].sum() > 0

    def test_switch_top1_is_k1(self):
        r = np.random.RandomState(1)
        probs = np.exp(r.randn(10, 4)).astype("float32")
        probs /= probs.sum(-1, keepdims=True)
        _, dispatch, _ = _topk_dispatch(jnp.asarray(probs), 1, 64)
        # top-1: each token occupies at most one (expert, slot)
        assert np.asarray(dispatch).sum(axis=(1, 2)).max() == 1


# ---------------------------------------------------------------------------
# capacity bucketing (core/bucketing lattice)
# ---------------------------------------------------------------------------
class TestCapacityBucketing:
    def test_caps_land_on_lattice(self):
        paddle.seed(0)
        moe = MoELayer(8, d_hidden=16, num_experts=8, gate="gshard",
                       group=False)
        caps = {T: moe._capacity(T) for T in range(8, 512, 8)}
        for T, cap in caps.items():
            assert cap <= T
            assert cap & (cap - 1) == 0, (T, cap)  # power of two
        # jittering T mints only a logarithmic number of capacities
        assert len(set(caps.values())) <= 8

    def test_naive_gate_keeps_full_capacity(self):
        paddle.seed(0)
        moe = MoELayer(8, d_hidden=16, num_experts=4, gate="naive",
                       group=False)
        assert moe._capacity(100) == 100   # no drops, no bucketing


# ---------------------------------------------------------------------------
# on-mesh parity vs the single-device dense-dispatch golden (WITH drops)
# ---------------------------------------------------------------------------
class TestMeshParity:
    def _losses(self, async_dispatch, steps=3):
        hcg, _ = _init_ep(dp=1, ep=4, mp=1, moe_configs={
            "ep_async_dispatch": async_dispatch})
        paddle.seed(7)
        d, h, E = 8, 16, 8
        model = MoELayer(d, d_hidden=h, num_experts=E, gate="gshard")
        # a tight capacity factor so the parity run actually drops
        # tokens (the gate asserts drop_rate > 0 below)
        model.gate.capacity_factor = 0.5
        assert model.world_size == 4
        state = {k: np.asarray(v._value)
                 for k, v in model.state_dict().items()}

        np.random.seed(3)
        x = np.random.randn(16, 4, d).astype("float32")
        y = np.random.randn(16, 4, d).astype("float32")

        def loss_fn(m, batch):
            out = m(batch["x"])
            return paddle.mean((out - batch["y"]) ** 2) \
                + 0.01 * m.aux_loss

        opt = paddle.optimizer.Adam(learning_rate=0.05,
                                    parameters=model.parameters())
        eng = ParallelEngine(model, opt, hcg.mesh)
        step = eng.train_step(loss_fn)
        batch = {"x": paddle.to_tensor(x), "y": paddle.to_tensor(y)}
        losses = [float(step(batch)) for _ in range(steps)]
        compiles_warm = eng.stats.compiles
        losses.append(float(step(batch)))
        # the acceptance gate: steady state is recompile-free
        assert eng.stats.compiles == compiles_warm
        params = {n: np.asarray(p._value)
                  for n, p in model.named_parameters()}
        return state, x, y, losses, params, eng

    def test_gshard_parity_with_drops(self):
        state, x, y, losses, params, eng = self._losses(False)

        # golden: the dense single-device MoE applied per batch SHARD
        # (same per-rank token count -> same capacity bucket -> the
        # same GShard queue/drop decisions), losses averaged like the
        # engine's pmean. Trained with plain Adam: its grads are the
        # mean over shards, exactly the engine's grad semantics.
        paddle.seed(7)
        golden = MoELayer(8, d_hidden=16, num_experts=8, gate="gshard",
                          group=False)
        golden.gate.capacity_factor = 0.5
        golden.set_state_dict({k: paddle.to_tensor(v)
                               for k, v in state.items()})
        g_opt = paddle.optimizer.Adam(learning_rate=0.05,
                                      parameters=golden.parameters())
        shards = 4
        Bl = x.shape[0] // shards

        g_losses = []
        for _ in range(len(losses)):
            total = None
            for i in range(shards):
                xb = paddle.to_tensor(x[i * Bl:(i + 1) * Bl])
                yb = paddle.to_tensor(y[i * Bl:(i + 1) * Bl])
                out = golden(xb)
                li = paddle.mean((out - yb) ** 2) \
                    + 0.01 * golden.aux_loss
                total = li if total is None else total + li
            total = total / shards
            total.backward()
            g_opt.step()
            g_opt.clear_grad()
            g_losses.append(float(total))

        np.testing.assert_allclose(losses, g_losses, rtol=1e-5,
                                   atol=1e-6)
        for n, pg in golden.named_parameters():
            np.testing.assert_allclose(params[n], np.asarray(pg._value),
                                       rtol=1e-5, atol=1e-5, err_msg=n)
        # the test must actually exercise capacity drops
        snap = eng.metrics_snapshot()["metrics"]
        drop = snap["paddle_tpu_moe_token_drop_rate"]["series"][0]
        assert drop["value"] > 0, "config did not drop any token"

    def test_async_dispatch_ring_matches_unfused(self):
        s0, x0, y0, l0, p0, _ = self._losses(False)
        s1, x1, y1, l1, p1, eng = self._losses(True)
        np.testing.assert_array_equal(x0, x1)
        for k in s0:
            np.testing.assert_array_equal(s0[k], s1[k])
        np.testing.assert_allclose(l0, l1, rtol=1e-6, atol=1e-7)
        for n in p0:
            np.testing.assert_allclose(p0[n], p1[n], rtol=1e-6,
                                       atol=1e-7, err_msg=n)
        # the fused program rides ppermute rings, not all_to_all
        led = eng.comm_ledger()
        assert led.ops_for(axis="ep", op="all_to_all") == 0
        assert led.ops_for(axis="ep", op="ppermute") > 0


# ---------------------------------------------------------------------------
# GPT-MoE end-to-end on the TP x EP x DP mesh
# ---------------------------------------------------------------------------
class TestGptMoeHybrid:
    def test_trains_with_ring_and_matches_golden_first_step(self):
        from paddle_tpu.models import (GPTForCausalLM,
                                       GPTPretrainingCriterion,
                                       gpt_moe_tiny)

        cfg = gpt_moe_tiny()
        # golden BEFORE fleet.init: plain layers, dense dispatch
        paddle.seed(0)
        golden = GPTForCausalLM(cfg)
        crit = GPTPretrainingCriterion(cfg)

        hcg, _ = _init_ep(dp=2, ep=2, mp=2,
                          moe_configs={"ep_async_dispatch": True})
        paddle.seed(0)
        model = GPTForCausalLM(cfg)
        opt = paddle.optimizer.AdamW(learning_rate=1e-3,
                                     parameters=model.parameters())
        eng = ParallelEngine(model, opt, hcg.mesh)

        def loss_fn(m, b):
            return crit(m(b["x"]), b["y"]) + m.aux_loss

        step = eng.train_step(loss_fn)
        r = np.random.RandomState(0)
        B, S = 8, 16
        ids = r.randint(0, cfg.vocab_size, (B, S + 1))
        x, y = ids[:, :-1], ids[:, 1:]
        batch = {"x": paddle.to_tensor(x), "y": paddle.to_tensor(y)}

        # golden loss = mean over the (dp x ep) batch shards of the
        # dense model's loss (same per-shard token count -> identical
        # capacity/drop decisions)
        shards, Bl = 4, B // 4
        g = np.mean([float(loss_fn(golden, {
            "x": paddle.to_tensor(x[i * Bl:(i + 1) * Bl]),
            "y": paddle.to_tensor(y[i * Bl:(i + 1) * Bl])}))
            for i in range(shards)])
        loss0 = float(step(batch))
        assert abs(loss0 - g) <= 1e-5, (loss0, g)

        compiles_warm = eng.stats.compiles
        losses = [float(step(batch)) for _ in range(3)]
        assert eng.stats.compiles == compiles_warm  # 0 recompiles
        assert losses[-1] < loss0                   # it trains
        # expert traffic rode the 'ep' axis (ring form)
        led = eng.comm_ledger()
        assert led.bytes_for(axis="ep", op="ppermute") > 0


# ---------------------------------------------------------------------------
# telemetry gauges through the compiled step
# ---------------------------------------------------------------------------
class TestMoeGauges:
    def test_gauges_present_and_schema_valid(self):
        import json

        from paddle_tpu import observability as obs
        from paddle_tpu.observability import catalog

        obs.reset_registry()
        hcg, _ = _init_ep(dp=2, ep=2, mp=2)
        paddle.seed(0)
        moe = MoELayer(8, d_hidden=16, num_experts=4, gate="gshard")
        opt = paddle.optimizer.Adam(learning_rate=0.01,
                                    parameters=moe.parameters())
        eng = ParallelEngine(moe, opt, hcg.mesh)
        step = eng.train_step(
            lambda m, b: paddle.mean(m(b["x"]) ** 2) + 0.01 * m.aux_loss)
        r = np.random.RandomState(0)
        batch = {"x": paddle.to_tensor(
            r.randn(8, 4, 8).astype("float32"))}
        float(step(batch))
        float(step(batch))
        snap = eng.metrics_snapshot()["metrics"]
        with open(catalog.SCHEMA_PATH) as f:
            schema = json.load(f)
        loads = snap["paddle_tpu_moe_expert_load"]["series"]
        assert {row["labels"]["expert"] for row in loads} == \
            {"0", "1", "2", "3"}
        assert sum(row["value"] for row in loads) == pytest.approx(1.0)
        for name in ("paddle_tpu_moe_expert_load",
                     "paddle_tpu_moe_token_drop_rate",
                     "paddle_tpu_moe_aux_loss"):
            assert name in schema
            for row in snap[name]["series"]:
                assert sorted(row["labels"]) == schema[name]["labels"]
        assert snap["paddle_tpu_moe_aux_loss"]["series"][0]["value"] > 0
        # the ledger publishes the ep axis into the comm counters
        assert eng._metrics["comm_bytes"].value(
            axis="ep", op="all_to_all") > 0


# ---------------------------------------------------------------------------
# moe_utils: uniform-count error + a2a round-trip gradient
# ---------------------------------------------------------------------------
class TestMoeUtils:
    def test_non_uniform_counts_error_is_actionable(self):
        from paddle_tpu.distributed.utils.moe_utils import global_scatter

        g = C.new_group(axis_names=("ep",), nranks=4, name="ep_err")
        x = paddle.to_tensor(np.zeros((8, 4), "float32"))
        with C.spmd_region():
            with pytest.raises(Exception) as ei:
                global_scatter(x, local_count=[3, 1, 2, 2], group=g)
        msg = str(ei.value)
        assert "non-uniform per-rank token counts" in msg
        assert "[3, 1, 2, 2]" in msg          # what was seen
        assert "uniform-slot" in msg          # what the layout requires
        assert "capacity" in msg and "MoELayer" in msg  # the fix

    def test_uniform_and_none_counts_pass(self):
        from paddle_tpu.distributed.utils.moe_utils import _check_uniform

        _check_uniform(None, 4, "global_scatter")
        _check_uniform([2, 2, 2, 2], 4, "global_scatter")
        _check_uniform(paddle.to_tensor(np.asarray([5, 5])), 2,
                       "global_gather")

    def test_roundtrip_grad_is_identity(self):
        """grad of global_gather(global_scatter(x)) == grad without the
        a2a pair: the round trip is the identity permutation, and the
        recorded backward is the reverse a2a pair."""
        from paddle_tpu.distributed.utils.moe_utils import (
            global_gather, global_scatter)

        mesh = Mesh(np.array(jax.devices()[:8]).reshape(8), ("ep",))
        g = C.new_group(axis_names=("ep",), nranks=8, name="ep_rt")
        E, Cap, d = 8, 2, 4
        r = np.random.RandomState(0)
        xv = jnp.asarray(r.randn(E, Cap, d), jnp.float32)
        wv = jnp.asarray(r.randn(d), jnp.float32)

        def f(xv, wv, roundtrip):
            with C.spmd_region():
                x = Tensor(xv, stop_gradient=False)
                w = Tensor(wv, stop_gradient=False)
                h = x * w
                if roundtrip:
                    h = global_scatter(h, group=g)
                    h = global_gather(h, group=g)
                loss = paddle.mean(h * h)
                loss.backward()
                return loss._value, x.grad._value, w.grad._value

        rt = jax.jit(_shard_map(lambda a, b: f(a, b, True), mesh,
                                (P(), P()), (P(), P(), P())))
        plain = jax.jit(_shard_map(lambda a, b: f(a, b, False), mesh,
                                   (P(), P()), (P(), P(), P())))
        lr, gxr, gwr = rt(xv, wv)
        lp, gxp, gwp = plain(xv, wv)
        assert float(lr) == pytest.approx(float(lp), rel=1e-6)
        np.testing.assert_allclose(np.asarray(gxr), np.asarray(gxp),
                                   rtol=1e-6, atol=1e-7)
        np.testing.assert_allclose(np.asarray(gwr), np.asarray(gwp),
                                   rtol=1e-6, atol=1e-7)

    def test_roundtrip_values_2d_form(self):
        """[E*C, d] squeeze form round-trips to the identity too."""
        from paddle_tpu.distributed.utils.moe_utils import (
            global_gather, global_scatter)

        mesh = Mesh(np.array(jax.devices()[:8]).reshape(8), ("ep",))
        g = C.new_group(axis_names=("ep",), nranks=8, name="ep_rt2")
        r = np.random.RandomState(1)
        xv = jnp.asarray(r.randn(16, 4), jnp.float32)

        def f(xv):
            with C.spmd_region():
                x = Tensor(xv, stop_gradient=True)
                return global_gather(global_scatter(x, group=g),
                                     group=g)._value

        out = jax.jit(_shard_map(f, mesh, (P(),), P()))(xv)
        np.testing.assert_allclose(np.asarray(out), np.asarray(xv),
                                   rtol=1e-6)
