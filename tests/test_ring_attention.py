"""Ring attention / context parallelism ('sep') tests.

The reference has no CP/ring attention (SURVEY.md §2.4) — this is the
planned superset feature; parity is checked against plain attention."""
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.distributed import fleet
from paddle_tpu.distributed.engine import ParallelEngine
from paddle_tpu.ops.attention import flash_attention
from paddle_tpu.ops.ring_attention import ring_attention, \
    ring_flash_attention

pytestmark = pytest.mark.slow  # multi-process / long-convergence; quick suite = -m 'not slow'


def test_ring_equals_flash_single_device():
    """axes=() ring (one block) reproduces plain causal attention."""
    rng = np.random.RandomState(0)
    B, S, H, D = 2, 16, 4, 8
    q = paddle.to_tensor(rng.randn(B, S, H, D).astype("float32"))
    k = paddle.to_tensor(rng.randn(B, S, H, D).astype("float32"))
    v = paddle.to_tensor(rng.randn(B, S, H, D).astype("float32"))
    out_r = ring_flash_attention(q, k, v, axes=(), causal=True)
    out_f = flash_attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out_r._value),
                               np.asarray(out_f._value), rtol=1e-5,
                               atol=1e-5)


def test_ring_attention_sep_parity():
    """sep=4 ring attention == full attention on the gathered sequence."""
    strategy = fleet.DistributedStrategy()
    strategy.hybrid_configs = {"dp_degree": 2, "sep_degree": 4,
                               "mp_degree": 1, "pp_degree": 1}
    hcg = fleet.init(is_collective=True, strategy=strategy)

    rng = np.random.RandomState(1)
    B, S, H, D = 2, 32, 4, 8
    qkv = [rng.randn(B, S, H, D).astype("float32") for _ in range(3)]
    golden = flash_attention(*[paddle.to_tensor(a) for a in qkv],
                             causal=True)

    import jax
    from jax import lax
    from jax.sharding import PartitionSpec as P

    from paddle_tpu.distributed import collective as C

    def run(q, k, v):
        with C.spmd_region():
            # shard seq over sep, run the ring, gather back
            outs = []
            idx = C.axis_index(("sep",))
            loc = S // 4
            ql, kl, vl = (lax.dynamic_slice_in_dim(a, idx * loc, loc, 1)
                          for a in (q, k, v))
            o = ring_flash_attention(
                paddle.Tensor(ql), paddle.Tensor(kl), paddle.Tensor(vl),
                axes=("sep",), causal=True)
            return lax.all_gather(o._value, "sep", axis=1, tiled=True)

    f = jax.shard_map(run, mesh=hcg.mesh, in_specs=(P(), P(), P()),
                      out_specs=P(), check_vma=False)
    out = jax.jit(f)(*qkv)
    np.testing.assert_allclose(np.asarray(out), np.asarray(golden._value),
                               rtol=1e-4, atol=1e-5)


def test_gpt_context_parallel_parity():
    """GPT with sep=4 context parallelism matches single-device training
    losses (exact ring attention + block position offsets)."""
    strategy = fleet.DistributedStrategy()
    strategy.hybrid_configs = {"dp_degree": 2, "sep_degree": 4,
                               "mp_degree": 1, "pp_degree": 1}
    hcg = fleet.init(is_collective=True, strategy=strategy)

    from paddle_tpu.models import (GPTForCausalLM, GPTPretrainingCriterion,
                                   gpt_tiny)

    cfg = gpt_tiny()
    paddle.seed(21)
    model = GPTForCausalLM(cfg)
    golden = GPTForCausalLM(cfg)
    golden.set_state_dict(model.state_dict())
    crit = GPTPretrainingCriterion(cfg)

    ids = np.random.RandomState(2).randint(0, cfg.vocab_size, (4, 32))

    g_opt = paddle.optimizer.AdamW(learning_rate=1e-3,
                                   parameters=golden.parameters())
    g_losses = []
    for _ in range(3):
        loss = crit(golden(paddle.to_tensor(ids)), paddle.to_tensor(ids))
        loss.backward()
        g_opt.step()
        g_opt.clear_grad()
        g_losses.append(float(loss))

    opt = paddle.optimizer.AdamW(learning_rate=1e-3,
                                 parameters=model.parameters())
    eng = ParallelEngine(model, opt, hcg.mesh)
    step = eng.train_step(lambda m, b: crit(m(b["x"]), b["y"]))
    for i in range(3):
        loss = step({"x": paddle.to_tensor(ids), "y": paddle.to_tensor(ids)})
        np.testing.assert_allclose(float(loss), g_losses[i], rtol=2e-4,
                                   atol=1e-6, err_msg=f"step {i}")


def test_masked_loss_unbalanced_split_parity():
    """Masked LM loss with wildly unbalanced mask across dp ranks must
    equal the single-device masked mean (global num/den, not
    mean-of-local-means)."""
    strategy = fleet.DistributedStrategy()
    strategy.hybrid_configs = {"dp_degree": 8, "mp_degree": 1,
                               "pp_degree": 1}
    hcg = fleet.init(is_collective=True, strategy=strategy)

    from paddle_tpu.models import (GPTForCausalLM, GPTPretrainingCriterion,
                                   gpt_tiny)

    cfg = gpt_tiny()
    paddle.seed(31)
    model = GPTForCausalLM(cfg)
    golden = GPTForCausalLM(cfg)
    golden.set_state_dict(model.state_dict())
    crit = GPTPretrainingCriterion(cfg)

    rng = np.random.RandomState(5)
    ids = rng.randint(0, cfg.vocab_size, (8, 16))
    mask = np.zeros((8, 16), dtype="float32")
    mask[0, :] = 1.0          # almost all valid tokens on rank 0
    mask[1:, 0] = 1.0         # one valid token on each other rank

    g_loss = crit(golden(paddle.to_tensor(ids)), paddle.to_tensor(ids),
                  paddle.to_tensor(mask))

    opt = paddle.optimizer.SGD(learning_rate=0.0,
                               parameters=model.parameters())
    eng = ParallelEngine(model, opt, hcg.mesh)
    step = eng.train_step(
        lambda m, b: crit(m(b["x"]), b["y"], b["mask"]))
    loss = step({"x": paddle.to_tensor(ids), "y": paddle.to_tensor(ids),
                 "mask": paddle.to_tensor(mask)})
    np.testing.assert_allclose(float(loss), float(g_loss), rtol=1e-4,
                               atol=1e-6)
