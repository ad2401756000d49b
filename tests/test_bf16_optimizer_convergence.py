"""bf16 optimizer-moment convergence guard (VERDICT item 10).

The train cells (benchmarks/, chip_smoke.py) train with AdamW moments
stored bfloat16 (state_dtype="bfloat16", re-quantized every step; update
math stays f32 —
optimizer/__init__.py _cast_state_in). This guards that the loss curve
stays inside a tolerance band of f32 moments over 200 steps — if this
ever fails, flip the cells' state_dtype or add stochastic rounding."""
import pytest
import numpy as np

import paddle_tpu as paddle
from paddle_tpu.distributed import fleet
from paddle_tpu.distributed.engine import ParallelEngine
from paddle_tpu.models import (GPTConfig, GPTForCausalLM,
                               GPTPretrainingCriterion)

pytestmark = pytest.mark.slow  # multi-process / long-convergence; quick suite = -m 'not slow'


def _run(state_dtype, steps=200):
    strategy = fleet.DistributedStrategy()
    strategy.hybrid_configs = {"dp_degree": 1, "mp_degree": 1}
    hcg = fleet.init(is_collective=True, strategy=strategy)
    cfg = GPTConfig(vocab_size=128, hidden_size=32, num_layers=2,
                    num_heads=2, max_position_embeddings=32)
    paddle.seed(123)
    model = GPTForCausalLM(cfg)
    crit = GPTPretrainingCriterion(cfg)
    opt = paddle.optimizer.AdamW(learning_rate=3e-3,
                                 parameters=model.parameters(),
                                 state_dtype=state_dtype)
    eng = ParallelEngine(model, opt, hcg.mesh)
    step = eng.train_step(lambda m, b: crit(m(b["x"]), b["y"]))
    r = np.random.RandomState(0)
    data = r.randint(0, cfg.vocab_size, (4, 17))
    batch = {"x": paddle.to_tensor(data[:, :-1]),
             "y": paddle.to_tensor(data[:, 1:])}
    return [float(step(batch)) for _ in range(steps)]


def test_bf16_moments_track_f32_loss_curve():
    f32 = _run(None)
    bf16 = _run("bfloat16")
    f32 = np.asarray(f32)
    bf16 = np.asarray(bf16)
    # same qualitative optimization: both must reach a deep overfit
    assert f32[-1] < 0.1 * f32[0]
    assert bf16[-1] < 0.1 * bf16[0], (f32[-1], bf16[-1])
    # and the curves stay inside a band: mean abs gap bounded relative
    # to the overall loss drop (bf16 moment noise must not change the
    # trajectory class)
    drop = f32[0] - f32[-1]
    gap = np.abs(f32 - bf16).mean()
    assert gap < 0.05 * drop, (gap, drop)
    # terminal quality within 15% of the f32 drop
    assert abs(f32[-1] - bf16[-1]) < 0.15 * drop, (f32[-1], bf16[-1])
