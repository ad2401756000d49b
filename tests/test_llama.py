"""Llama family: training (TP/engine) + compiled KV-cache generation.

Generation correctness standard: greedy decode with caches must emit the
same tokens as repeated full forwards (the reference validates its fused
decoder against the unfused path the same way)."""
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.distributed import fleet
from paddle_tpu.distributed.engine import ParallelEngine
from paddle_tpu.models import (LlamaForCausalLM, LlamaPretrainingCriterion,
                               llama_tiny)


def test_llama_forward_and_train_eager():
    cfg = llama_tiny()
    paddle.seed(0)
    model = LlamaForCausalLM(cfg)
    crit = LlamaPretrainingCriterion(cfg)
    opt = paddle.optimizer.AdamW(learning_rate=3e-3,
                                 parameters=model.parameters())
    ids = np.random.RandomState(0).randint(0, cfg.vocab_size, (2, 16))
    losses = []
    for _ in range(8):
        loss = crit(model(paddle.to_tensor(ids)), paddle.to_tensor(ids))
        loss.backward()
        opt.step()
        opt.clear_grad()
        losses.append(float(loss))
    assert losses[-1] < losses[0] - 1.0, losses


def test_llama_gqa_heads():
    cfg = llama_tiny()
    assert cfg.num_kv_heads == 2 and cfg.num_heads == 4
    model = LlamaForCausalLM(cfg)
    out = model(paddle.to_tensor(
        np.random.RandomState(1).randint(0, cfg.vocab_size, (2, 8))))
    assert out.shape == [2, 8, cfg.vocab_size]


def test_gqa_broadcast_matches_repeated_kv():
    """The no-copy GQA paths (broadcast q over [KV, rep]) match the
    materialized repeat_interleave reference exactly — fwd + grad for
    the training attention, fwd for the ragged decode cache path."""
    import jax.numpy as jnp

    import jax
    from paddle_tpu.ops.attention import \
        flash_attention_xla as flash_attention
    from paddle_tpu.ops.pallas.decode_attention import _dense_ragged

    r = np.random.RandomState(3)
    B, S, H, KV, D = 2, 8, 4, 2, 16
    q = jnp.asarray(r.randn(B, S, H, D), jnp.float32)
    k = jnp.asarray(r.randn(B, S, KV, D), jnp.float32)
    v = jnp.asarray(r.randn(B, S, KV, D), jnp.float32)

    def rep(t):
        return jnp.repeat(t, H // KV, axis=2)

    fwd = flash_attention.raw(q, k, v, causal=True)
    ref = flash_attention.raw(q, rep(k), rep(v), causal=True)
    np.testing.assert_allclose(np.asarray(fwd), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)
    gk = jax.grad(lambda kk: flash_attention.raw(
        q, kk, v, causal=True).sum())(k)
    gk_ref = jax.grad(lambda kk: flash_attention.raw(
        q, rep(kk), rep(v), causal=True).sum())(k)
    np.testing.assert_allclose(np.asarray(gk), np.asarray(gk_ref),
                               rtol=1e-5, atol=1e-5)

    # decode cache path: head-major [B, KV, M, D] caches, ragged offsets
    M = 32
    qd = jnp.asarray(r.randn(B, 1, H, D), jnp.float32)
    kc = jnp.asarray(r.randn(B, KV, M, D), jnp.float32)
    vc = jnp.asarray(r.randn(B, KV, M, D), jnp.float32)
    lens = jnp.asarray([20, 7], jnp.int32)
    out = _dense_ragged(qd, kc, vc, lens)
    ref = _dense_ragged(qd, jnp.repeat(kc, H // KV, axis=1),
                        jnp.repeat(vc, H // KV, axis=1), lens)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


def test_llama_tp_engine_parity():
    """mp=2 tensor-parallel Llama (GQA kv=2 shards 1 kv head/rank)
    matches single-device training."""
    strategy = fleet.DistributedStrategy()
    strategy.hybrid_configs = {"dp_degree": 4, "mp_degree": 2,
                               "pp_degree": 1}
    hcg = fleet.init(is_collective=True, strategy=strategy)

    cfg = llama_tiny()
    paddle.seed(5)
    model = LlamaForCausalLM(cfg)
    golden = LlamaForCausalLM(cfg)
    golden.set_state_dict(model.state_dict())
    crit = LlamaPretrainingCriterion(cfg)

    ids = np.random.RandomState(2).randint(0, cfg.vocab_size, (4, 16))

    g_opt = paddle.optimizer.AdamW(learning_rate=1e-3,
                                   parameters=golden.parameters())
    g_losses = []
    for _ in range(2):
        loss = crit(golden(paddle.to_tensor(ids)), paddle.to_tensor(ids))
        loss.backward()
        g_opt.step()
        g_opt.clear_grad()
        g_losses.append(float(loss))

    opt = paddle.optimizer.AdamW(learning_rate=1e-3,
                                 parameters=model.parameters())
    eng = ParallelEngine(model, opt, hcg.mesh)
    step = eng.train_step(lambda m, b: crit(m(b["x"]), b["y"]))
    for i in range(2):
        loss = step({"x": paddle.to_tensor(ids), "y": paddle.to_tensor(ids)})
        np.testing.assert_allclose(float(loss), g_losses[i], rtol=2e-4,
                                   atol=1e-6, err_msg=f"step {i}")


def test_generate_matches_full_forward():
    """Greedy cache decode == greedy argmax over repeated full forwards."""
    cfg = llama_tiny()
    paddle.seed(7)
    model = LlamaForCausalLM(cfg)
    model.eval()
    prompt = np.random.RandomState(3).randint(0, cfg.vocab_size, (2, 5))

    out = model.generate(paddle.to_tensor(prompt), max_new_tokens=6)
    gen = np.asarray(out._value)
    assert gen.shape == (2, 11)
    np.testing.assert_array_equal(gen[:, :5], prompt)

    # reference: re-run the full (uncached) forward each step
    cur = prompt
    from paddle_tpu.autograd import no_grad

    with no_grad():
        for _ in range(6):
            logits = model(paddle.to_tensor(cur))
            nxt = np.asarray(logits._value)[:, -1].argmax(-1)
            cur = np.concatenate([cur, nxt[:, None]], axis=1)
    np.testing.assert_array_equal(gen, cur)


def test_generate_sampling_runs():
    cfg = llama_tiny()
    paddle.seed(9)
    model = LlamaForCausalLM(cfg)
    model.eval()
    prompt = np.random.RandomState(4).randint(0, cfg.vocab_size, (1, 4))
    out = model.generate(paddle.to_tensor(prompt), max_new_tokens=5,
                         temperature=0.8, top_k=10, seed=1)
    assert out.shape == [1, 9]
    assert np.all(np.asarray(out._value) < cfg.vocab_size)


def test_decode_program_reuse():
    """The decode step compiles once and is reused (two cache keys total:
    prefill + decode)."""
    cfg = llama_tiny()
    model = LlamaForCausalLM(cfg)
    model.eval()
    prompt = np.zeros((1, 4), dtype="int64")
    model.generate(paddle.to_tensor(prompt), max_new_tokens=8)
    assert len(model._decode_fns) == 2
