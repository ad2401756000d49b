"""A prompt's own attention at offset 0 as flash attention over the K/V
the layer has just written (PR 43): the kernel against the pool's dense
path, the rule that picks the form, the engine's record of it, and the
programs the CPU still lowers.

Kernels run in the Pallas interpreter (``interpret=True`` is an argument
the tests pass; no kernel picks the mode for itself).
"""
import hashlib
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.inference import Config, ServingEngine, create_predictor
from paddle_tpu.models import llama
from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM, llama_tiny
from paddle_tpu.ops.pallas import decode_attention as da
from paddle_tpu.ops.pallas import flash_attention as fa
from paddle_tpu.ops.pallas import rms_norm


def _rand(r, *shape, dtype=jnp.float32):
    return jnp.asarray(r.randn(*shape), dtype)


# ---------------------------------------------------------------------------
# (a) fresh K/V at offset 0 == the dense path over the pool after the write
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("S,L,H,KV,npages,dtype", [
    (256, 200, 8, 2, 3, jnp.float32),      # GQA 4:1, a table wider than S
    (512, 300, 4, 1, 6, jnp.float32),      # four blocks of 128 a row block
    (256, 256, 4, 4, 2, jnp.float32),      # MHA, a full bucket
    (256, 131, 8, 2, 3, jnp.bfloat16),
])
def test_fresh_kv_equals_dense_over_the_pool(S, L, H, KV, npages, dtype):
    page, D, P, B = 128, 128, 16, 2
    r = np.random.RandomState(S + L)
    q = _rand(r, B, S, H, D, dtype=dtype)
    k = _rand(r, B, S, KV, D, dtype=dtype)
    v = _rand(r, B, S, KV, D, dtype=dtype)
    # what an earlier request left in the pool must not be seen
    k_pool = _rand(r, P, KV, page, D, dtype=dtype) * 50
    v_pool = _rand(r, P, KV, page, D, dtype=dtype) * 50
    tables = jnp.asarray(r.permutation(P - 1)[:B * npages].reshape(
        B, npages), jnp.int32)
    k_pool, v_pool = da.paged_kv_write(k_pool, v_pool, k, v, tables, 0)
    want = da.paged_attention_dense(q, k_pool, v_pool, tables,
                                    jnp.zeros((B,), jnp.int32))
    got = fa.flash_attention_gqa(q, k, v, block=128, interpret=True)
    tol = 2e-5 if dtype == jnp.float32 else 2e-2
    # rows past the prompt's end are padding: what they read is not
    # compared, and no real row can see them under the causal mask
    np.testing.assert_allclose(np.asarray(got[:, :L], np.float32),
                               np.asarray(want[:, :L], np.float32),
                               atol=tol, rtol=tol)


def test_a_block_is_the_bucket_by_default():
    r = np.random.RandomState(3)
    q, k = _rand(r, 1, 384, 4, 128), _rand(r, 1, 384, 2, 128)
    v = _rand(r, 1, 384, 2, 128)
    one = fa.flash_attention_gqa(q, k, v, interpret=True)       # 128 x 3
    kk, vv = (jnp.repeat(a, 2, axis=2) for a in (k, v))
    two = fa.flash_attention_fwd(q, kk, vv, True, None, True)
    np.testing.assert_allclose(np.asarray(one), np.asarray(two),
                               atol=2e-5, rtol=2e-5)


# ---------------------------------------------------------------------------
# (a') under a sliding window (PR 46): the lax blocks' rule, block for block
# ---------------------------------------------------------------------------
def _against_the_lax_blocks(B, S, H, KV, window, dtype=jnp.float32):
    from paddle_tpu.ops.blockwise_attention import (
        blockwise_causal_attention)

    r = np.random.RandomState(S + H + (window or 0))
    q = _rand(r, B, S, H, 128, dtype=dtype)
    k = _rand(r, B, S, KV, 128, dtype=dtype)
    v = _rand(r, B, S, KV, 128, dtype=dtype)
    got = fa.flash_attention_gqa(q, k, v, scale=0.11, window=window,
                                 block=128, interpret=True)
    want = blockwise_causal_attention(q, k, v, 0.11, window, None, 128)
    tol = 2e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               atol=tol, rtol=tol)


@pytest.mark.parametrize("group", [1, 4, 8])
@pytest.mark.parametrize("window", [
    None,
    256,        # whole blocks: the far pair is half dropped
    300,        # no multiple of the block: two far pairs a row block
    24,         # under a block: the diagonal is a far pair too
    128,        # one block: every pair under the diagonal is far
    512,        # drops nothing of 512 rows
])
def test_window_is_the_lax_blocks_window(window, group):
    _against_the_lax_blocks(1, 512, group * (2 if group < 8 else 1),
                            2 if group < 8 else 1, window)


@pytest.mark.parametrize("B,S,H,KV,window,dtype", [
    (2, 384, 8, 2, 200, jnp.float32),      # two prompts a call
    (2, 256, 8, 1, 1, jnp.float32),        # a row sees itself alone
    (1, 384, 8, 1, 257, jnp.bfloat16),     # the served type
])
def test_window_with_a_batch_and_in_bfloat16(B, S, H, KV, window, dtype):
    _against_the_lax_blocks(B, S, H, KV, window, dtype)


def _pairs(S, block, window):
    qi, kj = fa._gqa_pairs(S // block, block, window)
    return list(zip(qi.tolist(), kj.tolist()))


def test_tables_hold_the_pairs_with_a_visible_key_and_no_other():
    # no window: the lower triangle, as np.tril_indices gives it
    for n in (1, 4, 16):
        qi, kj = fa._gqa_pairs(n, 512, None)
        assert len(qi) == n * (n + 1) // 2
        want = np.tril_indices(n)
        assert (qi == want[0]).all() and (kj == want[1]).all()
    # trinity's window over blocks of 512: at most 5 pairs a row block
    assert [len(_pairs(S, 512, 2048)) for S in (8192, 4096, 2048, 512)] \
        == [70, 30, 10, 1]
    assert _pairs(2048, 512, 2048) == _pairs(2048, 512, None)
    for S, block, window in ((8192, 512, 2048), (1024, 128, 300),
                             (512, 128, 24), (512, 128, 128),
                             (1024, 256, 1)):
        pairs = _pairs(S, block, window)
        t, s = np.tril_indices(S)
        seen = t - s < window
        want = sorted({(a // block, b // block)
                       for a, b in zip(t[seen], s[seen])})
        assert pairs == want, (S, block, window)
        # a row block's pairs lie together, first key block first, the
        # diagonal last: the kernel's first and last steps
        rows = [a for a, _ in pairs]
        assert rows == sorted(rows)
        for i in set(rows):
            mine = [b for a, b in pairs if a == i]
            assert mine[-1] == i
            assert mine[0] == max(i * block - window + 1, 0) // block


# sha256 of the jaxpr (the kernel's body in it) of the call with no
# window, read from the parent commit (c61e29a) by this very code
GQA_NO_WINDOW = {
    ((1, 2048, 32, 128), 8):
        "8ec062c2101c3c8adb15853858f9be3b2fb1303f3cf8e8fc482199cdc16d8f5f",
    ((2, 1024, 32, 128), 4):
        "6de1f6d49b3dd86facea1f7b0d8fe49d62239d1b969316a276f31d951d4df2c8",
}


def _gqa_jaxpr(q_shape, KV, **kw):
    q = jax.ShapeDtypeStruct(q_shape, jnp.bfloat16)
    k = jax.ShapeDtypeStruct(q_shape[:2] + (KV, q_shape[3]), jnp.bfloat16)
    return jax.make_jaxpr(partial(fa.flash_attention_gqa, scale=0.088,
                                  **kw))(q, k, k)


@pytest.mark.parametrize("q_shape,KV", sorted(GQA_NO_WINDOW))
def test_no_window_is_the_kernel_it_was(q_shape, KV):
    """Tables, grid and step bodies of the Llama cells' call: the text
    of its jaxpr is the parent's, and a window that drops nothing traces
    to the same (the static argument apart)."""
    from test_flash_grad_kernel import _pallas_calls

    jaxpr = _gqa_jaxpr(q_shape, KV)
    assert hashlib.sha256(str(jaxpr).encode()).hexdigest() \
        == GQA_NO_WINDOW[(q_shape, KV)]
    B, S, H, _ = q_shape
    n = S // 512

    def grids(j):
        out = []
        for eqn in j.eqns:
            if eqn.primitive.name == "pallas_call":
                out.append(tuple(eqn.params["grid_mapping"].grid))
            for val in eqn.params.values():
                sub = getattr(val, "jaxpr", val)
                if hasattr(sub, "eqns"):
                    out += grids(sub)
        return out

    assert grids(jaxpr.jaxpr) == [(B, H // min(H // KV, 8),
                                   n * (n + 1) // 2)]
    wide = _gqa_jaxpr(q_shape, KV, window=S)
    assert _pallas_calls(wide.jaxpr) == {"flash_attention_fwd_gqa": 1}
    assert str(wide) == str(jaxpr)
    under = _gqa_jaxpr(q_shape, KV, window=S - 1)       # one key dropped
    assert grids(under.jaxpr) == grids(jaxpr.jaxpr)     # the same pairs,
    assert str(under).count("select_n") > str(jaxpr).count("select_n")


@pytest.mark.parametrize("q,kv,ok", [
    ((1, 2048, 32, 128), (1, 2048, 8, 128), True),
    ((2, 128, 4, 128), (2, 128, 4, 128), True),
    ((1, 64, 32, 128), (1, 64, 8, 128), False),       # under a block
    ((1, 1, 32, 128), (1, 1, 8, 128), False),         # a decode step
    ((1, 256, 32, 64), (1, 256, 8, 64), False),       # half the lanes
    ((1, 256, 32, 128), (1, 512, 8, 128), False),     # not self-attention
    ((1, 256, 6, 128), (1, 256, 4, 128), False),      # no whole groups
    ((1, 200, 8, 128), (1, 200, 2, 128), False),      # no block of 128
])
def test_gate(q, kv, ok):
    assert fa.flash_gqa_supported(q, kv) is ok


# ---------------------------------------------------------------------------
# (b) the rule, as a table
# ---------------------------------------------------------------------------
def _form(S, offset, valid=None, H=32, KV=8, fresh=True):
    q = (1, S, H, 128)
    return da.paged_attention_form(
        q, (32, KV, 128, 128), (1, S, KV, 128) if fresh else None,
        offset, valid)


def _traced(fn):
    """``fn`` of a scalar that is a tracer, as a jitted program sees its
    offset; the (static) result."""
    out = []
    jax.eval_shape(lambda o: out.append(fn(o)) or o, jnp.int32(0))
    return out[0]


@pytest.mark.parametrize("name,got,want", [
    ("the long-prompt buckets, where the paged gate refuses",
     lambda: [_form(S, 0) for S in (1024, 2048)], ["flash", "flash"]),
    ("a numpy 0 is as concrete as a Python one",
     lambda: _form(2048, np.int32(0)), "flash"),
    ("the first bucket over the threshold, the paged gate's own too",
     lambda: _form(2 * da.FLASH_OVER_ROWS, 0, H=8, KV=8), "flash"),
    ("at the threshold and under it the paged kernel keeps its shapes",
     lambda: [_form(S, 0) for S in (64, 128, da.FLASH_OVER_ROWS)],
     ["paged"] * 3),
    ("under the threshold, but the paged gate refuses (32 heads a group)",
     lambda: _form(128, 0, H=32, KV=1), "flash"),
    ("a traced offset: the cache may hold older rows",
     lambda: _traced(lambda o: _form(2048, o)), "dense"),
    ("a traced offset under the paged gate",
     lambda: _traced(lambda o: _form(256, o)), "paged"),
    ("per-row offsets", lambda: _form(2048, jnp.zeros((1,), jnp.int32)),
     "dense"),
    ("a concrete offset that is not 0", lambda: _form(2048, 128), "dense"),
    ("`valid` given: a chunk of a longer prompt",
     lambda: _form(2048, 0, valid=jnp.asarray([700])), "dense"),
    ("no fresh K/V handed over", lambda: _form(2048, 0, fresh=False),
     "dense"),
    ("a decode step", lambda: _traced(lambda o: _form(1, o)), "paged"),
    ("a bucket both gates refuse", lambda: _form(64, 0, H=64, KV=1),
     "dense"),
])
def test_form_rule(name, got, want):
    assert got() == want, name


# ---------------------------------------------------------------------------
# (c) the engine says which form a bucket traced, and serves the same tokens
# ---------------------------------------------------------------------------
def _model():
    paddle.set_default_dtype("float32")
    paddle.seed(7)
    m = LlamaForCausalLM(LlamaConfig(
        vocab_size=128, hidden_size=256, num_layers=2, num_heads=2,
        num_kv_heads=1, intermediate_size=128,
        max_position_embeddings=2048))
    m.eval()
    return m


def _engine(model):
    pred = create_predictor(
        Config().set_model(model).enable_paged_kv(page_size=128))
    return ServingEngine(pred, max_batch=2, decode_chunk=2)


@pytest.fixture
def kernels_on_cpu(monkeypatch):
    """The dispatch as a TPU takes it, with the attention kernels in the
    interpreter; the fused norm's gate closed (not what is tested)."""
    monkeypatch.setattr(llama, "_kernels_on", lambda: True)
    monkeypatch.setattr(rms_norm, "rms_norm_supported", lambda shape: False)
    monkeypatch.setattr(fa, "flash_attention_gqa",
                        partial(fa.flash_attention_gqa, interpret=True))
    monkeypatch.setattr(da, "paged_decode_attention",
                        partial(da.paged_decode_attention, interpret=True))


def _kernels(eng, site):
    """kernel name -> count over the program's ``pallas_call``s."""
    from test_flash_grad_kernel import _pallas_calls

    fn, avals = eng._site_programs[site]
    return _pallas_calls(jax.make_jaxpr(fn)(*avals).jaxpr)


def _serve(model, prompts):
    eng = _engine(model)
    rids = [eng.submit(p, max_new_tokens=3) for p in prompts]
    done = eng.run()
    return eng, [list(done[r].new_tokens) for r in rids]


def test_engine_reports_the_form_and_serves_the_dense_tokens(
        kernels_on_cpu, monkeypatch):
    model = _model()
    r = np.random.RandomState(11)
    prompts = [r.randint(0, 128, (n,)).astype(np.int32)
               for n in (168, 600)]       # buckets of 256 and 1,024 rows
    eng, tokens = _serve(model, prompts)
    assert eng.prefill_attention_forms() == {256: "paged", 1024: "flash"}
    # the long prompt's program holds the flash forward, a layer each,
    # and walks no page; the short one's walks the pool
    layers = model.config.num_layers
    assert _kernels(eng, ("prefill", 1024)) == {
        "flash_attention_fwd_gqa": layers}
    assert _kernels(eng, ("prefill", 256)) == {
        "paged_decode_attention": layers}

    monkeypatch.setattr(llama, "_kernels_on", lambda: False)
    dense, want = _serve(model, prompts)
    assert dense.prefill_attention_forms() == {256: "dense",
                                               1024: "dense"}
    assert tokens == want


# ---------------------------------------------------------------------------
# (d) on the CPU nothing changed: the parent's programs, the dense form
# ---------------------------------------------------------------------------
# sha256 of the StableHLO text of the tiny Llama engine's programs, read
# from the parent commit (1036d50) by this very code on the CPU
LLAMA_PROGRAMS = {
    ("prefill", 64):
        "ac8522207e8d491a1ecc041bf8e3851464f5d0b9fe39f65af160d85a3501fbdc",
    ("prefill", 128):
        "b07b4004e0d000b27e427f6a3bbbb60eeff1c7e9c60de94ab6d3cf4e365d7d7c",
    ("decode",):
        "1e89a211663121960c7dbd1a6166df8b30627d5faf62428f5f47c28677bcb1ad",
}


def llama_program_hashes():
    paddle.set_default_dtype("float32")
    paddle.seed(0)
    model = LlamaForCausalLM(llama_tiny())
    model.eval()
    pred = create_predictor(
        Config().set_model(model).enable_paged_kv(page_size=8))
    eng = ServingEngine(pred, max_batch=2, decode_chunk=2)
    for n in (40, 100):
        eng.submit(np.arange(n, dtype=np.int32), max_new_tokens=3)
    eng.run()
    return eng, {site: hashlib.sha256(
        eng.lowered_text(site).encode()).hexdigest()
        for site in eng.program_sites()}


def test_cpu_programs_are_the_parent_s_text():
    eng, hashes = llama_program_hashes()
    assert hashes == LLAMA_PROGRAMS
    assert eng.prefill_attention_forms() == {64: "dense", 128: "dense"}
    assert "tpu_custom_call" not in eng.lowered_text(("prefill", 128))
