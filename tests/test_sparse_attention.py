"""Attention over the keys a learned index chooses (``models/hybrid_moe``
kind ``"sparse"``, ``ops/sparse_attention.py``, the third pooled array of
``inference/kv_cache.py``, ``paged_decode_attention(keep=)``) and the
softmax router, at a tiny size on the CPU against the plain float32
reference (benchmarks/references/keye.py) on seeded weights: top-k 8 over
pages of 8, contexts several times the top-k.
"""
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import paddle_tpu as paddle  # noqa: E402
from benchmarks.harness import weights  # noqa: E402
from benchmarks.harness.families import sparse_moe_serving as fam  # noqa: E402
from benchmarks.references import keye as ref  # noqa: E402
from paddle_tpu.incubate.distributed.models.moe import (  # noqa: E402
    GatedMoELayer, SigmoidTopKGate)
from paddle_tpu.inference import (Config, ServingEngine,  # noqa: E402
                                  create_predictor)
from paddle_tpu.inference.kv_cache import (PagedKVCache,  # noqa: E402
                                           with_table, without_table)
from paddle_tpu.models.hybrid_moe import (HybridMoEConfig,  # noqa: E402
                                          HybridMoEForCausalLM,
                                          collect_selection,
                                          sparse_moe_tiny)
from paddle_tpu.ops.pallas import decode_attention as da  # noqa: E402
from paddle_tpu.ops.sparse_attention import (index_scores,  # noqa: E402
                                             keep_topk,
                                             sparse_causal_attention)

# the tiny twin of benchmarks/configs/keye-vl-2.0-30b-a3b.json, in the
# source's key names: 3 layers, 8 query heads on 2 KV heads of 16, 2
# index heads of 8 that keep 8 keys, 4 of 16 experts held
CFG = {
    "hidden_size": 64, "intermediate_size": 128,
    "moe_intermediate_size": 32, "num_hidden_layers": 3,
    "num_attention_heads": 8, "num_key_value_heads": 2, "head_dim": 16,
    "rope_theta": 10000.0, "num_experts": 4, "router_experts": 16,
    "expert_offset": 4, "num_experts_per_tok": 4, "norm_topk_prob": True,
    "mlp_only_layers": [], "decoder_sparse_step": 1,
    "sa_config": {"indexer_head_dim": 8, "indexer_num_heads": 2,
                  "indexer_num_kv_heads": 1, "kv_chunk_size": 16,
                  "q_chunk_size": 16, "topk": 8},
    "vocab_size": 256, "rms_norm_eps": 1e-6, "torch_dtype": "float32",
    "initializer_range": 0.3}
SEED = 2 ** 31 + 39
M = 128
PAGE = 8
TOPK = 8


def build(cfg=CFG, seed=SEED, max_len=M, **kw):
    paddle.set_default_dtype("float32")
    mcfg = fam.model_config(cfg, max_len)
    for k, v in kw.items():
        setattr(mcfg, k, v)
    model = HybridMoEForCausalLM(mcfg)
    model.eval()
    named = list(model.named_parameters())
    weights.load(named, {n: fam.names_of(n, cfg) for n, _ in named},
                 ref.leaf_table(cfg), seed, "float32")
    return model


@pytest.fixture(scope="module")
def model():
    return build()


@pytest.fixture(scope="module")
def tokens():
    return np.random.default_rng(39).integers(0, 256, 120).astype(np.int32)


def engine(model, **kw):
    pred = create_predictor(Config().set_model(model).enable_paged_kv(
        page_size=PAGE))
    return ServingEngine(pred, **kw)


def ref_logits(prompt, served, cfg=CFG, precision="float32"):
    return ref.ServeReference(cfg, SEED, precision).logits(
        [(prompt, served)])[0]


def brute_force(scores, valid, k):
    """The k largest valid entries of each row, ties to the lower
    index, by sorting."""
    want = np.zeros(valid.shape, bool)
    for idx in np.ndindex(*valid.shape[:-1]):
        cand = sorted((-scores[idx][m], m) for m in range(valid.shape[-1])
                      if valid[idx][m])
        for _, m in cand[:k]:
            want[idx][m] = True
    return want


# -- (a) the model against the reference --------------------------------------
def test_tiny_preset_is_the_family_s_mapping():
    c = sparse_moe_tiny()
    assert set(c.attention_kinds) == {"sparse"}
    assert set(c.ffn_kinds) == {"experts"}
    assert (c.router_score_func, c.num_shared_experts, c.qk_norm,
            c.head_on_last_row, c.rotary_kinds) == (
        "softmax", 0, True, True, ("sparse",))
    assert c.num_local_experts < c.num_experts
    assert c.max_position_embeddings >= 8 * c.index_topk
    got = fam.model_config(CFG, M)
    for f in ("attention_kinds", "ffn_kinds", "num_heads", "num_kv_heads",
              "qk_head_dim", "v_head_dim", "rotary_dim", "rope_theta",
              "rotary_kinds", "num_experts", "num_local_experts",
              "expert_offset", "num_experts_per_tok", "router_score_func",
              "num_shared_experts", "qk_norm", "head_on_last_row",
              "index_heads", "index_head_dim", "index_topk",
              "attention_block"):
        assert getattr(got, f) == getattr(c, f), f


def test_defaults_have_no_sparse_layer_and_a_sigmoid_router():
    c = HybridMoEConfig()
    assert "sparse" not in c.attention_kinds
    assert (c.router_score_func, c.index_heads, c.index_head_dim,
            c.index_topk) == ("sigmoid", 0, 0, 0)
    with pytest.raises(Exception, match="index_heads"):
        sparse_moe_tiny(index_heads=0)


def test_full_forward_is_the_reference(model, tokens):
    t = tokens[:80]
    got = np.asarray(model(paddle.to_tensor(t[None]))._value)[0]
    want = ref_logits(t[:1], np.append(t[1:], 0))
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-4)


def test_engine_prefill_then_decode_is_the_reference(model, tokens):
    """Prefill (tiers of rows, the head on the last row) then the decode
    program (index scores over the row's pages, the exact selection,
    attention under the kept mask): every served token's logit gap to
    the reference's full forward is 0 up to float32 noise, for two
    ragged requests sharing the batch whose contexts are 9 to 14 times
    the top-k."""
    eng = engine(model, max_batch=2, debug_invariants=True)
    cache = eng.cache
    assert cache.arrays == [3, 3, 3] and not cache.window
    assert {layer[0].shape[1:] for layer in eng.pools} == {(2, PAGE, 128)}
    assert {layer[1].shape[1:] for layer in eng.pools} == {(2, PAGE, 16)}
    assert {layer[2].shape[1:] for layer in eng.pools} == {(1, PAGE, 128)}
    # K and V of 2 heads and the index key, bf16-free float32 here
    assert cache.page_bytes == 3 * (2 * 128 + 2 * 16 + 128) * PAGE * 4
    prompts = (tokens[:21], tokens[5:75])
    rids = [eng.submit(prompts[0], max_new_tokens=50),
            eng.submit(prompts[1], max_new_tokens=40)]
    done = eng.run()
    for rid, prompt in zip(rids, prompts):
        served = np.asarray(done[rid].new_tokens)
        assert len(prompt) + len(served) > 8 * TOPK
        lg = ref_logits(prompt, served)
        assert ref.served_gap(lg, served).max() < 1e-3
    st = eng.moe_stats()
    assert st["dropped"] == 0 and (st["tokens"] > 0).all()
    snap = eng.metrics_snapshot()["metrics"]
    share = snap["paddle_tpu_serving_sparse_selected_share"]["series"][0][
        "value"]
    assert 8 / 110 <= share <= 8 / 60      # the last rounds' contexts
    kv = snap["paddle_tpu_serving_kv_bytes_per_context_token"]["series"][
        0]["value"]
    assert kv >= cache.page_bytes / PAGE    # the index pool is counted
    spans = [s for t in eng.request_traces() for s in t["spans"]
             if s["name"] in ("prefill", "decode")]
    assert spans and all(
        s["meta"]["index_pages"] == s["meta"]["full_pages"] for s in spans)


def test_a_dense_attention_twin_fails_the_same_tolerance(model, tokens):
    """The same weights with the selection switched off (every earlier
    key kept): what the engine serves is no longer the reference's."""
    dense = build(index_topk=M)
    eng = engine(dense, max_batch=1)
    prompt = tokens[5:75]
    rid = eng.submit(prompt, max_new_tokens=20)
    served = np.asarray(eng.run()[rid].new_tokens)
    lg = ref_logits(prompt, served)
    assert ref.served_gap(lg, served).max() > 1e-2
    # and it IS the reference's own selection-skipped control
    lg = ref_logits(prompt, served, precision="dense")
    assert ref.served_gap(lg, served).max() < 1e-3


def test_static_cache_generate_serves_the_engine_s_tokens(model, tokens):
    prompt = tokens[:37]
    eng = engine(model, max_batch=1)
    rid = eng.submit(prompt, max_new_tokens=12)
    want = eng.run()[rid].new_tokens
    for paged in (False, True):
        conf = Config().set_model(model)
        if paged:
            conf = conf.enable_paged_kv(page_size=PAGE)
        out = create_predictor(conf).generate(
            paddle.to_tensor(prompt[None]), max_new_tokens=12)
        assert list(np.asarray(out._value)[0, -12:]) == want, paged


# -- (b) the selection ---------------------------------------------------------
@pytest.mark.parametrize("M_, k", [(77, 8), (64, 1), (33, 32), (20, 64)])
def test_keep_topk_is_a_brute_force_top_k_with_ties(M_, k):
    rng = np.random.default_rng(M_ + k)
    x = (np.round(rng.normal(size=(3, 5, M_)) * 2) / 2).astype(np.float32)
    x[1, 2] = 0.0                           # a row of nothing but ties
    x[2, 0, ::3] = -np.inf
    valid = rng.random((3, 5, M_)) < 0.7
    valid[0, 0] = False                     # a row that sees nothing
    valid[0, 1, 3:] = False                 # fewer valid than k (k > 3)
    got = np.asarray(jax.jit(lambda s, v: keep_topk(s, v, k))(x, valid))
    np.testing.assert_array_equal(got, brute_force(x, valid, k))
    np.testing.assert_array_equal(got.sum(-1),
                                  np.minimum(valid.sum(-1), k))


@pytest.mark.parametrize("S, block, topk", [
    (48, 16, 8), (50, 16, 8), (40, 8, 20), (16, 16, 8), (24, 8, 64),
    (64, 16, 16)])
def test_sparse_prefill_is_masked_attention_over_the_brute_force_set(
        S, block, topk):
    """Rows in tiers and blocks, S not a multiple of the block, a top-k
    under and over a block, a top-k over the whole sequence."""
    rng = np.random.default_rng(S + block)
    B, H, KV, D, Hi, di = 2, 4, 2, 16, 3, 8
    f = lambda *s: rng.normal(size=s).astype(np.float32)
    q, k, v = f(B, S, H, D), f(B, S, KV, D), f(B, S, KV, D)
    iq, ik, iw = f(B, S, Hi, di), f(B, S, di), f(B, S, Hi)
    out, mask = jax.jit(lambda *a: sparse_causal_attention(
        *a, 0.25, topk, block=block, want_mask=True))(q, k, v, iq, ik, iw)
    sc = np.asarray(index_scores(iq, ik, iw))
    causal = np.broadcast_to(np.tril(np.ones((S, S), bool)), (B, S, S))
    want = brute_force(sc, causal, topk)
    np.testing.assert_array_equal(np.asarray(mask), want)
    kr, vr = np.repeat(k, H // KV, 2), np.repeat(v, H // KV, 2)
    s = np.einsum("bqhd,bkhd->bhqk", q, kr) * 0.25
    s = np.where(want[:, None], s, -1e30)
    p = np.exp(s - s.max(-1, keepdims=True))
    p /= p.sum(-1, keepdims=True)
    np.testing.assert_allclose(np.asarray(out),
                               np.einsum("bhqk,bkhd->bqhd", p, vr),
                               rtol=1e-5, atol=1e-5)
    plain = jax.jit(lambda *a: sparse_causal_attention(
        *a, 0.25, topk, block=block))(q, k, v, iq, ik, iw)
    np.testing.assert_array_equal(np.asarray(plain), np.asarray(out))


def test_the_model_s_kept_sets_are_the_reference_s(model, tokens):
    """``collect_selection``: rows under the top-k keep every earlier
    key, rows over it keep exactly top-k, and the sets are the
    reference's (float32 on both sides: no near-tie flips here)."""
    t = tokens[:70]
    with collect_selection() as kept:
        model(paddle.to_tensor(t[None]))
    assert len(kept) == 3
    r = ref.ServeReference(CFG, SEED)
    with jax.default_matmul_precision("highest"):
        r.forward([t], [(0, len(t))])
    pos = np.arange(len(t))
    for layer, mask in enumerate(kept):
        mask = np.asarray(mask)[0]
        np.testing.assert_array_equal(mask.sum(-1),
                                      np.minimum(pos + 1, TOPK))
        np.testing.assert_array_equal(
            mask[:TOPK], np.tril(np.ones((TOPK, len(t)), bool)))
        want = r.kept[layer][0][:, :len(t)]
        assert (mask == want).mean() > 0.999
    with collect_selection() as kept:     # closed again: nothing leaks
        pass
    assert kept == []


def test_forward_scopes_name_the_index_the_selection_and_the_attention(
        model):
    def names(fn, *args):
        jaxpr = jax.make_jaxpr(fn)(*args)
        out = set()

        def walk(j):
            for e in j.eqns:
                out.add(str(e.source_info.name_stack))
                for sub in jax.core.jaxprs_in_params(e.params):
                    walk(sub)

        walk(jaxpr.jaxpr)
        return "\n".join(out)

    text = names(lambda ids, n: model.forward(ids, lengths=n)._value,
                 jnp.zeros((1, 32), jnp.int32), jnp.asarray([9], jnp.int32))
    for scope in ("layer0.attn.sparse", "layer2.attn.sparse.index",
                  "layer1.attn.sparse.select", "layer0.attn.sparse.attend",
                  "layer2.moe", "head"):
        assert scope in text, scope


# -- (c) the third pooled array ------------------------------------------------
def test_the_index_pool_is_written_read_and_freed_with_its_pages(model,
                                                                 tokens):
    eng = engine(model, max_batch=2, debug_invariants=True)
    cache = eng.cache
    free0 = cache.counts()["free"]
    rid = eng.submit(tokens[:30], max_new_tokens=3)
    eng.step()                  # admitted and prefilled
    eng._drain()
    held = free0 - cache.counts()["free"]
    assert held == cache.pages_for(30 + 3)
    pages = [s for s in eng.slots if s is not None][0].pages
    for k_pool, v_pool, i_pool in eng.pools:
        got = np.asarray(i_pool)
        # the prompt's 30 positions: 3 whole pages and 6 rows of a 4th,
        # 8 published columns of the 128 pooled, the rest zeros
        for n, pg in enumerate(pages[:4]):
            rows = min(PAGE, 30 - n * PAGE)
            assert np.abs(got[pg, 0, :rows, :8]).min() > 0
            assert not got[pg, 0, :, 8:].any()
        assert not got[cache.trash].any() or True   # padding lands here
    # a page read carries the three arrays of every layer; written back
    # to another page it reads the same
    payload = cache.read_page(pages[0])
    assert [len(layer) for layer in payload["target"]] == [3, 3, 3]
    [spare] = cache.allocate(1)
    cache.write_page(spare, payload)
    again = cache.read_page(spare)
    for a, b in zip(payload["target"], again["target"]):
        for x, y in zip(a, b):
            np.testing.assert_array_equal(x, y)
    cache.copy_page(pages[1], spare)
    np.testing.assert_array_equal(
        cache.read_page(spare)["target"][2][2],
        cache.read_page(pages[1])["target"][2][2])
    cache.release_pages([spare])
    eng.run()
    assert eng.finished[rid].new_tokens
    assert cache.counts()["free"] == free0      # freed with K and V


def test_with_table_puts_the_table_after_a_layer_s_arrays():
    state = [("k", "v", "c"), ("k", "v", "i", "c")]
    got = with_table(state, "T", [2, 3])
    assert got == [("k", "v", "T", "c"), ("k", "v", "i", "T", "c")]
    assert without_table(got, [2, 3]) == state
    assert with_table([("k", "v")], ["A"]) == [("k", "v", "A")]
    assert without_table([("k", "v", "A", "c")]) == [("k", "v", "c")]


def test_a_model_that_pools_three_arrays_cannot_migrate_its_pages(model):
    cache = PagedKVCache(model, PAGE, M, 2, jnp.float32)
    with pytest.raises(Exception, match="pools 3 arrays a layer"):
        cache.check_stackable()
    with pytest.raises(Exception, match="index keys beside its K and V"):
        engine(model, max_batch=2, phase="decode")


REFUSED = {
    "prefill_chunk": dict(prefill_chunk=16),
    "prefix_cache": dict(prefix_cache=True),
    "host_spill": dict(host_spill_pages=4),
    "speculation": dict(spec_tokens=2),
}


@pytest.mark.parametrize("mode", sorted(REFUSED))
def test_the_engine_refuses_what_feeds_a_row_in_chunks(model, mode):
    kw = dict(REFUSED[mode])
    if mode == "speculation":
        kw["draft_predictor"] = create_predictor(
            Config().set_model(model).enable_paged_kv(page_size=PAGE))
    with pytest.raises(Exception) as e:
        engine(model, max_batch=2, **kw)
    text = str(e.value)
    assert "selects keys by a learned index" in text, text
    assert "takes no `valid`" in text, text
    assert {"prefill_chunk": "prefill_chunk", "prefix_cache": "prefix cache",
            "host_spill": "host spill", "speculation": "speculative"}[
        mode] in text


# -- (d) the decode kernel under a kept mask ------------------------------------
@pytest.mark.parametrize("B, KV, G, npages", [(3, 2, 4, 5), (2, 1, 8, 3)])
def test_paged_kernel_with_keep_is_its_dense_twin(B, KV, G, npages):
    rng = np.random.default_rng(B + npages)
    page, D, P = 8, 128, B * npages + 1
    H = KV * G
    f = lambda *s: jnp.asarray(rng.normal(size=s), jnp.float32)
    q = f(B, 1, H, D)
    kp, vp = f(P, KV, page, D), f(P, KV, page, D)
    tbl = jnp.asarray(rng.permutation(P - 1)[:B * npages].reshape(
        B, npages), jnp.int32)
    lens = jnp.asarray(rng.integers(1, npages * page - 1, B), jnp.int32)
    seen = np.arange(npages * page)[None] <= np.asarray(lens)[:, None]
    keep = jnp.asarray(seen & (rng.random((B, npages * page)) < 0.4)
                       | (np.arange(npages * page)[None]
                          == np.asarray(lens)[:, None]))
    got = da.paged_decode_attention(q, kp, vp, tbl, lens, scale=0.1,
                                    keep=keep, interpret=True)
    want = da.paged_attention_dense(q, kp, vp, tbl, lens, 0.1, None, None,
                                    keep)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)
    full = da.paged_attention_dense(q, kp, vp, tbl, lens, 0.1)
    assert np.abs(np.asarray(full) - np.asarray(want)).max() > 1e-3


@pytest.mark.parametrize("planted", [False, True])
def test_the_decode_step_counts_its_own_kept_keys(model, tokens,
                                                  monkeypatch, planted):
    """``selection_stats()``: the decode program itself counts, on the
    device beside the routing counters, the (row, layer) pairs whose
    kept count is not ``min(t + 1, topk)``. 0 as served; a selection
    that keeps every earlier key (planted) shows in it, and the routing
    counters keep their layout."""
    if planted:
        from paddle_tpu.ops import sparse_attention
        monkeypatch.setattr(sparse_attention, "keep_topk",
                            lambda scores, valid, k: valid)
    eng = engine(model, max_batch=2)
    eng.submit(tokens[:21], max_new_tokens=10)
    eng.run()
    st = eng.selection_stats()
    # 3 layers x 2 rows of the batch (one live) a step
    assert st["rows"] > 0 and st["rows"] % 6 == 0
    assert st["rows"] == int(eng.moe_stats()["tokens"].sum())
    assert (st["kept_keys_wrong"] > 0) == planted
    assert eng.moe_stats()["dropped"] == 0


def test_rows_are_written_into_a_pool_of_other_heads():
    """``paged_kv_write(more=)``: a third pool of ONE head under the
    table of K and V, page form (prefill) and row form (decode)."""
    rng = np.random.default_rng(3)
    P, page, B = 6, 8, 2
    kp, vp = jnp.zeros((P, 2, page, 128)), jnp.zeros((P, 2, page, 16))
    ip = jnp.zeros((P, 1, page, 128))
    tbl = jnp.asarray([[0, 1], [2, 3]], jnp.int32)
    f = lambda *s: jnp.asarray(rng.normal(size=s), jnp.float32)
    k, v, i = f(B, 16, 2, 128), f(B, 16, 2, 16), f(B, 16, 1, 128)
    kp, vp, ip = da.paged_kv_write(kp, vp, k, v, tbl, 0, more=((ip, i),))
    np.testing.assert_array_equal(np.asarray(ip)[2, 0], np.asarray(i)[1, :8, 0])
    np.testing.assert_array_equal(np.asarray(kp)[3, 1],
                                  np.asarray(k)[1, 8:, 1])
    k1, v1, i1 = f(B, 1, 2, 128), f(B, 1, 2, 16), f(B, 1, 1, 128)
    off = jnp.asarray([3, 12], jnp.int32)
    kp, vp, ip = da.paged_kv_write(kp, vp, k1, v1, tbl, off,
                                   more=((ip, i1),))
    np.testing.assert_array_equal(np.asarray(ip)[0, 0, 3],
                                  np.asarray(i1)[0, 0, 0])
    np.testing.assert_array_equal(np.asarray(ip)[3, 0, 4],
                                  np.asarray(i1)[1, 0, 0])
    np.testing.assert_array_equal(np.asarray(vp)[3, 1, 4],
                                  np.asarray(v1)[1, 0, 1])


# -- (e) the router and the shares ---------------------------------------------
def test_softmax_gate_by_hand():
    """Three experts, top-2, logits (0, ln 2, ln 3) for a token whose
    one feature is 1: p = (1/6, 2/6, 3/6); experts 2 and 1 are chosen
    with weights 3/5 and 2/5."""
    paddle.set_default_dtype("float32")
    gate = SigmoidTopKGate(1, 3, topk=2, score_func="softmax")
    assert not hasattr(gate, "bias")
    assert [n for n, _ in gate.named_parameters()] == ["weight"]
    gate.weight._value = jnp.asarray([[0.0, np.log(2.0), np.log(3.0)]],
                                     jnp.float32)
    idx, w = gate.route(jnp.ones((1, 1), jnp.float32))
    assert idx.tolist() == [[2, 1]]
    np.testing.assert_allclose(np.asarray(w), [[0.6, 0.4]], rtol=1e-6)
    # the sigmoid gate keeps its bias; an unknown score is refused
    assert hasattr(SigmoidTopKGate(1, 3, topk=2), "bias")
    with pytest.raises(ValueError, match="score_func"):
        SigmoidTopKGate(1, 3, score_func="tanh")


def test_the_eight_shares_add_up_to_the_uncut_layer():
    """Experts 0..15 held 2 to a holder on 8 holders (offsets 0, 2, ...
    14, as 0, 16, ... 112 of 128): the holders' parts add up to the
    layer that holds all 16, and that is the reference's uncut step 5;
    nothing is counted twice (no shared expert)."""
    def layer(offset, held):
        return GatedMoELayer(64, 32, 16, held, offset, top_k=4,
                             num_shared_experts=0, score_func="softmax")

    paddle.set_default_dtype("float32")
    whole = layer(0, 16)
    assert not whole.shared
    rng = np.random.default_rng(0)
    for p in whole.parameters():
        p._value = jnp.asarray(rng.normal(0, 0.2, p.shape), jnp.float32)
    x = jnp.asarray(rng.normal(0, 1, (24, 64)), jnp.float32)
    want = np.asarray(whole(x)._value)
    total = np.zeros_like(want)
    for off in range(0, 16, 2):
        part = layer(off, 2)
        for name in ("w_gate", "w_up", "w_down"):
            getattr(part, name)._value = getattr(whole, name)._value[
                off:off + 2]
        part.gate.weight._value = whole.gate.weight._value
        total += np.asarray(part(x)._value)
    cfg = dict(CFG, num_experts=16, expert_offset=0)
    with jax.default_matmul_precision("highest"):
        idx, w = ref.route(x, whole.gate.weight._value, cfg)
        y = sum(ref.expert_part(
            x, idx, w, j, whole.w_gate._value[j], whole.w_up._value[j],
            whole.w_down._value[j], "float32") for j in range(16))
    np.testing.assert_allclose(total, want, rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(want, np.asarray(y), rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(np.asarray(w.sum(-1)), 1.0, rtol=1e-6)
