"""Latent attention over the cache rows a learned index chooses
(``models/mla_moe.py`` with a query latent and an index,
``mla_paged_sparse_decode_attention``, the third pooled array) and the
router that keeps groups of experts first, at a tiny size on the CPU
against the plain float32 reference
(benchmarks/references/deepseek_v32.py) on seeded weights: top-k 8 over
pages of 8, contexts several times the top-k, 8 experts in 4 groups of
which 2 are kept.
"""
import hashlib
import os
import sys
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import paddle_tpu as paddle  # noqa: E402
from benchmarks.harness import weights  # noqa: E402
from benchmarks.harness.families import (  # noqa: E402
    sparse_mla_moe_serving as fam)
from benchmarks.references import deepseek_v32 as ref  # noqa: E402
from paddle_tpu.incubate.distributed.models.moe import (  # noqa: E402
    GatedMoELayer, SigmoidTopKGate)
from paddle_tpu.inference import (Config, ServingEngine,  # noqa: E402
                                  create_predictor)
from paddle_tpu.models.hybrid_moe import (HybridMoEForCausalLM,  # noqa: E402
                                          sparse_moe_tiny)
from paddle_tpu.models.mla_moe import (MLAMoEConfig,  # noqa: E402
                                       MLAMoEForCausalLM, mla_moe_tiny,
                                       sparse_mla_tiny)
from paddle_tpu.observability import moestats  # noqa: E402
from paddle_tpu.ops.pallas import mla_attention as ma  # noqa: E402
from paddle_tpu.ops.pallas import kept_attention as ka  # noqa: E402
from paddle_tpu.ops.sparse_attention import (collect_selection,  # noqa: E402
                                             index_scores, kept_mask,
                                             sparse_causal_attention)

# the tiny twin of benchmarks/configs/deepseek-v3.2-exp.json, in the
# source's key names: a dense layer and 2 expert layers, 4 heads of
# 16 + 8 / 16 off a 24-wide query latent, 2 index heads of 16 that keep
# 8 rows, 4 of 8 experts held, 4 groups of which 2 are kept
CFG = {
    "hidden_size": 64, "intermediate_size": 128,
    "moe_intermediate_size": 32, "num_hidden_layers": 3,
    "first_k_dense_replace": 1, "num_attention_heads": 4,
    "q_lora_rank": 24, "kv_lora_rank": 32, "qk_nope_head_dim": 16,
    "qk_rope_head_dim": 8, "v_head_dim": 16, "index_n_heads": 2,
    "index_head_dim": 16, "index_topk": 8, "n_routed_experts": 4,
    "router_experts": 8, "expert_offset": 0, "num_experts_per_tok": 2,
    "n_group": 4, "topk_group": 2, "n_shared_experts": 1,
    "routed_scaling_factor": 2.5, "scoring_func": "sigmoid",
    "norm_topk_prob": True, "topk_method": "noaux_tc",
    "moe_layer_freq": 1, "attention_bias": False, "rope_theta": 10000.0,
    "rope_scaling": {"type": "yarn", "factor": 4,
                     "original_max_position_embeddings": 32,
                     "beta_fast": 32, "beta_slow": 1, "mscale": 1,
                     "mscale_all_dim": 1},
    "vocab_size": 256, "rms_norm_eps": 1e-6, "torch_dtype": "float32",
    "initializer_range": 0.3}
SEED = 2 ** 31 + 41
M = 128
PAGE = 8
TOPK = 8


def build(cfg=CFG, seed=SEED, max_len=M, **kw):
    paddle.set_default_dtype("float32")
    mcfg = fam.model_config(cfg, max_len)
    mcfg.attention_block = 16
    for k, v in kw.items():
        setattr(mcfg, k, v)
    model = MLAMoEForCausalLM(mcfg)
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
    return np.random.default_rng(41).integers(0, 256, 120).astype(np.int32)


def engine(model, **kw):
    pred = create_predictor(Config().set_model(model).enable_paged_kv(
        page_size=PAGE))
    return ServingEngine(pred, **kw)


def ref_logits(prompt, served, cfg=CFG, precision="float32"):
    return ref.ServeReference(cfg, SEED, precision).logits(
        [(prompt, served)])[0]


# -- (a) the model against the reference --------------------------------------
def test_tiny_preset_is_the_family_s_mapping():
    c = sparse_mla_tiny()
    assert (c.q_lora_rank, c.use_qk_norm, c.head_on_last_row) == (
        24, False, True)
    assert c.num_local_experts < c.num_experts
    assert c.max_position_embeddings >= 8 * c.index_topk
    got = fam.model_config(CFG, M)
    got.attention_block = 16
    for f in ("num_layers", "num_heads", "q_lora_rank", "kv_lora_rank",
              "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim",
              "num_experts", "num_local_experts", "expert_offset",
              "num_experts_per_tok", "n_group", "topk_group",
              "num_shared_experts", "first_k_dense_replace",
              "use_qk_norm", "index_heads", "index_head_dim", "index_topk",
              "attention_block", "head_on_last_row", "rope_scaling"):
        assert getattr(got, f) == getattr(c, f), f


def test_every_new_field_is_off_by_default():
    c = MLAMoEConfig()
    assert (c.q_lora_rank, c.index_heads, c.index_head_dim, c.index_topk,
            c.n_group, c.topk_group, c.head_on_last_row) == (
        0, 0, 0, 0, 0, 0, False)
    m = MLAMoEForCausalLM(mla_moe_tiny())
    assert m.key_selection is None and not m.head_on_last_row
    assert {len(layer) for layer in m.kv_pool_shapes(4, 8)} == {2}
    assert m.moe_counter_shape() == (3, 4 + 3)
    names = [n for n, _ in m.named_parameters()]
    assert "layers.0.self_attn.q_proj" in names
    assert not any("index" in n or "q_a" in n for n in names)
    with pytest.raises(Exception, match="index_heads"):
        sparse_mla_tiny(index_heads=0)


def test_full_forward_is_the_reference(model, tokens):
    t = tokens[:80]
    got = np.asarray(model(paddle.to_tensor(t[None]))._value)[0]
    want = ref_logits(t[:1], np.append(t[1:], 0))
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-4)


def test_engine_prefill_then_decode_is_the_reference(model, tokens):
    """Prefill (the unabsorbed form over the kept sets, tiers of rows,
    the head on the last row) then the decode program (index scores
    over the row's index-key pages, the exact selection, the ABSORBED
    form under the kept mask): every served token's logit gap to the
    reference's full forward is 0 up to float32 noise, for two ragged
    requests sharing the batch whose contexts are 9 to 14 times the
    top-k."""
    eng = engine(model, max_batch=2, debug_invariants=True)
    cache = eng.cache
    assert cache.arrays == [3, 3, 3] and not cache.window
    assert {layer[0].shape[1:] for layer in eng.pools} == {(1, PAGE, 32)}
    assert {layer[1].shape[1:] for layer in eng.pools} == {(1, PAGE, 128)}
    assert {layer[2].shape[1:] for layer in eng.pools} == {(1, PAGE, 128)}
    # the latent, the rotated key and the index key, float32 here
    assert cache.page_bytes == 3 * (32 + 128 + 128) * PAGE * 4
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
    assert st["dropped"] == 0
    assert st["tokens"].tolist()[0] == 0 and (st["tokens"][1:] > 0).all()
    assert st["pairs"].shape == (3, 4)
    snap = eng.metrics_snapshot()["metrics"]
    share = snap["paddle_tpu_serving_sparse_selected_share"]["series"][0][
        "value"]
    assert 8 / 110 <= share <= 8 / 60      # the last rounds' contexts
    kv = snap["paddle_tpu_serving_kv_bytes_per_context_token"]["series"][
        0]["value"]
    assert kv >= cache.page_bytes / PAGE    # the three arrays counted
    spans = [s for t in eng.request_traces() for s in t["spans"]
             if s["name"] in ("prefill", "decode")]
    assert spans and all(
        s["meta"]["index_pages"] == s["meta"]["full_pages"] for s in spans)


def test_a_dense_latent_attention_twin_fails_the_same_tolerance(model,
                                                                tokens):
    """The same weights with the selection switched off (every earlier
    row kept): what the engine serves is no longer the reference's."""
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
def test_the_model_s_kept_sets_are_a_brute_force_top_k_and_the_reference_s(
        model, tokens):
    """``collect_selection``: rows under the top-k keep every earlier
    row, rows over it keep exactly top-k, the sets are the reference's
    (float32 on both sides), and the reference's are a brute-force top-k
    of ITS index scores."""
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
    rng = np.random.default_rng(0)
    sc = rng.normal(size=(5, 40)).astype(np.float32)
    sc[:, 7] = sc[:, 3]                      # a tie: the lower one first
    seen = np.arange(40)[None] <= np.asarray([3, 9, 20, 33, 39])[:, None]
    got = np.asarray(ref.selected(jnp.asarray(sc), jnp.asarray(seen), TOPK))
    for row in range(5):
        cand = sorted((-sc[row, m], m) for m in range(40) if seen[row, m])
        assert sorted(np.flatnonzero(got[row])) == sorted(
            m for _, m in cand[:TOPK])


def test_index_heads_a_few_at_a_time_give_the_same_scores():
    rng = np.random.default_rng(1)
    f = lambda *s: jnp.asarray(rng.normal(size=s), jnp.float32)
    iq, ik, iw = f(2, 5, 8, 16), f(2, 11, 16), f(2, 5, 8)
    whole = index_scores(iq, ik, iw)
    np.testing.assert_allclose(np.asarray(index_scores(iq, ik, iw, 2)),
                               np.asarray(whole), rtol=1e-5, atol=1e-5)
    np.testing.assert_array_equal(np.asarray(index_scores(iq, ik, iw, 8)),
                                  np.asarray(whole))


def test_the_index_turns_its_first_numbers_and_no_others(model):
    """Index queries come off the QUERY LATENT and only the first
    ``qk_rope_head_dim`` numbers of an index head turn: at another
    position the same input gives the same tail and another head."""
    attn = model.layers[0].self_attn
    assert tuple(attn.index_q_proj.shape) == (24, 2 * 16)
    x = jnp.ones((1, 1, 64), jnp.float32)
    cq = jnp.ones((1, 1, 24), jnp.float32)
    a0, b0, w0 = attn._index(x, cq, 0)
    a5, b5, w5 = attn._index(x, cq, 5)
    np.testing.assert_array_equal(np.asarray(a0[..., 8:]),
                                  np.asarray(a5[..., 8:]))
    np.testing.assert_array_equal(np.asarray(b0[..., 8:]),
                                  np.asarray(b5[..., 8:]))
    assert np.abs(np.asarray(a0[..., :8] - a5[..., :8])).max() > 1e-3
    assert np.abs(np.asarray(b0[..., :8] - b5[..., :8])).max() > 1e-3
    np.testing.assert_array_equal(np.asarray(w0), np.asarray(w5))


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
                  "head"):
        assert scope in text, scope
    caches = model._empty_caches(1, 32, jnp.float32)
    text = names(lambda ids: model.forward(
        ids, caches=caches, offset=jnp.asarray([9], jnp.int32))[0]._value,
        jnp.zeros((1, 1), jnp.int32))
    for part in ("index", "select", "attend"):
        assert f"layer1.attn.sparse.{part}" in text, part


# -- (c) the absorbed form under the kept mask ---------------------------------
@pytest.mark.parametrize("B, H, npages", [
    (3, 8, 5), (2, 16, 3),
    (7, 8, 10),     # more rows than slots, and no multiple of them
    (5, 16, 1),     # every row one page: its own position alone is kept
])
def test_sparse_latent_kernel_is_its_dense_twin_and_the_unabsorbed_form(
        B, H, npages):
    """``mla_paged_sparse_decode_attention`` (interpreted) against the
    dense twin over the same kept set, and both against the UNABSORBED
    form: per-head keys and values built from the kept latents."""
    rng = np.random.default_rng(B + npages)
    page, dc, dr, dn, dv = 16, 128, 128, 8, 8
    P = B * npages + 1
    f = lambda *s: jnp.asarray(rng.normal(size=s), jnp.float32)
    w_k, w_v = f(dc, H, dn) * 0.2, f(dc, H, dv) * 0.2
    q_n, q_r = f(B, H, dn), f(B, H, dr)
    cp, rp = f(P, 1, page, dc), f(P, 1, page, dr)
    tbl = jnp.asarray(rng.permutation(P - 1)[:B * npages].reshape(
        B, npages), jnp.int32)
    lens = jnp.asarray(rng.integers(1, npages * page - 1, B), jnp.int32)
    M_ = npages * page
    seen = np.arange(M_)[None] <= np.asarray(lens)[:, None]
    keep = seen & (rng.random((B, M_)) < 0.3)
    keep[:, :page] = False              # a page with no kept row
    keep |= np.arange(M_)[None] == np.asarray(lens)[:, None]
    keep = jnp.asarray(keep)
    q_lat = jnp.einsum("bhd,chd->bhc", q_n, w_k)
    got = ma.mla_paged_decode_attention(
        q_lat, q_r, cp, rp, tbl, lens, 0.1, keep=keep, interpret=True)
    want = ma.mla_paged_attention_dense(
        q_lat[:, None], q_r[:, None], cp, rp, tbl, lens, 0.1, keep)[:, 0]
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)
    full = ma.mla_paged_attention_dense(
        q_lat[:, None], q_r[:, None], cp, rp, tbl, lens, 0.1)[:, 0]
    assert np.abs(np.asarray(full) - np.asarray(want)).max() > 1e-3
    # unabsorbed: k = [c W_k | k_r], v = c W_v over the same kept rows
    c = np.asarray(cp)[np.asarray(tbl)][:, :, 0].reshape(B, M_, dc)
    r = np.asarray(rp)[np.asarray(tbl)][:, :, 0].reshape(B, M_, dr)
    k_n = np.einsum("bmc,chd->bmhd", c, np.asarray(w_k))
    v = np.einsum("bmc,chd->bmhd", c, np.asarray(w_v))
    s = (np.einsum("bhd,bmhd->bhm", np.asarray(q_n), k_n)
         + np.einsum("bhr,bmr->bhm", np.asarray(q_r), r)) * 0.1
    s = np.where(np.asarray(keep)[:, None], s, -np.inf)
    p = np.exp(s - s.max(-1, keepdims=True))
    o = np.einsum("bhm,bmhd->bhd", p / p.sum(-1, keepdims=True), v)
    np.testing.assert_allclose(
        np.einsum("bhc,chd->bhd", np.asarray(got), np.asarray(w_v)), o,
        rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("S, block, topk, H, late", [
    (64, 16, 8, 4, False), (96, 32, 40, 4, False),
    (64, 16, 8, 12, False),     # two groups of six heads a batch row
    (64, 16, 8, 11, False),     # no divisor up to eight: a head a step
    (32, 32, 8, 4, False),      # one block only
    (512, 256, 64, 2, False),   # a block of two lane widths
    (64, 16, 8, 4, True),       # key blocks with no kept key first
    (512, 128, 64, 3, True),
], ids=["64-16-8", "96-32-40", "heads12", "heads11", "one_block",
        "two_lane_widths", "late_keys", "late_keys_128"])
def test_kept_flash_kernel_is_its_dense_twin_and_the_loop(S, block, topk,
                                                          H, late):
    """``kept_mask`` gives the sets ``sparse_causal_attention`` selects,
    and ``kept_flash_attention`` (interpreted) over them is the dense
    twin's and the loop's output: keys of 24 against values of 16, every
    head its own, several heads a grid step under one mask tile. With
    ``late`` the mask is cut by hand: no row past the first block keeps
    a key of key block 0 (a row block whose whole first key block is
    empty), and every fourth row keeps its own position alone (its
    first blocks hold no kept key, the last one does): the dropped
    scores are ``-inf`` over a finite floor, so nothing is NaN."""
    rng = np.random.default_rng(S)
    f = lambda *s: jnp.asarray(rng.normal(size=s), jnp.float32)
    B, D, Dv, Hi, di = 2, 24, 16, 4, 8
    q, k, v = f(B, S, H, D), f(B, S, H, D), f(B, S, H, Dv)
    iq, ik, iw = f(B, S, Hi, di), f(B, S, di), f(B, S, Hi)
    keep = kept_mask(iq, ik, iw, topk, block, head_block=2)
    assert keep.shape == (B, S, S) and keep.dtype == jnp.bool_
    np.testing.assert_array_equal(
        np.asarray(keep.sum(-1)),
        np.broadcast_to(np.minimum(np.arange(S) + 1, topk), (B, S)))
    dense = partial(ka.kept_attention_dense, q, k, v, scale=0.3)
    if late:
        cut = np.asarray(keep).copy()
        cut[:, block:, :block] = False
        cut[:, 3::4] = False
        cut[:, np.arange(S), np.arange(S)] = True
        keep = jnp.asarray(cut)
        assert not cut[:, block:2 * block, :block].any()
        want = dense(keep=keep)
    else:
        want, mask = sparse_causal_attention(q, k, v, iq, ik, iw, 0.3, topk,
                                             block, want_mask=True)
        np.testing.assert_array_equal(np.asarray(keep), np.asarray(mask))
        np.testing.assert_allclose(np.asarray(dense(keep=keep)),
                                   np.asarray(want), rtol=2e-5, atol=2e-5)
    got = ka.kept_flash_attention(q, k, v, keep, 0.3, block,
                                  interpret=True)
    assert np.isfinite(np.asarray(got)).all()
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


def test_kept_flash_gate_and_heads_a_step():
    """The shape gate, and the heads a grid step takes from the shapes
    alone: the largest divisor of the head count up to eight."""
    assert ka.kept_flash_supported((1, 8192, 128, 192), (1, 8192, 128, 128))
    assert not ka.kept_flash_supported((1, 8448, 128, 192),
                                       (1, 8448, 128, 128))
    assert not ka.kept_flash_supported((1, 256, 128, 192),
                                       (1, 256, 128, 128))
    assert not ka.kept_flash_supported((1, 8192, 128, 192),
                                       (1, 8192, 128, 128), 64)
    heads = lambda H, D=192: ka._heads_a_step(H, 512, D, 128, 2)
    assert [heads(H) for H in (128, 12, 11, 4, 1)] == [8, 6, 1, 4, 1]
    assert heads(128, 1024) < 8         # wider keys: fewer heads fit
    assert ka._vmem_bytes(8, 512, 192, 128, 2) <= ka._VMEM_BUDGET \
        < ka._VMEM_LIMIT


def test_the_model_s_prefill_takes_the_kernel_where_the_chip_would(
        model, monkeypatch):
    """On a TPU the layer's prefill is ``kept_mask`` +
    ``kept_flash_attention``; elsewhere ``sparse_causal_attention``'s
    loop. Same output, same kept sets handed to a collection."""
    from functools import partial

    from paddle_tpu.ops import pallas

    attn = model.layers[1].self_attn
    rng = np.random.default_rng(7)
    f = lambda *s: jnp.asarray(rng.normal(size=s), jnp.float32)
    q, k, v = f(1, 48, 4, 24), f(1, 48, 4, 24), f(1, 48, 4, 16)
    iq, ik, iw = f(1, 48, 2, 16), f(1, 48, 16), f(1, 48, 2)
    with collect_selection() as kept:
        loop = attn._kept_prefill(q, k, v, iq, ik, iw)
    monkeypatch.setattr(pallas, "is_tpu_platform", lambda: True)
    monkeypatch.setattr(ka, "kept_flash_supported", lambda *a: True)
    monkeypatch.setattr(ka, "kept_flash_attention", partial(
        ka.kept_flash_attention, interpret=True))
    with collect_selection() as kept2:
        kern = attn._kept_prefill(q, k, v, iq, ik, iw)
    np.testing.assert_allclose(np.asarray(kern), np.asarray(loop),
                               rtol=2e-5, atol=2e-5)
    np.testing.assert_array_equal(np.asarray(kept[0]),
                                  np.asarray(kept2[0]))
    text = str(jax.make_jaxpr(
        lambda *a: attn._kept_prefill(*a))(q, k, v, iq, ik, iw))
    assert "kept_flash_attention" in text


# -- (d) the third pooled array ------------------------------------------------
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
    for c_pool, r_pool, i_pool in eng.pools:
        got = np.asarray(i_pool)
        # the prompt's 30 positions: 3 whole pages and 6 rows of a 4th,
        # 16 published columns of the 128 pooled, the rest zeros
        for n, pg in enumerate(pages[:4]):
            rows = min(PAGE, 30 - n * PAGE)
            assert np.abs(got[pg, 0, :rows, :16]).min() > 0
            assert not got[pg, 0, :, 16:].any()
        assert np.abs(np.asarray(c_pool)[pages[0], 0]).min() > 0
    payload = cache.read_page(pages[0])
    assert [len(layer) for layer in payload["target"]] == [3, 3, 3]
    [spare] = cache.allocate(1)
    cache.write_page(spare, payload)
    again = cache.read_page(spare)
    for a, b in zip(payload["target"], again["target"]):
        for x, y in zip(a, b):
            np.testing.assert_array_equal(x, y)
    cache.release_pages([spare])
    eng.run()
    assert eng.finished[rid].new_tokens
    assert cache.counts()["free"] == free0      # freed with the latents


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


@pytest.mark.parametrize("planted", [False, True])
def test_the_decode_step_counts_its_own_kept_rows(model, tokens,
                                                  monkeypatch, planted):
    """``selection_stats()``: the decode program itself counts, on the
    device beside the routing counters, the (row, layer) pairs whose
    kept count is not ``min(t + 1, topk)``, the dense layer's among
    them. 0 as served; a selection that keeps every earlier row
    (planted) shows in it, and the routing counters keep their
    layout."""
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
    assert st["rows"] == 3 * int(eng.moe_stats()["tokens"][1])
    assert (st["kept_keys_wrong"] > 0) == planted
    assert eng.moe_stats()["dropped"] == 0


# -- (e) the router and the shares ---------------------------------------------
def test_group_limited_gate_by_hand_and_the_ungrouped_gate_at_one_group():
    """Six experts in 3 groups of 2, top-2 of which 2 groups are kept,
    scores s' (bias 0, one feature 1): group sums (0.9 + 0.1, 0.6 + 0.5,
    0.8 + 0.05) = (1.0, 1.1, 0.85): groups 1 and 0 are kept, so expert 4
    (0.8, the second largest of all) is NOT chosen: the top-2 are
    experts 0 (0.9) and 2 (0.6), weights 0.9 / 1.5 and 0.6 / 1.5."""
    paddle.set_default_dtype("float32")
    s = np.asarray([0.9, 0.1, 0.6, 0.5, 0.8, 0.05])
    logit = jnp.asarray(np.log(s / (1 - s))[None], jnp.float32)
    gate = SigmoidTopKGate(1, 6, topk=2, n_group=3, topk_group=2)
    gate.weight._value = logit
    gate.bias._value = jnp.zeros((6,), jnp.float32)
    x = jnp.ones((1, 1), jnp.float32)
    idx, w, groups = gate.route_groups(x)
    assert idx.tolist() == [[0, 2]] and groups.tolist() == [[1, 0]]
    np.testing.assert_allclose(np.asarray(w), [[0.6, 0.4]], rtol=1e-5)
    assert [a.tolist() for a in gate.route(x)] == [idx.tolist(),
                                                   w.tolist()]
    # the bias steers groups and experts, never the weights
    gate.bias._value = jnp.asarray([0, 0, 0, 0, 0, 0.5], jnp.float32)
    idx, w, groups = gate.route_groups(x)
    assert groups.tolist() == [[2, 1]] and idx.tolist() == [[4, 2]]
    np.testing.assert_allclose(np.asarray(w), [[0.8 / 1.4, 0.6 / 1.4]],
                               rtol=1e-5)
    # one group (or none) is the ungrouped gate
    rng = np.random.default_rng(2)
    h = jnp.asarray(rng.normal(size=(9, 5)), jnp.float32)
    gates = [SigmoidTopKGate(5, 6, topk=2, **kw) for kw in
             ({}, dict(n_group=1, topk_group=1))]
    for g in gates:
        g.weight._value = jnp.asarray(rng.normal(size=(5, 6)),
                                      jnp.float32) \
            if g is gates[0] else gates[0].weight._value
        g.bias._value = jnp.full((6,), 0.01, jnp.float32)
    (i0, w0, g0), (i1, w1, g1) = (g.route_groups(h) for g in gates)
    assert g0 is None and g1 is None
    np.testing.assert_array_equal(np.asarray(i0), np.asarray(i1))
    np.testing.assert_array_equal(np.asarray(w0), np.asarray(w1))
    with pytest.raises(ValueError, match="groups"):
        SigmoidTopKGate(1, 6, topk=2, n_group=4, topk_group=2)
    with pytest.raises(ValueError, match="groups"):
        SigmoidTopKGate(1, 6, topk=4, n_group=3, topk_group=1)


def test_the_gate_s_groups_are_the_reference_s_and_are_recorded():
    paddle.set_default_dtype("float32")
    rng = np.random.default_rng(3)
    layer = GatedMoELayer(16, 8, 32, 4, 8, top_k=4, n_group=8,
                          topk_group=3)
    for p in layer.parameters():
        p._value = jnp.asarray(rng.normal(0, 0.3, p.shape), jnp.float32)
    x = jnp.asarray(rng.normal(size=(20, 16)), jnp.float32)
    moestats.begin()
    try:
        layer(x)
    finally:
        (rec,) = moestats.drain()
    cfg = {"router_experts": 32, "n_group": 8, "topk_group": 3,
           "num_experts_per_tok": 4, "routed_scaling_factor": 1.0}
    with jax.default_matmul_precision("highest"):
        idx, w, groups = ref.route(x, layer.gate.weight._value,
                                   layer.gate.bias._value, cfg)
    np.testing.assert_array_equal(np.asarray(rec["choices"]),
                                  np.asarray(idx))
    np.testing.assert_array_equal(np.asarray(rec["groups"]),
                                  np.asarray(groups))
    # every choice lies in a kept group; pairs by group add up
    in_kept = (np.asarray(idx)[:, :, None] // 4
               == np.asarray(groups)[:, None, :]).any(-1)
    assert in_kept.all()
    load = np.asarray(rec["group_load"])
    assert load.shape == (8,) and load.sum() == 20 * 4
    np.testing.assert_array_equal(
        load, np.bincount(np.asarray(idx).ravel() // 4, minlength=8))


def test_the_sixteen_shares_and_one_shared_expert_add_up_to_the_uncut_layer():
    """The router's 256 experts held 16 to a holder on 16 holders
    (``expert_offset`` 0, 16, ... 240; 8 groups of 32, a group on two
    holders): the holders' ROUTED parts plus the shared expert counted
    ONCE add up to the layer that holds all 256, and that is the
    reference's uncut step 7."""
    def layer(offset, held, shared):
        return GatedMoELayer(32, 16, 256, held, offset, top_k=8,
                             routed_scaling_factor=2.5, n_group=8,
                             topk_group=4, num_shared_experts=shared)

    paddle.set_default_dtype("float32")
    whole = layer(0, 256, 1)
    rng = np.random.default_rng(0)
    for p in whole.parameters():
        p._value = jnp.asarray(rng.normal(0, 0.2, p.shape), jnp.float32)
    whole.gate.bias._value = jnp.asarray(rng.normal(0, 0.02, (256,)),
                                         jnp.float32)
    x = jnp.asarray(rng.normal(0, 1, (24, 32)), jnp.float32)
    want = np.asarray(whole(x)._value)
    total = np.zeros_like(want)
    for off in range(0, 256, 16):
        part = layer(off, 16, 0)
        for name in ("w_gate", "w_up", "w_down"):
            getattr(part, name)._value = getattr(whole, name)._value[
                off:off + 16]
        part.gate.weight._value = whole.gate.weight._value
        part.gate.bias._value = whole.gate.bias._value
        total += np.asarray(part(x)._value)
    sh = [getattr(whole, "shared_" + n)._value
          for n in ("gate", "up", "down")]
    cfg = {"router_experts": 256, "n_group": 8, "topk_group": 4,
           "num_experts_per_tok": 8, "routed_scaling_factor": 2.5}
    with jax.default_matmul_precision("highest"):
        shared = np.asarray(ref.swiglu(x, *sh, "float32"))
        idx, w, _ = ref.route(x, whole.gate.weight._value,
                              whole.gate.bias._value, cfg)
        y = sum(ref.expert_part(
            x, idx, w, j, whole.w_gate._value[j], whole.w_up._value[j],
            whole.w_down._value[j], "float32") for j in range(256))
    np.testing.assert_allclose(total + shared, want, rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(want, np.asarray(y) + shared, rtol=1e-4,
                               atol=1e-5)
    np.testing.assert_allclose(np.asarray(w.sum(-1)), 2.5, rtol=1e-6)


# -- (f) the models that share this code ---------------------------------------
# sha256 of the StableHLO text of the tiny engines of the two accepted
# configurations that share this PR's code (``mla_moe_tiny``: sarvam's
# block; ``sparse_moe_tiny``: keye's), read from the parent commit
# (22eecc9) by this very code on the CPU: every new field at its
# default, they trace to the parent's programs
PARENT_PROGRAMS = {
    "sarvam": {
        ("prefill", 64):
            "5089c0f12faf8dc4109ab3f7fe2537d76c25412d831385dc25b74cc2e3a39cab",
        ("decode",):
            "f84af15891423ee41368e6dccced7293484198ee7447b034b660a7344e672321",
    },
    "keye": {
        ("prefill", 64):
            "00a06419eb048f4016018450e783bd6ad81a93ef370c010ff96702993797a391",
        ("decode",):
            "ec44678b8eafe44b5c53530e57631b81c7239dd951c20cae4ba7acb2d8729cd9",
    },
}


@pytest.mark.parametrize("which", sorted(PARENT_PROGRAMS))
def test_sarvam_s_and_keye_s_serving_programs_are_the_parent_s_text(which):
    paddle.set_default_dtype("float32")
    model = MLAMoEForCausalLM(mla_moe_tiny()) if which == "sarvam" \
        else HybridMoEForCausalLM(sparse_moe_tiny())
    model.eval()
    eng = engine(model, max_batch=2, decode_chunk=1)
    eng.submit(np.arange(40, dtype=np.int32), max_new_tokens=3)
    eng.run()
    got = {site: hashlib.sha256(
        eng.lowered_text(site).encode()).hexdigest()
        for site in eng.program_sites()}
    assert got == PARENT_PROGRAMS[which]
