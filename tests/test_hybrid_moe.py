"""The decoder with window and full attention layers and routed experts
(models/hybrid_moe.py), its two classes of pages (PagedKVCache), the
decode kernel's window, sink and two widths, the blockwise prefill
attention and its life under ServingEngine, against the plain float32
reference (benchmarks/references/mimo.py) at a tiny size on the CPU,
seeded weights. Also: engines of models without window layers keep one
page class and the round array they had.
"""
import os
import re
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import paddle_tpu as paddle  # noqa: E402
from benchmarks.harness import weights  # noqa: E402
from benchmarks.harness.families import hybrid_moe_serving as fam  # noqa: E402
from benchmarks.references import mimo as ref  # noqa: E402
from paddle_tpu.incubate.distributed.models.moe import GatedMoELayer  # noqa: E402
from paddle_tpu.inference import (Config, ServingEngine,  # noqa: E402
                                  create_predictor)
from paddle_tpu.inference.kv_cache import PagedKVCache  # noqa: E402
from paddle_tpu.models.hybrid_moe import (HybridMoEForCausalLM,  # noqa: E402
                                          hybrid_moe_tiny)
from paddle_tpu.models.llama import LlamaForCausalLM, llama_tiny  # noqa: E402
from paddle_tpu.models.mla_moe import (MLAMoEForCausalLM,  # noqa: E402
                                       mla_moe_tiny)
from paddle_tpu.ops.blockwise_attention import (  # noqa: E402
    blockwise_causal_attention)
from paddle_tpu.ops.pallas import decode_attention as da  # noqa: E402

# the tiny twin of benchmarks/configs/mimo-v2-flash.json: both attention
# kinds (2 and 4 KV heads under 8 query heads), keys 24 wide against
# values 16, 8 rotated dims, a window of 12 over pages of 8 (a ring of 3
# pages), a sink on the window kind, 4 of 16 experts held
CFG = {
    "hidden_size": 64, "intermediate_size": 128,
    "moe_intermediate_size": 32, "num_hidden_layers": 4,
    "hybrid_layer_pattern": [0, 1, 1, 0, 1, 1],
    "moe_layer_freq": [0, 1, 1, 1, 1, 1],
    "num_attention_heads": 8, "num_key_value_heads": 2,
    "swa_num_key_value_heads": 4, "swa_num_attention_heads": 8,
    "swa_head_dim": 24, "swa_v_head_dim": 16, "head_dim": 24,
    "v_head_dim": 16, "partial_rotary_factor": 0.334,
    "rope_theta": 50000, "swa_rope_theta": 100, "sliding_window": 12,
    "add_swa_attention_sink_bias": True,
    "add_full_attention_sink_bias": False,
    "attention_value_scale": 0.707, "n_routed_experts": 4,
    "router_experts": 16, "expert_offset": 4, "num_experts_per_tok": 4,
    "routed_scaling_factor": None, "vocab_size": 256,
    "layernorm_epsilon": 1e-5, "torch_dtype": "float32",
    "initializer_range": 0.3}
SEED = 2 ** 31 + 99
M = 128
PAGE = 8


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
    return build(attention_block=16)


@pytest.fixture(scope="module")
def tokens():
    return np.random.default_rng(3).integers(0, 256, 80).astype(np.int32)


def engine(model, **kw):
    pred = create_predictor(Config().set_model(model).enable_paged_kv(
        page_size=PAGE))
    return ServingEngine(pred, **kw)


def ref_logits(prompt, served, cfg=CFG):
    return ref.ServeReference(cfg, SEED).logits([(prompt, served)])[0]


# -- the model against the reference ------------------------------------------
def test_tiny_preset_has_every_mechanism():
    c = hybrid_moe_tiny()
    assert set(c.attention_kinds) == {"full", "window"}
    assert set(c.ffn_kinds) == {"dense", "experts"}
    assert c.kv_heads("full") != c.kv_heads("window")
    assert c.theta("full") != c.theta("window")
    assert c.sink("window") and not c.sink("full")
    assert c.rotary_dim < c.qk_head_dim != c.v_head_dim
    assert c.num_local_experts < c.num_experts
    assert c.max_position_embeddings > 3 * c.sliding_window


def test_full_forward_is_the_reference(model, tokens):
    got = np.asarray(model(paddle.to_tensor(tokens[None]))._value)[0]
    want = ref_logits(tokens[:1], np.append(tokens[1:], 0))
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-4)


def test_engine_prefill_then_decode_is_the_reference(model, tokens):
    """Prefill buckets + the decode program over both page classes:
    every served token's logit gap to the reference's full forward is 0
    up to float32 noise, for two ragged requests sharing the batch whose
    contexts pass three windows (12) and many pages (8): the ring of 3
    wraps and crosses page boundaries."""
    eng = engine(model, max_batch=2, debug_invariants=True)
    cache = eng.cache
    assert (cache.window, cache.ring, cache.Pw) == (12, 3, 2 * 3 + 1)
    shapes = [(a.shape, b.shape) for a, b in eng.pools]
    assert shapes == [
        ((eng.P, 2, PAGE, 128), (eng.P, 2, PAGE, 16)),
        ((7, 4, PAGE, 128), (7, 4, PAGE, 16)),
        ((7, 4, PAGE, 128), (7, 4, PAGE, 16)),
        ((eng.P, 2, PAGE, 128), (eng.P, 2, PAGE, 16))]
    prompts = (tokens[:21], tokens[5:50])
    rids = [eng.submit(prompts[0], max_new_tokens=60),
            eng.submit(prompts[1], max_new_tokens=40)]
    done = eng.run()
    for rid, prompt in zip(rids, prompts):
        served = np.asarray(done[rid].new_tokens)
        lg = ref_logits(prompt, served)
        assert ref.served_gap(lg, served).max() < 1e-3
    st = eng.moe_stats()
    assert st["dropped"] == 0
    assert st["forms"] == {"decode": "batched", "prefill": "batched"}
    assert st["rows"] == {}
    assert st["tokens"][0] == 0 and (st["tokens"][1:] > 0).all()
    np.testing.assert_array_equal(
        st["pairs"].sum(1) + st["absent_pairs"], st["tokens"] * 4)
    mem = eng.memory_summary()["state"]
    assert mem["kv_pool_bytes"] == mem["page_bytes"] * mem["pool_pages"] \
        + mem["window_page_bytes"] * mem["window_pool_pages"]
    eng.release_pools()
    assert eng.pools is None


def test_decode_is_batched_and_a_long_prefill_sorted():
    """A prompt of 150 tokens prefills in the 256 bucket, over the
    threshold: its expert layers sort and group (``ragged_dot`` in the
    program lowered for a TPU from here: traced off the TPU, and at
    widths the gate of ``ops/pallas/grouped_matmul.py`` refuses, the
    grouped product is XLA's), the decode program's (2 rows a step)
    are batched over the held experts and hold none, and the served
    tokens are the reference's through both."""
    prompt = np.random.default_rng(5).integers(0, 256, 150).astype(np.int32)
    eng = engine(build(max_len=384, attention_block=16), max_batch=2)
    rid = eng.submit(prompt, max_new_tokens=6)
    served = np.asarray(eng.run()[rid].new_tokens)
    assert ref.served_gap(ref_logits(prompt, served), served).max() < 1e-3
    st = eng.moe_stats()
    assert st["forms"] == {"decode": "batched", "prefill": "sorted"}
    assert st["grouped"] == {"decode": None, "prefill": "xla"}
    assert st["dropped"] == 0
    # the 256 bucket's 1,024 routed pairs, a quarter of them held: its
    # expert layers hold sorted_rows(256, 4, 4, 16) = 512 rows at a time
    assert st["rows"] == {256: (512, 1024)}
    assert eng.program_sites() == [("prefill", 256), ("decode",)]
    texts = {}
    for site in eng.program_sites():
        fn, avals = eng._site_programs[site]
        texts[site[0]] = fn.trace(*avals).lower(
            lowering_platforms=("tpu",)).as_text()
    assert "ragged_dot" in texts["prefill"]
    assert "ragged_dot" not in texts["decode"]
    assert not any("grouped_matmul" in t for t in texts.values())


def test_the_layers_form_follows_the_token_count_alone(model):
    """This model's expert layer (no shared expert, scaling 1): batched
    at the threshold, sorted one token above it."""
    from paddle_tpu.incubate.distributed.models.moe import moe_layer
    from paddle_tpu.observability import moestats

    layer = next(l.mlp for l in model.layers if l.is_moe)
    assert isinstance(layer, GatedMoELayer) and not layer.shared
    edge = moe_layer._BATCHED_MAX_TOKENS
    for T, form in ((edge, "batched"), (edge + 1, "sorted")):
        moestats.begin()
        try:
            jaxpr = jax.make_jaxpr(lambda v: layer(v)._value)(
                jnp.zeros((1, T, 64), jnp.float32))
        finally:
            recs = moestats.drain()
        assert [r["form"] for r in recs] == [form]
        assert ("ragged_dot" in str(jaxpr)) == (form == "sorted")
        assert [r.get("grouped") for r in recs] == \
            ["xla" if form == "sorted" else None]


def test_generate_over_the_static_cache_is_the_reference(model, tokens):
    """``Predictor.generate`` without pages: the dense masked attention
    over [B, KV, M, d] caches, window and sink included."""
    pred = create_predictor(Config().set_model(model))
    out = np.asarray(pred.generate(paddle.to_tensor(tokens[None, :30]),
                                   max_new_tokens=20)._value)[0]
    served = out[30:]
    assert ref.served_gap(ref_logits(tokens[:30], served),
                          served).max() < 1e-3


def test_the_four_shares_add_up_to_the_uncut_layer():
    """Experts 0..15 held 4 to a holder, no shared expert: the holders'
    parts add up to the layer that holds all 16, and that is the
    reference's uncut layer."""
    def layer(offset, held):
        return GatedMoELayer(64, 32, 16, held, offset, top_k=4,
                             routed_scaling_factor=1.0,
                             num_shared_experts=0)

    paddle.set_default_dtype("float32")
    whole = layer(0, 16)
    assert not whole.shared
    rng = np.random.default_rng(0)
    for p in whole.parameters():
        p._value = jnp.asarray(rng.normal(0, 0.2, p.shape), jnp.float32)
    x = jnp.asarray(rng.normal(0, 1, (24, 64)), jnp.float32)
    want = np.asarray(whole(x)._value)
    total = np.zeros_like(want)
    for off in (0, 4, 8, 12):
        part = layer(off, 4)
        for name in ("w_gate", "w_up", "w_down"):
            getattr(part, name)._value = getattr(whole, name)._value[
                off:off + 4]
        part.gate.weight._value = whole.gate.weight._value
        part.gate.bias._value = whole.gate.bias._value
        total += np.asarray(part(x)._value)
    np.testing.assert_allclose(total, want, rtol=1e-4, atol=1e-5)
    cfg = dict(CFG, n_routed_experts=16, expert_offset=0)
    with jax.default_matmul_precision("highest"):
        idx, g = ref.route(x, whole.gate.weight._value,
                           whole.gate.bias._value, cfg)
        y = sum(ref.expert_part(
            x, idx, g, j, whole.w_gate._value[j], whole.w_up._value[j],
            whole.w_down._value[j], "float32") for j in range(16))
    np.testing.assert_allclose(want, np.asarray(y), rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(np.asarray(g.sum(-1)), 1.0, rtol=1e-6)


def test_forward_scopes_name_each_layer_by_kind(model):
    """A trace's ops group by kind: ``layer{i}.attn.full`` /
    ``layer{i}.attn.window`` / ``layer{i}.moe`` in the name stacks."""
    jaxpr = jax.make_jaxpr(lambda ids: model(ids)._value)(
        jnp.zeros((1, 16), jnp.int32))
    stacks = {str(e.source_info.name_stack) for e in jaxpr.jaxpr.eqns}
    text = "\n".join(stacks)
    for scope in ("layer0.attn.full", "layer1.attn.window",
                  "layer2.attn.window", "layer3.attn.full", "layer0.mlp",
                  "layer1.moe", "layer3.moe"):
        assert scope in text, scope


# -- the kernels against their dense twins ------------------------------------
def _pools(rng, B, KV, page, Dk, Dv, ncols):
    P = B * ncols + 1
    kp = jnp.asarray(rng.standard_normal((P, KV, page, Dk)), jnp.float32)
    vp = jnp.asarray(rng.standard_normal((P, KV, page, Dv)), jnp.float32)
    tbl = rng.permutation(P - 1)[:B * ncols].reshape(B, ncols).astype(
        np.int32)
    return kp, vp, tbl


@pytest.mark.parametrize("sinks", [False, True])
@pytest.mark.parametrize("heads", [(64, 4), (64, 8)])     # 16 and 8 a KV head
def test_window_kernel_is_its_dense_twin_at_the_windows_edges(heads,
                                                              sinks):
    """Published widths (keys 192 padded to 256 against values 128),
    window 128 over pages of 128, a ring of 2: contexts below the
    window, at it (127 and 128 tokens before the new one), one past it,
    at the ring's seam (the new position is the first of a page whose
    ring column held the page two back) and deep in a later page."""
    H, KV = heads
    rng = np.random.default_rng(7)
    lens = [0, 5, 127, 128, 129, 255, 256, 257, 700]
    kp, vp, tbl = _pools(rng, len(lens), KV, 128, 256, 128, 2)
    q = jnp.asarray(rng.standard_normal((len(lens), 1, H, 256)),
                    jnp.float32)
    sk = jnp.asarray(rng.standard_normal(H), jnp.float32) if sinks else None
    kw = dict(scale=192 ** -0.5, sinks=sk, window=128)
    got = da.paged_decode_attention(q, kp, vp, tbl, jnp.asarray(lens),
                                    interpret=True, **kw)
    want = da.paged_attention_dense(q, kp, vp, tbl, jnp.asarray(lens), **kw)
    assert got.shape == (len(lens), 1, H, 128)
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("sinks", [False, True])
@pytest.mark.parametrize("heads", [(64, 4), (64, 8)])
def test_full_kernel_with_two_widths_is_its_dense_twin(heads, sinks):
    H, KV = heads
    rng = np.random.default_rng(8)
    lens = [0, 127, 128, 129, 300]
    kp, vp, tbl = _pools(rng, len(lens), KV, 128, 256, 128, 3)
    q = jnp.asarray(rng.standard_normal((len(lens), 1, H, 256)),
                    jnp.float32)
    sk = jnp.asarray(rng.standard_normal(H), jnp.float32) if sinks else None
    kw = dict(scale=192 ** -0.5, sinks=sk)
    got = da.paged_decode_attention(q, kp, vp, tbl, jnp.asarray(lens),
                                    interpret=True, **kw)
    want = da.paged_attention_dense(q, kp, vp, tbl, jnp.asarray(lens), **kw)
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)


# (Sq, G, KV, page, dtype) as tests/test_paged_ragged.py's DEPTH_CASES:
# plans of 2, 3 and 4 slots, whole and in head blocks
RING_DEPTH_CASES = [
    (512, 4, 1, 8, "float32"), (512, 4, 2, 16, "float32"),
    (1, 8, 4, 128, "bfloat16"), (1, 1, 32, 128, "bfloat16"),
    (1, 4, 2, 16, "float32"), (64, 4, 8, 128, "bfloat16"),
]

# lengths in ring columns ``c`` and pages ``pg``: rings wrapped several
# times, so that a row's first page is far from 0 and its pages sit in
# columns out of order, among short rows and free slots; rows of one
# page and free slots only, where the lookahead crosses a row at every
# visit and the last visits have nothing left to fetch
RING_WALKS = {
    "rings_wrapped_several_times": lambda c, pg, Sq: [
        3 * c * pg + 5, 0, (2 * c + 1) * pg, 1, 5 * c * pg - Sq, 0,
        c * pg - 1, (4 * c - 1) * pg + pg // 2],
    "one_page_and_free_slots": lambda c, pg, Sq: [
        0, min(5, max(pg - Sq, 0)), 0, 0, max(pg - Sq, 0), 0, 0],
}


@pytest.mark.parametrize("sinks", [False, True])
@pytest.mark.parametrize("walk", sorted(RING_WALKS))
@pytest.mark.parametrize("Sq,G,KV,page,dtype", RING_DEPTH_CASES)
def test_window_kernel_keeps_its_fetches_in_flight_across_rows(
        Sq, G, KV, page, dtype, walk, sinks):
    """The window kernel at every depth ``_paged_plan`` can return: a
    window of two pages and a bit, the ring that holds it."""
    window = 2 * page + 3
    ring = (window + Sq - 2) // page + 2
    lens = np.asarray(RING_WALKS[walk](ring, page, Sq), np.int32)
    rng = np.random.default_rng(Sq + 7 * G + 31 * KV + page + len(walk))
    kp, vp, tbl = _pools(rng, len(lens), KV, page, 128, 128, ring)
    q = jnp.asarray(rng.standard_normal((len(lens), Sq, KV * G, 128)),
                    dtype)
    kp, vp = kp.astype(dtype), vp.astype(dtype)
    sk = jnp.asarray(rng.standard_normal(KV * G), jnp.float32) \
        if sinks else None
    got = da.paged_decode_attention(q, kp, vp, tbl, jnp.asarray(lens),
                                    interpret=True, sinks=sk, window=window)
    want = da.paged_attention_dense(q, kp, vp, tbl, jnp.asarray(lens),
                                    sinks=sk, window=window)
    tol = 2e-5 if dtype == "float32" else 3e-2
    np.testing.assert_allclose(got.astype(jnp.float32),
                               want.astype(jnp.float32), rtol=tol, atol=tol)


def test_window_dense_twin_sees_exactly_the_window():
    """The twin itself against attention written out over a contiguous
    cache: a ring of 3 pages of 8 filled position by position, window
    12, the row's last 12 positions and no others weigh in."""
    rng = np.random.default_rng(9)
    KV, H, page, ring, W, ctx = 2, 4, 8, 3, 12, 45
    k = rng.standard_normal((ctx + 1, KV, 128)).astype(np.float32)
    v = rng.standard_normal((ctx + 1, KV, 16)).astype(np.float32)
    kp = np.zeros((ring + 1, KV, page, 128), np.float32)
    vp = np.zeros((ring + 1, KV, page, 16), np.float32)
    tbl = np.asarray([[2, 0, 1]], np.int32)
    for pos in range(ctx + 1):           # later pages overwrite earlier
        pid = tbl[0, (pos // page) % ring]
        kp[pid, :, pos % page], vp[pid, :, pos % page] = k[pos], v[pos]
    q = rng.standard_normal((1, 1, H, 128)).astype(np.float32)
    sk = rng.standard_normal(H).astype(np.float32)
    got = np.asarray(da.paged_attention_dense(
        jnp.asarray(q), jnp.asarray(kp), jnp.asarray(vp), tbl,
        jnp.asarray([ctx]), 0.3, jnp.asarray(sk), W))[0, 0]
    for h in range(H):
        ks, vs = k[ctx - W + 1:, h // 2], v[ctx - W + 1:, h // 2]
        e = np.exp(0.3 * ks @ q[0, 0, h])
        want = (e / (e.sum() + np.exp(sk[h]))) @ vs
        np.testing.assert_allclose(got[h], want, rtol=1e-4, atol=1e-5)


def test_kernel_gate_and_head_block_follow_the_shapes():
    """Mistral's call picks the block it picked before the two widths;
    the window and full calls of the published widths take every KV
    head in one fetch."""
    assert da.paged_supported((48, 1, 32, 128), (1024, 8, 128, 128))
    assert da._paged_plan(1, 4, 8, 128, 128, 2).hb == 8
    assert da._paged_vmem_bytes(8, 1, 4, 128, 128, 2) \
        == da._paged_vmem_bytes(8, 1, 4, 128, 128, 2, 128)
    assert da.paged_supported((128, 1, 64, 256), (257, 8, 128, 256),
                              (257, 8, 128, 128))
    assert not da.paged_supported((128, 1, 64, 256), (257, 8, 128, 256),
                                  (257, 8, 128, 64))
    # depth never costs a head: MiMo's window call keeps its 8, its full
    # call and Trinity's two (8 query heads on each of 4 KV heads) 4
    assert da._paged_plan(1, 8, 8, 128, 256, 2, 128).hb == 8
    assert da._paged_plan(1, 16, 4, 128, 256, 2, 128).hb == 4
    assert da._paged_plan(1, 8, 4, 128, 128, 2).hb == 4


def test_ring_write_lands_at_the_position_s_ring_column():
    page, ring, KV = 8, 3, 2
    kp = jnp.zeros((7, KV, page, 128)); vp = jnp.zeros((7, KV, page, 16))
    tbl = np.asarray([[4, 2, 5], [0, 1, 3]], np.int32)
    pos = np.asarray([29, 8])             # pages 3 and 1: columns 0 and 1
    k = jnp.ones((2, 1, KV, 128)); v = jnp.ones((2, 1, KV, 16))
    kp, vp = da.paged_kv_write(kp, vp, k, v, tbl, jnp.asarray(pos),
                               ring=True)
    hit = np.argwhere(np.asarray(kp)[:, 0, :, 0] == 1).tolist()
    assert hit == [[1, 0], [4, 5]]        # (page id, slot)
    assert float(vp.sum()) == 2 * KV * 16


@pytest.mark.parametrize("window", [None, 12])
@pytest.mark.parametrize("sinks", [False, True])
def test_blockwise_prefill_attention_is_attention_written_out(window,
                                                              sinks):
    rng = np.random.default_rng(4)
    S, H, KV, Dk, Dv = 50, 8, 2, 24, 16
    q = jnp.asarray(rng.standard_normal((2, S, H, Dk)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((2, S, KV, Dk)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((2, S, KV, Dv)), jnp.float32)
    sk = jnp.asarray(rng.standard_normal(H), jnp.float32) if sinks else None
    got = blockwise_causal_attention(q, k, v, 0.2, window, sk, block=16)
    pos = jnp.broadcast_to(jnp.arange(S), (2, S))
    want = da.attention_dense_masked(
        q, jnp.swapaxes(k, 1, 2), jnp.swapaxes(v, 1, 2), pos,
        jnp.zeros(2, jnp.int32), 0.2, sk, window)
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)


def test_prefill_program_builds_no_heads_by_rows_by_rows_array(model,
                                                               tokens):
    """The tiny preset's prefill at bucket 64 (rows a block 16): no
    float32 buffer of [8 heads, 64, 64] elements or more with the rows
    twice in it, in the program as compiled."""
    eng = engine(model, max_batch=1)
    eng.submit(tokens[:60], max_new_tokens=2)
    eng.run()
    text = eng.compiled_text(("prefill", 64))
    shapes = {tuple(int(d) for d in s.split(",") if d)
              for s in re.findall(r"f32\[([\d,]*)\]", text)}
    assert shapes
    assert not any(s.count(64) >= 2 and np.prod(s) >= 8 * 64 * 64
                   for s in shapes), sorted(shapes)


# -- two classes of pages ----------------------------------------------------
def test_page_accounting_of_both_classes(model, tokens):
    """A row's window pages are the same from admission to its end;
    both classes come back to free at finish; counts() and the gauges
    say so per class; the spans carry both counts."""
    eng = engine(model, max_batch=2, debug_invariants=True)
    c0 = eng.cache.counts()["classes"]
    assert c0["window"] == {"used": 0, "free": 6}
    rid = eng.submit(tokens[:30], max_new_tokens=50)
    eng.step()
    ring = list(eng.cache.wtables[0])
    assert eng.cache.counts()["classes"]["window"] == {"used": 3,
                                                      "free": 3}
    assert eng.cache.counts()["classes"]["full"]["used"] == 10
    seen = set()
    while eng.num_active:
        eng.step()
        if eng.slots[0] is not None:
            seen.add(tuple(eng.cache.wtables[0]))
    assert seen == {tuple(ring)}
    snap = eng.metrics_snapshot()["metrics"]
    rows = {(r["labels"]["class"], r["labels"]["state"]): r["value"]
            for r in snap["paddle_tpu_serving_kv_pages"]["series"]}
    assert rows[("window", "free")] == 6 and rows[("window", "used")] == 0
    assert rows[("full", "used")] == 0
    ratio = snap["paddle_tpu_serving_kv_bytes_per_context_token"][
        "series"][0]["value"]
    pb, wb = eng.cache.page_bytes, eng.cache.window_page_bytes
    assert (10 * pb + 3 * wb) / 80 <= ratio <= (10 * pb + 3 * wb) / 31
    assert eng.cache.counts()["classes"] == c0
    eng.check_invariants()
    spans = {s["name"]: s for s in eng.request_traces()[0]["spans"]}
    for name in ("prefill", "decode"):
        assert spans[name]["meta"]["full_pages"] == 10
        assert spans[name]["meta"]["window_pages"] == 3
    assert len(eng.finished[rid].new_tokens) == 50


def test_admission_waits_for_either_class():
    """The cache alone: rows take a ring each until the window class is
    out, whatever the full class has left; a released row gives both
    back (what a finish and a preemption both call)."""
    m = HybridMoEForCausalLM(hybrid_moe_tiny())
    cache = PagedKVCache(m, PAGE, 64, 2, jnp.float32)
    assert cache.rings_available()
    pages = [cache.allocate(3), cache.allocate(2)]
    for b in (0, 1):
        cache.set_row(b, pages[b])
        cache.take_ring(b)
    assert not cache.rings_available() and cache.available() > 0
    cache.check_invariants(pages, live_rows=[0, 1])
    np.testing.assert_array_equal(
        cache.window_prefill_rows(1, 30)[0],
        [cache.wtrash] + [cache.wtables[1, l % 3] for l in (1, 2, 3)]
        + [cache.wtrash] * 4)
    cache.release_row(1, pages[1])
    assert cache.rings_available()
    assert (cache.wtables[1] == cache.wtrash).all()
    cache.check_invariants(pages[:1], live_rows=[0])
    with pytest.raises(Exception, match="rows holding a ring"):
        cache.check_invariants(pages[:1], live_rows=[0, 1])


def test_check_invariants_catches_a_leaked_ring_page(model, tokens):
    eng = engine(model, max_batch=2)
    eng.submit(tokens[:20], max_new_tokens=4)
    eng.run()
    eng.check_invariants()
    eng.cache._wfree.pop()                  # a ring page nobody holds
    with pytest.raises(Exception, match="leaked or doubly held"):
        eng.check_invariants()


def test_pool_pages_auto_counts_both_classes(model):
    eng = engine(model, max_batch=2, pool_pages="auto")
    assert eng.cache.Pw == 7 and eng.P >= 8
    assert eng.cache.pool_bytes() == eng.cache.pool_bytes(window=False) \
        + eng.cache.pool_bytes(window=True)
    assert eng.cache.pool_bytes(window=True) \
        == eng.cache.Pw * eng.cache.window_page_bytes


REFUSED = {
    "chunked prefill": (dict(prefill_chunk=16), "unified ragged step"),
    "prefix cache": (dict(prefix_cache=True), "prefix cache"),
    "host spill": (dict(host_spill_pages=4), "prefix cache"),
    "speculative decoding": (dict(spec_tokens=2, draft_predictor="self"),
                             "speculative decoding"),
    "phase": (dict(phase="decode"), "disaggregated phases"),
}


@pytest.mark.parametrize("mode", sorted(REFUSED))
def test_engine_modes_a_ring_cannot_serve_are_refused(model, mode):
    kw, why = REFUSED[mode]
    pred = create_predictor(Config().set_model(model).enable_paged_kv(
        page_size=PAGE))
    kw = {k: pred if v == "self" else v for k, v in kw.items()}
    with pytest.raises(Exception, match=why) as e:
        ServingEngine(pred, max_batch=2, **kw)
    assert "window layers" in str(e.value)


def test_cache_paths_a_ring_cannot_serve_are_refused(model):
    m = model
    with pytest.raises(Exception, match="host spill tier"):
        PagedKVCache(m, PAGE, 64, 2, jnp.float32, spill_pages=2)
    with pytest.raises(Exception, match="draft"):
        PagedKVCache(m, PAGE, 64, 2, jnp.float32, draft=(m, jnp.float32))
    cache = PagedKVCache(m, PAGE, 64, 2, jnp.float32)
    with pytest.raises(Exception, match="migration"):
        cache.check_stackable()
    with pytest.raises(Exception, match="copy-on-write"):
        cache.copy_on_write(0)
    pred = create_predictor(Config().set_model(m).enable_paged_kv(
        page_size=PAGE))
    with pytest.raises(Exception, match="Predictor.generate over the paged"):
        pred.generate(paddle.to_tensor(np.zeros((1, 9), np.int64)),
                      max_new_tokens=2)


@pytest.mark.parametrize("name", ["llama", "mla_moe"])
def test_engines_without_window_layers_keep_one_class(name):
    """A Mistral-shaped and a latent-attention engine: one page class,
    no ring, the round array ``[B, npages + 3]``, and a decode program
    without the window kernel's name or a second table."""
    paddle.seed(0)
    m = LlamaForCausalLM(llama_tiny()) if name == "llama" \
        else MLAMoEForCausalLM(mla_moe_tiny())
    eng = engine(m, max_batch=2)
    rid = eng.submit(np.arange(11) % 50, max_new_tokens=5)
    assert len(eng.run()[rid].new_tokens) == 5
    cache = eng.cache
    assert (cache.window, cache.ring, cache.Pw) == (None, 0, 0)
    assert not any(cache.window_layers)
    assert list(cache.counts()["classes"]) == ["full"]
    assert cache.pool_bytes() == cache.page_bytes * cache.P
    text = eng.lowered_text(("decode",))
    assert f"tensor<2x{cache.npages + 3}xi32>" in text
    assert "paged_window" not in text
    spans = {s["name"]: s for s in eng.request_traces()[0]["spans"]}
    assert spans["prefill"]["meta"]["window_pages"] == 0


@pytest.mark.slow      # a 40 s many-core compile: kept out of the tier-1
def test_serving_programs_compile_for_a_v5e_in_place():   # run's workers
    """The engine's own decode and prefill programs at the benchmark
    configuration's widths (three layers: full, window, window; no
    weights), compiled by the TPU compiler for a described v5e in a
    process of its own: both decode kernels in decode and neither in
    prefill, 0 copies of a pool of either class, no float32 buffer of
    [64 heads, 1024, 1024] elements in the prefill program."""
    import json
    import subprocess

    p = subprocess.run(
        [sys.executable, os.path.join(ROOT, "tools", "mla_serving_aot.py"),
         "--config", "mimo-v2-flash", "--layers", "3"],
        capture_output=True, text=True, timeout=900)
    assert p.returncode == 0, p.stderr[-2000:]
    out = json.loads(p.stdout.strip().splitlines()[-1])
    if "skipped" in out:
        pytest.skip(out["skipped"])
    progs = {c["program"]: c for c in out["programs"]}
    assert set(progs) == {"decode", "prefill_1024"}
    for c in progs.values():
        assert c["pool_copies"] == 0 and c["expert_weight_copies"] == 0, c
    # batched over the 16 held experts in decode, sorted and grouped in
    # the prefill, on our kernel
    assert not progs["decode"]["ragged_dot"]
    assert not progs["prefill_1024"]["ragged_dot"]
    assert progs["decode"]["kernels"] == [
        "paged_decode_attention", "paged_window_decode_attention"]
    assert progs["prefill_1024"]["kernels"] == ["grouped_matmul"]
    assert progs["prefill_1024"]["largest_f32_elements"] < 64 * 1024 * 1024
    donated = progs["decode"]["donated"]
    assert len(donated) == 3 * 3, donated       # 3 layers x (k, v, counter)
    assert all(n.startswith("state") for n in donated), donated
