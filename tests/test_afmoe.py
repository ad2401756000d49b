"""The AFMoE block on ``models/hybrid_moe.py``'s switches (rotary on the
window layers only, q/k head norms, the gated attention result, sandwich
norms, the embedding multiplier, a shared expert, the head on a
prefill's last row) against the plain float32 reference
(benchmarks/references/trinity.py) at a tiny size on the CPU, seeded
weights; the page cache at a ring of 17 pages; and MiMo's block, which
the switches' defaults must leave as it was, down to the text of its
serving programs.
"""
import hashlib
import os
import sys
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import paddle_tpu as paddle  # noqa: E402
from benchmarks.harness import weights  # noqa: E402
from benchmarks.harness.families import afmoe_serving as fam  # noqa: E402
from benchmarks.references import trinity as ref  # noqa: E402
from paddle_tpu.incubate.distributed.models.moe import GatedMoELayer  # noqa: E402
from paddle_tpu.inference import (Config, ServingEngine,  # noqa: E402
                                  create_predictor)
from paddle_tpu.inference.kv_cache import PagedKVCache  # noqa: E402
from paddle_tpu.models.hybrid_moe import (HybridMoEConfig,  # noqa: E402
                                          HybridMoEForCausalLM, afmoe_tiny,
                                          hybrid_moe_tiny)

# the tiny twin of benchmarks/configs/trinity-mini.json, in the source's
# key names: 8 published layers of which 6 are run (0, 1 dense; 3 and 7
# full), 8 query heads on 2 KV heads of 16, a window of 24 over pages of
# 8 (a ring of 4 pages), 4 of 16 experts held beside a shared one
CFG = {
    "hidden_size": 64, "intermediate_size": 128,
    "moe_intermediate_size": 32, "num_hidden_layers": 6,
    "layer_types": ["sliding_attention"] * 3 + ["full_attention"]
    + ["sliding_attention"] * 3 + ["full_attention"],
    "layers_run": [0, 1, 2, 3, 4, 7], "num_dense_layers": 2,
    "num_attention_heads": 8, "num_key_value_heads": 2, "head_dim": 16,
    "rope_theta": 100, "sliding_window": 24, "num_experts": 4,
    "router_experts": 16, "expert_offset": 8, "num_experts_per_tok": 4,
    "route_scale": 2.826, "num_shared_experts": 1, "mup_enabled": True,
    "vocab_size": 256, "rms_norm_eps": 1e-5, "torch_dtype": "float32",
    "initializer_range": 0.3}
SEED = 2 ** 31 + 35
M = 160
PAGE = 8
SWITCHES_ON = dict(rotary_kinds=("window",), qk_norm=True,
                   attention_gate=True, sandwich_norm=True,
                   embedding_multiplier=8.0, num_shared_experts=1,
                   head_on_last_row=True)


def build(cfg=CFG, seed=SEED, max_len=M, **kw):
    paddle.set_default_dtype("float32")
    mcfg = fam.model_config(cfg, max_len)
    for k, v in dict(attention_block=16, **kw).items():
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
    return np.random.default_rng(35).integers(0, 256, 120).astype(np.int32)


def engine(model, **kw):
    pred = create_predictor(Config().set_model(model).enable_paged_kv(
        page_size=PAGE))
    return ServingEngine(pred, **kw)


def ref_logits(prompt, served, cfg=CFG):
    return ref.ServeReference(cfg, SEED).logits([(prompt, served)])[0]


# -- (a) the model against the reference --------------------------------------
def test_tiny_preset_has_every_switch_on_and_is_the_family_s_mapping():
    c = afmoe_tiny()
    for k, v in SWITCHES_ON.items():
        assert getattr(c, k) == v, k
    assert set(c.attention_kinds) == {"full", "window"}
    assert c.ffn_kinds[:3] == ["dense", "dense", "experts"]
    assert c.kv_heads("full") == c.kv_heads("window")
    assert c.qk_head_dim == c.v_head_dim == c.rotary_dim
    assert not (c.full_sink or c.window_sink)
    assert c.num_local_experts < c.num_experts
    assert -(-c.sliding_window // PAGE) + 1 >= 4
    assert c.max_position_embeddings > 4 * c.sliding_window
    # what the benchmark's family builds from the source's key names is
    # this preset (sizes of the test's CFG)
    got = fam.model_config(CFG, c.max_position_embeddings)
    for f in ("attention_kinds", "ffn_kinds", "num_heads", "num_kv_heads",
              "window_num_kv_heads", "qk_head_dim", "v_head_dim",
              "rotary_dim", "sliding_window", "num_experts",
              "num_local_experts", "expert_offset", "num_experts_per_tok",
              "routed_scaling_factor", *SWITCHES_ON):
        assert getattr(got, f) == getattr(c, f), f


def test_full_forward_is_the_reference(model, tokens):
    t = tokens[:80]
    got = np.asarray(model(paddle.to_tensor(t[None]))._value)[0]
    want = ref_logits(t[:1], np.append(t[1:], 0))
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-4)


def test_engine_prefill_then_decode_is_the_reference(model, tokens):
    """Prefill (the head on the last row) then the decode program over
    both page classes: every served token's logit gap to the
    reference's full forward is 0 up to float32 noise, for two ragged
    requests sharing the batch whose contexts pass four windows (24)
    and four rings (4 pages of 8): the ring wraps and a row's write
    lands over what the window has left."""
    eng = engine(model, max_batch=2, debug_invariants=True)
    cache = eng.cache
    assert (cache.window, cache.ring, cache.Pw) == (24, 4, 2 * 4 + 1)
    kinds = model.config.attention_kinds
    assert [a.shape[0] for a, _ in eng.pools] == [
        cache.Pw if k == "window" else eng.P for k in kinds]
    assert {a.shape[1:] for a, _ in eng.pools} == {(2, PAGE, 128)}
    assert {b.shape[1:] for _, b in eng.pools} == {(2, PAGE, 16)}
    prompts = (tokens[:21], tokens[5:75])
    rids = [eng.submit(prompts[0], max_new_tokens=110),
            eng.submit(prompts[1], max_new_tokens=80)]
    done = eng.run()
    for rid, prompt in zip(rids, prompts):
        served = np.asarray(done[rid].new_tokens)
        assert len(prompt) + len(served) > 4 * cache.ring * PAGE
        lg = ref_logits(prompt, served)
        assert ref.served_gap(lg, served).max() < 1e-3
    st = eng.moe_stats()
    assert st["dropped"] == 0
    assert (st["tokens"][:2] == 0).all() and (st["tokens"][2:] > 0).all()
    np.testing.assert_array_equal(
        st["pairs"].sum(1) + st["absent_pairs"], st["tokens"] * 4)
    snap = eng.metrics_snapshot()["metrics"]
    fill = snap["paddle_tpu_serving_window_ring_fill"]["series"][0]["value"]
    assert fill == 1.0            # both rows ended past their rings
    rows = {r["labels"]["kind"]: r["value"] for r in snap[
        "paddle_tpu_serving_prefill_tokens_total"]["series"]}
    assert rows["prompt"] >= 21 + 70 and rows["bucket"] >= 64 + 128


def test_window_ring_fill_of_a_short_request(model, tokens):
    """A row whose context ends inside its first ring page fills a
    quarter of its ring of 4."""
    eng = engine(model, max_batch=1)
    eng.submit(tokens[:3], max_new_tokens=4)
    eng.run()
    snap = eng.metrics_snapshot()["metrics"]
    assert snap["paddle_tpu_serving_window_ring_fill"]["series"][0][
        "value"] == 0.25


def test_forward_scopes_name_each_layer_and_the_head(model):
    jaxpr = jax.make_jaxpr(
        lambda ids, n: model.forward(ids, lengths=n)._value)(
        jnp.zeros((1, 16), jnp.int32), jnp.asarray([9], jnp.int32))
    text = "\n".join({str(e.source_info.name_stack)
                      for e in jaxpr.jaxpr.eqns})
    for scope in ("layer0.attn.window", "layer3.attn.full", "layer0.mlp",
                  "layer1.mlp", "layer2.moe", "layer5.moe", "head"):
        assert scope in text, scope


# -- (b) the shares ------------------------------------------------------------
def test_the_eight_shares_and_one_shared_expert_add_up_to_the_uncut_layer():
    """Experts 0..15 held 2 to a holder on 8 holders: the holders'
    ROUTED parts, plus the shared expert counted ONCE (every holder
    computes the same one), add up to the layer that holds all 16, and
    that is the reference's uncut step 7."""
    def layer(offset, held, shared):
        return GatedMoELayer(64, 32, 16, held, offset, top_k=4,
                             routed_scaling_factor=2.826,
                             num_shared_experts=shared)

    paddle.set_default_dtype("float32")
    whole = layer(0, 16, 1)
    assert whole.shared
    rng = np.random.default_rng(0)
    for p in whole.parameters():
        p._value = jnp.asarray(rng.normal(0, 0.2, p.shape), jnp.float32)
    x = jnp.asarray(rng.normal(0, 1, (24, 64)), jnp.float32)
    want = np.asarray(whole(x)._value)
    total = np.zeros_like(want)
    for off in range(0, 16, 2):
        part = layer(off, 2, 0)
        for name in ("w_gate", "w_up", "w_down"):
            getattr(part, name)._value = getattr(whole, name)._value[
                off:off + 2]
        part.gate.weight._value = whole.gate.weight._value
        part.gate.bias._value = whole.gate.bias._value
        total += np.asarray(part(x)._value)
    sh = [getattr(whole, "shared_" + n)._value
          for n in ("gate", "up", "down")]
    cfg = dict(CFG, num_experts=16, expert_offset=0)
    with jax.default_matmul_precision("highest"):
        shared = np.asarray(ref.swiglu(x, *sh, "float32"))
        idx, w = ref.route(x, whole.gate.weight._value,
                           whole.gate.bias._value, cfg)
        y = sum(ref.expert_part(
            x, idx, w, j, whole.w_gate._value[j], whole.w_up._value[j],
            whole.w_down._value[j], "float32") for j in range(16))
    np.testing.assert_allclose(total + shared, want, rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(want, np.asarray(y) + shared, rtol=1e-4,
                               atol=1e-5)
    np.testing.assert_allclose(np.asarray(w.sum(-1)), 2.826, rtol=1e-6)


# -- (c) the switches: off is MiMo's block -------------------------------------
MIMO_PARAMS_OF_A_LAYER = {
    "input_layernorm", "post_attention_layernorm", "self_attn.q_proj",
    "self_attn.k_proj", "self_attn.v_proj", "self_attn.o_proj"}
ADDED = {
    "rotary_kinds": set(), "embedding_multiplier": set(),
    "head_on_last_row": set(),
    "qk_norm": {"self_attn.q_norm", "self_attn.k_norm"},
    "attention_gate": {"self_attn.gate_proj"},
    "sandwich_norm": {"attention_out_layernorm", "mlp_out_layernorm"},
    "num_shared_experts": {"mlp.shared_gate", "mlp.shared_up",
                           "mlp.shared_down"}}


def _layer_params(model, i):
    pre = f"layers.{i}."
    return {n[len(pre):] for n, _ in model.named_parameters()
            if n.startswith(pre)}


def test_defaults_are_every_switch_off():
    c = HybridMoEConfig()
    assert (c.rotary_kinds, c.qk_norm, c.attention_gate, c.sandwich_norm,
            c.embedding_multiplier, c.num_shared_experts,
            c.head_on_last_row) == (("full", "window"), False, False,
                                    False, 1.0, 0, False)
    m = HybridMoEForCausalLM(hybrid_moe_tiny())
    assert not m.head_on_last_row
    assert _layer_params(m, 3) == MIMO_PARAMS_OF_A_LAYER | {
        "mlp.w_gate", "mlp.w_up", "mlp.w_down", "mlp.gate.weight",
        "mlp.gate.bias"}                       # a full expert layer
    assert _layer_params(m, 1) - _layer_params(m, 3) == {"self_attn.sinks"}


@pytest.mark.parametrize("switch", sorted(SWITCHES_ON))
def test_one_switch_on_adds_its_parameters_and_changes_the_function(
        switch, tokens):
    """MiMo's tiny block with ONE switch on: the parameters it adds are
    that switch's and no other's, every parameter the two models share
    gets the same values, and the logits move (``head_on_last_row``
    moves no logit: a prefill's one row is the gathered row, below)."""
    paddle.set_default_dtype("float32")
    off = HybridMoEForCausalLM(hybrid_moe_tiny())
    on = HybridMoEForCausalLM(hybrid_moe_tiny(**{
        switch: SWITCHES_ON[switch]}))
    assert _layer_params(on, 3) - _layer_params(off, 3) == ADDED[switch]
    assert _layer_params(off, 3) <= _layer_params(on, 3)
    rng = np.random.default_rng(1)
    base = dict(off.named_parameters())
    for n, p in base.items():
        p._value = jnp.asarray(rng.normal(0, 0.3, p.shape), jnp.float32)
    for n, p in on.named_parameters():
        p._value = base[n]._value if n in base else jnp.asarray(
            rng.normal(0, 0.3, p.shape), jnp.float32)
    ids = paddle.to_tensor(tokens[None, :40])
    a = np.asarray(off(ids)._value)
    b = np.asarray(on(ids)._value)
    if switch == "head_on_last_row":
        np.testing.assert_array_equal(a, b)
    else:
        assert np.abs(a - b).max() > 1e-3
    if switch == "rotary_kinds":
        full = [l.self_attn for l in on.layers if l.attn_kind == "full"]
        assert full and not any(hasattr(s, "_rope") for s in full)
        assert all(hasattr(l.self_attn, "_rope") for l in on.layers
                   if l.attn_kind == "window")


# sha256 of the StableHLO text of the tiny MiMo engine's programs, read
# from the parent commit (fead892) by this very code on the CPU: a change
# that alters what MiMo's block traces to changes them
MIMO_PROGRAMS = {
    ("prefill", 64):
        "947e01155876be704576a1aaf5cad87e882b25fdb57202cd2f16384a7cd6b594",
    ("decode",):
        "9c99701abb4520ae663c6a4cc1fabab2d27bd0e3dcc01db9157ed43c0ef28631",
}


def mimo_program_hashes():
    paddle.set_default_dtype("float32")
    model = HybridMoEForCausalLM(hybrid_moe_tiny())
    model.eval()
    eng = engine(model, max_batch=2)
    eng.submit(np.arange(40, dtype=np.int32), max_new_tokens=3)
    eng.run()
    return {site: hashlib.sha256(
        eng.lowered_text(site).encode()).hexdigest()
        for site in eng.program_sites()}


def test_mimo_s_serving_programs_are_the_parent_s_text():
    assert mimo_program_hashes() == MIMO_PROGRAMS


# -- (d) the head on the last row ----------------------------------------------
def test_last_row_head_is_the_gathered_row_of_the_full_logits(model,
                                                               tokens):
    """The same weights with the switch off: the prefill program
    computes ``[B, S, vocab]`` and gathers; on, ``[B, vocab]`` comes out
    of the model. Ragged rows of one bucket."""
    import copy

    whole = HybridMoEForCausalLM(copy.copy(model.config))
    whole.config.head_on_last_row = False
    whole.eval()
    for (_, p), (_, q) in zip(whole.named_parameters(),
                              model.named_parameters()):
        p._value = q._value
    assert model.head_on_last_row and not whole.head_on_last_row
    ids = np.zeros((2, 64), np.int32)
    ids[0, :37], ids[1, :64] = tokens[:37], tokens[10:74]
    lengths = jnp.asarray([37, 64], jnp.int32)
    out = {}
    for name, m in (("last", model), ("whole", whole)):
        pred = create_predictor(Config().set_model(m))
        caches = m._empty_caches(2, M, jnp.float32)
        fn = pred._prefill_fn(2, 64, M)
        out[name], _ = fn(tuple(p._value for p in pred._params),
                          jnp.asarray(ids), caches, lengths)
        text = fn.lower(tuple(p._value for p in pred._params),
                        jnp.asarray(ids), m._empty_caches(2, M, jnp.float32),
                        lengths).as_text()
        # [2, 64, 256] logits exist only in the program that gathers
        assert ("tensor<2x64x256xf32>" in text) == (name == "whole")
    assert out["last"].shape == (2, 256)
    np.testing.assert_allclose(out["last"], out["whole"], rtol=1e-5,
                               atol=1e-5)
    full = np.asarray(model(paddle.to_tensor(ids[:1, :37]))._value)[0]
    np.testing.assert_allclose(out["last"][0], full[36], rtol=2e-4,
                               atol=2e-4)


# -- (e) the page cache at a ring of 17 ----------------------------------------
WINDOW, BIG_PAGE = 128, 8          # ceil(128 / 8) + 1 = 17 pages a row


def ring_model():
    """What a cache asks of a model: 2 window layers and a full one."""
    kinds = [("window", WINDOW), "full", ("window", WINDOW)]
    return SimpleNamespace(
        kv_pool_shapes=lambda P, page: [
            ((P, 2, page, 128), (P, 2, page, 128))] * 3,
        kv_page_classes=lambda: kinds)


def ring_cache(rows=3):
    return PagedKVCache(ring_model(), BIG_PAGE, 1024, rows, jnp.float32,
                        pool_pages=64)


def test_ring_of_17_geometry_and_admission():
    c = ring_cache()
    assert (c.ring, c.Pw, c.wtrash) == (17, 3 * 17 + 1, 51)
    assert c.window_layers == [True, False, True]
    assert [a.shape[0] for a, _ in c.pools] == [52, 64, 52]
    assert c.window_page_bytes == 2 * 2 * 2 * BIG_PAGE * 128 * 4
    pages = [c.allocate(5), c.allocate(2)]
    for b in (0, 1):
        c.set_row(b, pages[b])
        assert sorted(c.take_ring(b)) == sorted(c.wtables[b])
    c.check_invariants(pages, live_rows=[0, 1])
    assert c.rings_available() and len(c._wfree) == 17
    # fewer than a ring's 17 pages free: no admission, whatever the full
    # class has left
    leaked = c._wfree.pop()
    assert not c.rings_available() and c.available() > 0
    with pytest.raises(Exception, match="out of pages"):
        c.take_ring(2)
    c._wfree.append(leaked)
    c.release_row(0, pages[0])
    assert len(c._wfree) == 34 and (c.wtables[0] == c.wtrash).all()
    c.check_invariants(pages[1:], live_rows=[1])
    c._wfree.pop()
    with pytest.raises(Exception, match="leaked or doubly held"):
        c.check_invariants(pages[1:], live_rows=[1])


@pytest.mark.parametrize("length", [
    1, 8, 9,                   # inside the first page, at its edge, over
    100, 136,                  # shorter than the ring, the ring exactly
    137, 17 * 8 * 4 + 3, 1024  # one page over; several rings; the table
])
def test_window_prefill_rows_at_a_ring_of_17(length):
    """The prompt's last 17 logical pages map to their ring columns
    (logical page l in column l % 17), every other logical page (older
    than the window reaches, or the bucket's padding) to the trash
    page; each ring column is named at most once."""
    c = ring_cache(rows=2)
    c.take_ring(0)
    ring = c.take_ring(1)
    row = c.window_prefill_rows(1, length)[0]
    assert row.shape == (1024 // BIG_PAGE,)
    last = (length - 1) // BIG_PAGE
    kept = list(range(max(0, last - 16), last + 1))
    for l in range(len(row)):
        if l in kept:
            assert row[l] == c.wtables[1, l % 17]
        else:
            assert row[l] == c.wtrash
    named = [p for p in row if p != c.wtrash]
    assert len(named) == len(set(named)) == min(last + 1, 17)
    assert set(named) <= set(ring)
    # every position the window of the NEXT token can reach is on a kept
    # page (the window never looks behind the ring)
    assert (max(0, length - WINDOW + 1)) // BIG_PAGE >= kept[0]


def test_ring_of_17_wraps_under_the_engine():
    """A model whose window of 128 over pages of 8 is a ring of 17,
    served past two rings: the tokens are its full forward's."""
    paddle.set_default_dtype("float32")
    cfg = afmoe_tiny(sliding_window=WINDOW, max_position_embeddings=320,
                     attention_kinds=["window", "full"],
                     ffn_kinds=["dense", "experts"])
    model = HybridMoEForCausalLM(cfg)
    model.eval()
    eng = engine(model, max_batch=2, debug_invariants=True)
    assert eng.cache.ring == 17
    prompt = np.random.default_rng(2).integers(0, 256, 150).astype(np.int32)
    rid = eng.submit(prompt, max_new_tokens=160)
    eng.submit(prompt[:5], max_new_tokens=6)
    out = np.asarray(eng.run()[rid].new_tokens)
    seq = np.concatenate([prompt, out])
    assert len(seq) > 2 * 17 * PAGE
    full = np.asarray(model(paddle.to_tensor(seq[None]))._value)[0]
    gap = full[149:-1].max(-1) - np.take_along_axis(
        full[149:-1], out[:, None], axis=1)[:, 0]
    assert gap.max() < 1e-3
    eng.check_invariants()
    assert eng.cache.counts()["classes"]["window"]["used"] == 0


def test_closed_loop_model_counts_what_the_benchmark_counts():
    """tools/closed_loop_model.py: the benchmark's own stream, so a seed
    gives one number, another seed another one near it, and the rate is
    the cell's (PERF.md section 6, PR 35: 17,135 on the chip)."""
    import json

    from tools import closed_loop_model as clm

    params = json.load(open(os.path.join(
        ROOT, "benchmarks", "traffic", "mixedlen-batch.json")))
    a, again, b = (clm.run(params, s, 48.0) for s in (7, 7, 3000000019))
    assert a == again and a != b
    assert 15_000 < a < 19_000 and abs(a - b) < 0.1 * a
    assert clm.spread([1.0, 2.0, 3.0, 4.0, 5.0, 60.0]) > \
        clm.spread_without_farthest([1.0, 2.0, 3.0, 4.0, 5.0, 60.0])


# -- (g) the prompt's own attention: the flash kernel where the shapes allow ---
def wide_model(**kw):
    """Trinity's shape of layer at a tiny size: 8 query heads on one KV
    head, q, k and v all 128 wide, no sink, a window (150) that is no
    multiple of the kernel's block and passes one; a window, a full and
    a second window layer."""
    paddle.set_default_dtype("float32")
    paddle.seed(46)
    cfg = afmoe_tiny(**dict(dict(
        attention_kinds=["window", "full", "window"],
        ffn_kinds=["dense", "experts", "experts"], num_heads=8,
        num_kv_heads=1, window_num_kv_heads=1, qk_head_dim=128,
        v_head_dim=128, rotary_dim=128, sliding_window=150,
        initializer_range=0.3, max_position_embeddings=320,
        attention_block=128), **kw))
    model = HybridMoEForCausalLM(cfg)
    model.eval()
    return model


@pytest.fixture
def kernels_on_cpu(monkeypatch):
    """The dispatch as a TPU takes it, the attention kernels in the
    interpreter (``interpret=True`` is the test's to pass); the fused
    norm's gate closed (not what is tested)."""
    from functools import partial

    from paddle_tpu.models import llama
    from paddle_tpu.ops.pallas import decode_attention as da
    from paddle_tpu.ops.pallas import flash_attention as fa
    from paddle_tpu.ops.pallas import rms_norm

    monkeypatch.setattr(llama, "_kernels_on", lambda: True)
    monkeypatch.setattr(rms_norm, "rms_norm_supported", lambda shape: False)
    monkeypatch.setattr(fa, "flash_attention_gqa",
                        partial(fa.flash_attention_gqa, interpret=True))
    monkeypatch.setattr(da, "paged_decode_attention",
                        partial(da.paged_decode_attention, interpret=True))


def _forms(model, S=256):
    """The form each layer of a forward with no cache takes (which of
    the two functions it calls), the windows the flash kernel was
    handed, the logits."""
    from paddle_tpu.models import hybrid_moe
    from paddle_tpu.observability import moestats
    from paddle_tpu.ops.pallas import flash_attention as fa

    forms, windows = [], []
    kernel, blocks = fa.flash_attention_gqa, \
        hybrid_moe.blockwise_causal_attention

    def flash(q, k, v, **kw):
        forms.append("flash")
        windows.append(kw["window"])
        return kernel(q, k, v, **kw)

    def blockwise(*args):
        forms.append("blockwise")
        return blocks(*args)

    ids = paddle.to_tensor(np.arange(S, dtype=np.int32)[None] % 256)
    moestats.begin()
    try:
        fa.flash_attention_gqa = flash
        hybrid_moe.blockwise_causal_attention = blockwise
        logits = np.asarray(model(ids)._value)
    finally:
        fa.flash_attention_gqa = kernel
        hybrid_moe.blockwise_causal_attention = blocks
        recs = moestats.drain()
    # no cache, no serving program: the collector holds the expert
    # layers' records alone (the benchmark's probe reads every record of
    # such a forward as one: mla_moe_serving.program_choices)
    assert all("choices" in r for r in recs) and recs
    return forms, windows, logits


@pytest.mark.parametrize("name,kw,S,want,windows", [
    ("trinity's shapes: every layer, the window layers under theirs",
     {}, 256, ["flash"] * 3, [150, None, 150]),
    ("a sink on the window layers: those stay in lax blocks",
     dict(window_sink=True), 256, ["blockwise", "flash", "blockwise"],
     [None]),
    ("keys wider than values",
     dict(qk_head_dim=256, rotary_dim=256), 256, ["blockwise"] * 3, []),
    ("values wider than a key head",
     dict(v_head_dim=256), 256, ["blockwise"] * 3, []),
    ("half the lanes", dict(qk_head_dim=64, v_head_dim=64, rotary_dim=64),
     256, ["blockwise"] * 3, []),
    ("a bucket no block of 128 rows divides", {}, 200,
     ["blockwise"] * 3, []),
])
def test_prefill_form_follows_the_layer_s_shapes(kernels_on_cpu, name, kw,
                                                 S, want, windows):
    model = wide_model(**kw)
    forms, handed, got = _forms(model, S)
    assert (forms, handed) == (want, windows), name
    if "flash" in forms:        # and the same function either way
        from paddle_tpu.models import llama

        llama._kernels_on = lambda: False       # the fixture restores it
        off, none, ref_logits_ = _forms(model, S)
        assert (off, none) == (["blockwise"] * 3, [])
        np.testing.assert_allclose(got, ref_logits_, rtol=2e-4, atol=2e-4)


def test_kernels_off_is_the_lax_blocks_on_any_shape():
    assert _forms(wide_model())[:2] == (["blockwise"] * 3, [])


def test_engine_prefills_on_the_flash_kernel_and_serves_the_same(
        kernels_on_cpu, monkeypatch):
    """A prompt of 200 tokens (a bucket of 256 rows, two blocks of 128
    under a window of 150) and one of 100 through ``ServingEngine``: the
    prefill programs hold the flash forward once a layer, the engine
    names the form a bucket, and the first token's logits and the served
    tokens are the blockwise path's."""
    from test_flash_grad_kernel import _pallas_calls

    from paddle_tpu.models import llama
    from paddle_tpu.observability import moestats

    model = wide_model()
    r = np.random.RandomState(46)
    prompts = [r.randint(0, 256, (n,)).astype(np.int32) for n in (200, 100)]

    def serve():
        eng = engine(model, max_batch=2, decode_chunk=2)
        rids = [eng.submit(p, max_new_tokens=3) for p in prompts]
        done = eng.run()
        # and the prefill program's own logits, the head on the last row
        ids = np.zeros((1, 256), np.int32)
        ids[0, :200] = prompts[0]
        pred = create_predictor(Config().set_model(model))
        moestats.begin()
        logits, _ = pred._prefill_fn(1, 256, 320)(
            tuple(p._value for p in pred._params), jnp.asarray(ids),
            model._empty_caches(1, 320, jnp.float32),
            jnp.asarray([200], jnp.int32))
        assert {r["attention"] for r in moestats.drain()
                if "attention" in r} == {eng.prefill_attention_forms()[256]}
        return eng, [list(done[r].new_tokens) for r in rids], \
            np.asarray(logits)

    eng, tokens, logits = serve()
    assert eng.prefill_attention_forms() == {128: "flash", 256: "flash"}
    for bucket in (128, 256):
        fn, avals = eng._site_programs[("prefill", bucket)]
        assert _pallas_calls(jax.make_jaxpr(fn)(*avals).jaxpr) == {
            "flash_attention_fwd_gqa": 3}
    monkeypatch.setattr(llama, "_kernels_on", lambda: False)
    lax_eng, want, want_logits = serve()
    assert lax_eng.prefill_attention_forms() == {128: "blockwise",
                                                 256: "blockwise"}
    assert tokens == want
    assert logits.shape == (1, 256)
    np.testing.assert_allclose(logits, want_logits, rtol=2e-4, atol=2e-4)
