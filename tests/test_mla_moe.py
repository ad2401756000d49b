"""The latent-attention expert decoder (models/mla_moe.py), its expert
layer (GatedMoELayer), its paged latent kernel and its life under
ServingEngine, against the plain float32 reference
(benchmarks/references/sarvam.py) at a tiny size on the CPU, seeded
weights. Also: the Llama engine's pools and program keys are as they
were before the engine took its pool geometry from the model.
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
from benchmarks.harness.families import mla_moe_serving as fam  # noqa: E402
from benchmarks.references import sarvam as ref  # noqa: E402
from paddle_tpu.incubate.distributed.models.moe import (  # noqa: E402
    GatedMoELayer, SigmoidTopKGate)
from paddle_tpu.incubate.distributed.models.moe import moe_layer  # noqa: E402
from paddle_tpu.incubate.distributed.models.moe.moe_layer import (  # noqa: E402
    routed_swiglu)
from paddle_tpu.inference import (Config, ServingEngine,  # noqa: E402
                                  create_predictor)
from paddle_tpu.models.mla_moe import (MLAMoEForCausalLM,  # noqa: E402
                                       yarn_inv_freq)
from paddle_tpu.ops.pallas import mla_attention as ma  # noqa: E402

CFG = {
    "hidden_size": 64, "intermediate_size": 128,
    "moe_intermediate_size": 32, "num_hidden_layers": 3,
    "first_k_dense_replace": 1, "num_attention_heads": 4,
    "kv_lora_rank": 32, "qk_nope_head_dim": 16, "qk_rope_head_dim": 8,
    "q_head_dim": 24, "head_dim": 40, "v_head_dim": 16,
    "num_experts": 4, "router_experts": 16, "expert_offset": 4,
    "num_experts_per_tok": 4, "num_shared_experts": 1,
    "routed_scaling_factor": 2.5, "use_qk_norm": True, "vocab_size": 256,
    "rope_theta": 10000, "rms_norm_eps": 1e-6,
    "rope_scaling": {"type": "deepseek_yarn", "factor": 4,
                     "original_max_position_embeddings": 32,
                     "beta_fast": 32, "beta_slow": 1, "mscale": 1,
                     "mscale_all_dim": 1},
    "torch_dtype": "float32", "initializer_range": 0.3}
SEED = 2 ** 31 + 99
M = 96


def build(cfg=CFG, seed=SEED, max_len=M):
    paddle.set_default_dtype("float32")
    model = MLAMoEForCausalLM(fam.model_config(cfg, max_len))
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
    return np.random.default_rng(3).integers(0, 256, 40).astype(np.int32)


def ref_logits(prompt, served, cfg=CFG):
    return ref.ServeReference(cfg, SEED).logits([(prompt, served)])[0]


def test_full_forward_is_the_reference(model, tokens):
    got = np.asarray(model(paddle.to_tensor(tokens[None]))._value)[0]
    want = ref_logits(tokens[:1], np.append(tokens[1:], 0))
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-4)


def test_yarn_frequencies_are_the_references():
    rs = CFG["rope_scaling"]
    np.testing.assert_allclose(yarn_inv_freq(8, 10000, rs),
                               ref.yarn_inv_freq(8, 10000, rs))
    big = {"factor": 40, "original_max_position_embeddings": 4096,
           "beta_fast": 32, "beta_slow": 1}
    inv = yarn_inv_freq(64, 10000, big)
    plain = 1.0 / 10000 ** (np.arange(0, 64, 2) / 64)
    np.testing.assert_allclose(inv, ref.yarn_inv_freq(64, 10000, big))
    assert inv[0] == plain[0]                       # fast dims untouched
    np.testing.assert_allclose(inv[-1], plain[-1] / 40)   # slow: / factor


def test_engine_prefill_then_decode_is_the_reference(model, tokens):
    """Prefill buckets + the decode program over the latent pool: every
    served token's logit gap to the reference's full forward is 0 up to
    float32 noise, for two ragged requests sharing the batch."""
    pred = create_predictor(Config().set_model(model).enable_paged_kv(
        page_size=8))
    eng = ServingEngine(pred, max_batch=2)
    shapes = [(p.shape, r.shape) for p, r in eng.pools]
    assert shapes == [((eng.P, 1, 8, 32), (eng.P, 1, 8, 128))] * 3
    rids = [eng.submit(tokens[:21], max_new_tokens=9),
            eng.submit(tokens[5:18], max_new_tokens=12)]
    done = eng.run()
    for rid, prompt in zip(rids, (tokens[:21], tokens[5:18])):
        served = np.asarray(done[rid].new_tokens)
        lg = ref_logits(prompt, served)
        assert ref.served_gap(lg, served).max() < 1e-3
    st = eng.moe_stats()
    assert st["dropped"] == 0
    # prompts of 21 and 13 tokens: buckets under the threshold too
    assert st["forms"] == {"decode": "batched", "prefill": "batched"}
    assert st["rows"] == {}
    assert (st["tokens"] == [0, st["tokens"][1], st["tokens"][1]]).all()
    assert st["tokens"][1] > 0 and st["tokens"][1] % 2 == 0   # B rows a step
    np.testing.assert_array_equal(
        st["pairs"].sum(1) + st["absent_pairs"], st["tokens"] * 4)
    np.testing.assert_array_equal(st["summed_pairs"], st["pairs"].sum(1))
    eng.release_pools()
    assert eng.pools is None


def tpu_program_text(eng, site):
    """A serving program that has run, lowered for a TPU from here: the
    CPU's own lowering writes XLA's grouped matmul out as a masked dense
    product, a TPU's keeps it (``ragged_dot``). (Traced here, so the
    sorted products are XLA's whatever the widths: a TPU traces
    ``ops/pallas/grouped_matmul.py`` where that kernel's gate admits
    them, which this model's 32 and 64 are not; at the published widths
    see ``test_serving_programs_compile_for_a_v5e_in_place``.)"""
    fn, avals = eng._site_programs[site]
    return fn.trace(*avals).lower(lowering_platforms=("tpu",)).as_text()


def test_decode_is_batched_and_a_long_prefill_sorted():
    """A prompt of 150 tokens prefills in the 256 bucket, over the
    threshold: its expert layers sort and group, the decode program's
    (2 rows a step) are batched over the held experts, and the served
    tokens are the reference's through both."""
    prompt = np.random.default_rng(5).integers(0, 256, 150).astype(np.int32)
    pred = create_predictor(Config().set_model(build(max_len=384))
                            .enable_paged_kv(page_size=8))
    eng = ServingEngine(pred, max_batch=2)
    assert eng.moe_stats()["forms"] == {"decode": None, "prefill": None}
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
    np.testing.assert_array_equal(st["summed_pairs"], st["pairs"].sum(1))
    assert eng.program_sites() == [("prefill", 256), ("decode",)]
    text = tpu_program_text(eng, ("prefill", 256))
    assert "ragged_dot" in text and "grouped_matmul" not in text
    text = tpu_program_text(eng, ("decode",))
    assert "ragged_dot" not in text and "grouped_matmul" not in text


def test_generate_static_and_paged_agree_with_full_forwards(model, tokens):
    want = list(tokens[:11])
    for _ in range(5):
        lg = np.asarray(model(paddle.to_tensor(
            np.asarray([want], np.int32)))._value)[0, -1]
        want.append(int(lg.argmax()))
    for conf in (Config().set_model(model),
                 Config().set_model(model).enable_paged_kv(page_size=8)):
        out = create_predictor(conf).generate(
            paddle.to_tensor(tokens[None, :11]), max_new_tokens=5)
        assert list(np.asarray(out._value)[0]) == want


def test_absorbed_is_unabsorbed(model, tokens):
    """One layer's attention over the same 24 positions: the prefill
    form (per-head keys and values) against the absorbed form through
    the cache (8 positions prefilled, 16 fed one at a time and 4 at a
    time)."""
    attn = model.layers[1].self_attn
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 24, 64), jnp.float32)
    want, _ = attn(x, cache=None)
    for step in (1, 4):
        cache = model._empty_caches(2, 32, jnp.float32)[0]
        out, cache = attn(x[:, :8], cache=cache, offset=0)
        outs = [out]
        for t in range(8, 24, step):
            out, cache = attn(x[:, t:t + step], cache=cache,
                              offset=jnp.asarray([t, t], jnp.int32))
            outs.append(out)
        np.testing.assert_allclose(np.concatenate(outs, 1), want,
                                   rtol=1e-4, atol=1e-5)


def _expert_layer(offset, held, seed=0):
    paddle.seed(seed)
    layer = GatedMoELayer(64, 32, 16, held, offset, top_k=4,
                          routed_scaling_factor=2.5)
    return layer


def test_the_four_shares_add_up_to_the_uncut_layer():
    """Experts 0..15 held 4 to a holder: the holders' routed parts, with
    the shared expert counted once, add up to the layer that holds all
    16, and that is the reference's uncut layer."""
    whole = _expert_layer(0, 16)
    rng = np.random.default_rng(0)
    for p in whole.parameters():
        p._value = jnp.asarray(rng.normal(0, 0.2, p.shape), jnp.float32)
    x = jnp.asarray(rng.normal(0, 1, (24, 64)), jnp.float32)
    want = np.asarray(whole(x)._value)
    shared = np.asarray(whole.shared_down._value.T @ (
        jax.nn.silu(x @ whole.shared_gate._value)
        * (x @ whole.shared_up._value)).T).T
    total = shared.copy()
    for off in (0, 4, 8, 12):
        part = _expert_layer(off, 4)
        for name in ("w_gate", "w_up", "w_down"):
            getattr(part, name)._value = getattr(whole, name)._value[
                off:off + 4]
        for name in ("shared_gate", "shared_up", "shared_down"):
            getattr(part, name)._value = getattr(whole, name)._value
        part.gate.weight._value = whole.gate.weight._value
        part.gate.bias._value = whole.gate.bias._value
        total += np.asarray(part(x)._value) - shared
    np.testing.assert_allclose(total, want, rtol=1e-4, atol=1e-5)
    # the reference, uncut: route, then expert by expert
    cfg = dict(CFG, num_experts=16, expert_offset=0)
    with jax.default_matmul_precision("highest"):
        idx, g = ref.route(x, whole.gate.weight._value,
                           whole.gate.bias._value, cfg)
        y = ref.swiglu(x, whole.shared_gate._value, whole.shared_up._value,
                       whole.shared_down._value, "float32")
        for j in range(16):
            y = y + ref.expert_part(
                x, idx, g, j, whole.w_gate._value[j], whole.w_up._value[j],
                whole.w_down._value[j], "float32")
    np.testing.assert_allclose(want, np.asarray(y), rtol=1e-4, atol=1e-5)


def test_selection_bias_changes_the_choice_and_never_the_weights():
    paddle.seed(1)
    gate = SigmoidTopKGate(64, 16, topk=4, routed_scaling_factor=2.5)
    rng = np.random.default_rng(2)
    gate.weight._value = jnp.asarray(rng.normal(0, 0.3, (64, 16)),
                                     jnp.float32)
    x = jnp.asarray(rng.normal(0, 1, (50, 64)), jnp.float32)
    gate.bias._value = jnp.zeros(16)
    idx0, w0 = map(np.asarray, gate.route(x))
    gate.bias._value = jnp.zeros(16).at[3].set(10.0)
    idx1, w1 = map(np.asarray, gate.route(x))
    assert (idx1 == 3).any(axis=1).all()        # the bias steers the choice
    assert not (idx0 == 3).any(axis=1).all()
    s = np.asarray(jax.nn.sigmoid(x @ gate.weight._value))
    for idx, w in ((idx0, w0), (idx1, w1)):     # weights: scores alone
        sel = np.take_along_axis(s, idx, 1)
        np.testing.assert_allclose(w, 2.5 * sel / sel.sum(1, keepdims=True),
                                   rtol=1e-5)
        np.testing.assert_allclose(w.sum(1), 2.5, rtol=1e-5)


FORMS = {"sorted": moe_layer.routed_swiglu_sorted,
         "batched": moe_layer.routed_swiglu_batched}


@pytest.mark.parametrize("form", FORMS)
def test_every_token_to_one_expert_drops_nothing(form):
    """No capacity: 40 tokens that all choose held expert 2 (and three
    absent ones) are all computed."""
    rng = np.random.default_rng(4)
    x = jnp.asarray(rng.normal(0, 1, (40, 64)), jnp.float32)
    wg, wu = (jnp.asarray(rng.normal(0, 0.2, (4, 64, 32)), jnp.float32)
              for _ in range(2))
    wd = jnp.asarray(rng.normal(0, 0.2, (4, 32, 64)), jnp.float32)
    idx = jnp.tile(jnp.asarray([[9, 6, 12, 1]], jnp.int32), (40, 1))
    g = jnp.asarray(rng.uniform(0.1, 1, (40, 4)), jnp.float32)
    y, sizes = FORMS[form](x, idx, g, wg, wu, wd, expert_offset=4)[:2]
    # pairs an expert, pairs of absent experts, pairs computed and summed
    assert list(np.asarray(sizes)) == [0, 0, 40, 0, 120, 40]
    want = g[:, 1:2] * ((jax.nn.silu(x @ wg[2]) * (x @ wu[2])) @ wd[2])
    np.testing.assert_allclose(np.asarray(y), np.asarray(want), rtol=1e-4,
                               atol=1e-5)


# case -> (tokens, router's width, held experts, passes of the sorted
# form). 24 tokens of 4 choices make 96 pairs, under one tile of sorted
# rows: one window, no loop. 192 tokens make 768 pairs over a bound of
# sorted_rows(192, 4, 4, 16) = 512.
ROUTINGS = {"random": (24, 16, 4, 1), "random_at_an_offset": (24, 16, 4, 1),
            "one_held_expert": (24, 16, 4, 1), "no_held_pair": (24, 16, 4, 1),
            "no_held_pair_of_many": (192, 16, 4, 0),
            "random_of_many": (192, 16, 4, 1),
            "every_pair_held": (192, 16, 4, 2),
            "held_pairs_at_the_bound": (192, 16, 4, 1),
            "held_pairs_one_over_the_bound": (192, 16, 4, 2),
            "all_experts_held": (192, 4, 4, 1)}


def _routing(case, rng, T, k, E):
    """[T, k] choices over E experts of which 4..7 are held (offset 4),
    or 0..3 for ``random`` and ``all_experts_held`` (offset 0)."""
    absent = np.asarray([0, 1, 2, 3, 8, 9, 10, 11, 12, 13, 14, 15])
    if case == "one_held_expert":
        return np.tile([[9, 6, 12, 1]], (T, 1)), 4
    if case.startswith("no_held_pair"):     # every choice outside 4..7
        return np.stack([rng.permutation(absent)[:k] for _ in range(T)]), 4
    if case == "every_pair_held":
        return np.stack([4 + rng.permutation(4) for _ in range(T)]), 4
    if case.startswith("held_pairs_"):
        # whole tokens on the held experts up to the bound, the rest
        # elsewhere; one pair more for the case over it
        M = moe_layer.sorted_rows(T, k, 4, E)
        idx = np.stack([4 + rng.permutation(4) if t < M // k
                        else rng.permutation(absent)[:k] for t in range(T)])
        if case.endswith("one_over_the_bound"):
            idx[-1, 2] = 6
        return idx, 4
    idx = np.stack([rng.permutation(E)[:k] for _ in range(T)])
    return idx, (0 if case in ("random", "all_experts_held") else 4)


@pytest.mark.parametrize("case", ROUTINGS)
def test_batched_form_is_the_sorted_form_is_a_loop_over_tokens(case):
    """The two forms of ``routed_swiglu`` and a plain loop (token by
    token, choice by choice) agree on seeded float32 weights, and their
    ``sizes`` agree element by element: also where the held pairs pass
    the sorted form's bound and it takes a second window of rows."""
    rng = np.random.default_rng(11)
    (T, E, El, passes_want), k, d, h = ROUTINGS[case], 4, 64, 32
    x = rng.normal(0, 1, (T, d)).astype(np.float32)
    wg, wu = (rng.normal(0, 0.2, (El, d, h)).astype(np.float32)
              for _ in range(2))
    wd = rng.normal(0, 0.2, (El, h, d)).astype(np.float32)
    idx, off = _routing(case, rng, T, k, E)
    w = rng.uniform(0.1, 1, (T, k)).astype(np.float32)
    want = np.zeros((T, d), np.float64)
    pairs = np.zeros(El, np.int64)
    for t in range(T):
        for j in range(k):
            e = idx[t, j] - off
            if 0 <= e < El:
                g, u = x[t] @ wg[e], x[t] @ wu[e]
                want[t] += w[t, j] * ((g / (1 + np.exp(-g)) * u) @ wd[e])
                pairs[e] += 1
    sizes_want = list(pairs) + [T * k - pairs.sum(), pairs.sum()]
    M = moe_layer.sorted_rows(T, k, El, E)
    held_want = {"no_held_pair": 0, "no_held_pair_of_many": 0,
                 "every_pair_held": T * k, "all_experts_held": T * k,
                 "held_pairs_at_the_bound": M,
                 "held_pairs_one_over_the_bound": M + 1}
    assert pairs.sum() == held_want.get(case, pairs.sum())
    args = (jnp.asarray(x), jnp.asarray(idx, jnp.int32), jnp.asarray(w),
            jnp.asarray(wg), jnp.asarray(wu), jnp.asarray(wd), off)
    for name, form in FORMS.items():
        if name == "sorted":
            y, sizes, passes, _ = form(*args, E)
            assert int(passes) == passes_want == max(
                -(-int(pairs.sum()) // M), int(M == T * k))
        else:
            y, sizes = form(*args)
        assert sizes.dtype == jnp.int32 and sizes.shape == (El + 2,)
        assert list(np.asarray(sizes)) == sizes_want
        np.testing.assert_allclose(np.asarray(y), want, rtol=1e-4,
                                   atol=1e-5)
    # the public entry takes the form its token count asks for, and says
    # what it took
    y, sizes, trace = routed_swiglu(*args, E)
    assert list(np.asarray(sizes)) == sizes_want
    np.testing.assert_allclose(np.asarray(y), want, rtol=1e-4, atol=1e-5)
    if T <= moe_layer._BATCHED_MAX_TOKENS:
        assert trace == {"form": "batched"}
    else:
        assert (trace["form"], trace["rows"], int(trace["passes"])) == \
            ("sorted", (M, T * k), passes_want)


def test_a_capacity_on_the_groups_shows_as_dropped_pairs(monkeypatch):
    """The SORTED form's computed pairs are counted from the sorted rows
    and the group sizes, not from the router's choice: clamp the groups
    to 16 rows, as a capacity would, and 24 of the 40 held pairs are
    missing from the count (``ServingEngine.moe_stats()["dropped"]`` =
    held - summed)."""
    real = moe_layer._group_sizes
    monkeypatch.setattr(moe_layer, "_group_sizes",
                        lambda *a: jnp.minimum(real(*a), 16))
    x = jnp.ones((40, 64), jnp.float32)
    w = jnp.ones((4, 64, 32), jnp.float32)
    idx = jnp.tile(jnp.asarray([[9, 6, 12, 1]], jnp.int32), (40, 1))
    _, sizes, _, _ = moe_layer.routed_swiglu_sorted(
        x, idx, jnp.ones((40, 4)), w, w, jnp.ones((4, 32, 64)),
        expert_offset=4)
    absent, summed = map(int, sizes[-2:])
    assert (absent, summed) == (120, 16)
    assert 40 * 4 - absent - summed == 24


def test_a_combine_that_misses_an_expert_shows_as_dropped_pairs(
        monkeypatch):
    """The BATCHED form's computed pairs are counted from the combine
    that is applied, not from the router's choice: zero the column of
    held expert 2 and its 40 pairs are missing from the count and from
    the sum, while the pairs of expert 1 (10 tokens) stay."""
    real = moe_layer._combine

    def without_expert_2(*a):
        pairs, c = real(*a)
        return pairs.at[:, 2].set(0), c

    x = jnp.ones((40, 64), jnp.float32)
    w = jnp.ones((4, 64, 32), jnp.float32)
    idx = jnp.tile(jnp.asarray([[9, 6, 12, 1]], jnp.int32), (40, 1))
    idx = idx.at[:10, 0].set(5)
    args = (x, idx, jnp.ones((40, 4)), w, w, jnp.ones((4, 32, 64)), 4)
    y_all, sizes = moe_layer.routed_swiglu_batched(*args)
    assert [int(v) for v in sizes[-2:]] == [110, 50]
    monkeypatch.setattr(moe_layer, "_combine", without_expert_2)
    y, sizes = moe_layer.routed_swiglu_batched(*args)
    absent, summed = map(int, sizes[-2:])
    assert (absent, summed) == (110, 10)
    assert 40 * 4 - absent - summed == 40
    assert float(jnp.abs(y[10:]).max()) == 0.0 < float(y_all[10:].min())
    np.testing.assert_allclose(np.asarray(y[:10]),
                               np.asarray(y_all[:10]) / 2, rtol=1e-6)


def _shapes(jaxpr):
    """Result shapes of every equation, those of the loops' and the
    called functions' bodies among them."""
    out = []
    for eq in jaxpr.eqns:
        out += [tuple(v.aval.shape) for v in eq.outvars
                if hasattr(v.aval, "shape")]
        for sub in eq.params.values():
            sub = getattr(sub, "jaxpr", sub)
            if hasattr(sub, "eqns"):
                out += _shapes(sub)
    return out


def _traced(layer, T):
    """(intermediate shapes of the layer's forward over T tokens, what
    its ``routed_swiglu`` recorded as it was traced, whether XLA's
    grouped matmul is in it)."""
    from paddle_tpu.observability import moestats

    moestats.begin()
    try:
        jaxpr = jax.make_jaxpr(lambda v: layer(v)._value)(
            jnp.zeros((T, layer.d_model), jnp.float32))
    finally:
        recs = moestats.drain()
    return _shapes(jaxpr.jaxpr), recs, "ragged_dot" in str(jaxpr)


@pytest.mark.parametrize("form", FORMS)
def test_expert_layer_builds_no_dispatch_tensor(form):
    """Neither form has [T, E, C] algebra: the router's width (24) shows
    in its own [T, 24] scores and nowhere else, there is no capacity,
    and the largest intermediate is a window of the sorted held pairs,
    [M, h] above the threshold (h the wider of h and d here; M =
    ``sorted_rows``, 256 of the 672 pairs) and the held experts'
    [El, T, h] at or below it."""
    paddle.seed(0)
    d, h, E, El, k = 32, 64, 24, 6, 4
    layer = GatedMoELayer(d, h, E, El, 6, top_k=k,
                          routed_scaling_factor=2.5)
    T = moe_layer._BATCHED_MAX_TOKENS + (40 if form == "sorted" else -40)
    shapes, recs, _ = _traced(layer, T)
    assert [r["form"] for r in recs] == [form]
    assert all(s in ((T, E), (E,), (1, E)) for s in shapes if E in s), \
        [s for s in shapes if E in s]
    biggest = max(shapes, key=lambda s: int(np.prod(s)))
    # (the shared expert's [T, h] and the broadcast tokens [El, T, d]
    # are smaller than either)
    M = moe_layer.sorted_rows(T, k, El, E)
    assert M == 256 < T * k
    assert int(np.prod(biggest)) == \
        (M * h if form == "sorted" else El * T * h), biggest


@pytest.mark.parametrize("T", [168, 1024])
def test_sorted_form_has_no_array_of_every_routed_pair(T):
    """No intermediate of the traced sorted form has ``T * k`` rows and
    more than one column: the pairs' int32 keys, numbers and weights are
    sorted, and what is gathered, multiplied, scaled and summed is a
    window of ``sorted_rows`` rows. The layer records that bound beside
    the pairs as it is traced."""
    paddle.seed(0)
    d, h, E, El, k = 32, 64, 24, 6, 4
    layer = GatedMoELayer(d, h, E, El, 6, top_k=k,
                          routed_scaling_factor=2.5)
    shapes, recs, grouped = _traced(layer, T)
    M = moe_layer.sorted_rows(T, k, El, E)
    assert grouped and M < T * k and M % moe_layer._SORTED_TILE == 0
    assert [(r["form"], r["rows"], r["grouped"]) for r in recs] == \
        [("sorted", (M, T * k), "xla")]
    wide = {d, h}
    assert [s for s in shapes if s and s[0] == T * k
            and (len(s) > 1 and s[-1] in wide)] == []
    assert (M, h) in shapes and (M, d) in shapes
    assert not any(int(np.prod(s)) >= T * k * min(d, h) for s in shapes)


def test_a_prompt_past_the_bound_takes_a_second_pass_of_the_same_rows():
    """A selection bias that sends every token to the four held experts:
    768 held pairs over a bound of 512 sorted rows. The layer reports 2
    passes (a device value, in its record), drops nothing, and gives what
    the batched form gives for the same choices."""
    from paddle_tpu.observability import moestats

    layer = _expert_layer(4, 4)
    layer.gate.bias._value = jnp.zeros(16).at[4:8].set(10.0)
    x = jnp.asarray(np.random.default_rng(5).normal(0, 1, (192, 64)),
                    jnp.float32)
    moestats.begin()
    try:
        y = layer(x)._value
    finally:
        (rec,) = moestats.drain()
    assert (rec["form"], rec["rows"], int(rec["passes"])) == \
        ("sorted", (512, 768), 2)
    assert list(np.asarray(rec["load"])) == [192] * 4 + [0, 768]
    idx, w = layer.gate.route(x)
    want, _ = moe_layer.routed_swiglu_batched(
        x, idx, w, layer.w_gate._value, layer.w_up._value,
        layer.w_down._value, 4)
    want = want + moe_layer.swiglu(x, layer.shared_gate._value,
                                   layer.shared_up._value,
                                   layer.shared_down._value)
    np.testing.assert_allclose(np.asarray(y), np.asarray(want), rtol=1e-4,
                               atol=1e-5)


def test_the_form_is_a_function_of_the_token_count_alone():
    """At the threshold the layer traces the batched form, one token
    above it the sorted one; nothing else is asked."""
    layer = _expert_layer(4, 4)
    edge = moe_layer._BATCHED_MAX_TOKENS
    assert [moe_layer.routed_form(t) for t in (1, edge, edge + 1, 4096)] \
        == ["batched", "batched", "sorted", "sorted"]
    for T, form in ((edge, "batched"), (edge + 1, "sorted")):
        _, recs, grouped = _traced(layer, T)
        assert [r["form"] for r in recs] == [form]
        assert grouped == (form == "sorted")
        # off the TPU the grouped product is XLA's; the batched form
        # has none to name
        assert [r.get("grouped") for r in recs] == \
            ["xla" if grouped else None]


# (columns of the table, tokens in cache a row): pages of 16 and a
# 128-wide latent in float32, so a visit holds TWO pages
LATENT_WALKS = {
    "ragged_with_a_free_row": (4, [0, 5, 17, 63]),
    "around_a_page_edge": (4, [31, 32, 33, 16]),
    # a row's own position is its page's last, and the next page's first
    "ends_on_a_page_edge": (4, [15, 16, 47, 48, 0]),
    # a whole table, and a scan that stepped on past it
    "fills_the_table": (4, [63, 0, 62, 63, 67, 48]),
    # the cursor crosses grid steps with fetches in flight; B is no
    # multiple of the slots; odd and even counts of pages a row
    "one_page_beside_ten": (10, [3, 159, 0, 150, 7, 155, 9]),
    "every_row_one_page": (10, [0, 0, 9, 0, 15]),
    "one_row": (10, [100]),
    "an_odd_table": (5, [79, 40, 64, 0]),
    # pages of 128 and a 512-wide latent: two pages pass the bytes a
    # walk keeps in flight, and a visit holds ONE
    "a_page_a_visit": (3, [130, 0, 383, 127]),
}
LATENT_DIMS = {"a_page_a_visit": (128, 512)}        # else (16, 128)


@pytest.mark.parametrize("kept", [False, True], ids=["all", "kept"])
@pytest.mark.parametrize("walk", sorted(LATENT_WALKS))
def test_latent_kernel_is_its_dense_twin(walk, kept):
    """The page walk against the dense twin. Table entries past a row's
    frontier name no page of the pool, and the pages an index outside
    the pool would clamp to hold NaN: a page read in error shows."""
    npages, lengths = LATENT_WALKS[walk]
    page, dc = LATENT_DIMS.get(walk, (16, 128))
    B, H, dr = len(lengths), 8, 128
    assert ma._latent_plan(H, page, dc, dr, 4)[0] == (1 if page == 128
                                                      else 2)
    P = B * npages + 3
    ks = jax.random.split(jax.random.PRNGKey(0), 4)
    ql = jax.random.normal(ks[0], (B, H, dc), jnp.float32)
    qr = jax.random.normal(ks[1], (B, H, dr), jnp.float32)
    cp = jax.random.normal(ks[2], (P, 1, page, dc), jnp.float32)
    rp = jax.random.normal(ks[3], (P, 1, page, dr), jnp.float32)
    rng = np.random.default_rng(1)
    clean = 1 + rng.permutation(P - 2)[:B * npages].reshape(
        B, npages).astype(np.int32)
    ln = np.asarray(lengths, np.int32)
    owned = np.arange(npages)[None] <= (ln // page)[:, None]
    tbl = np.where(owned, clean, P + 7)
    poison = jnp.asarray([0, P - 1])
    keep = None
    if kept:
        M = npages * page
        keep = rng.random((B, M)) < 0.4
        keep[:, page:2 * page] = False          # a page with no kept row
        keep[np.arange(B), np.minimum(ln, M - 1)] = True
        keep = jnp.asarray(keep)
    assert ma.mla_paged_supported(ql.shape, cp.shape, rp.shape)
    got = ma.mla_paged_decode_attention(
        ql, qr, cp.at[poison].set(jnp.nan), rp.at[poison].set(jnp.nan),
        tbl, ln, 0.11, interpret=True, keep=keep)
    want = ma.mla_paged_attention_dense(ql[:, None], qr[:, None], cp, rp,
                                        clean, ln, 0.11, keep)[:, 0]
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-5)


def test_latent_kernel_gate():
    ok = ((128, 64, 512), (2048, 1, 128, 512), (2048, 1, 128, 128))
    assert ma.mla_paged_supported(*ok)
    # the three cells' shapes of call
    for B, H, P in ((128, 64, 1408), (128, 64, 1536), (48, 128, 4096)):
        assert ma.mla_paged_supported((B, H, 512), (P, 1, 128, 512),
                                      (P, 1, 128, 128))
    assert not ma.mla_paged_supported((128, 64, 32), (64, 1, 16, 32),
                                      (64, 1, 16, 8))      # tiny latent
    assert not ma.mla_paged_supported((4, 64, 512), (64, 8, 128, 512),
                                      (64, 8, 128, 64))    # per-head cache
    # a rotated key that fills no lane: a page is copied whole from HBM
    assert not ma.mla_paged_supported(ok[0], ok[1], (2048, 1, 128, 64))
    # a row's blocks past the scoped VMEM (1,448 heads compile on a v5e,
    # 1,536 do not)
    assert ma.mla_paged_supported((8, 1448, 512), *ok[1:])
    assert not ma.mla_paged_supported((8, 1536, 512), *ok[1:])


@pytest.mark.parametrize("H, itemsize, plan", [
    (64, 2, (2, 3)), (128, 2, (2, 3)),  # the cells: 328 kB a visit of two
    (64, 4, (1, 3)),        # float32: two pages pass the bytes in flight
    (1448, 2, (1, 2)),      # the gate's last head count: VMEM's room
])
def test_latent_kernel_plan_follows_the_shapes(H, itemsize, plan):
    """(pages a visit, slots a pool) at d_c 512, d_r 128, pages of 128."""
    assert ma._latent_plan(H, 128, 512, 128, itemsize) == plan
    pages, depth = plan
    assert depth == 2 or ma._latent_vmem_bytes(
        H, 128, 512, 128, itemsize, depth, pages) <= ma._PAGED_VMEM_DEEP


@pytest.mark.parametrize("kw, needle", [
    ({"prefill_chunk": 16}, "valid"),
    ({"prefill_chunk": 16, "prefix_cache": True}, "valid"),
    ({"phase": "decode"}, "latent cache"),
])
def test_engine_paths_the_latent_pools_do_not_serve_are_refused(
        model, kw, needle):
    pred = create_predictor(Config().set_model(model).enable_paged_kv(
        page_size=8))
    with pytest.raises(Exception, match=needle):
        ServingEngine(pred, max_batch=2, **kw)


def test_llama_engine_pools_and_program_keys_unchanged():
    from paddle_tpu.models.llama import LlamaForCausalLM, llama_tiny

    paddle.seed(0)
    cfg = llama_tiny()
    pred = create_predictor(Config().set_model(
        LlamaForCausalLM(cfg)).enable_paged_kv(page_size=8))
    eng = ServingEngine(pred, max_batch=2, decode_chunk=2)
    shape = (eng.P, cfg.num_kv_heads, 8, cfg.head_dim)
    assert [(k.shape, v.shape) for k, v in eng.pools] == \
        [(shape, shape)] * cfg.num_layers
    assert eng.cache.counters is None and eng.moe_stats() is None
    eng.submit(np.arange(11) % 250, max_new_tokens=5)
    eng.run()
    assert list(eng._step_fns) == [(2, eng.M, 2, 0.0, 0, 1.0)]
    assert list(pred._prefill_fns) == [(1, 64, eng.M, 8)]
    assert eng.memory_summary()["state"]["page_bytes"] == \
        2 * cfg.num_layers * cfg.num_kv_heads * 8 * cfg.head_dim * 4
    assert eng.program_sites() == [("prefill", 64), ("decode",)]


def test_serving_programs_compile_for_a_v5e_in_place():
    """The engine's own decode and prefill programs of the decoder at
    the benchmark configuration's widths (two layers, no weights),
    compiled by the TPU compiler for a described v5e in a process of its
    own: 0 pool-shaped copies, the latent kernel in decode, our grouped
    matmul (``grouped_matmul``, and not XLA's) in the prefill program and
    neither in the decode program (its products are batched over the 32
    held experts), and no copy or transpose of a stacked expert weight
    array in either."""
    import json
    import subprocess

    p = subprocess.run(
        [sys.executable, os.path.join(ROOT, "tools", "mla_serving_aot.py")],
        capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-2000:]
    out = json.loads(p.stdout.strip().splitlines()[-1])
    if "skipped" in out:
        pytest.skip(out["skipped"])
    progs = {c["program"]: c for c in out["programs"]}
    assert set(progs) == {"decode", "prefill_1024"}
    for c in progs.values():
        assert c["pool_copies"] == 0 and c["expert_weight_copies"] == 0, c
    assert not progs["decode"]["ragged_dot"]
    assert not progs["prefill_1024"]["ragged_dot"]
    assert progs["decode"]["kernels"] == ["mla_paged_decode_attention"] \
        and progs["prefill_1024"]["kernels"] == ["grouped_matmul"]
    # the pools and the routing counters are donated, the round's one
    # host array (tables, pos, token, mask) is not
    donated = progs["decode"]["donated"]
    assert len(donated) == 2 * 3, donated       # 2 layers x (a, b, counter)
    assert all(n.startswith("state") for n in donated), donated
