"""The shortcut-connected double layer of ``models/mla_moe.py``
(``shortcut_moe``: two latent attentions and two dense parts a layer
around ONE expert branch), its router (a softmax with a bias on the
choice, weights not renormalised, identity experts past the real ones)
and its life under ``ServingEngine`` (two pooled tuples a layer, one
routing counter a tuple), against the plain float32 reference
(benchmarks/references/longcat.py) at a tiny size on the CPU, seeded
weights. Also: with every new field at its default the engines of
``mla_moe_tiny()`` and ``sparse_mla_tiny()`` lower to the parent's text.
"""
import hashlib
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
from benchmarks.harness.families import shortcut_moe_serving as fam  # noqa: E402
from benchmarks.references import longcat as ref  # noqa: E402
from paddle_tpu.incubate.distributed.models.moe import (  # noqa: E402
    GatedMoELayer, SigmoidTopKGate)
from paddle_tpu.inference import (Config, ServingEngine,  # noqa: E402
                                  create_predictor)
from paddle_tpu.models.mla_moe import (MLAMoEConfig,  # noqa: E402
                                       MLAMoEForCausalLM, mla_moe_tiny,
                                       shortcut_moe_tiny, sparse_mla_tiny)
from paddle_tpu.observability import get_registry  # noqa: E402

CFG = {
    "hidden_size": 64, "ffn_hidden_size": 128, "expert_ffn_hidden_size": 32,
    "num_layers": 2, "num_hidden_layers": 4, "num_attention_heads": 4,
    "q_lora_rank": 24, "kv_lora_rank": 32, "qk_nope_head_dim": 16,
    "qk_rope_head_dim": 8, "v_head_dim": 16, "n_routed_experts": 4,
    "router_experts": 8, "expert_offset": 4, "zero_expert_num": 4,
    "zero_expert_type": "identity", "moe_topk": 3,
    "routed_scaling_factor": 6, "mla_scale_q_lora": True,
    "mla_scale_kv_lora": True, "attention_bias": False,
    "attention_method": "MLA", "vocab_size": 256, "rope_theta": 10000,
    "rms_norm_eps": 1e-5, "torch_dtype": "float32",
    "initializer_range": 0.3}
SEED = 2 ** 31 + 45
M = 96
PAGE = 8


def build(cfg=CFG, seed=SEED, max_len=M):
    paddle.set_default_dtype("float32")
    model = MLAMoEForCausalLM(fam.model_config(cfg, max_len))
    model.eval()
    named = list(model.named_parameters())
    weights.load(named, {n: fam.names_of(n, cfg) for n, _ in named},
                 ref.leaf_table(cfg), seed, "float32")
    # the file's N(0, 5e-4) bias is as wide as a 768-wide softmax's
    # neighbours lie apart; 12 outputs lie 1e-2 apart: a bias that wide
    rng = np.random.default_rng(7)
    for layer in model.layers:
        layer.mlp.gate.bias._value = jnp.asarray(
            rng.normal(0, 0.03, 12), jnp.float32)
    return model


class BiasedReference(ref.ServeReference):
    """The reference with the selection biases ``build`` planted."""

    def __init__(self, model, *a):
        super().__init__(*a)
        self.bias = [layer.mlp.gate.bias._value for layer in model.layers]

    def _params(self, names):
        out = super()._params(names)
        if "router_bias" in out:
            out["router_bias"] = self.bias[int(names[0].split(".")[1])]
        return out


@pytest.fixture(scope="module")
def model():
    return build()


@pytest.fixture(scope="module")
def tokens():
    return np.random.default_rng(45).integers(0, 256, 40).astype(np.int32)


@pytest.fixture(scope="module")
def reference(model):
    return BiasedReference(model, CFG, SEED)


def engine(model, **kw):
    pred = create_predictor(Config().set_model(model).enable_paged_kv(
        page_size=PAGE))
    return ServingEngine(pred, **kw)


# -- (a) the model against the reference --------------------------------------
def test_tiny_preset_is_the_family_s_mapping_and_every_field_is_off():
    c = shortcut_moe_tiny()
    got = fam.model_config(dict(CFG, expert_offset=0), 128)
    for f in fam.FIELDS + (
            "num_layers", "num_heads", "q_lora_rank", "kv_lora_rank",
            "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim",
            "intermediate_size", "moe_intermediate_size", "num_experts",
            "num_local_experts", "expert_offset", "num_experts_per_tok",
            "num_shared_experts", "first_k_dense_replace", "use_qk_norm",
            "routed_scaling_factor", "rope_scaling", "rms_norm_eps"):
        assert getattr(got, f) == getattr(c, f), f
    d = MLAMoEConfig()
    assert (d.shortcut_moe, d.zero_expert_num, d.router_score_func,
            d.router_bias, d.norm_topk_prob, d.mla_scale_q_lora,
            d.mla_scale_kv_lora) == (False, 0, "sigmoid", None, True,
                                     False, False)
    assert (d.attention_sublayers, d.q_lora_scale, d.kv_lora_scale) == (
        1, 1.0, 1.0)
    assert (c.attention_sublayers, c.q_lora_scale, c.kv_lora_scale) == (
        2, (64 / 24) ** 0.5, 2 ** 0.5)
    with pytest.raises(Exception, match="q_lora_rank"):
        mla_moe_tiny(mla_scale_q_lora=True)
    with pytest.raises(Exception, match="num_shared_experts"):
        shortcut_moe_tiny(num_shared_experts=1)


def test_full_forward_is_the_reference(model, reference, tokens):
    got = np.asarray(model(paddle.to_tensor(tokens[None]))._value)[0]
    want = reference.logits([(tokens[:1], np.append(tokens[1:], 0))])[0]
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-4)


def test_engine_prefill_then_decode_is_the_reference(model, reference,
                                                     tokens):
    """Prefill buckets + the decode program over FOUR latent pools (two a
    layer): every served token's logit gap to the reference's full
    forward is 0 up to float32 noise, for two ragged requests sharing
    the batch; the counters add up with the identity picks among them,
    and those are what the reference's router counts over the rows the
    decode steps fed."""
    eng = engine(model, max_batch=2)
    shapes = [(p.shape, r.shape) for p, r in eng.pools]
    assert shapes == [((eng.P, 1, PAGE, 32), (eng.P, 1, PAGE, 128))] * 4
    assert model.moe_counter_shape() == (4, 4 + 4)
    prompts = (tokens[:21], tokens[5:18])
    rids = [eng.submit(p, max_new_tokens=10) for p in prompts]
    done = eng.run()
    served = [np.asarray(done[r].new_tokens) for r in rids]
    logits = reference.logits(list(zip(prompts, served)))
    for lg, out in zip(logits, served):
        assert ref.served_gap(lg, out).max() < 1e-3
    st = eng.moe_stats()
    assert st["dropped"] == 0
    assert st["pairs"].shape == (4, 4) and st["zero_pairs"].shape == (4,)
    # both rows decode in step from their first token to their last: 9
    # steps of 2 rows, fed the served tokens but the last
    assert (st["tokens"] == [18, 0, 18, 0]).all()
    np.testing.assert_array_equal(
        st["pairs"].sum(1) + st["absent_pairs"] + st["zero_pairs"],
        st["tokens"] * 3)
    np.testing.assert_array_equal(st["summed_pairs"], st["pairs"].sum(1))
    fed = np.concatenate([first + len(p) + np.arange(len(out) - 1)
                          for (first, _), p, out in zip(
                              [(0, 0), (len(prompts[0]) + 9, 0)], prompts,
                              served)])
    host = [int((c[fed] >= 8).sum()) for c in reference.chosen()]
    assert sum(host) > 0
    np.testing.assert_array_equal(st["zero_pairs"][[0, 2]], host)
    share = get_registry().snapshot()["metrics"][
        "paddle_tpu_moe_zero_pick_share"]["series"][0]["value"]
    assert share == pytest.approx(sum(host) / (36 * 3))


def test_absorbed_is_unabsorbed_with_both_scale_factors(model):
    """One attention over the same 16 positions: the prefill form
    (per-head keys and values off the SCALED latent) against the
    absorbed form through the cache of UNSCALED latents (8 positions
    prefilled, 8 fed one and four at a time); and the factors are in
    the numbers: without them the output differs."""
    attn = model.layers[1].self_attn[1]
    assert attn.cfg.q_lora_scale != 1 and attn.cfg.kv_lora_scale != 1
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 16, 64), jnp.float32)
    want, _ = attn(x, cache=None)
    for step in (1, 4):
        cache = model._empty_caches(2, 32, jnp.float32)[3]
        out, cache = attn(x[:, :8], cache=cache, offset=0)
        outs = [out]
        for t in range(8, 16, step):
            out, cache = attn(x[:, t:t + step], cache=cache,
                              offset=jnp.asarray([t, t], jnp.int32))
            outs.append(out)
        np.testing.assert_allclose(np.concatenate(outs, 1), want,
                                   rtol=1e-4, atol=1e-5)
    plain = MLAMoEForCausalLM(shortcut_moe_tiny(
        mla_scale_q_lora=False, mla_scale_kv_lora=False)
    ).layers[1].self_attn[1]
    for (_, p), (_, q) in zip(plain.named_parameters(),
                              attn.named_parameters()):
        p._value = q._value
    assert np.abs(np.asarray(plain(x, cache=None)[0] - want)).max() > 1e-2


def test_forward_scopes_name_the_five_parts_of_a_layer(model):
    jaxpr = jax.make_jaxpr(lambda ids: model.forward(ids)._value)(
        jnp.zeros((1, 16), jnp.int32))
    stacks = set()

    def walk(j):
        for e in j.eqns:
            stacks.add(str(e.source_info.name_stack))
            for sub in jax.core.jaxprs_in_params(e.params):
                walk(sub)

    walk(jaxpr.jaxpr)
    text = "\n".join(stacks)
    for scope in ("layer0/layer0.attn0", "layer1/layer1.attn1",
                  "layer0/layer0.mlp0", "layer1/layer1.mlp1",
                  "layer0/layer0.moe.shortcut"):
        assert scope in text, scope


# -- (b) the router and the shares --------------------------------------------
@pytest.mark.parametrize("bias_std", [0.0, 0.05])
def test_router_is_the_reference_s(bias_std):
    """softmax over real + identity outputs, chosen on probability +
    bias, weights = scaling x probability with no division; the bias
    moves the choice and never a weight."""
    paddle.seed(3)
    gate = SigmoidTopKGate(64, 12, topk=3, routed_scaling_factor=6.0,
                           score_func="softmax", bias_on_choice=True,
                           norm_topk_prob=False)
    rng = np.random.default_rng(1)
    gate.weight._value = jnp.asarray(rng.normal(0, 0.3, (64, 12)),
                                     jnp.float32)
    gate.bias._value = jnp.asarray(rng.normal(0, bias_std, 12), jnp.float32)
    x = jnp.asarray(rng.normal(0, 1, (200, 64)), jnp.float32)
    idx, w = gate.route(x)
    with jax.default_matmul_precision("highest"):
        ridx, rw = ref.route(x, gate.weight._value, gate.bias._value,
                             {"moe_topk": 3, "routed_scaling_factor": 6.0})
    np.testing.assert_array_equal(np.asarray(idx), np.asarray(ridx))
    np.testing.assert_allclose(np.asarray(w), np.asarray(rw), rtol=1e-5)
    p = np.asarray(jax.nn.softmax(x @ gate.weight._value, -1))
    np.testing.assert_allclose(
        np.asarray(w), 6.0 * np.take_along_axis(p, np.asarray(idx), -1),
        rtol=1e-4)
    total = np.asarray(w).sum(-1)                   # no division
    assert (total < 6.0).all() and np.median(total) < 5.9
    plain = np.argsort(-p, -1)[:, :3]
    moved = (np.sort(plain, -1) != np.sort(np.asarray(idx), -1)).any(-1)
    assert moved.any() == bool(bias_std)
    # today's gates are untouched: a softmax gate has no bias, a sigmoid
    # gate has one, both renormalise
    assert not hasattr(SigmoidTopKGate(8, 4, score_func="softmax"), "bias")
    g = SigmoidTopKGate(64, 12, topk=3, routed_scaling_factor=2.5)
    np.testing.assert_allclose(np.asarray(g.route(x)[1]).sum(-1), 2.5,
                               rtol=1e-5)


def _expert_layer(offset, held, Z=4):
    return GatedMoELayer(64, 32, 8, held, offset, top_k=3,
                         routed_scaling_factor=6.0, num_shared_experts=0,
                         score_func="softmax", zero_expert_num=Z,
                         router_bias=True, norm_topk_prob=False)


def test_the_shares_add_up_to_the_uncut_layer():
    """Real experts 0..7 held 2 to a holder beside 4 identity experts:
    the holders' routed parts, with the identity term (which every
    holder computes whole for its own rows) counted once, add up to the
    layer that holds all 8, and that is the reference's uncut branch;
    every holder's counter reads tokens x k = held + absent + identity
    with the same identity count."""
    paddle.seed(0)
    whole = _expert_layer(0, 8)
    rng = np.random.default_rng(0)
    for p in whole.parameters():
        p._value = jnp.asarray(rng.normal(0, 0.2, p.shape), jnp.float32)
    x = jnp.asarray(rng.normal(0, 1, (24, 64)), jnp.float32)
    zeros = jnp.zeros((8 + 4,), jnp.int32)
    out, cnt = whole(x, counts=zeros)
    want, cnt = np.asarray(out._value), np.asarray(cnt)
    assert cnt[8] == 0 and cnt[-1] == 24          # nobody absent
    assert cnt[:8].sum() + cnt[10] == 24 * 3 and cnt[9] == cnt[:8].sum()
    cfg = dict(CFG, router_experts=8, n_routed_experts=8, expert_offset=0)
    with jax.default_matmul_precision("highest"):
        idx, g = ref.route(x, whole.gate.weight._value,
                           whole.gate.bias._value, cfg)
        ident = np.asarray(ref.identity_part(x, idx, g, cfg))
        y = ident
        for j in range(8):
            y = y + ref.expert_part(
                x, idx, g, j, whole.w_gate._value[j], whole.w_up._value[j],
                whole.w_down._value[j], "float32")
    assert int((np.asarray(idx) >= 8).sum()) == cnt[10] > 0
    np.testing.assert_allclose(want, np.asarray(y), rtol=1e-4, atol=1e-5)
    total = ident.copy()
    for off in (0, 2, 4, 6):
        part = _expert_layer(off, 2)
        for name in ("w_gate", "w_up", "w_down"):
            getattr(part, name)._value = getattr(whole, name)._value[
                off:off + 2]
        part.gate.weight._value = whole.gate.weight._value
        part.gate.bias._value = whole.gate.bias._value
        out, c = part(x, counts=jnp.zeros((2 + 4,), jnp.int32))
        c = np.asarray(c)
        assert c[4] == cnt[10] and c[:2].sum() + c[2] + c[4] == 24 * 3
        np.testing.assert_array_equal(c[:2], cnt[off:off + 2])
        total += np.asarray(out._value) - ident
    np.testing.assert_allclose(total, want, rtol=1e-4, atol=1e-5)


# -- (c) the pool, the counters and the engine's refusals ---------------------
def test_lend_refuses_unequal_lengths_of_pools_and_counters(model):
    eng = engine(model, max_batch=2)
    cache = eng.cache
    assert len(cache.pools) == len(cache.counters) == 4
    assert [len(t) for t in cache.lend()] == [3] * 4
    cache.counters = cache.counters[:2]     # one a LAYER: the old zip
    with pytest.raises(Exception, match="4 pooled tuples and 2 device"):
        cache.lend()


@pytest.mark.parametrize("kw, needle", [
    ({"prefill_chunk": 16}, "valid"),
    ({"prefill_chunk": 16, "prefix_cache": True}, "valid"),
    ({"prefix_cache": True}, "needs chunked prefill"),
    ({"prefill_chunk": 16, "spec_tokens": 2}, "valid"),
    ({"spec_tokens": 2}, "rides the unified chunked step"),
    ({"phase": "decode"}, "latent cache"),
])
def test_engine_paths_the_forward_does_not_serve_are_refused(
        model, kw, needle):
    kw = dict(kw)
    if "spec_tokens" in kw:
        kw["draft_predictor"] = create_predictor(
            Config().set_model(model).enable_paged_kv(page_size=PAGE))
    with pytest.raises(Exception, match=needle):
        engine(model, max_batch=2, **kw)


# -- (d) the models that share this code ---------------------------------------
# sha256 of the StableHLO text of the tiny engines of the two accepted
# configurations of this file's model class (``mla_moe_tiny``: sarvam's
# block; ``sparse_mla_tiny``: deepseek-v3.2-exp's), read from the parent
# commit (1eee2c7) by this very code on the CPU: every new field at its
# default, they trace to the parent's programs
PARENT_PROGRAMS = {
    "mla_moe_tiny": {
        ("prefill", 64):
            "5089c0f12faf8dc4109ab3f7fe2537d76c25412d831385dc25b74cc2e3a39cab",
        ("decode",):
            "f84af15891423ee41368e6dccced7293484198ee7447b034b660a7344e672321",
    },
    "sparse_mla_tiny": {
        ("prefill", 64):
            "181ff4fd7df35e421c17ec9f2a4bad58a0f79fff560bd9b524b0f4be86bdf33f",
        ("decode",):
            "b1674b01afed12aaaa59000b4186e8e09b49fd118a2c9064408a8d1c52259b19",
    },
}


@pytest.mark.parametrize("which", sorted(PARENT_PROGRAMS))
def test_accepted_serving_programs_are_the_parent_s_text(which):
    paddle.set_default_dtype("float32")
    model = MLAMoEForCausalLM(
        {"mla_moe_tiny": mla_moe_tiny,
         "sparse_mla_tiny": sparse_mla_tiny}[which]())
    model.eval()
    eng = engine(model, max_batch=2, decode_chunk=1)
    eng.submit(np.arange(40, dtype=np.int32), max_new_tokens=3)
    eng.run()
    got = {site: hashlib.sha256(
        eng.lowered_text(site).encode()).hexdigest()
        for site in eng.program_sites()}
    assert got == PARENT_PROGRAMS[which]
