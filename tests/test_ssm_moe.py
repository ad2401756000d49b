"""A decoder of ONE mixer a layer (models/ssm_moe.py): the state-space
mixer's forms against each other (ops/ssm.py), the model against its
plain reference (benchmarks/references/nemotron_h.py) at a tiny size on
the CPU, seeded weights, through the full forward and through
``ServingEngine``'s slot and pages; latent relu² experts in both routed
forms and the shares adding up; the engine's refusals; the programs of
the models that share the changed code.
"""
import hashlib
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

import paddle_tpu as paddle  # noqa: E402
from benchmarks.harness.families import ssm_moe_serving as fam  # noqa: E402
from benchmarks.references import nemotron_h as ref  # noqa: E402
from paddle_tpu.incubate.distributed.models.moe import (  # noqa: E402
    GatedMoELayer, moe_layer)
from paddle_tpu.inference import (Config, ServingEngine,  # noqa: E402
                                  create_predictor)
from paddle_tpu.models.hybrid_moe import (HybridMoEForCausalLM,  # noqa: E402
                                          afmoe_tiny, hybrid_moe_tiny)
from paddle_tpu.models.mla_moe import (MLAMoEForCausalLM,  # noqa: E402
                                       mla_moe_tiny)
from paddle_tpu.models.ssm_moe import (SSMMoEConfig,  # noqa: E402
                                       SSMMoEForCausalLM, ssm_moe_tiny)
from paddle_tpu.ops import ssm  # noqa: E402

PAGE, SEED = 8, 48
# ssm_moe_tiny() under the source's key names, float32 throughout
CFG = {
    "hidden_size": 64, "expand": 1, "mamba_num_heads": 8,
    "mamba_head_dim": 8, "n_groups": 2, "ssm_state_size": 16,
    "conv_kernel": 4, "use_conv_bias": True, "chunk_size": 8,
    "num_attention_heads": 8, "num_key_value_heads": 2, "head_dim": 16,
    "hybrid_override_pattern": "MEM*EM", "num_hidden_layers": 6,
    "layers_run": [0, 1, 2, 3, 4, 5], "n_routed_experts": 4,
    "router_experts": 16, "expert_offset": 4, "num_experts_per_tok": 4,
    "routed_scaling_factor": 2.5, "norm_topk_prob": True,
    "moe_intermediate_size": 48, "moe_latent_size": 32,
    "moe_shared_expert_intermediate_size": 96, "n_shared_experts": 1,
    "mlp_hidden_act": "relu2", "norm_eps": 1e-5, "vocab_size": 256,
    "time_step_min": 0.001, "time_step_max": 0.1, "time_step_floor": 1e-4,
    "torch_dtype": "float32", "ssm_state_dtype": "float32",
    "initializer_range": 0.3}


def build(cfg=CFG, max_len=128):
    paddle.set_default_dtype("float32")
    model = SSMMoEForCausalLM(fam.model_config(cfg, max_len))
    model.eval()
    fam.load(list(model.named_parameters()), cfg, ref.leaf_table(cfg), SEED)
    return model


@pytest.fixture(scope="module")
def model():
    return build()


@pytest.fixture(scope="module")
def reference():
    return ref.ServeReference(CFG, SEED)


def engine(model, **kw):
    pred = create_predictor(Config().set_model(model).enable_paged_kv(
        page_size=PAGE))
    return ServingEngine(pred, **kw)


# -- (a) the recurrence's three forms -----------------------------------------
def scan_inputs(B=2, S=37, nh=8, P=4, G=2, N=16, seed=0):
    r = np.random.default_rng(seed)
    f = lambda *s: jnp.asarray(r.normal(size=s), jnp.float32)
    return (f(B, S, nh, P), jax.nn.softplus(f(B, S, nh)),
            -jnp.asarray(r.uniform(1, 16, nh), jnp.float32),
            f(B, S, G, N), f(B, S, G, N))


@pytest.mark.parametrize("chunk", [8, 16, 64])
def test_chunked_form_is_the_sequential_scan(chunk):
    """37 positions are no multiple of 8 or 16, and fewer than 64."""
    x, dt, A, Bm, Cm = scan_inputs()
    y0, H0 = ssm.ssd_sequential(x, dt, A, Bm, Cm)
    y1, H1 = ssm.ssd_chunked(x, dt, A, Bm, Cm, chunk)
    np.testing.assert_allclose(y1, y0, rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(H1, H0, rtol=1e-4, atol=1e-5)


def test_prefill_then_steps_is_the_sequential_scan():
    x, dt, A, Bm, Cm = scan_inputs()
    y0, H0 = ssm.ssd_sequential(x, dt, A, Bm, Cm)
    k = 20
    _, H = ssm.ssd_chunked(x[:, :k], dt[:, :k], A, Bm[:, :k], Cm[:, :k], 8)
    ys = []
    for t in range(k, x.shape[1]):
        y, H = ssm.ssd_step(H, x[:, t], dt[:, t], A, Bm[:, t], Cm[:, t])
        ys.append(y)
    np.testing.assert_allclose(jnp.stack(ys, 1), y0[:, k:], rtol=1e-4,
                               atol=1e-4)
    np.testing.assert_allclose(H, H0, rtol=1e-4, atol=1e-5)


def test_a_step_of_zero_changes_no_state():
    x, dt, A, Bm, Cm = scan_inputs()
    real = jnp.arange(x.shape[1])[None, :, None] < jnp.asarray(
        [23, 37])[:, None, None]
    _, H = ssm.ssd_chunked(x, jnp.where(real, dt, 0.0), A, Bm, Cm, 8)
    _, H23 = ssm.ssd_chunked(x[:1, :23], dt[:1, :23], A, Bm[:1, :23],
                             Cm[:1, :23], 8)
    np.testing.assert_allclose(H[0], H23[0], rtol=1e-5, atol=1e-6)


def test_convolution_forms_and_its_tail():
    r = np.random.default_rng(1)
    c = jnp.asarray(r.normal(size=(2, 11, 6)), jnp.float32)
    w = jnp.asarray(r.normal(size=(6, 4)), jnp.float32)
    b = jnp.asarray(r.normal(size=(6,)), jnp.float32)
    full = ssm.causal_conv(c, w, b)
    want = np.zeros((2, 11, 6), np.float32)
    cp = np.pad(np.asarray(c), ((0, 0), (3, 0), (0, 0)))
    for t in range(11):
        want[:, t] = (cp[:, t:t + 4] * np.asarray(w).T[None]).sum(1) + b
    np.testing.assert_allclose(full, jax.nn.silu(want), rtol=1e-5,
                               atol=1e-6)
    # the tail of a row of 2 real positions reaches before position 0
    tail = ssm.conv_tail(c, jnp.asarray([2, 9]), 4)
    np.testing.assert_array_equal(tail[0, 0], 0.0)
    np.testing.assert_array_equal(tail[0, 1:], c[0, :2])
    np.testing.assert_array_equal(tail[1], c[1, 6:9])
    np.testing.assert_array_equal(ssm.conv_tail(c, None, 4), c[:, 8:])
    out, new = ssm.conv_step(tail.reshape(2, -1), c[:, 9], w, b)
    np.testing.assert_allclose(out[1], full[1, 9], rtol=1e-5, atol=1e-6)
    np.testing.assert_array_equal(new[1].reshape(3, 6), c[1, 7:10])


# -- (b) the mixer: a padded prefill, a reused slot ---------------------------
def test_right_padded_prefill_hands_decode_the_unpadded_state(model):
    mixer = model.layers[0].mixer
    r = np.random.default_rng(2)
    u = jnp.asarray(r.normal(size=(1, 32, 64)), jnp.float32)
    empty = lambda: tuple(
        jnp.zeros((1,) + s, d or jnp.float32)
        for s, d in model.state_shapes()[0])
    y19, (H19, t19) = mixer(u[:, :19], cache=empty(), offset=0)
    # 13 positions of pad that the recurrence must not walk
    y32, (H32, t32) = mixer(u, cache=empty(), offset=0,
                            lengths=jnp.asarray([19]))
    np.testing.assert_allclose(H32, H19, rtol=1e-5, atol=1e-6)
    np.testing.assert_array_equal(t32, t19)
    np.testing.assert_allclose(y32[:, :19], y19, rtol=1e-4, atol=1e-5)
    # and WITHOUT the lengths the pad is walked: the state differs
    _, (Hbad, _) = mixer(u, cache=empty(), offset=0)
    assert float(jnp.abs(Hbad - H19).max()) > 1e-3


def test_prefill_writes_the_slot_whole_and_no_other(model):
    """The slot's last request leaves nothing behind: the arrays start
    as garbage, a prefill of slot 1 overwrites slot 1 alone."""
    mixer = model.layers[0].mixer
    r = np.random.default_rng(3)
    u = jnp.asarray(r.normal(size=(1, 16, 64)), jnp.float32)
    dirty = tuple(jnp.full((3,) + s, 7.0, d or jnp.float32)
                  for s, d in model.state_shapes()[0])
    clean = tuple(jnp.zeros((3,) + s, d or jnp.float32)
                  for s, d in model.state_shapes()[0])
    slot = jnp.asarray([1], jnp.int32)
    _, (Hd, td, _) = mixer(u, cache=dirty + (slot,), offset=0,
                           lengths=jnp.asarray([11]))
    _, (Hc, tc, _) = mixer(u, cache=clean + (slot,), offset=0,
                           lengths=jnp.asarray([11]))
    np.testing.assert_array_equal(Hd[1], Hc[1])
    np.testing.assert_array_equal(td[1], tc[1])
    assert float(jnp.abs(Hd[1]).max()) > 0
    for other in (0, 2):
        np.testing.assert_array_equal(Hd[other], 7.0)
        np.testing.assert_array_equal(td[other], 7.0)


def test_a_chunk_at_an_offset_is_refused(model):
    mixer = model.layers[0].mixer
    cache = tuple(jnp.zeros((1,) + s, d or jnp.float32)
                  for s, d in model.state_shapes()[0])
    with pytest.raises(Exception, match="the slot no longer holds"):
        mixer(jnp.zeros((1, 4, 64)), cache=cache,
              offset=jnp.asarray([8]))


# -- (c) the model against the reference --------------------------------------
def test_tiny_preset_is_the_family_s_mapping():
    c, got = ssm_moe_tiny(), fam.model_config(CFG, 128)
    for f in SSMMoEConfig.__dataclass_fields__:
        if f not in ("initializer_range", "attention_block"):
            assert getattr(got, f) == getattr(c, f), f
    assert c.mixer_kinds == ["ssm", "experts", "ssm", "attention",
                             "experts", "ssm"]
    with pytest.raises(Exception, match="mixer_kinds"):
        ssm_moe_tiny(mixer_kinds=["ssm", "mlp"])


def test_contract_a_layer(model):
    assert model.kv_page_classes() == ["state", "none", "state", "full",
                                       "none", "state"]
    assert [len(s) for s in model.kv_pool_shapes(9, PAGE)] == \
        [0, 0, 0, 2, 0, 0]
    assert [len(s) for s in model.state_shapes()] == [2, 0, 2, 0, 0, 2]
    assert model.moe_counter_layers() == [1, 4]
    assert model.moe_counter_shape() == (2, 4 + 3)
    assert model.head_on_last_row is True
    names = {n for n, _ in model.named_parameters()}
    assert "layers.1.mixer.latent_down" in names
    assert "layers.1.mixer.w_gate" not in names         # relu2: two
    assert "layers.1.mixer.shared_gate" not in names    # matrices
    assert dict(model.named_parameters())[
        "layers.1.mixer.shared_up"].shape == [64, 96]


def test_full_forward_is_the_reference(model, reference):
    tokens = np.random.default_rng(48).integers(0, 256, 45).astype(np.int32)
    got = np.asarray(model(paddle.to_tensor(tokens[None]))._value)[0]
    want = reference.logits([(tokens[:1], np.append(tokens[1:], 0))])[0]
    np.testing.assert_allclose(got, want, rtol=5e-4, atol=5e-4)


def test_engine_through_slot_and_pages_is_the_reference(model, reference):
    """Seven requests over three rows, so rows are admitted and released
    mid-run and every slot is reused: each served token is the
    reference's best of a full forward, within a float32 rounding."""
    r = np.random.default_rng(5)
    eng = engine(model, max_batch=3, decode_chunk=1, debug_invariants=True)
    reqs = []
    for L, n in [(11, 6), (24, 9), (17, 4), (5, 12), (30, 7), (9, 5),
                 (13, 8)]:
        prompt = r.integers(0, 256, L).astype(np.int32)
        reqs.append((eng.submit(prompt, max_new_tokens=n), prompt))
    done = eng.run()
    pairs = [(p, np.asarray(done[rid].new_tokens)) for rid, p in reqs]
    for lg, (_, served) in zip(reference.logits(pairs), pairs):
        assert ref.served_gap(lg, served).max() < 1e-3
    st = eng.moe_stats()
    assert st["pairs"].shape == (2, 4)          # two counters, six layers
    assert st["dropped"] == 0 and (st["tokens"] > 0).all()
    assert st["forms"] == {"decode": "batched", "prefill": "batched"}
    eng.check_invariants()
    assert eng.cache.counts()["classes"]["state"] == {"used": 0, "free": 3}
    mem = eng.memory_summary()["state"]
    assert mem["state_bytes"] == 3 * mem["state_row_bytes"] > 0
    text = eng.lowered_text(("decode",))
    for scope in ("layer0.ssm", "layer0.ssm.scan", "layer3.attn.full",
                  "layer1.moe", "layer1.moe.latent"):
        assert scope in eng._lower(("decode",)).as_text(debug_info=True)
    assert "tpu_custom_call" not in text        # the CPU's dense twins


def test_static_cache_generate_is_the_engine(model):
    """``Predictor.generate`` over the static caches and over the paged
    cache (B rows prefilled at once, their slots their rows)."""
    ids = np.random.default_rng(6).integers(0, 256, (2, 16)).astype(
        np.int32)
    outs = []
    for paged in (False, True):
        conf = Config().set_model(model)
        if paged:
            conf.enable_paged_kv(page_size=PAGE)
        out = create_predictor(conf).generate(
            paddle.to_tensor(ids), max_new_tokens=5, lengths=[11, 16])
        outs.append(np.asarray(out._value))
    np.testing.assert_array_equal(outs[0], outs[1])


# -- (d) latent relu2 experts -------------------------------------------------
def latent_layer(offset=4, held=4, seed=9, **kw):
    paddle.set_default_dtype("float32")
    paddle.seed(seed)
    layer = GatedMoELayer(
        64, 48, 16, held, offset, top_k=4, routed_scaling_factor=2.5,
        num_shared_experts=0, latent_size=32, activation="relu2",
        shared_hidden=96, **kw)
    r = np.random.default_rng(seed)
    layer.gate.bias._value = jnp.asarray(r.normal(0, 0.02, 16), jnp.float32)
    return layer


def reference_layer(layer, x, held):
    """references/nemotron_h.py's own functions over the layer's
    weights: the routed part of the held experts, the shared expert."""
    cfg = {"num_experts_per_tok": 4, "routed_scaling_factor": 2.5,
           "norm_topk_prob": True}
    with jax.default_matmul_precision("highest"):
        idx, w = ref.route(x, layer.gate.weight._value,
                           layer.gate.bias._value, cfg)
        low = x @ layer.latent_down._value
        r = sum(ref.expert_part(low, idx, w, j, layer.w_up._value[i],
                                layer.w_down._value[i], "float32")
                for i, j in enumerate(held))
        return r @ layer.latent_up._value, ref.relu2(
            x, layer.shared_up._value, layer.shared_down._value, "float32")


@pytest.mark.parametrize("tokens, form", [(24, "batched"), (160, "sorted")])
def test_latent_relu2_experts_in_both_routed_forms(tokens, form,
                                                   monkeypatch):
    monkeypatch.setattr(moe_layer, "_BATCHED_MAX_TOKENS", 128)
    assert moe_layer.routed_form(tokens) == form
    layer = latent_layer(latent_scope="layer9.moe.latent")
    x = jnp.asarray(np.random.default_rng(1).normal(size=(tokens, 64)),
                    jnp.float32)
    got, counts = layer(x, counts=jnp.zeros((4 + 3,), jnp.int32))
    routed, shared = reference_layer(layer, x, range(4, 8))
    np.testing.assert_allclose(got._value, routed + shared, rtol=2e-4,
                               atol=2e-5)
    counts = np.asarray(counts)
    assert counts[-1] == tokens
    assert counts[:4].sum() + counts[4] == tokens * 4   # held + absent
    assert counts[5] == counts[:4].sum()                # all summed


def test_the_shares_add_up():
    """Over the four ``expert_offset``s the routed parts, with the
    projections' linearity used once and the shared expert counted
    once, equal the uncut layer."""
    x = jnp.asarray(np.random.default_rng(2).normal(size=(24, 64)),
                    jnp.float32)
    whole = latent_layer(offset=0, held=16)
    parts = []
    for k in range(4):
        part = latent_layer(offset=4 * k, held=4)
        for n in ("latent_down", "latent_up", "shared_up", "shared_down"):
            getattr(part, n)._value = getattr(whole, n)._value
        part.gate.weight._value = whole.gate.weight._value
        part.gate.bias._value = whole.gate.bias._value
        part.w_up._value = whole.w_up._value[4 * k:4 * k + 4]
        part.w_down._value = whole.w_down._value[4 * k:4 * k + 4]
        parts.append(part(x)._value)
    shared = moe_layer.relu2_mlp(x, whole.shared_up._value,
                                 whole.shared_down._value)
    total = sum(parts) - 3 * shared         # the shared expert once
    np.testing.assert_allclose(total, whole(x)._value, rtol=2e-4,
                               atol=2e-5)
    routed, sh = reference_layer(whole, x, range(16))
    np.testing.assert_allclose(total, routed + sh, rtol=2e-4, atol=2e-5)


def test_what_the_expert_layer_refuses():
    with pytest.raises(Exception, match="'swiglu' or 'relu2'"):
        GatedMoELayer(64, 48, 16, activation="gelu")
    with pytest.raises(Exception, match="exclude each other"):
        GatedMoELayer(64, 48, 16, latent_size=32, zero_expert_num=4,
                      score_func="softmax")


# -- (e) what the engine refuses ----------------------------------------------
@pytest.mark.parametrize("kw, needle", [
    (dict(prefill_chunk=16),
     "prefill_chunk.*state layers.*one slot a row.*would have to carry"),
    (dict(prefill_chunk=16, prefix_cache=True),
     "prefill_chunk.*state layers"),
    (dict(prefix_cache=True), "prefix cache.*state layers.*donor's state"),
    (dict(host_spill_pages=4), "prefix cache.*state layers"),
    (dict(spec_tokens=2), "speculative decoding.*state layers.*roll back"),
    (dict(phase="decode"), "disaggregated phases.*state layers.*migrates"),
])
def test_engine_refuses_with_the_reason(model, kw, needle):
    with pytest.raises(Exception, match=needle):
        engine(model, max_batch=2, **kw)


def test_admission_waits_for_a_slot(model):
    """More requests than rows: every one is served, a slot a row."""
    eng = engine(model, max_batch=2, debug_invariants=True)
    rids = [eng.submit(np.arange(5 + i, dtype=np.int32), max_new_tokens=3)
            for i in range(5)]
    eng.step()
    assert eng.cache.counts()["classes"]["state"]["used"] == 2
    assert not eng.cache.slots_available()
    done = eng.run()
    assert sorted(done) == rids


# -- (f) the models that share the changed code -------------------------------
# sha256 of the StableHLO text of the tiny engines of three accepted
# model presets, read from the parent commit (a9d24ff) by this very code
# on the CPU: with every new argument of GatedMoELayer at its default and
# no state layer, they trace to the parent's programs
PARENT_PROGRAMS = {
    "afmoe_tiny": {
        ("prefill", 64):
            "bea7b266a82ff39fbaf271ce99aaab41ebde17f18ee2d18312697bdd18ede4d8",
        ("decode",):
            "f9303eed17c430096218de253f44027c51345367533720d3cc5b54d65dcbdffe",
    },
    "hybrid_moe_tiny": {
        ("prefill", 64):
            "947e01155876be704576a1aaf5cad87e882b25fdb57202cd2f16384a7cd6b594",
        ("decode",):
            "9c99701abb4520ae663c6a4cc1fabab2d27bd0e3dcc01db9157ed43c0ef28631",
    },
    "mla_moe_tiny": {
        ("prefill", 64):
            "5089c0f12faf8dc4109ab3f7fe2537d76c25412d831385dc25b74cc2e3a39cab",
        ("decode",):
            "f84af15891423ee41368e6dccced7293484198ee7447b034b660a7344e672321",
    },
}


@pytest.mark.parametrize("which", sorted(PARENT_PROGRAMS))
def test_accepted_serving_programs_are_the_parent_s_text(which):
    paddle.set_default_dtype("float32")
    paddle.seed(0)
    cls, make = {"afmoe_tiny": (HybridMoEForCausalLM, afmoe_tiny),
                 "hybrid_moe_tiny": (HybridMoEForCausalLM, hybrid_moe_tiny),
                 "mla_moe_tiny": (MLAMoEForCausalLM, mla_moe_tiny)}[which]
    model = cls(make())
    model.eval()
    eng = engine(model, max_batch=2, decode_chunk=1)
    eng.submit(np.arange(40, dtype=np.int32), max_new_tokens=3)
    eng.run()
    got = {site: hashlib.sha256(
        eng.lowered_text(site).encode()).hexdigest()
        for site in eng.program_sites()}
    assert got == PARENT_PROGRAMS[which]
