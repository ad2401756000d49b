"""The tape's backward of the Pallas flash-attention ops (ISSUE 38).

The generic backward, ``jax.vjp`` of the op, runs the forward kernel a
second time for the custom_vjp's residuals, and XLA does not merge two
Mosaic calls. The Pallas ops (``flash_attention_pallas``,
``flash_attn_varlen_pallas``) return the rows' logsumexp beside ``out``
and register a grad kernel that calls the two backward kernels on them.
Checked here on the CPU with the gate forced open and the kernels in
interpreter mode: what the traced program holds, that the gradients are
the generic path's bit for bit, that the XLA ops still take the generic
path, and the counter that says which path the tape took.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
import paddle_tpu.ops.attention as attn
from paddle_tpu.autograd import engine as tape
from paddle_tpu.core.dispatch import def_op
from paddle_tpu.ops.pallas.flash_attention import flash_attention_fwd

FLASH_KERNELS = ("flash_attention_fwd", "flash_attention_dq",
                 "flash_attention_dkv")


@pytest.fixture
def pallas_on_cpu(monkeypatch):
    """Open the gate and run the kernels in interpreter mode (the ops
    pass interpret=False: no kernel picks the mode for itself)."""
    fwd, bwd = attn.flash_attention_with_lse, attn.flash_attention_bwd
    monkeypatch.setattr(attn, "is_tpu_platform", lambda: True)
    monkeypatch.setattr(
        attn, "flash_attention_with_lse",
        lambda q, k, v, causal, scale, _interpret, *seg:
        fwd(q, k, v, causal, scale, True, *seg))
    monkeypatch.setattr(
        attn, "flash_attention_bwd",
        lambda q, k, v, out, lse, g, causal, scale, _interpret, *seg:
        bwd(q, k, v, out, lse, g, causal, scale, True, *seg))


@def_op("test_flash_attention_generic")
def _generic_flash(q, k, v, causal=False):
    """The op as it was: the kernel's custom_vjp behind the tape's
    generic backward."""
    rep = q.shape[2] // k.shape[2]
    if rep != 1:
        k, v = jnp.repeat(k, rep, axis=2), jnp.repeat(v, rep, axis=2)
    return flash_attention_fwd(q, k, v, causal, None, True)


def _pallas_calls(jaxpr, found=None):
    """kernel name -> count over the equations of a jaxpr, walking into
    sub-jaxprs the program runs (a custom_vjp_call's ``call_jaxpr``) and
    not into the text a printed jaxpr also shows (``fwd_jaxpr_thunk``,
    ``bwd``), which is no equation of this program."""
    found = {} if found is None else found
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            name = eqn.params["name"]
            found[name] = found.get(name, 0) + 1
        for key, val in eqn.params.items():
            if key in ("fwd_jaxpr_thunk", "bwd"):
                continue
            for sub in (val if isinstance(val, (list, tuple)) else [val]):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    _pallas_calls(sub, found)
    return found


def _rand(shape, dtype=jnp.float32, seed=0):
    return jnp.asarray(np.random.RandomState(seed).randn(*shape), dtype)


def _taped_grads(op, arrays, weight, **attrs):
    """out and d(sum(out * weight))/d(arrays) through the tape."""
    ts = [paddle.to_tensor(a, stop_gradient=False) for a in arrays]
    out = op(*ts, **attrs)
    (out * paddle.to_tensor(weight)).sum().backward()
    return (out._value,) + tuple(t.grad._value for t in ts)


# ---------------------------------------------------------------------------
# (a) what the traced program holds
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("n_calls", [1, 3])
def test_one_forward_kernel_per_attention_call(pallas_on_cpu, n_calls):
    q = _rand((1, 128, 2, 128))

    def step(op, q, k, v):
        tq, tk, tv = (paddle.to_tensor(a, stop_gradient=False)
                      for a in (q, k, v))
        x = tq
        for _ in range(n_calls):
            x = op(x, tk, tv, causal=True)
        x.sum().backward()
        return x._value, tq.grad._value, tk.grad._value, tv.grad._value

    def calls(op):
        closed = jax.make_jaxpr(lambda *a: step(op, *a))(q, q, q)
        live, _ = jax._src.interpreters.partial_eval.dce_jaxpr(
            closed.jaxpr, [True] * len(closed.jaxpr.outvars))
        return _pallas_calls(closed.jaxpr), _pallas_calls(live)

    want = {name: n_calls for name in FLASH_KERNELS}
    assert calls(attn.flash_attention) == (want, want)
    # the walk does see a replay: the generic backward holds two
    # forwards a call, dead-code elimination or not
    twice = dict(want, flash_attention_fwd=2 * n_calls)
    assert calls(_generic_flash) == (twice, twice)


# ---------------------------------------------------------------------------
# (b) the same gradients, bit for bit
# ---------------------------------------------------------------------------
_CASES = {
    "causal": dict(q=(2, 256, 2, 128), kv=(2, 256, 2, 128), causal=True),
    "full": dict(q=(2, 256, 2, 128), kv=(2, 256, 2, 128), causal=False),
    "rectangular": dict(q=(1, 128, 2, 128), kv=(1, 384, 2, 128),
                        causal=True),
    "grouped_kv": dict(q=(1, 128, 4, 128), kv=(1, 128, 1, 128),
                       causal=True),
    "bf16": dict(q=(1, 256, 2, 128), kv=(1, 256, 2, 128), causal=True,
                 dtype=jnp.bfloat16),
    "bf16_grouped_kv": dict(q=(1, 128, 8, 128), kv=(1, 128, 2, 128),
                            causal=False, dtype=jnp.bfloat16),
}


@pytest.mark.parametrize("case", _CASES.values(), ids=_CASES.keys())
def test_grad_kernel_equals_generic_vjp(pallas_on_cpu, case):
    dtype = case.get("dtype", jnp.float32)
    q = _rand(case["q"], dtype, 1)
    k = _rand(case["kv"], dtype, 2)
    v = _rand(case["kv"], dtype, 3)
    w = _rand(case["q"], dtype, 4)

    got = _taped_grads(attn.flash_attention, (q, k, v), w,
                       causal=case["causal"])
    assert tape.last_backward_nodes()[0] == 1

    def loss(q, k, v):
        out = _generic_flash.raw(q, k, v, causal=case["causal"])
        return jnp.sum(out * w), out

    grads, out = jax.grad(loss, (0, 1, 2), has_aux=True)(q, k, v)
    for name, a, b in zip(("out", "dq", "dk", "dv"), got, (out,) + grads):
        assert a.dtype == b.dtype and a.shape == b.shape, name
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b), name)
    # and the generic path through the tape agrees with jax.grad
    old = _taped_grads(_generic_flash, (q, k, v), w, causal=case["causal"])
    assert tape.last_backward_nodes()[0] == 0
    for a, b in zip(got, old):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("causal", [True, False])
def test_varlen_grad_kernel_equals_generic_vjp(pallas_on_cpu, causal):
    T, H, D = 256, 2, 128
    q, k, v, w = (_rand((T, H, D), seed=s) for s in range(4))
    cu = paddle.to_tensor(np.array([0, 96, 160, 256], np.int32))

    got = _taped_grads(
        lambda q, k, v: attn.flash_attn_varlen(q, k, v, cu, cu,
                                               causal=causal, scale=0.11),
        (q, k, v), w)
    assert tape.last_backward_nodes()[0] == 1

    seg = attn._segments_from_cu(cu._value, T)[None]

    def loss(q, k, v):
        out = flash_attention_fwd(q[None], k[None], v[None], causal, 0.11,
                                  True, seg, seg)[0]
        return jnp.sum(out * w), out

    grads, out = jax.grad(loss, (0, 1, 2), has_aux=True)(q, k, v)
    for name, a, b in zip(("out", "dq", "dk", "dv"), got, (out,) + grads):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b), name)
    # tokens of one sequence only: the first sequence's gradient does
    # not see the weights of the others
    w2 = w.at[96:].set(0.0)
    alone = _taped_grads(
        lambda q, k, v: attn.flash_attn_varlen(q, k, v, [0, 96, 160, 256],
                                               [0, 96, 160, 256],
                                               causal=False, scale=0.11),
        (q, k, v), w2)
    assert float(jnp.abs(alone[1][96:]).max()) == 0.0


def _call_flash(q, cu, *args, **kw):
    return attn.flash_attention_pallas(q[None], q[None], q[None],
                                       *args, **kw)


def _call_varlen(q, cu, *args, **kw):
    return attn.flash_attn_varlen_pallas(q, q, q, cu, cu, *args, **kw)


@pytest.mark.parametrize("call", [_call_flash, _call_varlen])
def test_a_static_reaches_the_grad_kernel_or_is_refused(pallas_on_cpu, call):
    """run_grad hands a grad kernel the node's KEYWORD statics only, so a
    static passed by position would run a causal forward and a full
    backward: the Pallas ops refuse it, and the keyword arrives."""
    q = paddle.to_tensor(_rand((128, 2, 128)), stop_gradient=False)
    cu = jnp.asarray([0, 128], jnp.int32)
    with pytest.raises(TypeError):
        call(q, cu, True)
    grads = {}
    for causal in (True, False):
        q.clear_grad()
        call(q, cu, causal=causal)[0].sum().backward()
        assert tape.last_backward_nodes()[0] == 1
        grads[causal] = np.asarray(q.grad._value)
    # the last row attends to every key either way; the first row sees
    # one key under the causal mask and all of them without it
    assert np.abs(grads[True] - grads[False]).max() > 1e-3
    want = jax.grad(lambda a: jnp.sum(flash_attention_fwd(
        a[None], a[None], a[None], True, None, True)))(q._value)
    # dq + dk + dv: the tape adds the three in another order
    np.testing.assert_allclose(grads[True], np.asarray(want), rtol=1e-6)


def test_pallas_op_differentiates_under_jax_vjp_too(pallas_on_cpu):
    """pp_layers runs a block under no_grad() and differentiates the
    pure stage function with jax.vjp: the op's raw kernel keeps a
    custom_vjp of its own, whose lse output takes no cotangent."""
    q, k, v, w = (_rand((1, 128, 2, 128), seed=s) for s in range(4))

    def loss(fn):
        return jax.grad(lambda q, k, v: jnp.sum(fn(q, k, v) * w),
                        (0, 1, 2))(q, k, v)

    got = loss(lambda q, k, v: attn.flash_attention_pallas.raw(
        q, k, v, causal=True)[0])
    want = loss(lambda q, k, v: _generic_flash.raw(q, k, v, causal=True))
    for a, b in zip(got, want):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    with paddle.no_grad():
        out = attn.flash_attention(paddle.to_tensor(q), paddle.to_tensor(k),
                                   paddle.to_tensor(v), causal=True)
    assert out.stop_gradient and tuple(out.shape) == (1, 128, 2, 128)


# ---------------------------------------------------------------------------
# (c) the XLA ops keep the generic path
# ---------------------------------------------------------------------------
_XLA_CASES = {
    # as on a TPU, with shapes and arguments the gate refuses
    "head_dim_64": (True, (2, 128, 2, 64), (2, 128, 2, 64), {}),
    "grouped_head_dim_64": (True, (2, 128, 4, 64), (2, 128, 2, 64), {}),
    "q_longer_than_kv": (True, (1, 256, 2, 128), (1, 128, 2, 128), {}),
    "dropout": (True, (1, 128, 2, 128), (1, 128, 2, 128),
                {"dropout": 0.25}),
    # and off the TPU, where the gate is shut for every shape
    "cpu": (False, (1, 128, 2, 128), (1, 128, 2, 128), {}),
}


@pytest.mark.parametrize("case", _XLA_CASES.values(), ids=_XLA_CASES.keys())
def test_xla_path_keeps_the_generic_backward(monkeypatch, case):
    as_tpu, q_shape, kv_shape, attrs = case
    monkeypatch.setattr(attn, "is_tpu_platform", lambda: as_tpu)

    def boom(*a, **k):
        raise AssertionError("the Pallas kernel was reached")

    monkeypatch.setattr(attn, "flash_attention_with_lse", boom)
    monkeypatch.setattr(attn, "flash_attention_bwd", boom)
    if attrs:
        attrs = dict(attrs, dropout_key=jax.random.PRNGKey(5))
    q, k, v, w = (_rand(s, seed=i) for i, s in
                  enumerate((q_shape, kv_shape, kv_shape, q_shape)))

    got = _taped_grads(attn.flash_attention, (q, k, v), w, causal=True,
                       **attrs)
    assert tape.last_backward_nodes()[0] == 0

    def loss(q, k, v):
        out = attn.flash_attention_xla.raw(q, k, v, causal=True, **attrs)
        return jnp.sum(out * w), out

    grads, out = jax.grad(loss, (0, 1, 2), has_aux=True)(q, k, v)
    for name, a, b in zip(("out", "dq", "dk", "dv"), got, (out,) + grads):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-6,
                                   atol=1e-6, err_msg=name)


def test_varlen_xla_path_keeps_the_generic_backward(monkeypatch):
    """Unequal packs under causal: the kernel's global frontier would be
    wrong, so the op takes the dense mask even on a TPU."""
    monkeypatch.setattr(attn, "is_tpu_platform", lambda: True)
    q, k, v, w = (_rand((128, 2, 128), seed=s) for s in range(4))
    cu_q, cu_k = [0, 64, 128], [0, 32, 128]
    got = _taped_grads(
        lambda q, k, v: attn.flash_attn_varlen(q, k, v, cu_q, cu_k,
                                               causal=True),
        (q, k, v), w)
    assert tape.last_backward_nodes()[0] == 0

    grads = jax.grad(lambda q, k, v: jnp.sum(attn.flash_attn_varlen_xla.raw(
        q, k, v, cu_q, cu_k, causal=True) * w), (0, 1, 2))(q, k, v)
    for a, b in zip(got[1:], grads):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-6,
                                   atol=1e-6)


def test_names_that_key_on_the_op_follow_both_paths():
    from paddle_tpu import amp
    from paddle_tpu.distributed.auto_parallel import spmd_rules

    for name in ("flash_attention", "flash_attention_pallas"):
        assert name in amp.white_list()
        assert spmd_rules._RULES[name] is spmd_rules._attention
    assert attn.flash_attention_xla.opdef.name == "flash_attention"
    assert attn.flash_attention_xla.opdef.grad_fn is None
    assert attn.flash_attn_varlen_xla.opdef.grad_fn is None
    assert attn.flash_attention_pallas.opdef.grad_fn is not None
    assert attn.flash_attn_varlen_pallas.opdef.grad_fn is not None


def test_amp_and_dist_attr_reach_the_pallas_op(pallas_on_cpu):
    from jax.sharding import PartitionSpec as P

    q = paddle.to_tensor(_rand((1, 128, 2, 128)), stop_gradient=False)
    q.dist_attr = P("dp", None, "mp", None)
    with paddle.amp.auto_cast(dtype="bfloat16"):
        out = attn.flash_attention(q, q, q, causal=True)
    assert out._value.dtype == jnp.bfloat16
    assert tuple(out.dist_attr) == ("dp", None, "mp", None)


# ---------------------------------------------------------------------------
# (d) the counter, read from the engine of a two-layer GPT step
# ---------------------------------------------------------------------------
def _gpt_step(monkeypatch, attention):
    """One SGD step (lr 1: the update IS the gradient) of a two-layer
    GPT with heads of 128 through ParallelEngine -> loss, gradients,
    the engine."""
    import paddle_tpu.models.gpt as gpt
    from paddle_tpu.distributed import fleet
    from paddle_tpu.distributed.engine import ParallelEngine

    strategy = fleet.DistributedStrategy()
    strategy.hybrid_configs = {"dp_degree": 1, "mp_degree": 1}
    hcg = fleet.init(is_collective=True, strategy=strategy)
    paddle.seed(7)
    cfg = gpt.GPTConfig(vocab_size=256, hidden_size=256, num_layers=2,
                        num_heads=2, max_position_embeddings=128)
    model = gpt.GPTForCausalLM(cfg)
    crit = gpt.GPTPretrainingCriterion(cfg)
    opt = paddle.optimizer.SGD(learning_rate=1.0,
                               parameters=model.parameters())
    eng = ParallelEngine(model, opt, hcg.mesh)
    ids = np.random.RandomState(0).randint(0, 256, (2, 129))
    before = [np.asarray(p._value) for p in model.parameters()]
    monkeypatch.setattr(gpt, "flash_attention", attention)
    step = eng.train_step(lambda m, b: crit(m(b["x"]), b["y"]))
    loss = float(step({"x": paddle.to_tensor(ids[:, :-1]),
                       "y": paddle.to_tensor(ids[:, 1:])}))
    grads = [b - np.asarray(p._value)
             for b, p in zip(before, model.parameters())]
    return loss, grads, eng


def test_gpt_step_counts_two_explicit_grad_nodes(pallas_on_cpu, monkeypatch):
    loss, grads, eng = _gpt_step(monkeypatch, attn.flash_attention)
    explicit, generic = eng.backward_nodes
    assert explicit == 2 and generic > 0
    assert eng.stats.compiles == 1

    was_loss, was_grads, was_eng = _gpt_step(monkeypatch, _generic_flash)
    assert was_eng.backward_nodes == (0, explicit + generic)
    assert loss == was_loss and np.isfinite(loss)
    assert any(np.abs(g).max() > 0 for g in grads)
    for g, w in zip(grads, was_grads):
        np.testing.assert_array_equal(g, w)


# ---------------------------------------------------------------------------
# (e) chip_smoke's count of the compiled step's kernels, in each layout
# ---------------------------------------------------------------------------
_LAYOUTS = {"train": {"dp_degree": 1, "mp_degree": 1},
            "mp2dp2": {"dp_degree": 2, "mp_degree": 2},
            "pp2mp2": {"dp_degree": 1, "mp_degree": 2, "pp_degree": 2}}


@pytest.mark.parametrize("layout", _LAYOUTS)
def test_chip_smoke_flash_counts_by_layout(monkeypatch, layout):
    """chip_smoke.check_flash_calls on what it reads on the chip: one
    step on CPU devices with the kernels in interpreter mode, then the
    SAME jitted step lowered again for the TPU platform with the kernels
    as Mosaic calls. The tape's layouts hold one forward a layer and as
    many explicit grad nodes; the pipeline scans its stacked layers under
    no_grad(): O(1) calls in its text and no op node on its tape."""
    import chip_smoke
    import paddle_tpu.models.gpt as gpt
    from paddle_tpu.distributed import fleet
    from paddle_tpu.distributed.engine import ParallelEngine

    mode = {"interpret": True}
    fwd, bwd = attn.flash_attention_with_lse, attn.flash_attention_bwd
    monkeypatch.setattr(attn, "is_tpu_platform", lambda: True)
    monkeypatch.setattr(
        attn, "flash_attention_with_lse",
        lambda q, k, v, causal, scale, _interpret, *seg:
        fwd(q, k, v, causal, scale, mode["interpret"], *seg))
    monkeypatch.setattr(
        attn, "flash_attention_bwd",
        lambda q, k, v, out, lse, g, causal, scale, _interpret, *seg:
        bwd(q, k, v, out, lse, g, causal, scale, mode["interpret"], *seg))

    degrees = _LAYOUTS[layout]
    pipe = "pp_degree" in degrees
    strategy = fleet.DistributedStrategy()
    strategy.hybrid_configs = dict(degrees)
    if pipe:
        strategy.pipeline_configs = {"accumulate_steps": 2,
                                     "micro_batch_size": 2}
    hcg = fleet.init(is_collective=True, strategy=strategy)
    paddle.seed(3)
    n_layers = 4
    cfg = gpt.GPTConfig(vocab_size=256, hidden_size=512,
                        num_layers=n_layers, num_heads=4,
                        max_position_embeddings=128)
    model = gpt.GPTForCausalLMPipe(cfg) if pipe else gpt.GPTForCausalLM(cfg)
    opt = paddle.optimizer.AdamW(learning_rate=1e-3,
                                 parameters=model.parameters())
    ids = np.random.RandomState(0).randint(0, 256, (4, 129))
    x, y = paddle.to_tensor(ids[:, :-1]), paddle.to_tensor(ids[:, 1:])
    if pipe:
        dist_model = fleet.distributed_model(model)
        loss = dist_model.train_batch([x, y],
                                      fleet.distributed_optimizer(opt))
        eng = dist_model.engine
    else:
        crit = gpt.GPTPretrainingCriterion(cfg)
        eng = ParallelEngine(model, opt, hcg.mesh)
        loss = eng.train_step(lambda m, b: crit(m(b["x"]), b["y"]))(
            {"x": x, "y": y})
    assert np.isfinite(float(loss))

    mode["interpret"] = False
    jax.clear_caches()          # the traced step holds the interpreter's
    text = eng._with_aot_args(
        eng._last_key, lambda fn, args: fn.trace(*args).lower(
            lowering_platforms=("tpu",)).as_text())
    found = chip_smoke.kernel_names(text)
    chip_smoke.check_flash_calls(found, eng, n_layers, pipe)
    if pipe:
        assert found["flash_attention_fwd"] < n_layers
        assert eng.backward_nodes[0] == 0
        with pytest.raises(chip_smoke.Failed):      # the tape's demands
            chip_smoke.check_flash_calls(found, eng, n_layers, False)
    else:
        assert {found[k] for k in FLASH_KERNELS} == {n_layers}
        assert eng.backward_nodes[0] == n_layers
