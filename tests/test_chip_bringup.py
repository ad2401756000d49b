"""What the chip bring-up (chip_smoke.py, ISSUE 21) rests on, checked on
the CPU: every Pallas kernel lowers for the TPU at the smoke's full-width
shapes; importing the package or the launcher creates no backend; the
compile-cache rule; the peaks table refuses an unknown TPU; a kernel the
gate admitted is called bare, so its failure is the caller's failure;
every entry of the smoke's ``SERVE_CASES`` is complete and passes its
phase's checks at toy sizes.
"""
import inspect
import json
import os
import re
import subprocess
import sys
import warnings

import jax
import jax.numpy as jnp
import pytest

import chip_smoke
import paddle_tpu as paddle
from paddle_tpu import _bootstrap
from paddle_tpu.observability import flops

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ---------------------------------------------------------------------------
# kernels: JAX's own lowering to a Mosaic custom call, no chip needed
# ---------------------------------------------------------------------------
_CASES = chip_smoke.kernel_cases(chip_smoke.Sizes(rehearsal=False))


@pytest.mark.parametrize("case", _CASES, ids=[c[0] for c in _CASES])
def test_kernel_lowers_for_tpu_at_full_width(case):
    name, expect, build, _tol = case
    kern, _dense, avals = build(
        lambda shape: jax.ShapeDtypeStruct(shape, jnp.bfloat16))
    text = jax.jit(kern).trace(*avals).lower(
        lowering_platforms=("tpu",)).as_text()
    assert "tpu_custom_call" in text
    found = chip_smoke.kernel_names(text)
    assert all(found.get(n, 0) >= 1 for n in expect), (name, found)


def test_no_kernel_resolves_interpret_for_itself():
    from paddle_tpu.ops.pallas import (decode_attention as da,
                                       flash_attention as fa,
                                       ragged_paged_attention as ra,
                                       rms_norm as rn)

    for fn in (fa.flash_attention_fwd, rn.rms_norm_fused,
               da.decode_attention, da.paged_decode_attention,
               ra.ragged_paged_attention):
        fn = getattr(fn, "__wrapped__", fn)
        assert inspect.signature(fn).parameters["interpret"].default \
            is False, fn


# ---------------------------------------------------------------------------
# the serving phases: one body, a table of cases
# ---------------------------------------------------------------------------
_SERVE = [(phase, case) for phase, cases in chip_smoke.SERVE_CASES.items()
          for case in cases]
_SERVE_IDS = [case.label for _, case in _SERVE]


@pytest.fixture(scope="module")
def pallas_sources():
    root = os.path.join(REPO, "paddle_tpu", "ops", "pallas")
    return "".join(open(os.path.join(root, f)).read()
                   for f in sorted(os.listdir(root)) if f.endswith(".py"))


@pytest.mark.parametrize("rehearsal", [True, False], ids=["toy", "full"])
@pytest.mark.parametrize("phase,case", _SERVE, ids=_SERVE_IDS)
def test_serve_case_is_complete(phase, case, rehearsal, pallas_sources):
    """An entry of ``SERVE_CASES`` builds its config at both sizes (no
    model, no engine), says everything ``serve_case`` asks of it, names
    decode kernels that exist, and belongs to a phase the parent runs."""
    sz = chip_smoke.Sizes(rehearsal)
    cfg, model_cls = case.build(sz)
    assert isinstance(model_cls, type) and cfg.num_layers >= 1
    assert phase in chip_smoke.ONE_CHIP_PHASES + chip_smoke.EXTRA_PHASES
    assert chip_smoke.PHASE_TIMEOUT[phase] > 0
    traffic = case.traffic(sz)
    assert sorted(traffic) == ["batch", "mix", "new", "warm"]
    longest = max(traffic["mix"]) + traffic["new"]
    assert traffic["mix"] and longest <= cfg.max_position_embeddings
    assert all(n + traffic["new"] <= cfg.max_position_embeddings
               for n in traffic["warm"])
    engines = case.engines(sz)
    assert engines and all(isinstance(kw, dict) for kw in engines)
    assert case.pools is None or all(
        len(hw) == 2 and min(hw) >= 1 for hw in case.pools(cfg))
    assert case.donated(cfg) >= 2 if callable(case.donated) \
        else case.donated >= 2
    kernels = case.decode_kernels(cfg)
    assert kernels
    for name, calls in kernels.items():
        assert calls >= 1 and f'"{name}"' in pallas_sources, name


@pytest.fixture(scope="module")
def jax_events():
    return chip_smoke.JaxEvents()


@pytest.fixture
def smoke_globals():
    """``serve_case`` sets the process's default dtype, as a child of the
    smoke may; a test worker gets it back."""
    dtype = paddle.get_default_dtype()
    yield
    paddle.set_default_dtype(dtype)
    paddle.set_flags({"use_pallas_kernels": True})


def _stem(line: str) -> str:
    return re.sub(r"\d[\d.]*(e[-+]?\d+)?", "#", line)[:_STEM]


_STEM = 56
# the `  ok   ` lines a rehearsal of each case prints, in order, numbers
# as "#" and cut at _STEM characters
_OK_LINES = {
    "llama": [
        "a layer pools # arrays of # pages: #x#x# | #x#x#",
        "warm-up mix drained (#)",
        "every request returned # tokens of the vocabulary",
        "no compile after warm-up (eng.stats.compiles #, # XLA co",
        "reference forward: finite logits of shape (#, #)",
        "first token # scores within # of the reference forward's",
        "every served token of the first request scores within # ",
        "program ('decode',) holds Mosaic calls {} (asked: {'page",
        "compiled program ('decode',): # copies of a whole pool #",
        "compiled program ('decode',) donates the # arrays it was",
        "compiled program ('prefill', #): # copies of a whole poo",
        "decode rounds launched with the one before unretired: # ",
        "prefill programs attend as {#: 'dense', #: 'dense', #: '",
        "program ('prefill', #) holds no other attention kernel",
        "program ('prefill', #) holds Mosaic calls {}",
        "program ('prefill', #) holds no other attention kernel",
        "program ('prefill', #) holds Mosaic calls {}",
        "program ('prefill', #) holds no other attention kernel",
        "program ('prefill', #) holds Mosaic calls {}",
        "the programs that ran: [\"('decode',)\", \"('prefill', #)\",",
        "first tokens of the prompts in the buckets [#, #] score ",
        "a layer pools # arrays of # pages: #x#x# | #x#x#",
        "warm-up mix drained (#)",
        "every request returned # tokens of the vocabulary",
        "no compile after warm-up (eng.stats.compiles #, # XLA co",
        "reference forward: finite logits of shape (#, #)",
        "first token # scores within # of the reference forward's",
        "every served token of the first request scores within # ",
        "program ('decode',) holds Mosaic calls {} (asked: {'page",
        "compiled program ('unified', #): # copies of a whole poo",
        "compiled program ('decode',): # copies of a whole pool #",
        "compiled program ('decode',) donates the # arrays it was",
        "decode rounds launched with the one before unretired: # ",
        "program ('unified', #) holds Mosaic calls {}",
        "the programs that ran: [\"('decode',)\", \"('unified', #)\"]",
        "first tokens of the prompts in the buckets [#, #] score ",
    ],
    "latent": [
        "a layer pools # arrays of # pages: #x#x# | #x#x#",
        "warm-up mix drained (#)",
        "every request returned # tokens of the vocabulary",
        "no compile after warm-up (eng.stats.compiles #, # XLA co",
        "reference forward: finite logits of shape (#, #)",
        "first token # scores within # of the reference forward's",
        "every served token of the first request scores within # ",
        "expert layers dropped # routed pairs of #",
        "expert products' forms {'decode': 'batched', 'prefill': ",
        "grouped products in the lowered programs: {('decode',): ",
        "program ('decode',) holds Mosaic calls {} (asked: {'mla_",
        "compiled program ('decode',): # copies of a whole pool #",
        "compiled program ('decode',) donates the # arrays it was",
        "compiled program ('prefill', #): # copies of a whole poo",
        "decode rounds launched with the one before unretired: # ",
    ],
    "shortcut": [
        "a layer pools # arrays of # pages: #x#x# | #x#x#",
        "warm-up mix drained (#)",
        "every request returned # tokens of the vocabulary",
        "no compile after warm-up (eng.stats.compiles #, # XLA co",
        "reference forward: finite logits of shape (#, #)",
        "first token # scores within # of the reference forward's",
        "every served token of the first request scores within # ",
        "expert layers dropped # routed pairs of #",
        "expert products' forms {'decode': 'batched', 'prefill': ",
        "grouped products in the lowered programs: {('decode',): ",
        "program ('decode',) holds Mosaic calls {} (asked: {'mla_",
        "compiled program ('decode',): # copies of a whole pool #",
        "compiled program ('decode',) donates the # arrays it was",
        "compiled program ('prefill', #): # copies of a whole poo",
        "decode rounds launched with the one before unretired: # ",
        "tokens x # = held + absent + identity pairs in every exp",
        "identity experts took # of the decode steps' picks (# of",
    ],
    "mimo": [
        "full decode kernel, keys # against values #, sink False,",
        "window decode kernel, keys # against values #, sink True",
        "every request returned # tokens of the vocabulary",
        "reference forward: finite logits of shape (#, #)",
        "first token # scores within # of the reference forward's",
        "every served token of the first request scores within # ",
        "expert layers dropped # routed pairs of #",
        "expert products' forms {'decode': 'batched', 'prefill': ",
        "grouped products in the lowered programs: {('decode',): ",
        "program ('decode',) holds Mosaic calls {} (asked: {'page",
        "compiled program ('decode',): # copies of a whole pool #",
        "compiled program ('decode',) donates the # arrays it was",
        "compiled program ('prefill', #): # copies of a whole poo",
        "decode rounds launched with the one before unretired: # ",
        "two classes of pages: # full, # window (a ring of # a ro",
        "the longest context # wrapped its ring at #",
        "both classes back to free: {'full': {'used': #, 'free': ",
        "prefill programs attend as {#: 'blockwise'}",
        "program ('prefill', #) holds Mosaic calls {}",
    ],
    "afmoe": [
        "full decode kernel, keys # against values #, sink False,",
        "window decode kernel, keys # against values #, sink Fals",
        "every request returned # tokens of the vocabulary",
        "reference forward: finite logits of shape (#, #)",
        "first token # scores within # of the reference forward's",
        "every served token of the first request scores within # ",
        "expert layers dropped # routed pairs of #",
        "expert products' forms {'decode': 'batched', 'prefill': ",
        "grouped products in the lowered programs: {('decode',): ",
        "program ('decode',) holds Mosaic calls {} (asked: {'page",
        "compiled program ('decode',): # copies of a whole pool #",
        "compiled program ('decode',) donates the # arrays it was",
        "compiled program ('prefill', #): # copies of a whole poo",
        "decode rounds launched with the one before unretired: # ",
        "two classes of pages: # full, # window (a ring of # a ro",
        "the longest context # wrapped its ring at #",
        "both classes back to free: {'full': {'used': #, 'free': ",
        "prefill programs attend as {#: 'blockwise'}",
        "program ('prefill', #) holds Mosaic calls {}",
    ],
    "sparse": [
        "decode kernel under a kept mask (# query heads a KV head",
        "a layer pools # arrays of # pages: #x#x# | #x#x# | #x#x#",
        "every request returned # tokens of the vocabulary",
        "reference forward: finite logits of shape (#, #)",
        "first token # scores within # of the reference forward's",
        "every served token of the first request scores within # ",
        "expert layers dropped # routed pairs of #",
        "expert products' forms {'decode': 'batched', 'prefill': ",
        "grouped products in the lowered programs: {('decode',): ",
        "program ('decode',) holds Mosaic calls {} (asked: {'page",
        "compiled program ('decode',): # copies of a whole pool #",
        "compiled program ('decode',) donates the # arrays it was",
        "compiled program ('prefill', #): # copies of a whole poo",
        "decode rounds launched with the one before unretired: # ",
        "the longest context # is # times the # keys a query keep",
        "kept_keys_wrong == #: every row of # layers of a full fo",
        "the decode steps' own count on the device: kept_keys_wro",
        "every page back to free: {'free': #, 'idle': #, 'registe",
        "sorted rows a prefill bucket (bound, routed pairs) {}; p",
    ],
    "ssm": [
        "warm-up mix drained (#)",
        "every request returned # tokens of the vocabulary",
        "no compile after warm-up (eng.stats.compiles #, # XLA co",
        "reference forward: finite logits of shape (#, #)",
        "first token # scores within # of the reference forward's",
        "every served token of the first request scores within # ",
        "expert layers dropped # routed pairs of #",
        "expert products' forms {'decode': 'batched', 'prefill': ",
        "grouped products in the lowered programs: {('decode',): ",
        "program ('decode',) holds Mosaic calls {} (asked: {'page",
        "compiled program ('decode',): # copies of a whole pool #",
        "compiled program ('decode',) donates the # arrays it was",
        "compiled program ('prefill', #): # copies of a whole poo",
        "decode rounds launched with the one before unretired: # ",
        "a row's slot over # state layers is # bytes, H kept in [",
        "every slot and page back to free: {'full': {'used': #, '",
        "a chunked engine is refused with its reason: prefill_chu",
    ],
}


@pytest.mark.parametrize(
    "case", [c for _, c in _SERVE if c.label in _OK_LINES],
    ids=list(_OK_LINES))
def test_serve_case_rehearsal_in_process(case, capsys, jax_events,
                                         smoke_globals):
    """The body and the case's own checks run at toy sizes on the CPU
    and print the lines they printed when this list was written
    (sparse_mla takes 35 s here and is left to ``--rehearsal``)."""
    chip_smoke.serve_case(chip_smoke.Sizes(rehearsal=True), case,
                          jax_events, {"kind": "cpu"})
    out = capsys.readouterr().out
    assert "  FAIL " not in out
    ok = [_stem(ln[7:]) for ln in out.splitlines()
          if ln.startswith("  ok   ")]
    assert ok == _OK_LINES[case.label]


# ---------------------------------------------------------------------------
# the parent of a launch must leave the chip to its child
# ---------------------------------------------------------------------------
def test_imports_create_no_backend_and_place_the_cache():
    """In a fresh process not pinned to the CPU (no backend is created,
    so the missing TPU is never asked for)."""
    env = {k: v for k, v in os.environ.items()
           if k not in ("JAX_PLATFORMS", "JAX_COMPILATION_CACHE_DIR")}
    out = subprocess.run(
        [sys.executable, "-c",
         "import json, jax, paddle_tpu\n"
         "import paddle_tpu.distributed.launch.main\n"
         "from jax._src import xla_bridge\n"
         "print(json.dumps({'backends': len(xla_bridge._backends),"
         " 'dir': jax.config.jax_compilation_cache_dir}))"],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=120)
    assert out.returncode == 0, out.stderr[-2000:]
    got = json.loads(out.stdout.strip().splitlines()[-1])
    assert got["backends"] == 0
    assert got["dir"] == os.path.join(REPO, ".paddle_tpu_cache", "xla")


# ---------------------------------------------------------------------------
# compile cache: placed from outside, else one fixed path in the checkout
# ---------------------------------------------------------------------------
def test_compile_cache_rule():
    rule = _bootstrap.compile_cache_dir
    # the variable set: the code sets nothing (JAX reads it itself)
    assert rule({"JAX_COMPILATION_CACHE_DIR": "/somewhere"}, "") is None
    assert rule({"JAX_COMPILATION_CACHE_DIR": "/somewhere"}, "cpu") is None
    # unset: the fixed path inside the checkout, the same every call
    fixed = rule({}, "")
    assert fixed == rule({}, "tpu") == rule({}, "")
    assert fixed == os.path.join(REPO, ".paddle_tpu_cache", "xla")
    # a process pinned to the CPU keeps none
    assert rule({}, "cpu") is None
    # the autotune cache moved beside it, out of the user's home
    from paddle_tpu.ops.pallas import autotune

    assert autotune._default_path() == os.path.join(
        REPO, ".paddle_tpu_cache", "autotune.json")


# ---------------------------------------------------------------------------
# one peaks table; an unknown TPU is an error
# ---------------------------------------------------------------------------
class _FakeDevice:
    def __init__(self, platform, device_kind):
        self.platform, self.device_kind = platform, device_kind


def test_peaks_table_known_unknown_and_cpu():
    v5e = _FakeDevice("tpu", "TPU v5 lite")
    assert flops.peak_flops_per_chip(v5e) == (197e12, 0.819e12)
    assert flops.ici_bytes_per_sec(v5e) == 200e9
    unknown = _FakeDevice("tpu", "TPU v99 mega")
    with pytest.raises(KeyError, match="TPU v99 mega"):
        flops.peak_flops_per_chip(unknown)
    with pytest.raises(KeyError):
        flops.ici_bytes_per_sec(unknown)
    assert flops.peak_flops_per_chip(jax.devices()[0]) == (0.0, 0.0)


# ---------------------------------------------------------------------------
# gate, then the kernel bare: a failure behind an admitting gate propagates
# ---------------------------------------------------------------------------
class _MosaicSaysNo(RuntimeError):
    pass


def _boom(*a, **k):
    raise _MosaicSaysNo("refused by the compiler")


def _as_tpu(monkeypatch):
    import paddle_tpu.ops.attention as attn
    import paddle_tpu.ops.pallas as pallas

    monkeypatch.setattr(pallas, "is_tpu_platform", lambda: True)
    monkeypatch.setattr(attn, "is_tpu_platform", lambda: True)


def _flash_site(monkeypatch):
    import paddle_tpu.ops.attention as attn

    monkeypatch.setattr(attn, "flash_attention_with_lse", _boom)
    q = paddle.to_tensor(jnp.zeros((1, 128, 2, 128), jnp.float32))
    return lambda: attn.flash_attention(q, q, q, causal=True)


def _rms_norm_site(monkeypatch):
    import paddle_tpu.ops.pallas.rms_norm as rn
    from paddle_tpu.ops import nn_ops

    monkeypatch.setattr(rn, "rms_norm_fused", _boom)
    x = paddle.to_tensor(jnp.zeros((8, 128), jnp.float32))
    w = paddle.to_tensor(jnp.ones((128,), jnp.float32))
    return lambda: nn_ops.rms_norm(x, w)


def _llama_paged_site(monkeypatch):
    import paddle_tpu.ops.pallas.decode_attention as da
    from paddle_tpu.models import llama

    monkeypatch.setattr(da, "paged_decode_attention", _boom)
    q = jnp.zeros((2, 1, 4, 128), jnp.float32)
    pool = jnp.zeros((5, 4, 16, 128), jnp.float32)
    tbl = jnp.zeros((2, 2), jnp.int32)
    return lambda: llama._paged_attention(q, pool, pool, tbl,
                                          jnp.zeros((2,), jnp.int32), 1)


@pytest.mark.parametrize("site", [_flash_site, _rms_norm_site,
                                  _llama_paged_site])
def test_kernel_failure_behind_admitting_gate_propagates(site, monkeypatch):
    _as_tpu(monkeypatch)
    call = site(monkeypatch)
    with warnings.catch_warnings():
        warnings.simplefilter("error")        # a warn-and-go-on would raise
        with pytest.raises(_MosaicSaysNo):
            call()


def test_gate_rejection_takes_the_dense_path(monkeypatch):
    """The other half of the rule: a shape the gate rejects never
    reaches the kernel, on a TPU or off it."""
    import paddle_tpu.ops.pallas.rms_norm as rn
    from paddle_tpu.ops import nn_ops

    _as_tpu(monkeypatch)
    monkeypatch.setattr(rn, "rms_norm_fused", _boom)
    x = paddle.to_tensor(jnp.ones((3, 96), jnp.float32))   # sub-lane H
    w = paddle.to_tensor(jnp.ones((96,), jnp.float32))
    out = nn_ops.rms_norm(x, w)
    assert out.shape == [3, 96] or tuple(out.shape) == (3, 96)


# ---------------------------------------------------------------------------
# what kept the 1.3B step off the chip: state_dtype died in Adam's **kwargs,
# the moments stayed f32 and the step's arguments alone were 12.2 GiB
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("cls", ["Adam", "AdamW"])
def test_state_dtype_reaches_the_moments(cls):
    m = paddle.nn.Linear(4, 4)
    opt = getattr(paddle.optimizer, cls)(parameters=m.parameters(),
                                         state_dtype="bfloat16")
    m(paddle.to_tensor(jnp.ones((2, 4), jnp.float32))).sum().backward()
    opt.step()
    moments = [v for st in opt._states.values() for v in st.values()
               if jnp.issubdtype(v.dtype, jnp.floating)]
    assert moments and all(v.dtype == jnp.bfloat16 for v in moments)
