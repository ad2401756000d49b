"""The engines' own host spans (``observability/trace.py::span``) and
the scopes of the compiled train step.

Under test:
- ``span()`` with no profiler session open: no event anywhere, the
  region stack pushed and popped (also on an exception)
- inside ONE ``jax.profiler`` session (CPU): a tiny ``ServingEngine``,
  plain and chunked, writes every serving span of PERF.md's table with
  its fields, nested as the code nests, a retire's ``round`` naming an
  earlier launch; a tiny two-step train run writes the five train spans;
  a ``RecordEvent`` lands in the same file under its own name
- the train step's lowered text names the four scopes
- the decode rounds are kept once: no ``decode_round`` span a request,
  one ``engine`` lane in the Chrome export, ``first_round`` /
  ``last_round`` on a request's ``decode`` span, and a retire of a
  128-row engine creates no span object at all
- ``TraceAnnotation`` is opened in two modules of the package only
"""
import re
import subprocess
import sys
from pathlib import Path
from types import SimpleNamespace

import numpy as np
import pytest

import jax

import paddle_tpu as paddle
from paddle_tpu import observability as obs
from paddle_tpu import profiler
from paddle_tpu.observability import spans as _spans
from paddle_tpu.observability.trace import current_regions, span

ROOT = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(ROOT))
from benchmarks.harness import program_spans as PS  # noqa: E402
from benchmarks.harness.xplane import find_xplane  # noqa: E402


def _my_regions():
    import threading

    me = f"({threading.get_ident()})"
    return next((v for k, v in current_regions().items()
                 if k.endswith(me)), [])


# ---------------------------------------------------------------------------
# span() with no session open
# ---------------------------------------------------------------------------
class TestSpanAlone:
    def test_pushes_and_pops_the_region_stack(self):
        assert _my_regions() == []
        with span("serving.step", active=1, queued=0):
            with span("serving.tick"):
                assert _my_regions() == ["serving.step", "serving.tick"]
            assert _my_regions() == ["serving.step"]
        assert _my_regions() == []

    def test_pops_on_an_exception(self):
        with pytest.raises(KeyError):
            with span("train.step", step=3):
                with span("train.dispatch", fresh=False):
                    raise KeyError("boom")
        assert _my_regions() == []

    def test_shares_the_stack_with_annotate(self):
        with span("train.step", step=1):
            with obs.annotate("forward"):
                assert _my_regions() == ["train.step", "forward"]
        assert _my_regions() == []

    def test_opens_no_named_scope(self):
        # a scope opened on the host would rename every program traced
        # under it: the lowered text must not know the span
        def f(x):
            return x * 2

        with span("serving.prefill", rid=1, seq_bucket=8,
                  prompt_tokens=5):
            text = jax.jit(f).lower(np.ones(4, np.float32)).as_text(
                debug_info=True)
        assert "serving.prefill" not in text


# ---------------------------------------------------------------------------
# one profiler session over both engines
# ---------------------------------------------------------------------------
def _engine(model, **kw):
    from paddle_tpu.inference import (Config, ServingEngine,
                                      create_predictor)

    pred = create_predictor(
        Config().set_model(model).enable_paged_kv(page_size=8))
    return ServingEngine(pred, **kw)


@pytest.fixture(scope="module")
def llama():
    from paddle_tpu.models.llama import LlamaForCausalLM, llama_tiny

    paddle.seed(11)
    return LlamaForCausalLM(llama_tiny())


@pytest.fixture(scope="module")
def session(llama, tmp_path_factory):
    """What the file holds (``prog``; ``path``), the plain engine with
    its ``rids``, the train engine ``teng``."""
    from jax.profiler import ProfileOptions

    from paddle_tpu.distributed import fleet
    from paddle_tpu.distributed.engine import ParallelEngine
    from paddle_tpu.models import (GPTConfig, GPTForCausalLM,
                                   GPTPretrainingCriterion)

    obs.reset_registry()
    V = llama.config.vocab_size
    r = np.random.RandomState(0)
    plain = _engine(llama, max_batch=2, decode_chunk=2)
    chunked = _engine(llama, max_batch=2, prefill_chunk=16)

    paddle.seed(0)
    cfg = GPTConfig(vocab_size=128, hidden_size=32, num_layers=2,
                    num_heads=2, max_position_embeddings=32)
    model = GPTForCausalLM(cfg)
    crit = GPTPretrainingCriterion(cfg)
    opt = paddle.optimizer.AdamW(learning_rate=1e-4,
                                 parameters=model.parameters())
    strategy = fleet.DistributedStrategy()
    strategy.hybrid_configs = {"dp_degree": 8, "mp_degree": 1}
    hcg = fleet.init(is_collective=True, strategy=strategy)
    teng = ParallelEngine(model, opt, hcg.mesh)
    step = teng.train_step(lambda m, b: crit(m(b["x"]), b["y"]))
    ids = r.randint(0, 128, (8, 17))
    batch = {"x": paddle.to_tensor(ids[:, :-1]),
             "y": paddle.to_tensor(ids[:, 1:])}

    with span("before.the.session"):
        pass
    d = tmp_path_factory.mktemp("host_spans")
    opts = ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(str(d), profiler_options=opts)
    try:
        rids = [plain.submit(r.randint(1, V, (L,)), max_new_tokens=6)
                for L in (7, 12, 24)]
        plain.run()
        chunked.submit(r.randint(1, V, (40,)), max_new_tokens=3)
        chunked.run()
        for _ in range(2):
            float(step(batch))
        with profiler.RecordEvent("operator.block"):
            pass
    finally:
        jax.profiler.stop_trace()
    with span("after.the.session"):
        pass
    path = find_xplane(str(d))
    return SimpleNamespace(prog=PS.read(path), path=path, plain=plain,
                           rids=rids, teng=teng)


def _named(prog, name):
    return [s for s in prog.spans if s.name == name]


def _parent(prog, s):
    """The innermost other span that encloses ``s`` on its thread."""
    best = None
    for o in prog.spans:
        if o is s or o.line != s.line:
            continue
        if o.start <= s.start and s.start + s.dur <= o.start + o.dur:
            if best is None or o.start >= best.start:
                best = o
    return best


SERVING_FIELDS = {
    "serving.step": {"active", "queued"},
    "serving.admit": {"free_slots"},
    "serving.prefill": {"rid", "seq_bucket", "prompt_tokens", "attention"},
    "serving.prefill.dispatch": set(),
    "serving.prefill.fetch": set(),
    "serving.launch": {"round", "rows", "overlapped"},
    "serving.retire": {"round", "rows"},
    "serving.retire.fetch": set(),
    "serving.unified_round": {"rows", "chunks"},
    "serving.tick": set(),
}
TRAIN_FIELDS = {
    "train.step": {"step"}, "train.flush_scalars": set(),
    "train.assemble": set(), "train.dispatch": {"fresh"},
    "train.record": set(),
}


class TestSession:
    def test_no_event_outside_the_session(self, session):
        names = {s.name for s in session.prog.spans}
        assert "before.the.session" not in names
        assert "after.the.session" not in names

    @pytest.mark.parametrize("name", sorted(SERVING_FIELDS))
    def test_every_serving_span_with_its_fields(self, session, name):
        found = _named(session.prog, name)
        assert found, name
        for s in found:
            assert set(s.fields) == SERVING_FIELDS[name], s

    def test_serving_spans_nest_as_the_code_does(self, session):
        prog = session.prog
        want = {"serving.prefill.dispatch": "serving.prefill",
                "serving.prefill.fetch": "serving.prefill",
                "serving.prefill": "serving.admit",
                "serving.admit": "serving.step",
                "serving.launch": "serving.step",
                "serving.retire.fetch": "serving.retire",
                "serving.unified_round": "serving.step",
                "serving.tick": "serving.step"}
        for child, parent in want.items():
            for s in _named(prog, child):
                assert _parent(prog, s).name == parent, (child, s)
        # a retire belongs to a step, or to run()'s final drain
        for s in _named(prog, "serving.retire"):
            p = _parent(prog, s)
            assert p is None or p.name in ("serving.step",
                                           "serving.admit"), p

    def test_prefill_fields_name_the_request(self, session):
        prog, eng, rids = session.prog, session.plain, session.rids
        pre = [s for s in _named(prog, "serving.prefill")
               if s.fields["rid"] in rids]
        assert sorted(s.fields["rid"] for s in pre) == sorted(rids)
        assert sorted(s.fields["prompt_tokens"] for s in pre) == \
            [7, 12, 24]
        for s in pre:
            assert s.fields["seq_bucket"] >= s.fields["prompt_tokens"]
            assert eng.trace_context(s.fields["rid"])["trace_id"]

    def test_a_retire_names_an_earlier_launch(self, session):
        prog = session.prog
        launches = {}
        for s in _named(prog, "serving.launch"):
            launches.setdefault(s.fields["round"], []).append(s)
        retires = _named(prog, "serving.retire")
        assert retires
        for s in retires:
            mine = [x for x in launches.get(s.fields["round"], ())
                    if x.start + x.dur <= s.start
                    and x.fields["rows"] == s.fields["rows"]]
            assert mine, s
        # one round in flight: some launch went out over an unretired one
        assert any(s.fields["overlapped"] for x in launches.values()
                   for s in x)

    @pytest.mark.parametrize("name", sorted(TRAIN_FIELDS))
    def test_every_train_span_with_its_fields(self, session, name):
        found = _named(session.prog, name)
        assert len(found) == 2, name            # two steps
        for s in found:
            assert set(s.fields) == TRAIN_FIELDS[name], s
            if name != "train.step":
                assert _parent(session.prog, s).name == "train.step"

    def test_train_fields_say_which_step_and_whether_it_compiled(
            self, session):
        prog = session.prog
        assert [s.fields["step"] for s in _named(prog, "train.step")] \
            == [1, 2]
        assert [bool(s.fields["fresh"])
                for s in _named(prog, "train.dispatch")] == [True, False]

    def test_record_event_lands_in_the_same_file(self, session):
        # (read raw: the reader keeps the program's own names only)
        from jax.profiler import ProfileData

        names = set()
        for plane in ProfileData.from_file(session.path).planes:
            for line in plane.lines:
                names.update(e.name for e in line.events)
        assert "operator.block" in names
        assert "paddle_tpu/serving.step" in names

    def test_the_step_names_the_four_scopes(self, session):
        teng = session.teng
        text = teng.lowered_text(debug_info=True)
        for scope in PS.SCOPES:
            assert re.search(rf'loc\("(?:[^"]*/)?{scope}/', text), scope
        # the step as it runs carries no more than these at its top
        # level: the plain text (what a compile hashes) has none
        assert "grad_sync" not in teng.lowered_text()


# ---------------------------------------------------------------------------
# the decode rounds, kept once
# ---------------------------------------------------------------------------
class TestRoundsKeptOnce:
    def test_chrome_export_has_one_engine_lane(self, session):
        eng = session.plain
        evs = eng.export_request_traces()["traceEvents"]
        lanes = {e["tid"]: e["args"]["name"] for e in evs
                 if e["ph"] == "M"}
        engine = [t for t, n in lanes.items() if n == "engine"]
        assert len(engine) == 1
        rounds = [e for e in evs if e["name"] == "decode_round"]
        assert rounds and {e["tid"] for e in rounds} == set(engine)
        assert len(rounds) == len(eng.rounds)
        for t in eng.request_traces():
            assert "decode_round" not in [s["name"] for s in t["spans"]]

    def test_a_decode_span_names_its_rounds(self, session):
        eng = session.plain
        kept = {r[0] for r in eng.rounds}
        for t in eng.request_traces():
            dec = next(s for s in t["spans"] if s["name"] == "decode")
            first, last = (dec["meta"]["first_round"],
                           dec["meta"]["last_round"])
            assert first <= last and {first, last} <= kept

    def test_rounds_are_bounded(self, llama):
        from paddle_tpu.inference import serving

        eng = _engine(llama, max_batch=2)
        assert eng.rounds.maxlen == serving.ROUNDS_KEPT == 4096

    def test_a_retire_of_128_rows_creates_no_span(self, llama,
                                                  monkeypatch):
        eng = _engine(llama, max_batch=128)
        V = llama.config.vocab_size
        r = np.random.RandomState(5)
        made = []
        init = _spans.Span.__init__

        def counting(self, *a, **kw):
            made.append(a[0])
            init(self, *a, **kw)

        monkeypatch.setattr(_spans.Span, "__init__", counting)
        for _ in range(128):
            eng.submit(r.randint(1, V, (4,)), max_new_tokens=5)
        per_retire = []
        retire = eng._retire_round

        def watched(rnd):
            n, done = len(made), len(eng.finished)
            retire(rnd)
            per_retire.append((len(rnd.rows), len(made) - n,
                               len(eng.finished) - done))

        monkeypatch.setattr(eng, "_retire_round", watched)
        eng.run()
        assert len(eng.finished) == 128
        full = [(rows, n, fin) for rows, n, fin in per_retire
                if rows == 128]
        assert full
        for rows, n, finished in per_retire:
            # nothing a row a round: only the "e2e" span of a request
            # that this retire finished
            assert n == finished, per_retire
        assert any(n == 0 for _, n, _ in full)
        # a request's spans do not grow with its rounds
        assert sorted(set(made)) == ["decode", "e2e", "prefill", "queued"]
        assert len(made) == 4 * 128


def test_trace_annotation_is_opened_in_two_modules_only():
    out = subprocess.run(
        ["grep", "-rl", "TraceAnnotation", "--include=*.py",
         str(ROOT / "paddle_tpu")], capture_output=True, text=True)
    found = sorted(str(Path(p).relative_to(ROOT))
                   for p in out.stdout.split())
    assert found == ["paddle_tpu/observability/trace.py",
                     "paddle_tpu/profiler/__init__.py"]
