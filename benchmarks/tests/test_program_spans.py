"""The program's own spans and scopes (``harness/program_spans.py``) on
hand-built traces with hand-worked values: the idle closure, the median,
the scope shares; then the reader on a file the CPU's profiler wrote.
CPU only, by hand: ``JAX_PLATFORMS=cpu python -m pytest
benchmarks/tests/test_program_spans.py -q``.
"""
import pytest

from benchmarks.harness import program_spans as PS
from benchmarks.harness import reduce as R
from benchmarks.harness.reducers import (idle_under_span, scope_time_share,
                                         span_seconds)

PREFILL = ["serving.prefill", "serving.prefill.dispatch",
           "serving.prefill.fetch"]
ROUND = ["serving.launch", "serving.retire", "serving.retire.fetch",
         "serving.unified_round"]
BOOK = ["serving.admit", "serving.tick", "serving.step"]


def S(name, a, b, **fields):
    return PS.ProgramSpan(name, a, b - a, fields)


def serving() -> R.Trace:
    """Window 0..10 s, one device busy [0,0.5] [1.5,2.2] [2.4,3.0]
    [3.4,5.5] [6,9.5]: idle 0.5-1.5, 2.2-2.4, 3.0-3.4, 5.5-6, 9.5-10 =
    2.6 s. Two engine steps, [0,4] and [5,9]; the first admits one
    prefill."""
    ops = [R.Op(f"fusion.{i}", a, b - a, 0, "jit_step", "")
           for i, (a, b) in enumerate([(0, .5), (1.5, 2.2), (2.4, 3.0),
                                       (3.4, 5.5), (6, 9.5)])]
    spans = [
        S("serving.step", 0, 4, active=0, queued=1),
        S("serving.admit", 0, 2, free_slots=2),
        S("serving.prefill", .4, 1.9, rid=7, seq_bucket=256,
          prompt_tokens=200),
        S("serving.prefill.dispatch", .4, 1.0),
        S("serving.prefill.fetch", 1.0, 1.8),
        S("serving.launch", 2.0, 2.3, round=0, rows=1, overlapped=False),
        S("serving.tick", 2.3, 2.5),
        S("serving.step", 5, 9, active=1, queued=0),
        S("serving.admit", 5, 5.2, free_slots=1),
        S("serving.launch", 5.2, 5.6, round=1, rows=1, overlapped=True),
        S("serving.retire", 5.6, 8.5, round=0, rows=1),
        S("serving.retire.fetch", 5.7, 8.0),
        S("serving.tick", 8.5, 9),
    ]
    return R.Trace(ops, spans, (0.0, 10.0))


def test_idle_is_charged_to_the_innermost_span_by_hand():
    idle = PS.idle_by_owner(serving())
    want = {
        # 0.5-1.5: dispatch 0.5-1.0, fetch 1.0-1.5
        "serving.prefill.dispatch": 0.5, "serving.prefill.fetch": 0.5,
        # 2.2-2.4: launch to 2.3, tick from 2.3
        "serving.launch": 0.1 + 0.1,            # and 5.5-5.6
        "serving.tick": 0.1,
        # 3.0-3.4: the step itself, nothing inside it open
        "serving.step": 0.4,
        # 5.6-6.0: retire 5.6-5.7, its fetch 5.7-6.0
        "serving.retire": 0.1, "serving.retire.fetch": 0.3,
        # 9.5-10: outside every span
        PS.NO_SPAN: 0.5,
    }
    assert idle == pytest.approx(want)


def test_the_sweep_is_reduce_idle_gaps():
    tr = serving()
    assert PS.idle_by_owner(tr) == pytest.approx(
        dict(R.idle_gaps(tr, n=10 ** 6)))
    # spans that start together, end together, touch, or lie on a
    # second host thread: still the latest-starting one that covers
    tr.spans += [S("other.thread", 2.9, 3.2), S("serving.step", 9, 9.7),
                 S("serving.admit", 9, 9.7), S("zero", 3.1, 3.1)]
    assert PS.idle_by_owner(tr) == pytest.approx(
        dict(R.idle_gaps(tr, n=10 ** 6)))


def test_the_idle_shares_close_on_device_idle_share():
    tr = serving()
    parts = [PS.idle_share_under(tr, g) for g in (PREFILL, ROUND, BOOK)]
    assert parts == pytest.approx([10.0, 6.0, 5.0])
    outside = PS.idle_share_under(tr, [PS.NO_SPAN])
    assert outside == pytest.approx(5.0)
    assert sum(parts) + outside == pytest.approx(R.idle_share(tr))
    # two devices: the worst one (least busy) is the one charged, as in
    # reduce.idle_share
    tr.ops.append(R.Op("fusion.9", 0.0, 9.9, 1, "jit_step", ""))
    assert sum(PS.idle_share_under(tr, g) for g in (PREFILL, ROUND, BOOK,
                                                    [PS.NO_SPAN])) \
        == pytest.approx(R.idle_share(tr)) == pytest.approx(26.0)


def test_median_and_share_of_a_span_by_hand():
    tr = serving()
    tr.spans += [S("serving.prefill.dispatch", 6, 6.2),
                 S("serving.prefill.dispatch", 7, 7.1),
                 # past the window: not counted
                 S("serving.prefill.dispatch", 11, 14),
                 # across its edge: counted, clipped for the share
                 S("serving.retire.fetch", 9.5, 10.5)]
    assert PS.median_ms(tr, "serving.prefill.dispatch") == \
        pytest.approx(200.0)                    # of 600, 200, 100
    assert PS.seconds_share(tr, "serving.retire.fetch") == \
        pytest.approx(100 * (2.3 + 0.5) / 10)
    assert PS.median_ms(tr, "serving.unified_round") is None
    assert PS.seconds_share(tr, "serving.unified_round") is None


def test_scope_of_an_op_name():
    f = PS.scope_of
    # as the chip's trace spells them (tf_op = <op_name>:<type>)
    assert f("jit(flat_step)/forward/gpt/embed/jit(_take)/gather:") == \
        "forward"
    # the first component that IS a scope: the tape replays the
    # forward's names under the backward's
    assert f("jit(flat_step)/backward/transpose(jvp())/dot_general:") == \
        "backward"
    assert f("jit(s)/backward/transpose(jvp(forward))/mul:") == "backward"
    assert f("jit(s)/transpose(jvp(forward/layer0))/mul:") == ""
    assert f("jit(s)/optimizer/convert_element_type:") == "optimizer"
    assert f("jit(s)/shard_map/grad_sync/psum:") == "grad_sync"
    assert f("jit(s)/forwarder/x:") == f("copy.1") == f(None) == ""


def _pb(*fields) -> bytes:
    """A protobuf message from (field number, int | bytes) pairs."""
    def varint(n):
        out = b""
        while n > 0x7F:
            out += bytes([n & 0x7F | 0x80])
            n >>= 7
        return out + bytes([n])

    out = b""
    for f, v in fields:
        if isinstance(v, int):
            out += varint(f << 3) + varint(v)
        else:
            out += varint(f << 3 | 2) + varint(len(v)) + v
    return out


def test_op_names_reads_tf_op_from_the_wire_format():
    """An XSpace by hand, as ``xplane.proto`` numbers its fields: a host
    plane that is skipped, and a device plane whose ops name their
    ``tf_op`` as a string, as a reference to a stat's name, or not."""
    def stat_meta(i, name):
        return (5, _pb((1, i), (2, _pb((1, i), (2, name)))))

    def event_meta(i, name, *stats):
        return (4, _pb((1, i), (2, _pb((1, i), (2, name),
                                       *[(5, s) for s in stats]))))

    fused = b"%fusion.7 = bf16[8,128]{1,0} fusion(%p), kind=kLoop"
    device = _pb(
        (1, 3), (2, b"/device:TPU:0"),
        # a line with an event: skipped whole (3 = lines)
        (3, _pb((1, 1), (2, b"XLA Ops"), (4, _pb((1, 1), (2, 50))))),
        stat_meta(1, b"flops"), stat_meta(2, b"tf_op"),
        stat_meta(9, b"jit(s)/optimizer/adamw/mul:"),
        event_meta(1, fused, _pb((1, 1), (3, 4096)),
                   _pb((1, 2), (5, b"jit(s)/forward/layer0/dot:"))),
        event_meta(2, b"%fusion.8 = f32[] fusion(%q)",
                   _pb((1, 2), (7, 9))),
        event_meta(3, b"%copy.1 = bf16[8] copy(%x)", _pb((1, 1), (3, 8))))
    host = _pb((1, 4), (2, b"/host:CPU"),
               stat_meta(2, b"tf_op"),
               event_meta(1, b"not.a.device.op",
                          _pb((1, 2), (5, b"jit(s)/backward/x:"))))
    names = PS.op_names(_pb((1, host), (1, device)))
    assert names == {
        fused.decode(): "jit(s)/forward/layer0/dot:",
        "%fusion.8 = f32[] fusion(%q)": "jit(s)/optimizer/adamw/mul:"}
    assert [PS.scope_of(v) for v in names.values()] == \
        ["forward", "optimizer"]
    with pytest.raises(ValueError):
        PS.op_names(b"\x0b")          # a group: no such file is ours


def train() -> R.Trace:
    """Busy 8 s of 10 on one device: forward 2, backward 3 + 1 of
    grad_sync, optimizer 1.5, 0.5 under no scope."""
    rows = [("forward", 0, 2), ("backward", 2, 5), ("grad_sync", 5, 6),
            ("optimizer", 7, 8.5), ("", 8.5, 9)]
    ops = [R.Op(f"fusion.{i}", a, b - a, 0, scope, "")
           for i, (scope, a, b) in enumerate(rows)]
    spans = [S("train.step", 0, 1.2, step=4),
             S("train.flush_scalars", 0.1, 0.3),
             S("train.assemble", 0.3, 0.5),
             S("train.dispatch", 0.5, 0.9, fresh=False),
             S("train.record", 0.9, 1.2),
             S("train.step", 6.2, 7.5, step=5),
             S("train.flush_scalars", 6.25, 6.6),
             S("train.assemble", 6.6, 6.8),
             S("train.dispatch", 6.8, 7.2, fresh=False),
             S("train.record", 7.2, 7.5)]
    return R.Trace(ops, spans, (0.0, 10.0))


def test_scope_shares_by_hand():
    tr = train()
    assert PS.scope_share(tr, ["forward"]) == pytest.approx(100 * 2 / 8)
    assert PS.scope_share(tr, ["backward", "grad_sync"]) == \
        pytest.approx(100 * 4 / 8)
    assert PS.scope_share(tr, ["optimizer"]) == \
        pytest.approx(100 * 1.5 / 8)


def test_train_idle_shares_by_hand():
    tr = train()
    # idle 6-7 (step from 6.2: 0.05 self, flush 0.35, assemble 0.2,
    # dispatch 0.2) and 9-10 (outside)
    flush = PS.idle_share_under(tr, ["train.flush_scalars"])
    disp = PS.idle_share_under(tr, ["train.assemble", "train.dispatch"])
    other = PS.idle_share_under(tr, ["train.step", "train.record"])
    assert [flush, disp, other] == pytest.approx([3.5, 4.0, 0.5])
    assert flush + disp + other + PS.idle_share_under(tr, [PS.NO_SPAN]) \
        == pytest.approx(R.idle_share(tr)) == pytest.approx(20.0)


def test_self_seconds_is_duration_less_children():
    own = PS.self_seconds(serving().spans)
    assert own["serving.step"] == pytest.approx(
        [4 - (2 + .3 + .2), 4 - (.2 + .4 + 2.9 + .5)])
    assert own["serving.admit"] == pytest.approx([2 - 1.5, .2])
    assert own["serving.prefill"] == pytest.approx([1.5 - .6 - .8])
    assert own["serving.retire"] == pytest.approx([2.9 - 2.3])
    assert own["serving.retire.fetch"] == pytest.approx([2.3])


# -- the reducers, and the file -----------------------------------------------
def _ctx(trace, prog):
    return {"trace": trace, "_program_spans": prog}


def test_reducers_read_nothing_from_a_tree_without_spans(monkeypatch):
    tr = serving()
    bare = R.Trace(tr.ops, [], tr.window)
    monkeypatch.setattr(PS, "last_trace", lambda: None)
    for ctx in ({"trace": bare},                       # no file at all
                _ctx(bare, PS.Program([], tr.window)),  # the parent's
                # another run's file: its window is not this trace's
                _ctx(bare, PS.Program(tr.spans, (0.0, 9.0)))):
        assert idle_under_span.read(ctx, PREFILL) is None
        assert span_seconds.read(ctx, "serving.retire.fetch",
                                 "share") is None
        assert scope_time_share.read(ctx, ["forward"]) is None


def test_reducers_on_this_runs_program():
    tr = serving()
    ctx = _ctx(R.Trace(tr.ops, [R.Span("bench/eng.step", 0, 4)],
                       tr.window), PS.Program(tr.spans, tr.window))
    assert idle_under_span.read(ctx, PREFILL) == pytest.approx(10.0)
    assert span_seconds.read(ctx, "serving.prefill.dispatch",
                             "median_ms") == pytest.approx(600.0)
    assert span_seconds.read(ctx, "serving.retire.fetch", "share") == \
        pytest.approx(23.0)
    t = train()
    ctx = _ctx(R.Trace(t.ops, [], t.window),
               PS.Program(t.spans, t.window, scoped_ops=t.ops))
    assert scope_time_share.read(ctx, ["backward", "grad_sync"]) == \
        pytest.approx(50.0)
    # a trace whose ops name no scope (the persistent cache gave an
    # executable compiled without them): nothing, not zero
    ctx["_program_spans"].scoped_ops = [
        R.Op(o.name, o.start, o.dur, 0, "", "") for o in t.ops]
    assert scope_time_share.read(ctx, ["forward"]) is None


def test_read_finds_spans_fields_and_window_in_a_profile(tmp_path):
    import jax
    import jax.numpy as jnp

    from benchmarks.harness.xplane import WINDOW_SPAN, find_xplane
    from paddle_tpu.observability.trace import span

    f = jax.jit(lambda x: (x @ x).sum())
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    try:
        with jax.profiler.TraceAnnotation(WINDOW_SPAN):
            with span("serving.step", active=3, queued=1):
                with span("serving.prefill", rid=9, seq_bucket=256,
                          prompt_tokens=130):
                    f(x).block_until_ready()
    finally:
        jax.profiler.stop_trace()
    prog = PS.read(find_xplane(str(tmp_path)), scopes=True)
    assert [s.name for s in prog.spans] == ["serving.step",
                                            "serving.prefill"]
    step, prefill = prog.spans
    assert step.fields == {"active": 3, "queued": 1}
    assert prefill.fields == {"rid": 9, "seq_bucket": 256,
                              "prompt_tokens": 130}
    lo, hi = prog.window
    assert lo <= step.start <= prefill.start and \
        prefill.start + prefill.dur <= step.start + step.dur <= hi
    assert step.line == prefill.line and prog.scoped_ops == []
