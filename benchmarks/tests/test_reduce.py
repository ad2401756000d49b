"""The reduction from a trace to numbers, against hand-worked values on a
small made-up trace, and against plain re-computation on a slice of a
real one (``data/train_1p3b_v5e_50ms.json``: the first 50 ms of a traced
``train-1.3b-1chip`` step on a TPU v5 lite, my chip run, PR 23). CPU only.
"""
import os

import pytest

from benchmarks.harness import reduce as R
from benchmarks.harness.xplane import leaf_ops, parse_op

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


def made_up() -> R.Trace:
    """Window 0..10 s. Device 0: ops at [1,3] [2.5,4] (overlap) [6,7];
    an all-reduce at [7,9] with a fusion inside it at [7.5,8]. Device 1:
    one op [0,5]. Host spans: step [0,4.5], collect [4.5,6], nothing
    after 9.5."""
    ops = [R.Op("fusion.1", 1.0, 2.0, 0, "jit_step(1)", "bf16[8,128]"),
           R.Op("flash_attention_fwd.2", 2.5, 1.5, 0, "jit_step(1)",
                "bf16[2,8] mosaic"),
           R.Op("copy.3", 6.0, 1.0, 0, "jit_prefill_fn(2)", ""),
           R.Op("all-reduce.4", 7.0, 2.0, 0, "jit_step(1)", ""),
           R.Op("fusion.5", 7.5, 0.5, 0, "jit_step(1)", "bf16[8,128]"),
           R.Op("fusion.6", 0.0, 5.0, 1, "jit_step(1)", ""),
           # an async span never counts as busy time
           R.Op("copy-start.7", 0.0, 10.0, 0, "", "", True)]
    spans = [R.Span("bench/step", 0.0, 4.5), R.Span("bench/collect", 4.5,
                                                    1.5),
             R.Span("bench/inner", 4.0, 0.25)]
    return R.Trace(ops, spans, (0.0, 10.0))


def test_interval_arithmetic():
    assert R.union([(1, 3), (2.5, 4), (6, 7)]) == [(1, 4), (6, 7)]
    assert R.total(R.union([(1, 3), (2.5, 4), (6, 7)])) == 4
    assert R.clip([(-1, 2), (9, 12), (20, 21)], 0, 10) == [(0, 2), (9, 10)]
    assert R.subtract([(0, 10)], [(1, 4), (6, 9)]) == [(0, 1), (4, 6),
                                                        (9, 10)]
    assert R.subtract([(7, 9)], [(7.5, 8)]) == [(7, 7.5), (8, 9)]


def test_busy_and_idle_by_hand():
    tr = made_up()
    # device 0: [1,4] + [6,9] = 6 s busy; device 1: 5 s
    assert R.busy_by_device(tr) == {0: 6.0, 1: 5.0}
    assert R.busy_seconds(tr) == 5.5                  # mean over devices
    assert R.idle_share(tr) == pytest.approx(50.0)    # worst device: 1


def test_kernel_time_and_shares_by_hand():
    tr = made_up()
    flash = R.select(tr, ["flash_attention_fwd"])
    assert [o.name for o in flash] == ["flash_attention_fwd.2"]
    assert R.op_seconds(flash, tr.window) == 1.5
    # durations add up to 1.5 of 11 busy seconds (6 + 5)
    assert R.time_share(tr, ["mosaic"]) == pytest.approx(100 * 1.5 / 11)
    assert R.program_share(tr, "prefill") == pytest.approx(100 * 1 / 11)
    # clipped to the window
    tr.window = (3.0, 10.0)
    assert R.op_seconds(R.select(tr, ["flash_attention_fwd"]),
                        tr.window) == 1.0


def test_gap_attribution_by_hand():
    tr = made_up()
    # worst device is 1: idle [5,10]. collect covers [5,6], no span after
    assert R.idle_gaps(tr) == [["(no span)", 4.0], ["bench/collect", 1.0]]
    # device 0 alone: gaps [0,1] step, [4,6]: inner [4,4.25], step
    # [4.25,4.5], collect [4.5,6]; [9,10] none
    tr.ops = [o for o in tr.ops if o.device == 0]
    got = dict(map(tuple, R.idle_gaps(tr)))
    assert got == pytest.approx({"bench/step": 1.25, "bench/inner": 0.25,
                                 "bench/collect": 1.5, "(no span)": 1.0})


def test_exposed_collective_by_hand():
    tr = made_up()
    # all-reduce [7,9] minus compute [7.5,8] = 1.5 s exposed of 10 s
    assert R.exposed_collective_share(tr) == pytest.approx(15.0)
    tr.ops = [o for o in tr.ops if not o.name.startswith("all-reduce")]
    assert R.exposed_collective_share(tr) is None     # nothing to read


def test_breakdown_names():
    tr = made_up()
    top = R.top_ops(tr, 3)
    assert top[0] == ["fusion", 2.5]        # fusion.6: 5 s over 2 devices
    assert top[1][0] == "fusion_bf16_8_128" and top[1][1] == 1.25
    assert R.group_name(R.Op("copy.12", 0, 1, 0, "",
                             "bf16[512,8,128,128]")) == \
        "copy_bf16_512_8_128_128"


def test_parse_op_and_nesting():
    name, detail = parse_op(
        '%jvp_flash_attention_fwd_.47 = (bf16[32,2048,128]{2,1,0:T(8,128)'
        '(2,1)}, f32[32,1,2048]{2,1,0}) custom-call(bf16[32,2048,128] '
        '%bitcast.1), custom_call_target="tpu_custom_call"')
    assert name == "jvp_flash_attention_fwd_.47"
    assert detail == "bf16[32,2048,128] mosaic"
    assert parse_op("%copy.3 = bf16[512,8,128,128]{3,2,1,0} copy(%p)") == \
        ("copy.3", "bf16[512,8,128,128]")
    # JAX names a tensor-parallel all-reduce after its psum: the opcode says
    psum = parse_op("%psum.7 = bf16[2,2048,4096]{2,1,0:T(8,128)(2,1)} "
                    "all-reduce(bf16[2,2048,4096]{2,1,0} %fusion.1), "
                    "channel_id=3, replica_groups={{0,1},{2,3}}")
    assert psum == ("psum.7", "bf16[2,2048,4096] all-reduce")
    assert R.is_collective(R.Op(*psum[:1], 0, 1, 0, "", psum[1]))
    assert R.is_collective(R.Op("all-gather-start.2", 0, 1, 0))
    assert not R.is_collective(R.Op("fusion.2", 0, 1, 0, "", "bf16[8]"))
    # a while that spans its body's ops is dropped, the body stays
    ops = [R.Op("while.1", 0.0, 10.0, 0), R.Op("fusion.2", 1.0, 2.0, 0),
           R.Op("fusion.3", 4.0, 5.0, 0), R.Op("copy.4", 11.0, 1.0, 0)]
    assert [o.name for o in leaf_ops(ops)] == ["fusion.2", "fusion.3",
                                               "copy.4"]


@pytest.fixture(scope="module")
def recorded() -> R.Trace:
    with open(os.path.join(DATA, "train_1p3b_v5e_50ms.json")) as f:
        return R.Trace.from_json(f.read())


def test_recorded_trace_adds_up(recorded):
    tr = recorded
    assert tr.devices == [0] and tr.window_s == pytest.approx(0.05)
    busy = R.busy_by_device(tr)[0]
    # ops on one TPU core run one after another: the union is the sum
    plain = sum(min(o.start + o.dur, tr.window[1]) - max(o.start,
                                                         tr.window[0])
                for o in R.select(tr))
    assert busy == pytest.approx(plain, rel=1e-6)
    assert 0 < busy <= tr.window_s
    assert R.idle_share(tr) == pytest.approx(100 * (1 - busy / 0.05))
    gaps = sum(s for _, s in R.idle_gaps(tr, n=1000))
    assert gaps == pytest.approx(tr.window_s - busy, rel=1e-6)
    flash = R.select(tr, ["flash_attention_fwd"])
    assert flash and all("mosaic" in o.detail for o in flash)
    assert all(o.program.startswith("jit_flat_step") for o in flash)
    for share in (R.time_share(tr, ["mosaic"]),
                  R.time_share(tr, ["flash_attention"]),
                  R.program_share(tr, "jit_flat_step")):
        assert 0 < share <= 100 + 1e-9
    assert R.Trace.from_json(tr.to_json()).to_json() == tr.to_json()
