"""Hand-worked cases of the window and full decode kernels' cost
functions and of the reducer this configuration brings (a roofline share
over 100% gets a later PR refused, so the counts are pinned here)."""
import os
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, REPO)

from benchmarks.harness import reduce as R  # noqa: E402
from benchmarks.harness.hybrid_cost import (global_decode,  # noqa: E402
                                            window_decode)
from benchmarks.harness.kernel_cost import least_seconds  # noqa: E402
from benchmarks.harness.peaks import peaks_for  # noqa: E402
from benchmarks.harness.reducers import (host_value,  # noqa: E402
                                         hybrid_roofline, op_time_share)

H, DK, DV, W, PAGE = 64, 192, 128, 128, 128
QO = H * (DK + DV) * 2                   # q in (192 a head) + o out (128)
KEY = (DK + DV) * 2                      # one key and value of one KV head


@pytest.mark.parametrize("ctx, keys", [(1, 1), (127, 127), (128, 128),
                                       (129, 128), (3200, 128)])
def test_window_decode_by_hand(ctx, keys):
    """A row sees min(ctx, 128) keys of 8 KV heads, read once: the ring
    never counts more than 128, whatever the context."""
    flops, nbytes = window_decode([(1, ctx)], H, 8, DK, DV, W)
    assert flops == 2 * 64 * keys * 320
    assert nbytes == keys * 8 * KEY + QO
    assert QO == 40_960 and 8 * KEY == 5_120
    if keys == 128:
        assert (flops, nbytes) == (5_242_880, 696_320)


@pytest.mark.parametrize("ctx, pages", [(1, 1), (127, 1), (128, 1),
                                        (129, 2), (3200, 25)])
def test_global_decode_by_hand(ctx, pages):
    """A row sees every key; bytes are the pages it references, whole,
    of 4 KV heads."""
    flops, nbytes = global_decode([(1, ctx)], H, 4, DK, DV, PAGE)
    assert flops == 2 * 64 * ctx * 320
    assert nbytes == pages * 128 * 4 * KEY + QO
    if ctx == 3200:
        assert (flops, nbytes) == (131_072_000, 8_232_960)


def test_rows_add_up():
    rows = [(1, 300), (1, 3200), (1, 1)]
    for fn, args in ((window_decode, (H, 8, DK, DV, W)),
                     (global_decode, (H, 4, DK, DV, PAGE))):
        f, b = fn(rows, *args)
        parts = [fn([r], *args) for r in rows]
        assert f == sum(p[0] for p in parts)
        assert b == sum(p[1] for p in parts)


def test_both_kernels_are_memory_bound_on_a_v5e():
    pk = peaks_for("TPU v5 lite")
    for f, b in (window_decode([(1, 2000)], H, 8, DK, DV, W),
                 global_decode([(1, 2000)], H, 4, DK, DV, PAGE)):
        assert b / pk.hbm_bytes > f / pk.flops
        assert least_seconds(f, b, pk) == b / pk.hbm_bytes


CFG = {"num_attention_heads": H, "head_dim": DK, "v_head_dim": DV,
       "num_key_value_heads": 4, "swa_num_key_value_heads": 8,
       "sliding_window": W, "num_hidden_layers": 7,
       "hybrid_layer_pattern": [0, 1, 1, 1, 1, 0, 1, 1, 1],
       "serving": {"page_size": PAGE}}


def _trace(program="jit_step"):
    ops = [R.Op("fusion.1", 0.0, 2e-6, 0, program, "bf16[128,4096]"),
           R.Op("custom-call.7", 2e-6, 8.5e-6, 0, program,
                "paged_window_decode_attention mosaic"),
           R.Op("custom-call.9", 12e-6, 2.0e-6, 0, program,
                "paged_decode_attention mosaic")]
    return R.Trace(ops, [], (0.0, 2e-5))


def test_roofline_shares_by_hand():
    """One traced step of one row at context 3200. Window: 5 layers x
    696,320 B / 819 GB/s = 4.251 us against 8.5 us traced. Full: 2
    layers x 8,232,960 B / 819 GB/s = 20.1 us against 2 us: a share over
    100% is what a cost counted too high would read, and the reducer
    does not hide it."""
    ctx = {"trace": _trace(), "cfg": CFG,
           "host": {"decode_rows": [(1, 3200)]},
           "peaks": peaks_for("TPU v5 lite")}
    w = hybrid_roofline.read(ctx, "jit_step", "decode_rows", "window")
    assert w == pytest.approx(100 * 5 * 696_320 / 0.819e12 / 8.5e-6)
    assert 50.0 < w < 50.1
    g = hybrid_roofline.read(ctx, "jit_step", "decode_rows", "full")
    assert g == pytest.approx(100 * 2 * 8_232_960 / 0.819e12 / 2.0e-6)
    assert g > 100
    assert op_time_share.read(ctx, names=[
        "paged_window_decode_attention",
        "paged_decode_attention"]) == pytest.approx(100 * 10.5 / 12.5)


def test_the_two_kernels_names_do_not_match_each_other():
    tr = _trace()
    assert [o.name for o in R.select(
        tr, ["paged_decode_attention"])] == ["custom-call.9"]
    assert [o.name for o in R.select(
        tr, ["paged_window_decode_attention"])] == ["custom-call.7"]


def test_nothing_to_read_without_the_kernel_the_rows_or_the_layers():
    """Where the trace holds no such kernel, the host logged no rows or
    the configuration has no such layers, the reader returns None and
    raises nothing."""
    pk = peaks_for("TPU v5 lite")
    host = {"decode_rows": [(1, 128)]}
    base = {"trace": _trace(), "cfg": CFG, "host": host, "peaks": pk}
    assert hybrid_roofline.read(dict(base, trace=R.Trace(
        [], [], (0.0, 1e-5))), "jit_step", "decode_rows", "window") is None
    assert hybrid_roofline.read(dict(base, host={}), "jit_step",
                                "decode_rows", "full") is None
    dense = dict(base, cfg={"num_hidden_layers": 16})
    assert hybrid_roofline.read(dense, "jit_step", "decode_rows",
                                "window") is None
    no_window = dict(base, cfg=dict(CFG, hybrid_layer_pattern=[0] * 9))
    assert hybrid_roofline.read(no_window, "jit_step", "decode_rows",
                                "window") is None
    assert host_value.read(base, key="kv_bytes_per_context_token") is None
