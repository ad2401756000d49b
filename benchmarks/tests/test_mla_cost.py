"""Hand-worked cases of the latent decode kernel's cost function and of
the reducers this configuration brings (a roofline share over 100% gets
a later PR refused, so the counts are pinned here)."""
import os
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, REPO)

from benchmarks.harness import reduce as R  # noqa: E402
from benchmarks.harness.kernel_cost import least_seconds  # noqa: E402
from benchmarks.harness.mla_cost import latent_decode  # noqa: E402
from benchmarks.harness.peaks import peaks_for  # noqa: E402
from benchmarks.harness.reducers import (mla_roofline,  # noqa: E402
                                         op_time_share)

H, DC, DR, PAGE = 64, 512, 64, 128
Q_BYTES = H * (2 * DC + DR) * 2          # q in (576 a head) + u out (512)


def test_one_full_page():
    # 128 cache rows x 64 heads x (576 score + 512 value) multiply-adds
    flops, nbytes = latent_decode([(1, 128)], H, DC, DR, PAGE)
    assert flops == 2 * 64 * 128 * 1088 == 17_825_792
    # one page of 128 rows x 576 numbers x 2 bytes, read once for all heads
    assert nbytes == 128 * 576 * 2 + Q_BYTES == 147_456 + 139_264


def test_a_row_past_the_page_boundary_reads_a_second_page():
    f1, b1 = latent_decode([(1, 128)], H, DC, DR, PAGE)
    f2, b2 = latent_decode([(1, 129)], H, DC, DR, PAGE)
    assert b2 - b1 == 147_456
    assert f2 - f1 == 2 * 64 * 1088


def test_rows_add_up_and_heads_do_not_multiply_the_cache_bytes():
    rows = [(1, 300), (1, 1792), (1, 1)]
    f, b = latent_decode(rows, H, DC, DR, PAGE)
    parts = [latent_decode([r], H, DC, DR, PAGE) for r in rows]
    assert f == sum(p[0] for p in parts) and b == sum(p[1] for p in parts)
    _, b_half = latent_decode(rows, H // 2, DC, DR, PAGE)
    pages = 3 + 14 + 1
    assert b - b_half == 3 * Q_BYTES // 2      # only q and u follow H
    assert b == pages * 147_456 + 3 * Q_BYTES


def test_the_kernel_is_memory_bound_on_a_v5e():
    f, b = latent_decode([(1, 1024)], H, DC, DR, PAGE)
    pk = peaks_for("TPU v5 lite")
    assert b / pk.hbm_bytes > f / pk.flops
    assert least_seconds(f, b, pk) == b / pk.hbm_bytes


CFG = {"num_attention_heads": H, "kv_lora_rank": DC,
       "qk_rope_head_dim": DR, "num_hidden_layers": 6,
       "serving": {"page_size": PAGE}}


def _trace(name="mla_paged_decode_attention", program="jit_step"):
    ops = [R.Op("fusion.1", 0.0, 2e-6, 0, program, "bf16[128,4096]"),
           R.Op("custom-call.7", 2e-6, 4.2e-6, 0, program,
                f"{name} mosaic")]
    return R.Trace(ops, [], (0.0, 1e-5))


def test_roofline_share_by_hand():
    """One traced step of one row at 128 positions, six layers: the least
    time is 6 x 286,720 B / 819 GB/s = 2.1005 us against 4.2 us traced."""
    ctx = {"trace": _trace(), "cfg": CFG, "host": {"decode_rows": [(1, 128)]},
           "peaks": peaks_for("TPU v5 lite")}
    v = mla_roofline.read(ctx, program="jit_step", rows="decode_rows")
    assert v == pytest.approx(100 * 6 * 286_720 / 0.819e12 / 4.2e-6)
    assert 49.9 < v < 50.1
    assert op_time_share.read(
        ctx, names=["mla_paged_decode_attention"]) == pytest.approx(
            100 * 4.2 / 6.2)


def test_nothing_to_read_without_the_kernel_or_the_latent_config():
    """Without the kernel in the trace and in a cell of another
    configuration the roofline reader returns None and raises nothing."""
    host = {"decode_rows": [(1, 128)]}
    pk = peaks_for("TPU v5 lite")
    other = {"trace": _trace("paged_decode_attention"), "cfg": CFG,
             "host": host, "peaks": pk}
    assert mla_roofline.read(other, "jit_step", "decode_rows") is None
    dense = dict(other, trace=_trace(), cfg={"num_hidden_layers": 16})
    assert mla_roofline.read(dense, "jit_step", "decode_rows") is None
    assert mla_roofline.read(dict(other, trace=_trace(), host={}),
                             "jit_step", "decode_rows") is None
