"""FLOPs and bytes of the kernels against hand-worked cases, and: no
roofline share computed from the recorded trace exceeds 100%. CPU only."""
import os

import pytest

from benchmarks.harness import kernel_cost as K
from benchmarks.harness import reduce as R
from benchmarks.harness.peaks import PEAKS_BY_DEVICE_KIND, peaks_for

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


def test_flash_forward_by_hand():
    # B=1, S=4, H=1, D=2: causal pairs 4*5/2 = 10; QK^T and PV are each
    # 2*D ops a pair: 4*2*10 = 80. Bytes: q,k,v,o = 4 arrays of 8 bf16.
    assert K.flash_attention_fwd(1, 4, 1, 2) == (80.0, 64.0)
    # the train-1.3b-1chip call: B=2, S=2048, H=16, D=128
    flops, nbytes = K.flash_attention_fwd(2, 2048, 16, 128)
    assert flops == 4 * 128 * (2048 * 2049 / 2) * 32
    assert nbytes == 4 * 2 * 2048 * 16 * 128 * 2


def test_flash_backward_by_hand():
    # dq: 3 matmuls, dkv: 4 matmuls, 2*D ops a pair each
    assert K.flash_attention_dq(1, 4, 1, 2)[0] == 6 * 2 * 10
    assert K.flash_attention_dkv(1, 4, 1, 2)[0] == 8 * 2 * 10
    assert K.flash_attention_dq(1, 4, 1, 2)[1] == 5 * 8 * 2
    assert K.flash_attention_dkv(1, 4, 1, 2)[1] == 6 * 8 * 2
    # forward + both backward passes = 4.5 forwards of matmul work
    f = sum(fn(2, 2048, 16, 128)[0] for fn in (
        K.flash_attention_fwd, K.flash_attention_dq,
        K.flash_attention_dkv))
    assert f == 4.5 * K.flash_attention_fwd(2, 2048, 16, 128)[0]


def test_paged_decode_by_hand():
    # one row decoding (Sq=1) at context 300, GQA 32 query / 8 KV heads,
    # D=128, page 128: 300 pairs; flops 4*D*H*pairs; 3 pages of K and of
    # V at 8 heads; q and o of one position
    flops, nbytes = K.paged_attention([(1, 300)], 32, 8, 128, 128)
    assert flops == 4 * 128 * 32 * 300
    assert nbytes == 2 * 3 * 128 * 8 * 128 * 2 + 2 * 1 * 32 * 128 * 2
    # rows add up; a row at an exact page boundary takes no extra page
    two = K.paged_attention([(1, 300), (1, 256)], 32, 8, 128, 128)
    assert two[0] == flops + 4 * 128 * 32 * 256
    assert two[1] == nbytes + 2 * 2 * 128 * 8 * 128 * 2 + 2 * 32 * 128 * 2
    # only the referenced pages: nothing depends on the pool's size


def test_paged_prefill_by_hand():
    # a prefill of 5 tokens into an empty row (Sq = kv = 5): causal pairs
    # 1+2+3+4+5 = 15
    flops, nbytes = K.paged_attention([(5, 5)], 32, 8, 128, 128)
    assert flops == 4 * 128 * 32 * 15
    assert nbytes == 2 * 1 * 128 * 8 * 128 * 2 + 2 * 5 * 32 * 128 * 2
    # a chunk of 3 new tokens after 4 cached: queries see 5, 6, 7 keys
    assert K.paged_attention([(3, 7)], 1, 1, 1, 4)[0] == 4 * (5 + 6 + 7)


def test_least_seconds_and_peaks():
    p = peaks_for("TPU v5 lite")
    assert (p.flops, p.hbm_bytes) == (197e12, 0.819e12)
    assert K.least_seconds(197e12, 1.0, p) == pytest.approx(1.0)
    assert K.least_seconds(1.0, 0.819e12, p) == pytest.approx(1.0)
    assert peaks_for("TPU v5e") == PEAKS_BY_DEVICE_KIND["TPU v5 lite"]
    with pytest.raises(KeyError):
        peaks_for("TPU v9 imaginary")


def test_no_share_of_the_recorded_trace_exceeds_100():
    """Every flash-attention call in the recorded v5e trace took longer
    than the least time its useful work needs: a share above 100% would
    mean the work is counted too high or the time too low."""
    with open(os.path.join(DATA, "train_1p3b_v5e_50ms.json")) as f:
        tr = R.Trace.from_json(f.read())
    p = peaks_for("TPU v5 lite")
    shape = (2, 2048, 16, 128)
    seen = 0
    for kernel in ("flash_attention_fwd", "flash_attention_dq",
                   "flash_attention_dkv"):
        least = K.least_seconds(*getattr(K, kernel)(*shape), p)
        for op in R.select(tr, [kernel]):
            if op.start >= tr.window[0] and \
                    op.start + op.dur <= tr.window[1]:
                seen += 1
                assert 0 < 100 * least / op.dur <= 100, (op.name, op.dur)
    assert seen >= 3
