"""Paged (block-table) KV cache + ragged-batch decode.

Reference parity targets:
- phi/kernels/fusion/gpu/block_multi_head_attention_kernel.cu +
  block_attn.h (paged cache attention kernel)
- python/paddle/incubate/nn/functional/block_multihead_attention.py:19
  (python surface / semantics: per-seq block tables, ragged lengths)

TPU redesign under test: the physical page id comes from a
scalar-prefetched block table, read by the kernel's own loop over the
pages a row owns (ops/pallas/decode_attention.py), and the Predictor
allocates pages per row with a trash page absorbing right-pad writes.
"""
import numpy as np
import pytest

import jax.numpy as jnp

import paddle_tpu as paddle
from paddle_tpu.ops.pallas import decode_attention as da
from paddle_tpu.ops.pallas.decode_attention import (
    _dense_ragged, decode_attention, paged_attention_dense,
    paged_decode_attention)


def _rand(r, *shape):
    return jnp.asarray(r.randn(*shape), jnp.float32)


# (Sq, G, KV, page, dtype) for the kernel's page walk: decode, a small
# and the largest prefill bucket; MHA and two GQA groupings; 1, 2 and 8
# KV heads, so that the head block is one head, a proper divisor of KV
# and all of KV (TestHeadBlockRule pins which); every page the tools
# compile; both pool dtypes
WALK_CASES = [
    (1, 1, 1, 8, "float32"), (1, 1, 2, 16, "bfloat16"),
    (1, 1, 8, 64, "float32"), (1, 1, 8, 128, "bfloat16"),
    (1, 4, 1, 16, "float32"), (1, 4, 2, 64, "bfloat16"),
    (1, 4, 8, 128, "bfloat16"), (1, 4, 8, 8, "float32"),
    (1, 4, 8, 16, "float32"), (1, 4, 8, 64, "bfloat16"),
    (1, 8, 1, 64, "bfloat16"), (1, 8, 2, 128, "float32"),
    (1, 8, 8, 8, "float32"), (1, 8, 8, 128, "bfloat16"),
    (64, 1, 2, 8, "float32"), (64, 1, 8, 64, "bfloat16"),
    (64, 4, 1, 64, "float32"), (64, 4, 8, 128, "bfloat16"),
    (64, 4, 8, 16, "float32"), (64, 8, 8, 128, "float32"),
    (512, 1, 8, 64, "bfloat16"), (512, 1, 2, 128, "float32"),
    (512, 4, 1, 8, "float32"), (512, 4, 8, 128, "bfloat16"),
]


def _walk_batch(r, Sq, G, KV, page, dtype, poison=False):
    """One batch that holds every edge of the page walk: lengths 0, 1,
    page-1, page, page+1 and the whole table, free slots (length 0)
    between the live rows, physical pages in shuffled order. With
    ``poison`` the pages no row owns hold NaN and the table entries past
    each row's frontier name those pages or no page at all; the second
    result is the clean pool and table the dense twin may read."""
    D = 128
    npages = -(-(page + 1 + Sq) // page) + 1
    full = npages * page - Sq
    lens = np.array([0, 1, 0, page - 1, page, 0, page + 1, full], np.int32)
    B = len(lens)
    P = B * npages + 3
    order = r.permutation(P)
    tbl = order[:B * npages].reshape(B, npages).astype(np.int32)
    kp = r.randn(P, KV, page, D).astype("float32")
    vp = r.randn(P, KV, page, D).astype("float32")
    q = jnp.asarray(r.randn(B, Sq, KV * G, D), dtype)
    clean = (jnp.asarray(kp, dtype), jnp.asarray(vp, dtype),
             jnp.asarray(tbl))
    if not poison:
        return q, clean, clean, jnp.asarray(lens)
    owned = (lens + Sq - 1) // page + 1           # pages a row walks
    past = np.arange(npages)[None] >= owned[:, None]
    spare = order[B * npages:]
    bad = np.where(r.rand(B, npages) < 0.5, spare[0],
                   np.where(r.rand(B, npages) < 0.5, P + 1000, -7))
    dirty_tbl = np.where(past, bad, tbl).astype(np.int32)
    unowned = np.ones(P, bool)
    unowned[tbl[~past]] = False
    kp_d, vp_d = kp.copy(), vp.copy()
    kp_d[unowned] = np.nan
    vp_d[unowned] = np.nan
    # the dense twin gathers every table entry and multiplies a masked
    # probability of 0 into it: it reads zeros where the kernel must
    # read nothing
    kp[unowned] = 0
    vp[unowned] = 0
    clean = (jnp.asarray(kp, dtype), jnp.asarray(vp, dtype),
             jnp.asarray(np.where(past, spare[0], tbl).astype(np.int32)))
    dirty = (jnp.asarray(kp_d, dtype), jnp.asarray(vp_d, dtype),
             jnp.asarray(dirty_tbl))
    return q, dirty, clean, jnp.asarray(lens)


def _tol(dtype):
    # bf16 operands: the kernel rounds p to the pool's dtype before PV,
    # the dense twin keeps it in float32
    return 1e-4 if dtype == "float32" else 3e-2


class TestPagedKernel:
    def test_ragged_vector_offset_matches_dense(self):
        r = np.random.RandomState(0)
        B, H, KV, D, M = 3, 8, 2, 128, 512
        q = _rand(r, B, 1, H, D)
        kc, vc = _rand(r, B, KV, M, D), _rand(r, B, KV, M, D)
        lens = jnp.asarray([100, 37, 411], jnp.int32)
        out = decode_attention(q, kc, vc, lens, interpret=True)
        ref = _dense_ragged(q, kc, vc, lens)
        assert float(jnp.abs(out - ref).max()) < 1e-4

    @pytest.mark.parametrize("Sq", [1, 8])
    def test_paged_matches_gathered_dense(self, Sq):
        r = np.random.RandomState(1)
        B, H, KV, D, M, page = 3, 8, 2, 128, 512, 64
        npages = M // page
        P = B * npages + 5
        q = _rand(r, B, Sq, H, D)
        kp, vp = _rand(r, P, KV, page, D), _rand(r, P, KV, page, D)
        # scrambled physical page order: proves the table indirection
        tbl = jnp.asarray(
            r.permutation(P)[:B * npages].reshape(B, npages), jnp.int32)
        lens = jnp.asarray([100, 37, 411], jnp.int32)
        out = paged_decode_attention(q, kp, vp, tbl, lens,
                                     interpret=True)
        ref = paged_attention_dense(q, kp, vp, tbl, lens)
        assert float(jnp.abs(out - ref).max()) < 1e-4

    @pytest.mark.parametrize("Sq,G,KV,page,dtype", WALK_CASES)
    def test_page_walk_edges_in_one_batch(self, Sq, G, KV, page, dtype):
        r = np.random.RandomState(Sq + 7 * G + 31 * KV + page)
        q, (kp, vp, tbl), _, lens = _walk_batch(r, Sq, G, KV, page, dtype)
        assert da.paged_supported(q.shape, kp.shape)
        out = paged_decode_attention(q, kp, vp, tbl, lens, interpret=True)
        ref = paged_attention_dense(q, kp, vp, tbl, lens)
        assert out.shape == q.shape and out.dtype == q.dtype
        err = jnp.abs(out.astype(jnp.float32) - ref.astype(jnp.float32))
        assert float(err.max()) < _tol(dtype)

    @pytest.mark.parametrize("Sq,G,KV,page,dtype", [
        (1, 4, 8, 128, "bfloat16"), (1, 1, 8, 16, "float32"),
        (1, 8, 2, 64, "bfloat16"), (64, 4, 8, 128, "bfloat16"),
        (64, 1, 8, 16, "float32"), (512, 4, 2, 128, "float32")])
    def test_walk_stops_at_the_frontier(self, Sq, G, KV, page, dtype):
        """Pages no row owns hold NaN, and the table past each row's
        frontier names them, or ids outside the pool: a walk that went
        one page too far would read them."""
        r = np.random.RandomState(Sq + 7 * G + 31 * KV + page + 1)
        q, (kp, vp, tbl), (kc, vc, tc), lens = _walk_batch(
            r, Sq, G, KV, page, dtype, poison=True)
        assert bool(jnp.isnan(kp.astype(jnp.float32)).any())
        out = paged_decode_attention(q, kp, vp, tbl, lens, interpret=True)
        assert bool(jnp.isfinite(out.astype(jnp.float32)).all())
        ref = paged_attention_dense(q, kc, vc, tc, lens)
        err = jnp.abs(out.astype(jnp.float32) - ref.astype(jnp.float32))
        assert float(err.max()) < _tol(dtype)

    def test_paged_vs_contiguous_cache(self):
        """Pages laid out to mirror a contiguous cache must reproduce
        the contiguous kernel's output exactly."""
        r = np.random.RandomState(2)
        B, H, KV, D, M, page = 2, 4, 4, 128, 256, 64
        npages = M // page
        q = _rand(r, B, 1, H, D)
        kc, vc = _rand(r, B, KV, M, D), _rand(r, B, KV, M, D)
        # pool[b*npages + j] = cache[b][:, j*page:(j+1)*page]
        kp = jnp.swapaxes(kc.reshape(B, KV, npages, page, D), 1, 2) \
            .reshape(B * npages, KV, page, D)
        vp = jnp.swapaxes(vc.reshape(B, KV, npages, page, D), 1, 2) \
            .reshape(B * npages, KV, page, D)
        tbl = jnp.arange(B * npages, dtype=jnp.int32).reshape(B, npages)
        lens = jnp.asarray([200, 129], jnp.int32)
        paged = paged_decode_attention(q, kp, vp, tbl, lens,
                                       interpret=True)
        dense = decode_attention(q, kc, vc, lens, interpret=True)
        assert float(jnp.abs(paged - dense).max()) < 1e-4


# (Sq, G, KV, page, dtype) whose plans hold 2 (a single head at the
# gate's edge: VMEM has no room for more), 3 and 4 slots, each once with
# every KV head in a fetch and once with a block smaller than KV
# (several grid steps a row): TestPlanRule pins which
DEPTH_CASES = [
    (512, 4, 1, 8, "float32"), (512, 4, 2, 16, "float32"),
    (1, 8, 4, 128, "bfloat16"), (1, 1, 32, 128, "bfloat16"),
    (1, 4, 2, 16, "float32"), (64, 4, 8, 128, "bfloat16"),
]

# what the lookahead must survive, as (lengths in pages and tokens,
# sinks): rows of one page and free slots only, so that it crosses a
# row at every visit; a batch that ends in free slots behind a long
# row, and one of a single free slot, so that fewer visits are left
# than it runs ahead; mixed rows with a sink
DEEP_WALKS = {
    "one_page_and_free_slots": (lambda pg, Sq: [
        0, min(5, max(pg - Sq, 0)), 0, 0, max(pg - Sq, 0), 0, 1, 0, 0],
        False),
    "the_batch_ends_in_free_slots": (lambda pg, Sq: [
        pg + 1, 0, max(3 * pg - Sq, 0), 0, 0], False),
    "a_single_free_slot": (lambda pg, Sq: [0], False),
    "mixed_rows_with_a_sink": (lambda pg, Sq: [
        0, 1, pg, 2 * pg + 1, 0, pg - 1], True),
}


class TestDeepWalk:
    """The page walk keeps ``depth - 1`` fetches in flight across rows
    and grid steps: at every depth the plan can return the kernel is
    its dense twin, and reads no page a row does not own (NaN there,
    and table entries past a frontier that name no page)."""

    @pytest.mark.parametrize("walk", sorted(DEEP_WALKS))
    @pytest.mark.parametrize("Sq,G,KV,page,dtype", DEPTH_CASES)
    def test_kernel_is_its_dense_twin(self, Sq, G, KV, page, dtype, walk):
        lens_of, sink = DEEP_WALKS[walk]
        lens = np.asarray(lens_of(page, Sq), np.int32)
        r = np.random.RandomState(len(walk) + Sq + 7 * G + 31 * KV + page)
        B, npages = len(lens), int(lens.max() + Sq - 1) // page + 2
        P = B * npages + 2
        order = r.permutation(P)
        tbl = order[:B * npages].reshape(B, npages).astype(np.int32)
        past = np.arange(npages)[None] > ((lens + Sq - 1) // page)[:, None]
        kp = r.randn(P, KV, page, 128).astype("float32")
        vp = r.randn(P, KV, page, 128).astype("float32")
        unowned = np.ones(P, bool)
        unowned[tbl[~past]] = False
        dirty = [np.where(unowned[:, None, None, None], np.nan, x)
                 for x in (kp, vp)]
        clean = [np.where(unowned[:, None, None, None], 0, x)
                 for x in (kp, vp)]
        q = jnp.asarray(r.randn(B, Sq, KV * G, 128), dtype)
        sk = jnp.asarray(r.randn(KV * G), jnp.float32) if sink else None
        out = paged_decode_attention(
            q, *(jnp.asarray(x, dtype) for x in dirty),
            jnp.asarray(np.where(past, P + 1000, tbl), jnp.int32),
            jnp.asarray(lens), interpret=True, sinks=sk)
        ref = paged_attention_dense(
            q, *(jnp.asarray(x, dtype) for x in clean),
            jnp.asarray(np.where(past, order[-1], tbl), jnp.int32),
            jnp.asarray(lens), sinks=sk)
        assert bool(jnp.isfinite(out.astype(jnp.float32)).all())
        err = jnp.abs(out.astype(jnp.float32) - ref.astype(jnp.float32))
        assert float(err.max()) < _tol(dtype)


class TestPlanRule:
    """``_paged_plan``: a pure function of the call's shapes."""

    def test_decode_takes_every_head_and_a_prefill_bucket_one(self):
        # the serving cells: 32 q heads over 8 KV heads of 128, page 128
        assert da._paged_plan(1, 4, 8, 128, 128, 2).hb == 8
        for Sb in (64, 128, 256, 512):
            assert da._paged_plan(Sb, 4, 8, 128, 128, 2).hb == 1
        # MHA at Llama-7B widths (chip_smoke.py): a proper divisor
        assert da._paged_plan(1, 1, 32, 128, 128, 2).hb == 16

    def test_walk_cases_cover_one_head_a_divisor_and_all(self):
        kinds = set()
        for Sq, G, KV, page, dtype in WALK_CASES:
            hb = da._paged_plan(Sq, G, KV, page, 128,
                                jnp.dtype(dtype).itemsize).hb
            assert KV % hb == 0
            kinds.add("one" if hb == 1 and KV > 1 else
                      "all" if hb == KV else "divisor")
        assert kinds == {"one", "divisor", "all"}

    def test_depth_cases_cover_every_depth_whole_and_in_head_blocks(self):
        seen = set()
        for Sq, G, KV, page, dtype in DEPTH_CASES:
            plan = da._paged_plan(Sq, G, KV, page, 128,
                                  jnp.dtype(dtype).itemsize)
            seen.add((plan.depth, plan.hb < KV))
        assert seen == {(d, blocks) for d in (2, 3, 4)
                        for blocks in (False, True)}

    def test_depth_follows_the_bytes_of_a_fetch(self):
        """Slots enough that two fetches and ``_PAGED_IN_FLIGHT`` bytes
        fly beside the page being computed, to ``_PAGED_MAX_DEPTH``; the
        bytes are those of ``depth - 1`` fetches of K and V."""
        for KV, D, Dv in ((4, 128, 128), (8, 128, 128), (8, 256, 128),
                          (4, 256, 128), (1, 128, 128)):
            hb, depth, flying = da._paged_plan(1, 4, KV, 128, D, 2, Dv)
            fetch = hb * 128 * (D + Dv) * 2
            assert flying == (depth - 1) * fetch
            assert 2 <= depth <= da._PAGED_MAX_DEPTH
            assert flying >= da._PAGED_IN_FLIGHT \
                or depth == da._PAGED_MAX_DEPTH
            assert depth == 3 or (depth - 2) * fetch < da._PAGED_IN_FLIGHT
        # the serving cells' decode calls: two fetches in flight each
        assert da._paged_plan(1, 8, 4, 128, 128, 2).depth == 3      # trinity
        assert da._paged_plan(1, 4, 8, 128, 128, 2).depth == 3      # mistral
        assert da._paged_plan(1, 8, 8, 128, 256, 2, 128).depth == 3  # mimo

    @pytest.mark.parametrize("itemsize", [2, 4])
    @pytest.mark.parametrize("page", [8, 16, 64, 128])
    def test_nothing_exceeds_the_budget(self, page, itemsize):
        """A block of several heads fits the budget at two slots and
        the deeper one at its depth; a single head (the floor) fits
        Mosaic's scoped limit at every shape the gate admits, and a
        depth over 2 is never what takes it past the deeper budget."""
        for KV in (1, 2, 4, 8, 32):
            for G in (1, 4, 8):
                for Sq in (1, 2, 5, 16, 64, 128, 256, 512, 2048):
                    if not da.paged_supported((1, Sq, KV * G, 128),
                                              (4, KV, page, 128)):
                        continue
                    hb, depth, _ = da._paged_plan(Sq, G, KV, page, 128,
                                                  itemsize)
                    need = da._paged_vmem_bytes(hb, Sq, G, page, 128,
                                                itemsize)
                    assert need <= (da._PAGED_VMEM_BUDGET if hb > 1
                                    else 16 * 2 ** 20), (Sq, G, KV, hb)
                    if depth > 2:
                        assert da._paged_vmem_bytes(
                            hb, Sq, G, page, 128, itemsize, depth=depth) \
                            <= da._PAGED_VMEM_DEEP, (Sq, G, KV, hb, depth)
                    # the largest: the next divisor up does not fit
                    for up in range(hb + 1, KV + 1):
                        if KV % up == 0:
                            assert da._paged_vmem_bytes(
                                up, Sq, G, page, 128, itemsize) \
                                > da._PAGED_VMEM_BUDGET
                            break


# The cells' shapes of call of ``paged_decode_attention``:
# (B, H, KV, D, npages, P, keywords) -> sha256 of the jaxpr (the kernel's
# body in it), read from the parent of PR 47 (ff0c998) by this very code:
# the page walk moved into ``_page_walk``, which the latent kernel
# shares, and the dense kernel's text did not change by it
WALK_TEXT = {
    "chat": ((48, 32, 8, 128, 19, 512, {}),
        "2ae43a8f67a37a5d0555a8b5196e1bc0b91f9908891958b0e6fd708910fd8666"),
    "trinity_window_ring": (
        (64, 32, 4, 128, 17, 2048, {"window": 2048}),
        "b3cd091d17770c8c463fcf8ef7e3b160001adedac2b5001ad40f02cce9bbfd00"),
    "mimo_sink_window": (
        (128, 64, 8, 256, 2, 1024, {"window": 128, "sinks": True}),
        "7edc5ef19fdd2baad6666ed936d77499103405e66ed71aac7e2084643a56cc86"),
    "keye_keep": ((128, 32, 4, 128, 70, 4096, {"keep": True}),
        "feb6cebc7e0a8b2a27fce1ffda8593dfacb5009475f479793f287f391097c462"),
}


def _walk_text(B, H, KV, D, npages, P, kw):
    import hashlib

    import jax

    page, Dv = 128, 128
    S = jax.ShapeDtypeStruct
    args = [S((B, 1, H, D), jnp.bfloat16), S((P, KV, page, D), jnp.bfloat16),
            S((P, KV, page, Dv), jnp.bfloat16), S((B, npages), jnp.int32),
            S((B,), jnp.int32)]
    names = [n for n in ("sinks", "keep") if kw.get(n)]
    if "sinks" in names:
        args.append(S((H,), jnp.float32))
    if "keep" in names:
        args.append(S((B, npages * page), jnp.bool_))

    def call(q, kp, vp, tbl, ln, *rest):
        return paged_decode_attention.__wrapped__(
            q, kp, vp, tbl, ln, window=kw.get("window"),
            **dict(zip(names, rest)))

    return hashlib.sha256(
        str(jax.make_jaxpr(call)(*args)).encode()).hexdigest()


@pytest.mark.parametrize("cell", sorted(WALK_TEXT))
def test_the_dense_kernel_on_the_shared_walk_is_the_kernel_it_was(cell):
    shape, parent = WALK_TEXT[cell]
    assert _walk_text(*shape) == parent


class TestRaggedGenerate:
    @classmethod
    def setup_class(cls):
        from paddle_tpu.models.llama import LlamaForCausalLM, llama_tiny

        paddle.seed(0)
        cls.cfg = llama_tiny()
        cls.model = LlamaForCausalLM(cls.cfg)
        r = np.random.RandomState(0)
        cls.lens = [11, 24, 17]
        cls.S0 = max(cls.lens)
        cls.ids = np.zeros((3, cls.S0), np.int64)
        for b, L in enumerate(cls.lens):
            cls.ids[b, :L] = r.randint(1, cls.cfg.vocab_size, (L,))

    def _pred(self, **cfg_calls):
        from paddle_tpu.inference import Config, create_predictor

        conf = Config().set_model(self.model)
        if cfg_calls.get("paged"):
            conf.enable_paged_kv(page_size=8)
        return create_predictor(conf)

    def test_ragged_equals_per_row_solo(self):
        """Each ragged row must produce exactly the tokens it would
        produce decoded alone (no lockstep, no pad contamination)."""
        pred = self._pred()
        out = np.asarray(pred.generate(
            paddle.to_tensor(self.ids), max_new_tokens=6,
            lengths=np.array(self.lens))._value)
        for b, L in enumerate(self.lens):
            solo = np.asarray(pred.generate(
                paddle.to_tensor(self.ids[b:b + 1, :L]),
                max_new_tokens=6)._value)[0, L:]
            assert (out[b, self.S0:] == solo).all(), (b, out[b], solo)

    def test_paged_equals_dense(self):
        out = np.asarray(self._pred().generate(
            paddle.to_tensor(self.ids), max_new_tokens=6,
            lengths=np.array(self.lens))._value)
        out_p = np.asarray(self._pred(paged=True).generate(
            paddle.to_tensor(self.ids), max_new_tokens=6,
            lengths=np.array(self.lens))._value)
        assert (out == out_p).all()

    def test_eos_freezes_row(self):
        pred = self._pred()
        base = np.asarray(pred.generate(
            paddle.to_tensor(self.ids), max_new_tokens=6,
            lengths=np.array(self.lens))._value)
        eos = int(base[0, self.S0 + 1])  # row 0's 2nd new token
        out = np.asarray(pred.generate(
            paddle.to_tensor(self.ids), max_new_tokens=6,
            lengths=np.array(self.lens), eos_token_id=eos)._value)
        row = out[0, self.S0:]
        assert row[1] == eos and (row[2:] == eos).all()
        # rows that never hit eos are unchanged
        for b in (1, 2):
            if eos not in base[b, self.S0:]:
                assert (out[b] == base[b]).all()

    def test_paged_pool_is_smaller_than_dense(self):
        """The point of paging: sum-of-lengths pages, not B*max_len."""
        from paddle_tpu.inference import Config, create_predictor

        pred = create_predictor(
            Config().set_model(self.model).enable_paged_kv(page_size=8))
        n_new = 4
        caches, P = pred._paged_caches(self.lens, n_new, 64, 8,
                                       jnp.float32)
        dense_rows = 3 * 64
        assert P * 8 < dense_rows
        # every owned page id is unique; unowned entries hit the trash
        tables = np.asarray(caches[0][2])
        owned = [t for b, L in enumerate(self.lens)
                 for t in tables[b, :-(-(L + n_new) // 8)]]
        assert len(owned) == len(set(owned))
        assert (tables.max() == P - 1)  # trash page referenced


def test_block_multihead_attention_reference_surface():
    """The reference's exact python API name over the paged kernel
    (reference: incubate/nn/functional/block_multihead_attention.py:19)
    — decode phase: per-row write at seq_lens_decoder, ragged attend."""
    import paddle_tpu.incubate.nn.functional as IF
    from paddle_tpu.ops.pallas.decode_attention import \
        paged_attention_dense

    r = np.random.RandomState(0)
    B, H, D, page, npages = 2, 4, 8, 8, 4
    P = B * npages + 1
    kp = jnp.asarray(r.randn(P, H, page, D), jnp.float32)
    vp = jnp.asarray(r.randn(P, H, page, D), jnp.float32)
    tbl = jnp.asarray(r.permutation(P - 1)[:B * npages]
                      .reshape(B, npages), jnp.int32)
    lens = np.array([[5], [13]], np.int32)
    qkv = r.randn(B, 3 * H * D).astype("float32")
    z = paddle.to_tensor(np.zeros((B, 1), "int32"))
    out, _, kc, vc = IF.block_multihead_attention(
        paddle.to_tensor(qkv), paddle.to_tensor(kp), paddle.to_tensor(vp),
        z, paddle.to_tensor(lens),
        paddle.to_tensor(np.ones((B, 1), "int32")),
        None, None, None, None, paddle.to_tensor(tbl), block_size=page)
    q = qkv.reshape(B, 3, H, D)[:, 0]
    kn, vn = np.asarray(kc._value), np.asarray(vc._value)
    ref = paged_attention_dense(jnp.asarray(q)[:, None], jnp.asarray(kn),
                                jnp.asarray(vn), tbl,
                                jnp.asarray(lens.reshape(-1)))
    assert np.abs(np.asarray(out._value).reshape(B, 1, H, D)
                  - np.asarray(ref)).max() < 1e-5
    for b, L in enumerate([5, 13]):
        p_id, s = int(tbl[b, L // page]), L % page
        assert np.allclose(kn[p_id, :, s, :],
                           qkv.reshape(B, 3, H, D)[b, 1])


def test_block_multihead_attention_gqa_layout():
    """Reference GQA qkv layout: (H + 2*KV)*D consecutive head planes;
    kv heads land in the KV-head cache and q attends grouped."""
    import paddle_tpu.incubate.nn.functional as IF
    from paddle_tpu.ops.pallas.decode_attention import \
        paged_attention_dense

    r = np.random.RandomState(1)
    B, H, KV, D, page, npages = 2, 8, 2, 8, 8, 4
    P = B * npages + 1
    kp = jnp.asarray(r.randn(P, KV, page, D), jnp.float32)
    vp = jnp.asarray(r.randn(P, KV, page, D), jnp.float32)
    tbl = jnp.asarray(r.permutation(P - 1)[:B * npages]
                      .reshape(B, npages), jnp.int32)
    lens = np.array([[5], [13]], np.int32)
    qkv = r.randn(B, (H + 2 * KV) * D).astype("float32")
    z = paddle.to_tensor(np.zeros((B, 1), "int32"))
    out, _, kc, vc = IF.block_multihead_attention(
        paddle.to_tensor(qkv), paddle.to_tensor(kp), paddle.to_tensor(vp),
        z, paddle.to_tensor(lens),
        paddle.to_tensor(np.ones((B, 1), "int32")),
        None, None, None, None, paddle.to_tensor(tbl), block_size=page)
    heads = qkv.reshape(B, H + 2 * KV, D)
    ref = paged_attention_dense(
        jnp.asarray(heads[:, :H])[:, None], jnp.asarray(kc._value),
        jnp.asarray(vc._value), tbl, jnp.asarray(lens.reshape(-1)))
    assert np.abs(np.asarray(out._value).reshape(B, 1, H, D)
                  - np.asarray(ref)).max() < 1e-5
    kn = np.asarray(kc._value)
    p_id, s = int(tbl[0, 5 // page]), 5 % page
    assert np.allclose(kn[p_id, :, s, :], heads[0, H:H + KV])
    # seq_lens_decoder beyond the table must refuse loudly
    with pytest.raises(Exception, match="block table"):
        IF.block_multihead_attention(
            paddle.to_tensor(qkv), paddle.to_tensor(kp),
            paddle.to_tensor(vp), z,
            paddle.to_tensor(np.array([[32], [1]], "int32")),
            paddle.to_tensor(np.ones((B, 1), "int32")),
            None, None, None, None, paddle.to_tensor(tbl),
            block_size=page)
