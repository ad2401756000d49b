"""Chunked prefill + the unified ragged paged-attention kernel.

Under test (the ISSUE-12 tentpole):
- kernel parity: the Pallas ragged kernel vs its dense XLA fallback on
  decode-only, prefill-only, and mixed batches, with chunk starts that
  straddle page boundaries and dead (seq_len 0) rows
- two-program equivalence: the unified dense math collapses EXACTLY
  (bit-level) onto the legacy paged prefill path when every slot is
  valid
- ServingEngine chunked mode: token-level parity with one-request-at-
  a-time Predictor.generate across mixed streams, chunk boundaries off
  the page lattice, arrivals mid-decode, prefill-only requests
- the compile-stability acceptance: after one warmup mix, arbitrary
  length mixes trigger ZERO additional compiles on the unified lattice
- incremental page accounting: a long prompt is admitted on its FIRST
  chunk's pages, so a short request co-admits where the legacy
  whole-footprint reservation would have queued it
- preemption liveness: a page-starved pool completes exactly (youngest
  mid-prefill row bounces to the queue head, elders drain first)
- per-chunk spans in the request traces; tpulint zero-baseline pins
"""
import sys
from pathlib import Path

import numpy as np
import pytest

import paddle_tpu as paddle
import jax.numpy as jnp
from paddle_tpu.inference import Config, ServingEngine, create_predictor
from paddle_tpu.models.llama import LlamaForCausalLM, llama_tiny
from paddle_tpu.ops.pallas.ragged_paged_attention import (
    ragged_paged_attention, ragged_paged_attention_dense)

REPO = Path(__file__).resolve().parent.parent


@pytest.fixture(scope="module")
def tiny_model():
    paddle.seed(11)
    return LlamaForCausalLM(llama_tiny())


@pytest.fixture()
def paged_pred(tiny_model):
    return create_predictor(
        Config().set_model(tiny_model).enable_paged_kv(page_size=8))


def _solo(tiny_model, prompt, n_new):
    """One-request-at-a-time Predictor reference output."""
    pred = create_predictor(
        Config().set_model(tiny_model).enable_paged_kv(page_size=8))
    return np.asarray(pred.generate(paddle.to_tensor(prompt[None]),
                                    max_new_tokens=n_new)._value)[0]


def _prompts(lens, vocab, seed=0):
    r = np.random.RandomState(seed)
    return [r.randint(1, vocab, (L,)) for L in lens]


# ---------------------------------------------------------------------------
# kernel parity: Pallas ragged kernel vs dense XLA fallback
# ---------------------------------------------------------------------------
def _pool(r, B, npages, KV, page, D, extra=5):
    P = B * npages + extra
    kp = jnp.asarray(r.randn(P, KV, page, D), jnp.float32)
    vp = jnp.asarray(r.randn(P, KV, page, D), jnp.float32)
    # scrambled physical page order: proves the table indirection
    tbl = jnp.asarray(r.permutation(P)[:B * npages].reshape(B, npages),
                      jnp.int32)
    return kp, vp, tbl


class TestRaggedKernelParity:
    B, Sq, H, KV, D, page, npages = 4, 16, 8, 2, 128, 8, 16

    def _check(self, starts, seq_lens, seed=3):
        r = np.random.RandomState(seed)
        q = jnp.asarray(r.randn(self.B, self.Sq, self.H, self.D),
                        jnp.float32)
        kp, vp, tbl = _pool(r, self.B, self.npages, self.KV, self.page,
                            self.D)
        st = jnp.asarray(starts, jnp.int32)
        nv = jnp.asarray(seq_lens, jnp.int32)
        out = ragged_paged_attention(q, kp, vp, tbl, st, nv,
                                     interpret=True)
        ref = ragged_paged_attention_dense(q, kp, vp, tbl, st, nv)
        assert float(jnp.abs(out - ref).max()) < 1e-4

    def test_mixed_batch_chunk_straddles_pages(self):
        # row 0: chunk starting mid-page (5 + 16 crosses two page
        # boundaries); row 1: decode deep in the cache; row 2: chunk
        # from position 0; row 3: dead slot
        self._check([5, 77, 0, 0], [16, 1, 16, 0])

    def test_decode_only_batch(self):
        self._check([10, 1, 55, 127], [1, 1, 1, 1], seed=4)

    def test_prefill_only_batch(self):
        self._check([0, 8, 16, 3], [16, 16, 16, 16], seed=5)

    def test_partial_chunks_and_dead_rows(self):
        # ragged seq_lens below the Sq lattice (token-budget splits)
        self._check([31, 0, 9, 64], [7, 0, 3, 12], seed=6)

    def test_dead_rows_output_exact_zero(self):
        r = np.random.RandomState(7)
        q = jnp.asarray(r.randn(self.B, self.Sq, self.H, self.D),
                        jnp.float32)
        kp, vp, tbl = _pool(r, self.B, self.npages, self.KV, self.page,
                            self.D)
        nv = jnp.asarray([0, 4, 0, 1], jnp.int32)
        st = jnp.asarray([0, 11, 0, 30], jnp.int32)
        for fn in (lambda: ragged_paged_attention(
                       q, kp, vp, tbl, st, nv, interpret=True),
                   lambda: ragged_paged_attention_dense(
                       q, kp, vp, tbl, st, nv)):
            out = np.asarray(fn())
            assert (out[0] == 0).all() and (out[2] == 0).all()
            # and invalid tail slots of live rows are zeroed too
            assert (out[1, 4:] == 0).all() and (out[3, 1:] == 0).all()

    def test_fully_valid_matches_two_program_path_bitwise(self):
        """With every slot valid, the unified dense math must collapse
        BIT-EXACTLY onto the legacy paged dense path (same gather, same
        mask, same einsums) — the two-program equivalence the serving
        parity tests lean on."""
        from paddle_tpu.ops.pallas.decode_attention import \
            paged_attention_dense

        r = np.random.RandomState(8)
        q = jnp.asarray(r.randn(self.B, self.Sq, self.H, self.D),
                        jnp.float32)
        kp, vp, tbl = _pool(r, self.B, self.npages, self.KV, self.page,
                            self.D)
        st = jnp.asarray([0, 24, 5, 80], jnp.int32)
        nv = jnp.full((self.B,), self.Sq, jnp.int32)
        uni = np.asarray(ragged_paged_attention_dense(
            q, kp, vp, tbl, st, nv))
        legacy = np.asarray(paged_attention_dense(q, kp, vp, tbl, st))
        np.testing.assert_array_equal(uni, legacy)


# ---------------------------------------------------------------------------
# ServingEngine chunked mode: parity with sequential serving
# ---------------------------------------------------------------------------
class TestChunkedServingParity:
    def test_mixed_stream_matches_sequential(self, tiny_model,
                                             paged_pred):
        """Chunk boundaries off the page lattice (L=7, 19, 33), prompts
        both under and over Sc, a stream longer than the batch: every
        request produces exactly the tokens it gets decoded alone."""
        V = tiny_model.config.vocab_size
        eng = ServingEngine(paged_pred, max_batch=2, prefill_chunk=16)
        prompts = _prompts([7, 4, 19, 33, 5], V)
        rids = [eng.submit(p, max_new_tokens=6) for p in prompts]
        done = eng.run()
        assert sorted(done) == sorted(rids)
        for rid, p in zip(rids, prompts):
            np.testing.assert_array_equal(done[rid].output_ids,
                                          _solo(tiny_model, p, 6))

    def test_token_budget_partial_chunks(self, tiny_model, paged_pred):
        """A budget below the chunk bucket splits feeds mid-chunk (and
        mid-page) without changing any emitted token."""
        V = tiny_model.config.vocab_size
        eng = ServingEngine(paged_pred, max_batch=3, prefill_chunk=16,
                            prefill_token_budget=10)
        prompts = _prompts([23, 9, 17], V, seed=1)
        rids = [eng.submit(p, max_new_tokens=5) for p in prompts]
        done = eng.run()
        for rid, p in zip(rids, prompts):
            np.testing.assert_array_equal(done[rid].output_ids,
                                          _solo(tiny_model, p, 5))

    def test_arrival_mid_decode_chunks_interleave(self, tiny_model,
                                                  paged_pred):
        """A long prompt submitted while others decode feeds its chunks
        through the unified step WITHOUT stopping the decode rows, and
        still matches the sequential reference."""
        V = tiny_model.config.vocab_size
        eng = ServingEngine(paged_pred, max_batch=3, prefill_chunk=16)
        a, b, c = _prompts([8, 5, 40], V, seed=2)
        ra = eng.submit(a, max_new_tokens=8)
        rb = eng.submit(b, max_new_tokens=8)
        for _ in range(3):
            eng.step()
        assert eng.num_active == 2
        na = len(eng.slots[[i for i in range(3)
                            if eng.slots[i] is not None
                            and eng.slots[i].req.rid == ra][0]]
                 .req.new_tokens)
        rc = eng.submit(c, max_new_tokens=4)   # long arrival mid-decode
        eng.step()                             # one unified chunk round
        # the decode rows advanced THROUGH the chunk round (no HOL)
        sa = [s for s in eng.slots if s is not None
              and s.req.rid == ra]
        if sa:                                  # not finished yet
            assert len(sa[0].req.new_tokens) > na
        done = eng.run()
        for rid, p, n in ((ra, a, 8), (rb, b, 8), (rc, c, 4)):
            np.testing.assert_array_equal(done[rid].output_ids,
                                          _solo(tiny_model, p, n))

    def test_prefill_only_requests(self, tiny_model, paged_pred):
        """max_new_tokens=1: the unified step serves pure prefill-chunk
        batches (no decode rows ever)."""
        V = tiny_model.config.vocab_size
        eng = ServingEngine(paged_pred, max_batch=2, prefill_chunk=16)
        prompts = _prompts([21, 34], V, seed=3)
        rids = [eng.submit(p, max_new_tokens=1) for p in prompts]
        done = eng.run()
        for rid, p in zip(rids, prompts):
            np.testing.assert_array_equal(done[rid].output_ids,
                                          _solo(tiny_model, p, 1))


# ---------------------------------------------------------------------------
# compile stability on the unified lattice
# ---------------------------------------------------------------------------
class TestUnifiedCompileStability:
    def test_zero_recompiles_after_warmup(self, tiny_model, paged_pred):
        V = tiny_model.config.vocab_size
        eng = ServingEngine(paged_pred, max_batch=4, prefill_chunk=16)
        for p in _prompts([7, 40], V, seed=5):        # warmup mix
            eng.submit(p, max_new_tokens=5)
        eng.run()
        warm = eng.stats.compiles
        assert warm > 0
        mixes = [(3, 9, 21), (33, 5), (30, 2, 14, 8), (13,)]
        for i, mix in enumerate(mixes):
            for p in _prompts(list(mix), V, seed=6 + i):
                eng.submit(p, max_new_tokens=5)
            eng.run()
        assert eng.stats.compiles == warm, (
            f"recompiled under traffic: {eng.stats.as_dict()}")
        assert eng.stats.cache_hits > 0

    def test_unified_site_ledgers_registered(self, tiny_model):
        """The ("unified", Sc) site shows up in the CompileStats notes
        and the memory-ledger map (the bench's HBM acceptance hook)."""
        pred = create_predictor(
            Config().set_model(tiny_model).enable_paged_kv(page_size=8))
        V = tiny_model.config.vocab_size
        eng = ServingEngine(pred, max_batch=2, prefill_chunk=16,
                            mem_ledger=True)
        eng.submit(_prompts([20], V, seed=9)[0], max_new_tokens=4)
        eng.run()
        assert eng.Sc == 16
        assert eng.memory_ledger(("unified", 16)) is not None
        assert any(k[0] == "unified" for k in eng.stats.bucket_tokens)


# ---------------------------------------------------------------------------
# incremental page accounting + preemption liveness
# ---------------------------------------------------------------------------
class TestIncrementalPages:
    def test_long_prompt_coadmits_short(self, tiny_model):
        """pool = 15 usable pages; the long request's full footprint is
        14 pages, the short one needs 2. Legacy whole-footprint
        reservation leaves 1 free page — the short request queues.
        Chunked admission reserves only the first chunk (2 pages), so
        BOTH are in flight immediately — and both still decode
        exactly."""
        V = tiny_model.config.vocab_size
        long_p = _prompts([104], V, seed=10)[0]   # ceil(112/8)=14 pages
        short_p = _prompts([8], V, seed=11)[0]    # ceil(16/8)=2 pages

        def mk(**kw):
            pred = create_predictor(Config().set_model(tiny_model)
                                    .enable_paged_kv(page_size=8))
            return ServingEngine(pred, max_batch=2, pool_pages=15, **kw)

        legacy = mk()
        legacy.submit(long_p, max_new_tokens=8)
        legacy.submit(short_p, max_new_tokens=8)
        legacy.step()
        assert legacy.num_active == 1 and len(legacy.queue) == 1

        eng = mk(prefill_chunk=16)
        rl = eng.submit(long_p, max_new_tokens=8)
        rs = eng.submit(short_p, max_new_tokens=8)
        eng.step()
        assert eng.num_active == 2 and not eng.queue
        done = eng.run()
        np.testing.assert_array_equal(done[rl].output_ids,
                                      _solo(tiny_model, long_p, 8))
        np.testing.assert_array_equal(done[rs].output_ids,
                                      _solo(tiny_model, short_p, 8))
        # every page came back
        assert eng.cache.counts()["free"] == 15

    def test_page_starved_pool_preempts_and_completes(self, tiny_model):
        """Two prompts whose combined footprint exceeds the pool: both
        co-admit on first-chunk pages, collide mid-prefill, and the
        youngest bounces back to the queue (preemption by exact
        recomputation — no token sampled yet). The stream drains with
        exact outputs."""
        V = tiny_model.config.vocab_size
        a, b = _prompts([40, 40], V, seed=12)     # 6 pages each, 7 usable

        def mk():
            pred = create_predictor(Config().set_model(tiny_model)
                                    .enable_paged_kv(page_size=8))
            return ServingEngine(pred, max_batch=2, pool_pages=7,
                                 prefill_chunk=16)

        eng = mk()
        ra = eng.submit(a, max_new_tokens=8)
        rb = eng.submit(b, max_new_tokens=8)
        eng.step()
        assert eng.num_active == 2                # both co-admitted
        done = eng.run()
        np.testing.assert_array_equal(done[ra].output_ids,
                                      _solo(tiny_model, a, 8))
        np.testing.assert_array_equal(done[rb].output_ids,
                                      _solo(tiny_model, b, 8))
        assert eng.cache.counts()["free"] == 7          # pool fully returned
        # the loser's trace records the preemption instant
        spans = [sp["name"] for t in eng.request_traces()
                 for sp in t["spans"]]
        assert "preempt" in spans


# ---------------------------------------------------------------------------
# per-chunk spans + TTFT semantics
# ---------------------------------------------------------------------------
class TestChunkSpans:
    def test_chunk_spans_cover_the_prompt(self, tiny_model, paged_pred):
        V = tiny_model.config.vocab_size
        eng = ServingEngine(paged_pred, max_batch=1, prefill_chunk=16)
        p = _prompts([39], V, seed=13)[0]          # 3 chunks: 16+16+7
        rid = eng.submit(p, max_new_tokens=3)
        done = eng.run()
        tr = [t for t in eng.request_traces() if t["rid"] == rid][0]
        chunks = [sp for sp in tr["spans"]
                  if sp["name"] == "prefill_chunk"]
        assert [c["meta"]["chunk"] for c in chunks] == [0, 1, 2]
        assert [c["meta"]["tokens"] for c in chunks] == [16, 16, 7]
        assert sum(c["meta"]["tokens"] for c in chunks) == len(p)
        # TTFT stays first-token time: the prefill stage span closes
        # when the LAST chunk samples, not per chunk
        req = done[rid]
        assert req.t_first_token >= chunks[-1]["t0"]
        names = [sp["name"] for sp in tr["spans"]]
        assert "prefill" in names and "decode" in names \
            and "e2e" in names

    def test_chunk_rounds_interleave_decode_rounds(self, tiny_model,
                                                   paged_pred):
        """The Chrome-trace view of the tentpole: while a long prompt
        chunks in, the other request's decode_round spans keep landing
        BETWEEN its prefill_chunk spans."""
        V = tiny_model.config.vocab_size
        eng = ServingEngine(paged_pred, max_batch=2, prefill_chunk=16)
        short, long_p = _prompts([6, 48], V, seed=14)
        rs = eng.submit(short, max_new_tokens=10)
        for _ in range(2):
            eng.step()                  # short is mid-decode
        eng.submit(long_p, max_new_tokens=2)
        eng.run()
        tr = [t for t in eng.request_traces() if t["rid"] == rs][0]
        rounds = [sp for sp in tr["spans"]
                  if sp["name"] == "decode_round"
                  and sp["meta"].get("unified")]
        # the short request decoded through unified (chunk-carrying)
        # rounds — the no-head-of-line-blocking acceptance
        assert rounds, [sp["name"] for sp in tr["spans"]]


# ---------------------------------------------------------------------------
# tpulint: the rewritten scheduler + new kernel stay at ZERO baseline
# ---------------------------------------------------------------------------
def test_tpulint_unified_serving_zero_baseline():
    sys.path.insert(0, str(REPO))
    try:
        from tools.tpulint import ALL_RULES, lint_paths

        findings = lint_paths(
            [REPO / "paddle_tpu" / "inference" / "serving.py",
             REPO / "paddle_tpu" / "inference" / "kv_cache.py",
             REPO / "paddle_tpu" / "ops" / "pallas"
                  / "ragged_paged_attention.py"],
            ALL_RULES, root=REPO)
    finally:
        sys.path.remove(str(REPO))
    assert findings == [], [str(f) for f in findings]
