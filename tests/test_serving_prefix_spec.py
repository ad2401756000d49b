"""Prefix-cache sharing + speculative decoding on the serving engine.

Under test (inference/serving.py, the two PR-16 serving optimizations):
- shared-prefix admission maps cached pages into the new slot's block
  table and the chunk planner starts at the first COLD chunk (the
  chunk plan is asserted through the per-request prefill_chunk spans)
- copy-on-write on divergence: a full-prefix-hit refeed copies the
  final shared page first, and the DONOR's output stays bit-identical
- greedy speculative decoding commits exactly the plain-decode token
  stream (bit-gated), with tokens/step > 1 at nonzero acceptance
- preempting a slot that holds shared pages leaves the sharer intact
- idle cached pages are reclaimed (LRU) under pool pressure
- the ref-counted free-list accounting invariant holds across
  admit/evict/preempt/shed/finish (debug_invariants mode)
- ZERO recompiles after warmup with both features on (the compile
  lattice gains no data-dependent shapes)
- a multi-tenant trace (a few system prompts, many users) served with
  the cache on, off and on under speculation gives ONE token stream,
  its fed + skipped ledger closes and most lookups hit
"""
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.core.enforce import PreconditionNotMetError
from paddle_tpu.inference import Config, ServingEngine, create_predictor
from paddle_tpu.models.llama import (LlamaForCausalLM, llama_tiny,
                                     llama_tiny_draft)

PAGE = 8


@pytest.fixture(scope="module")
def tiny_model():
    paddle.seed(11)
    return LlamaForCausalLM(llama_tiny())


@pytest.fixture(scope="module")
def draft_model():
    paddle.seed(13)
    return LlamaForCausalLM(llama_tiny_draft())


@pytest.fixture()
def paged_pred(tiny_model):
    return create_predictor(
        Config().set_model(tiny_model).enable_paged_kv(page_size=PAGE))


@pytest.fixture()
def draft_pred(draft_model):
    return create_predictor(
        Config().set_model(draft_model).enable_paged_kv(page_size=PAGE))


_SOLO_CACHE = {}


def _solo(tiny_model, prompt, n_new):
    """One-request-at-a-time Predictor reference output. One module-
    wide predictor (its bucketed programs reuse across prompt shapes)
    and memoized outputs keep the 20+ reference decodes cheap."""
    if "pred" not in _SOLO_CACHE:
        _SOLO_CACHE["pred"] = create_predictor(
            Config().set_model(tiny_model).enable_paged_kv(
                page_size=PAGE))
    key = (prompt.tobytes(), n_new)
    if key not in _SOLO_CACHE:
        pred = _SOLO_CACHE["pred"]
        _SOLO_CACHE[key] = np.asarray(
            pred.generate(paddle.to_tensor(prompt[None]),
                          max_new_tokens=n_new)._value)[0]
    return _SOLO_CACHE[key]


def _sys_prompt(pages, seed=5):
    r = np.random.RandomState(seed)
    return r.randint(1, 256, (pages * PAGE,))


def _with_tail(sysp, tail, seed):
    r = np.random.RandomState(seed)
    return np.concatenate([sysp, r.randint(1, 256, (tail,))])


def _chunk_spans(eng, rid):
    for tr in eng.request_traces():
        if tr["rid"] == rid:
            return [s for s in tr["spans"]
                    if s["name"] == "prefill_chunk"]
    return []


class TestPrefixCache:
    def test_shared_prefix_skips_prefill_chunks(self, tiny_model,
                                                paged_pred):
        """A request sharing a 4-page prefix with an earlier one feeds
        ONE chunk starting at the cached frontier instead of three —
        asserted on the chunk plan (prefill_chunk spans) — and both
        outputs match the sequential reference exactly."""
        sysp = _sys_prompt(4)                       # 32 tokens, Sc = 16
        eng = ServingEngine(paged_pred, max_batch=2, prefill_chunk=16,
                            prefix_cache=True, debug_invariants=True)
        donor = _with_tail(sysp, 0, 1)              # exactly the prefix
        sharer = _with_tail(sysp, 8, 2)             # prefix + 1 cold page
        rid0 = eng.submit(donor, max_new_tokens=4)
        eng.run()                                   # donor registers pages
        rid1 = eng.submit(sharer, max_new_tokens=4)
        done = eng.run()
        s = eng.prefix_cache_stats()
        assert s["hits"] == 4 and s["skipped_tokens"] >= 32
        spans = _chunk_spans(eng, rid1)
        assert len(spans) == 1                      # 3 chunks skipped
        assert spans[0]["meta"]["start"] == 32      # first COLD token
        assert spans[0]["meta"]["tokens"] == 8
        # ledger-exact reuse accounting: fed + skipped == prompt tokens
        assert s["fed_tokens"] + s["skipped_tokens"] == \
            len(donor) + len(sharer)
        np.testing.assert_array_equal(
            done[rid0].output_ids, _solo(tiny_model, donor, 4))
        np.testing.assert_array_equal(
            done[rid1].output_ids, _solo(tiny_model, sharer, 4))

    def test_cow_divergence_keeps_donor_bit_identical(self, tiny_model,
                                                      paged_pred):
        """A full-prompt hit refeeds its last token into a shared page
        — the copy-on-write must leave the mid-decode donor's pages
        untouched: both requests equal the sequential reference."""
        sysp = _sys_prompt(3)
        eng = ServingEngine(paged_pred, max_batch=2, prefill_chunk=16,
                            prefix_cache=True, debug_invariants=True)
        rid0 = eng.submit(sysp, max_new_tokens=10)
        for _ in range(4):                  # donor reaches mid-decode
            eng.step()
        rid1 = eng.submit(sysp.copy(), max_new_tokens=10)
        done = eng.run()
        assert eng.prefix_cache_stats()["cow"] >= 1
        ref = _solo(tiny_model, sysp, 10)
        np.testing.assert_array_equal(done[rid0].output_ids, ref)
        np.testing.assert_array_equal(done[rid1].output_ids, ref)

    def test_preempting_sharer_leaves_other_sharer_intact(
            self, tiny_model, paged_pred):
        """Two admitted requests share the donor's cached pages; page
        starvation preempts the YOUNGER one mid-prefill. The elder
        sharer (refcount drops 2 -> 1) must keep decoding on the
        still-live pages, and the preempted request restarts exactly."""
        sysp = _sys_prompt(3)                        # 3 cached pages
        eng = ServingEngine(paged_pred, max_batch=3, pool_pages=8,
                            prefill_chunk=16, prefix_cache=True,
                            debug_invariants=True)
        rid_d = eng.submit(sysp, max_new_tokens=4)
        eng.run()                                    # donor -> 3 idle pages
        cold = np.random.RandomState(9).randint(1, 256, (40,))
        rid_x = eng.submit(cold, max_new_tokens=4)       # elder, cold
        rid_1 = eng.submit(_with_tail(sysp, 8, 3), max_new_tokens=4)
        rid_2 = eng.submit(_with_tail(sysp, 8, 4), max_new_tokens=4)
        done = eng.run()
        preempts = [s for tr in eng.request_traces()
                    for s in tr["spans"] if s["name"] == "preempt"]
        assert preempts, "scenario must starve pages into a preemption"
        for rid, p in [(rid_d, sysp), (rid_x, cold),
                       (rid_1, _with_tail(sysp, 8, 3)),
                       (rid_2, _with_tail(sysp, 8, 4))]:
            np.testing.assert_array_equal(
                done[rid].output_ids, _solo(tiny_model, p, 4))

    def test_lru_reclaim_under_pool_pressure(self, tiny_model,
                                             paged_pred):
        """Distinct prompts fill the cache with idle registered pages;
        later admissions must reclaim them (oldest first) instead of
        stalling — and every output stays exact."""
        eng = ServingEngine(paged_pred, max_batch=2, pool_pages=8,
                            prefill_chunk=16, prefix_cache=True,
                            debug_invariants=True)
        prompts = [_sys_prompt(3, seed=20 + i) for i in range(4)]
        done = {}
        for p in prompts:                   # sequential: cache fills up
            eng.submit(p, max_new_tokens=4)
            done.update(eng.run())
        s = eng.prefix_cache_stats()
        assert s["reclaimed"] >= 1
        rids = sorted(done)
        for rid, p in zip(rids, prompts):
            np.testing.assert_array_equal(
                done[rid].output_ids, _solo(tiny_model, p, 4))

    def test_requires_chunked_mode(self, paged_pred):
        with pytest.raises(PreconditionNotMetError):
            ServingEngine(paged_pred, max_batch=2, prefix_cache=True)


class TestPoolInvariant:
    def test_invariant_holds_across_lifecycle(self, paged_pred):
        """admit / finish / preempt / shed / reclaim sequences keep
        free + idle + refcounted-live an exact partition of the pool
        (debug mode checks after every transition; one more explicit
        check after the drain)."""
        sysp = _sys_prompt(3)
        eng = ServingEngine(paged_pred, max_batch=2, pool_pages=8,
                            prefill_chunk=16, prefix_cache=True,
                            max_queue=3, debug_invariants=True)
        for i in range(6):                  # overflows max_queue: sheds
            eng.submit(_with_tail(sysp, 2 + i, 30 + i),
                       max_new_tokens=3)
        eng.run()
        shed = [r for r in eng.finished.values() if r.shed]
        assert shed, "queue bound must shed"
        eng.check_invariants()
        c = eng.cache.counts()
        free = c["free"] + c["idle"]
        live = sum(1 for pg in range(eng.P - 1) if eng.cache.refcount(pg))
        assert free + live == eng.P - 1

    def test_invariant_catches_double_free(self, paged_pred):
        """The checker is not a tautology: corrupting the free list
        (a simulated double free) must raise."""
        eng = ServingEngine(paged_pred, max_batch=2, prefill_chunk=16,
                            prefix_cache=True)
        eng.check_invariants()
        eng.cache._free_pages.append(eng.cache._free_pages[0])
        with pytest.raises(PreconditionNotMetError, match="invariant"):
            eng.check_invariants()


class TestSpeculativeDecoding:
    def _outputs(self, pred, prompts, n_new, **kw):
        eng = ServingEngine(pred, max_batch=3, prefill_chunk=16,
                            debug_invariants=True, **kw)
        rids = [eng.submit(p, max_new_tokens=n_new) for p in prompts]
        done = eng.run()
        return eng, [done[r].output_ids for r in rids]

    def test_greedy_spec_bit_identical_to_plain(self, tiny_model,
                                                paged_pred, draft_pred):
        """The acceptance gate: with a REAL (distinct) draft model the
        committed ids equal plain greedy decode token-for-token."""
        r = np.random.RandomState(3)
        prompts = [r.randint(1, 256, (L,)) for L in [7, 12, 21, 5, 9]]
        _, plain = self._outputs(paged_pred, prompts, 10)
        eng, spec = self._outputs(paged_pred, prompts, 10,
                                  draft_predictor=draft_pred,
                                  spec_tokens=3)
        for a, b in zip(plain, spec):
            np.testing.assert_array_equal(a, b)
        s = eng.spec_stats()
        assert s["rounds"] > 0 and s["tokens_per_step"] >= 1.0

    def test_self_speculation_tokens_per_step(self, tiny_model,
                                              paged_pred):
        """Target-as-its-own-draft: every proposal matches the target
        argmax chain, so acceptance is 1.0 and each verify round
        commits k+1 tokens (minus budget-capped tails) — tokens/step
        must clear 1 by a wide margin, outputs still exact."""
        r = np.random.RandomState(4)
        prompts = [r.randint(1, 256, (L,)) for L in [7, 12, 9]]
        _, plain = self._outputs(paged_pred, prompts, 12)
        eng, spec = self._outputs(paged_pred, prompts, 12,
                                  draft_predictor=paged_pred,
                                  spec_tokens=3)
        for a, b in zip(plain, spec):
            np.testing.assert_array_equal(a, b)
        s = eng.spec_stats()
        assert s["accept_rate"] > 0.9
        assert s["tokens_per_step"] > 2.0

    def test_spec_requires_greedy_and_chunked(self, tiny_model,
                                              paged_pred):
        with pytest.raises(PreconditionNotMetError):
            ServingEngine(paged_pred, max_batch=2,
                          draft_predictor=paged_pred, spec_tokens=2)
        cfg = Config().set_model(tiny_model).enable_paged_kv(
            page_size=PAGE)
        cfg.generation.temperature = 0.7
        hot = create_predictor(cfg)
        with pytest.raises(PreconditionNotMetError):
            ServingEngine(hot, max_batch=2, prefill_chunk=16,
                          draft_predictor=hot, spec_tokens=2)
        with pytest.raises(PreconditionNotMetError):
            ServingEngine(paged_pred, max_batch=2, prefill_chunk=16,
                          spec_tokens=2)    # draft missing


class TestComposedCompileStability:
    def test_zero_recompiles_after_warmup_both_features(
            self, tiny_model, paged_pred, draft_pred):
        """Prefix cache + spec decode together: after one warmup mix
        (cold prompt, shared prefix, full hit with CoW, decode), a
        varied stream triggers ZERO additional XLA compiles."""
        sysp = _sys_prompt(2)
        eng = ServingEngine(paged_pred, max_batch=3, prefill_chunk=16,
                            prefix_cache=True, debug_invariants=True,
                            draft_predictor=draft_pred, spec_tokens=3)
        for p, n in [(_with_tail(sysp, 5, 1), 6),
                     (_with_tail(sysp, 9, 2), 6), (sysp.copy(), 4)]:
            eng.submit(p, max_new_tokens=n)
        eng.run()
        warm = eng.stats.compiles
        for i in range(6):
            eng.submit(_with_tail(sysp, 3 + i, 40 + i),
                       max_new_tokens=4 + (i % 3))
        eng.submit(sysp.copy(), max_new_tokens=3)
        done = eng.run()
        assert eng.stats.compiles == warm, "recompiled after warmup"
        assert eng.prefix_cache_stats()["hits"] > 0
        for req in done.values():
            ref = _solo(tiny_model, req.prompt, req.max_new_tokens)
            np.testing.assert_array_equal(req.output_ids, ref)


class TestMultiTenantTrace:
    def test_cache_on_off_and_speculation_give_one_stream(
            self, tiny_model, paged_pred):
        """Both features reorder the same computation: 12 users over 3
        system prompts of 4 pages arrive two a round; the three serves
        agree token for token, every prompt token was fed or skipped
        exactly once, and more than half of the page lookups hit."""
        r = np.random.RandomState(16)
        systems = [r.randint(1, 256, (4 * PAGE,)) for _ in range(3)]
        trace = [np.concatenate([systems[r.randint(3)],
                                 r.randint(1, 256, (r.randint(4, 12),))])
                 for _ in range(12)]

        def serve(**kw):
            eng = ServingEngine(paged_pred, max_batch=4, prefill_chunk=16,
                                debug_invariants=True, **kw)
            rids, i = [], 0
            while i < len(trace) or eng.queue or eng.num_active:
                for p in trace[i:i + 2]:
                    rids.append(eng.submit(p, max_new_tokens=8))
                i += 2
                eng.step()
            eng.run()           # nothing left in flight
            return eng, [tuple(eng.finished[rid].new_tokens)
                         for rid in rids]

        eng_on, out_on = serve(prefix_cache=True)
        _, out_off = serve()
        _, out_spec = serve(prefix_cache=True, draft_predictor=paged_pred,
                            spec_tokens=3)
        assert out_on == out_off == out_spec
        assert all(len(o) == 8 for o in out_on)
        s = eng_on.prefix_cache_stats()
        assert s["fed_tokens"] + s["skipped_tokens"] == \
            sum(len(p) for p in trace)
        assert s["hits"] / s["lookups"] > 0.5
