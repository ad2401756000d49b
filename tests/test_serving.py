"""Continuous-batching ServingEngine over the ragged paged KV cache.

Under test (inference/serving.py + the Predictor compile-stability
layer):
- token-level parity with one-request-at-a-time Predictor.generate
- arrivals mid-decode join the in-flight batch (continuous batching)
- early-EOS rows are evicted, their pages return to the free list, and
  queued requests backfill the freed slots
- the compile counter stays FLAT after warmup across varied length
  mixes (the acceptance gate: bucketed (B, Sb, P) program lattice)
"""
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.inference import (Config, ServingEngine, create_predictor)
from paddle_tpu.models.llama import LlamaForCausalLM, llama_tiny


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


class TestServingParity:
    def test_mixed_length_stream_matches_sequential(self, tiny_model,
                                                    paged_pred):
        """A stream longer than the batch, mixed lengths: every request
        produces exactly the tokens it gets decoded alone."""
        V = tiny_model.config.vocab_size
        eng = ServingEngine(paged_pred, max_batch=2)
        prompts = _prompts([7, 4, 11, 5, 9], V)
        rids = [eng.submit(p, max_new_tokens=6) for p in prompts]
        done = eng.run()
        assert sorted(done) == sorted(rids)
        for rid, p in zip(rids, prompts):
            ref = _solo(tiny_model, p, 6)
            np.testing.assert_array_equal(done[rid].output_ids, ref)

    def test_chunked_decode_matches_sequential(self, tiny_model,
                                               paged_pred):
        """decode_chunk > 1 fuses steps into one scan launch without
        changing any emitted token."""
        V = tiny_model.config.vocab_size
        eng = ServingEngine(paged_pred, max_batch=3, decode_chunk=4)
        prompts = _prompts([9, 13, 6], V, seed=1)
        rids = [eng.submit(p, max_new_tokens=7) for p in prompts]
        done = eng.run()
        for rid, p in zip(rids, prompts):
            np.testing.assert_array_equal(done[rid].output_ids,
                                          _solo(tiny_model, p, 7))

    def test_arrival_mid_decode(self, tiny_model, paged_pred):
        """A request submitted while others are mid-decode joins the
        batch (continuous batching) and still decodes exactly."""
        V = tiny_model.config.vocab_size
        eng = ServingEngine(paged_pred, max_batch=3)
        a, b, c = _prompts([8, 5, 12], V, seed=2)
        ra = eng.submit(a, max_new_tokens=8)
        rb = eng.submit(b, max_new_tokens=8)
        for _ in range(3):
            eng.step()                       # a, b are mid-decode
        assert eng.num_active == 2
        rc = eng.submit(c, max_new_tokens=4)  # arrival mid-decode
        done = eng.run()
        for rid, p, n in ((ra, a, 8), (rb, b, 8), (rc, c, 4)):
            np.testing.assert_array_equal(done[rid].output_ids,
                                          _solo(tiny_model, p, n))


class TestEvictionBackfill:
    def test_eos_evicts_and_backfills(self, tiny_model, paged_pred):
        """A row hitting EOS early frees its slot+pages; a queued
        request backfills while the other row keeps decoding."""
        V = tiny_model.config.vocab_size
        a, b, c = _prompts([7, 9, 6], V, seed=3)
        ref_a = _solo(tiny_model, a, 8)
        eos = int(ref_a[len(a) + 1])          # a's 2nd new token
        eng = ServingEngine(paged_pred, max_batch=2)
        free0 = eng.cache.counts()["free"]
        ra = eng.submit(a, max_new_tokens=8, eos_token_id=eos)
        rb = eng.submit(b, max_new_tokens=8)
        rc = eng.submit(c, max_new_tokens=3)  # queued: batch is full
        eng.step()
        assert rc not in eng.finished and eng.queue  # c waits
        done = eng.run()
        # a stopped AT the eos token, well before its budget
        assert done[ra].new_tokens[-1] == eos
        assert len(done[ra].new_tokens) == 2
        # c was admitted after a's eviction and decoded exactly
        np.testing.assert_array_equal(done[rc].output_ids,
                                      _solo(tiny_model, c, 3))
        # b never saw any of it
        np.testing.assert_array_equal(done[rb].output_ids,
                                      _solo(tiny_model, b, 8))
        # every page returned to the free list
        assert eng.cache.counts()["free"] == free0
        assert (eng.cache.tables == eng.cache.trash).all()

    def test_dropping_the_engine_frees_the_pools_at_once(self, paged_pred):
        """The engine and its cache are no reference cycle (the cache
        runs its page programs through the engine WEAKLY): the pools'
        device memory goes with the last reference, not with the next
        garbage collection."""
        import gc
        import weakref

        eng = ServingEngine(paged_pred, max_batch=2)
        eng.submit(np.arange(1, 12), max_new_tokens=2)
        eng.run()
        gone = weakref.ref(eng), weakref.ref(eng.cache)
        gc.disable()
        try:
            del eng
            assert [r() for r in gone] == [None, None]
        finally:
            gc.enable()

    def test_pool_capacity_gates_admission(self, tiny_model):
        """Admission waits for pages, not just slots; a request that
        can never fit is refused loudly at submit."""
        pred = create_predictor(
            Config().set_model(tiny_model).enable_paged_kv(page_size=8))
        V = tiny_model.config.vocab_size
        # pool bucketed to 8 pages (7 usable): two 3-page requests fit,
        # a third must wait for an eviction
        eng = ServingEngine(pred, max_batch=3, pool_pages=7)
        prompts = _prompts([17, 18, 16], V, seed=4)
        rids = [eng.submit(p, max_new_tokens=5) for p in prompts]
        eng.step()
        assert eng.num_active == 2 and len(eng.queue) == 1
        done = eng.run()
        for rid, p in zip(rids, prompts):
            np.testing.assert_array_equal(done[rid].output_ids,
                                          _solo(tiny_model, p, 5))
        with pytest.raises(Exception, match="pool"):
            eng.submit(np.ones(60, np.int64), max_new_tokens=5)


class TestCompileStability:
    def test_engine_compiles_flat_across_mixes(self, tiny_model,
                                               paged_pred):
        """After warmup on ONE length mix, serving >= 4 different
        length mixes triggers ZERO additional compiles (acceptance
        criterion)."""
        V = tiny_model.config.vocab_size
        eng = ServingEngine(paged_pred, max_batch=4)
        for p in _prompts([7, 12], V, seed=5):        # warmup mix
            eng.submit(p, max_new_tokens=5)
        eng.run()
        warm = eng.stats.compiles
        assert warm > 0
        mixes = [(3, 9, 21), (5, 5), (30, 2, 14, 8), (13,)]
        for i, mix in enumerate(mixes):
            for p in _prompts(list(mix), V, seed=6 + i):
                eng.submit(p, max_new_tokens=5)
            eng.run()
        assert eng.stats.compiles == warm, (
            f"recompiled under traffic: {eng.stats.as_dict()}")
        assert eng.stats.cache_hits > 0
        assert eng.stats.tokens > 0

    def test_predictor_pool_bucket_reuses_programs(self, tiny_model):
        """The Predictor side of the tentpole: P bucketed like S, so
        varied ragged mixes reuse one (prefill, decode) program pair."""
        pred = create_predictor(
            Config().set_model(tiny_model).enable_paged_kv(page_size=8))
        V = tiny_model.config.vocab_size
        r = np.random.RandomState(9)

        def gen(lens):
            ids = np.zeros((len(lens), max(lens)), np.int64)
            for b, L in enumerate(lens):
                ids[b, :L] = r.randint(1, V, (L,))
            return pred.generate(paddle.to_tensor(ids),
                                 lengths=np.array(lens),
                                 max_new_tokens=6)

        gen([11, 24, 17])                     # warmup mix
        warm = pred.stats.compiles
        for lens in ([9, 30, 4], [16, 16, 23], [5, 19, 8], [25, 7, 13]):
            gen(lens)
        assert pred.stats.compiles == warm, pred.stats.as_dict()

    def test_paged_pool_size_is_bucketed(self, tiny_model):
        pred = create_predictor(
            Config().set_model(tiny_model).enable_paged_kv(page_size=8))
        import jax.numpy as jnp

        _, P1 = pred._paged_caches([11, 24, 17], 4, 64, 8, jnp.float32)
        _, P2 = pred._paged_caches([9, 30, 4], 4, 64, 8, jnp.float32)
        assert P1 == P2                       # same bucket, same shape
        assert P1 & (P1 - 1) == 0             # power of two


def test_engine_requires_paged_config(tiny_model):
    pred = create_predictor(Config().set_model(tiny_model))
    with pytest.raises(Exception, match="paged"):
        ServingEngine(pred)
