"""One decode round in flight (inference/serving.py, PR 30).

``ServingEngine.step()`` launches decode round k and only then retires
round k-1: the one blocking fetch of a round happens while the device
already runs the next. Under test, on the CPU with a tiny llama and a
tiny latent-attention expert decoder:

- greedy tokens are ``Predictor.generate``'s, request by request, with
  admissions landing mid-decode, with
  ``decode_chunk`` 1 and 4, and in chunked / prefix / spill /
  speculative engines;
- an ``eos_token_id`` hit is found a round late: one wasted round, its
  token dropped, the pool's accounting intact;
- every drain point leaves no round in flight and the host's view
  current: ``export_request`` / ``import_request``,
  ``_preempt_youngest``, ``release_pools``, ``moe_stats``,
  ``run(max_steps=n)``;
- a launch is ONE upload (the round array) and the decode program
  donates what the cache lent, not that array;
- ``overlap_stats()`` and the two instruments count what happened.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.inference import Config, ServingEngine, create_predictor
from paddle_tpu.models.llama import LlamaForCausalLM, llama_tiny
from paddle_tpu.models.mla_moe import MLAMoEForCausalLM, mla_moe_tiny
from paddle_tpu.observability.catalog import serving_metrics

PAGE = 8


class Family:
    """A tiny model, its paged predictor and the greedy reference,
    remembered per (prompt, n)."""

    def __init__(self, name):
        paddle.seed(11)
        self.name = name
        self.model = (LlamaForCausalLM(llama_tiny()) if name == "llama"
                      else MLAMoEForCausalLM(mla_moe_tiny()))
        self.model.eval()
        self.vocab = self.model.config.vocab_size
        self._ref = {}

    def predictor(self, temperature=0.0, seed=0):
        cfg = Config().set_model(self.model).enable_paged_kv(page_size=PAGE)
        cfg.generation.temperature = temperature
        cfg.generation.seed = seed
        return create_predictor(cfg)

    def reference(self, prompt, n):
        """The first n greedy tokens after ``prompt``, decoded alone by
        ``Predictor.generate``: no engine, no round."""
        key = (tuple(int(t) for t in prompt), n)
        if key not in self._ref:
            out = self.predictor().generate(
                paddle.to_tensor(np.asarray(prompt)[None]),
                max_new_tokens=n)
            self._ref[key] = [int(t) for t in np.asarray(
                out._value)[0, len(prompt):]]
        return self._ref[key]

    def prompts(self, lens, seed=0):
        r = np.random.RandomState(seed)
        return [r.randint(1, self.vocab, (L,)) for L in lens]


_FAMILIES = {}


def family(name):
    if name not in _FAMILIES:
        _FAMILIES[name] = Family(name)
    return _FAMILIES[name]


@pytest.fixture(scope="module", params=["llama", "mla_moe"])
def fam(request):
    return family(request.param)


@pytest.fixture(scope="module")
def llama():
    return family("llama")


def settled(eng):
    st = eng.overlap_stats()
    assert st["in_flight"] == 0, st
    return st


# -- the same tokens ----------------------------------------------------------
@pytest.mark.parametrize("chunk", [1, 4])
def test_arrivals_mid_decode_decode_as_alone(fam, chunk):
    """Requests that join while a round is in flight enter through the
    round's host token column; rows already in the batch are fed from
    the device. Every request gets the reference's tokens."""
    eng = ServingEngine(fam.predictor(), max_batch=3, decode_chunk=chunk,
                        debug_invariants=True)
    a, b, c, d, e = fam.prompts([9, 5, 13, 7, 11], seed=chunk)
    want = {}
    for p, n in ((a, 10), (b, 9)):
        want[eng.submit(p, max_new_tokens=n)] = (p, n)
    for _ in range(3):
        eng.step()
    assert eng.overlap_stats()["in_flight"] == 1     # a round is out
    want[eng.submit(c, max_new_tokens=6)] = (c, 6)   # lands mid-decode
    eng.step()
    eng.step()
    for p, n in ((d, 7), (e, 1)):                    # queue behind a full batch
        want[eng.submit(p, max_new_tokens=n)] = (p, n)
    done = eng.run()
    assert sorted(done) == sorted(want)
    for rid, (p, n) in want.items():
        assert done[rid].new_tokens == fam.reference(p, n), rid
    st = settled(eng)
    assert st["rounds"] > 2 and 0 < st["overlapped"] < st["rounds"]
    assert eng.cache.counts()["free"] == eng.cache.usable
    assert (eng.cache.tables == eng.cache.trash).all()


def test_step_launches_one_round_and_retires_the_one_before(fam):
    """The ``step()`` contract: a round's tokens reach ``new_tokens``
    one call later; a slot whose last round is out is ``finishing``, not
    ``decode``; a call with nothing to launch retires what is out."""
    [p] = fam.prompts([10], seed=3)
    ref = fam.reference(p, 3)
    eng = ServingEngine(fam.predictor(), max_batch=2)
    rid = eng.submit(p, max_new_tokens=3)
    eng.step()                      # prefill (token 0), launch round 0
    slot = eng.slots[0]
    assert slot.req.new_tokens == ref[:1] and slot.state == "decode"
    assert eng.overlap_stats()["in_flight"] == 1
    eng.step()                      # launch round 1 (the last), retire 0
    assert slot.req.new_tokens == ref[:2] and slot.state == "finishing"
    assert eng.num_active == 1 and rid not in eng.finished
    eng.step()                      # nothing to launch: retire round 1
    assert eng.finished[rid].new_tokens == ref
    assert eng.num_active == 0 and eng.finished[rid].t_finish > 0
    st = settled(eng)
    assert (st["rounds"], st["overlapped"]) == (2, 1)


@pytest.mark.parametrize("chunk", [1, 4])
def test_eos_is_found_a_round_late(fam, chunk):
    """The row rides one round more than it needed: that round's token
    is dropped, its K/V write lands in a page the row held at the
    launch, and the pool's accounting holds (``debug_invariants``
    re-checks it at every finish)."""
    a, b = fam.prompts([7, 12], seed=5)
    ref_a, ref_b = fam.reference(a, 8), fam.reference(b, 12)
    k = next(i for i in range(2, 8) if ref_a[i] not in ref_a[:i])
    alone = ServingEngine(fam.predictor(), max_batch=2, decode_chunk=chunk,
                          debug_invariants=True)
    rid = alone.submit(a, max_new_tokens=8, eos_token_id=ref_a[k])
    done = alone.run()
    assert done[rid].new_tokens == ref_a[:k + 1]
    needed = -(-k // chunk)           # rounds that bring tokens 1..k
    assert settled(alone)["rounds"] == needed + 1    # and one wasted
    assert alone.cache.counts()["free"] == alone.cache.usable
    # with a neighbour mid-decode and a request waiting for the slot
    eng = ServingEngine(fam.predictor(), max_batch=2, decode_chunk=chunk,
                        debug_invariants=True)
    ra = eng.submit(a, max_new_tokens=8, eos_token_id=ref_a[k])
    rb = eng.submit(b, max_new_tokens=12)
    rc = eng.submit(a, max_new_tokens=5)
    done = eng.run()
    assert done[ra].new_tokens == ref_a[:k + 1]
    assert done[rb].new_tokens == ref_b
    assert done[rc].new_tokens == ref_a[:5]
    settled(eng)
    assert eng.cache.counts()["free"] == eng.cache.usable


@pytest.mark.parametrize("kw", [
    {"prefill_chunk": 16},
    {"prefill_chunk": 16, "prefix_cache": True},
    {"prefill_chunk": 16, "prefix_cache": True, "host_spill_pages": 4,
     "pool_pages": 16},
    {"prefill_chunk": 16, "prefix_cache": True, "spec_tokens": 3},
], ids=["chunked", "prefix", "spill", "speculative"])
def test_chunked_engines_overlap_only_runs_of_decode_rounds(llama, kw):
    """Chunked mode drains before every unified round and before it
    reserves, copies or spills a page; only its runs of pure-decode
    rounds overlap (none in speculative mode, whose decode rows always
    take the unified verify step). Tokens as decoded alone."""
    kw = dict(kw)
    if "spec_tokens" in kw:
        kw["draft_predictor"] = llama.predictor()
    eng = ServingEngine(llama.predictor(), max_batch=3,
                        debug_invariants=True, **kw)
    r = np.random.RandomState(9)
    sysp = r.randint(1, llama.vocab, (3 * PAGE,))
    prompts = [np.concatenate([sysp, r.randint(1, llama.vocab, (n,))])
               for n in (3, 9, 1, 6)] + [sysp] + llama.prompts([5, 21], 4)
    want = {}
    for i, p in enumerate(prompts):
        n = 5 + i % 4
        want[eng.submit(p, max_new_tokens=n)] = (p, n)
        if i % 2:
            eng.step()
            eng.step()
    done = eng.run()
    for rid, (p, n) in want.items():
        assert done[rid].new_tokens == llama.reference(p, n), rid
    st = settled(eng)
    if "spec_tokens" in kw:
        assert st["rounds"] == 0
    else:
        assert st["overlapped"] > 0
    eng.check_invariants()


def test_sampled_streams_repeat_from_the_seed(fam):
    """With a temperature the key advances inside the decode program:
    the stream is another than earlier versions drew, and the same from
    the same seed."""
    def serve(seed):
        eng = ServingEngine(fam.predictor(temperature=0.9, seed=seed),
                            max_batch=2, decode_chunk=2)
        rids = [eng.submit(p, max_new_tokens=9)
                for p in fam.prompts([6, 11, 8], seed=6)]
        done = eng.run()
        return [done[r].new_tokens for r in rids]

    first = serve(3)
    assert serve(3) == first
    assert serve(4) != first
    assert all(len(t) == 9 and max(t) < fam.vocab for t in first)


# -- drain points -------------------------------------------------------------
def in_flight_engine(fam, n_new=12, **kw):
    """An engine three steps into two requests: a round is out."""
    eng = ServingEngine(fam.predictor(), max_batch=2, **kw)
    reqs = [(p, n_new) for p in fam.prompts([9, 14], seed=7)]
    rids = [eng.submit(p, max_new_tokens=n) for p, n in reqs]
    for _ in range(3):
        eng.step()
    assert eng.overlap_stats()["in_flight"] == 1
    return eng, rids, reqs


def test_run_with_max_steps_leaves_nothing_in_flight(fam):
    eng, rids, reqs = in_flight_engine(fam)
    part = eng.run(max_steps=2)
    assert not part and settled(eng)["rounds"] == 5
    for s, (p, n) in zip(eng.slots, reqs):
        # the prefill's token and one a round launched, all of them read
        assert s.req.new_tokens == fam.reference(p, n)[:6]
    done = eng.run()
    for rid, (p, n) in zip(rids, reqs):
        assert done[rid].new_tokens == fam.reference(p, n)
    settled(eng)


def test_release_pools_retires_the_round_first(fam):
    eng, rids, reqs = in_flight_engine(fam)
    eng.release_pools()
    settled(eng)
    assert eng.pools is None
    for s, (p, n) in zip(eng.slots, reqs):
        assert s.req.new_tokens == fam.reference(p, n)[:4]


def test_moe_stats_counts_every_launched_round():
    fam = family("mla_moe")
    eng, _, _ = in_flight_engine(fam, decode_chunk=2)
    st = eng.moe_stats()
    rounds = settled(eng)["rounds"]
    assert rounds == 3
    # every row of the batch is routed in every step of every round
    assert st["tokens"].tolist() == [0] + [rounds * 2 * eng.B] * 2   # dense, 2 expert layers
    assert st["dropped"] == 0
    done = eng.run()
    assert all(len(r.new_tokens) == 12 for r in done.values())


def test_preempt_retires_the_round_first(llama):
    """A page-starved chunked engine: the youngest mid-prefill row
    bounces (the breaker drains first), everything finishes exactly."""
    eng = ServingEngine(llama.predictor(), max_batch=2, prefill_chunk=16,
                        pool_pages=7, debug_invariants=True)
    prompts = llama.prompts([6 * PAGE - 3, 6 * PAGE - 5], seed=8)
    preempted = serving_metrics()["requests"].value(event="preempted")
    rids = [eng.submit(p, max_new_tokens=3) for p in prompts]
    done = eng.run()
    assert serving_metrics()["requests"].value(event="preempted") > preempted
    for rid, p in zip(rids, prompts):
        assert done[rid].new_tokens == llama.reference(p, 3)
    settled(eng)
    # and the breaker itself, called with a round out
    eng, _, _ = in_flight_engine(llama, prefill_chunk=16)
    eng._preempt_youngest()
    settled(eng)


def test_export_and_import_retire_the_round_first(llama):
    """A decode replica adopts a second row while its first is mid-decode
    with a round out: the import drains, the adopted row enters the next
    round through the host token column, both decode as alone."""
    peng = ServingEngine(llama.predictor(), max_batch=2, prefill_chunk=16,
                         phase="prefill")
    deng = ServingEngine(llama.predictor(), max_batch=2, prefill_chunk=16,
                         phase="decode", debug_invariants=True)
    prompts = llama.prompts([13, 19], seed=10)
    local = []
    for p in prompts:
        peng.submit(p, max_new_tokens=9)
        while not peng.migratable():
            peng.step()
        pkg = peng.export_request(peng.migratable()[0])
        settled(peng)
        local.append(deng.import_request(pkg))
        assert local[-1] is not None
        settled(deng)                       # drained by the import
        for _ in range(3):
            deng.step()
        assert deng.overlap_stats()["in_flight"] == 1
    done = deng.run()
    for rid, p in zip(local, prompts):
        assert done[rid].new_tokens == llama.reference(p, 9)
    assert settled(deng)["overlapped"] > 0


# -- what a launch costs, what the program is given ---------------------------
def test_a_round_is_one_upload_and_one_dispatch(fam, monkeypatch):
    """In a run of decode rounds the host uploads ONE array a round (the
    tables, pos, host token and mask together), splits no key, and the
    cache binds no table."""
    eng, _, _ = in_flight_engine(fam, n_new=20)
    uploads, splits = [], []
    real_asarray, real_split = jnp.asarray, jax.random.split

    def asarray(a, *args, **kw):
        if isinstance(a, np.ndarray):
            uploads.append(a.shape)
        return real_asarray(a, *args, **kw)

    monkeypatch.setattr(jnp, "asarray", asarray)
    monkeypatch.setattr(jax.random, "split",
                        lambda *a, **kw: splits.append(1) or
                        real_split(*a, **kw))
    monkeypatch.setattr(eng.cache, "bind", None)    # not called at all
    before = eng.overlap_stats()["rounds"]
    for _ in range(5):
        eng.step()
    assert eng.overlap_stats()["rounds"] == before + 5
    assert uploads == [(eng.B, eng.cache.npages + 3)] * 5
    assert not splits


def test_decode_program_donates_what_it_was_lent_not_the_round(fam):
    eng, _, _ = in_flight_engine(fam)
    eng.run()
    fn, avals = eng._site_programs[("decode",)]
    pvals, state, round_, tok_prev, rng = avals
    assert round_.shape == (eng.B, eng.cache.npages + 3)
    assert round_.dtype == jnp.int32 and tok_prev.shape == (eng.B,)
    layers = len(eng.pools)
    per_layer = 2 + (eng.cache.counters is not None)
    assert [len(s) for s in state] == [per_layer] * layers
    donated = eng.donated_params(eng.compiled_text(("decode",)))
    assert len(donated) == per_layer * layers
    assert all(n.startswith("state") for n in donated), donated
    assert fn.__name__ == "step"        # the benchmark finds `jit_step`


def test_round_instruments_count_what_overlap_stats_counts(fam):
    m = serving_metrics()
    base = {k: m["rounds"].value(overlapped=k) for k in ("true", "false")}
    waits = m["fetch_wait"].count()
    eng, _, _ = in_flight_engine(fam)
    eng.run()
    st = settled(eng)
    got = {k: m["rounds"].value(overlapped=k) - base[k]
           for k in ("true", "false")}
    assert got == {"true": st["overlapped"],
                   "false": st["rounds"] - st["overlapped"]}
    assert m["fetch_wait"].count() - waits == st["rounds"]
    assert st["overlapped_share"] == st["overlapped"] / st["rounds"] > 0.5
    assert st["fetch_wait_p50_s"] >= 0.0
    snap = eng.metrics_snapshot()["metrics"]
    assert "paddle_tpu_serving_rounds_total" in snap
    assert "paddle_tpu_serving_fetch_wait_seconds" in snap
