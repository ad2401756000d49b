"""The paged KV cache alone (inference/kv_cache.py): no model runs and no
engine exists here. A model is only asked for the shapes it pools, so a
stand-in with a config (K and V) or a ``kv_pool_shapes`` (a latent and a
rotated key) is all a cache needs.
"""
from types import SimpleNamespace

import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.core.enforce import PreconditionNotMetError
from paddle_tpu.inference.kv_cache import (PagedKVCache, page_bytes,
                                           pool_shapes, with_table,
                                           without_table)

PAGE = 4


def kv_model(layers=2, heads=2, dim=8):
    return SimpleNamespace(config=SimpleNamespace(
        num_layers=layers, num_kv_heads=heads, head_dim=dim))


def latent_model(layers=2, latent=512, rope=128):
    return SimpleNamespace(kv_pool_shapes=lambda P, page: [
        ((P, 1, page, latent), (P, 1, page, rope))] * layers)


MODELS = {"kv": kv_model, "latent": latent_model}


def make(model="kv", **kw):
    kw.setdefault("pool_pages", 8)
    return PagedKVCache(MODELS[model](), PAGE, 32, 2, np.float32, **kw)


def fill(cache, pg, seed):
    """Write a recognisable payload into page ``pg`` of every set."""
    r = np.random.RandomState(seed)
    payload = {"target": [tuple(r.rand(*s[1:]).astype(np.float32)
                                for s in ab) for ab in cache.shapes]}
    if cache.draft_pools is not None:
        payload["draft"] = [tuple(r.rand(*a.shape[1:]).astype(np.float32)
                                  for a in ab) for ab in cache.draft_pools]
    cache.write_page(pg, payload)
    return payload


def same(got, want):
    assert got.keys() == want.keys()
    for name in want:
        for g, w in zip(got[name], want[name]):
            for a, b in zip(g, w):
                np.testing.assert_array_equal(a, b)


# -- geometry -----------------------------------------------------------------
@pytest.mark.parametrize("model, shapes, nbytes", [
    ("kv", ((2, PAGE, 8), (2, PAGE, 8)), 2 * 2 * 2 * PAGE * 8 * 4),
    ("latent", ((1, PAGE, 512), (1, PAGE, 128)), 2 * PAGE * 640 * 4),
])
@pytest.mark.parametrize("want, P", [(None, 32), ("auto", 32), (8, 8),
                                     (9, 16), (3, 8)])
def test_geometry(model, shapes, nbytes, want, P):
    """P on the power-of-two lattice (floor 8; None and "auto" without
    device memory statistics give the most 2 rows of 32 tokens can
    reference, +1 trash page), the trash page last, every table entry on
    it, and both arrays at the shapes the model pools."""
    c = make(model, pool_pages=want)
    assert (c.P, c.trash, c.usable, c.npages) == (P, P - 1, P - 1, 8)
    assert c.tables.shape == (2, 8) and (c.tables == c.trash).all()
    assert [(a.shape, b.shape) for a, b in c.pools] == \
        [((P,) + shapes[0], (P,) + shapes[1])] * 2
    assert c.shapes == pool_shapes(MODELS[model](), P, PAGE)
    assert c.page_bytes == nbytes == page_bytes(MODELS[model](), PAGE,
                                                np.float32)
    assert c.pool_bytes() == P * nbytes
    assert (c.pages_for(1), c.pages_for(4), c.pages_for(5)) == (1, 1, 2)
    assert c.available() == P - 1 and c.counts()["free"] == P - 1
    c.release()
    assert c.pools is None


@pytest.mark.parametrize("model", ["kv", "latent"])
@pytest.mark.parametrize("draft", [False, True])
def test_bind_commit_and_rows(model, draft):
    """bind: per layer (a, b, table), one table BUFFER per layer (the
    pytree is donated; one buffer cannot be donated twice); commit takes
    the arrays back; the draft set has the draft model's shapes over the
    same P; masked rows read all-trash, the extended column is trash."""
    c = make(model, draft=(kv_model(layers=1, dim=16), np.float32)
             if draft else None)
    c.set_row(0, [5, 2])
    c.set_row(1, [4])
    assert c.tables[0].tolist() == [5, 2] + [7] * 6
    caches = c.bind(c.rows(), draft=draft)
    assert len(caches) == (1 if draft else 2)
    assert all(len(t) == 3 for t in caches)
    assert len({id(t[2]) for t in caches}) == len(caches)
    np.testing.assert_array_equal(caches[0][2], c.tables)
    if draft:
        assert caches[0][0].shape == (8, 2, PAGE, 16)
    back = [(a + 1, b + 2, t) for a, b, t in caches]
    c.commit(back, draft=draft)
    pools = c.draft_pools if draft else c.pools
    assert all(p[0] is q[0] and p[1] is q[1] for p, q in zip(pools, back))
    assert (c.pools[0][0] is back[0][0]) != draft   # the other set untouched
    assert c.rows(1).tolist() == [[4] + [7] * 7]
    masked = c.rows(only=[1])
    assert (masked[0] == 7).all() and masked[1, 0] == 4
    assert c.tables[0, 0] == 5                      # a copy was masked
    assert (c.rows(only=[]) == 7).all()
    ext = c.rows(extended=True)
    assert ext.shape == (2, 9) and (ext[:, -1] == 7).all()


def test_counters_ride_what_the_decode_program_is_lent():
    """``lend`` / ``take_back``: the donated part of the decode program,
    no table in it; ``with_table`` / ``without_table`` put the ONE table
    every layer reads in and out inside the program."""
    m = kv_model()
    m.moe_counter_shape = lambda: (2, 5)
    c = PagedKVCache(m, PAGE, 32, 2, np.float32, pool_pages=8)
    assert [n.shape for n in c.counters] == [(5,), (5,)]
    assert all(len(t) == 3 for t in c.bind(c.rows()))   # (a, b, table)
    state = c.lend()
    assert all(len(t) == 3 and t[2].shape == (5,) for t in state)
    table = jnp.asarray(c.rows())
    caches = with_table(state, table)
    assert all(len(t) == 4 and t[2] is table for t in caches)
    back = without_table([t[:3] + (t[3] + 3,) for t in caches])
    c.take_back(back)
    assert [int(n.sum()) for n in c.counters] == [15, 15]
    assert all(p[0] is s[0] and p[1] is s[1]
               for p, s in zip(c.pools, state))
    plain = make()
    assert plain.counters is None
    assert all(len(t) == 2 for t in plain.lend())
    assert all(len(t) == 3 for t in with_table(plain.lend(), table))
    plain.take_back(plain.lend())
    assert plain.counters is None


# -- accounting ---------------------------------------------------------------
@pytest.mark.parametrize("model", ["kv", "latent"])
def test_allocate_pin_release_round_trip(model):
    c = make(model)
    a = c.allocate(3)
    assert a == [6, 5, 4] and [c.refcount(p) for p in a] == [1, 1, 1]
    assert c.available() == 4
    c.check_invariants([a])
    c.pin(a[:1])                            # a second row shares page 6
    assert c.refcount(6) == 2 and c.shared(a) == [0]
    c.check_invariants([a, a[:1]])
    c.release_pages(a)
    assert c.refcount(6) == 1 and c.available() == 6
    c.set_row(1, a[:1])
    c.release_row(1, a[:1])
    assert (c.tables == c.trash).all()
    assert c.available() == 7 and c.counts()["free"] == 7
    c.check_invariants([])
    assert sorted(c.allocate(7)) == list(range(7))   # trash never handed out
    with pytest.raises(PreconditionNotMetError, match="exhausted"):
        c.allocate(1)


def test_register_first_writer_wins_and_match_prefix():
    c = make()
    prompt = np.arange(11)
    hs = c.prefix_hashes(prompt)
    assert len(hs) == 2 and hs == c.prefix_hashes(list(prompt) + [99])[:2]
    assert hs[1] != c.prefix_hashes(np.r_[9, prompt[1:]])[1]   # whole prefix
    a, b, other = c.allocate(3)
    c.register(hs[0], a)
    c.register(hs[0], other)                # hash taken: stays with a
    c.register(hs[1], a)                    # page taken: not published
    assert c.match_prefix(hs) == ([a], 0)
    c.register(hs[1], b)
    assert c.match_prefix(hs) == ([a, b], 0)
    assert c.match_prefix([hs[1]]) == ([b], 0)
    assert c.match_prefix([123, hs[0]]) == ([], 0)
    assert c.prefix_stats()["registered"] == 2
    assert c.shared([a, b, other]) == [0, 1]     # registered = immutable
    c.release_pages([a, b, other])
    assert c.match_prefix(hs) == ([a, b], 2)     # idle, still hit-able
    assert c.counts() == {"free": 5, "idle": 2, "registered": 2,
                          "hit_rate": 0.0,
                          "classes": {"full": {"used": 0, "free": 5}}}
    c.pin([a])
    assert c.match_prefix(hs) == ([a, b], 1) and c.counts()["idle"] == 1
    c.note(lookups=4, hits=1, skipped_tokens=PAGE)
    st = c.prefix_stats()
    assert (st["hit_rate"], st["skipped_tokens"], st["idle_pages"],
            st["registered_pages"]) == (0.25, PAGE, 1, 2)
    c.check_invariants([[a]])


@pytest.mark.parametrize("spill", [0, 2])
def test_lru_reclaim_is_oldest_first(spill):
    """Idle registered pages are reclaimed oldest-first and their hash
    unregistered; with the host tier on the payload is staged and
    ``fault_in`` brings it back, the oldest dropped past the cap."""
    c = make(spill_pages=spill)
    pages = c.allocate(7)
    want = {pg: fill(c, pg, pg) for pg in pages[:3]}
    for i, pg in enumerate(pages[:3]):
        c.register(100 + i, pg)
    for pg in (pages[1], pages[0], pages[2]):        # idle in this order
        c.release_pages([pg])
    assert c.available() == 3 and c.counts()["free"] == 0
    [got] = c.allocate(1)
    assert got == pages[1]
    assert c.match_prefix([101]) == ([], 0)
    assert c.match_prefix([100]) == ([pages[0]], 1)
    assert c.prefix_stats()["reclaimed"] == 1
    assert c.allocate(2) == [pages[0], pages[2]]
    st = c.spill_stats()
    c.check_invariants([pages])
    if not spill:
        assert st == {"spilled": 0, "faulted": 0, "dropped": 0,
                      "host_pages": 0, "host_bytes": 0,
                      "transfer_bytes": {}}
        c.fault_in([100, 101, 102], floor=0)         # nothing to fault
        return
    assert (st["spilled"], st["dropped"], st["host_pages"]) == (3, 1, 2)
    assert st["host_bytes"] == 2 * c.page_bytes
    assert st["transfer_bytes"] == {"d2h": 3 * c.page_bytes}
    c.release_pages(pages[3:])                       # 4 free pages
    c.fault_in([100, 102], floor=4)                  # no room above the floor
    assert c.spill_stats()["faulted"] == 0
    assert c.spill_stats()["host_pages"] == 2        # kept for next time
    assert c.match_prefix([100, 102]) == ([], 0)
    c.fault_in([100, 102], floor=0)
    hits, idle = c.match_prefix([100, 102])
    assert len(hits) == 2 and idle == 2
    same(c.read_page(hits[0]), want[pages[0]])
    same(c.read_page(hits[1]), want[pages[2]])
    st = c.spill_stats()
    assert (st["faulted"], st["host_pages"]) == (2, 0)
    assert st["transfer_bytes"]["h2d"] == 2 * c.page_bytes
    c.check_invariants([pages[:3]])


def test_register_on_the_device_drops_the_host_copy():
    """A hash whose fault-in stopped at the floor is fed again and
    registered device-side: the host copy goes, the tier owns a hash on
    one side only."""
    c = make(spill_pages=2)
    pages = c.allocate(7)
    c.register(7, pages[0])
    c.release_pages(pages[:1])
    c.allocate(1)                                    # reclaims + spills
    c.fault_in([7], floor=10)                        # no room: stays host
    assert c.spill_stats()["host_pages"] == 1
    c.register(7, pages[1])
    assert c.spill_stats()["host_pages"] == 0
    assert c.spill_stats()["dropped"] == 1
    c.check_invariants([pages])


@pytest.mark.parametrize("draft", [False, True])
def test_copy_on_write(draft):
    c = make(draft=(kv_model(dim=16), np.float32) if draft else None)
    [old] = c.allocate(1)
    want = fill(c, old, 3)
    c.pin([old])                                     # shared by two rows
    new = c.copy_on_write(old)
    assert new != old and c.refcount(old) == 1 and c.refcount(new) == 1
    same(c.read_page(new), want)
    same(c.read_page(old), want)
    assert c.prefix_stats()["cow"] == 1
    c.check_invariants([[old], [new]])
    c.warm_copy()
    same(c.read_page(new), want)


def test_page_programs_are_noted_and_dispatched_once_per_set():
    """Every page program goes through the owner's ``dispatch`` under
    the sites and CompileStats keys the engine has always used."""
    sites, notes = [], []
    stats = SimpleNamespace(note=lambda prog, key: notes.append((prog, key)))

    def dispatch(site, fn, *args):
        sites.append(site)
        return fn(*args)

    events = []
    metrics = {"prefix_events": SimpleNamespace(
        inc=lambda n=1, event=None: events.append((event, n)))}
    c = make(draft=(kv_model(layers=1), np.float16), dispatch=dispatch,
             stats=stats, metrics=metrics)
    [a] = c.allocate(1)
    c.copy_page(a, a)
    payload = c.read_page(a)
    c.write_page(a, payload)
    assert sites == [("page_copy",), ("page_copy_draft",), ("page_read",),
                     ("page_read_draft",), ("page_write",),
                     ("page_write_draft",)]
    assert notes[:2] == [("page_copy", ("target", 2, str(np.float32))),
                         ("page_copy", ("draft", 1, str(np.float16)))]
    c.register(1, a)
    c.note(lookups=2, hits=2)
    c.pin([a])
    c.copy_on_write(a)
    assert events == [("registered", 1), ("hit", 2), ("cow", 1)]


# -- a row's export / import ----------------------------------------------------
def test_export_import_reproduces_arrays_and_table_row():
    src, dst = make(), make(pool_pages=16)
    pages = src.allocate(3)
    src.set_row(1, pages)
    want = [fill(src, pg, 10 + pg) for pg in pages[:2]]
    payloads, row = src.export_row(1, pages[:2])
    assert row.tolist() == pages + [src.trash] * 5
    assert [p.shape for p in payloads] == [(4, 2, PAGE, 8)] * 2
    dst.allocate(2)                                  # other ids over there
    got = dst.import_row(0, payloads, 3)
    assert len(got) == 3 and dst.tables[0, :3].tolist() == got
    assert (dst.tables[0, 3:] == dst.trash).all()
    for pg, w in zip(got, want):
        same(dst.read_page(pg), w)
    assert [dst.refcount(p) for p in got] == [1, 1, 1]


@pytest.mark.parametrize("call", ["export", "import", "check"])
def test_a_latent_page_is_not_stacked(call):
    """Two pooled arrays of different shapes cannot migrate as one
    stacked array: refused where a page would be stacked."""
    c = make("latent")
    pages = c.allocate(1)
    with pytest.raises(PreconditionNotMetError, match="latent cache"):
        if call == "export":
            c.export_row(0, pages)
        elif call == "import":
            c.import_row(0, [np.zeros((4, 1, PAGE, 512), np.float32)], 1)
        else:
            c.check_stackable()


# -- the invariant is not a tautology -----------------------------------------
def _double_free(c, held):
    c._free_pages.append(c._free_pages[0])


def _freed_while_held(c, held):
    c.release_pages(held[0][:1])


def _leak(c, held):
    c.allocate(1)


def _drift(c, held):
    c.pin(held[0][:1])


def _trash(c, held):
    c._free_pages.append(c.trash)


def _unregistered_idle(c, held):
    c._lru[c._free_pages.pop()] = None


def _both_tiers(c, held):
    c.register(5, held[0][0])
    c._spilled[5] = {}


@pytest.mark.parametrize("fault, needle", [
    (_double_free, "duplicate pages on the free list"),
    (_freed_while_held, "refcounted pages != pages held"),
    (_leak, "refcounted pages != pages held"),
    (_drift, "refcount drift"),
    (_trash, "trash page entered circulation"),
    (_unregistered_idle, "LRU page not registered"),
    (_both_tiers, "host-spilled"),
])
def test_check_invariants_catches(fault, needle):
    c = make(spill_pages=1)
    held = [c.allocate(2), c.allocate(1)]
    c.check_invariants(held)
    fault(c, held)
    with pytest.raises(PreconditionNotMetError, match=needle):
        c.check_invariants(held)


# -- the state class: one slot a row beside the pages -------------------------
def state_model(counters=True):
    """Five layers: state, none, full, state, none (a state-space mixer,
    an expert layer, an attention layer...): H in float32 and a tail in
    the cache's type a state layer, K and V the one paged layer, and a
    routing counter on the two layers that keep nothing."""
    kv = lambda P, page: ((P, 2, page, 8), (P, 2, page, 8))
    st = (((3, 2, 4), "float32"), ((6,), None))
    m = SimpleNamespace(
        kv_pool_shapes=lambda P, page: [(), (), kv(P, page), (), ()],
        kv_page_classes=lambda: ["state", "none", "full", "state", "none"],
        state_shapes=lambda: [st, (), (), st, ()])
    if counters:
        m.moe_counter_shape = lambda: (2, 5)
        m.moe_counter_layers = lambda: [1, 4]
    return m


def state_cache(**kw):
    return PagedKVCache(state_model(), PAGE, 32, 3, jnp.bfloat16,
                        pool_pages=8, **kw)


def test_state_class_geometry_and_what_rides():
    c = state_cache()
    assert c.state_layers == [True, False, False, True, False]
    assert c.arrays == [2, 0, 2, 2, 0]
    # a slot: 2 layers x (24 float32 + 6 bfloat16), whatever the context
    assert c.state_row_bytes == 2 * (24 * 4 + 6 * 2)
    assert c.state_bytes() == 3 * c.state_row_bytes
    assert [a.dtype for a in c.pools[0]] == [jnp.float32, jnp.bfloat16]
    assert [a.shape for a in c.pools[3]] == [(3, 3, 2, 4), (3, 6)]
    # the paged class counts its pages alone
    assert c.page_bytes == 2 * 2 * PAGE * 8 * 2
    assert c.pool_bytes() == c.P * c.page_bytes
    assert c.counts()["classes"]["state"] == {"used": 0, "free": 3}
    # a prefill is bound the slot it writes in a state layer's table's
    # place, the block table elsewhere; a layer that keeps nothing is
    # its table alone
    c.take_slot(1)
    bound = c.bind(c.rows(1), slots=c.prefill_slots(1))
    assert [len(t) for t in bound] == [3, 1, 3, 3, 1]
    assert np.asarray(bound[0][2]).tolist() == [1]
    assert bound[2][2].shape == (1, c.npages)
    c.commit(bound)
    assert [len(p) for p in c.pools] == [2, 0, 2, 2, 0]
    # the decode program is lent arrays and counters, and the counters
    # ride on the layers the model names
    state = c.lend()
    assert [len(t) for t in state] == [2, 1, 2, 2, 1]
    table = jnp.asarray(c.rows())
    caches = with_table(state, table, c.arrays)
    assert [len(t) for t in caches] == [3, 2, 3, 3, 2]
    assert caches[1][0] is table and caches[0][2] is table
    back = without_table([t[:-1] + (t[-1] + 1,) if len(t) == 2 else t
                          for t in caches], c.arrays)
    c.take_back(back)
    assert [int(n.sum()) for n in c.counters] == [5, 5]
    assert c.pools[0][0] is state[0][0]


def test_counter_layers_must_match_the_counters():
    m = state_model()
    m.moe_counter_layers = lambda: [1]          # two counters, one layer
    c = PagedKVCache(m, PAGE, 32, 3, jnp.bfloat16, pool_pages=8)
    with pytest.raises(PreconditionNotMetError, match="zip would drop"):
        c.lend()
    m = state_model()
    del m.moe_counter_layers                    # five tuples, two counters
    c = PagedKVCache(m, PAGE, 32, 3, jnp.bfloat16, pool_pages=8)
    with pytest.raises(PreconditionNotMetError, match="zip would drop"):
        c.lend()


def test_slots_are_taken_at_admission_and_given_back():
    c = state_cache()
    assert c.slots_available()
    for b in (0, 2, 1):
        assert c.take_slot(b) == b
    assert not c.slots_available()
    with pytest.raises(PreconditionNotMetError, match="slot already"):
        c.take_slot(2)
    c.check_invariants([], live_rows=[0, 1, 2])
    c.release_row(2, [])
    assert c.slots_available()
    assert c.counts()["classes"]["state"] == {"used": 2, "free": 1}
    c.check_invariants([], live_rows=[0, 1])
    assert c.take_slot(2) == 2
    # a cache without state layers never runs out of what it has not
    assert make().slots_available() and make().prefill_slots(0) is None


def _slot_held_twice(c):
    c._slots[1] = c._slots[0]


def _slot_leaked(c):
    c._slots.pop(0)                 # neither free nor any row's


def _slot_of_a_dead_row(c):
    c.take_slot(2)                  # row 2 serves nothing


@pytest.mark.parametrize("fault, needle", [
    (_slot_held_twice, "leaked or doubly held slot"),
    (_slot_leaked, "leaked or doubly held slot"),
    (_slot_of_a_dead_row, "rows holding a slot"),
])
def test_check_invariants_catches_a_slot(fault, needle):
    c = state_cache()
    c.take_slot(0)
    c.take_slot(1)
    c.check_invariants([], live_rows=[0, 1])
    fault(c)
    with pytest.raises(PreconditionNotMetError, match=needle):
        c.check_invariants([], live_rows=[0, 1])


@pytest.mark.parametrize("call, what", [
    (lambda: state_cache(spill_pages=2), "the host spill tier"),
    (lambda: PagedKVCache(state_model(), PAGE, 32, 3, jnp.bfloat16,
                          pool_pages=8,
                          draft=(kv_model(), jnp.bfloat16)),
     "a draft's pools"),
    (lambda: state_cache().copy_on_write(0), "copy-on-write"),
    (lambda: state_cache().check_stackable(), "migration of a row"),
    (lambda: state_cache().refuse_stateful(True, "this"), "this"),
])
def test_state_layers_refuse_what_moves_pages(call, what):
    with pytest.raises(PreconditionNotMetError,
                       match=f"{what}.*state layers.*ONE slot a row"):
        call()
    state_cache().refuse_stateful(False, "this")
    make().refuse_stateful(True, "a cache without state layers")


def test_state_shapes_must_name_the_state_layers():
    m = state_model()
    m.kv_page_classes = lambda: ["state", "none", "full", "none", "none"]
    with pytest.raises(PreconditionNotMetError, match="state_shapes"):
        PagedKVCache(m, PAGE, 32, 3, jnp.bfloat16, pool_pages=8)
    m = state_model()
    m.kv_page_classes = lambda: ["state", "none", "ring", "state", "none"]
    with pytest.raises(PreconditionNotMetError, match="layer's class"):
        PagedKVCache(m, PAGE, 32, 3, jnp.bfloat16, pool_pages=8)
