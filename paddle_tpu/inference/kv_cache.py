"""The paged KV cache: one owner of what a page is.

``PagedKVCache`` holds what the serving scheduler (``serving.py``), the
static-batch ``Predictor`` and the disaggregation layer (``router.py``,
``disagg.py``) need of the physical page pool: its GEOMETRY (page size,
table width, bucketed pool size ``P``, the trash page ``P - 1`` every
unowned table entry maps to, the per-layer shapes of the two pooled
arrays), its device ARRAYS with the block tables (``bind`` lends them to
a compiled program, ``commit`` takes the donated arrays back), the page
ACCOUNTING (free list, refcounts, the hash<->page bijection and LRU of
the prefix cache) under ONE re-entrant lock that lives here and nowhere
else, the PAGE PROGRAMS (read, write, copy; traced page ids), the HOST
SPILL TIER and a row's EXPORT / IMPORT payload. Beside the pages it holds
the second kind of per-request state, for a model with STATE layers (a
state-space mixer's recurrent state): arrays of FIXED size a row, one
slot a row, never paged, never shared (the class docstring).

No jitted dispatch ever runs under the lock: a reclaim only STAGES
``(page, hash)`` for the host tier, and the device read that captures
the payload runs in ``allocate`` after the lock is released and before
the pages are handed out. The cache does not know what a request or a
slot is (``check_invariants`` takes the page lists that are held), and
nothing here imports the scheduler.
"""
from __future__ import annotations

import threading
from collections import Counter, OrderedDict
from typing import Any, Callable, Dict, Iterable, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from ..core.bucketing import bucket as _bucket
from ..core.enforce import enforce
from ..observability import memledger as _ml

__all__ = ["PagedKVCache", "pool_shapes", "page_bytes", "page_classes",
           "state_shapes", "with_table", "without_table"]


def pool_shapes(model, P: int, page: int):
    """Per layer, the shapes of the arrays the page pool holds for it,
    ``[P, cache heads, page, width]`` each: two (K and V; a latent and a
    rotated key of a latent-attention model) or more (a layer whose
    attention selects keys by a learned index pools its index keys as a
    third). A model says so itself (``kv_pool_shapes(P, page)``);
    without that it is K and V of ``num_kv_heads x head_dim``."""
    fn = getattr(model, "kv_pool_shapes", None)
    if fn is not None:
        return [tuple(tuple(a) for a in layer) for layer in fn(P, page)]
    cfg = model.config
    shape = (P, cfg.num_kv_heads, page, cfg.head_dim)
    return [(shape, shape)] * cfg.num_layers


def page_classes(model) -> Tuple[List[bool], Optional[int]]:
    """(per layer: is it a window layer, the window or None). A model
    says so itself (``kv_page_classes()``: per layer ``"full"`` — a row
    holds a page for every page of its context — or ``("window", n)`` —
    it only ever reads its last ``n`` positions); without that every
    layer is full. A layer may also name ``"state"`` (it keeps arrays
    of fixed size a row and no page: ``state_shapes``) or ``"none"`` (it
    keeps nothing); neither is a window layer."""
    fn = getattr(model, "kv_page_classes", None)
    if fn is None:
        return [False] * len(pool_shapes(model, 1, 1)), None
    classes = list(fn())
    enforce(all(isinstance(c, tuple) or c in ("full", "state", "none")
                for c in classes),
            'a layer\'s class is "full", ("window", n), "state" or '
            f'"none": the model names {classes}')
    windows = {c[1] for c in classes if isinstance(c, tuple)}
    enforce(len(windows) <= 1,
            f"one window class a model: its layers name {sorted(windows)}")
    return [isinstance(c, tuple) for c in classes], \
        (int(windows.pop()) if windows else None)


def state_shapes(model, dtype) -> List[tuple]:
    """Per layer, ``((shape a row, dtype), ..)`` of the arrays a STATE
    layer keeps, ``()`` of every other layer. A model says so itself
    (``state_shapes()``, a dtype of None meaning the cache's); without
    that no layer keeps any. The layers that do are those
    ``kv_page_classes()`` names ``"state"``, and they pool no page."""
    fn = getattr(model, "state_shapes", None)
    n = len(pool_shapes(model, 1, 1))
    if fn is None:
        return [()] * n
    out = [tuple((tuple(int(d) for d in shape), jnp.dtype(dt or dtype))
                 for shape, dt in layer) for layer in fn()]
    classes = list(model.kv_page_classes())
    enforce(len(out) == n and all(
        bool(layer) == (c == "state") and not (layer and pools)
        for layer, c, pools in zip(out, classes,
                                   pool_shapes(model, 1, 1))),
        "state_shapes() names arrays for exactly the layers "
        'kv_page_classes() calls "state", and those pool no page')
    return out


def page_bytes(model, page: int, dtype, window: bool = False) -> int:
    """Bytes one page takes over the pooled arrays of every layer of
    its class (``window``: the window layers; else the full ones, which
    without ``kv_page_classes`` is every layer)."""
    mask, _ = page_classes(model)
    return sum(int(np.prod(a))
               for layer, w in zip(pool_shapes(model, 1, page), mask)
               if w == window for a in layer) * np.dtype(dtype).itemsize


def _payload_nbytes(payload) -> int:
    return sum(int(a.nbytes) for rows in payload.values()
               for kv in rows for a in kv)


class PagedKVCache:
    """One fixed-size physical page pool for ``max_batch`` table rows of
    ``max_length`` tokens, with its allocator.

    ``pool_pages``: pages wanted, the trash page included (bucketed to a
    power of two, floor 8: compiled programs are keyed on the pool
    shape); ``None`` = the most the batch can ever reference;
    ``"auto"`` = what fits the device's free memory beside
    ``resident_bytes``, capped at that. ``draft=(model, dtype)`` adds
    the second array set. ``dispatch(site, fn, *args)`` runs a page
    program (the engine's ledger capture; default a plain call),
    ``stats`` is the ``CompileStats`` the programs are noted on and
    ``metrics`` the serving instrument set the prefix and spill events
    are counted on — both optional: a cache alone counts in
    ``prefix_stats()`` / ``spill_stats()`` only.

    TWO CLASSES OF PAGES for a model with window layers
    (``page_classes``). The full class is everything above: the pool
    ``P``, ``tables``, the allocator. The window class has its own
    arrays (only the window layers have them, ``[Pw, ...]``), its own
    page ids ``0 .. Pw - 2`` with a trash page ``Pw - 1``, its own free
    list, and a ``[B, ring]`` table, ``ring = ceil(window / page) + 1``:
    a row takes its ring at admission (``take_ring``), keeps it for life
    and gives it back in ``release_row``; position ``pos`` lives in ring
    column ``(pos // page) % ring``, over what was ``ring`` pages back,
    which the window no longer reaches. ``Pw = B * ring + 1`` always (a
    row of the batch can never be short of a ring). A program is bound
    the table of each layer's class (``bind`` / ``layer_tables``). The
    prefix cache, the spill tier, copy-on-write, export / import and a
    draft's pools stay full-class only and are REFUSED for such a model
    (``refuse_windowed``): a page shared or moved at position p would
    need the window layers' last keys at p, which the ring has
    overwritten.

    THE STATE CLASS for a model with state layers (``state_shapes``): a
    state layer's arrays are ``[B, ...]`` in their own dtypes, row b the
    SLOT of table row b (so a decode step updates them in place, row
    for row, with no gather), taken at admission (``take_slot``) and
    given back in ``release_row``. They ride ``bind`` / ``lend`` /
    ``take_back`` / ``commit`` donated, in the layer's tuple where a
    paged layer has its pools, and a prefill is bound the slots it
    writes in the table's place. A layer that keeps NOTHING has an empty
    tuple, and a model's device counters ride on the layers it names
    (``moe_counter_layers()``; every layer without that). What shares,
    spills, copies or moves pages is REFUSED for such a model
    (``refuse_stateful``): the state at the page's position is gone.
    """

    def __init__(self, model, page: int, max_length: int, max_batch: int,
                 dtype, pool_pages=None, resident_bytes: int = 0,
                 spill_pages: int = 0, draft: Optional[Tuple] = None,
                 dispatch: Optional[Callable] = None, stats=None,
                 metrics: Optional[Dict[str, Any]] = None):
        self.page = int(page)
        self.npages = -(-int(max_length) // self.page)
        self.B = int(max_batch)
        self.dtype = dtype
        self.page_bytes = page_bytes(model, self.page, dtype)
        # the window class: fixed by the batch, never sized from memory
        self.window_layers, self.window = page_classes(model)
        self.ring = self.Pw = self.window_page_bytes = 0
        if self.window:
            self.ring = -(-self.window // self.page) + 1
            self.Pw = self.B * self.ring + 1
            self.window_page_bytes = page_bytes(model, self.page, dtype,
                                                window=True)
            self.refuse_windowed(spill_pages, "the host spill tier")
            self.refuse_windowed(draft is not None, "a draft's pools")
            resident_bytes += self.Pw * self.window_page_bytes
        # the state class: fixed by the batch too, a slot a row
        self.state = state_shapes(model, dtype)
        self.state_layers = [bool(layer) for layer in self.state]
        self.state_row_bytes = sum(
            int(np.prod(shape)) * dt.itemsize
            for layer in self.state for shape, dt in layer)
        if self.state_row_bytes:
            self.refuse_stateful(spill_pages, "the host spill tier")
            self.refuse_stateful(draft is not None, "a draft's pools")
            resident_bytes += self.B * self.state_row_bytes
        # one pool for the owner's whole lifetime, on the power-of-two
        # bucket lattice: the compiled programs are keyed on this shape
        # and NEVER change it. "auto" sizes it from measured HBM
        # headroom (memledger.suggest_pool_pages: bytes_limit minus the
        # resident params, 10% margin) capped at the geometric maximum
        # the batch can ever reference; backends without memory stats
        # (the CPU harness) fall back to the geometric default.
        geom = self.B * self.npages + 1
        if pool_pages == "auto":
            fit = _ml.suggest_pool_pages(jax.devices()[0],
                                         self.page_bytes, resident_bytes)
            want = min(fit, geom) if fit else geom
        else:
            want = pool_pages or geom
        self.P = _bucket(int(want), lo=8)
        self.trash = self.P - 1
        self.shapes = pool_shapes(model, self.P, self.page)
        if self.window:
            self.shapes = [
                tuple((self.Pw,) + a[1:] for a in layer) if w else layer
                for layer, w in zip(self.shapes, self.window_layers)]
        self.shapes = [
            tuple((self.B,) + shape for shape, _ in st) if st else layer
            for layer, st in zip(self.shapes, self.state)]
        # arrays a layer keeps: where its table sits in a cache tuple
        self.arrays = [len(layer) for layer in self.shapes]
        self.pools = [
            tuple(jnp.zeros(a, dt) for a, (_, dt) in zip(layer, st)) if st
            else tuple(jnp.zeros(a, dtype) for a in layer)
            for layer, st in zip(self.shapes, self.state)]
        self.draft_pools = None
        self.draft_dtype = None
        if draft is not None:
            dmodel, self.draft_dtype = draft
            self.draft_pools = [
                tuple(jnp.zeros(a, self.draft_dtype) for a in layer)
                for layer in pool_shapes(dmodel, self.P, self.page)]
        # device counters a model keeps beside its pools (the routing
        # counters of an expert model): one small int32 array per
        # layer, donated to the decode program with the caches
        cshape = getattr(model, "moe_counter_shape", None)
        self.counters = None
        if cshape is not None:
            layers, *row = cshape()
            self.counters = [jnp.zeros(row, jnp.int32)
                             for _ in range(layers)]
        # the layers whose tuple a counter rides on: the model's own
        # list, or every layer in order
        named = getattr(model, "moe_counter_layers", None)
        self.counter_layers = list(named()) if named is not None \
            else list(range(len(self.pools)))
        self.tables = np.full((self.B, self.npages), self.trash, np.int32)
        self.wtrash = self.Pw - 1
        self.wtables = np.full((self.B, self.ring), self.wtrash, np.int32)
        self._wfree = list(range(self.Pw - 1))
        self._rings: Dict[int, List[int]] = {}      # table row -> ring
        # state slots: slot b belongs to table row b and to no other
        self._sfree = list(range(self.B)) if self.state_row_bytes else []
        self._slots: Dict[int, int] = {}            # table row -> slot
        # Pages become ref-counted and content-addressable. _hash_page
        # maps the rolling prompt-prefix hash of a COMPLETED
        # page-aligned chunk to the physical page that holds its KV;
        # _page_hash is the inverse; _lru keeps registered pages whose
        # refcount dropped to 0 (still hit-able, reclaimed oldest-first
        # under pool pressure).
        self._lock = threading.RLock()
        self._free_pages = list(range(self.P - 1))
        self._refcount = [0] * self.P
        self._hash_page: Dict[int, int] = {}
        self._page_hash: Dict[int, int] = {}
        self._lru: "OrderedDict[int, None]" = OrderedDict()
        self._pfx = {"lookups": 0, "hits": 0, "cow": 0, "reclaimed": 0,
                     "registered": 0, "skipped_tokens": 0,
                     "fed_tokens": 0}
        # host memory tier (distributed/host_offload.py is the
        # training-side twin): up to spill_pages reclaimed prefix-cache
        # pages keep their payload in host memory, keyed by the SAME
        # rolling prefix hash, and fault back through the normal
        # admission path (one page allocation + one page write, then
        # registered + idle so the hit run pins it like any cached
        # page). A hash's KV lives device-side OR host-side, never both.
        self.spill_pages = int(spill_pages or 0)
        self._spilled: "OrderedDict[int, Any]" = OrderedDict()
        self._spill_pending: List[Tuple[int, int]] = []
        self._spill_ledger: Dict[Tuple[str, str], int] = {}
        self._spill_counts = {"spilled": 0, "faulted": 0, "dropped": 0}
        self._dispatch = dispatch or (lambda _site, fn, *a: fn(*a))
        self._stats = stats
        self._metrics = metrics
        read, write, copy = _page_programs()
        self._page_read = jax.jit(read)
        self._page_write = jax.jit(write, donate_argnums=(0,))
        self._page_copy = jax.jit(copy, donate_argnums=(0,))

    # -- geometry --------------------------------------------------------
    @property
    def usable(self) -> int:
        """Pages that can ever be allocated (the trash page cannot)."""
        return self.P - 1

    def pages_for(self, tokens: int) -> int:
        return -(-tokens // self.page)

    def pool_bytes(self, window: Optional[bool] = None) -> int:
        """Bytes of the PAGED arrays: every layer's, or one class's."""
        return sum(_ml.shard_bytes(a)
                   for layer, w, st in zip(self.pools, self.window_layers,
                                           self.state_layers)
                   if not st and (window is None or w == window)
                   for a in layer)

    def state_bytes(self) -> int:
        """Bytes of the state layers' arrays, every slot."""
        return sum(_ml.shard_bytes(a)
                   for layer, st in zip(self.pools, self.state_layers)
                   if st for a in layer)

    def release(self) -> None:
        """Give the device arrays back; the cache serves nothing after."""
        self.pools = self.draft_pools = None

    def refuse_windowed(self, asked, what: str) -> None:
        """``what`` works on full-class pages alone: refused, with the
        reason, for a model that has window layers."""
        enforce(not (self.window and asked),
                f"{what} cannot serve a model with window layers: its "
                f"window layers keep a ring of {self.ring} pages a row, "
                f"the last {self.window} positions and no more, so a page "
                "shared, spilled, copied or moved at position p would "
                "need the window layers' keys up to p, which the ring "
                "has overwritten")

    def refuse_stateful(self, asked, what: str) -> None:
        """``what`` needs a row's state at a position other than its
        last: refused, with the reason, for a model with state layers."""
        enforce(not (self.state_row_bytes and asked),
                f"{what} cannot serve a model with state layers: a state "
                f"layer keeps ONE slot a row ({self.state_row_bytes} "
                "bytes over its layers), the recurrent state after the "
                "row's last position and no other, so a page shared, "
                "spilled, copied or moved at position p would need the "
                "state at p, which the slot no longer holds")

    # -- the state class: one slot a row ---------------------------------
    def slots_available(self) -> bool:
        """Whether one more row can take a slot (always, without state
        layers)."""
        with self._lock:
            return bool(self._sfree) or not self.state_row_bytes

    def take_slot(self, b: int) -> int:
        """Table row b takes its slot, slot b, for life. Nothing is
        cleared here: the row's prefill writes the slot whole."""
        with self._lock:
            enforce(b not in self._slots and b in self._sfree,
                    f"table row {b} holds its state slot already")
            self._sfree.remove(b)
            self._slots[b] = b
        return b

    def prefill_slots(self, b: int) -> Optional[np.ndarray]:
        """What a prefill of table row b is bound in a state layer's
        table's place: the slot it writes, ``[1]``. None without state
        layers."""
        if not self.state_row_bytes:
            return None
        return np.asarray([self._slots[b]], np.int32)

    # -- the window class: a ring of pages a row -------------------------
    def rings_available(self) -> bool:
        """Whether one more row can take its ring (always, without
        window layers)."""
        with self._lock:
            return len(self._wfree) >= self.ring

    def take_ring(self, b: int) -> List[int]:
        """Table row b takes its ring of window pages, for life."""
        with self._lock:
            enforce(b not in self._rings and len(self._wfree) >= self.ring,
                    f"table row {b} holds a ring already, or the window "
                    "class is out of pages")
            ring = [self._wfree.pop() for _ in range(self.ring)]
            self._rings[b] = ring
        self.wtables[b, :] = ring
        return ring

    def window_prefill_rows(self, b: int, length: int
                            ) -> Optional[np.ndarray]:
        """The LOGICAL table ``[1, npages]`` a prefill of ``length``
        tokens writes row b's window layers through: the prompt's last
        ``ring`` pages map to their ring columns, every other page (the
        older ones the window has left, the bucket's padding) to the
        trash page. None without window layers."""
        if not self.window:
            return None
        out = np.full((1, self.npages), self.wtrash, np.int32)
        last = (length - 1) // self.page
        for l in range(max(0, last - self.ring + 1), last + 1):
            out[0, l] = self.wtables[b, l % self.ring]
        return out

    # -- tables, bind / commit -------------------------------------------
    def set_row(self, b: int, pages: List[int]) -> None:
        """Table row b maps ``pages`` in order, every other entry the
        trash page."""
        self.tables[b, :] = self.trash
        self.tables[b, :len(pages)] = pages

    def rows(self, b: Optional[int] = None, only=None,
             extended: bool = False) -> np.ndarray:
        """The block tables a program is bound to: row ``b`` alone
        (``[1, npages]``) or all of them; with ``only``, every row NOT
        listed reads all-trash (a row that rides a round it must not
        write in); ``extended`` adds the model's ``valid`` contract, one
        trailing column that ALWAYS maps to the trash page (dead-slot
        and overdraft writes land there; attention slices it back
        off)."""
        tbl = self.tables if b is None else self.tables[b:b + 1]
        if only is not None:
            tbl = np.full_like(self.tables, self.trash)
            tbl[only] = self.tables[only]
        if extended:
            tbl = np.concatenate(
                [tbl, np.full((len(tbl), 1), self.trash, np.int32)],
                axis=1)
        return tbl

    def window_rows(self, only=None) -> np.ndarray:
        """The ring tables ``[B, ring]`` (``[B, 0]`` without window
        layers); with ``only``, every row NOT listed reads all-trash, as
        in ``rows``."""
        if only is None:
            return self.wtables
        tbl = np.full_like(self.wtables, self.wtrash)
        tbl[only] = self.wtables[only]
        return tbl

    def bind(self, rows: np.ndarray, draft: bool = False,
             wrows: Optional[np.ndarray] = None,
             slots: Optional[np.ndarray] = None) -> List[tuple]:
        """The per-layer ``(a, b[, c], table)`` tuples a compiled
        program takes whole: ``rows`` for a full layer, ``wrows`` for a
        window layer, ``slots`` (the slots written; the rows' own in
        order where not given) for a state layer. One table upload per
        layer: the cache pytree is
        DONATED to the program, and XLA rejects donating one buffer
        twice. A program that runs every round takes ``lend()`` and ONE
        table of its own instead (``with_table``)."""
        if draft:
            return [layer + (jnp.asarray(rows),)
                    for layer in self.draft_pools]
        if slots is None and self.state_row_bytes:
            slots = np.arange(len(rows), dtype=np.int32)
        return [layer + (jnp.asarray(
            slots if st else wrows if w else rows),)
            for layer, w, st in zip(self.pools, self.window_layers,
                                    self.state_layers)]

    def layer_tables(self, table, wtable):
        """Inside a traced program: per layer, the table of its class."""
        return [wtable if w else table for w in self.window_layers]

    def commit(self, caches: List[tuple], draft: bool = False) -> None:
        """Take back what ``bind`` lent, as the program returned it."""
        pools = [tuple(c[:len(have)]) for c, have in zip(
            caches, self.draft_pools if draft else self.pools)]
        if draft:
            self.draft_pools = pools
        else:
            self.pools = pools

    def lend(self) -> List[tuple]:
        """What a program that brings its own table is given to donate:
        per layer its pooled arrays, ``+ (counter,)`` for a model that
        keeps device counters beside its pools. No table, so no
        upload."""
        if self.counters is None:
            return list(self.pools)
        on = self.counter_layers
        enforce(len(on) == len(self.counters)
                and all(0 <= i < len(self.pools) for i in on)
                and len(set(on)) == len(on),
                f"{len(self.pools)} pooled tuples and "
                f"{len(self.counters)} device counters, to ride on layers "
                f"{on}: a model keeps "
                "one counter a tuple of kv_pool_shapes() (a layer of two "
                "attentions two) or names the tuples that carry one "
                "(moe_counter_layers()), and a zip would drop the longer "
                "list's tail in silence")
        out = list(self.pools)
        for i, n in zip(on, self.counters):
            out[i] = out[i] + (n,)
        return out

    def take_back(self, state: List[tuple]) -> None:
        """Take back what ``lend`` lent, as the program returned it."""
        self.pools = [tuple(s[:n]) for s, n in zip(state, self.arrays)]
        if self.counters is not None:
            self.counters = [state[i][self.arrays[i]]
                             for i in self.counter_layers]

    # -- page accounting (ref-counted pool + prefix cache) ---------------
    def available(self) -> int:
        """Pages the allocator can produce right now: the free list
        plus idle registered pages the LRU would yield."""
        with self._lock:
            return len(self._free_pages) + len(self._lru)

    def allocate(self, n: int) -> List[int]:
        """Pop n pages at refcount 1 — free list first, then reclaim
        idle cached pages oldest-first. Callers check ``available``.
        Reclaims staged for host spill are drained here AFTER the lock
        is released and BEFORE the pages are handed out: the payload is
        still intact (nothing writes a page between reclaim and its
        next prefill dispatch) and the device read never holds the
        lock."""
        with self._lock:
            out = []
            for _ in range(n):
                if not self._free_pages:
                    self._reclaim()
                pg = self._free_pages.pop()
                self._refcount[pg] = 1
                out.append(pg)
            pending = bool(self._spill_pending)
        if pending:
            self._drain_spills()
        return out

    def _reclaim(self):
        """Evict the oldest idle cached page: unregister its hash and
        return it to the free list (the cache yields under pressure).
        With the host tier on, the (page, hash) pair is staged so
        ``allocate`` captures the payload host-side after release."""
        with self._lock:
            enforce(self._lru, "page pool exhausted: allocator asked "
                    "to reclaim with no idle cached pages")
            pg, _ = self._lru.popitem(last=False)
            h = self._page_hash.pop(pg)
            del self._hash_page[h]
            self._pfx["reclaimed"] += 1
            self._event("reclaimed")
            if self.spill_pages:
                self._spill_pending.append((pg, h))
            self._free_pages.append(pg)

    def pin(self, pages: List[int]) -> None:
        """Take one reference on each cached page (an admission hit);
        an idle page leaves the LRU — it is live again."""
        with self._lock:
            for pg in pages:
                self._refcount[pg] += 1
                if self._refcount[pg] == 1:
                    self._lru.pop(pg, None)

    def release_pages(self, pages: List[int]) -> None:
        """Drop one reference per page. A registered page that idles
        parks on the LRU tail (still hit-able); an unregistered one
        goes straight back to the free list."""
        with self._lock:
            for pg in pages:
                self._refcount[pg] -= 1
                if self._refcount[pg] > 0:
                    continue
                if pg in self._page_hash:
                    self._lru[pg] = None
                else:
                    self._free_pages.append(pg)

    def release_row(self, b: int, pages: List[int]) -> None:
        """Evict table row b: its pages released (and its ring of window
        pages and its state slot, if it holds them), the row all-trash.
        The slot's arrays are left as they are: the next request's
        prefill writes them whole."""
        self.release_pages(pages)
        self.set_row(b, [])
        with self._lock:
            ring = self._rings.pop(b, None)
            if ring is not None:
                self._wfree.extend(ring)
            slot = self._slots.pop(b, None)
            if slot is not None:
                self._sfree.append(slot)
        if ring is not None:
            self.wtables[b, :] = self.wtrash

    def register(self, h: int, pg: int) -> None:
        """Publish a completed page under its prefix hash. First
        writer wins: a page already registered (or a hash already
        mapped) stays as-is, so the maps remain a bijection."""
        with self._lock:
            if h in self._hash_page or pg in self._page_hash:
                return
            self._hash_page[h] = pg
            self._page_hash[pg] = h
            self._pfx["registered"] += 1
            # a prompt fed again from scratch (its fault-in stopped at
            # the floor) lands here with a host copy still kept: the
            # tier owns a hash on ONE side, and the device's is newer
            if self._spilled.pop(h, None) is not None:
                self._spill_counts["dropped"] += 1
        self._event("registered")

    def prefix_hashes(self, prompt) -> List[int]:
        """Rolling hash per FULL page-aligned prompt chunk: h_j covers
        prompt[:(j+1)*page], so equal hashes mean equal whole
        prefixes — a hit run is always a shared prefix, never a
        shared interior."""
        page = self.page
        arr = np.ascontiguousarray(np.asarray(prompt, np.int64))
        out: List[int] = []
        h = hash(("paddle_tpu_prefix", page))
        for j in range(len(arr) // page):
            h = hash((h, arr[j * page:(j + 1) * page].tobytes()))
            out.append(h)
        return out

    def match_prefix(self, hashes: List[int]) -> Tuple[List[int], int]:
        """The pages of the longest leading run of ``hashes`` the
        device holds, and how many of them are idle: those count toward
        ``available`` but a hit is about to pin them."""
        hits: List[int] = []
        with self._lock:
            for h in hashes:
                pg = self._hash_page.get(h)
                if pg is None:
                    break
                hits.append(pg)
            idle = sum(1 for pg in hits if self._refcount[pg] == 0)
        return hits, idle

    def shared(self, pages: List[int]) -> List[int]:
        """Indices into ``pages`` of those a write must not land in:
        held by another row (refcount > 1) or registered in the prefix
        cache (immutable)."""
        with self._lock:
            return [i for i, pg in enumerate(pages)
                    if self._refcount[pg] > 1 or pg in self._page_hash]

    def refcount(self, pg: int) -> int:
        with self._lock:
            return self._refcount[pg]

    def note(self, **deltas: int) -> None:
        """Add to the prefix-cache counters what only the caller sees
        (lookups / hits / skipped_tokens at admission, fed_tokens)."""
        with self._lock:
            for k, v in deltas.items():
                self._pfx[k] += v
        if deltas.get("hits"):
            self._event("hit", deltas["hits"])

    def _event(self, event: str, n: int = 1):
        if self._metrics is not None:
            self._metrics["prefix_events"].inc(n, event=event)

    def counts(self) -> Dict[str, Any]:
        """The occupancy gauges' counts, from one look under the lock:
        the full class at the top, and ``classes`` with pages used and
        free of each class the cache has."""
        with self._lock:
            lk = self._pfx["lookups"]
            free = len(self._free_pages)
            out = {"free": free, "idle": len(self._lru),
                   "registered": len(self._page_hash),
                   "hit_rate": self._pfx["hits"] / lk if lk else 0.0,
                   "classes": {"full": {
                       "used": self.usable - free - len(self._lru),
                       "free": free}}}
            if self.window:
                wfree = len(self._wfree)
                out["classes"]["window"] = {
                    "used": self.Pw - 1 - wfree, "free": wfree}
            if self.state_row_bytes:
                out["classes"]["state"] = {
                    "used": len(self._slots), "free": len(self._sfree)}
            return out

    def prefix_stats(self) -> Dict[str, Any]:
        """Host-side prefix-cache counters: page lookups/hits at
        admission, prompt tokens skipped vs fed, copy-on-writes, LRU
        reclaims, plus the current registered/idle page counts."""
        with self._lock:
            out = dict(self._pfx)
            out["hit_rate"] = (out["hits"] / out["lookups"]
                               if out["lookups"] else 0.0)
            out["registered_pages"] = len(self._page_hash)
            out["idle_pages"] = len(self._lru)
            return out

    def check_invariants(self, held_pages: Iterable[List[int]],
                         live_rows: Optional[Iterable[int]] = None) -> None:
        """Pool-accounting invariant (the free-list hardening gate):
        free list, idle (LRU) pages, and refcounted live pages
        partition the usable pool exactly; every page's refcount
        equals the number of page lists in ``held_pages`` (one per
        live row) holding it; the hash<->page maps stay bijective.
        With window layers: the free window pages and the rows' rings
        partition that class, a row's ring is what its table row names,
        and (given ``live_rows``, the table rows in use) exactly those
        rows hold a ring. With state layers: the free slots and the
        rows' slots partition the slots, a row's slot is its own, and
        (given ``live_rows``) exactly those rows hold one. Raises on any
        violation — double free, leak, or refcount drift."""
        held = Counter(pg for pages in held_pages for pg in pages)
        with self._lock:
            bad: List[str] = []
            if self.state_row_bytes:
                taken = list(self._slots.values())
                if sorted(taken + self._sfree) != list(range(self.B)):
                    bad.append(
                        f"state class: free({len(self._sfree)}) + held"
                        f"({len(taken)}) do not partition its {self.B} "
                        "slots (a leaked or doubly held slot)")
                if any(b != slot for b, slot in self._slots.items()):
                    bad.append("a row holds another row's state slot")
                if live_rows is not None and \
                        set(live_rows) != set(self._slots):
                    bad.append(
                        f"rows holding a slot {sorted(self._slots)} != "
                        f"rows in use {sorted(set(live_rows))}")
            if self.window:
                rung = [pg for r in self._rings.values() for pg in r]
                if sorted(rung + self._wfree) != list(range(self.Pw - 1)):
                    bad.append(
                        f"window class: free({len(self._wfree)}) + rings"
                        f"({len(rung)}) do not partition its "
                        f"{self.Pw - 1} pages (a leaked or doubly held "
                        "ring page)")
                if any(list(self.wtables[b]) != r
                       for b, r in self._rings.items()):
                    bad.append("a window table row is not its row's ring")
                if live_rows is not None and \
                        set(live_rows) != set(self._rings):
                    bad.append(
                        f"rows holding a ring {sorted(self._rings)} != "
                        f"rows in use {sorted(set(live_rows))}")
            usable = self.P - 1
            free, lru = list(self._free_pages), list(self._lru)
            fs, ls = set(free), set(lru)
            live = {pg for pg in range(usable) if self._refcount[pg] > 0}
            if len(fs) != len(free):
                bad.append("duplicate pages on the free list")
            if self.trash in fs | ls | live:
                bad.append("trash page entered circulation")
            if fs & ls or fs & live or ls & live:
                bad.append("free/idle/live page sets overlap")
            if len(free) + len(lru) + len(live) != usable:
                bad.append(f"free({len(free)}) + idle({len(lru)}) + "
                           f"live({len(live)}) != pool({usable})")
            if set(held) != live:
                bad.append("refcounted pages != pages held by slots")
            drift = {pg: (int(c), self._refcount[pg])
                     for pg, c in held.items()
                     if self._refcount[pg] != c}
            if drift:
                bad.append(f"refcount drift (held, rc): {drift}")
            if len(self._hash_page) != len(self._page_hash) or \
                    set(self._page_hash) != set(self._hash_page.values()):
                bad.append("prefix hash maps out of sync")
            if not ls <= set(self._page_hash):
                bad.append("LRU page not registered in the cache")
            if set(self._spilled) & set(self._hash_page):
                bad.append("hash both device-registered and host-"
                           "spilled (the tier owns a hash exclusively)")
            if len(self._spilled) > max(self.spill_pages, 0):
                bad.append(f"host tier over its cap: "
                           f"{len(self._spilled)} > {self.spill_pages}")
            enforce(not bad,
                    "serving pool invariant violated: " + "; ".join(bad))

    # -- the page programs -----------------------------------------------
    def _sets(self):
        """(name, arrays, dtype) of every array set, target first."""
        out = [("target", self.pools, self.dtype)]
        if self.draft_pools is not None:
            out.append(("draft", self.draft_pools, self.draft_dtype))
        return out

    def _run(self, prog: str, name: str, fn, pools, dtype, *args):
        if self._stats is not None:
            self._stats.note(prog, (name, len(pools), str(dtype)))
        site = (prog,) if name == "target" else (f"{prog}_{name}",)
        return self._dispatch(site, fn, pools, *args)

    def read_page(self, pg: int) -> Dict[str, list]:
        """One page of every array set, copied host-side: per set, per
        layer, the page's two rows."""
        src = jnp.asarray(pg, jnp.int32)
        out = {}
        for name, pools, dtype in self._sets():
            rows = self._run("page_read", name, self._page_read, pools,
                             dtype, src)
            out[name] = [tuple(np.asarray(r) for r in kv) for kv in rows]
        return out

    def write_page(self, pg: int, payload: Dict[str, list]) -> None:
        """The inverse of ``read_page``: every set the payload carries."""
        dst = jnp.asarray(pg, jnp.int32)
        for name, pools, dtype in self._sets():
            if name not in payload:
                continue
            rows = [tuple(jnp.asarray(a) for a in kv)
                    for kv in payload[name]]
            self.commit(self._run("page_write", name, self._page_write,
                                  pools, dtype, rows, dst),
                        draft=name == "draft")

    def copy_page(self, src: int, dst: int) -> None:
        """Copy one physical page in every array set (the sets share
        page ids). A trash-page self-copy is a no-op write that
        pre-compiles the program."""
        s = jnp.asarray(src, jnp.int32)
        d = jnp.asarray(dst, jnp.int32)
        for name, pools, dtype in self._sets():
            self.commit(self._run("page_copy", name, self._page_copy,
                                  pools, dtype, s, d),
                        draft=name == "draft")

    def warm_copy(self) -> None:
        """Compile the page-copy program(s) ahead of the first real
        copy-on-write."""
        self.copy_page(self.trash, self.trash)

    def copy_on_write(self, old: int) -> int:
        """A private copy of a shared page (device-side copy into a
        freshly allocated page); the reference on the shared original
        is dropped. Returns the new page."""
        self.refuse_windowed(True, "copy-on-write of a shared page")
        self.refuse_stateful(True, "copy-on-write of a shared page")
        [new] = self.allocate(1)
        self.copy_page(old, new)
        self.release_pages([old])
        self.note(cow=1)
        self._event("cow")
        return new

    # -- host spill tier (the serving face of distributed/host_offload) --
    def _note_spill(self, direction: str, nbytes: int):
        """Book one ledger entry and republish the offload gauges.
        Cumulative totals as GAUGES (set, not inc) — the same contract
        as the training tier, so the closed-form cross-check reads one
        number per (component, direction)."""
        with self._lock:
            k = ("kv_page", direction)
            self._spill_ledger[k] = self._spill_ledger.get(k, 0) + nbytes
            host = sum(_payload_nbytes(p) for p in self._spilled.values())
            vals = dict(self._spill_ledger)
            npages = len(self._spilled)
        m = self._metrics
        if m is None:
            return
        for (comp, d), v in vals.items():
            m["offload_bytes"].set(v, component=comp, direction=d)
        m["offload_host"].set(host, component="kv_page")
        m["offload_spilled_pages"].set(npages)

    def _drain_spills(self):
        """Capture staged reclaim payloads host-side (d2h). Runs with
        the lock RELEASED; the staged pages sit on the free list or in
        the caller's fresh allocation, unwritten until the next
        compiled dispatch, so the read is race-free."""
        with self._lock:
            pending, self._spill_pending = self._spill_pending, []
        for pg, h in pending:
            payload = self.read_page(pg)
            with self._lock:
                self._spilled[h] = payload
                self._spill_counts["spilled"] += 1
                dropped = 0
                while len(self._spilled) > self.spill_pages:
                    self._spilled.popitem(last=False)
                    dropped += 1
                self._spill_counts["dropped"] += dropped
            self._note_spill("d2h", _payload_nbytes(payload))

    def fault_in(self, hashes: List[int], floor: int) -> None:
        """Fault host-spilled prefix pages back onto the device ahead
        of admission: extend the DEVICE hit run of ``hashes`` with
        spilled ones by allocating one page each (normal accounting —
        the allocation may itself reclaim/spill colder pages), writing
        the payload back, and registering the page idle so the hit run
        pins it like any cached page. Stops while more than ``floor``
        pages stay available."""
        with self._lock:
            if not self._spilled:
                return
        for h in hashes:
            with self._lock:
                if h in self._hash_page:
                    continue          # device run keeps extending
                payload = self._spilled.pop(h, None)
            if payload is None:
                return                # run over: neither cached nor spilled
            if self.available() <= floor:
                with self._lock:      # keep it host-side for next time
                    self._spilled[h] = payload
                    self._spilled.move_to_end(h, last=False)
                return
            [pg] = self.allocate(1)
            self.write_page(pg, payload)
            self.register(h, pg)
            self.release_pages([pg])      # idle + registered: hit-able
            with self._lock:
                self._spill_counts["faulted"] += 1
            self._note_spill("h2d", _payload_nbytes(payload))

    def spill_stats(self) -> Dict[str, Any]:
        """Host-tier counters: pages spilled/faulted/dropped, resident
        host bytes, and the cumulative transfer ledger per direction."""
        with self._lock:
            out = dict(self._spill_counts)
            out["host_pages"] = len(self._spilled)
            out["host_bytes"] = sum(_payload_nbytes(p)
                                    for p in self._spilled.values())
            out["transfer_bytes"] = {d: v for (_c, d), v
                                     in self._spill_ledger.items()}
            return out

    # -- a row's export / import (disaggregated serving) -----------------
    def check_stackable(self) -> None:
        """A page migrates as ONE stacked array of every layer's pooled
        arrays; pools of several shapes cannot be stacked."""
        what = ("the migration of a row's pages (export / import, the "
                "disaggregated phases)")
        self.refuse_windowed(True, what)
        self.refuse_stateful(True, what)
        shapes = sorted({a[1:] for layer in self.shapes for a in layer})
        enforce(len(shapes) == 1,
                "the disaggregated phases migrate a page as ONE stacked "
                "array of every layer's pooled arrays; this model pools "
                f"{max(self.arrays)} arrays a layer, of the shapes "
                + " and ".join(map(str, shapes)) + " (a latent cache, or "
                "a layer's index keys beside its K and V), so run it on "
                "unified replicas (phase=None)")

    def export_row(self, b: int, pages: List[int]
                   ) -> Tuple[List[np.ndarray], np.ndarray]:
        """The payloads of ``pages`` (read through the compiled
        page-read program — traced src index, so exports never
        recompile) and a copy of table row b. Each payload is one
        ``[arrays, heads, page, width]`` array (every layer's pooled
        arrays in order, layer by layer)."""
        self.check_stackable()
        payloads = [np.stack([a for kv in
                              self.read_page(pg)["target"]
                              for a in kv])
                    for pg in pages]
        return payloads, self.tables[b].copy()

    def import_row(self, b: int, payloads: List[np.ndarray],
                   n_pages: int) -> List[int]:
        """Allocate ``n_pages`` for table row b and write ``payloads``
        into the first of them through the compiled page-write program
        (traced dst index — imports never recompile). Callers check
        ``available``."""
        self.check_stackable()
        pages = self.allocate(n_pages)
        cuts = np.cumsum(self.arrays)[:-1]
        for pg, arr in zip(pages, payloads):
            self.write_page(pg, {"target": [
                tuple(layer) for layer in np.split(arr, cuts)]})
        self.set_row(b, pages)
        return pages


def with_table(state: List[tuple], table,
               arrays: Optional[List[int]] = None) -> List[tuple]:
    """Inside a traced program: the per-layer ``(a, b[, c], table[,
    counter])`` tuples ``model.forward`` takes, put together from what
    ``lend`` lent and the ONE table every layer reads (or a list, the
    table of each layer's class: ``PagedKVCache.layer_tables``).
    ``arrays``: how many arrays each layer pools
    (``PagedKVCache.arrays``; two each where not given): the table
    follows them."""
    arrays = arrays or [2] * len(state)
    tables = table if isinstance(table, list) else [table] * len(state)
    return [tuple(s[:n]) + (t,) + tuple(s[n:])
            for s, t, n in zip(state, tables, arrays)]


def without_table(caches: List[tuple],
                  arrays: Optional[List[int]] = None) -> List[tuple]:
    """The inverse: what the program hands back for ``take_back``."""
    arrays = arrays or [2] * len(caches)
    return [tuple(c[:n]) + tuple(c[n + 1:])
            for c, n in zip(caches, arrays)]


def _page_programs():
    """The page read, write and copy programs (jitted per cache, the
    pools donated to the two that write). src/dst page ids are TRACED
    scalars (dynamic slice in/out), so every (src, dst) pair reuses the
    same executable — a Python-side ``.at[dst].set(pool[src])`` would
    recompile per pair."""

    def read(pools, src):
        return jax.tree_util.tree_map(
            lambda a: lax.dynamic_index_in_dim(a, src, axis=0,
                                               keepdims=False),
            pools)

    def write(pools, rows, dst):
        return jax.tree_util.tree_map(
            lambda a, r: lax.dynamic_update_slice_in_dim(
                a, r[None], dst, axis=0),
            pools, rows)

    def copy(pools, src, dst):
        def one(a):
            row = lax.dynamic_index_in_dim(a, src, axis=0,
                                           keepdims=True)
            return lax.dynamic_update_slice_in_dim(a, row, dst,
                                                   axis=0)

        return jax.tree_util.tree_map(one, pools)

    return read, write, copy
