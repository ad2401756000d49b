"""Continuous-batching serving engine over the ragged paged KV cache.

The ``Predictor`` serves one batch per ``generate()`` call: every row
starts and finishes together (static batching), and the physical page
pool is sized per call. This module adds the traffic-grade layer the
reference serves with block_multi_head_attention + its serving runtime
(reference capability: llm.predictor / fused blha continuous batching;
design per the Ragged Paged Attention paper in PAPERS.md — ONE compiled
program for arbitrary length mixes):

- ``ServingEngine`` schedules over ONE fixed-size physical page pool
  (``PagedKVCache``, inference/kv_cache.py: its shape never changes for
  the engine's lifetime) with a host-side page free list. Requests are
  admitted into B slots of an in-flight batch; a request's pages are
  popped from the free list at admission and pushed back at
  completion — eviction + backfill, not drain-and-refill.
- PREFILL runs per arrival at [1, Sb] with Sb on the same power-of-two
  bucket lattice as the Predictor, writing straight into the arrival's
  pages through its block-table row (right-pad writes land in the
  shared trash page).
- DECODE is one shared compiled step for the whole batch: [B, 1] tokens
  at per-row offsets against the shared pool. Free slots ride along
  with an all-trash table row (their writes land in the trash page,
  their outputs are ignored) so the program shape is ALWAYS
  (B, pool_bucket) — admissions and evictions never change a compiled
  shape. ``decode_chunk`` fuses that many decode steps into one
  ``lax.scan`` launch; admission/eviction happens at chunk boundaries.
- ONE DECODE ROUND IS IN FLIGHT: ``step()`` launches round k and only
  then retires round k-1 (the one blocking fetch), so the device runs a
  round while the host finishes rows, returns to its caller, admits and
  prepares the next. The program carries its own feed — the last token
  of a round and the sampling key stay on the device, a row new to the
  batch enters through a host token column — and a round's host inputs
  travel as one array. A row's position and the tokens it has left are
  known from counts alone, so only an ``eos_token_id`` hit is found a
  round late (one wasted round, its token dropped). Whoever needs the
  host's view current retires the round first (``_drain``).

CHUNKED PREFILL (``prefill_chunk``, the Ragged Paged Attention design):
the per-arrival prefill program above head-of-line-blocks every decode
row for the length of the longest arriving prompt. With a chunk size
set, prompts are instead split into <= Sc-token chunks (Sc power-of-two
bucketed, a multiple of the page size) and folded into ONE unified
compiled step of fixed shape [B, Sc]: every row carries host-side
``(kind, start, seq_len)`` metadata — a prefill row feeds its next
chunk (seq_len <= Sc), a decode row its last sampled token
(seq_len = 1), an idle row nothing (seq_len = 0) — and the attention
inside is the ragged paged kernel (ops/pallas/ragged_paged_attention),
whose per-row DMA frontier makes the one program's HBM traffic come in
at or below the old two-program sum. A token-budget policy
(``prefill_token_budget``) caps prefill tokens per step so decode rows
always advance: the worst-case inter-token stall under a long-prompt
arrival drops from one full prefill to one chunk round. Rounds with no
chunk to feed fall back to the cheap fused [B, 1] decode scan — both
programs live on the same fixed lattice, so the zero-recompile
guarantee is unchanged. Pages are reserved INCREMENTALLY per chunk
(admission needs only the first chunk's pages; the decode tail is
reserved before the last chunk feeds), so a long prompt no longer
hoards pages it cannot use yet; a page-starved engine preempts the
youngest mid-prefill row (no tokens sampled yet — restart is exact)
back to the queue head rather than deadlock.

PREFIX CACHE (``prefix_cache=True``, chunked mode): chunk frontiers
land exactly on page boundaries (Sc is a multiple of the page size),
so a completed page holds the KV of one page-aligned prompt chunk and
nothing else. Physical pages become ref-counted and content-addressed:
a rolling hash per page-aligned prompt chunk keys completed pages, and
admission maps the longest cached prefix straight into the new slot's
block table (refcount++, ZERO copies, zero FLOPs — the Ragged Paged
Attention indirection makes a shared page addressable from any row).
``_plan_chunks`` then starts prefill at the first cold chunk, so
fleets sharing a system prompt skip its prefill entirely. Registered
pages are IMMUTABLE; page-aligned frontiers mean the only write that
can ever land in a hit page is the full-prompt-hit refeed of the last
prompt token, which copy-on-writes that one page first (one compiled
dynamic-slice copy program, traced src/dst — no recompiles). Release
paths (_finish / _preempt_youngest) decrement refcounts; idle cached
pages park on an LRU the allocator reclaims under pool pressure, so
the cache yields memory before anything stalls.

SPECULATIVE DECODING (``draft_predictor`` + ``spec_tokens=k``, greedy
chunked mode): a small draft model proposes k greedy tokens per decode
row (one fused [B, 1]-step scan against draft KV pools that SHARE the
engine's page tables and allocator — prefix hits and CoW cover the
draft for free), and ONE verify dispatch on the existing unified
[B, Sc] lattice scores all k+1 positions per row (argmax at every
slot instead of the last — same shapes, zero new program geometries).
The host accepts the longest proposal prefix that matches the
target's own greedy argmax chain and commits accepted+1 tokens per
round, so decode needs ~1/(accepted+1) of the device rounds while the
committed ids stay BIT-IDENTICAL to plain greedy decode (each
committed token equals the target argmax given exactly the committed
history — acceptance only reorders when positions are scored, never
what they are conditioned on).

Compile stability: every program is keyed on the small fixed lattice
(batch B, seq bucket Sb, pool bucket P). After one warmup mix, a stream
with arbitrary length mixes triggers ZERO additional XLA compiles —
asserted via the shared ``CompileStats`` counters (``engine.stats``),
and statically by ``tools/tpulint`` (host-sync-in-jit +
recompile-hazard): every int reaching a ``*_fn`` factory here is either
``_bucket``-quantized (Sb, P) or an engine-lifetime constant (B, M,
chunk, k), and the host syncs (first-token sample, chunk readback,
accept loop) sit outside the compiled scan.
"""
from __future__ import annotations

import re
import time
from collections import deque
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Tuple

import jax
import numpy as np
import jax.numpy as jnp
from jax import lax

from ..core.enforce import enforce
from ..observability import commledger as _cl
from ..observability import memledger as _ml
from ..observability import moestats as _moestats
from ..observability.catalog import serving_metrics as _serving_metrics
from ..observability.spans import (RequestTrace, SpanRing,
                                   format_traceparent as
                                   _format_traceparent,
                                   parse_traceparent as
                                   _parse_traceparent)
from ..observability.trace import span as _span
from ..tensor import Tensor
from .kv_cache import (PagedKVCache, page_classes, state_shapes,
                       with_table, without_table)

__all__ = ["ServingEngine", "ServingRequest"]

# how long after a shed health() keeps reporting "degraded"
DEGRADED_WINDOW_S = 30.0


@dataclass
class ServingRequest:
    """One serving request and (once finished) its result."""

    rid: int
    prompt: np.ndarray                   # [L] int prompt tokens
    max_new_tokens: int
    eos_token_id: Optional[int] = None
    new_tokens: List[int] = field(default_factory=list)
    # telemetry timestamps (perf_counter domain): TTFT = t_first_token
    # - t_submit; TPOT = (t_finish - t_first_token) / (n_tokens - 1)
    t_submit: float = 0.0
    t_first_token: float = 0.0
    t_finish: float = 0.0
    # graceful degradation: why this request was load-shed (None =
    # served normally); admission deadline in the t_submit clock domain
    shed_reason: Optional[str] = None
    deadline: Optional[float] = None
    # W3C trace identity (observability/spans.py): trace_id spans
    # processes, span_id is this request's root span in THIS engine,
    # parent_span_id the submitting caller's span elsewhere
    trace_id: Optional[str] = None
    span_id: Optional[str] = None
    parent_span_id: Optional[str] = None

    @property
    def shed(self) -> bool:
        return self.shed_reason is not None

    @property
    def traceparent(self) -> Optional[str]:
        """The ``00-<trace_id>-<span_id>-01`` header downstream work
        on this request should carry (None before submit stamps the
        identity)."""
        if self.trace_id is None or self.span_id is None:
            return None
        return _format_traceparent(self.trace_id, self.span_id)

    @property
    def output_ids(self) -> np.ndarray:
        """prompt + generated tokens (the Predictor.generate layout)."""
        return np.concatenate([np.asarray(self.prompt, np.int64),
                               np.asarray(self.new_tokens, np.int64)])


# retired decode rounds the engine keeps (``ServingEngine.rounds``)
ROUNDS_KEPT = 4096


class _Slot:
    """Host-side state of one in-flight batch row."""

    __slots__ = ("req", "pages", "pos", "state", "fed", "chunks", "seq",
                 "hashes", "registered", "hit_pages", "inflight",
                 "first_round", "last_round")

    def __init__(self, req: ServingRequest, pages: List[int],
                 state: str = "decode", seq: int = 0):
        self.req = req
        self.pages = pages
        # cache position the NEXT decode input token is written at
        self.pos = len(req.prompt)
        # chunked-prefill scheduler state: "prefill" while prompt
        # tokens remain unfed, then "decode"; legacy (unchunked) slots
        # are born "decode" because admission prefills synchronously.
        # "finishing" once the round that brings the row's last token
        # is launched: it rides no further round and waits for that
        # one to be retired
        self.state = state
        # tokens of launched decode rounds the host has not read yet
        self.inflight = 0
        self.fed = 0            # prompt tokens already written
        self.chunks = 0         # chunks fed (span/telemetry index)
        self.seq = seq          # admission order (scheduler fairness)
        # prefix-cache bookkeeping: rolling hash per page-aligned
        # prompt chunk (None with the cache off), how many leading
        # pages are already registered/shared, and how many arrived
        # as cache hits at admission
        self.hashes: Optional[List[int]] = None
        self.registered = 0
        self.hit_pages = 0
        # indices of the first and the last round the row rode (the
        # request's ``decode`` span carries them at eviction)
        self.first_round: Optional[int] = None
        self.last_round: Optional[int] = None

    def rode(self, index: int):
        if self.first_round is None:
            self.first_round = index
        self.last_round = index


@dataclass
class _Round:
    """One launched decode round the host has not read yet: the device
    array of its tokens, and per row in it ``(b, slot, tokens it
    takes)``."""

    toks: Any
    rows: List[tuple]
    t0: float
    index: int


class ServingEngine:
    """Continuous batching over a Predictor with a paged KV cache.

    >>> pred = create_predictor(Config().set_model(m).enable_paged_kv(64))
    >>> eng = ServingEngine(pred, max_batch=8)
    >>> rid = eng.submit(prompt_ids, max_new_tokens=64)
    >>> done = eng.run()          # {rid: ServingRequest}
    >>> done[rid].output_ids

    ``submit`` only queues; ``step()`` runs one admission + decode round
    (the unit a serving loop would tick), ``run()`` drains everything.

    The ``step()`` contract: it LAUNCHES one decode round and RETIRES
    the one launched by the call before, so a round's tokens reach
    ``req.new_tokens`` one call later. A slot whose last round is out
    reads ``state == "finishing"`` (never ``"decode"`` with tokens to
    come) and comes free when that round is retired; a ``step()`` with
    nothing to launch retires what is in flight, and ``run()`` leaves
    nothing in flight, whether it ran to the end or to ``max_steps``.
    The drain rule for new code: anything that reads or reshuffles
    slots, pages or device counters while a round may be outstanding
    calls ``_drain()`` first. Greedy streams are what a synchronous
    engine returns; sampled streams (temperature > 0) repeat from the
    seed but differ from versions that split the key on the host.

    The engine is the scheduler: the queue, the slots and the rounds.
    What a page is — geometry, device arrays, tables, allocator, prefix
    map, spill tier, page programs — belongs to ``self.cache``
    (``inference/kv_cache.py``), and the engine's own code is
    single-threaded: the one lock is the cache's. Public surface that
    callers outside the package hold the engine to (``benchmarks/``
    reads it): ``P``, ``slots`` (``pos``, ``req.new_tokens``, ``state``),
    ``queue``, ``finished``, ``num_active``, ``stats``, ``submit``,
    ``step``, ``run``, ``request_traces()``, ``program_sites()``,
    ``lowered_text(site)``, ``moe_stats()``, ``selection_stats()``,
    ``overlap_stats()``, ``release_pools()``, and
    ``pools``, which may be ASSIGNED ``None`` to free the device arrays.
    """

    def __init__(self, predictor, max_batch: Optional[int] = None,
                 pool_pages=None, decode_chunk: int = 1,
                 trace_ring: int = 256, mem_ledger: bool = False,
                 max_queue: Optional[int] = None,
                 admission_deadline_s: Optional[float] = None,
                 prefill_chunk: Optional[int] = None,
                 prefill_token_budget: Optional[int] = None,
                 prefix_cache: bool = False,
                 draft_predictor=None, spec_tokens: int = 0,
                 host_spill_pages: int = 0,
                 phase: Optional[str] = None,
                 debug_invariants: bool = False):
        import inspect
        import os
        import weakref

        from . import _bucket

        cfg = predictor.config
        enforce(cfg._kv_page_size,
                "ServingEngine serves over the paged KV cache; call "
                "Config.enable_paged_kv(page_size) before "
                "create_predictor")
        self.pred = predictor
        self.page = int(cfg._kv_page_size)
        mcfg = predictor._model.config
        self.M = int(cfg.max_length or mcfg.max_position_embeddings)
        self.B = int(max_batch or cfg.max_batch_size)
        enforce(self.B >= 1 and decode_chunk >= 1,
                "max_batch and decode_chunk must be >= 1")
        self.chunk = int(decode_chunk)
        # chunked prefill: prompts feed the unified [B, Sc] step in
        # <= Sc-token chunks; Sc lives on the shared power-of-two
        # lattice AND is a multiple of the page size (bucket with
        # lo=page gives both), so chunk frontiers land on page
        # boundaries and the compiled shape never varies
        self.chunked = prefill_chunk is not None
        # a model with window layers keeps a ring of pages a row for
        # them (kv_cache.PagedKVCache): what feeds a row in pieces or
        # shares, copies or moves its pages is refused, with its reason
        _, window = page_classes(predictor._model)
        if window:
            ring = f"the last {window} positions of a row and no more"
            enforce(not self.chunked,
                    "prefill_chunk (the unified ragged step) cannot serve "
                    "a model with window layers: a chunk's rows attend "
                    "through the block table to what earlier chunks "
                    f"wrote, and a window layer's ring holds {ring}, "
                    "addressed by position and not by table column; "
                    "serve it in the default mode (bucketed prefill)")
            enforce(draft_predictor is None and not spec_tokens,
                    "speculative decoding cannot serve a model with "
                    "window layers: a rejected draft token has already "
                    f"overwritten a ring slot ({ring}), and the verify "
                    "step rides the unified ragged step")
            enforce(phase is None,
                    "the disaggregated phases cannot serve a model with "
                    "window layers: a row migrates as its full-class "
                    "pages, and its window layers' ring "
                    f"({ring}) is not among them; run unified replicas")
            enforce(not prefix_cache and not host_spill_pages,
                    "the prefix cache (and its host spill tier) cannot "
                    "serve a model with window layers: a hit at position "
                    "p would need the window layers' keys before p, and "
                    f"the donor's ring holds {ring}")
        # a model with state layers (a state-space mixer's recurrent
        # state; kv_cache.PagedKVCache's state class) keeps ONE slot a
        # row, the state after the row's last position: what needs the
        # state at another position is refused, with its reason
        if any(state_shapes(predictor._model, "float32")):
            slot = ("a state layer keeps one slot a row, the recurrent "
                    "state after the row's LAST position and no other")
            enforce(not self.chunked,
                    "prefill_chunk (the unified ragged step) cannot serve "
                    f"a model with state layers: {slot}, its forward takes "
                    "no `valid`, and a chunk that starts at position p "
                    "would have to carry the state from p - 1 through a "
                    "round that other rows ride; serve it in the default "
                    "mode (bucketed prefill)")
            enforce(draft_predictor is None and not spec_tokens,
                    "speculative decoding cannot serve a model with state "
                    f"layers: {slot}, so a rejected draft token has "
                    "already advanced the slot and nothing holds the "
                    "state to roll back to")
            enforce(phase is None,
                    "the disaggregated phases cannot serve a model with "
                    f"state layers: a row migrates as its pages, and {slot}"
                    ", which is not among them; run unified replicas")
            enforce(not prefix_cache and not host_spill_pages,
                    "the prefix cache (and its host spill tier) cannot "
                    f"serve a model with state layers: {slot}, so a hit "
                    "at position p would need the donor's state at p, "
                    "which its slot has long since left behind")
        # a model whose attention selects keys by a learned index
        # (model.key_selection: the keys a query keeps) scores a row's
        # index keys through the block table in its decode step alone
        self._key_selection = getattr(predictor._model, "key_selection",
                                      None)
        if self._key_selection:
            why = ("a model whose attention selects keys by a learned "
                   f"index (the {self._key_selection} of largest index "
                   "score a query): its forward takes no `valid`, so a "
                   "row is fed whole (bucketed prefill) or one position "
                   "at a time, and a chunk's rows would have to score "
                   "and select over the index keys that earlier chunks "
                   "wrote through the block table")
            enforce(not self.chunked,
                    "prefill_chunk (the unified ragged step) cannot "
                    f"serve {why}; serve it in the default mode")
            enforce(draft_predictor is None and not spec_tokens,
                    f"speculative decoding cannot serve {why}, and the "
                    "verify step rides the unified ragged step")
            enforce(not prefix_cache and not host_spill_pages,
                    "the prefix cache (and its host spill tier) cannot "
                    f"serve {why}, and a prefix hit feeds the rest of "
                    "the prompt as a chunk")
        if self.chunked:
            enforce(int(prefill_chunk) >= 1, "prefill_chunk must be >= 1")
            self.Sc = min(_bucket(int(prefill_chunk), lo=self.page),
                          _bucket(self.M, lo=self.page))
            enforce("valid" in inspect.signature(
                predictor._model.forward).parameters,
                "prefill_chunk needs a model whose forward accepts the "
                "unified ragged metadata kwarg `valid` (see "
                "models/llama.py)")
            self.prefill_budget = int(prefill_token_budget or self.Sc)
            enforce(self.prefill_budget >= 1,
                    "prefill_token_budget must be >= 1")
        else:
            self.Sc = 0
            self.prefill_budget = 0
        # disaggregated serving (inference/disagg.py drives the
        # migration): a "prefill" replica parks each row the moment its
        # first token samples, holding the committed KV pages for
        # export; a "decode" replica only adopts migrated rows (submit
        # is refused). None = unified, both phases on one replica.
        enforce(phase in (None, "prefill", "decode"),
                'ServingEngine phase must be None, "prefill", or '
                '"decode"')
        self.phase = phase
        if phase == "prefill":
            enforce(self.chunked,
                    'phase="prefill" runs the chunked unified step at '
                    "full MFU; set prefill_chunk")
        if phase is not None:
            enforce(draft_predictor is None,
                    "disaggregated phases do not carry the draft "
                    "pools; run speculative decoding on unified "
                    "replicas")
        self._admit_seq = 0
        # chunked-mode admission backpressure: while an active row is
        # page-stalled, new admissions pause so the freed/free pages
        # reach the OLDEST stalled row first (otherwise a preempted
        # request could be readmitted straight into the pages its
        # elder is waiting for — livelock)
        self._page_stalled = False
        self._dtype = predictor._params[0]._value.dtype
        # prefix cache: admission maps the longest cached prefix into
        # the new row's table, the chunk planner skips it
        self.prefix = bool(prefix_cache)
        if self.prefix:
            enforce(self.chunked,
                    "prefix_cache needs chunked prefill "
                    "(prefill_chunk): cache hits are whole "
                    "page-aligned chunks the chunk planner skips")
        enforce(not host_spill_pages or self.prefix,
                "host_spill_pages rides the prefix cache (pages are "
                "keyed by prefix hash); set prefix_cache=True")
        # debug-mode pool-accounting invariant (free + idle + live
        # partition the pool; refcounts == slot membership) checked
        # after every admit/finish/preempt — the free-list hardening
        # gate for the refcount migration
        self.debug = bool(debug_invariants)
        self.slots: List[Optional[_Slot]] = [None] * self.B
        self.queue: deque = deque()
        self.finished: Dict[int, ServingRequest] = {}
        self.stats = predictor.stats      # shared compile telemetry
        # unified telemetry: TTFT/TPOT histograms, occupancy gauges,
        # admission/eviction/backfill counters (observability/catalog).
        # All host-side — the compiled prefill/decode programs are
        # untouched, so the compile lattice stays exactly as flat
        self._metrics = _serving_metrics()
        self._stats_reported = (self.stats.compiles,
                                self.stats.cache_hits)
        # per-request lifecycle traces (observability/spans): live
        # traces keyed by rid; finished ones land in a bounded ring
        # with Chrome-trace export. Host-side perf_counter floats only.
        self.traces = SpanRing(maxlen=trace_ring)
        self._live_traces: Dict[int, RequestTrace] = {}
        self._round = 0
        # the decode rounds, kept once and not once a row: the last
        # ROUNDS_KEPT retired rounds as (index, t_launch, t_retire,
        # rows), the ``engine`` lane of the Chrome export
        self.rounds: deque = deque(maxlen=ROUNDS_KEPT)
        # static comm ledgers of the prefill/decode programs (empty on
        # a single-device mesh; populated the first time a program
        # traces with collectives, republished per execution)
        self._ledgers: Dict[Any, Any] = {}
        # per-program HBM memory ledgers (observability/memledger):
        # analyzed at a site's FIRST execution (before the call — the
        # cache buffers are donated) when the knob is on. One extra
        # trace + AOT compile per site; the jit cache and CompileStats
        # are untouched, so the (B, Sb, P) lattice stays exactly flat.
        self._mem_on = bool(mem_ledger) or bool(int(os.environ.get(
            "PADDLE_TPU_MEM_LEDGER", "0") or 0))
        self._mem_ledgers: Dict[Any, Any] = {}
        # site -> (jitted fn, arg shapes) of every program that has run
        self._site_programs: Dict[Any, Any] = {}
        # "decode" | "prefill" | .. -> the forms an expert model's
        # layers traced in this engine's programs of that kind, and the
        # grouped product their sorted form took
        self._moe_forms: Dict[str, set] = {}
        self._moe_grouped: Dict[str, set] = {}
        # prefill bucket -> the form its program's attention over the
        # pool traced ("flash" | "paged" | "dense")
        self._prefill_attention: Dict[int, str] = {}
        self._moe_rows: Dict[int, Tuple[int, int]] = {}
        self._live_peak = 0
        self.gen = cfg.generation
        self._rng = jax.random.PRNGKey(self.gen.seed)
        self._step_fns: Dict[Any, Any] = {}
        self._next_rid = 0
        # speculative decoding: a draft model proposes spec_tokens
        # greedy tokens per decode row; ONE verify dispatch on the
        # SAME unified [B, Sc] lattice scores all k+1 positions per
        # row. The draft's KV pools share the cache's page tables and
        # allocator (same page ids, draft geometry), so prefix hits
        # and copy-on-write cover the draft for free.
        self.spec = int(spec_tokens or 0)
        enforce((draft_predictor is None) == (self.spec == 0),
                "speculative decoding needs BOTH draft_predictor and "
                "spec_tokens >= 1 (or neither)")
        self._draft = draft_predictor
        if draft_predictor is not None:
            enforce(self.chunked,
                    "speculative decoding rides the unified chunked "
                    "step; set prefill_chunk")
            enforce(not self.gen.temperature,
                    "speculative decoding is greedy-verify only "
                    "(temperature=0): acceptance compares the draft "
                    "against the target argmax chain")
            enforce(self.spec >= 1 and self.spec + 1 <= self.Sc,
                    f"spec_tokens must satisfy 1 <= k <= Sc-1 (k+1 "
                    f"verify positions ride one [B, {self.Sc}] row)")
            dcfg = draft_predictor._model.config
            enforce("valid" in inspect.signature(
                draft_predictor._model.forward).parameters,
                "the draft model's forward must accept the unified "
                "ragged metadata kwarg `valid` (see models/llama.py)")
            enforce(dcfg.vocab_size == mcfg.vocab_size,
                    "draft and target models must share a vocabulary")
            enforce(int(dcfg.max_position_embeddings) >= self.M,
                    "draft max_position_embeddings must cover the "
                    "engine's max_length")
            self._draft_dtype = draft_predictor._params[0]._value.dtype
        self._spec = {"proposed": 0, "accepted": 0, "rounds": 0,
                      "committed": 0}
        # one decode round in flight: launched, not read yet. The token
        # feed of the next round stays on the device (_tok_last)
        self._inflight: Optional[_Round] = None
        self._tok_last = jnp.zeros((self.B,), jnp.int32)
        self._t_retired = 0.0
        self._overlap = {"rounds": 0, "overlapped": 0}
        # the page pool: ONE for the engine's whole lifetime, its shape
        # (on the same bucket lattice as Predictor._paged_caches) keys
        # every compiled program here. Its page programs run through
        # _run_captured like the engine's own, so their sites, ledgers
        # and CompileStats notes are the engine's — through a weak
        # reference: a cache that held the engine would be a cycle, and
        # the pools' HBM would wait for a garbage collection
        run = weakref.WeakMethod(self._run_captured)
        self.cache = PagedKVCache(
            predictor._model, self.page, self.M, self.B, self._dtype,
            pool_pages=pool_pages,
            resident_bytes=sum(_ml.shard_bytes(p._value)
                               for p in predictor._params)
            if pool_pages == "auto" else 0,
            spill_pages=host_spill_pages,
            draft=None if draft_predictor is None else
            (draft_predictor._model, self._draft_dtype),
            dispatch=lambda *a: run()(*a), stats=self.stats,
            metrics=self._metrics)
        if phase is not None:
            self.cache.check_stackable()
        if self.prefix:
            # the first real copy-on-write after warmup then costs
            # zero compiles
            self.cache.warm_copy()
        # graceful degradation: a bounded admission queue sheds at
        # submit (reason "queue_full"); a per-request admission deadline
        # sheds queued requests whose wait already blew their budget
        # (reason "deadline") BEFORE paying a prefill for them. Shed
        # requests never reach prefill, so TTFT stays honest — the shed
        # path is counted on paddle_tpu_serving_shed_total instead.
        self.max_queue = int(max_queue) if max_queue else None
        self.admission_deadline_s = admission_deadline_s
        self._last_shed_time: Optional[float] = None
        # /healthz integration: report "degraded" while shedding
        from ..observability import exporter as _exporter

        ref = weakref.ref(self)

        def _health_provider():
            eng = ref()
            if eng is None:
                return None              # engine gone: exporter prunes
            return {"component": "serving", "status": eng.health()}

        self._health_provider = _health_provider
        _exporter.add_health_provider(_health_provider)

        # durable metrics history: PADDLE_TPU_TIMESERIES_DIR attaches
        # the background registry sampler (observability/timeseries.py;
        # PADDLE_TPU_TIMESERIES_S sets the interval) — host-side only,
        # so serving programs and their compile caches are untouched
        self.sampler = None
        ts_dir = os.environ.get("PADDLE_TPU_TIMESERIES_DIR")
        if ts_dir:
            from ..observability import timeseries as _ts

            try:
                self.sampler = _ts.attach_dir(
                    ts_dir, interval_s=float(os.environ.get(
                        "PADDLE_TPU_TIMESERIES_S", "5.0")))
            except (OSError, ValueError):
                self.sampler = None    # unwritable dir: serve anyway

    @property
    def P(self) -> int:
        """Pages in the pool, the trash page included."""
        return self.cache.P

    @property
    def pools(self):
        """The cache's device arrays, per layer; assigning ``None``
        frees them (``release_pools``)."""
        return self.cache.pools

    @pools.setter
    def pools(self, value):
        self.cache.pools = value

    # -- admission -------------------------------------------------------
    def submit(self, prompt, max_new_tokens: Optional[int] = None,
               eos_token_id: Optional[int] = None,
               deadline_s: Optional[float] = None,
               trace_id: Optional[str] = None,
               parent_span_id: Optional[str] = None) -> int:
        """Queue one request; returns its rid (admission happens inside
        step()/run(), when a slot and enough free pages exist).

        Graceful degradation: with ``max_queue`` set, a full queue sheds
        the request immediately (it lands in ``finished`` with
        ``shed_reason="queue_full"`` and zero tokens). ``deadline_s``
        (default: the engine's ``admission_deadline_s``) bounds how long
        the request may wait for admission before being shed.

        Cross-process tracing: ``trace_id`` is either a 32-hex W3C
        trace id or a full ``traceparent`` header (in which case the
        caller's span id is taken from it); ``parent_span_id``
        overrides/supplies the caller's 16-hex span id. Missing pieces
        are generated, so every request ALWAYS carries a valid trace
        identity — read it back from ``ServingRequest.traceparent`` or
        ``trace_context(rid)`` to stitch a multi-replica trace."""
        enforce(self.phase != "decode",
                'a phase="decode" replica only adopts migrated '
                "requests (import_request); route submissions to a "
                "prefill or unified replica")
        ids = np.asarray(prompt._value if isinstance(prompt, Tensor)
                         else prompt).reshape(-1).astype(np.int64)
        n_new = int(max_new_tokens if max_new_tokens is not None
                    else self.gen.max_new_tokens)
        eos = eos_token_id if eos_token_id is not None \
            else self.gen.eos_token_id
        L = len(ids)
        enforce(L >= 1 and n_new >= 1, "empty prompt / max_new_tokens")
        enforce(L + n_new <= self.M,
                f"prompt ({L}) + max_new_tokens ({n_new}) exceeds cache "
                f"length {self.M}; raise Config.max_length")
        need = self.cache.pages_for(L + n_new)
        enforce(need <= self.cache.usable,
                f"request needs {need} pages but the pool only has "
                f"{self.cache.usable}; raise pool_pages")
        if trace_id is not None and "-" in trace_id:
            # a full traceparent header: the caller's span becomes
            # this trace's parent unless explicitly overridden. A
            # malformed or all-zero header (routers inject these) must
            # not fail the request: mint a fresh trace id and book the
            # reject reason instead.
            try:
                tid, parent = _parse_traceparent(trace_id)
            except ValueError:
                self._metrics["trace_parse_errors"].inc(
                    reason="malformed_traceparent")
                trace_id = None
            else:
                trace_id = tid
                if parent_span_id is None:
                    parent_span_id = parent
        rid = self._next_rid
        self._next_rid += 1
        now = time.perf_counter()
        dls = deadline_s if deadline_s is not None \
            else self.admission_deadline_s
        req = ServingRequest(rid, ids, n_new, eos, t_submit=now,
                             deadline=(now + dls) if dls is not None
                             else None)
        meta = {"prompt_len": L, "max_new_tokens": n_new}
        try:
            tr = RequestTrace(rid, meta=meta, trace_id=trace_id,
                              parent_span_id=parent_span_id)
        except ValueError:
            # bare ids that fail W3C validation get the same
            # treatment: fresh identity, reason on the counter
            self._metrics["trace_parse_errors"].inc(
                reason="invalid_trace_id")
            tr = RequestTrace(rid, meta=meta)
        req.trace_id = tr.trace_id
        req.span_id = tr.span_id
        req.parent_span_id = tr.parent_span_id
        tr.begin("queued", now)
        self._live_traces[rid] = tr
        self._metrics["requests"].inc(event="submitted")
        if self.max_queue is not None and len(self.queue) >= self.max_queue:
            self._shed(req, "queue_full")
            return rid
        self.queue.append(req)
        self._metrics["queue_depth"].set(len(self.queue))
        return rid

    def _shed(self, req: ServingRequest, reason: str):
        """Load-shed a queued request: it finishes with no tokens, no
        TTFT observation (shed latency must not pollute the latency
        SLO histograms), and a shed counter tick. The decision itself
        lands in the request's span trace as a zero-length "shed"
        event (an "i" instant in the Chrome export), so
        export_request_traces shows shed requests — when and why they
        were turned away — not just the ones that completed."""
        req.shed_reason = reason
        req.t_finish = time.perf_counter()
        self.finished[req.rid] = req
        self._last_shed_time = req.t_finish
        m = self._metrics
        m["shed"].inc(reason=reason)
        tr = self._live_traces.pop(req.rid, None)
        if tr is not None:
            # the queued span closes but is NOT observed on the stage
            # histogram — shed latency stays out of the SLO percentiles
            # exactly like the TTFT exclusion above
            sp = tr.end("queued", req.t_finish)
            tr.meta["shed_reason"] = reason
            tr.add("shed", req.t_finish, req.t_finish,
                   {"reason": reason,
                    "queued_seconds": (sp.seconds if sp is not None
                                       else 0.0)})
            self.traces.add(tr)

    def health(self) -> str:
        """"ok", or "degraded" while the engine is shedding load (a
        shed within the last ``DEGRADED_WINDOW_S``, or the admission
        queue at its bound) — surfaced on /healthz by the metrics exporter."""
        if self.max_queue is not None and \
                len(self.queue) >= self.max_queue:
            return "degraded"
        if self._last_shed_time is not None and \
                time.perf_counter() - self._last_shed_time \
                <= DEGRADED_WINDOW_S:
            return "degraded"
        return "ok"

    def check_invariants(self):
        """The cache's pool-accounting invariant against the pages this
        engine's slots hold (``PagedKVCache.check_invariants``)."""
        self.cache.check_invariants(
            (s.pages for s in self.slots if s is not None),
            live_rows=[b for b, s in enumerate(self.slots)
                       if s is not None])

    def prefix_cache_stats(self) -> Dict[str, Any]:
        """Host-side prefix-cache counters: page lookups/hits at
        admission, prompt tokens skipped vs fed, copy-on-writes, LRU
        reclaims, plus the current registered/idle page counts."""
        return self.cache.prefix_stats()

    def spill_stats(self) -> Dict[str, Any]:
        """Host-tier counters: pages spilled/faulted/dropped, resident
        host bytes, and the cumulative transfer ledger per direction."""
        return self.cache.spill_stats()

    def spec_stats(self) -> Dict[str, Any]:
        """Speculative-decoding counters: drafts proposed/accepted,
        decode rounds, tokens committed — accept_rate and
        tokens_per_step are the two headline ratios."""
        out = dict(self._spec)
        out["accept_rate"] = (out["accepted"] / out["proposed"]
                              if out["proposed"] else 0.0)
        out["tokens_per_step"] = (out["committed"] / out["rounds"]
                                  if out["rounds"] else 0.0)
        return out

    def overlap_stats(self) -> Dict[str, Any]:
        """How the decode rounds overlapped the host: rounds launched,
        how many of them while the round before was still unretired,
        rounds in flight now (0 after ``run()``), and the median time a
        retire blocked in its fetch — near zero means the host sets the
        pace, near the device's round time means the device does. (The
        median is the process registry's histogram: every engine's.)"""
        out = dict(self._overlap)
        out["overlapped_share"] = (out["overlapped"] / out["rounds"]
                                   if out["rounds"] else 0.0)
        out["in_flight"] = int(self._inflight is not None)
        out["fetch_wait_p50_s"] = \
            self._metrics["fetch_wait"].percentile(50)
        return out

    def _admit_plan(self, req: ServingRequest):
        """Admission plan (allocates nothing for the request; with the
        host tier on it first faults the prompt's spilled pages back,
        so the match sees them as ordinary idle hits): returns (cold pages
        to allocate now, reserve pages the availability check must
        also cover — idle hit pages among them: they count as available
        but the hit itself is about to pin them —, cache-hit pages,
        prefix hashes, fed0 = prompt tokens the cache already holds).
        Legacy: the whole len+new footprint. Chunked: only the first
        chunk's pages past the hit run — the rest are reserved
        incrementally (_plan_chunks). A FULL-prompt hit refeeds the
        last prompt token (fed0 = L-1) so the unified step still
        samples token 0; its copy-on-write page is in the reserve."""
        cache = self.cache
        L = len(req.prompt)
        if not self.chunked:
            return (cache.pages_for(L + req.max_new_tokens), 0,
                    [], None, 0)
        if not self.prefix:
            return (cache.pages_for(min(L, self.Sc)), 0, [], None, 0)
        hashes = cache.prefix_hashes(req.prompt)
        cache.fault_in(hashes, cache.pages_for(min(L, self.Sc)) + 1)
        hits, idle = cache.match_prefix(hashes)
        k = len(hits)
        fed0 = k * self.page
        if fed0 >= L:
            fed0 = L - 1
        cold = cache.pages_for(min(L, fed0 + self.Sc)) - k
        reserve = (1 if fed0 < k * self.page else 0) + idle
        return max(cold, 0), reserve, hits, hashes, fed0

    def _pvals(self):
        return tuple(p._value for p in self.pred._params)

    def _admit(self):
        """FIFO-admit queued requests into free slots while pages last;
        each admission runs one bucketed prefill into the shared pool.
        Requests whose admission deadline already passed are shed here,
        BEFORE any prefill is spent on them."""
        while self.queue:
            now = time.perf_counter()
            req = self.queue[0]
            if req.deadline is not None and now > req.deadline:
                self.queue.popleft()
                self._shed(req, "deadline")
                self._metrics["queue_depth"].set(len(self.queue))
                continue
            if self.chunked and self._page_stalled and self.num_active:
                return    # backpressure: stalled elders drain first
            free = [b for b in range(self.B) if self.slots[b] is None]
            if not free:
                return
            if self.chunked:
                # the plan may fault pages in, and the chunk rounds
                # that follow copy on write and spill
                self._drain()
            cold, reserve, hits, hashes, fed0 = self._admit_plan(req)
            if cold + reserve > self.cache.available() \
                    or not self.cache.rings_available() \
                    or not self.cache.slots_available():
                return                    # head-of-line waits for evictions
            self.queue.popleft()
            b = free[0]
            # a backfill is an admission that joins rows mid-decode
            # (the continuous-batching event; a cold admit is not one)
            backfill = self.num_active > 0
            self.cache.pin(hits)          # BEFORE any reclaim can run
            pages = list(hits) + self.cache.allocate(cold)
            self.cache.set_row(b, pages)
            if self.cache.window:
                self.cache.take_ring(b)
            if self.cache.state_row_bytes:
                self.cache.take_slot(b)
            slot = _Slot(
                req, pages, state="prefill" if self.chunked else "decode",
                seq=self._admit_seq)
            slot.hashes = hashes
            slot.hit_pages = len(hits)
            slot.registered = len(hits)
            slot.fed = fed0
            self.slots[b] = slot
            self._admit_seq += 1
            m = self._metrics
            m["requests"].inc(event="admitted")
            if hashes is not None:
                self.cache.note(lookups=len(hashes), hits=len(hits),
                                skipped_tokens=fed0)
            if self.debug:
                self.check_invariants()
            if backfill:
                m["requests"].inc(event="backfilled")
            m["queue_depth"].set(len(self.queue))
            tr = self._live_traces.get(req.rid)
            if tr is not None:
                sp = tr.end("queued", time.perf_counter())
                tr.meta["backfill"] = bool(backfill)
                if sp is not None:
                    m["stage_seconds"].observe(sp.seconds,
                                               stage="queued")
            if self.chunked:
                # chunks feed inside the unified rounds; the prefill
                # stage span (admit -> first token) opens here
                if tr is not None:
                    tr.begin("prefill", time.perf_counter())
            else:
                self._prefill(b)

    def _prefill(self, b: int):
        from . import _bucket, _sample

        slot = self.slots[b]
        req = slot.req
        t0 = time.perf_counter()
        L = len(req.prompt)
        Sb = min(_bucket(L), self.M)
        with _span("serving.prefill", rid=req.rid, seq_bucket=Sb,
                   prompt_tokens=L,
                   attention=self._prefill_attention.get(
                       Sb, "unrecorded")):
            with _span("serving.prefill.dispatch"):
                ids = np.zeros((1, Sb), np.int32)
                ids[0, :L] = req.prompt
                caches = self.cache.bind(
                    self.cache.rows(b),
                    wrows=self.cache.window_prefill_rows(b, L),
                    slots=self.cache.prefill_slots(b))
                fn = self.pred._prefill_fn(1, Sb, self.M)
                self.stats.note("prefill",
                                (1, Sb, self.M, self.page, self.P,
                                 str(ids.dtype), str(self._dtype)))
                last, caches = self._run_captured(
                    ("prefill", Sb), fn, self._pvals(), jnp.asarray(ids),
                    caches, jnp.asarray([L], jnp.int32))
                self.cache.commit(caches)
                self._rng, sub = jax.random.split(self._rng)
                tok = _sample(last, sub, self.gen)
            with _span("serving.prefill.fetch"):
                tok0 = int(np.asarray(tok)[0])
            req.new_tokens.append(tok0)
            self.stats.count_tokens(("prefill", Sb, self.P), 1)
            now = time.perf_counter()
            req.t_first_token = now
            m = self._metrics
            m["prefill_seconds"].observe(now - t0)
            m["ttft"].observe(now - req.t_submit)
            m["tokens"].inc(1, phase="prefill")
            m["prefill_tokens"].inc(L, kind="prompt")
            m["prefill_tokens"].inc(Sb, kind="bucket")
            tr = self._live_traces.get(req.rid)
            if tr is not None:
                held = {"full_pages": len(slot.pages),
                        "window_pages": self.cache.ring}
                if self._key_selection:
                    # its index keys are pooled with K and V, a page
                    # each of the row's full-class pages
                    held["index_pages"] = len(slot.pages)
                tr.add("prefill", t0, now, {"seq_bucket": Sb, **held})
                m["stage_seconds"].observe(now - t0, stage="prefill")
                tr.begin("decode", now, held)    # closed at eviction
            if len(req.new_tokens) >= req.max_new_tokens or \
                    (req.eos_token_id is not None
                     and tok0 == req.eos_token_id):
                self._finish(b)

    # -- decode ----------------------------------------------------------
    def _decode_step_fn(self):
        """One shared compiled decode program for the whole in-flight
        batch: [B] tokens at per-row offsets against the fixed pool,
        ``chunk`` steps fused in one lax.scan. Keyed ONLY on lattice
        constants — admissions/evictions never change its shape.

        The program carries its own feed, so a round can be launched
        before the one ahead of it has been read: ``state`` is what the
        cache lends (donated: the pools, and an expert model's
        counters); ``round_`` is the ONE host array of a round,
        ``[B, npages + 3]`` int32 (``[B, npages + ring + 3]`` for a
        model with window layers), not donated and read by every layer
        — the block tables (then the ring tables), then a column each
        of ``pos``, a host token
        and a mask; a row starts from its host token where the mask is
        set (a row new to the batch) and from ``tok_prev``, the last
        token of the round before, where it is not. The key advances
        inside and is handed back. Returns ``(toks [B, chunk], the last
        of them [B], state, key)``."""
        gen = self.gen
        key = (self.B, self.M, self.chunk, gen.temperature, gen.top_k,
               gen.top_p)
        if key in self._step_fns:
            return self._step_fns[key]
        model, params = self.pred._model, self.pred._params
        chunk, cache = self.chunk, self.cache
        from . import _sample
        from ..autograd import no_grad
        from ..distributed.engine import bind_params

        def step(pvals, state, round_, tok_prev, rng):
            npg = round_.shape[1] - 3
            table, pos0 = round_[:, :npg], round_[:, npg]
            tok0 = jnp.where(round_[:, npg + 2] != 0, round_[:, npg + 1],
                             tok_prev)
            if cache.window:
                table = cache.layer_tables(
                    table[:, :cache.npages], table[:, cache.npages:])

            def body(carry, _):
                tok, caches, pos, rng = carry
                with no_grad(), bind_params(params, pvals):
                    logits, caches = model.forward(
                        Tensor(tok[:, None], stop_gradient=True),
                        caches=caches, offset=pos)
                lv = (logits._value if isinstance(logits, Tensor)
                      else logits)
                rng, sub = jax.random.split(rng)
                nxt = _sample(lv[:, -1], sub, gen)
                return (nxt, caches, pos + 1, rng), nxt

            (tok, caches, _, rng), toks = lax.scan(
                body, (tok0, with_table(state, table, cache.arrays), pos0,
                       rng), None, length=chunk)
            return (jnp.swapaxes(toks, 0, 1), tok,     # [B, chunk], [B]
                    without_table(caches, cache.arrays), rng)

        self._step_fns[key] = jax.jit(step, donate_argnums=(1,))
        return self._step_fns[key]

    # -- unified chunked-prefill + decode step ---------------------------
    def _unified_step_fn(self):
        """THE unified compiled step (chunked mode): fixed [B, Sc] ids
        at per-row ``(start, seq_len)`` metadata against the shared
        pool — prefill-chunk rows, decode rows, and dead rows in one
        dispatch (the ragged paged-attention kernel underneath). Keyed
        ONLY on lattice constants; the metadata is DATA, not shape."""
        gen = self.gen
        key = ("unified", self.B, self.Sc, self.M, gen.temperature,
               gen.top_k, gen.top_p)
        if key in self._step_fns:
            return self._step_fns[key]
        model, params = self.pred._model, self.pred._params
        from . import _sample
        from ..autograd import no_grad
        from ..distributed.engine import bind_params

        def step(pvals, ids, caches, starts, nvalid, rng):
            with no_grad(), bind_params(params, pvals):
                logits, caches = model.forward(
                    Tensor(ids, stop_gradient=True), caches=caches,
                    offset=starts, valid=nvalid)
            lv = (logits._value if isinstance(logits, Tensor)
                  else logits)
            # each row samples at its LAST valid slot: a decode row's
            # next token, a final prefill chunk's first token; mid-
            # prefill / dead rows sample garbage the host ignores
            idx = jnp.maximum(nvalid - 1, 0)
            last = jnp.take_along_axis(
                lv, idx[:, None, None], axis=1)[:, 0]
            rng, sub = jax.random.split(rng)
            return _sample(last, sub, gen), caches

        self._step_fns[key] = jax.jit(step, donate_argnums=(2,))
        return self._step_fns[key]

    # -- speculative decoding --------------------------------------------
    def _unified_spec_step_fn(self):
        """The spec-mode unified step: IDENTICAL forward on the same
        [B, Sc] lattice, but greedy argmax at EVERY position instead
        of a sample at the last — a decode row feeding its last token
        plus k draft tokens gets all k+1 verify logits from the one
        dispatch. Keyed only on lattice constants."""
        key = ("unified_spec", self.B, self.Sc, self.M)
        if key in self._step_fns:
            return self._step_fns[key]
        model, params = self.pred._model, self.pred._params
        from ..autograd import no_grad
        from ..distributed.engine import bind_params

        def step(pvals, ids, caches, starts, nvalid):
            with no_grad(), bind_params(params, pvals):
                logits, caches = model.forward(
                    Tensor(ids, stop_gradient=True), caches=caches,
                    offset=starts, valid=nvalid)
            lv = (logits._value if isinstance(logits, Tensor)
                  else logits)
            toks = jnp.argmax(lv.astype(jnp.float32), axis=-1)
            return toks.astype(jnp.int32), caches

        self._step_fns[key] = jax.jit(step, donate_argnums=(2,))
        return self._step_fns[key]

    def _propose_fn(self):
        """The draft proposal program: k greedy [B, 1] draft steps
        fused in one lax.scan against the draft pools (same page
        tables). Rows not proposing ride along at valid 0 — their
        writes land in the trash column."""
        key = ("propose", self.B, self.spec, self.M)
        if key in self._step_fns:
            return self._step_fns[key]
        model, params = self._draft._model, self._draft._params
        k, M = self.spec, self.M
        from ..autograd import no_grad
        from ..distributed.engine import bind_params

        def propose(pvals, tok0, caches, pos0, nv):
            def body(carry, _):
                tok, caches, pos = carry
                with no_grad(), bind_params(params, pvals):
                    logits, caches = model.forward(
                        Tensor(tok[:, None], stop_gradient=True),
                        caches=caches, offset=pos, valid=nv)
                lv = (logits._value if isinstance(logits, Tensor)
                      else logits)
                nxt = jnp.argmax(lv[:, -1].astype(jnp.float32),
                                 axis=-1).astype(jnp.int32)
                # rows near their token budget draft past their own
                # horizon; clamp keeps positions in the cache (the
                # overdraft lanes are never read — the host caps
                # acceptance at k_use = remaining - 1)
                pos = jnp.minimum(pos + 1, M - 1)
                return (nxt, caches, pos), nxt

            # k+1 steps for k proposals: the extra step writes the
            # k-th proposal's KV, so a fully-accepted run leaves no
            # gap in the draft cache (its emission is discarded)
            (_, caches, _), toks = lax.scan(
                body, (tok0, caches, pos0), None, length=k + 1)
            return jnp.swapaxes(toks, 0, 1), caches      # [B, k+1]

        self._step_fns[key] = jax.jit(propose, donate_argnums=(2,))
        return self._step_fns[key]

    def _draft_chunk_fn(self):
        """Prompt chunks mirrored into the DRAFT pools (same [B, Sc]
        ragged metadata, logits discarded) so the draft proposes from
        full prompt context once a row reaches decode."""
        key = ("draft_chunk", self.B, self.Sc, self.M)
        if key in self._step_fns:
            return self._step_fns[key]
        model, params = self._draft._model, self._draft._params
        from ..autograd import no_grad
        from ..distributed.engine import bind_params

        def feed(pvals, ids, caches, starts, nvalid):
            with no_grad(), bind_params(params, pvals):
                _logits, caches = model.forward(
                    Tensor(ids, stop_gradient=True), caches=caches,
                    offset=starts, valid=nvalid)
            return caches

        self._step_fns[key] = jax.jit(feed, donate_argnums=(2,))
        return self._step_fns[key]

    def _draft_pvals(self):
        return tuple(p._value for p in self._draft._params)

    def _propose(self, k_use: Dict[int, int]) -> np.ndarray:
        """Run the draft proposal scan for this round's decode rows;
        returns the [B, k] proposed ids. Also writes the rows' last
        committed token into the draft KV (keeping the draft cache
        exactly one committed token behind the target's)."""
        B = self.B
        tok = np.zeros((B,), np.int32)
        pos = np.zeros((B,), np.int32)
        nv = np.zeros((B,), np.int32)
        for b, ku in k_use.items():
            if ku <= 0:
                continue
            s = self.slots[b]
            tok[b] = s.req.new_tokens[-1]
            pos[b] = s.pos + len(s.req.new_tokens) - 1
            nv[b] = 1
        caches = self.cache.bind(self.cache.rows(extended=True),
                                 draft=True)
        fn = self._propose_fn()
        self.stats.note("draft_propose",
                        (B, self.spec, self.M, self.page, self.P,
                         str(self._draft_dtype)))
        toks, caches = self._run_captured(
            ("draft_propose",), fn, self._draft_pvals(),
            jnp.asarray(tok), caches, jnp.asarray(pos),
            jnp.asarray(nv))
        self.cache.commit(caches, draft=True)
        return np.asarray(toks)

    def _draft_feed(self, feeders, ids: np.ndarray, starts: np.ndarray):
        """Mirror this round's prompt chunks into the draft pools
        (decode rows masked to valid 0 — their draft KV advances in
        _propose). Cache-hit pages already hold draft KV from the
        request that first fed them: the pools share page ids."""
        B = self.B
        nvf = np.zeros((B,), np.int32)
        for b, n, _last in feeders:
            nvf[b] = n
        caches = self.cache.bind(self.cache.rows(extended=True),
                                 draft=True)
        fn = self._draft_chunk_fn()
        self.stats.note("draft_chunk",
                        (B, self.Sc, self.M, self.page, self.P,
                         str(self._draft_dtype)))
        caches = self._run_captured(
            ("draft_chunk", self.Sc), fn, self._draft_pvals(),
            jnp.asarray(ids), caches, jnp.asarray(starts),
            jnp.asarray(nvf))
        self.cache.commit(caches, draft=True)

    def _plan_chunks(self):
        """Pick this round's prefill feeders (admission order) under
        the token budget, reserving pages incrementally: a chunk needs
        pages up to its own frontier only, except the LAST chunk, which
        also secures the decode tail (so decode rows never stall on
        pages). Returns (feeders, stalled): feeders as (row, n_tokens,
        is_last); stalled True when some row's reservation could not be
        met this round (it waits for evictions — or preemption when
        nothing else can move)."""
        feeders: List[tuple] = []
        stalled = False
        budget = self.prefill_budget
        rows = sorted((b for b in range(self.B)
                       if self.slots[b] is not None
                       and self.slots[b].state == "prefill"),
                      key=lambda b: self.slots[b].seq)
        for b in rows:
            if budget <= 0:
                break
            s = self.slots[b]
            L = len(s.req.prompt)
            n = min(L - s.fed, self.Sc, budget)
            if n <= 0:
                continue
            last = s.fed + n == L
            want_tokens = (L + s.req.max_new_tokens) if last \
                else (s.fed + n)
            extra = self.cache.pages_for(want_tokens) - len(s.pages)
            # copy-on-write: shared/registered pages are immutable, so
            # any page this chunk writes into that another slot (or
            # the cache) can still see is copied to a private page
            # first. Page-aligned frontiers make this rare: it only
            # fires on the full-prefix-hit refeed (position L-1 lands
            # in the final hit page).
            cow = self._cow_plan(s, n)
            if max(extra, 0) + len(cow) > self.cache.available():
                stalled = True
                self._metrics["prefill_stall"].inc()
                continue
            if extra > 0:
                s.pages.extend(self.cache.allocate(extra))
            for j in cow:
                s.pages[j] = self.cache.copy_on_write(s.pages[j])
            if extra > 0 or cow:
                self.cache.set_row(b, s.pages)
            feeders.append((b, n, last))
            budget -= n
        self._page_stalled = stalled
        return feeders, stalled

    def _cow_plan(self, s: _Slot, n: int) -> List[int]:
        """Table columns of ``s`` whose pages the next n-token chunk
        writes into while shared or registered in the prefix cache —
        those must be copied before the write."""
        if not self.prefix:
            return []
        jlo = s.fed // self.page
        jhi = min((s.fed + n - 1) // self.page, len(s.pages) - 1)
        return [jlo + i for i in
                self.cache.shared(s.pages[jlo:jhi + 1])]

    def _unified_round(self, feeders):
        """One unified dispatch: every feeder writes its next prompt
        chunk, every decode row advances — one token (plain), or up
        to spec_tokens+1 (speculative: last token + k draft proposals
        verified in the same dispatch), dead rows ride along at
        seq_len 0 — ONE compiled program, fixed shape."""
        self._drain()       # it reads and appends every row's tokens
        t0 = time.perf_counter()
        B = self.B
        spec = self._draft is not None
        ids = np.zeros((B, self.Sc), np.int32)
        starts = np.zeros((B,), np.int32)
        nvalid = np.zeros((B,), np.int32)
        feed = {b: (n, last) for b, n, last in feeders}
        decode_rows = []
        k_use: Dict[int, int] = {}
        for b in range(B):
            s = self.slots[b]
            if s is None:
                continue
            if s.state == "decode":
                # spec: draft up to k tokens but never past the row's
                # remaining budget (the last token is never an input,
                # so remaining-1 verify inputs suffice)
                ku = 0
                if spec:
                    ku = max(0, min(self.spec, s.req.max_new_tokens
                                    - len(s.req.new_tokens) - 1))
                k_use[b] = ku
                ids[b, 0] = s.req.new_tokens[-1]
                starts[b] = s.pos + len(s.req.new_tokens) - 1
                nvalid[b] = 1 + ku
                decode_rows.append(b)
            elif b in feed:
                n, _last = feed[b]
                ids[b, :n] = s.req.prompt[s.fed:s.fed + n]
                starts[b] = s.fed
                nvalid[b] = n
            # stalled/out-of-budget prefill rows and free slots stay
            # at seq_len 0: no writes (redirected to the trash
            # column), no attention, output ignored
        if spec and any(k_use[b] > 0 for b in decode_rows):
            drafts = self._propose(k_use)
            for b in decode_rows:
                ku = k_use[b]
                if ku > 0:
                    ids[b, 1:1 + ku] = drafts[b, :ku]
        caches = self.cache.bind(self.cache.rows(extended=True))
        if spec:
            fn = self._unified_spec_step_fn()
            self.stats.note("unified_spec",
                            (B, self.Sc, self.M, self.page, self.P,
                             str(self._dtype)))
            toks, caches = self._run_captured(
                ("unified_spec", self.Sc), fn, self._pvals(),
                jnp.asarray(ids), caches, jnp.asarray(starts),
                jnp.asarray(nvalid))
        else:
            fn = self._unified_step_fn()
            self.stats.note("unified",
                            (B, self.Sc, self.M, self.page, self.P,
                             self.gen.temperature, self.gen.top_k,
                             self.gen.top_p, str(self._dtype)))
            self._rng, sub = jax.random.split(self._rng)
            toks, caches = self._run_captured(
                ("unified", self.Sc), fn, self._pvals(),
                jnp.asarray(ids), caches, jnp.asarray(starts),
                jnp.asarray(nvalid), sub)
        self.cache.commit(caches)
        # mirror the chunks into the draft pools BEFORE commits can
        # retire a feeder (a finished row's table goes all-trash, and
        # its registered pages must carry draft KV into the cache)
        if spec and feeders:
            self._draft_feed(feeders, ids, starts)
        toks = np.asarray(toks)     # plain: [B]; spec: [B, Sc]
        now = time.perf_counter()
        m = self._metrics
        fed_tokens = 0
        for b, n, last in feeders:
            s = self.slots[b]
            req = s.req
            tr = self._live_traces.get(req.rid)
            if tr is not None:
                # per-chunk span: Chrome request traces show chunk
                # scheduling interleaved with the decode rounds
                tr.add("prefill_chunk", t0, now,
                       {"chunk": s.chunks, "tokens": n, "start": s.fed})
            s.fed += n
            s.chunks += 1
            fed_tokens += n
            m["prefill_chunks"].inc()
            if self.prefix and s.hashes:
                # pages fully behind the fed frontier now hold final
                # immutable KV: publish them under their prefix hash
                full = s.fed // self.page
                for j in range(s.registered, full):
                    self.cache.register(s.hashes[j], s.pages[j])
                s.registered = max(s.registered, full)
            if last:
                tok0 = int(toks[b, nvalid[b] - 1]) if spec \
                    else int(toks[b])
                req.new_tokens.append(tok0)
                req.t_first_token = now
                m["ttft"].observe(now - req.t_submit)
                m["tokens"].inc(1, phase="prefill")
                s.state = "decode"
                if tr is not None:
                    sp = tr.end("prefill", now)
                    if sp is not None:
                        m["prefill_seconds"].observe(sp.seconds)
                        m["stage_seconds"].observe(sp.seconds,
                                                   stage="prefill")
                    tr.begin("decode", now)    # closed at eviction
                if len(req.new_tokens) >= req.max_new_tokens or \
                        (req.eos_token_id is not None
                         and tok0 == req.eos_token_id):
                    self._finish(b)
                elif self.phase == "prefill":
                    # disaggregated: the committed KV pages are ready
                    # to stream out — park the row for export
                    # (migratable/export_request) instead of decoding
                    # it on this replica
                    s.state = "migrate"
        if self.prefix:
            self.cache.note(fed_tokens=fed_tokens)
        emitted = 0
        for b in decode_rows:
            req = self.slots[b].req
            acc = 0
            if spec:
                # greedy verify: toks[b, i] is the target argmax AFTER
                # consuming input i, so draft i is accepted iff it
                # equals the previous committed token's argmax —
                # commit the accepted run plus the one bonus token
                ku = k_use[b]
                seq = [int(toks[b, 0])]
                for i in range(1, ku + 1):
                    if int(ids[b, i]) != seq[-1]:
                        break
                    seq.append(int(toks[b, i]))
                acc = len(seq) - 1
                self._spec["proposed"] += ku
                self._spec["accepted"] += acc
            else:
                seq = [int(toks[b])]
            self.slots[b].rode(self._round)
            tr = self._live_traces.get(req.rid)
            if tr is not None:
                # a row's span and not the round's: it carries what the
                # ROW proposed and accepted, which a round cannot
                meta = {"round": self._round, "unified": True}
                if spec:
                    meta.update(proposed=k_use[b], accepted=acc)
                tr.add("decode_round", t0, now, meta)
            for t in seq:
                req.new_tokens.append(t)
                emitted += 1
                if len(req.new_tokens) >= req.max_new_tokens or \
                        (req.eos_token_id is not None
                         and t == req.eos_token_id):
                    self._finish(b)
                    break           # rest of the run is discarded
        if spec and decode_rows:
            # rounds counts decode-ROW verify steps (one per row per
            # dispatch), so committed/rounds is the per-row
            # tokens-per-step ratio: 1.0 at zero acceptance
            self._spec["rounds"] += len(decode_rows)
            self._spec["committed"] += emitted
        self.stats.count_tokens(
            (("unified_spec" if spec else "unified"), self.Sc, self.P),
            fed_tokens + emitted)
        m["unified_round_seconds"].observe(now - t0)
        if emitted:
            m["tokens"].inc(emitted, phase="decode")
        self._round += 1

    def _preempt_youngest(self):
        """Deadlock breaker: when every mid-prefill row is stalled on
        pages and no decode row can free any, bounce the YOUNGEST
        mid-prefill row back to the queue head — it has sampled no
        token yet, so restarting its prefill from scratch is exact.
        The oldest row is never preempted, so it monotonically acquires
        pages and the engine always makes progress."""
        self._drain()
        rows = [b for b in range(self.B)
                if self.slots[b] is not None
                and self.slots[b].state == "prefill"]
        if len(rows) <= 1:
            return                  # never preempt the only/oldest row
        b = max(rows, key=lambda b: self.slots[b].seq)
        s = self.slots[b]
        now = time.perf_counter()
        # refcount-aware release: pages shared with elder slots (or
        # registered in the prefix cache) survive the preemption —
        # the sharers keep decoding against them untouched
        self.cache.release_row(b, s.pages)
        self.slots[b] = None
        self.queue.appendleft(s.req)
        m = self._metrics
        m["requests"].inc(event="preempted")
        m["queue_depth"].set(len(self.queue))
        tr = self._live_traces.get(s.req.rid)
        if tr is not None:
            tr.end("prefill", now)     # partial prefill span, kept
            tr.add("preempt", now, now,
                   {"reason": "pages", "fed": s.fed})
            tr.begin("queued", now)
        if self.debug:
            self.check_invariants()

    def _chunked_round(self):
        """One chunked-mode tick: feed chunks through the unified step
        when any are ready (decode rows ride along); otherwise run the
        cheap fused decode scan — except in spec mode, where decode
        rows always take the unified verify path (draft proposals need
        the [B, Sc] lattice); preempt only when nothing can move. Only
        runs of pure-decode rounds overlap: a mid-prefill row (its
        pages are reserved, copied on write and spilled here) or a
        unified round first retires the decode round in flight."""
        if any(s is not None and s.state == "prefill" for s in self.slots):
            self._drain()
        feeders, stalled = self._plan_chunks()
        has_decode = any(s is not None and s.state == "decode"
                         for s in self.slots)
        if feeders or (self._draft is not None and has_decode):
            with _span("serving.unified_round", rows=self.num_active,
                       chunks=len(feeders)):
                self._unified_round(feeders)
        elif stalled and not has_decode:
            self._preempt_youngest()
        else:
            self._decode_round()    # with no decode row: only retires

    def _decode_round(self):
        """Launch the next decode round, THEN retire the one before it:
        the device runs round k while the host reads round k-1, finishes
        its rows, returns to the caller, admits, and prepares round
        k+1. With no row to launch this only retires."""
        prev = self._inflight
        self._inflight = self._launch_round()
        if prev is not None:
            self._retire_round(prev)

    def _launch_round(self) -> Optional[_Round]:
        """Build a round's one host array, dispatch, keep the device
        arrays that come back; reads no device value. A row's position
        and the tokens it has left are known from counts alone (what the
        host holds plus what the unretired round brings), so a row whose
        last token is on its way is left out, as are free slots and
        mid-prefill rows: their table rows read all-trash, their writes
        hit the trash page, their outputs are ignored."""
        rows = [(b, s) for b, s in enumerate(self.slots)
                if s is not None and s.state == "decode"]
        if not rows:
            return None
        overlapped = self._inflight is not None    # still unretired
        with _span("serving.launch", round=self._round, rows=len(rows),
                   overlapped=overlapped):
            t0 = time.perf_counter()
            riding = [b for b, _ in rows]
            npg = self.cache.npages + self.cache.ring
            host = np.zeros((self.B, npg + 3), np.int32)
            host[:, :self.cache.npages] = self.cache.rows(only=riding)
            if self.cache.ring:
                host[:, self.cache.npages:npg] = \
                    self.cache.window_rows(only=riding)
            taken = []
            for b, s in rows:
                req = s.req
                have = len(req.new_tokens) + s.inflight
                host[b, npg] = s.pos + have - 1
                if not s.inflight:
                    # new to the batch, or the pipeline was drained: the
                    # host knows the row's last token and feeds it
                    host[b, npg + 1] = req.new_tokens[-1]
                    host[b, npg + 2] = 1
                take = min(self.chunk, req.max_new_tokens - have)
                s.inflight += take
                s.rode(self._round)
                if have + take >= req.max_new_tokens:
                    s.state = "finishing"
                taken.append((b, s, take))
            fn = self._decode_step_fn()
            self.stats.note("serve_decode",
                            (self.B, self.M, self.chunk, self.P,
                             self.gen.temperature, self.gen.top_k,
                             self.gen.top_p, str(self._dtype)))
            toks, self._tok_last, state, self._rng = self._run_captured(
                ("decode",), fn, self._pvals(), self.cache.lend(),
                jnp.asarray(host), self._tok_last, self._rng)
            self.cache.take_back(state)
            self._overlap["rounds"] += 1
            self._overlap["overlapped"] += overlapped
            self._metrics["rounds"].inc(
                overlapped="true" if overlapped else "false")
            rnd = _Round(toks, taken, t0, self._round)
            self._round += 1
            return rnd

    def _retire_round(self, rnd: _Round):
        """The one blocking fetch of a decode round, then what the host
        owes its rows: tokens appended, finished rows evicted, metrics
        and the round's one record. A row that an ``eos_token_id``
        finished a round ago rode this round too (the hit was not known
        at its launch): its slot is gone or another request's, and its
        token is dropped."""
        with _span("serving.retire", round=rnd.index, rows=len(rnd.rows)):
            t_wait = time.perf_counter()
            with _span("serving.retire.fetch"):
                toks = np.asarray(rnd.toks)
            now = time.perf_counter()
            m = self._metrics
            m["fetch_wait"].observe(now - t_wait)
            # the round is kept once (``self.rounds``), launch to retire,
            # and not as a span a row: 128 rows were 128 span objects
            self.rounds.append((rnd.index, rnd.t0, now, len(rnd.rows)))
            emitted = 0
            for b, s, take in rnd.rows:
                if self.slots[b] is not s:
                    continue
                s.inflight -= take
                req = s.req
                for t in toks[b, :take]:
                    t = int(t)
                    req.new_tokens.append(t)
                    emitted += 1
                    if len(req.new_tokens) >= req.max_new_tokens or \
                            (req.eos_token_id is not None
                             and t == req.eos_token_id):
                        self._finish(b)
                        break           # rest of the chunk is discarded
            self.stats.count_tokens(
                ("decode", self.B, self.chunk, self.P), emitted)
            # a round's time is what it had of the device: from its
            # launch, or from the moment the round ahead of it was read
            m["decode_round_seconds"].observe(
                now - max(rnd.t0, self._t_retired))
            m["tokens"].inc(emitted, phase="decode")
            self._t_retired = now
            live = [s for s in self.slots if s is not None]
            if live:
                cache = self.cache
                ctx = [s.pos + len(s.req.new_tokens) for s in live]
                m["kv_bytes_per_token"].set(
                    (sum(len(s.pages) for s in live) * cache.page_bytes
                     + len(live) * cache.ring * cache.window_page_bytes)
                    / sum(ctx))
                if self._key_selection:
                    m["sparse_selected_share"].set(
                        sum(min(c, self._key_selection) for c in ctx)
                        / sum(ctx))
                if cache.window:
                    m["window_ring_fill"].set(
                        sum(min(cache.pages_for(c), cache.ring)
                            for c in ctx)
                        / (len(live) * cache.ring))

    def _drain(self):
        """Retire the round in flight, if any: whoever reads or
        reshuffles slots, pages or counters calls this first, so the
        host's view is current."""
        rnd, self._inflight = self._inflight, None
        if rnd is not None:
            self._retire_round(rnd)

    def _finish(self, b: int):
        """Evict a finished row: one reference dropped per page
        (registered pages park on the cache LRU, the rest return to
        the free list), table row to all-trash, slot open for
        backfill."""
        slot = self.slots[b]
        self.cache.release_row(b, slot.pages)
        self.slots[b] = None
        self.finished[slot.req.rid] = slot.req
        req = slot.req
        req.t_finish = time.perf_counter()
        m = self._metrics
        m["requests"].inc(event="evicted")
        if len(req.new_tokens) > 1 and req.t_first_token:
            m["tpot"].observe((req.t_finish - req.t_first_token)
                              / (len(req.new_tokens) - 1))
        tr = self._live_traces.pop(req.rid, None)
        if tr is not None:
            sp = tr.end("decode", req.t_finish)
            if sp is not None:
                m["stage_seconds"].observe(sp.seconds, stage="decode")
                if slot.first_round is not None:
                    sp.meta.update(first_round=slot.first_round,
                                   last_round=slot.last_round)
            tr.meta["new_tokens"] = len(req.new_tokens)
            tr.add("e2e", req.t_submit, req.t_finish)
            m["stage_seconds"].observe(req.t_finish - req.t_submit,
                                       stage="e2e")
            self.traces.add(tr)
        if self.debug:
            self.check_invariants()

    # -- disaggregated prefill/decode hooks (inference/disagg.py) --------
    def prefix_match(self, hashes: List[int]) -> int:
        """Leading page-aligned prompt chunks whose KV this replica's
        prefix cache already holds — the router's affinity signal
        (computed over the SAME rolling hashes, ``cache.prefix_hashes``,
        pages are registered under)."""
        return len(self.cache.match_prefix(hashes)[0])

    def migratable(self) -> List[int]:
        """rids parked for migration on a prefill replica: prompt
        fully prefilled, first token committed, KV pages held for
        export to a decode replica."""
        return [s.req.rid for s in self.slots
                if s is not None and s.state == "migrate"]

    def can_import(self, prompt_len: int, max_new_tokens: int) -> bool:
        """Whether import_request would accept a request of this
        geometry RIGHT NOW (a free slot plus its full page footprint).
        False is the backpressure signal the disagg layer acts on."""
        if any(s is None for s in self.slots):
            return self.cache.pages_for(prompt_len + max_new_tokens) \
                <= self.cache.available()
        return False

    def export_request(self, rid: int) -> Dict[str, Any]:
        """Export one migratable row: the committed KV page payloads
        and its block-table row (``PagedKVCache.export_row``), and the
        host request state; the row is then evicted (pages released,
        slot open for backfill). Delivery framing — crc32 per page,
        wire-byte booking — lives in inference/disagg.py."""
        self._drain()
        b = next((i for i, s in enumerate(self.slots)
                  if s is not None and s.state == "migrate"
                  and s.req.rid == rid), None)
        enforce(b is not None,
                f"rid {rid} is not parked for migration")
        s = self.slots[b]
        req = s.req
        k = self.cache.pages_for(len(req.prompt))  # with committed KV
        payloads, table_row = self.cache.export_row(b, s.pages[:k])
        now = time.perf_counter()
        tr = self._live_traces.pop(rid, None)
        if tr is not None:
            tr.end("decode", now)
            tr.add("migrate_out", now, now, {"pages": k})
            self.traces.add(tr)
        pkg = {"rid": rid, "prompt": req.prompt,
               "max_new_tokens": req.max_new_tokens,
               "eos_token_id": req.eos_token_id,
               "new_tokens": list(req.new_tokens),
               "t_submit": req.t_submit,
               "t_first_token": req.t_first_token,
               "trace_id": req.trace_id, "parent_span_id": req.span_id,
               "pages": payloads, "table_row": table_row}
        self.cache.release_row(b, s.pages)
        self.slots[b] = None
        self._metrics["requests"].inc(event="migrated_out")
        if self.debug:
            self.check_invariants()
        return pkg

    def import_request(self, pkg: Dict[str, Any]) -> Optional[int]:
        """Adopt a migrated request on a decode replica: allocate its
        full page footprint, write the committed page payloads
        (``PagedKVCache.import_row``), and park the row mid-decode
        exactly where the prefill replica stopped. Returns the local rid, or None
        when this replica refuses (no free slot / not enough pages) —
        the disagg layer's backpressure signal. crc verification
        happens in inference/disagg.py BEFORE this call."""
        enforce(self.phase != "prefill",
                "a prefill replica cannot adopt migrated rows")
        self._drain()
        prompt = np.asarray(pkg["prompt"], np.int64)
        L, n_new = len(prompt), int(pkg["max_new_tokens"])
        free = [b for b in range(self.B) if self.slots[b] is None]
        need = self.cache.pages_for(L + n_new)
        if not free or need > self.cache.available():
            return None
        b = free[0]
        pages = self.cache.import_row(b, pkg["pages"], need)
        rid = self._next_rid
        self._next_rid += 1
        req = ServingRequest(rid, prompt, n_new, pkg["eos_token_id"],
                             new_tokens=list(pkg["new_tokens"]),
                             t_submit=pkg["t_submit"],
                             t_first_token=pkg["t_first_token"])
        slot = _Slot(req, pages, state="decode", seq=self._admit_seq)
        slot.fed = L
        self._admit_seq += 1
        self.slots[b] = slot
        tr = RequestTrace(rid, meta={"prompt_len": L,
                                     "max_new_tokens": n_new,
                                     "migrated": True},
                          trace_id=pkg.get("trace_id"),
                          parent_span_id=pkg.get("parent_span_id"))
        req.trace_id = tr.trace_id
        req.span_id = tr.span_id
        req.parent_span_id = tr.parent_span_id
        now = time.perf_counter()
        tr.add("migrate_in", now, now, {"pages": len(pkg["pages"])})
        tr.begin("decode", now)    # closed at eviction
        self._live_traces[rid] = tr
        self._metrics["requests"].inc(event="migrated_in")
        if self.debug:
            self.check_invariants()
        return rid

    # -- driving ---------------------------------------------------------
    @property
    def num_active(self) -> int:
        return sum(s is not None for s in self.slots)

    def step(self):
        """One serving tick: admit arrivals, then one shared round —
        legacy mode prefills each arrival at admission (synchronously,
        dispatched behind the decode round in flight) and decodes the
        batch; chunked mode folds pending prompt chunks and decode rows
        into the unified dispatch (_chunked_round).

        A decode round is launched here and retired by the NEXT call
        (class docstring): this call launches round k and then retires
        round k-1, so the tokens of round k reach ``req.new_tokens``
        one call later, and a call with nothing to launch retires what
        is in flight."""
        active = self.num_active
        with _span("serving.step", active=active, queued=len(self.queue)):
            with _span("serving.admit", free_slots=self.B - active):
                self._admit()
            if self.chunked:
                self._chunked_round()
            else:
                self._decode_round()
            with _span("serving.tick"):
                self._note_tick()

    def _note_tick(self):
        """Per-tick occupancy gauges + compile-counter deltas, then one
        registry snapshot into the stall flight-record ring."""
        m = self._metrics
        m["queue_depth"].set(len(self.queue))
        m["active_slots"].set(self.num_active)
        c = self.cache.counts()
        n_free, n_idle, n_reg = c["free"], c["idle"], c["registered"]
        m["free_pages"].set(n_free)
        usable = self.cache.usable       # trash page is never allocable
        # idle cached pages are reclaimable on demand: occupancy
        # reports pages slots actually hold, not cache residue
        m["page_occupancy"].set(
            (usable - n_free - n_idle) / usable if usable else 0.0)
        for cls, n in c["classes"].items():
            if cls == "state":          # slots, not pages
                m["state_slots"].set(n["used"])
                m["state_bytes"].set(
                    n["used"] * self.cache.state_row_bytes)
                continue
            m["kv_pages"].set(n["used"], **{"class": cls, "state": "used"})
            m["kv_pages"].set(n["free"], **{"class": cls, "state": "free"})
        if self.prefix:
            m["prefix_hit_rate"].set(c["hit_rate"])
            m["prefix_pages"].set(n_reg - n_idle, state="active")
            m["prefix_pages"].set(n_idle, state="idle")
            # the hash-table size router prefix-affinity steering
            # reads (idle-list length rides prefix_pages{state=idle})
            m["prefix_hash_entries"].set(n_reg)
        if self._draft is not None:
            pr = self._spec["proposed"]
            m["spec_accept_rate"].set(
                self._spec["accepted"] / pr if pr else 0.0)
            rd = self._spec["rounds"]
            m["spec_tokens_per_step"].set(
                self._spec["committed"] / rd if rd else 0.0)
        rc, rh = self._stats_reported
        if self.stats.compiles > rc:
            m["compiles"].inc(self.stats.compiles - rc, site="serving")
        if self.stats.cache_hits > rh:
            m["cache_hits"].inc(self.stats.cache_hits - rh,
                                site="serving")
        self._stats_reported = (self.stats.compiles,
                                self.stats.cache_hits)
        if self._mem_on:
            lb = _ml.live_bytes()
            if lb:
                self._live_peak = max(self._live_peak, lb)
                m["mem_live"].set(lb)
                m["mem_live_peak"].set(self._live_peak)
        from ..observability import get_registry

        get_registry().snapshot()

    def _run_captured(self, site, fn, *args):
        """Run a compiled program under a comm-ledger capture: when the
        call traces (first execution) its static ledger is stored under
        ``site``; every execution republishes the stored ledger to the
        comm_bytes/comm_ops counters. Single-device programs record
        nothing and publish nothing. With the memory ledger on, the
        site's FIRST execution also stores an XLA memory_analysis of
        the same program (lowered BEFORE the call: the cache buffers
        are donated), republished as mem gauges per execution."""
        # a model's layers record, as they are traced, the form of their
        # attention over the pool and of an expert layer's products:
        # listen while a site runs for the first time
        listen = site not in self._site_programs
        if listen:
            _moestats.begin()
        try:
            return self._run_site(site, fn, *args)
        finally:
            if listen:
                recs = _moestats.drain()
                for key, kept in (("form", self._moe_forms),
                                  ("grouped", self._moe_grouped)):
                    said = {r[key] for r in recs if key in r}
                    if said:
                        kept.setdefault(site[0], set()).update(said)
                if site[0] == "prefill":
                    attn = {r["attention"] for r in recs
                            if "attention" in r}
                    if attn:
                        self._prefill_attention[site[1]] = \
                            "+".join(sorted(attn))
                    self._moe_rows.update(   # the bucket's sorted rows
                        {site[1]: r["rows"] for r in recs if "rows" in r})

    def _run_site(self, site, fn, *args):
        if self._mem_on and site not in self._mem_ledgers:
            self._mem_ledgers[site] = _ml.analyze(
                fn, args, program="_".join(str(s) for s in site))
        if site not in self._site_programs:
            # shapes only (the cache buffers are donated by the call):
            # enough for lowered_text to re-lower this program later
            self._site_programs[site] = (fn, jax.tree_util.tree_map(
                lambda a: jax.ShapeDtypeStruct(
                    a.shape, a.dtype,
                    sharding=getattr(a, "sharding", None))
                if hasattr(a, "shape") else a, args))
        with _cl.capture() as cap:
            out = fn(*args)
        if len(cap):
            self._ledgers[site] = cap
        led = self._ledgers.get(site)
        if led is not None:
            led.publish(self._metrics["comm_bytes"],
                        self._metrics["comm_ops"])
        mled = self._mem_ledgers.get(site)
        if mled is not None:
            mled.publish(self._metrics)
        return out

    def comm_ledger(self, site) -> Optional[Any]:
        """Static comm ledger of a compiled serving program: site is
        ("decode",), ("prefill", seq_bucket), ("unified", chunk_bucket)
        in chunked mode, ("unified_spec", chunk_bucket) /
        ("draft_propose",) / ("draft_chunk", chunk_bucket) with
        speculative decoding, or ("page_copy",) with the prefix
        cache."""
        return self._ledgers.get(site)

    def lowered_text(self, site) -> Optional[str]:
        """StableHLO text of a serving program that has run (site as in
        ``comm_ledger``; ``program_sites()`` lists them), lowered again
        from the same jitted function at the recorded shapes — one extra
        trace, no XLA compile. Each Pallas kernel in it is a
        ``tpu_custom_call`` carrying its ``kernel_name``."""
        low = self._lower(site)
        return None if low is None else low.as_text()

    def compiled_text(self, site) -> Optional[str]:
        """Optimized HLO of the same program, as the backend compiled it
        (layouts, fusions, copies): ``lowered_text`` plus one XLA
        compile, or a hit in the persistent cache."""
        low = self._lower(site)
        return None if low is None else low.compile().as_text()

    @staticmethod
    def pool_copies(hlo_text: str, pool_dims) -> int:
        """Ops named ``copy*`` in optimized HLO whose result has the
        page pool's shape: each is a layout change of a whole pool, so
        its bytes scale with the pool and not with the rows a step
        writes. 0 in every serving program since ``paged_kv_write``."""
        dims = ",".join(str(d) for d in pool_dims)
        return len(re.findall(
            r"^\s*(?:ROOT\s+)?%?copy[\w.\-]*\s*=\s*\w+\["
            + re.escape(dims) + r"\]", hlo_text, re.M))

    @staticmethod
    def donated_params(hlo_text: str) -> List[str]:
        """Names of the entry parameters optimized HLO aliases to an
        output, in parameter order: what the program writes in place.
        The decode program's are the pools (and an expert model's
        counters), never its round array."""
        head = hlo_text.split("\n", 1)[0]
        idx = sorted({int(n) for n in re.findall(
            r"\{[\d, ]*\}: \((\d+), ", head)})
        sig = re.search(r"^ENTRY [^(]*\((.*?)\) -> ", hlo_text, re.M)
        names = [a.split(":")[0] for a in sig.group(1).split(", ")]
        return [names[i] for i in idx]

    def _lower(self, site):
        prog = self._site_programs.get(site)
        if prog is None:
            return None
        fn, avals = prog
        return fn.lower(*avals)

    def program_sites(self) -> List[Any]:
        """Sites of every compiled serving program run so far."""
        return list(self._site_programs)

    # -- memory accounting (observability/memledger) ---------------------
    def memory_ledger(self, site=("decode",)) -> Optional[Any]:
        """Static HBM memory ledger of a compiled serving program
        (site as in ``comm_ledger``); populated at the site's first
        execution when the engine was built with ``mem_ledger=True``
        (or PADDLE_TPU_MEM_LEDGER=1)."""
        return self._mem_ledgers.get(site)

    def memory_summary(self) -> Dict[str, Any]:
        """The serving memory section bench lines carry: every
        analyzed executable's byte classes plus the measured resident
        state (params + the KV page pool, with the per-page byte cost
        and pool geometry the "auto" sizing uses)."""
        return {
            "executables": {led.program: led.to_dict()
                            for led in self._mem_ledgers.values()},
            "state": {
                "params_bytes": sum(_ml.shard_bytes(p._value)
                                    for p in self.pred._params),
                "kv_pool_bytes": self.cache.pool_bytes(),
                "page_bytes": self.cache.page_bytes,
                "pool_pages": self.P,
                # a model with window layers: kv_pool_bytes ==
                # page_bytes * pool_pages
                # + window_page_bytes * window_pool_pages
                "window_page_bytes": self.cache.window_page_bytes,
                "window_pool_pages": self.cache.Pw,
                "window_ring": self.cache.ring,
                # a model with state layers: one slot a row beside the
                # pages, state_bytes == state_row_bytes * max_batch
                "state_bytes": self.cache.state_bytes(),
                "state_row_bytes": self.cache.state_row_bytes,
                "live_peak_bytes": self._live_peak,
            },
        }

    def release_pools(self) -> None:
        """Give the page pools' device memory back while the engine and
        its model are still held (a caller that needs the HBM for
        another program over the same weights). The engine serves
        nothing after this."""
        self._drain()
        self.cache.release()

    def _device_counters(self) -> np.ndarray:
        self._drain()
        return np.stack([np.asarray(a) for a in self.cache.counters]
                        ).astype(np.int64)

    def selection_stats(self) -> Optional[Dict[str, int]]:
        """What the DECODE steps of a model that selects keys counted on
        the device beside the routing counters, fetched now: ``rows`` =
        (row, layer) pairs that selected (every row of the decode batch,
        live or not, in every step since the engine was built) and
        ``kept_keys_wrong`` = those whose kept count was not ``min(t +
        1, topk)``. None for a model that selects nothing."""
        if not self._key_selection or self.cache.counters is None:
            return None
        c = self._device_counters()
        return {"kept_keys_wrong": int(c[:, -2].sum()),
                "rows": int(c[:, -1].sum())}

    def prefill_attention_forms(self) -> Dict[int, str]:
        """``{prefill bucket: form}``: how each prefill program this
        engine traced attends to its prompt, as the model's layers
        recorded it (``models/llama.py::_paged_attention``,
        ``models/hybrid_moe.py``): "flash" (causal flash attention over
        the K/V the layer has just written, under a window on a hybrid
        model's window layers), "paged" (the block-table kernel over the
        pool), "dense" (the pool's pages gathered, plain XLA) or
        "blockwise" (a hybrid model's prompt in ``lax`` blocks:
        ``ops/blockwise_attention.py``); layers that differ give both
        words joined by "+". A bucket whose
        program an earlier engine of the same predictor traced, or a
        model that records none, is absent. The ``serving.prefill`` span
        carries the same word as ``attention`` ("unrecorded" for an
        absent bucket, its first prefill among them: a span's fields are
        fixed when it opens, before that program is traced)."""
        return dict(sorted(self._prefill_attention.items()))

    def moe_stats(self) -> Optional[Dict[str, Any]]:
        """Routing counters of an expert model's decode steps, fetched
        from the device now (the only time the host reads them; no step
        waits for this): per layer, the routed pairs each held expert
        took, the pairs that went to experts held elsewhere, the pairs
        whose product was computed and summed, and the tokens seen
        (every row of the decode batch, live or not: a dead row is
        routed like any other). ``zero_pairs`` = the pairs that chose an
        IDENTITY expert (a layer with ``zero_expert_num``; 0 elsewhere):
        they cost no product, are held by nobody and absent nowhere, and
        feed the gauge ``paddle_tpu_moe_zero_pick_share``. ``dropped`` =
        the pairs the router sent to a held expert (tokens x k less the
        absent and the identity ones) that the grouped products did not
        cover; the layer has no capacity, so anything but 0 is a fault
        of the products' bookkeeping.
        ``forms`` = the form the expert layers took in the decode
        program and in the prefill programs (``routed_form``: "batched"
        | "sorted"; both joined by "+" if the buckets differ), as they
        recorded it when this engine traced them; None for a kind it
        has not traced. ``grouped`` = beside it, the grouped product the
        sorted form's calls took there (``moe_layer.grouped_product``:
        "pallas" = ``ops/pallas/grouped_matmul.py`` | "xla" =
        ``lax.ragged_dot``; None where no program of the kind was traced
        in the sorted form). ``rows`` = for each prefill bucket traced in
        the sorted form, (the sorted rows its expert layers hold at a time,
        ``moe_layer.sorted_rows``; the bucket's routed pairs). None for
        a model without routed experts."""
        if self.cache.counters is None:
            return None
        c = self._device_counters()
        if self._key_selection:         # selection_stats()' two slots
            c = c[:, :-2]
        mcfg = self.pred._model.config
        k = int(getattr(mcfg, "num_experts_per_tok", 0))
        # [pairs a held expert .., absent, summed, (identity,) tokens]
        z = 1 if getattr(mcfg, "zero_expert_num", 0) else 0
        tokens = c[:, -1]
        zero = c[:, -2] if z else np.zeros_like(tokens)
        absent, summed = c[:, -3 - z], c[:, -2 - z]
        out = {"pairs": c[:, :-3 - z], "absent_pairs": absent,
               "summed_pairs": summed, "zero_pairs": zero,
               "tokens": tokens,
               "dropped": int((tokens * k - absent - summed - zero).sum())}
        if z and tokens.any():
            self._metrics["moe_zero_pick_share"].set(
                float(zero.sum()) / (float(tokens.sum()) * k))
        said = lambda kept: {kind: "+".join(sorted(kept.get(kind, ())))
                             or None for kind in ("decode", "prefill")}
        return {**out, "forms": said(self._moe_forms),
                "grouped": said(self._moe_grouped),
                "rows": dict(sorted(self._moe_rows.items()))}

    def roofline_report(self):
        """Roofline verdict of the shared decode round
        (memledger.roofline): FLOPs from the 2N-per-token forward over
        the full B x chunk round, HBM traffic from the decode
        executable's memory ledger, ICI from its comm ledger's wire
        bytes, against the median measured round time. Serving decode
        is expected HBM-bound on chip (the weight-bandwidth
        roofline)."""
        cfg = getattr(self.pred._model, "config", None)
        n_params = None
        fn = getattr(cfg, "num_params", None)
        if callable(fn):
            try:
                n_params = int(fn())
            except Exception:
                n_params = None
        if n_params is None:
            n_params = sum(
                int(np.prod(p._value.shape)) for p in self.pred._params)
        n_dev = max(jax.device_count(), 1)
        fl = 2.0 * n_params * self.B * self.chunk / n_dev
        led = self._mem_ledgers.get(("decode",))
        traffic = led.traffic_bytes if led is not None and \
            led.available else 0.0
        comm = self._ledgers.get(("decode",))
        wire = comm.bytes_for() if comm is not None else 0.0
        step_s = self._metrics["decode_round_seconds"].percentile(50)
        return _ml.roofline(
            step_seconds=step_s, flops_per_step=fl,
            hbm_traffic_bytes=traffic, wire_bytes=wire,
            device=jax.devices()[0], program="decode")

    # -- per-request traces ----------------------------------------------
    def request_traces(self) -> List[Dict[str, Any]]:
        """Finished request traces (bounded ring), oldest first — each
        with its queued/prefill/decode/e2e spans; ``decode`` carries
        ``first_round``/``last_round``, indices into ``self.rounds``
        (chunked and speculative rounds also leave a ``decode_round``
        span a row: ``_unified_round``)."""
        return self.traces.to_dicts()

    def export_request_traces(self, path: Optional[str] = None
                              ) -> Dict[str, Any]:
        """Chrome-trace JSON (chrome://tracing / Perfetto) of the
        finished request traces plus any still in flight, and ONE
        ``engine`` lane with the last decode rounds (``self.rounds``:
        ``decode_round`` events, launch to retire, each overlapping the
        next); writes to
        ``path`` when given and returns the trace dict. Every event's
        args carry the request's ``trace_id``/``span_id`` (and
        ``parent_span_id`` when the caller supplied one), so traces
        exported by different replicas stitch on ``trace_id``."""
        rounds = [("decode_round", t0, t1, {"round": i, "rows": rows})
                  for i, t0, t1, rows in self.rounds]
        return self.traces.to_chrome_trace(
            path, extra=list(self._live_traces.values()),
            lanes={"engine": rounds})

    def trace_context(self, rid: int) -> Optional[Dict[str, Any]]:
        """The W3C trace identity of one request — live or finished —
        or None for an unknown rid. ``traceparent`` is the header a
        router propagates to the NEXT hop (it names this request's
        root span as the parent)::

            {"trace_id", "span_id", "parent_span_id", "traceparent"}
        """
        tr = self._live_traces.get(rid)
        if tr is not None:
            return {"trace_id": tr.trace_id, "span_id": tr.span_id,
                    "parent_span_id": tr.parent_span_id,
                    "traceparent": tr.traceparent}
        req = self.finished.get(rid)
        if req is not None and req.trace_id is not None:
            return {"trace_id": req.trace_id, "span_id": req.span_id,
                    "parent_span_id": req.parent_span_id,
                    "traceparent": req.traceparent}
        return None

    def metrics_snapshot(self):
        """Current registry snapshot (TTFT/TPOT histograms, occupancy,
        counters): the in-process API."""
        self._note_tick()
        from ..observability import get_registry

        return get_registry().snapshot()

    def run(self, max_steps: Optional[int] = None
            ) -> Dict[int, ServingRequest]:
        """Drain the queue + in-flight batch; returns {rid: request}.
        Whether it ran to the end or to ``max_steps``, no decode round
        is left in flight: what ``finished`` and every ``new_tokens``
        show is current."""
        steps = 0
        while self.queue or self.num_active:
            self.step()
            steps += 1
            if max_steps is not None and steps >= max_steps:
                break
        self._drain()
        return self.finished
