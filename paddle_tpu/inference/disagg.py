"""Disaggregated prefill/decode serving: live KV page migration.

Long prompts are compute-bound and decode is HBM-bound (the Gemma-on-
TPU serving roofline split in PAPERS.md), so co-locating both phases on
one replica always leaves one resource idle. This module splits a
serving fleet by phase: ``phase="prefill"`` replicas run the chunked
``[B, Sc]`` unified step at full MFU and park each request the moment
its first token samples; a :class:`KVMigrator` then streams the
request's committed KV pages to a ``phase="decode"`` replica running
the cheap fused decode scan at high batch. The Ragged Paged Attention
paper's location-independent page indirection is what makes the pages
movable at all — a migrated page is just a pool row plus a block-table
entry on the receiving side.

Wire format (``pack_migration`` / ``unpack_migration``): one
``[2*layers, kv_heads, page, head_dim]`` payload array per committed
page, each crc32-checked with the SAME shard codec the checkpoint
writer/loader uses (``distributed.checkpoint.array_crc32``), plus the
row's block table and the host request state (prompt, committed
tokens, trace identity). A crc mismatch raises
:class:`MigrationCorruptError` and the request is retried on a fresh
replica — exact, because a greedy prefill restart recommits the same
first token.

Byte accounting is ledger-exact at a closed form per request::

    wire_bytes = committed_pages * page_bytes + block_table_row_bytes

Every migration books its payload on the comm ledger
(observability/commledger) as point-to-point ``ppermute`` records
under the ``migrate`` axis — ``wire_bytes("ppermute", payload) ==
payload`` — so ``paddle_tpu_comm_bytes_total{axis="migrate"}`` and
``paddle_tpu_serving_migration_bytes_total`` pin to the closed form
exactly.

Backpressure: a decode replica refuses a migration (``can_import``
False — no free slot or pages) and the row simply stays parked on its
prefill replica, holding its pages. A page-starved prefill replica
then stalls admissions and, when nothing else can move, bounces its
youngest mid-prefill row back to the queue head (PR 12's preemption) —
no token has been sampled for that row, so the restart is exact.

Compile stability: export reads pages through the engine's ONE
compiled page-read program (traced src index) and import writes them
through the ONE page-write program (traced dst index), so a warmed
fleet migrates with ZERO additional XLA compiles on either replica
kind.
"""
from __future__ import annotations

import time
from typing import Any, Dict, List, Optional

import numpy as np

from ..distributed.checkpoint import array_crc32
from ..observability import commledger as _cl
from ..observability.catalog import serving_metrics as _serving_metrics

__all__ = ["KVMigrator", "MigrationCorruptError", "pack_migration",
           "unpack_migration", "migration_nbytes", "MIGRATE_AXES"]

# the comm-ledger axis migrations are booked under (point-to-point
# page moves between replicas — ppermute semantics: wire == payload)
MIGRATE_AXES = ("migrate",)


class MigrationCorruptError(RuntimeError):
    """A transferred KV page payload failed its crc32 — the migration
    is dropped and the request retried on a fresh replica."""


def migration_nbytes(pkg: Dict[str, Any]) -> int:
    """The closed form for one request's migration wire bytes:
    committed pages x page_bytes + the block-table row."""
    return int(sum(int(a.nbytes) for a in pkg["pages"])
               + int(pkg["table_row"].nbytes))


def pack_migration(pkg: Dict[str, Any]) -> Dict[str, Any]:
    """Frame an exported request for the wire: contiguous page
    payloads with one crc32 each (the checkpoint shard codec) plus
    the closed-form byte count."""
    pages = [np.ascontiguousarray(a) for a in pkg["pages"]]
    table = np.ascontiguousarray(pkg["table_row"])
    wire = dict(pkg)
    wire["pages"] = pages
    wire["table_row"] = table
    wire["page_crc32"] = [array_crc32(a) for a in pages]
    wire["wire_bytes"] = int(sum(a.nbytes for a in pages)
                             + table.nbytes)
    return wire


def unpack_migration(wire: Dict[str, Any]) -> Dict[str, Any]:
    """Verify every page payload against its recorded crc32 (exactly
    like a checkpoint shard on load); raises
    :class:`MigrationCorruptError` on the first mismatch."""
    for j, (a, want) in enumerate(zip(wire["pages"],
                                      wire["page_crc32"])):
        got = array_crc32(a)
        if got != want:
            raise MigrationCorruptError(
                f"KV page payload {j} failed its crc32 ({got:#010x} "
                f"!= recorded {want:#010x}) — dropping the migration "
                "so the request can be retried on a fresh replica")
    return wire


def _retry_info(pkg: Dict[str, Any]) -> Dict[str, Any]:
    """What a router needs to resubmit a failed migration's request
    from scratch (greedy prefill restart is exact)."""
    return {"prompt": pkg["prompt"],
            "max_new_tokens": pkg["max_new_tokens"],
            "eos_token_id": pkg["eos_token_id"],
            "trace_id": pkg["trace_id"],
            "parent_span_id": pkg["parent_span_id"]}


class KVMigrator:
    """Streams committed KV pages from prefill replicas to decode
    replicas. ``pump(prefill_replicas)`` is one migration tick: every
    migratable row either moves to an accepting decode replica or
    stays parked (backpressure). Returns one event dict per attempted
    migration: ``{"status": "ok", "src", "src_rid", "dst",
    "dst_rid"}``, or ``{"status": "crc_error" | "refused", "src",
    "src_rid", "request": <resubmit info>}``."""

    def __init__(self, decode_replicas: List[Any]):
        self.decode = list(decode_replicas)
        self._metrics = _serving_metrics()
        # cumulative wire bytes, pinned to the per-request closed form
        self.wire_bytes = 0
        self.migrated = 0

    def _pick(self, prompt_len: int, max_new_tokens: int):
        """The least-loaded decode replica that can adopt this
        geometry right now, or None (backpressure)."""
        cands = [e for e in self.decode
                 if e.can_import(prompt_len, max_new_tokens)]
        if not cands:
            return None
        return max(cands, key=lambda e: e.cache.available())

    def _transmit(self, wire: Dict[str, Any]) -> Dict[str, Any]:
        """The wire seam: in-process fleets hand the frame over
        directly; a cross-host transport (or a fault-injecting test)
        overrides this."""
        return wire

    def pump(self, prefill_replicas: List[Any]) -> List[Dict[str, Any]]:
        """One migration tick over the prefill side of the fleet."""
        events = []
        for peng in prefill_replicas:
            for rid in list(peng.migratable()):
                s = next(s for s in peng.slots
                         if s is not None and s.req.rid == rid)
                dst = self._pick(len(s.req.prompt),
                                 s.req.max_new_tokens)
                if dst is None:
                    # row stays parked holding its pages; the prefill
                    # replica's own stall/preempt machinery throttles
                    self._metrics["migrations"].inc(result="refused")
                    continue
                events.append(self._migrate(peng, rid, dst))
        return events

    def _migrate(self, src, rid: int, dst) -> Dict[str, Any]:
        t0 = time.perf_counter()
        pkg = src.export_request(rid)
        wire = self._transmit(pack_migration(pkg))
        nbytes = int(wire["wire_bytes"])
        # ledger-exact booking: every byte on the migration wire is a
        # point-to-point page move, recorded like any collective —
        # ppermute wire == payload, so the ledger total IS the closed
        # form pages x page_bytes + block-table row
        with _cl.capture() as led:
            for arr in wire["pages"]:
                _cl.note("ppermute", MIGRATE_AXES, arr.shape,
                         arr.dtype, p=2)
            _cl.note("ppermute", MIGRATE_AXES, wire["table_row"].shape,
                     wire["table_row"].dtype, p=2)
        led.publish(self._metrics["comm_bytes"],
                    self._metrics["comm_ops"])
        self.wire_bytes += nbytes
        self._metrics["migration_bytes"].inc(nbytes)
        try:
            pkg2 = unpack_migration(wire)
        except MigrationCorruptError as e:
            self._metrics["migrations"].inc(result="crc_error")
            return {"status": "crc_error", "src": src, "src_rid": rid,
                    "error": str(e), "request": _retry_info(pkg)}
        nrid = dst.import_request(pkg2)
        if nrid is None:
            # the capacity check raced an admission on the decode
            # replica; the export already evicted the row, so the
            # request restarts from scratch like a corrupt frame
            self._metrics["migrations"].inc(result="refused")
            return {"status": "refused", "src": src, "src_rid": rid,
                    "request": _retry_info(pkg)}
        self.migrated += 1
        self._metrics["migrations"].inc(result="ok")
        self._metrics["migration_seconds"].observe(
            time.perf_counter() - t0)
        return {"status": "ok", "src": src, "src_rid": rid,
                "dst": dst, "dst_rid": nrid}
