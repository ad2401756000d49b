"""Disaggregated prefill/decode serving (ISSUE 20): the multi-replica
front door (inference/router.py) + live KV page migration
(inference/disagg.py).

The contract under test, end to end on the 8-vdev CPU harness:

- **Bit-identical streams**: a phase-split fleet (1 prefill + 1 decode
  replica) serving a bursty Poisson arrival trace emits EXACTLY the
  token streams a unified fleet (2 co-located replicas) emits for the
  same arrivals — migration moves KV pages bit-exact, greedy decode is
  deterministic, so disaggregation is a pure scheduling change.
- **Ledger-exact migration bytes**: every request's migration wire
  traffic pins to the closed form ``ceil(L/page) * page_bytes +
  block_table_row_bytes``, booked through the comm ledger as
  ``ppermute`` records under the ``migrate`` axis AND on the
  ``paddle_tpu_serving_migration_bytes_total`` counter.
- **CRC on every page**: each migrated page payload carries the SAME
  crc32 shard codec checkpoints use; a corrupted frame is detected,
  dropped, and the request retried on a FRESH prefill replica with the
  same trace identity — final tokens still bit-identical.
- **Zero post-warmup recompiles** on BOTH replica kinds: export reads
  pages through the one compiled page-read program, import writes
  through the one page-write program.
- **Routing policy**: health (in-process + FleetCollector overlay)
  filters, prefix affinity steers shared-prefix traffic to the replica
  already holding the pages, least-loaded breaks ties; placement books
  ``paddle_tpu_router_requests_total{replica, decision}``.
- **Trace stitching**: the router's traceparent follows the request
  across prefill -> migrate -> decode, so per-replica traces stitch on
  one trace_id.

Plus the ISSUE 20 satellites: malformed client traceparent mints a
fresh id (counted, never raised), the prefix-cache hash-table gauge,
and the tpulint zero-finding pin on the two new files.
"""
import json
import sys
import threading
import time
import urllib.request
from pathlib import Path

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.inference import (Config, KVMigrator, MigrationCorruptError,
                                  Router, RouterServer, ServingEngine,
                                  create_predictor)
from paddle_tpu.inference.disagg import (MIGRATE_AXES, migration_nbytes,
                                         pack_migration, unpack_migration)
from paddle_tpu.models.llama import LlamaForCausalLM, llama_tiny
from paddle_tpu.observability.catalog import serving_metrics
from paddle_tpu.observability.spans import (format_traceparent,
                                            make_span_id, make_trace_id)

REPO = Path(__file__).resolve().parent.parent
if str(REPO) not in sys.path:                 # direct pytest invocation
    sys.path.insert(0, str(REPO))

PAGE = 8


@pytest.fixture(scope="module")
def model():
    paddle.seed(11)
    return LlamaForCausalLM(llama_tiny())


def _engine(model, phase=None, **kw):
    kw.setdefault("max_batch", 4)
    kw.setdefault("prefill_chunk", 16)
    kw.setdefault("pool_pages", 32)
    pred = create_predictor(
        Config().set_model(model).enable_paged_kv(page_size=PAGE))
    return ServingEngine(pred, phase=phase, **kw)


def _poisson_trace(n, rate=1.5, seed=5):
    """Bursty Poisson arrivals: [(arrival_step, prompt, n_new)]."""
    r = np.random.RandomState(seed)
    t, out = 0.0, []
    for _ in range(n):
        t += r.exponential(1.0 / rate)
        out.append((int(t), r.randint(1, 256, (int(r.randint(4, 30)),)),
                    int(r.randint(2, 7))))
    return out


def _drive(router, trace):
    """Feed the arrival trace into the router on its step clock; drain;
    returns {trace_index: ServingRequest}."""
    gids = {}
    step = i = 0
    while i < len(trace) or router.pending:
        while i < len(trace) and trace[i][0] <= step:
            _, prompt, n_new = trace[i]
            gids[i] = router.submit(prompt, max_new_tokens=n_new)
            i += 1
        router.step()
        step += 1
        assert step < 5000, "fleet wedged"
    return {k: router.result(g) for k, g in gids.items()}


def _page_bytes(eng):
    mcfg = eng.pred._model.config
    return (2 * mcfg.num_layers * mcfg.num_kv_heads * PAGE
            * mcfg.head_dim * np.dtype(eng._dtype).itemsize)


# ---------------------------------------------------------------------------
# the tentpole: phase-split fleet == unified fleet, bit for bit
# ---------------------------------------------------------------------------
class TestDisaggParity:
    def test_bursty_trace_bit_identical_exact_bytes_zero_recompiles(
            self, model):
        trace = _poisson_trace(10)

        # unified fleet: 2 co-located replicas behind the same router
        uni = Router([("u0", _engine(model)), ("u1", _engine(model))])
        base = _drive(uni, trace)

        # disaggregated fleet: 1 prefill + 1 decode
        peng = _engine(model, phase="prefill")
        deng = _engine(model, phase="decode")
        rt = Router([("prefill0", peng), ("decode0", deng)])
        m = serving_metrics()
        mig_bytes0 = m["migration_bytes"].value()
        comm0 = m["comm_bytes"].value(axis="migrate", op="ppermute")

        # warm both replica kinds through the full path, then pin
        warm = rt.submit(np.arange(1, 20), max_new_tokens=4)
        rt.run()
        assert rt.result(warm) is not None
        pw, dw = peng.stats.compiles, deng.stats.compiles

        got = _drive(rt, trace)

        # 1) bit-identical committed token streams, request by request
        assert {k: list(r.new_tokens) for k, r in got.items()} \
            == {k: list(r.new_tokens) for k, r in base.items()}
        # every request flowed through migration (none decoded locally)
        assert rt.migrator.migrated == len(trace) + 1

        # 2) wire bytes pin to the closed form, on the migrator, the
        #    migration counter, AND the comm ledger's migrate axis
        pb = _page_bytes(peng)
        want = sum((-(-len(p) // PAGE)) * pb + peng.cache.npages * 4
                   for _, p, _ in trace)
        want += (-(-19 // PAGE)) * pb + peng.cache.npages * 4   # the warmup
        assert rt.migrator.wire_bytes == want
        assert m["migration_bytes"].value() - mig_bytes0 == want
        assert m["comm_bytes"].value(axis="migrate",
                                     op="ppermute") - comm0 == want

        # 3) zero post-warmup recompiles on BOTH replica kinds
        assert peng.stats.compiles == pw
        assert deng.stats.compiles == dw

        # phase occupancy gauge exists and was swept back to idle
        assert m["phase_slots"].value(phase="prefill") == 0
        assert m["phase_slots"].value(phase="decode") == 0

    def test_migration_wire_format_crc_roundtrip(self, model):
        peng = _engine(model, phase="prefill")
        deng = _engine(model, phase="decode")
        rt = Router([("p0", peng), ("d0", deng)])
        gid = rt.submit(np.arange(1, 18), max_new_tokens=3)
        # run prefill only until the row parks for migration
        steps = 0
        while not peng.migratable():
            peng.step()
            steps += 1
            assert steps < 200
        pkg = peng.export_request(peng.migratable()[0])
        # payload geometry: one [2L, kv_heads, page, head_dim] per page
        mcfg = model.config
        assert [a.shape for a in pkg["pages"]] == \
            [(2 * mcfg.num_layers, mcfg.num_kv_heads, PAGE,
              mcfg.head_dim)] * (-(-17 // PAGE))
        wire = pack_migration(pkg)
        assert wire["wire_bytes"] == migration_nbytes(pkg)
        assert len(wire["page_crc32"]) == len(wire["pages"])
        assert unpack_migration(wire) is wire    # clean frame passes
        # a single flipped byte in any page is caught
        bad = dict(wire)
        tampered = [a.copy() for a in wire["pages"]]
        tampered[-1].view(np.uint8).reshape(-1)[0] ^= 0xFF
        bad["pages"] = tampered
        with pytest.raises(MigrationCorruptError):
            unpack_migration(bad)
        del rt, gid

    def test_crc_corruption_detected_and_retried_fresh_replica(
            self, model):
        """A corrupted frame must not lose or corrupt the request: the
        router resubmits it to the OTHER prefill replica (same trace),
        and the final stream is still bit-identical to unified."""
        solo = _engine(model)
        srid = solo.submit(np.arange(1, 22), max_new_tokens=5)
        want = list(solo.run()[srid].new_tokens)

        p0 = _engine(model, phase="prefill")
        p1 = _engine(model, phase="prefill")
        deng = _engine(model, phase="decode")
        rt = Router([("p0", p0), ("p1", p1), ("d0", deng)])

        class _CorruptOnce(KVMigrator):
            def _transmit(self, wire):
                if not getattr(self, "tampered", False):
                    self.tampered = True
                    pages = [a.copy() for a in wire["pages"]]
                    pages[0].view(np.uint8).reshape(-1)[3] ^= 0xFF
                    wire = dict(wire, pages=pages)
                return wire

        rt.migrator = _CorruptOnce(rt.migrator.decode)
        m = serving_metrics()
        crc0 = m["migrations"].value(result="crc_error")
        retry0 = m["router_requests"].value(replica="p1",
                                            decision="retry")

        tp = format_traceparent(make_trace_id(), make_span_id())
        gid = rt.submit(np.arange(1, 22), max_new_tokens=5,
                        traceparent=tp)
        res = rt.run(max_steps=2000)
        req = res[gid]
        assert list(req.new_tokens) == want
        assert m["migrations"].value(result="crc_error") - crc0 == 1
        # both empty replicas tie on load, so the first placement goes
        # to p0 and the retry MUST land on the fresh replica p1
        assert m["router_requests"].value(replica="p1",
                                          decision="retry") - retry0 == 1
        # the retried request kept the router's trace identity
        assert req.trace_id == tp.split("-")[1]

    def test_decode_backpressure_parks_rows_until_capacity(self, model):
        """A saturated decode replica refuses imports; parked rows keep
        their pages on the prefill side and drain as capacity frees —
        nothing is lost, everything stays bit-identical."""
        solo = _engine(model)
        prompts = [np.arange(1 + i, 15 + i) for i in range(5)]
        want = []
        for p in prompts:
            rid = solo.submit(p, max_new_tokens=6)
            want.append(list(solo.run()[rid].new_tokens))

        peng = _engine(model, phase="prefill")
        deng = _engine(model, phase="decode", max_batch=1)
        rt = Router([("p0", peng), ("d0", deng)])
        m = serving_metrics()
        refused0 = m["migrations"].value(result="refused")
        gids = [rt.submit(p, max_new_tokens=6) for p in prompts]
        res = rt.run(max_steps=3000)
        assert [list(res[g].new_tokens) for g in gids] == want
        # the 1-slot decode replica must actually have pushed back
        assert m["migrations"].value(result="refused") > refused0

    def test_trace_stitches_across_prefill_migrate_decode(self, model):
        peng = _engine(model, phase="prefill")
        deng = _engine(model, phase="decode")
        rt = Router([("p0", peng), ("d0", deng)])
        tid = make_trace_id()
        tp = format_traceparent(tid, make_span_id())
        gid = rt.submit(np.arange(1, 20), max_new_tokens=4,
                        traceparent=tp)
        req = rt.run()[gid]
        # one trace id across both replicas; the decode-side span's
        # parent is the prefill-side request span
        assert req.trace_id == tid
        ptrace = peng.export_request_traces()
        devents = deng.export_request_traces()["traceEvents"]
        pevents = ptrace["traceEvents"]
        assert any(e["args"].get("trace_id") == tid for e in pevents)
        assert any(e["args"].get("trace_id") == tid for e in devents)
        assert any(e["name"] == "migrate_out" for e in pevents)
        assert any(e["name"] == "migrate_in" for e in devents)
        pspan = next(e["args"]["span_id"] for e in pevents
                     if e["args"].get("trace_id") == tid)
        assert req.parent_span_id == pspan


# ---------------------------------------------------------------------------
# the front door: health -> affinity -> least-loaded, HTTP surface
# ---------------------------------------------------------------------------
class TestRouterSteering:
    def test_prefix_affinity_steers_to_warm_replica(self, model):
        e0 = _engine(model, prefix_cache=True)
        e1 = _engine(model, prefix_cache=True)
        rt = Router([("r0", e0), ("r1", e1)])
        m = serving_metrics()
        aff0 = m["router_requests"].value(replica="r0",
                                          decision="affinity")
        sysp = np.arange(1, 1 + 2 * PAGE)       # two full shared pages
        g0 = rt.submit(sysp, max_new_tokens=2)  # cold: least-loaded->r0
        rt.run()
        assert e0.finished and not e1.finished
        tail = np.arange(200, 206)
        g1 = rt.submit(np.concatenate([sysp, tail]), max_new_tokens=2)
        rt.run()
        assert rt.result(g1) is not None
        # the shared-prefix request steered to the replica holding the
        # pages, and actually hit its cache
        assert m["router_requests"].value(replica="r0",
                                          decision="affinity") \
            - aff0 == 1
        assert e0.prefix_cache_stats()["hits"] >= 1
        del g0

    def test_degraded_replica_skipped_until_fleet_wide(self, model):
        e0 = _engine(model)
        e1 = _engine(model)
        rt = Router([("r0", e0), ("r1", e1)])
        e0.health = lambda: "degraded"          # shedding replica
        gid = rt.submit(np.arange(1, 10), max_new_tokens=2)
        rt.run()
        assert rt.result(gid) is not None
        assert e1.finished and not e0.finished
        assert rt.healthz()["status"] == "degraded"
        # a fully-degraded pool still serves (shed beats blackhole)
        e1.health = lambda: "degraded"
        gid2 = rt.submit(np.arange(1, 10), max_new_tokens=2)
        rt.run()
        assert rt.result(gid2) is not None

    def test_collector_overlay_filters_remote_degraded(self, model):
        """A FleetCollector-style overlay (member_health verdicts from
        scraped /healthz + staleness) vetoes replicas the in-process
        signal can't see failing."""
        class _Overlay:
            def __init__(self, bad):
                self.bad = set(bad)

            def member_health(self, name):
                return {"status": "degraded" if name in self.bad
                        else "ok", "reason": "stale"}

        e0, e1 = _engine(model), _engine(model)
        rt = Router([("r0", e0), ("r1", e1)],
                    collector=_Overlay(["r0"]))
        gid = rt.submit(np.arange(1, 12), max_new_tokens=2)
        rt.run()
        assert rt.result(gid) is not None
        assert e1.finished and not e0.finished
        hz = rt.healthz()
        assert hz["status"] == "degraded"
        assert hz["replicas"]["r0"]["health"] == "degraded"

    def test_http_front_door_round_trip(self, model):
        solo = _engine(model)
        srid = solo.submit(np.arange(1, 14), max_new_tokens=3)
        want = list(solo.run()[srid].new_tokens)

        peng = _engine(model, phase="prefill")
        deng = _engine(model, phase="decode")
        rt = Router([("p0", peng), ("d0", deng)])
        tp = format_traceparent(make_trace_id(), make_span_id())
        out = {}

        with RouterServer(rt) as srv:
            def client():
                req = urllib.request.Request(
                    srv.url + "/v1/generate",
                    data=json.dumps(
                        {"prompt": list(range(1, 14)),
                         "max_new_tokens": 3}).encode(),
                    headers={"Content-Type": "application/json",
                             "traceparent": tp})
                with urllib.request.urlopen(req, timeout=60) as r:
                    out["resp"] = json.loads(r.read())

            t = threading.Thread(target=client, daemon=True)
            t.start()
            deadline = time.monotonic() + 60
            while not rt.pending:       # wait for the POST to enqueue
                assert time.monotonic() < deadline
                time.sleep(0.005)
            srv.serve_pending()
            t.join(timeout=30)
            assert not t.is_alive()
            hz = json.loads(urllib.request.urlopen(
                srv.url + "/healthz", timeout=30).read())
            st = json.loads(urllib.request.urlopen(
                srv.url + "/stats", timeout=30).read())
        assert out["resp"]["tokens"] == want
        assert out["resp"]["trace_id"] == tp.split("-")[1]
        assert out["resp"]["shed_reason"] is None
        assert hz["status"] == "ok"
        assert set(hz["replicas"]) == {"p0", "d0"}
        assert st["migrated"] == 1

    def test_decode_replica_refuses_direct_submission(self, model):
        deng = _engine(model, phase="decode")
        with pytest.raises(Exception):
            deng.submit(np.arange(1, 10), max_new_tokens=2)


# ---------------------------------------------------------------------------
# satellites
# ---------------------------------------------------------------------------
class TestTraceParentSatellite:
    def test_malformed_traceparent_mints_fresh_id_and_counts(
            self, model):
        eng = _engine(model)
        m = serving_metrics()
        c0 = m["trace_parse_errors"].value(reason="malformed_traceparent")
        rid = eng.submit(np.arange(1, 10), max_new_tokens=2,
                         trace_id="00-zz-bad-header")
        req = eng.run()[rid]
        assert req.trace_id is not None and len(req.trace_id) == 32
        assert m["trace_parse_errors"].value(
            reason="malformed_traceparent") - c0 == 1

    def test_invalid_bare_trace_id_counts_separately(self, model):
        eng = _engine(model)
        m = serving_metrics()
        c0 = m["trace_parse_errors"].value(reason="invalid_trace_id")
        rid = eng.submit(np.arange(1, 10), max_new_tokens=2,
                         trace_id="nothex")
        req = eng.run()[rid]
        assert req.trace_id is not None and len(req.trace_id) == 32
        assert m["trace_parse_errors"].value(
            reason="invalid_trace_id") - c0 == 1


class TestPrefixGaugeSatellite:
    def test_prefix_hash_entries_gauge_tracks_table(self, model):
        eng = _engine(model, prefix_cache=True)
        eng.submit(np.arange(1, 1 + 3 * PAGE), max_new_tokens=2)
        eng.run()
        m = serving_metrics()
        assert m["prefix_hash_entries"].value() == eng.cache.counts()["registered"]
        assert m["prefix_hash_entries"].value() >= 3


class TestDisaggLintPins:
    def test_new_files_lint_zero_findings(self):
        """The router and the migration wire join serving.py's pinned
        zero-baseline scope: every tpulint rule (shared-mutation and
        blocking-under-lock included) must report NOTHING on them."""
        from tools.tpulint import ALL_RULES, lint_paths

        findings = lint_paths(
            [REPO / "paddle_tpu/inference/router.py",
             REPO / "paddle_tpu/inference/disagg.py"],
            ALL_RULES, root=REPO)
        assert findings == [], "\n".join(
            f"{f.path}:{f.line}: {f.rule}: {f.message}"
            for f in findings)

    def test_new_files_inside_shared_mutation_scope(self):
        from tools.tpulint.rules.shared_mutation import _in_scope

        assert _in_scope("paddle_tpu/inference/router.py")
        assert _in_scope("paddle_tpu/inference/disagg.py")
        assert _in_scope("paddle_tpu/inference/kv_cache.py")

    def test_migrate_axis_vocabulary(self):
        assert MIGRATE_AXES == ("migrate",)
