"""run_report: render a run's goodput waterfall and health-event
timeline from the crash-durable goodput journal.

The goodput ledger (paddle_tpu/observability/goodput.py) journals every
second of a — possibly crash-interrupted — run into
``<checkpoint base>/goodput.jsonl``: closed wall-clock segments from a
fixed taxonomy (compile / step_compute / ckpt_stall / ckpt_async /
restore / recovery_restart / input_wait / idle), process (re)start
markers, and the health monitor's anomaly events. This tool is the
human-facing view:

- **waterfall**: per-segment seconds and percentages of the run's wall
  clock (idle synthesized as the unattributed remainder, ckpt_async
  shown separately as overlapped), plus the headline ``goodput_pct`` =
  productive step seconds / wall seconds — spanning every restart the
  journal absorbed,
- **event timeline**: health events (loss/grad spikes, stalls,
  restart signals) and process restarts in run-relative time.

Usage::

    python -m tools.run_report --run-dir <ckpt base> [--json]
    python -m tools.run_report --merge <host-dir> <host-dir>... [--json]

``--merge`` overlays several hosts' goodput journals into one fleet
waterfall: a per-host lane each (wall / goodput_pct / restarts /
segment split, host = dir basename) plus a combined restart-and-event
timeline on the fleet clock (seconds since the earliest start any
journal recorded). The full cross-host view (step-time skew, byte
totals from metrics.jsonl) lives in tools/fleet_report.py.

Exit codes: 0 on success, 2 when no journal was found. The tool only
reads.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
from typing import Any, Dict, List, Optional

from paddle_tpu.observability import goodput as _gp

__all__ = ["journal_report", "merge_report", "main"]

_BAR_WIDTH = 40


def journal_report(base_or_path: str) -> Optional[Dict[str, Any]]:
    """Summary + timeline of one goodput journal (a checkpoint base
    dir or the journal file itself); None when no journal exists."""
    path = base_or_path
    if os.path.isdir(path):
        path = os.path.join(path, _gp.JOURNAL_NAME)
    if not os.path.isfile(path):
        return None
    records = _gp.read_journal(path)
    if not records:
        return None
    summary = _gp.summarize(records)
    t0 = None
    for r in records:
        if r.get("ev") == "run":
            t0 = float(r["ts"]) if t0 is None else min(t0, float(r["ts"]))
    timeline: List[Dict[str, Any]] = []
    for r in records:
        if r.get("ev") == "run":
            timeline.append({
                "t": round(float(r["ts"]) - (t0 or 0.0), 3),
                "what": "resume" if r.get("resumed") else "start",
                "pid": r.get("pid")})
        elif r.get("ev") == "h":
            e = {"t": round(float(r.get("ts", 0.0)) - (t0 or 0.0), 3),
                 "what": r.get("kind", "event")}
            for k in ("step", "value", "median", "z", "reason"):
                if k in r:
                    e[k] = r[k]
            timeline.append(e)
        elif r.get("ev") == "e" and r.get("seg") == "recovery_restart":
            timeline.append({
                "t": round(float(r["t0"]) - (t0 or 0.0), 3),
                "what": "recovery_restart",
                "seconds": round(float(r["t1"]) - float(r["t0"]), 3)})
    timeline.sort(key=lambda e: e["t"])
    return {"journal": path, "summary": summary, "timeline": timeline}


def merge_report(dirs: List[str]) -> Dict[str, Any]:
    """Overlay several hosts' goodput journals (one dir per host, host
    name = dir basename): per-host lanes plus a combined restart/event
    timeline on the fleet clock (earliest run start = t 0)."""
    hosts: List[Dict[str, Any]] = []
    for d in dirs:
        name = os.path.basename(os.path.normpath(d)) or d
        path = d
        if os.path.isdir(path):
            path = os.path.join(path, _gp.JOURNAL_NAME)
        lane: Dict[str, Any] = {"host": name, "dir": d,
                                "summary": None, "events": []}
        records = _gp.read_journal(path) if os.path.isfile(path) else []
        if records:
            lane["summary"] = _gp.summarize(records)
            for r in records:
                if r.get("ev") == "run":
                    lane["events"].append({
                        "ts": float(r["ts"]),
                        "what": "resume" if r.get("resumed")
                        else "start", "pid": r.get("pid")})
                elif r.get("ev") == "h":
                    e = {"ts": float(r.get("ts", 0.0)),
                         "what": r.get("kind", "event")}
                    for k in ("step", "value", "z", "reason"):
                        if k in r:
                            e[k] = r[k]
                    lane["events"].append(e)
                elif (r.get("ev") == "e"
                        and r.get("seg") == "recovery_restart"):
                    lane["events"].append({
                        "ts": float(r["t0"]),
                        "what": "recovery_restart",
                        "seconds": round(float(r["t1"])
                                         - float(r["t0"]), 3)})
        hosts.append(lane)
    t0 = min((e["ts"] for h in hosts for e in h["events"]),
             default=None)
    timeline: List[Dict[str, Any]] = []
    for h in hosts:
        for e in h["events"]:
            timeline.append({
                "t": round(e["ts"] - (t0 or 0.0), 3), "host": h["host"],
                **{k: v for k, v in e.items() if k != "ts"}})
        h.pop("events", None)
    timeline.sort(key=lambda e: e["t"])
    gp = [h["summary"]["goodput_pct"] for h in hosts if h["summary"]]
    return {
        "hosts": hosts,
        "fleet_goodput_pct": {
            "min": round(min(gp), 2), "max": round(max(gp), 2),
            "mean": round(sum(gp) / len(gp), 2)} if gp else None,
        "timeline": timeline,
    }


def _bar(pct: float) -> str:
    n = int(round(_BAR_WIDTH * min(max(pct, 0.0), 100.0) / 100.0))
    return "#" * n


def _print_merge(rep: Dict[str, Any]) -> None:
    print(f"run_report --merge: {len(rep['hosts'])} host lane(s)")
    width = max((len(h["host"]) for h in rep["hosts"]), default=4)
    for h in rep["hosts"]:
        s = h["summary"]
        if s is None:
            print(f"  {h['host']:<{width}} (no goodput journal under "
                  f"{h['dir']!r})")
            continue
        print(f"  {h['host']:<{width}} wall {s['wall_seconds']:>9.3f}s"
              f"  goodput {s['goodput_pct']:>6.2f}%  restarts "
              f"{s['restarts']}  {_bar(s['goodput_pct'])}")
        segs = sorted(s["segments"].items(), key=lambda kv: -kv[1])
        lane = "  ".join(f"{seg} {s['segment_pct'].get(seg, 0.0):.1f}%"
                         for seg, _ in segs if s["segment_pct"].get(seg))
        if lane:
            print(f"  {'':<{width}}   {lane}")
    if rep["fleet_goodput_pct"]:
        g = rep["fleet_goodput_pct"]
        print(f"  fleet goodput min {g['min']:.2f}%  max {g['max']:.2f}%"
              f"  mean {g['mean']:.2f}%")
    if rep["timeline"]:
        print("\ncombined restart timeline "
              "(t = seconds since earliest start)")
        for e in rep["timeline"]:
            extra = " ".join(f"{k}={e[k]}" for k in
                             ("pid", "step", "value", "z", "seconds",
                              "reason") if k in e)
            print(f"  t+{e['t']:>10.3f}  {e['host']:<{width}} "
                  f"{e['what']:<18} {extra}")


def _print_report(rep: Dict[str, Any]) -> None:
    s = rep["summary"]
    print(f"run_report: {rep['journal']}")
    print(f"  wall {s['wall_seconds']:.3f}s   goodput "
          f"{s['goodput_pct']:.1f}%   restarts {s['restarts']}   "
          f"events {s['events']}")
    print("\ngoodput waterfall (foreground segments sum to wall)")
    segs = sorted(s["segments"].items(), key=lambda kv: -kv[1])
    width = max((len(k) for k, _ in segs), default=8)
    for seg, sec in segs:
        pct = s["segment_pct"].get(seg, 0.0)
        print(f"  {seg:<{width}} {sec:>10.3f}s {pct:>6.2f}% "
              f"{_bar(pct)}")
    if s["overlapped_seconds"]:
        over = "  ".join(f"{k} {v:.3f}s" for k, v in
                         s["overlapped_seconds"].items())
        print(f"  overlapped (off the critical path): {over}")
    if rep["timeline"]:
        print("\nevent timeline (t = seconds since run start)")
        for e in rep["timeline"]:
            extra = " ".join(f"{k}={e[k]}" for k in
                             ("pid", "step", "value", "z",
                              "seconds", "reason") if k in e)
            print(f"  t+{e['t']:>10.3f}  {e['what']:<18} {extra}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(prog="run_report",
                                 description=__doc__.splitlines()[0])
    ap.add_argument("--run-dir", default=None,
                    help="checkpoint base dir (or goodput.jsonl path) "
                         "holding the run's goodput journal")
    ap.add_argument("--merge", nargs="+", default=None,
                    metavar="host-dir",
                    help="overlay several hosts' goodput journals "
                         "(one dir per host) into one fleet waterfall")
    ap.add_argument("--json", action="store_true", dest="as_json",
                    help="emit the report as one JSON doc")
    args = ap.parse_args(argv)

    if args.merge:
        rep = merge_report(args.merge)
        if all(h["summary"] is None for h in rep["hosts"]):
            print("run_report: no goodput journal under "
                  + ", ".join(repr(d) for d in args.merge),
                  file=sys.stderr)
            return 2
        if args.as_json:
            print(json.dumps(rep, indent=1))
        else:
            _print_merge(rep)
        return 0

    rep = journal_report(args.run_dir) if args.run_dir else None
    if rep is None:
        print("run_report: no goodput journal"
              + (f" under {args.run_dir!r}" if args.run_dir else ""),
              file=sys.stderr)
        return 2
    if args.as_json:
        print(json.dumps({"run": rep}, indent=1))
        return 0
    _print_report(rep)
    return 0


if __name__ == "__main__":
    sys.exit(main())
