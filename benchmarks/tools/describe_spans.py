"""What the program's own spans say about the last traced run: the table
to read before writing a perf issue about the host.

    python -m benchmarks.tools.describe_spans [--ops 40] [--json out.json]

1. self time a span name (a span's duration less its children's): where
   the host's time inside ``serving.step`` / ``train.step`` went;
2. the prefills by ``seq_bucket``: span, dispatch and fetch beside the
   device time of the prefill program each one started;
3. the idle closure: the worst device's idle time by the innermost span
   open meanwhile, which adds up to ``device_idle_share``, and how much
   of each line fell BETWEEN programs (no XLA module running: the device
   waited for the host) as against between the ops of a running program
   (the device's own; a blocking fetch collects those while it waits);
4. the train step's scopes: share of busy time under ``forward`` /
   ``backward`` / ``grad_sync`` / ``optimizer``.

``--ops N`` prints N device ops spread over the window with the scope
read from each, its ``tf_op`` and its name as the file has them: look
there first if (4) reads nothing. Get the file with ``--trace 1`` (the
harness's own window) or, outside the benchmark, with
``jax.profiler.start_trace`` around the engine's steps: then there is no
``bench/trace_window`` and the window is the ops' extent.
"""
import argparse
import json
import statistics
import sys

from benchmarks.harness import program_spans as PS
from benchmarks.harness import reduce as R
from benchmarks.harness.xplane import load


def _ms(xs):
    return f"{1e3 * statistics.median(xs):9.3f}" if xs else "        -"


def self_table(prog: PS.Program, out) -> None:
    by_line = {}
    for s in prog.spans:
        by_line.setdefault(s.line, []).append(s)
    own = {}
    for spans in by_line.values():      # nesting is a thread's
        for k, v in PS.self_seconds(spans).items():
            own.setdefault(k, []).extend(v)
    total = {}
    for s in prog.spans:
        total[s.name] = total.get(s.name, 0.0) + s.dur
    out(f"{'span':<28}{'n':>7}{'self s':>10}{'self ms p50':>13}"
        f"{'total s':>10}")
    for k in sorted(own, key=lambda k: -sum(own[k])):
        out(f"{k:<28}{len(own[k]):>7}{sum(own[k]):>10.4f}"
            f"{_ms(own[k]):>13}{total[k]:>10.4f}")


def prefill_table(prog: PS.Program, out) -> None:
    spans = [s for s in prog.spans if s.name == "serving.prefill"]
    if not spans:
        return
    runs = sorted((m for m in prog.modules if "prefill" in m[1]),
                  key=lambda m: m[2])
    kids = {n: sorted((s for s in prog.spans if s.name == n),
                      key=lambda s: s.start)
            for n in ("serving.prefill.dispatch", "serving.prefill.fetch")}
    rows = {}
    j = 0
    for s in sorted(spans, key=lambda s: s.start):
        while j < len(runs) and runs[j][2] < s.start:
            j += 1
        dev = None
        if j < len(runs) and runs[j][2] < s.start + s.dur:
            dev, j = runs[j][3], j + 1
        row = rows.setdefault(s.fields.get("seq_bucket"),
                              {"span": [], "dispatch": [], "fetch": [],
                               "device": [], "tokens": []})
        row["span"].append(s.dur)
        row["tokens"].append(s.fields.get("prompt_tokens", 0))
        for n, key in (("serving.prefill.dispatch", "dispatch"),
                       ("serving.prefill.fetch", "fetch")):
            row[key].extend(k.dur for k in kids[n]
                            if s.start <= k.start < s.start + s.dur)
        if dev is not None:
            row["device"].append(dev)
    out(f"{'seq_bucket':>10}{'n':>6}{'tokens p50':>12}{'span ms':>10}"
        f"{'dispatch':>10}{'fetch':>10}{'device ms':>11}")
    for b in sorted(rows, key=lambda b: (b is None, b)):
        r = rows[b]
        out(f"{str(b):>10}{len(r['span']):>6}"
            f"{statistics.median(r['tokens']):>12.0f}{_ms(r['span']):>10}"
            f"{_ms(r['dispatch']):>10}{_ms(r['fetch']):>10}"
            f"{_ms(r['device']):>11}")


def idle_table(tr: R.Trace, prog: PS.Program, out) -> None:
    idle = PS.idle_by_owner(tr)
    share = R.idle_share(tr)
    if share is None:
        return
    # the same charging over program runs in place of ops: what is idle
    # there is idle with nothing on the device at all
    busy = R.busy_by_device(tr)
    dev = min(busy, key=busy.get)
    runs = R.Trace([R.Op(name, a, d, dev) for dv, name, a, d in prog.modules
                    if dv == dev], tr.spans, tr.window)
    between = PS.idle_by_owner(runs)
    w = tr.window_s
    out(f"device_idle_share {share:.4f}% of a window of {w:.3f} s; by the "
        "innermost span open meanwhile (of which between programs):")
    for k in sorted(idle, key=lambda k: -idle[k]):
        out(f"  {k:<28}{idle[k]:>10.5f} s{100 * idle[k] / w:>9.4f}%"
            f"{100 * between.get(k, 0.0) / w:>9.4f}%")
    out(f"  {'sum':<28}{sum(idle.values()):>10.5f} s"
        f"{100 * sum(idle.values()) / w:>9.4f}%"
        f"{100 * sum(between.values()) / w:>9.4f}%")


def scope_table(prog: PS.Program, window, out) -> None:
    tr = R.Trace(prog.scoped_ops, [], window)
    if not R.busy_by_device(tr):
        return
    named = 0.0
    for s in PS.SCOPES:
        v = R.program_share(tr, s) or 0.0
        named += v
        out(f"  {s:<12}{v:>9.3f}% of busy")
    out(f"  {'(no scope)':<12}{100 - named:>9.3f}%")


def ops_sample(path: str, n: int, out) -> None:
    from jax.profiler import ProfileData

    with open(path, "rb") as f:
        tf_op = PS.op_names(f.read())
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/device:"):
            continue
        for line in plane.lines:
            if line.name != "XLA Ops":
                continue
            evs = list(line.events)
            for e in evs[::max(1, len(evs) // n)][:n]:
                op = tf_op.get(e.name)
                out(f"[{PS.scope_of(op)}] tf_op={op!r} {e.name[:300]}")
        return                  # one device is enough to look at


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--ops", type=int, default=0)
    ap.add_argument("--json")
    args = ap.parse_args()
    path = PS.last_trace()
    if path is None:
        print("no trace found; run a cell with --trace 1 first",
              file=sys.stderr)
        return 1
    prog = PS.read(path, scopes=True)
    base = load(path)
    tr = R.Trace(base.ops, prog.spans, prog.window or base.window)
    lines = []

    def out(s=""):
        lines.append(s)
        print(s)

    out(f"{path}: {len(prog.spans)} spans of the program, "
        f"{len(base.ops)} device ops, window {tr.window_s:.3f} s")
    if not prog.spans:
        out("the program wrote no span of its own (a tree before PR 37?)")
    out("\n-- self time by span --")
    self_table(prog, out)
    out("\n-- prefills by seq_bucket --")
    prefill_table(prog, out)
    out("\n-- idle closure --")
    idle_table(tr, prog, out)
    out("\n-- scopes of the compiled step --")
    scope_table(prog, tr.window, out)
    if args.ops:
        out(f"\n-- {args.ops} device ops --")
        ops_sample(path, args.ops, out)
    if args.json:
        with open(args.json, "w") as f:
            json.dump({"path": path, "text": lines,
                       "idle_by_owner": PS.idle_by_owner(tr),
                       "window_s": tr.window_s}, f)
    return 0


if __name__ == "__main__":
    sys.exit(main())
