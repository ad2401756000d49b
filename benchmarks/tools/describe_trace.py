"""Look at the last traced run by hand: what the ``.xplane.pb`` holds
(planes, lines, first events with their stats), and the reduced Trace as
JSON (``--json out.json``, optionally only the first ``--head`` seconds
of the window), which is what ``benchmarks/tests/data`` keeps.

    python -m benchmarks.tools.describe_trace [--json out.json] [--head 0.3]
"""
import argparse
import os
import sys

from benchmarks.harness import reduce as R
from benchmarks.harness.runner import TRACE_DIR
from benchmarks.harness.xplane import describe, find_xplane, load


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--json")
    ap.add_argument("--head", type=float)
    ap.add_argument("--limit", type=int, default=12)
    args = ap.parse_args()
    root = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    path = find_xplane(os.path.join(root, TRACE_DIR))
    if path is None:
        print("no trace found; run a cell with --trace 1 first",
              file=sys.stderr)
        return 1
    print(describe(path, args.limit))
    if args.json:
        tr = load(path)
        if args.head:
            lo = tr.window[0]
            tr.window = (lo, lo + args.head)
            tr.ops = [o for o in tr.ops if o.start < lo + args.head
                      and o.start + o.dur > lo]
            tr.spans = [s for s in tr.spans if s.start < lo + args.head
                        and s.start + s.dur > lo]
        os.makedirs(os.path.dirname(os.path.abspath(args.json)),
                    exist_ok=True)
        with open(args.json, "w") as f:
            f.write(tr.to_json())
        print(f"{len(tr.ops)} ops, {len(tr.spans)} spans -> {args.json}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
