"""How far ``served_tokens_per_s`` of a closed-loop cell moves from seed to
seed with NOTHING but the order of the requests changed (PERF.md section
6, PR 35). No chip and no JAX: a model of the loop on the host.

The stream is the benchmark's own (``benchmarks/harness/traffic/
lengths.py``: cycles of stratified quantiles, permuted from the seed);
the engine is ``ServingEngine``'s legacy round (every free row admits the
queue's head and prefills it at B=1, then one decode step for all live
rows); the number is ``traffic/closed_loop.py``'s (prompt + generated
tokens of the requests that END in the window, over the time to the last
of them). A step's time is a fit to the traced run of
``serve-trinity-mixedlen-batch`` (seed 3500000011, my chip run, PR 35):
a prefill of bucket S 18.5 us a token + 1.2e-6 ms x S^2 (88 ms at the
mean bucket); a decode step 5.1 ms + 4.9 us a window page in reach (at
most 17 a row) + 1.27 us a full-class page (11.6 ms at the mix's
contexts). It gives that cell's medians within 0.3% (18,084 | 17,165
against 18,137 | 17,135 on the chip at sigma 1.0 | 0.7). It does NOT
give a seed's own number: which requests end inside the window is
chaotic in the step times, so the chip's runs and the model's agree in
distribution only. What comes out is NOT a device number.

    python tools/closed_loop_model.py --traffic mixedlen-batch \
        [--sigma 0.7] [--seconds 48] [--ramp 30] [--seeds 120]

One JSON line: the median, a run's standard deviation as a share of the
mean, and the spread (first to third quartile over the median) of each
set of six runs, plain and with the set's farthest run left out.
"""
import argparse
import json
import os
import statistics
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from benchmarks.harness.traffic.lengths import paired, seeded  # noqa: E402

PAGE, RING, ROWS = 128, 17, 64
PREFILL_MS = (0.0185, 1.2e-6)           # a token, a token squared
DECODE_MS = (5.1, 0.0049, 0.00127)      # a step, a window page, a full page


def bucket(n: int) -> int:
    """``core/bucketing.py::bucket`` (copied: this tool imports no JAX)."""
    b = 64
    while b < n:
        b *= 2
    return b


def run(params, seed: int, seconds: float) -> float:
    """One run's ``served_tokens_per_s``."""
    rng = seeded(seed, 3)
    buf = []

    def nxt():
        nonlocal buf
        if not buf:
            c = params["cycle"]
            p, o = paired(params["prompt"], params["output"], c, rng)
            buf = [(int(p[k]), int(o[k])) for k in range(c)][::-1]
        return buf.pop()

    queue = [nxt() for _ in range(params["clients"])]
    slots = [None] * ROWS                # [prompt, outputs, tokens so far]
    t, done = 0.0, []
    w_open = params["ramp_s"]
    w_close = w_open + seconds
    while t < w_close:
        for b in range(ROWS):
            if slots[b] is None and queue:
                n, out = queue.pop(0)
                s = bucket(n)
                t += (PREFILL_MS[0] * s + PREFILL_MS[1] * s * s) * 1e-3
                slots[b] = [n, out, 1]
        pages = [-(-(s[0] + s[2]) // PAGE) for s in slots if s is not None]
        t += (DECODE_MS[0] + DECODE_MS[1] * sum(min(p, RING) for p in pages)
              + DECODE_MS[2] * sum(pages)) * 1e-3
        for b, s in enumerate(slots):
            if s is None:
                continue
            s[2] += 1
            if s[2] >= s[1]:
                done.append((t, s[0] + s[1]))
                slots[b] = None
                queue.append(nxt())
    inside = [(at, n) for at, n in done if w_open <= at < w_close]
    return sum(n for _, n in inside) / (max(at for at, _ in inside) - w_open)


def spread(values) -> float:
    q = statistics.quantiles(values, n=4)
    return (q[2] - q[0]) / statistics.median(values)


def spread_without_farthest(values) -> float:
    m = statistics.median(values)
    rest = sorted(values, key=lambda v: abs(v - m))[:-1]
    q = statistics.quantiles(rest, n=4)
    return min(spread(values), (q[2] - q[0]) / m)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--traffic", default="mixedlen-batch")
    ap.add_argument("--sigma", type=float)
    ap.add_argument("--seconds", type=float, default=48.0)
    ap.add_argument("--ramp", type=float)
    ap.add_argument("--seeds", type=int, default=120)
    a = ap.parse_args()
    params = json.load(open(os.path.join(
        ROOT, "benchmarks", "traffic", a.traffic + ".json")))
    if a.sigma is not None:
        params["prompt"]["sigma"] = a.sigma
    if a.ramp is not None:
        params["ramp_s"] = a.ramp
    vals = [run(params, 2_100_000_000 + 104_729 * i, a.seconds)
            for i in range(a.seeds)]
    sets = [vals[i:i + 6] for i in range(0, len(vals) - 5, 6)]
    print(json.dumps({
        "traffic": a.traffic, "sigma": params["prompt"].get("sigma"),
        "seconds": a.seconds, "ramp_s": params["ramp_s"], "runs": len(vals),
        "median": statistics.median(vals),
        "std_pct": 100 * statistics.pstdev(vals) / statistics.mean(vals),
        "set_spread_pct": [round(100 * spread(s), 2) for s in sets],
        "set_spread_without_farthest_pct":
            [round(100 * spread_without_farthest(s), 2) for s in sets]}))


if __name__ == "__main__":
    main()
