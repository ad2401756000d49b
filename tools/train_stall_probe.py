"""What a long train step was doing: the benchmark's own training system
(`benchmarks/harness/families/gpt_fleet.py`, the cell's configuration,
traffic and weights), stepped back to back as the harness steps it, with
what the harness's result line cannot show beside every step:

- ``dispatch_ms``: the engine's call until it returns the loss tensor,
  ``fetch_ms``: ``float(loss)`` until the device hands the number over;
- ``beat_gap_ms``: the longest silence of a thread that wakes every
  5 ms and needs the GIL for a few hundred nanoseconds. A step that is
  long while the beat went on was waiting outside the interpreter (the
  runtime, the device); a beat as silent as the step is long means the
  process did not run (the machine's pause) or the GIL was held
  (the collector, a C call);
- ``gc_ms``: the collector's pauses inside the step;
- ``cpu_ms``: the process's own CPU time, ``steal_ticks`` from
  ``/proc/stat`` where the machine shows them.

Prints the twelve longest steps over ``--over`` times the median, the
health monitor's events and the flight records written, and a summary.

    chiprun -- python tools/train_stall_probe.py --seconds 540
    (from another checkout: PYTHONPATH=. python <path to this file>)
"""
import argparse
import gc
import json
import os
import statistics
import sys
import threading
import time

sys.path.insert(0, os.getcwd())


class Beat(threading.Thread):
    def __init__(self, every=0.005):
        super().__init__(daemon=True)
        self.every, self.worst, self.stop = every, 0.0, False

    def run(self):
        last = time.perf_counter()
        while not self.stop:
            time.sleep(self.every)
            now = time.perf_counter()
            self.worst = max(self.worst, now - last - self.every)
            last = now

    def take(self):
        w, self.worst = self.worst, 0.0
        return w


def steal_ticks():
    try:
        with open("/proc/stat") as f:
            return int(f.readline().split()[8])
    except (OSError, IndexError, ValueError):
        return None


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", default="train-1.3b-1chip")
    ap.add_argument("--seed", type=int, default=3830000003)
    ap.add_argument("--seconds", type=float, default=540.0)
    ap.add_argument("--over", type=float, default=1.25)
    ap.add_argument("--json")
    args = ap.parse_args()

    from benchmarks.harness.runner import Cell

    c = Cell(os.getcwd(), args.workload)
    import jax

    import paddle_tpu  # noqa: F401  (first: places the compile cache)
    from paddle_tpu.observability import healthmon

    devs = jax.devices()[:c.chips]
    print(f"device: {devs[0].device_kind} x {len(devs)}", flush=True)
    t_start = time.perf_counter()
    plan = c.kind.plan(c.traffic, args.seed, args.seconds,
                       c.cfg["vocab_size"])
    system = c.family.build(c.cfg, c.traffic, plan, args.seed, devs)
    print(f"warm-up: {system.warm()}", flush=True)
    print(f"set-up {time.perf_counter() - t_start:.1f}s", flush=True)
    gc.collect()
    gc.freeze()                 # as the harness does before its window

    gc_pause = [0.0, 0.0]       # in this step | when the running one began

    def on_gc(phase, info):
        if phase == "start":
            gc_pause[1] = time.perf_counter()
        else:
            gc_pause[0] += time.perf_counter() - gc_pause[1]

    gc.callbacks.append(on_gc)
    beat = Beat()
    beat.start()
    time.sleep(0.05)
    beat.take()

    rows = []
    i = system.steps_done
    t0 = time.perf_counter()
    while time.perf_counter() - t0 < args.seconds:
        x, y = system.batches[i % len(system.batches)]
        gc_pause[0] = 0.0
        cpu, steal = time.process_time(), steal_ticks()
        a = time.perf_counter()
        loss = system._step(x, y)
        b = time.perf_counter()
        value = float(loss)
        e = time.perf_counter()
        rows.append({
            "i": i, "at_s": a - t0, "step_ms": 1e3 * (e - a),
            "dispatch_ms": 1e3 * (b - a), "fetch_ms": 1e3 * (e - b),
            "beat_gap_ms": 1e3 * beat.take(), "gc_ms": 1e3 * gc_pause[0],
            "cpu_ms": 1e3 * (time.process_time() - cpu),
            "steal_ticks": None if steal is None
            else steal_ticks() - steal, "loss": value})
        i += 1
    beat.stop = True
    gc.callbacks.remove(on_gc)

    med = {k: statistics.median(r[k] for r in rows)
           for k in ("step_ms", "dispatch_ms", "fetch_ms", "beat_gap_ms",
                     "cpu_ms")}
    long_ = [r for r in rows if r["step_ms"] > args.over * med["step_ms"]]
    for r in sorted(long_, key=lambda r: -r["step_ms"])[:12]:
        print("long step: " + json.dumps(
            {k: round(v, 3) if isinstance(v, float) else v
             for k, v in r.items()}), flush=True)
    span = rows[-1]["at_s"] + rows[-1]["step_ms"] / 1e3
    tokens = len(rows) * system.tokens_per_step
    eng = system._engine()
    events = [dict(ev) for mon in (getattr(eng, "_health", None),
                                   healthmon.get_monitor())
              if mon is not None for ev in mon.events()]
    flight_dir = os.environ.get("PADDLE_TPU_FLIGHT_DIR", "flight_records")
    flights = sorted(os.listdir(flight_dir)) \
        if os.path.isdir(flight_dir) else []
    summary = {
        "steps": len(rows), "span_s": span,
        "tokens_per_s": tokens / span, "median": med,
        "step_ms_max": max(r["step_ms"] for r in rows),
        "beat_gap_ms_max": max(r["beat_gap_ms"] for r in rows),
        "gc_ms_total": sum(r["gc_ms"] for r in rows),
        "long_steps": len(long_), "over": args.over,
        "backward_nodes": getattr(eng, "backward_nodes", None),
        "engine_compiles": system.compiles(),
        "health_events": len(events),
        "health_events_first": [{k: ev.get(k) for k in
                                 ("kind", "step", "value", "median", "z",
                                  "flight_record")} for ev in events[:16]],
        "flight_records": flights}
    print("summary: " + json.dumps(summary), flush=True)
    if args.json:
        os.makedirs(os.path.dirname(args.json) or ".", exist_ok=True)
        with open(args.json, "w") as f:
            json.dump({"summary": summary, "rows": rows}, f)
    return 0


if __name__ == "__main__":
    sys.exit(main())
