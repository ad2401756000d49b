"""Run one cell once: data in, one JSON line out.

Everything that belongs to one configuration, one traffic mix or one
per-layer metric is a file of its own, found by the name in
``BENCHMARK.json``: the configuration's file names a ``family``
(``harness/families/<family>.py``), the traffic file a ``kind``
(``harness/traffic/<kind>.py``), a metric file a ``reducer``
(``harness/reducers/<reducer>.py``). Adding a cell, a mix or a metric
adds files and entries and edits none.
"""
from __future__ import annotations

import contextlib
import gc
import importlib
import json
import os
import shutil
import sys
import time
from typing import Callable, Dict, List, Optional

TRACE_DIR = os.path.join(".paddle_tpu_cache", "bench_trace")
LAST_CHECK = os.path.join(".paddle_tpu_cache", "bench_last_check.json")
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class NoAccelerator(RuntimeError):
    pass


class CompileCounter:
    """XLA compile requests (fresh or served from the persistent cache),
    from JAX's own monitoring events."""

    def __init__(self):
        import jax.monitoring as mon

        self.n = 0
        mon.register_event_duration_secs_listener(self._on)

    def _on(self, name, secs, **kw):
        if name == COMPILE_EVENT:
            self.n += 1


class Phases:
    """The run's clock: where set-up ends and the window opens, the host
    spans, the traced stretch, the time spent in the reference."""

    def __init__(self, trace: bool, trace_seconds: float, trace_dir: str,
                 compiles: CompileCounter, peak_bytes: Callable[[], int],
                 t0: Optional[float] = None):
        self.trace, self.trace_seconds = trace, trace_seconds
        self.trace_dir, self.compiles = trace_dir, compiles
        self._peak_bytes = peak_bytes
        self.t0 = time.perf_counter() if t0 is None else t0
        self.t_open: Optional[float] = None
        self.compiles_at_open = 0
        self.compiles_in_window = 0
        self.peak = 0
        self.xplane: Optional[str] = None

    def span(self, name: str):
        if not self.trace:
            return contextlib.nullcontext()
        import jax

        return jax.profiler.TraceAnnotation(name)

    def open_window(self, at: Optional[float] = None) -> None:
        self.t_open = time.perf_counter() if at is None else at
        self.compiles_at_open = self.compiles.n

    def close_window(self) -> None:
        self.peak = self._peak_bytes()
        self.compiles_in_window = self.compiles.n - self.compiles_at_open

    @property
    def setup_s(self) -> float:
        return self.t_open - self.t0

    def traced(self, fn: Callable[[float], int]) -> Optional[int]:
        """Run ``fn(trace_seconds)`` (more of the same traffic) under the
        profiler. Not called at all in a ``--trace 0`` run."""
        if not self.trace:
            return None
        import jax
        from jax.profiler import ProfileOptions

        from .xplane import WINDOW_SPAN, find_xplane

        shutil.rmtree(self.trace_dir, ignore_errors=True)
        opts = ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(self.trace_dir, profiler_options=opts)
        try:
            with jax.profiler.TraceAnnotation(WINDOW_SPAN):
                n = fn(self.trace_seconds)
        finally:
            jax.profiler.stop_trace()
        self.compiles_in_window = self.compiles.n - self.compiles_at_open
        self.xplane = find_xplane(self.trace_dir)
        return n


def _load_json(path: str) -> Dict:
    with open(path) as f:
        return json.load(f)


def _find(items: List[Dict], name: str, what: str) -> Dict:
    for it in items:
        if it["name"] == name:
            return it
    raise KeyError(f"{what} {name!r} is not in BENCHMARK.json "
                   f"({[i['name'] for i in items]})")


class Cell:
    """One entry of ``workloads`` with everything its names lead to: the
    configuration and traffic files, the family and the traffic kind."""

    def __init__(self, root: str, workload: str):
        self.bench = _load_json(os.path.join(root, "BENCHMARK.json"))
        self.data = os.path.join(root, self.bench["paths"][0])
        self.entry = _find(self.bench["workloads"], workload, "workload")
        conf = _find(self.bench["configs"], self.entry["config"],
                     "configuration")
        self.cfg = _load_json(os.path.join(root, conf["file"]))
        self.traffic = _load_json(os.path.join(
            self.data, "traffic", self.entry["traffic"] + ".json"))
        self.chips = self.entry["chips"]

    @property
    def family(self):
        return importlib.import_module(
            f"benchmarks.harness.families.{self.cfg['family']}")

    @property
    def kind(self):
        return importlib.import_module(
            f"benchmarks.harness.traffic.{self.traffic['kind']}")


def peak_bytes(devices) -> int:
    peak = 0
    for d in devices:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return peak


def run_cell(root: str, workload: str, seed: int, seconds: float,
             trace: bool, require_tpu: bool = True,
             say: Callable[[str], None] = print,
             t0: Optional[float] = None) -> Dict:
    """Run one cell; returns the result object (the caller prints it as
    the last line). ``root`` holds ``BENCHMARK.json``; data files are
    found under its first ``paths`` entry. ``require_tpu=False`` is for
    the CPU tests of the harness, never for the command. ``t0`` is when
    the process started (set-up is counted from there)."""
    c = Cell(root, workload)
    bench, data, cell, cfg, traffic = (c.bench, c.data, c.entry, c.cfg,
                                       c.traffic)

    import jax

    import paddle_tpu  # noqa: F401  (first: places the compile cache)

    devs = jax.devices()
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs)}
    say(f"device: {device}  jax {jax.__version__}  compile cache: "
        f"{jax.config.jax_compilation_cache_dir}")
    if require_tpu and (device["platform"] != "tpu"
                        or len(devs) < cell["chips"]):
        raise NoAccelerator(
            f"cell {workload} needs {cell['chips']} TPU chip(s); jax found "
            f"{len(devs)} x {device['platform']} ({device['kind']})")
    used = devs[:cell["chips"]]

    family, kind = c.family, c.kind
    compiles = CompileCounter()
    phases = Phases(trace, float(traffic.get("trace_s", 4.0)),
                    os.path.join(root, TRACE_DIR), compiles,
                    lambda: peak_bytes(used), t0)

    plan = kind.plan(traffic, seed, seconds, cfg["vocab_size"])
    system = family.build(cfg, traffic, plan, seed, used)
    say(f"built in {time.perf_counter() - phases.t0:.1f}s")
    say(f"warm-up: {system.warm()}")
    say(f"set-up before traffic {time.perf_counter() - phases.t0:.1f}s, "
        f"{compiles.n} compile requests")
    # what set-up built lives as long as the run: keep the collector from
    # walking it in the middle of the window (a pause there is a stall
    # for every request in flight)
    gc.collect()
    gc.freeze()
    result = kind.run(system, plan, seconds, phases)
    engine_compiles = system.compiles()
    say("counts: " + json.dumps(result["counts"]))

    # -- correctness, outside the window ----------------------------------
    t_ref = time.perf_counter()
    checks = family.check(system, result)
    checks.append({"name": "XLA compile requests inside the window",
                   "value": phases.compiles_in_window, "limit": 0})
    for c in checks:
        c["ok"] = bool(c["value"] <= c["limit"])
        say(f"check: {c['name']}: {c['value']:.6g} (limit {c['limit']:g}) "
            f"{'ok' if c['ok'] else 'FAIL'}"
            + "".join(f" {k}={v:.6g}" if isinstance(v, float)
                      else f" {k}={v}" for k, v in c.items()
                      if k in ("leaf", "program", "reference", "mean_gap",
                               "next")
                      and v is not None))
    details = getattr(system, "details", None)
    if details is not None:      # for a person: every number compared
        os.makedirs(os.path.join(root, ".paddle_tpu_cache"), exist_ok=True)
        with open(os.path.join(root, LAST_CHECK), "w") as f:
            json.dump({"workload": workload, "seed": seed,
                       "checks": checks, "details": details}, f)
    say(f"reference and checks took {time.perf_counter() - t_ref:.1f}s; "
        f"engine compiles {engine_compiles}")

    # -- metrics -----------------------------------------------------------
    def in_cell(m: Dict) -> bool:
        return "workloads" not in m or workload in m["workloads"]

    e2e = [m for m in bench["end_to_end"] if in_cell(m)]
    values = dict(result["metrics"], setup_s=phases.setup_s)
    metrics = {}
    correct = all(c["ok"] for c in checks)
    if trace:
        from . import reduce as R
        from .peaks import peaks_for
        from .xplane import load

        tr = load(phases.xplane, cpu_fallback=not require_tpu) \
            if phases.xplane else R.Trace()
        ctx = {"trace": tr, "host": result["host"], "cfg": cfg,
               "traffic": traffic, "cell": cell, "system": {
                   "peak_bytes": phases.peak, "n_chips": cell["chips"]},
               "peaks": peaks_for(device["kind"]) if require_tpu
               else peaks_for("TPU v5 lite")}
        names = {m["name"] for m in e2e}
        for m in bench["per_layer"]:
            if not in_cell(m) or m["moves"] not in names:
                continue
            spec = _load_json(os.path.join(data, "metrics",
                                           m["name"] + ".json"))
            reducer = importlib.import_module(
                f"benchmarks.harness.reducers.{spec['reducer']}")
            v = reducer.read(ctx, **spec.get("args", {}))
            if v is not None:
                metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
        device["busy_s"] = R.busy_seconds(tr)
        device["window_s"] = tr.window_s
        breakdown = {"device_ops": R.top_ops(tr), "idle_gaps":
                     R.idle_gaps(tr)}
        if require_tpu and not device["busy_s"] > 0:
            say("check: no operation ran on the device in the traced "
                "window FAIL")
            correct = False
    else:
        breakdown = None
        for m in e2e:
            v = values.get(m["name"])
            if v is None:
                say(f"check: end-to-end metric {m['name']} has no value "
                    "FAIL")
                correct = False
                continue
            metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
    device["memory_peak_bytes"] = phases.peak
    out = {"correct": bool(correct), "attempted": result["attempted"],
           "failed": result["failed"], "metrics": metrics,
           "device": device}
    if breakdown is not None:
        out["breakdown"] = breakdown
    say(f"setup_s {phases.setup_s:.2f}  total "
        f"{time.perf_counter() - phases.t0:.1f}s")
    return out


def main(argv: Optional[List[str]] = None,
         t0: Optional[float] = None) -> int:
    import argparse

    ap = argparse.ArgumentParser(description="run one benchmark cell once")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    root = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    try:
        out = run_cell(root, args.workload, args.seed, args.seconds,
                       bool(args.trace), t0=t0)
    except NoAccelerator as e:
        print(f"benchmark: no accelerator: {e}", file=sys.stderr)
        return 3
    sys.stdout.flush()
    print(json.dumps(out), flush=True)
    return 0
