"""Read, on the chip and at a cell's own size, the two numbers every
limit is set from: what sound runs of the program give against the
float32 reference, and what the control gives (the reference in the
program's place, in the nearest precision below the configuration's).

    python -m benchmarks.tools.control --workload <cell> --seeds 1,2,3 \
        [--seconds 20]

One process, one seed after another. A training cell needs no window
(its numbers come from the first steps); a serving cell gets a short one
at the cell's own load. Prints one JSON line per seed and writes them to
``chiprun_out/control_<cell>.jsonl``. Not part of a benchmark run.
"""
import argparse
import json
import os
import sys
import time

from benchmarks.harness import runner


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    args = ap.parse_args()
    root = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    c = runner.Cell(root, args.workload)
    cfg, traffic = c.cfg, c.traffic

    import jax

    import paddle_tpu  # noqa: F401

    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < c.chips:
        print("control: needs the cell's TPU chips", file=sys.stderr)
        return 3
    family, kind = c.family, c.kind
    os.makedirs(os.path.join(root, "chiprun_out"), exist_ok=True)
    out_path = os.path.join(root, "chiprun_out",
                            f"control_{args.workload}.jsonl")
    for seed in map(int, args.seeds.split(",")):
        t0 = time.perf_counter()
        plan = kind.plan(traffic, seed, args.seconds, cfg["vocab_size"])
        system = family.build(cfg, traffic, plan, seed,
                              devs[:c.chips])
        system.warm()
        result = {}
        if traffic["kind"] != "train_steps":
            phases = runner.Phases(False, 0.0, "", runner.CompileCounter(),
                                   lambda: 0)
            result = kind.run(system, plan, args.seconds, phases)
        row = dict(family.control(system, result), seed=seed,
                   workload=args.workload,
                   seconds=round(time.perf_counter() - t0, 1))
        line = json.dumps(row)
        print(line, flush=True)
        with open(out_path, "a") as f:
            f.write(line + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
