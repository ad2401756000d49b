"""Find the knee of an open-loop cell once, on the chip: one process, one
set-up, one short window per rate. The knee is the highest rate at which
the queue does not grow through the window; the cell then runs at about
four fifths of it (the number goes into the traffic file and PERF.md).

    python -m benchmarks.tools.sweep --workload serve-chat-steady \
        --rates 2,3,4,5,6 --seconds 25

Not part of a benchmark run.
"""
import argparse
import json
import os
import sys

from benchmarks.harness import runner


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=25.0)
    ap.add_argument("--seed", type=int, default=7)
    args = ap.parse_args()
    root = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    c = runner.Cell(root, args.workload)
    cfg, traffic = c.cfg, c.traffic

    import jax

    import paddle_tpu  # noqa: F401

    devs = jax.devices()
    if devs[0].platform != "tpu":
        print("sweep: needs a TPU", file=sys.stderr)
        return 3
    family, kind = c.family, c.kind
    plan = kind.plan(traffic, args.seed, args.seconds, cfg["vocab_size"])
    system = family.build(cfg, traffic, plan, args.seed, devs[:1])
    print("warm-up:", system.warm(), flush=True)
    os.makedirs(os.path.join(root, "chiprun_out"), exist_ok=True)
    for k, rate in enumerate(map(float, args.rates.split(","))):
        t = dict(traffic, rate_per_s=rate)
        system.traffic = t
        plan = kind.plan(t, args.seed + k, args.seconds, cfg["vocab_size"])
        phases = runner.Phases(False, 0.0, "", runner.CompileCounter(),
                               lambda: 0)
        res = kind.run(system, plan, args.seconds, phases)
        while system.busy():          # drain before the next rate
            system.step()
        system.pop_finished()
        row = {"rate_per_s": rate, "attempted": res["attempted"],
               "failed": res["failed"], **res["counts"]}
        print(json.dumps(row), flush=True)
        with open(os.path.join(root, "chiprun_out",
                               f"sweep_{args.workload}.jsonl"), "a") as f:
            f.write(json.dumps(row) + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
