"""``python -m benchmarks.run --workload <name> --seed <n> --seconds <s>
--trace <0|1>``: run one cell of ``BENCHMARK.json`` once, in this
process, on the machine it is started on. Fails without a TPU."""
import sys
import time

T0 = time.perf_counter()    # set-up is counted from here: imports too

from benchmarks.harness.runner import main  # noqa: E402

if __name__ == "__main__":
    sys.exit(main(t0=T0))
