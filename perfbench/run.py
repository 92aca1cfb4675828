"""python perfbench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

One run of one cell of BENCHMARK.json; the last line of standard output
is the result (perfbench/harness.py).  Refuses to run off a TPU."""
import time

T0 = time.time()                      # process start, for `setup_s`

import os  # noqa: E402
import sys  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from perfbench import harness  # noqa: E402

if __name__ == "__main__":
    sys.exit(harness.main(entry=os.path.abspath(__file__), t_start=T0))
