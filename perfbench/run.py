"""The benchmark's one command:

    python3 perfbench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Everything it runs is found by name from BENCHMARK.json (perfbench/harness.py).
"""

import os
import sys
import time

T0 = time.perf_counter()  # set-up is timed from here (plus process age)

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from perfbench.harness import main  # noqa: E402

if __name__ == "__main__":
    sys.exit(main(sys.argv[1:], t0=T0))
