"""python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s>
--trace <0|1>: one run of one cell in one process that holds the chips."""
import os
import sys
import time

T_START = time.perf_counter()
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmark.harness.main import main  # noqa: E402

if __name__ == "__main__":
    main(t_start=T_START)
