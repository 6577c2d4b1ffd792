"""One run of a cell as ``benchmark/run.py`` makes it, with the metrics of
``benchmark/harness/inside.py`` on a traced run's line too — the builder's
tool for reading them on the chip until ``BENCHMARK.json`` and the cells'
own files list them (PERF.md section 7). Same arguments as ``run.py``.

    python3 benchmark/tools/inside_run.py --workload doc_batch \\
        --seed 5550001 --seconds 50 --trace 1
"""
import os
import sys
import time

T_START = time.perf_counter()
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from benchmark.harness import inside, loader, main  # noqa: E402


if __name__ == "__main__":
    plain = loader.Cell
    loader.Cell = lambda name: inside.extend(plain(name))
    main.main(t_start=T_START)
