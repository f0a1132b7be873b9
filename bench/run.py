"""Run one benchmark cell on the chip and print its result line.

    python3 bench/run.py --workload icarus.batch --seed 7 --seconds 51 --trace 0

The last line of standard output is one JSON object (``correct``,
``attempted``, ``failed``, ``metrics``, ``device``, with ``--trace 1``
also ``breakdown``, and last ``checks``: each number compared with its
limit, also printed as the last lines of standard error). Without a TPU,
or with fewer chips than the cell asks for, it prints no result and
exits 2. See ``bench/harness.py`` for what a run does.
"""
import time

T_START = time.perf_counter()

import sys  # noqa: E402
from pathlib import Path  # noqa: E402

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from bench.harness import main  # noqa: E402

if __name__ == "__main__":
    sys.exit(main(t_start=T_START))
