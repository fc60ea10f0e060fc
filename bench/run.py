"""Run one benchmark cell once, on the machine's accelerator.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cells, metrics and bounds are in ``BENCHMARK.json`` at the root of
the checkout; see ``bench/harness.py``.  The last line of standard
output is the result object; the comparisons behind ``correct`` are the
last lines of standard error.  Exits non-zero, with no result, where
JAX finds no accelerator, one whose kind has no published peaks in
``bench/peaks.py``, or fewer chips than the cell asks for.
"""

import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench.harness import main  # noqa: E402

if __name__ == "__main__":
    sys.exit(main())
