#!/usr/bin/env python3
"""Run one cell of ``BENCHMARK.json`` once on the chips of this machine.

    python3 chipbench/run.py --workload smollm-360m.decode-long --seed 7 \
        --seconds 10 --trace 0

Set-up (weights from the seed, compiles, warm-up) is timed as ``setup_s``;
then the cell's traffic runs for ``--seconds`` and the end-to-end metrics
(``--trace 0``) or the per-layer metrics read from a profiler trace of the
window (``--trace 1``) are printed as one JSON line, last on standard
output.  Served outputs are compared with a plain float32 reference after
the window; the numbers compared stand, with their limits, last on standard
error and under ``checks`` in the line.  With no TPU, or fewer chips than
the cell needs, the run exits non-zero and prints no result.
"""

import time

T_START = time.perf_counter()

import sys  # noqa: E402
from pathlib import Path  # noqa: E402

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

from chipbench import harness  # noqa: E402

if __name__ == "__main__":
    sys.exit(harness.main(t_start=T_START))
