#!/usr/bin/env python3
"""Readings that a cell's limits are set from, on the chip at the cell's
own sizes: the number compared for `correct` for the program on each seed
and, on some of them, for the control (the plain reference put in the
program's place, in the precision below the configuration's).

    python3 chipbench/control.py --workload smollm-360m.decode-long \
        --seeds 1-12 --control-seeds 1-3

Prints one line per seed, then the lower reading (the largest the program
gives) and the upper one (the smallest the control gives).  The benchmark's
own runs never run the control.
"""

from __future__ import annotations

import argparse
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

from chipbench import harness  # noqa: E402


def seed_list(text: str) -> list:
    out = []
    for part in text.split(","):
        lo, _, hi = part.partition("-")
        out += range(int(lo), int(hi or lo) + 1)
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="1-12")
    ap.add_argument("--control-seeds", default="1-3")
    args = ap.parse_args(argv)
    sys.path.insert(0, str(harness.REPO / "src"))
    cell = harness.load_cell(args.workload)
    harness.prepare_process()
    harness.require_devices(cell.chips)
    driver = harness.load_module(
        cell.root / "drivers" / f"{cell.traffic['driver']}.py")
    program, control = [], []
    for seed, p, c in driver.readings(cell, seed_list(args.seeds),
                                      set(seed_list(args.control_seeds))):
        program.append(p)
        if c is not None:
            control.append(c)
        print(f"seed {seed}: program {p} control {c}", flush=True)
    for k in program[0]:
        lower = max(p[k] for p in program)
        upper = min(c[k] for c in control)
        print(f"{args.workload} {k}: lower {lower!r} over {len(program)} "
              f"seeds; upper {upper!r} over {len(control)} seeds "
              f"(fp8); upper/lower "
              f"{upper / lower if lower else float('inf'):.3f}", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
