"""The harness end to end at tiny sizes on the CPU (Pallas kernels in
interpret mode), on cells it has never seen, found by name."""

from __future__ import annotations

import os
import subprocess
import sys
import time

import pytest

from chipbench import harness

CELLS = ["tiny-dense.tiny-gen", "tiny-mamba2.tiny-gen"]
BIG_SEED = 2**31 + 12345


def run_tiny(root, name, seed=BIG_SEED, patch=None, trace=False):
    cell = harness.load_cell(name, root / "BENCHMARK.json", root)
    return harness.run_cell(cell, seed, 1.0, trace, time.perf_counter(),
                            platform="cpu", patch=patch)


@pytest.mark.parametrize("name", CELLS)
def test_new_cell_runs_and_is_correct(tiny_root, name):
    line = run_tiny(tiny_root, name)
    assert line["correct"], line["checks"]
    assert set(line["metrics"]) == {"tokens_per_s", "tpot_p95_ms", "setup_s"}
    assert line["metrics"]["tokens_per_s"]["value"] > 0
    assert line["attempted"] >= 2 and line["failed"] == 0
    assert line["device"]["platform"] == "cpu"
    assert list(line)[-1] == "checks"


def test_no_tpu_exits_nonzero_and_prints_nothing():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, str(harness.HERE / "run.py"), "--workload",
         "smollm-360m.decode-long", "--seed", "1", "--seconds", "1",
         "--trace", "0"], capture_output=True, text=True, env=env,
        cwd=harness.REPO, timeout=300)
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
    assert "no TPU" in proc.stderr
