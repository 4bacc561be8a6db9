#!/usr/bin/env python3
"""Rehearsal of the benchmark without a chip (run with JAX_PLATFORMS=cpu).

    JAX_PLATFORMS=cpu python3 chipbench/rehearse.py [--cells a,b] [--skip-tiny]

1. Every driver end to end at tiny sizes on the CPU, Pallas kernels in
   interpret mode (the cells of ``tests/conftest.py``); prints each run's
   checks, and no device metric.
2. Each cell's step programs at full size compiled for a described TPU v5e
   (nothing runs); prints the compiler's memory analysis per program.
"""

from __future__ import annotations

import argparse
import os
import sys
import time
from pathlib import Path

os.environ.setdefault("TPU_LOG_DIR", "disabled")

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE.parent))
sys.path.insert(0, str(HERE.parent / "src"))

from chipbench import harness  # noqa: E402

GB = 1e9


def tiny_runs() -> None:
    import pytest
    # the tiny cells are the tests' own: run those tests
    rc = pytest.main(["-q", "-p", "no:cacheprovider",
                      str(HERE / "tests" / "test_harness.py"),
                      "-k", "new_cell"])
    if rc != 0:
        raise SystemExit(f"rehearse: tiny runs failed ({rc})")


def _chip_mesh():
    import numpy as np
    import jax
    from jax.experimental import topologies
    from jax.sharding import AxisType, Mesh
    from repro.distributed.sharding import MeshInfo
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    mesh = Mesh(np.array(topo.devices[:1]).reshape(1, 1), ("data", "model"),
                axis_types=(AxisType.Auto, AxisType.Auto))
    return MeshInfo(mesh)


def compile_cell(name: str) -> dict:
    """Compiles the cell's prefill and decode programs for one described
    v5e; returns {program: memory analysis}."""
    import jax
    from repro.configs.base import InputShape
    from repro.kernels.decode_attention import ops as dops
    from repro.kernels.flash_attention import ops as fops
    from repro.kernels.ssd_scan import ops as sops
    from repro.launch import steps
    for ops in (dops, fops, sops):      # kernels for the chip, not interpret
        ops._on_cpu = lambda: False
    cell = harness.load_cell(name)
    cfg = harness.program_config(cell.config)
    t = cell.traffic
    B, P, G, C = t["batch"], t["prompt_len"], t["gen_len"], t["capacity"]
    minfo = _chip_mesh()
    out = {}
    with minfo.mesh:
        pf, pargs, _, _ = steps.make_prefill_step(
            cfg, minfo, InputShape("prefill", P, B, "prefill"), capacity=C)
        df, dargs, _, _ = steps.make_decode_step(
            cfg, minfo, InputShape("decode", C, B, "decode"))
        for prog, fn, args in (("prefill", pf, pargs), ("decode", df, dargs)):
            t0 = time.perf_counter()
            compiled = fn.lower(*args).compile()
            ma = compiled.memory_analysis()
            out[prog] = {
                "compile_s": round(time.perf_counter() - t0, 1),
                "argument_gb": ma.argument_size_in_bytes / GB,
                "output_gb": ma.output_size_in_bytes / GB,
                "temp_gb": ma.temp_size_in_bytes / GB,
                "alias_gb": ma.alias_size_in_bytes / GB,
                "kernels": compiled.as_text().count("tpu_custom_call")}
    return out


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--cells", default=None,
                    help="comma-separated cells to compile (default: all)")
    ap.add_argument("--skip-tiny", action="store_true")
    args = ap.parse_args(argv)
    import jax
    jax.config.update("jax_enable_compilation_cache", False)
    if not args.skip_tiny:
        tiny_runs()
    import json
    bench = json.loads(harness.BENCHMARK.read_text())
    names = (args.cells.split(",") if args.cells
             else [w["name"] for w in bench["workloads"]])
    for name in names:
        for prog, ma in compile_cell(name).items():
            print(f"{name} {prog}: {ma}", flush=True)


if __name__ == "__main__":
    main()
