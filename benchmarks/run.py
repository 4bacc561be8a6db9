# One function per paper table. Print ``name,us_per_call,derived`` CSV.
"""Benchmark harness: ``PYTHONPATH=src python -m benchmarks.run [--only X]
[--json DIR] [--check DIR]``.

``--json DIR`` additionally writes one ``BENCH_<suite>.json`` per suite
(rows + wall time + autotune-cache stats) — the persisted perf trajectory:
each PR's recorded baselines live next to the previous ones, so a
regression shows up as a diff, not a memory.

``--check DIR`` re-runs every suite that has a committed
``BENCH_<suite>.json`` in DIR and compares the fresh throughput/goodput
metrics row by row against the baseline, exiting nonzero on any >10%
regression (``--check-tol`` to change).  Wall-clock rows (us_per_call)
are NOT gated — they are too noisy across machines; the gated metrics
come from the simulated-time engines and are deterministic per seed.
The kernels suite is gated on its ``maxerr=`` rows (pallas vs reference
max abs error — a lower-is-better envelope; see ``_LOWER_METRICS``) plus
row presence, not on its wall-clock timings.

Suites (one per paper table/figure — DESIGN.md §8):
  fig1          BS / MTL sweeps (preliminary study)
  table5        Profiler TI_B / TI_MT + decisions vs paper Table 4
  fig5          DNNScaler vs Clipper throughput, 30 jobs
  table6        power efficiency on MT jobs
  fig7          adaptation-speed traces
  fig9          SLO-change sensitivity
  fig11         sole-MT check on B jobs
  fig12         B+MT combination
  llm           DNNScaler on the assigned architectures (TPU model)
  cluster       multi-job cluster serving: paper vs hybrid vs pure knobs
  churn         online admit/drain churn: union vs dynamic vs shared surface
  partition     spatial partition sharing: uniform vs heterogeneous shares
  burst         open-loop bursty arrivals: DNNScaler vs static (beyond paper)
  sim           fleet-scale simulator: vectorized engine vs object reference
                at 1000 jobs x 1000 devices (gated on the speedup ratio)
  scenarios     scenario matrix: {steady,diurnal,flash} traffic x
                {fixed,spot} capacity x {pack,spread} power packing —
                gated on goodput and joules-per-good-request, with
                attainment/conservation/power-sum asserts in-process
  tokens        token-level continuous batching: slot engine vs the static
                bucketed baseline on one ragged decode trace (gated on
                goodput and the capped continuous/static ratio), plus the
                paged-KV kernel vs the ragged oracle (maxerr)
  disagg        disaggregated prefill/decode: prefill pool + KV-transfer
                fabric vs the best single-device mode on a long-prefill
                trace (gated on goodput and the fleet/single ratio),
                chunked vs co-tenant prefill TTFT attainment, and the
                fabric's transfer accounting vs the analytic model (maxerr)
  alpha         ablation: hysteresis coefficient alpha (paper: 0.85 empirical)
  matcomp       ablation: matrix completion vs naive interpolation
  kernels       Pallas kernel micro-benches (interpret mode)
  real_decode   wall-clock tiny-model decode
  roofline      per-(arch x shape x mesh) terms from the dry-run JSON
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import re
import sys
import time


def suites():
    from benchmarks import (costmodel_benches, disagg_benches, kernel_benches,
                            paper_benches, roofline_bench, scenario_benches,
                            sim_benches, token_benches)
    return {
        "fig1": paper_benches.bench_fig1_sweeps,
        "table5": paper_benches.bench_table5_profiler,
        "fig5": paper_benches.bench_fig5_throughput,
        "table6": paper_benches.bench_table6_power,
        "fig7": paper_benches.bench_fig7_traces,
        "fig9": paper_benches.bench_fig9_sensitivity,
        "fig11": paper_benches.bench_fig11_sole_mt,
        "fig12": paper_benches.bench_fig12_combination,
        "llm": paper_benches.bench_llm_serving,
        "cluster": paper_benches.bench_cluster,
        "churn": paper_benches.bench_churn,
        "partition": paper_benches.bench_partition,
        "burst": paper_benches.bench_burst,
        "alpha": paper_benches.bench_alpha_ablation,
        "matcomp": paper_benches.bench_matrix_completion_ablation,
        "matcomp_nl": paper_benches.bench_matcomp_nonlinear,
        "sim": sim_benches.bench_sim,
        "scenarios": scenario_benches.bench_scenarios,
        "tokens": token_benches.bench_tokens,
        "disagg": disagg_benches.bench_disagg,
        "kernels": kernel_benches.bench_kernels,
        "real_decode": kernel_benches.bench_real_decode,
        "roofline": roofline_bench.bench_roofline,
        "costmodel": costmodel_benches.bench_costmodel,
    }


_COUNTER_KEYS = ("hits", "misses", "timings", "tunes")


def _autotune_stats() -> dict:
    try:
        from repro.perf import autotune
        return autotune.cache_stats()
    except Exception:  # noqa: BLE001 — stats must never fail a bench run
        return {}


def _autotune_delta(before: dict, after: dict) -> dict:
    """Per-suite view: counters as deltas (one process runs many suites;
    cumulative numbers would credit earlier suites' tuning to later ones),
    cache size/location as absolutes."""
    out = dict(after)
    for k in _COUNTER_KEYS:
        if k in after and k in before:
            out[k] = after[k] - before[k]
    return out


# metrics gated by --check: simulated-time results, deterministic per seed
# (wall-clock us_per_call rows are informational only — too noisy to gate).
# "speedup" is the sim suite's vector/object steps-per-second ratio, pinned
# capped (see sim_benches) so the gate floor stays above the 20x contract.
_CHECKED_METRICS = ("thr", "goodput", "speedup")

# lower-is-better gated metrics: numeric-accuracy rows (the kernels suite's
# pallas-vs-reference max abs error).  These are deterministic per seed on
# one machine but float arithmetic differs slightly across CPUs/XLA
# versions, so the gate is a generous (ratio, absolute-floor) envelope:
# regression iff fresh > ratio * baseline + floor — catching a kernel that
# went numerically wrong, not a last-ulp wobble.
_LOWER_METRICS = {"maxerr": (4.0, 1e-6),
                  # joules per good request (scenarios suite): energy is
                  # simulated-deterministic per seed, so the envelope only
                  # absorbs small goodput wobble, not machine noise
                  "jpg": (1.25, 1e-9),
                  # held-out HLO cost-model prediction error (costmodel
                  # suite's leave-one-job-out median relative error): fully
                  # deterministic — analytic truth surfaces, fixed fold
                  # order — so the envelope only absorbs BLAS/solver
                  # last-ulp drift across platforms, not model regressions
                  "medrelerr": (1.5, 0.02)}


def _parse_metrics(derived) -> dict:
    """``k=<float><unit>`` pairs out of a derived string."""
    out = {}
    for part in str(derived).split(","):
        if "=" not in part:
            continue
        k, v = part.split("=", 1)
        m = re.match(r"[-+]?[0-9]*\.?[0-9]+(?:[eE][-+]?[0-9]+)?", v.strip())
        if m:
            out[k.strip()] = float(m.group(0))
    return out


def check_against(base_dir: str, *, tol: float = 0.10,
                  only=None) -> int:
    """Re-run every suite with a committed BENCH_<suite>.json in
    `base_dir` and compare fresh throughput/goodput metrics row by row.
    Returns the number of regressions (fresh < (1 - tol) * baseline)."""
    table = suites()
    regressions = 0
    checked = 0
    for path in sorted(glob.glob(os.path.join(base_dir, "BENCH_*.json"))):
        committed = json.load(open(path))
        suite = committed.get("suite")
        if only and suite not in only:
            continue
        if suite not in table:
            # a committed baseline whose suite the harness no longer knows
            # is a broken gate, not a skip: the silent pass used to hide a
            # renamed/deleted suite until its regressions shipped
            print(f"CHECK {suite or path}: UNKNOWN suite for baseline "
                  f"{os.path.basename(path)} — not registered in suites()")
            regressions += 1
            continue
        gated = _CHECKED_METRICS + tuple(_LOWER_METRICS)
        if not any(m in _parse_metrics(r.get("derived", ""))
                   for r in committed.get("rows", [])
                   for m in gated):
            continue    # nothing gated in this baseline (wall-clock-only
            #             suites): don't burn time re-running
        try:
            fresh_rows = table[suite]()
        except Exception as e:  # noqa: BLE001
            print(f"CHECK {suite}: ERROR {type(e).__name__}: {e}")
            regressions += 1
            continue
        if not fresh_rows:
            # a suite that exists in the baseline dir but produced nothing
            # fresh would previously sail through the row loop untested
            print(f"CHECK {suite}: NO FRESH ROWS (baseline has "
                  f"{len(committed.get('rows', []))})")
            regressions += 1
            continue
        fresh = {name: _parse_metrics(derived)
                 for name, _, derived in fresh_rows}
        for name, metrics in fresh.items():
            # a truncated engine run means the row's metrics cover a
            # partial horizon — never comparable, always a failure
            if metrics.get("truncated"):
                print(f"CHECK {suite}: TRUNCATED row {name} "
                      f"(hit max_steps before the simulated horizon)")
                regressions += 1
        for row in committed.get("rows", []):
            base = _parse_metrics(row.get("derived", ""))
            got = fresh.get(row["name"])
            if got is None:
                print(f"CHECK {suite}: MISSING row {row['name']}")
                regressions += 1
                continue
            for metric in _CHECKED_METRICS:
                if metric not in base:
                    continue
                checked += 1
                if metric not in got:
                    print(f"CHECK {suite}: {row['name']} lost "
                          f"metric {metric}")
                    regressions += 1
                elif got[metric] < (1.0 - tol) * base[metric]:
                    print(f"CHECK {suite}: REGRESSION {row['name']} "
                          f"{metric} {base[metric]:.1f} -> "
                          f"{got[metric]:.1f} "
                          f"({got[metric] / base[metric] - 1.0:+.1%})")
                    regressions += 1
            for metric, (ratio, floor) in _LOWER_METRICS.items():
                if metric not in base:
                    continue
                checked += 1
                if metric not in got:
                    print(f"CHECK {suite}: {row['name']} lost "
                          f"metric {metric}")
                    regressions += 1
                elif got[metric] > ratio * base[metric] + floor:
                    print(f"CHECK {suite}: REGRESSION {row['name']} "
                          f"{metric} {base[metric]:.2e} -> "
                          f"{got[metric]:.2e} (limit "
                          f"{ratio * base[metric] + floor:.2e})")
                    regressions += 1
    print(f"CHECK: {checked} metrics compared, {regressions} regressions "
          f"(tolerance {tol:.0%})")
    return regressions


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    help="comma-separated suite names (default: all)")
    ap.add_argument("--json", nargs="?", const=".", default=None,
                    metavar="DIR",
                    help="also write BENCH_<suite>.json files into DIR "
                         "(default: current directory)")
    ap.add_argument("--check", default=None, metavar="DIR",
                    help="compare a fresh run against the committed "
                         "BENCH_*.json baselines in DIR; exit nonzero on "
                         "any >tol regression")
    ap.add_argument("--check-tol", type=float, default=0.10,
                    help="relative regression tolerance for --check "
                         "(default 0.10)")
    args = ap.parse_args()
    from repro.launch import compile_cache
    compile_cache.configure()
    if args.check:
        only = set(args.only.split(",")) if args.only else None
        if check_against(args.check, tol=args.check_tol, only=only):
            raise SystemExit(1)
        return
    table = suites()
    names = args.only.split(",") if args.only else list(table)
    if args.json:
        os.makedirs(args.json, exist_ok=True)

    print("name,us_per_call,derived")
    failures = 0
    for name in names:
        fn = table[name]
        at_before = _autotune_stats()
        t0 = time.time()
        try:
            rows = fn()
        except Exception as e:  # noqa: BLE001
            print(f"{name}/ERROR,0,{type(e).__name__}:{e}")
            failures += 1
            continue
        wall = time.time() - t0
        for rname, us, derived in rows:
            print(f"{rname},{us:.2f},{derived}")
        print(f"{name}/_suite_wall,{wall * 1e6:.0f},ok", file=sys.stderr)
        if args.json:
            path = os.path.join(args.json, f"BENCH_{name}.json")
            with open(path, "w") as f:
                json.dump({
                    "suite": name,
                    "suite_wall_s": wall,
                    "rows": [{"name": r, "us_per_call": u, "derived": d}
                             for r, u, d in rows],
                    "autotune": _autotune_delta(at_before, _autotune_stats()),
                }, f, indent=2)
            print(f"{name} -> {path}", file=sys.stderr)
    if failures:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
