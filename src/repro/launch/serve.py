"""Serving launcher: run a job (paper DNN or assigned LLM arch) under a
controller and report throughput / p95 / power efficiency — or serve the
whole 30-job Table-4 trace on a simulated cluster.

    PYTHONPATH=src python -m repro.launch.serve --job 5 --controller dnnscaler
    PYTHONPATH=src python -m repro.launch.serve --job 5 --controller hybrid
    PYTHONPATH=src python -m repro.launch.serve --arch smollm-360m \
        --controller clipper --slo-ms 50
    PYTHONPATH=src python -m repro.launch.serve --arch gemma2-2b --tiny --real
    PYTHONPATH=src python -m repro.launch.serve --cluster --devices 12 \
        --controller hybrid --seconds 240
    PYTHONPATH=src python -m repro.launch.serve --churn --devices 5 \
        --seconds 150 --churn-policy surface
    PYTHONPATH=src python -m repro.launch.serve --partition \
        --partition-policy het --devices 3 --seconds 120
"""

from __future__ import annotations

import argparse
import os
from typing import Optional

import jax
import jax.numpy as jnp

from repro.core.controller import (ClipperController, DNNScalerController,
                                   StaticController)
from repro.core.matrix_completion import LatencyEstimator
from repro.serving import device_model as dm
from repro.serving.engine import ServingEngine
from repro.serving.executor import RealExecutor, SimExecutor
from repro.serving.workload import PAPER_JOBS


def build_library(estimator: LatencyEstimator, exclude_id: int) -> None:
    """Seed matrix completion with 'historically profiled' jobs (each MTL
    curve priced in one vectorized mt_latency_grid call)."""
    mtls = list(range(1, 11))
    for j in PAPER_JOBS[:8]:
        if j.job_id == exclude_id:
            continue
        curve = dm.mt_latency_curve(dm.TESLA_P40, j.profile(), 1, mtls)
        estimator.add_library_row(dict(zip(mtls, curve)))


def make_controller(name: str, executor, slo_s: float, job_id: int = -1,
                    bs: int = 1, mtl: int = 1, *, surface_library=None,
                    surface_key=None, max_bs: int = 128):
    if name in ("dnnscaler", "hybrid"):
        est = LatencyEstimator(max_mtl=10)
        build_library(est, job_id)
        mode = "hybrid" if name == "hybrid" else "auto"
        return DNNScalerController(executor, slo_s, estimator=est, mode=mode,
                                   max_bs=max_bs,
                                   surface_library=surface_library,
                                   surface_key=surface_key)
    if name == "clipper":
        return ClipperController(slo_s)
    return StaticController(bs=bs, mtl=mtl)


def real_executor_for(arch: str, tiny: bool, seed: int = 0) -> tuple:
    from repro.configs.base import InputShape, get_config
    from repro.models import api
    cfg = get_config(arch, tiny=tiny)
    rng = jax.random.PRNGKey(seed)
    params = api.init_params(rng, cfg)

    @jax.jit
    def fwd(params, batch):
        loss, _ = api.train_loss(params, batch, cfg, remat=False)
        return loss

    def make_batch(n):
        return api.make_batch(rng, cfg, InputShape("serve", 128, n, "train"))

    # admission is bounded by the device's own memory where the backend
    # reports it (the executor sizes items from its compiled buckets)
    stats = jax.devices()[0].memory_stats() or {}
    return RealExecutor(fwd, params, make_batch,
                        mem_bytes=stats.get("bytes_limit")), cfg


def main(argv=None) -> Optional[dict]:
    """Runs the launcher on ``argv`` (default ``sys.argv``).  A single-job
    run returns its summary: label, approach, steady knobs, engine summary
    and, for a real executor, its exec-cache counters."""
    ap = argparse.ArgumentParser()
    ap.add_argument("--job", type=int, default=None, help="paper job # (1-30)")
    ap.add_argument("--arch", default=None, help="assigned architecture id")
    ap.add_argument("--tiny", action="store_true")
    ap.add_argument("--real", action="store_true",
                    help="wall-clock executor (tiny models)")
    ap.add_argument("--controller", default="dnnscaler",
                    choices=["dnnscaler", "hybrid", "clipper", "static"])
    ap.add_argument("--cluster", action="store_true",
                    help="serve the full 30-job trace on a simulated fleet")
    ap.add_argument("--churn", action="store_true",
                    help="online churn: jobs admit/drain mid-run with "
                         "migration-aware re-placement")
    ap.add_argument("--churn-policy", default="surface",
                    choices=["union", "dynamic", "surface"],
                    help="placement policy for --churn (see "
                         "serving.cluster.run_churn_cluster)")
    ap.add_argument("--token-engine", action="store_true",
                    help="token-level continuous batching for a decode "
                         "job: bs = max live decode slots, admit-on-free-"
                         "slot / evict-on-EOS, TTFT+TPOT SLOs "
                         "(serving.token_engine)")
    ap.add_argument("--token-policy", default="both",
                    choices=["continuous", "static", "both"],
                    help="slot engine, fixed-shape bucketed baseline, or "
                         "both on the same ragged trace")
    ap.add_argument("--slots", type=int, default=16,
                    help="max live decode slots (continuous) / batch size "
                         "(static baseline) for --token-engine")
    ap.add_argument("--requests", type=int, default=300,
                    help="trace length for --token-engine")
    ap.add_argument("--rate-rps", type=float, default=12.0,
                    help="arrival rate for the --token-engine trace")
    ap.add_argument("--ttft-slo-ms", type=float, default=1000.0)
    ap.add_argument("--tpot-slo-ms", type=float, default=50.0)
    ap.add_argument("--prefill-mode", default="cotenant",
                    choices=["cotenant", "timeslice", "chunked", "disagg"],
                    help="prefill priced as a co-resident tenant, "
                         "time-sliced on the decode tenant, split into "
                         "fixed token-budget chunks piggybacked on decode "
                         "steps, or DISAGGREGATED onto a dedicated "
                         "prefill pool with KV streamed over the "
                         "interconnect fabric (serving.disagg)")
    ap.add_argument("--prefill-pool", type=int, default=2,
                    help="--prefill-mode disagg: prefill-pool members "
                         "(dedicated devices)")
    ap.add_argument("--prefill-chunk", type=int, default=256,
                    help="--prefill-mode chunked: prefill tokens "
                         "piggybacked per decode step")
    ap.add_argument("--scenarios", action="store_true",
                    help="one scenario-matrix cell: time-varying traffic "
                         "x spot capacity x power packing on the MPS "
                         "partition planner (see "
                         "serving.cluster.run_scenario_cluster)")
    ap.add_argument("--scenario-traffic", default="steady",
                    choices=["steady", "diurnal", "flash"],
                    help="traffic shape for --scenarios: constant, "
                         "compressed diurnal swing, or a 3x flash crowd")
    ap.add_argument("--spot", action="store_true",
                    help="--scenarios: mark one device preemptible and "
                         "revoke it once mid-run (grace window, restore)")
    ap.add_argument("--power-policy", default=None,
                    choices=["pack", "spread"],
                    help="--scenarios placement objective: consolidate "
                         "tenants to power-gate idle devices, or spread "
                         "for headroom (default: legacy scoring)")
    ap.add_argument("--partition", action="store_true",
                    help="spatial partitioning (MPS/MIG-style slices): "
                         "serve the mixed small/large trace with the "
                         "share knob active")
    ap.add_argument("--partition-policy", default="het",
                    choices=["uniform", "het", "het-mig"],
                    help="uniform = 1/k time-share baseline (same pricing "
                         "model, migrations); het = heterogeneous MPS "
                         "shares + cheap resizes; het-mig = MIG grid")
    ap.add_argument("--devices", type=int, default=None,
                    help="fleet size for --cluster / --churn "
                         "(default 12 / 5)")
    ap.add_argument("--seconds", type=float, default=None,
                    help="simulated-time horizon for --cluster / --churn "
                         "(default 90 / 150)")
    ap.add_argument("--bs", type=int, default=1)
    ap.add_argument("--mtl", type=int, default=1)
    ap.add_argument("--slo-ms", type=float, default=None)
    ap.add_argument("--steps", type=int, default=500)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--autotune", action="store_true",
                    help="tune Pallas tile sizes on cache miss (fills the "
                         "persistent autotune cache; otherwise cache-only)")
    ap.add_argument("--autotune-cache-dir", default=None, metavar="DIR",
                    help="autotune cache location (default: "
                         "$REPRO_AUTOTUNE_CACHE, $REPRO_PROFILE_STORE, or "
                         "./.profile_store)")
    ap.add_argument("--profile-store", default=None, metavar="DIR",
                    help="cross-run profile store: reload persisted "
                         "surface rows / migration calibrations before "
                         "serving and persist this run's probing "
                         "afterwards (warm start; see perf.profile_store)")
    ap.add_argument("--train-cost-model", default=None, metavar="DEVCLASS",
                    help="maintenance action: train the learned HLO cost "
                         "model for DEVCLASS (e.g. tesla-p40) from the "
                         "--profile-store's persisted surface rows, save "
                         "it into the store's cost_model section, and "
                         "exit.  The next cluster boot serves it as the "
                         "zero-probe prediction tier (perf.cost_model)")
    ap.add_argument("--record", default=None, metavar="NAME",
                    help="record this cluster/churn/partition run's inputs "
                         "and event stream into the profile store under "
                         "NAME, for later `report --replay NAME` what-if "
                         "analysis")
    ap.add_argument("--vectorized", action="store_true",
                    help="use the array-backed VectorClusterEngine "
                         "(bit-identical results, faster at fleet scale)")
    args = ap.parse_args(argv)

    from repro.launch import compile_cache
    compile_cache.configure()
    from repro.perf import autotune
    autotune.configure(cache_dir=args.autotune_cache_dir,
                       tune_on_miss=args.autotune or None)
    store = None
    if args.profile_store is not None:
        from repro.perf.profile_store import ProfileStore
        store = ProfileStore(args.profile_store)
        if args.autotune_cache_dir is None and \
                not os.environ.get("REPRO_AUTOTUNE_CACHE"):
            # one store for all three artifacts: the tuned-tile
            # generation that staleness-gates the persisted surface rows
            # must come from the SAME document the rows live in
            autotune.configure(cache_dir=args.profile_store)

    if args.record and not (args.cluster or args.churn or args.partition
                            or args.scenarios):
        ap.error("--record applies to --cluster / --churn / --partition "
                 "/ --scenarios runs only")

    if args.train_cost_model is not None:
        if store is None:
            ap.error("--train-cost-model requires --profile-store (the "
                     "model is trained from its persisted surface rows)")
        from repro.perf import cost_model as cm
        dc = args.train_cost_model
        model = cm.train_cost_model(store, dc,
                                    autotune_generation=autotune.generation())
        if model is None:
            rows = sum(1 for r in store.section("surfaces").values()
                       if isinstance(r, dict)
                       and r.get("device_class") == dc)
            print(f"cost model[{dc}]: NOT trained — {rows} surface rows "
                  f"for this device class; need >= 4 with recognizable "
                  f"signatures and a device model (tesla-p40 / tpu-v5e)")
            return
        cm.save_cost_model(store, model)
        store.save()
        print(f"cost model[{dc}]: trained on {model.n_rows} surface rows "
              f"({len(model.train_signatures)} signatures), "
              f"{len(model.rung_factors)} share-rung factors — saved to "
              f"{store.path}")
        return

    def warn_truncated(agg: dict) -> None:
        # satellite of the max_steps bugfix: a truncated run used to look
        # like a finished one; now the aggregate says so and we warn
        if agg.get("truncated"):
            print("WARNING: run truncated at max_steps — metrics cover a "
                  "partial horizon, not the full simulated window")

    if args.token_engine:
        from repro.serving.token_engine import (ragged_decode_trace,
                                                run_token_serving)
        from repro.configs.base import get_config
        cfg = get_config(args.arch or "gemma2-2b")
        prof = dm.llm_profile(cfg, mode="decode", kv_seq_budget=1024)
        trace = ragged_decode_trace(args.requests, args.seed,
                                    rate_rps=args.rate_rps)
        if args.prefill_mode == "disagg":
            from repro.serving.disagg import run_disagg_serving
            rep = run_disagg_serving(
                prof, seed=args.seed, trace=trace,
                n_prefill=args.prefill_pool, kv_seq_budget=1024,
                max_slots=args.slots, mtl=args.mtl,
                ttft_slo_s=args.ttft_slo_ms / 1e3,
                tpot_slo_s=args.tpot_slo_ms / 1e3,
                use_controller=args.controller == "hybrid")
            warn_truncated(rep)
            assert rep["conserved"], "request conservation violated"
            fab = rep["fabric"]
            print(f"token-engine[{cfg.name}] disagg: "
                  f"{args.prefill_pool}-member prefill pool over "
                  f"{fab['interconnect']} "
                  f"({fab['bw_bps'] / 1e9:.0f} GB/s): goodput "
                  f"{rep['goodput_tokens_s']:.0f} tok/s, TTFT p95 "
                  f"{rep['ttft_p95_s'] * 1e3:.0f}ms (attain "
                  f"{rep['ttft_attainment']:.3f}), TPOT p95 "
                  f"{rep['tpot_p95_s'] * 1e3:.2f}ms (attain "
                  f"{rep['tpot_attainment']:.3f}), KV moved "
                  f"{fab['bytes_moved'] / 1e9:.1f} GB in "
                  f"{fab['transfers']} transfers "
                  f"({fab['busy_s'] * 1e3:.0f}ms on the wire)")
            return
        policies = (["continuous", "static"] if args.token_policy == "both"
                    else [args.token_policy])
        print(f"token-engine[{cfg.name}]: {len(trace)} requests @ "
              f"{args.rate_rps:.1f} req/s, {args.slots} slots, "
              f"prefill={args.prefill_mode}, TTFT SLO "
              f"{args.ttft_slo_ms:.0f}ms / TPOT SLO "
              f"{args.tpot_slo_ms:.1f}ms")
        reports = {}
        for pol in policies:
            rep = run_token_serving(
                prof, policy=pol, seed=args.seed, trace=trace,
                max_slots=args.slots, static_bs=args.slots, mtl=args.mtl,
                ttft_slo_s=args.ttft_slo_ms / 1e3,
                tpot_slo_s=args.tpot_slo_ms / 1e3,
                use_controller=args.controller == "hybrid",
                prefill_mode=args.prefill_mode,
                chunk_tokens=args.prefill_chunk)
            warn_truncated(rep)
            assert rep["conserved"], "request conservation violated"
            reports[pol] = rep
            print(f"  {pol:>10}: goodput {rep['goodput_tokens_s']:.0f} "
                  f"tok/s (throughput {rep['throughput_tokens_s']:.0f}), "
                  f"TTFT p95 {rep['ttft_p95_s']*1e3:.0f}ms "
                  f"(attain {rep['ttft_attainment']:.3f}), TPOT p95 "
                  f"{rep['tpot_p95_s']*1e3:.2f}ms "
                  f"(attain {rep['tpot_attainment']:.3f}), "
                  f"mean live slots {rep['mean_live_slots']:.1f}, "
                  f"conservation OK")
        if len(reports) == 2:
            ratio = (reports["continuous"]["goodput_tokens_s"]
                     / max(reports["static"]["goodput_tokens_s"], 1e-9))
            print(f"  continuous/static goodput ratio: {ratio:.2f}x")
        return

    if args.scenarios:
        from repro.serving.cluster import run_scenario_cluster
        if args.controller not in ("dnnscaler", "hybrid"):
            ap.error("--scenarios supports --controller dnnscaler or "
                     "hybrid")
        mode = "hybrid" if args.controller == "hybrid" else "auto"
        rep = run_scenario_cluster(
            args.scenario_traffic, spot=args.spot,
            power_policy=args.power_policy,
            n_devices=args.devices or 4,
            horizon_s=args.seconds or 150.0, mode=mode, seed=args.seed,
            vectorized=args.vectorized,
            record=args.record, record_store=store)
        agg = rep["aggregate"]
        warn_truncated(agg)
        assert agg["conserved"], "request conservation violated"
        cap = "spot" if args.spot else "fixed"
        jpg = agg["joules_per_good_request"]
        print(f"scenario[{args.scenario_traffic}/{cap}/"
              f"{args.power_policy or 'legacy'}]: {agg['jobs']} tenancies "
              f"on {agg['devices']} devices — goodput {agg['goodput']:.1f}"
              f"/s, min attainment {agg['min_attainment']:.3f}, "
              f"conservation OK")
        print(f"  energy {agg['energy_j']:.0f}J (idle "
              f"{agg['idle_energy_j']:.0f}J + dynamic "
              f"{agg['dynamic_energy_j']:.0f}J) on "
              f"{agg['devices_powered']} powered devices — "
              + (f"{jpg:.4f} J per good request" if jpg is not None
                 else "no good requests"))
        if args.spot:
            print(f"  {agg['preemptions']} revocations: "
                  f"{agg['preempt_evacuated']} tenants evacuated, "
                  f"{agg['preempt_killed']} force-killed at the grace "
                  f"deadline")
        for r in rep["per_job"]:
            share = f"{r['share']:.3f}" if r["share"] is not None else "—"
            flags = "".join(("P" if r["preempted"] else "",
                             "M" if r["migrations"] else ""))
            print(f"  job {r['job_id']:>5} {r['dnn']:<26} share {share:>6} "
                  f"attain {r['slo_attainment']:.3f} {flags}")
        return

    if args.partition:
        from repro.serving.cluster import run_partition_cluster
        if args.controller not in ("dnnscaler", "hybrid"):
            ap.error("--partition supports --controller dnnscaler or hybrid")
        mode = "hybrid" if args.controller == "hybrid" else "auto"
        rep = run_partition_cluster(args.partition_policy, mode=mode,
                                    n_devices=args.devices or 3,
                                    horizon_s=args.seconds or 120.0,
                                    seed=args.seed, profile_store=store,
                                    vectorized=args.vectorized,
                                    record=args.record, record_store=store)
        agg = rep["aggregate"]
        warn_truncated(agg)
        assert agg["conserved"], "request conservation violated"
        print(f"partition[{args.partition_policy}/{mode}]: {agg['jobs']} "
              f"tenancies on {agg['devices']} devices "
              f"(kind={agg['partition']}) — goodput {agg['goodput']:.1f}/s, "
              f"throughput {agg['aggregate_throughput']:.1f}/s")
        print(f"  {agg['resizes']} resizes "
              f"({agg['resize_stall_s']:.2f}s stalls vs "
              f"{agg['resize_equiv_migration_stall_s']:.1f}s had each been "
              f"a migration), {agg['migrations']} migrations "
              f"({agg['migration_stall_s']:.1f}s)")
        for r in rep["per_job"]:
            share = f"{r['share']:.3f}" if r["share"] is not None else "—"
            print(f"  job {r['job_id']:>5} {r['dnn']:<26} share {share:>6} "
                  f"bs {r['bs']:>3} mtl {r['mtl']:>2} "
                  f"thr {r['throughput']:>7.1f}/s "
                  f"attain {r['slo_attainment']:.3f}")
        return

    if args.churn:
        from repro.serving.cluster import run_churn_cluster
        if args.controller not in ("dnnscaler", "hybrid"):
            ap.error("--churn supports --controller dnnscaler or hybrid")
        mode = "hybrid" if args.controller == "hybrid" else "auto"
        rep = run_churn_cluster(args.churn_policy, mode=mode,
                                n_devices=args.devices or 5,
                                horizon_s=args.seconds or 150.0,
                                seed=args.seed, profile_store=store,
                                vectorized=args.vectorized,
                                record=args.record, record_store=store)
        agg = rep["aggregate"]
        warn_truncated(agg)
        assert agg["conserved"], "request conservation violated"
        print(f"churn[{args.churn_policy}/{mode}]: {agg['jobs']} tenancies "
              f"on {agg['devices']} devices — goodput {agg['goodput']:.1f}"
              f"/s, throughput {agg['aggregate_throughput']:.1f}/s, "
              f"{agg['admissions']} admissions / {agg['drains']} drains / "
              f"{agg['migrations']} migrations "
              f"({agg['migration_stall_s']:.1f}s stalls), "
              f"conservation OK")
        if store is not None:
            s = store.stats()
            print(f"  profile store {s['root']}: "
                  f"{rep['aggregate'].get('store_rows_loaded', 0)} rows "
                  f"loaded / {rep['aggregate'].get('store_rows_evicted', 0)} "
                  f"evicted on load; now "
                  f"{s['sections'].get('surfaces', 0)} surface rows, "
                  f"{s['sections'].get('migrations', 0)} migration "
                  f"calibrations")
        return

    if args.cluster:
        from repro.serving.cluster import run_paper_cluster
        if args.controller == "static":
            ap.error("--controller static is not supported with --cluster "
                     "(per-job static knobs have no cluster-wide meaning); "
                     "choose dnnscaler, hybrid, or clipper")
        for flag, val, default in (("--job", args.job, None),
                                   ("--arch", args.arch, None),
                                   ("--slo-ms", args.slo_ms, None),
                                   ("--bs", args.bs, 1),
                                   ("--mtl", args.mtl, 1)):
            if val != default:
                ap.error(f"{flag} has no effect with --cluster "
                         "(jobs use their Table-4 SLOs and scaler-chosen "
                         "knobs)")
        mode = {"dnnscaler": "auto", "hybrid": "hybrid",
                "clipper": "clipper"}[args.controller]
        rep = run_paper_cluster(mode, n_devices=args.devices or 12,
                                sim_time_limit=args.seconds or 90.0,
                                seed=args.seed, vectorized=args.vectorized,
                                record=args.record, record_store=store)
        agg = rep["aggregate"]
        warn_truncated(agg)
        print(f"cluster[{mode}]: {agg['jobs']} jobs on {agg['devices']} "
              f"devices — aggregate {agg['aggregate_throughput']:.1f} "
              f"items/s, {agg['jobs_meeting_slo']}/{agg['feasible_jobs']} "
              f"feasible jobs meet SLO, stalls {agg['total_stall_s']:.1f}s")
        return

    if args.job is not None:
        job = PAPER_JOBS[args.job - 1]
        prof = job.profile()
        slo = args.slo_ms / 1e3 if args.slo_ms else job.slo_s
        executor = SimExecutor(prof, seed=args.seed)
        ctrl = make_controller(args.controller, executor, slo, job.job_id,
                               args.bs, args.mtl)
        engine = ServingEngine(SimExecutor(prof, seed=args.seed + 1), slo)
        label = f"job{job.job_id} {prof.name}"
    elif args.arch and args.real:
        executor, cfg = real_executor_for(args.arch, args.tiny, args.seed)
        base = executor.mean_latency(1, 1)
        from repro.serving.token_engine import memory_slot_cap
        max_bs = memory_slot_cap(executor, 128)
        slo = args.slo_ms / 1e3 if args.slo_ms else base * 4
        lib = surface_key = None
        if store is not None and args.controller in ("dnnscaler", "hybrid"):
            # cross-run warm start: prior runs of this architecture seed
            # the scaler through the persisted shared surface
            from repro.core.matrix_completion import SurfaceLibrary
            from repro.perf import autotune as _at
            lib = SurfaceLibrary()
            surface_key = f"{cfg.name}/serve"
            res = store.load_surfaces(lib,
                                      device_class=executor.device_class,
                                      autotune_generation=_at.generation())
            print(f"profile store: {len(res['loaded'])} surface rows "
                  f"loaded, {len(res['evicted'])} evicted")
        ctrl = make_controller(args.controller, executor, slo,
                               surface_library=lib, surface_key=surface_key,
                               max_bs=max_bs)
        engine = ServingEngine(executor, slo, instance_launch_s=0.2)
        label = f"{cfg.name} (real)"
    else:
        from repro.configs.base import get_config
        cfg = get_config(args.arch)
        prof = dm.llm_profile(cfg, mode="decode")
        base = dm.batch_latency(dm.TPU_V5E, prof, 1)
        slo = args.slo_ms / 1e3 if args.slo_ms else base * 4
        executor = SimExecutor(prof, device=dm.TPU_V5E, seed=args.seed,
                               mesh_shape=(16, 16))
        ctrl = make_controller(args.controller, executor, slo)
        engine = ServingEngine(
            SimExecutor(prof, device=dm.TPU_V5E, seed=args.seed + 1,
                        mesh_shape=(16, 16)), slo)
        label = f"{cfg.name} (TPU submesh tenancy)"

    acc = engine.run(ctrl, max_steps=args.steps)
    s = acc.summary()
    act = ctrl.action()
    approach = getattr(ctrl, "approach", args.controller)
    print(f"{label}: controller={args.controller} approach={approach} "
          f"steady(bs={act.bs}, mtl={act.mtl})")
    real = isinstance(executor, RealExecutor)
    # a real executor's power is assumed, not measured: no power_eff
    power = "" if real else f"  power_eff {s['power_efficiency']:.2f}/W"
    print(f"  throughput {s['throughput']:.1f}/s  p95 {s['p95_s']*1e3:.1f}ms "
          f"(SLO {slo*1e3:.1f}ms)  attainment {s['slo_attainment']:.3f}"
          + power)
    out = {"label": label, "approach": approach, "bs": act.bs,
           "mtl": act.mtl, "slo_s": slo, "summary": s}
    if real:
        cs = executor.cache_stats
        print(f"  exec-cache hits {cs.hits} misses {cs.misses} "
              f"(hit rate {cs.hit_rate:.2f})  compile "
              f"{cs.compile_time_s:.2f}s charged "
              f"{s['compile_stall_s']:.2f}s  max_bs {max_bs}")
        out["cache_stats"] = cs
    if hasattr(ctrl, "probe_count"):
        print(f"  probes: {ctrl.probe_count} distinct (bs, mtl) points")
    if store is not None and getattr(ctrl, "surface_library", None) is not None:
        from repro.perf import autotune as _at
        wrote = store.persist_surface(
            ctrl.surface_library, ctrl.surface_key,
            signature=ctrl.surface_key, device_class=executor.device_class,
            autotune_generation=_at.generation())
        store.save()
        print(f"  profile store: surface row "
              f"{'persisted' if wrote else 'too sparse to persist'} "
              f"({store.path})")
    return out


if __name__ == "__main__":
    main()
