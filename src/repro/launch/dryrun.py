import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (arch x input-shape x mesh).

The two lines above MUST run before any other import (jax locks the device
count at first init); 512 placeholder CPU devices back the production meshes:

    single-pod:  (16, 16)       ("data", "model")      256 chips
    multi-pod:   (2, 16, 16)    ("pod", "data", "model")  512 chips

For each combination this prints/records ``memory_analysis()`` (proves fit),
``cost_analysis()`` (FLOPs/bytes for the roofline) and the collective bytes
parsed from the optimized HLO.  Results land in experiments/dryrun/*.json.

Usage:
    PYTHONPATH=src python -m repro.launch.dryrun [--arch ID] [--shape NAME]
        [--mesh single|multi|both] [--out DIR]
"""

import argparse
import json
import time
import traceback

import jax

from repro.configs.base import ARCH_IDS, INPUT_SHAPES, get_config
from repro.distributed.sharding import MeshInfo
from repro.launch.mesh import make_production_mesh
from repro.launch import steps as steps_lib
from repro.perf import roofline


def skip_reason(cfg, shape) -> str | None:
    if shape.name == "long_500k" and not cfg.supports_long_context:
        return ("full-attention arch: long_500k needs an SSM, hybrid or "
                "sliding-window arch (ModelConfig.supports_long_context)")
    return None


def run_one(arch: str, shape_name: str, multi_pod: bool, out_dir: str,
            verbose: bool = True, variant: str = "baseline",
            step_kwargs: dict | None = None) -> dict:
    cfg = get_config(arch)
    shape = INPUT_SHAPES[shape_name]
    mesh_name = "multi" if multi_pod else "single"
    rec = {"arch": arch, "shape": shape_name, "mesh": mesh_name,
           "variant": variant, "chips": 512 if multi_pod else 256}

    reason = skip_reason(cfg, shape)
    if reason:
        rec["status"] = "SKIP"
        rec["reason"] = reason
        if out_dir:
            os.makedirs(out_dir, exist_ok=True)
            suffix = "" if variant == "baseline" else f"__{variant}"
            roofline.save_json(os.path.join(
                out_dir, f"{arch}__{shape_name}__{mesh_name}{suffix}.json"), rec)
        return rec

    t0 = time.time()
    try:
        minfo = MeshInfo(make_production_mesh(multi_pod=multi_pod))
        with minfo.mesh:
            fn, arg_specs, _, _ = steps_lib.make_step(cfg, minfo, shape,
                                                      **(step_kwargs or {}))
            lowered = fn.lower(*arg_specs)
            t_lower = time.time() - t0
            compiled = lowered.compile()
            t_compile = time.time() - t0 - t_lower
            mem = compiled.memory_analysis()
            rl = roofline.analyze(compiled, cfg, shape, rec["chips"])
        rec.update({
            "status": "OK",
            "lower_s": round(t_lower, 1),
            "compile_s": round(t_compile, 1),
            "memory_analysis": {
                "argument_size": getattr(mem, "argument_size_in_bytes", None),
                "output_size": getattr(mem, "output_size_in_bytes", None),
                "temp_size": getattr(mem, "temp_size_in_bytes", None),
                "alias_size": getattr(mem, "alias_size_in_bytes", None),
                "generated_code_size": getattr(
                    mem, "generated_code_size_in_bytes", None),
            },
            "roofline": rl.to_dict(),
        })
        if verbose:
            print(f"[{arch} x {shape_name} x {mesh_name} x {variant}] OK "
                  f"lower={t_lower:.0f}s compile={t_compile:.0f}s "
                  f"dominant={rl.dominant} "
                  f"t=(c {rl.t_compute*1e3:.2f} | m {rl.t_memory*1e3:.2f} | "
                  f"x {rl.t_collective*1e3:.2f}) ms "
                  f"useful={rl.useful_flops_ratio:.2f}")
            print(f"  memory_analysis: {mem}")
    except Exception as e:  # noqa: BLE001 — record the failure, keep going
        rec["status"] = "FAIL"
        rec["error"] = f"{type(e).__name__}: {e}"
        rec["traceback"] = traceback.format_exc()[-4000:]
        if verbose:
            print(f"[{arch} x {shape_name} x {mesh_name}] FAIL: {rec['error']}")

    if out_dir:
        os.makedirs(out_dir, exist_ok=True)
        suffix = "" if variant == "baseline" else f"__{variant}"
        fname = f"{arch}__{shape_name}__{mesh_name}{suffix}.json"
        roofline.save_json(os.path.join(out_dir, fname), rec)
    return rec


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None, help="single arch id (default all)")
    ap.add_argument("--shape", default=None, help="single shape (default all)")
    ap.add_argument("--mesh", default="both", choices=["single", "multi", "both"])
    ap.add_argument("--out", default="experiments/dryrun")
    ap.add_argument("--variant", default="baseline",
                    help="label; combine with --param-mode/--micro")
    ap.add_argument("--param-mode", default=None,
                    help="override inference param sharding: infer|tp")
    ap.add_argument("--micro", type=int, default=None,
                    help="override train microbatch count")
    args = ap.parse_args()

    step_kwargs = {}
    if args.param_mode:
        step_kwargs["param_mode"] = args.param_mode
    if args.micro:
        step_kwargs["num_microbatches"] = args.micro

    archs = [args.arch] if args.arch else list(ARCH_IDS)
    shapes = [args.shape] if args.shape else list(INPUT_SHAPES)
    meshes = {"single": [False], "multi": [True],
              "both": [False, True]}[args.mesh]

    results = []
    for arch in archs:
        for shape in shapes:
            for multi in meshes:
                results.append(run_one(arch, shape, multi, args.out,
                                       variant=args.variant,
                                       step_kwargs=step_kwargs))

    ok = sum(r["status"] == "OK" for r in results)
    skip = sum(r["status"] == "SKIP" for r in results)
    fail = sum(r["status"] == "FAIL" for r in results)
    print(f"\n=== dry-run summary: {ok} OK / {skip} SKIP / {fail} FAIL "
          f"of {len(results)} ===")
    for r in results:
        if r["status"] == "FAIL":
            print(f"  FAIL {r['arch']} x {r['shape']} x {r['mesh']}: "
                  f"{r['error']}")
    if fail:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
