#!/usr/bin/env python3
"""Smoke run of the serving path on a TPU, at the published widths of
smollm-360m with random weights made from ``--seed``.

    python3 chip_smoke.py             # one chip: requests, then serving loop
    python3 chip_smoke.py --chips 4   # sharded prefill/decode vs one device

One chip runs two phases.  *Requests*: eight prompts go through jitted
``api.prefill`` and then greedy ``api.decode_step`` calls, once with the XLA
kernels and once with the Pallas kernels (teacher-forced on the XLA run's
tokens); logits and argmax must agree and each Pallas executable must hold a
compiled kernel (``tpu_custom_call``).  A third run, the XLA path in f32 at
full matmul precision on the same tokens, is the witness both bf16 runs are
measured against.  *Serving loop*: the paper's
controller (``repro.launch.serve --real --controller hybrid``) picks Batching
or Multi-Tenancy and scales the knobs on wall-clock steps.

``--chips 4`` runs only the sharded ``make_prefill_step`` and
``make_decode_step`` on a 2x2 (data, model) mesh and compares them with
single-device prefill and decode on the same inputs.

Everything runs in this one process.  With no TPU, or when a phase fails,
the script exits non-zero and prints no result.  The last line of a passing
run is one JSON object naming the device.  Compiled programs are kept in
JAX's persistent cache (``repro.launch.compile_cache``), so a second run in
the same checkout compiles less.
"""

from __future__ import annotations

import argparse
import functools
import json
import sys
import time
from pathlib import Path

SRC = Path(__file__).resolve().parent / "src"

# Requests phase: BATCH prompts of PROMPT tokens prefilled into a CAPACITY
# cache, then DECODE_STEPS greedy decode steps.  The four-chip phase uses the
# same prompts and SHARDED_DECODE_STEPS decode steps; the serving loop runs
# SERVING_STEPS controller steps.
BATCH, PROMPT, CAPACITY = 8, 512, 1024
DECODE_STEPS = 32
SHARDED_DECODE_STEPS = 4
SERVING_STEPS = 40

# Pallas-vs-XLA and sharded-vs-one-device tolerance: the decode tolerance
# of tests/test_models.py and tests/test_distributed.py.  Its prefill
# tolerance (3e-2, set on two-layer configs) is reported, not enforced: at
# 32 layers a few prefill logits of the two bf16 paths differ by up to
# 0.041 on a v5e, and the bf16 XLA path alone strays past 3e-2 from its f32
# witness.  So the Pallas path is held to the witness instead: its max and
# rms distance from it may exceed the bf16 XLA path's by WITNESS_SLACK at
# most.
TOL = dict(atol=5e-2, rtol=5e-2)
TWO_LAYER_PREFILL_TOL = dict(atol=3e-2, rtol=3e-2)
WITNESS_SLACK = 1.25
# Greedy tokens may differ only where the XLA run's own top two logits lie
# within TIE_GAP of each other: two runs each within atol of one another
# can swap such a pair, and no other.
TIE_GAP = 2 * TOL["atol"]


def _tpu_devices(chips: int):
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise SystemExit(f"chip_smoke: JAX found no TPU (platform "
                         f"{devices[0].platform!r})")
    if len(devices) < chips:
        raise SystemExit(f"chip_smoke: --chips {chips} needs {chips} TPU "
                         f"devices, JAX found {len(devices)}")
    return devices


def _argmax_agreement(ref, got) -> tuple[int, int, float]:
    """(exact, near-tie, widest near-tie gap) over the rows.  A row whose
    argmax differs passes only when the reference's logits for the two
    tokens lie within TIE_GAP; any other row raises."""
    import numpy as np
    a_ref, a_got = ref.argmax(-1), got.argmax(-1)
    rows = np.arange(ref.shape[0])
    gap = ref[rows, a_ref] - ref[rows, a_got]
    differ = a_ref != a_got
    bad = differ & (gap > TIE_GAP)
    if bad.any():
        raise AssertionError(
            f"argmax differs beyond a near-tie in rows {np.flatnonzero(bad)}: "
            f"reference gap {gap[bad]} > {TIE_GAP}")
    return (int((~differ).sum()), int(differ.sum()),
            float(gap[differ].max(initial=0.0)))


def _prefill(params, batch, *, cfg, capacity):
    from repro.models import api
    return api.prefill(params, batch, cfg, capacity)


def _decode(params, cache, tokens, pos, *, cfg):
    from repro.models import api
    return api.decode_step(params, cache, tokens, pos, cfg)


def _outside(ref, got, tol) -> int:
    import numpy as np
    return int((np.abs(got - ref) > tol["atol"] + tol["rtol"] * np.abs(ref))
               .sum())


def _run_requests(cfg, params, tokens, forced):
    """Compiles prefill and decode for ``cfg`` and runs the prompts through
    them, feeding each step's greedy token or, given ``forced``, those
    tokens.  Returns the logits of every step, the tokens fed, and the
    ``tpu_custom_call`` count of each executable."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    prefill = jax.jit(functools.partial(_prefill, cfg=cfg, capacity=CAPACITY))
    decode = jax.jit(functools.partial(_decode, cfg=cfg), donate_argnums=(1,))
    t0 = time.perf_counter()
    prefill_x = prefill.lower(params, {"tokens": tokens}).compile()
    cache_abs = jax.eval_shape(prefill, params, {"tokens": tokens})[1]
    decode_x = decode.lower(
        params, cache_abs, jax.ShapeDtypeStruct((BATCH,), jnp.int32),
        jax.ShapeDtypeStruct((), jnp.int32)).compile()
    compile_s = time.perf_counter() - t0
    kernels = (prefill_x.as_text().count("tpu_custom_call"),
               decode_x.as_text().count("tpu_custom_call"))

    t0 = time.perf_counter()
    out, cache = prefill_x(params, {"tokens": tokens})
    seq = [out]
    tok = forced[0] if forced else jnp.argmax(out, -1).astype(jnp.int32)
    fed = [tok]
    for i in range(DECODE_STEPS):
        out, cache = decode_x(params, cache, tok,
                              jnp.asarray(PROMPT + i, jnp.int32))
        seq.append(out)
        tok = (forced[i + 1] if forced
               else jnp.argmax(out, -1).astype(jnp.int32))
        fed.append(tok)
    jax.block_until_ready(seq)
    run_s = time.perf_counter() - t0
    print(f"requests[{cfg.kernel_impl}, {cfg.dtype}]: compile {compile_s:.3f}s "
          f"(prefill + decode), run {run_s:.3f}s for prefill "
          f"{BATCH}x{PROMPT} + {DECODE_STEPS} decode steps, tpu_custom_call "
          f"prefill {kernels[0]} decode {kernels[1]}", flush=True)
    return [np.asarray(x, np.float32) for x in seq], fed, kernels


def requests_phase(cfg, seed: int) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.models import api

    t0 = time.perf_counter()
    params = jax.block_until_ready(
        api.init_params(jax.random.PRNGKey(seed), cfg))
    tokens = jax.block_until_ready(jax.random.randint(
        jax.random.PRNGKey(seed + 1), (BATCH, PROMPT), 0, cfg.vocab_size,
        jnp.int32))
    print(f"requests: parameters and prompts made in "
          f"{time.perf_counter() - t0:.3f}s", flush=True)

    xla, fed, _ = _run_requests(cfg.replace(kernel_impl="xla"), params,
                                tokens, None)
    pallas, _, kernels = _run_requests(cfg.replace(kernel_impl="pallas"),
                                       params, tokens, fed)
    # the witness: the XLA path on the same weights, in f32 at full matmul
    # precision, fed the same tokens
    params32 = jax.tree.map(lambda x: x.astype(jnp.float32), params)
    with jax.default_matmul_precision("highest"):
        witness, _, _ = _run_requests(
            cfg.replace(kernel_impl="xla", dtype="float32"), params32,
            tokens, fed)

    def worst(a, b):
        return [float(np.abs(x - y).max()) for x, y in zip(a, b)]

    def rms(a, b):
        return float(np.sqrt(np.mean([np.mean((x - y) ** 2)
                                      for x, y in zip(a, b)])))

    d_px = worst(pallas, xla)
    tight = TWO_LAYER_PREFILL_TOL
    print(f"requests: pallas vs xla max |dlogit| prefill {d_px[0]:.5f} "
          f"({_outside(xla[0], pallas[0], tight)} of {xla[0].size} outside "
          f"{tight['atol']}), decode {max(d_px[1:]):.5f} (worst step "
          f"{int(np.argmax(d_px[1:]))})", flush=True)
    # (max, rms) distance of the bf16 XLA and Pallas paths from the witness
    strays = {}
    for name, sl in (("prefill", slice(0, 1)), ("decode", slice(1, None))):
        x_err, p_err = strays[name] = [
            (max(worst(got[sl], witness[sl])), rms(got[sl], witness[sl]))
            for got in (xla, pallas)]
        print(f"requests: {name} vs f32 witness, max |dlogit| / rms: xla "
              f"{x_err[0]:.5f} / {x_err[1]:.5f}, pallas {p_err[0]:.5f} / "
              f"{p_err[1]:.5f}", flush=True)
    print(f"requests: prefill logits outside {tight['atol']} of the f32 "
          f"witness: xla {_outside(witness[0], xla[0], tight)}, pallas "
          f"{_outside(witness[0], pallas[0], tight)}", flush=True)
    exact = ties = 0
    widest = 0.0
    for i, (x, p) in enumerate(zip(xla, pallas)):
        if not np.isfinite(p).all():
            raise AssertionError(f"non-finite Pallas logits at step {i}")
        np.testing.assert_allclose(p, x, **TOL,
                                   err_msg=f"pallas vs xla, step {i}")
        e, t, g = _argmax_agreement(x, p)
        exact, ties, widest = exact + e, ties + t, max(widest, g)
    print(f"requests: argmax agrees in {exact} of {exact + ties} rows, "
          f"{ties} near-ties (widest XLA gap {widest:.5f}, allowed "
          f"{TIE_GAP})", flush=True)
    for name, (x_err, p_err) in strays.items():
        for what, p, x in zip(("max |dlogit|", "rms"), p_err, x_err):
            if p > WITNESS_SLACK * x:
                raise AssertionError(
                    f"{name}: pallas strays from the f32 witness by {what} "
                    f"{p:.5f}, more than {WITNESS_SLACK} x the bf16 XLA "
                    f"path's {x:.5f}")
    if min(kernels) == 0:
        raise AssertionError(f"a Pallas executable holds no compiled "
                             f"kernel: {kernels}")


def serving_phase(arch: str, seed: int) -> None:
    import math
    from repro.launch import serve
    t0 = time.perf_counter()
    out = serve.main(["--arch", arch, "--real", "--controller", "hybrid",
                      "--steps", str(SERVING_STEPS), "--seed", str(seed)])
    wall = time.perf_counter() - t0
    cs, s = out["cache_stats"], out["summary"]
    if not (math.isfinite(s["throughput"]) and s["throughput"] > 0):
        raise AssertionError(f"serving loop throughput {s['throughput']}")
    print(f"serving: approach {out['approach']} steady bs {out['bs']} mtl "
          f"{out['mtl']}; {cs.misses} compiles in {cs.compile_time_s:.3f}s, "
          f"exec-cache hits {cs.hits} misses {cs.misses}; {SERVING_STEPS} "
          f"steps in {wall:.3f}s wall", flush=True)


def sharded_phase(cfg, seed: int) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.configs.base import InputShape
    from repro.launch import steps as steps_lib
    from repro.launch.mesh import make_host_mesh
    from repro.models import api

    minfo = make_host_mesh(data=2, model=2)
    t0 = time.perf_counter()
    params = api.init_params(jax.random.PRNGKey(seed), cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(seed + 1),
                                (BATCH, PROMPT), 0, cfg.vocab_size, jnp.int32)
    prefill_ref = jax.jit(functools.partial(_prefill, cfg=cfg,
                                            capacity=CAPACITY))
    decode_ref = jax.jit(functools.partial(_decode, cfg=cfg))

    ref_logits, ref_cache = prefill_ref(params, {"tokens": tokens})
    fed, ref_seq = [], [ref_logits]
    cache = ref_cache
    for i in range(SHARDED_DECODE_STEPS):
        fed.append(jnp.argmax(ref_seq[-1], -1).astype(jnp.int32))
        out, cache = decode_ref(params, cache, fed[-1],
                                jnp.asarray(PROMPT + i, jnp.int32))
        ref_seq.append(out)
    jax.block_until_ready(ref_seq)
    ref_s = time.perf_counter() - t0

    with minfo.mesh:
        t0 = time.perf_counter()
        prefill_fn, *_ = steps_lib.make_prefill_step(
            cfg, minfo, InputShape("smoke", PROMPT, BATCH, "prefill"),
            capacity=CAPACITY)
        logits, sh_cache = prefill_fn(params, {"tokens": tokens})
        decode_fn, *_ = steps_lib.make_decode_step(
            cfg, minfo, InputShape("smoke", CAPACITY, BATCH, "decode"))
        seq = [logits]
        # decode from the reference cache, as the single-device run did
        cache = jax.tree.map(jnp.copy, ref_cache)
        for i in range(SHARDED_DECODE_STEPS):
            out, cache = decode_fn(params, cache, fed[i],
                                   jnp.asarray(PROMPT + i, jnp.int32))
            seq.append(out)
        jax.block_until_ready(seq)
        wall = time.perf_counter() - t0

    worst = 0.0
    for i, (a, b) in enumerate(zip(seq, ref_seq)):
        a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
        np.testing.assert_allclose(a, b, **TOL,
                                   err_msg=f"sharded vs one device, step {i}")
        worst = max(worst, float(np.abs(a - b).max()))
    for a, b in zip(jax.tree.leaves(sh_cache), jax.tree.leaves(ref_cache)):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32), **TOL,
                                   err_msg="sharded vs one-device cache")
    print(f"sharded[data=2, model=2]: prefill {BATCH}x{PROMPT} + "
          f"{SHARDED_DECODE_STEPS} decode steps agree with one device, max |dlogit| {worst:.5f}; "
          f"one device (parameter init, compile, run) {ref_s:.3f}s, "
          f"sharded (compile, run) {wall:.3f}s", flush=True)


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    if not (SRC / "repro").is_dir():
        raise SystemExit(f"chip_smoke: no repository source at {SRC}")
    sys.path.insert(0, str(SRC))

    devices = _tpu_devices(args.chips)
    from repro.configs.base import get_config
    from repro.launch import compile_cache
    print(f"compile cache: {compile_cache.configure()}", flush=True)
    cfg = get_config("smollm-360m")
    t0 = time.perf_counter()
    if args.chips == 4:
        sharded_phase(cfg, args.seed)
    else:
        requests_phase(cfg, args.seed)
        serving_phase(cfg.name, args.seed)
    print(f"phases done in {time.perf_counter() - t0:.3f}s", flush=True)
    dev = devices[0]
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))


if __name__ == "__main__":
    main()
