"""Pallas kernel autotuner: per-(kernel, shape-class, dtype, backend) search
over tile parameters, with roofline-guided candidate pruning and a
persistent JSON cache.

The three Pallas kernels (flash attention, decode attention, SSD scan) ran
with hard-coded tile sizes regardless of shape or backend; every serving
configuration paid whatever that default cost.  This module searches the
small tile-parameter space per *shape class* (dims bucketed to powers of
two, so one tuning run covers a neighborhood of shapes), prunes obviously
bad tilings with the same arithmetic-intensity terms `perf/roofline.py`
uses (modeled bound time = max(flops/peak, bytes/bw), VMEM-footprint hard
limit), then wall-clock-times the survivors.  Timing is interpret-mode
safe: on CPU the kernels run in Pallas interpret mode, which is exactly
what CI exercises — the cache key carries the backend, so CPU-tuned
entries never leak onto a TPU.

Results persist in the cross-run profile store (``perf.profile_store``):
the ``autotune`` section of ``profile_store.json`` under
``configure(cache_dir=...)``, the ``REPRO_AUTOTUNE_CACHE`` env var (legacy
override), ``REPRO_PROFILE_STORE``, or ``.profile_store/`` in the working
directory — a legacy ``autotune_cache.json`` found in the same directory
is imported once on first touch.  Every persisted tuning bumps the store's
``autotune`` *generation* (``generation()``); the RealExecutor keys its
AOT executable cache on it, so a new tuning invalidates stale executables.
The ``kernels/*/ops.py`` wrappers consult ``lookup(...)`` when the caller
does not pass explicit tile kwargs: explicit kwargs always win, an empty
cache falls back to the historical hard-coded defaults, and
``tune_on_miss`` (off by default — CI must not spend minutes tuning) lets
``--autotune`` runs fill the cache.
"""

from __future__ import annotations

import json
import os
import time
from typing import Callable, Optional

import numpy as np

from repro.perf import profile_store
from repro.perf.roofline import HBM_BW, PEAK_FLOPS

VMEM_BYTES = 16 * 2 ** 20       # per-core VMEM budget (TPU v5e)
STEP_S = 0.4e-6                 # fixed cost of one Pallas grid step (v5e)
PRUNE_RATIO = 3.0               # keep candidates within this factor of the
                                # best modeled bound time
DEFAULT_CACHE_DIR = profile_store.DEFAULT_STORE_DIR
_LEGACY_CACHE_FILE = "autotune_cache.json"

# Hard-coded defaults — the fallback when the cache is empty, and always
# kept in the candidate set so tuning can only improve on them.  The decode
# kernel's None values are derived from the operand shapes
# (``kernels/decode_attention/ops.py``, ``decode_tiling``).
DEFAULTS = {
    "flash_attention": {"block_q": 128, "block_k": 128},
    "decode_attention": {"rows": None, "block_k": None},
    "paged_decode_attention": {"page_size": 64},
    "ssd_scan": {"chunk": 128},
}

_state = {
    "cache_dir": None,            # resolved lazily (env var wins)
    "tune_on_miss": False,
    "enabled": True,
    "legacy_checked": None,       # root whose legacy file was imported
    "hits": 0,
    "misses": 0,
    "timings": 0,                 # individual candidate timings run
    "tunes": 0,                   # full searches run
}


def configure(cache_dir: Optional[str] = None,
              tune_on_miss: Optional[bool] = None,
              enabled: Optional[bool] = None) -> None:
    """Set autotuner behavior; any argument left None is unchanged."""
    if cache_dir is not None:
        _state["cache_dir"] = cache_dir
        _state["legacy_checked"] = None
        _store().reload()         # re-read from the (possibly new) location
    if tune_on_miss is not None:
        _state["tune_on_miss"] = tune_on_miss
    if enabled is not None:
        _state["enabled"] = enabled


def cache_dir() -> str:
    return (_state["cache_dir"] or os.environ.get("REPRO_AUTOTUNE_CACHE")
            or profile_store.default_root())


def cache_path() -> str:
    return os.path.join(cache_dir(), profile_store.STORE_FILE)


def _store() -> profile_store.ProfileStore:
    return profile_store.store_for(cache_dir())


def generation() -> int:
    """The resident tuned-tile generation: bumped on every persisted
    tuning.  The RealExecutor folds it into its AOT executable-cache key
    so a new tuning invalidates stale executables."""
    return _store().generation("autotune")


def cache_stats() -> dict:
    mem = _load()
    return {"entries": len(mem), "hits": _state["hits"],
            "misses": _state["misses"], "timings": _state["timings"],
            "tunes": _state["tunes"], "generation": generation(),
            "cache_dir": cache_dir()}


def reset_counters() -> None:
    _state.update(hits=0, misses=0, timings=0, tunes=0)


def _load() -> dict:
    """The autotune section of the profile store, importing a legacy
    pre-store ``autotune_cache.json`` sitting in the same directory once
    (earlier PRs' tuned tiles keep working after the migration)."""
    store = _store()
    sec = store.section("autotune")
    if not sec and _state["legacy_checked"] != store.root:
        _state["legacy_checked"] = store.root
        try:
            with open(os.path.join(cache_dir(), _LEGACY_CACHE_FILE)) as f:
                legacy = json.load(f)
        except (OSError, ValueError):
            legacy = None
        if isinstance(legacy, dict):
            for k, v in legacy.items():
                store.put("autotune", k, v)
    return sec


def _save() -> None:
    _store().save()


def _backend() -> str:
    import jax
    return jax.default_backend()


def _bucket(n: int, floor: int = 8) -> int:
    """Next power of two >= n: one tuning run per shape neighborhood."""
    b = floor
    while b < n:
        b *= 2
    return b


def _dtype_name(dtype) -> str:
    return np.dtype(dtype).name if not hasattr(dtype, "name") else dtype.name


# ---------------------------------------------------------------------------
# Shape classes: the cache key dims per kernel (bucketed where continuous).
# ---------------------------------------------------------------------------
def shape_class(kernel: str, **dims) -> dict:
    # BKV / H: the parallel grid axes.  They do not change which tiling is
    # arithmetically best on TPU, but they multiply the per-grid-step
    # overhead that dominates interpret-mode timing — leaving them out made
    # the tuner pick chunk sizes that lost on the caller's real head count.
    if kernel == "flash_attention":
        return {"BKV": _bucket(dims.get("BKV", 1), 1),
                "G": dims["G"], "hd": dims["hd"],
                "Tq": _bucket(dims["Tq"]), "Tk": _bucket(dims["Tk"]),
                "causal": bool(dims["causal"])}
    if kernel == "decode_attention":
        return {"BKV": _bucket(dims.get("BKV", 1), 1),
                "G": dims["G"], "hd": dims["hd"], "S": _bucket(dims["S"])}
    if kernel == "paged_decode_attention":
        # S is the per-slot sequence BUDGET the paged cache is sized for —
        # the page size is a layout knob chosen at cache construction, so
        # the class is keyed the same way as the dense decode kernel
        return {"BKV": _bucket(dims.get("BKV", 1), 1),
                "G": dims["G"], "hd": dims["hd"], "S": _bucket(dims["S"])}
    if kernel == "ssd_scan":
        return {"H": _bucket(dims.get("H", 1), 1),
                "P": dims["P"], "N": dims["N"], "T": _bucket(dims["T"])}
    raise KeyError(kernel)


def _key(kernel: str, backend: str, dtype: str, cls: dict) -> str:
    dims = ",".join(f"{k}={v}" for k, v in sorted(cls.items()))
    return f"{kernel}|{backend}|{dtype}|{dims}"


# ---------------------------------------------------------------------------
# Candidate tilings + roofline models (bound time, VMEM footprint).
# ---------------------------------------------------------------------------
def _flash_candidates(cls: dict) -> list:
    out = []
    for bq in (32, 64, 128, 256):
        for bk in (32, 64, 128, 256):
            if bq <= cls["Tq"] and bk <= cls["Tk"]:
                out.append({"block_q": bq, "block_k": bk})
    return out or [dict(DEFAULTS["flash_attention"])]


def _flash_model(cls: dict, cand: dict, dtype: str) -> tuple:
    sz = np.dtype(dtype).itemsize
    G, hd, Tq, Tk = cls["G"], cls["hd"], cls["Tq"], cls["Tk"]
    bq, bk = cand["block_q"], cand["block_k"]
    nq, nk = Tq // bq, Tk // bk
    # q tile refetched per k step, k/v per q step; out written once
    bytes_ = sz * (G * Tq * hd * nk + 2 * Tk * hd * nq + G * Tq * hd)
    flops = 4.0 * G * Tq * Tk * hd
    eff = (min(G * bq, 128) / 128.0) * (min(bk, 128) / 128.0)
    bound = max(flops / (PEAK_FLOPS * eff), bytes_ / HBM_BW)
    vmem = (sz * (G * bq * hd + 2 * bk * hd)
            + 4 * (2 * G * bq * 128 + G * bq * hd + G * bq * bk))
    return bound, vmem


def _decode_candidates(cls: dict) -> list:
    return [dict(DEFAULTS["decode_attention"])] + [
        {"rows": r, "block_k": bk} for r in (1, 8, 32)
        for bk in (128, 256, 512, 1024, 2048)
        if r <= cls["BKV"] and bk <= cls["S"]]


def _decode_model(cls: dict, cand: dict, dtype: str) -> tuple:
    from repro.kernels.decode_attention.ops import decode_tiling
    BKV, G, hd, S = cls["BKV"], cls["G"], cls["hd"], cls["S"]
    t = decode_tiling(BKV, S, G, hd, dtype, rows=cand["rows"],
                      block_k=cand["block_k"])
    # every K/V tile once, q re-read per k step, out written once
    bytes_ = (t.steps * t.step_bytes
              + np.dtype(dtype).itemsize * BKV * G * hd * (S // t.block_k + 1))
    flops = 4.0 * BKV * G * S * hd
    eff = (min(G, 128) / 128.0) * (min(t.block_k, 128) / 128.0)
    bound = (max(flops / (PEAK_FLOPS * eff), bytes_ / HBM_BW)
             + t.steps * STEP_S)
    return bound, t.vmem_bytes


def _paged_candidates(cls: dict) -> list:
    out = [{"page_size": p} for p in (32, 64, 128, 256) if p <= cls["S"]]
    return out or [dict(DEFAULTS["paged_decode_attention"])]


def _paged_model(cls: dict, cand: dict, dtype: str) -> tuple:
    # a page is the paged kernel's k-block, one (batch x KV-head) row per
    # grid step: the dense decode kernel's terms at rows = 1, block_k =
    # page_size (the block table adds a few scalar-prefetch bytes per step)
    return _decode_model(cls, {"rows": 1, "block_k": cand["page_size"]},
                         dtype)


def _ssd_candidates(cls: dict) -> list:
    out = [{"chunk": c} for c in (32, 64, 128, 256)
           if c <= cls["T"] and cls["T"] % c == 0]
    return out or [dict(DEFAULTS["ssd_scan"])]


def _ssd_model(cls: dict, cand: dict, dtype: str) -> tuple:
    sz = np.dtype(dtype).itemsize
    P, N, T = cls["P"], cls["N"], cls["T"]
    c = cand["chunk"]
    # intra-chunk terms are quadratic in the chunk: smaller chunks do fewer
    # FLOPs, larger chunks fill the MXU — the classic SSD tradeoff
    flops = T * (2.0 * c * (N + P) + 4.0 * N * P)
    bytes_ = sz * (2 * T * P + T + 2 * T * N + P * N)
    eff = (min(c, 128) / 128.0) * (min(max(N, P), 128) / 128.0)
    bound = max(flops / (PEAK_FLOPS * eff), bytes_ / HBM_BW)
    vmem = 4 * (c * P + c + 2 * c * N + P * N + 3 * c * c)
    return bound, vmem


_KERNELS: dict = {
    "flash_attention": (_flash_candidates, _flash_model),
    "decode_attention": (_decode_candidates, _decode_model),
    "paged_decode_attention": (_paged_candidates, _paged_model),
    "ssd_scan": (_ssd_candidates, _ssd_model),
}


def prune_candidates(kernel: str, cls: dict, dtype: str,
                     ratio: float = PRUNE_RATIO) -> list:
    """Roofline-guided pruning: drop tilings whose modeled bound time is
    worse than `ratio` x the best model, or whose VMEM footprint cannot
    fit.  The hard-coded default survives unconditionally — pruning may
    only ever remove challengers, never the fallback."""
    cands_fn, model_fn = _KERNELS[kernel]
    cands = cands_fn(cls)
    scored = []
    for cand in cands:
        bound, vmem = model_fn(cls, cand, dtype)
        scored.append((cand, bound, vmem))
    feasible = [s for s in scored if s[2] <= VMEM_BYTES]
    if not feasible:
        feasible = scored            # degenerate: keep everything
    best = min(b for _, b, _ in feasible)
    kept = [c for c, b, _ in feasible if b <= ratio * best]
    default = DEFAULTS[kernel]
    if all(c != default for c in kept) and any(
            c == default for c in cands):
        kept.append(dict(default))
    return kept


# ---------------------------------------------------------------------------
# Timing (interpret-mode safe: runs the ops wrapper, which selects
# interpret mode on CPU automatically).
# ---------------------------------------------------------------------------
def _time_call(fn: Callable, iters: int = 3) -> float:
    import jax
    jax.block_until_ready(fn())     # compile / first-trace warmup
    times = []
    for _ in range(iters):
        t0 = time.perf_counter()
        jax.block_until_ready(fn())
        times.append(time.perf_counter() - t0)
    _state["timings"] += 1
    times.sort()
    return times[len(times) // 2]   # median: one OS spike must not decide


def _flash_bench(cls: dict, dtype: str, cand: dict) -> Callable:
    import jax
    import jax.numpy as jnp
    from repro.kernels.flash_attention.ops import flash_attention
    B = cls["BKV"]                  # folded batch*kv heads: the parallel grid
    G, hd, Tq, Tk = cls["G"], cls["hd"], cls["Tq"], cls["Tk"]
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (B, Tq, G, hd), jnp.float32).astype(dtype)
    k = jax.random.normal(ks[1], (B, Tk, 1, hd), jnp.float32).astype(dtype)
    v = jax.random.normal(ks[2], (B, Tk, 1, hd), jnp.float32).astype(dtype)
    return lambda: flash_attention(q, k, v, causal=cls["causal"],
                                   block_q=cand["block_q"],
                                   block_k=cand["block_k"])


def _decode_bench(cls: dict, dtype: str, cand: dict) -> Callable:
    import jax
    import jax.numpy as jnp
    from repro.kernels.decode_attention.ops import (decode_attention,
                                                    decode_tiling)
    B = cls["BKV"]
    G, hd, S = cls["G"], cls["hd"], cls["S"]
    # the tiling the kernel runs, resolved here: passing None would send
    # the wrapper back to the cache this search is filling
    t = decode_tiling(B, S, G, hd, dtype, rows=cand["rows"],
                      block_k=cand["block_k"])
    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    q = jax.random.normal(ks[0], (B, G, hd), jnp.float32).astype(dtype)
    kc = jax.random.normal(ks[1], (B, S, 1, hd), jnp.float32).astype(dtype)
    vc = jax.random.normal(ks[2], (B, S, 1, hd), jnp.float32).astype(dtype)
    pos = jnp.asarray(S - 1, jnp.int32)
    return lambda: decode_attention(q, kc, vc, pos, rows=t.rows,
                                    block_k=t.block_k)


def _paged_bench(cls: dict, dtype: str, cand: dict) -> Callable:
    # unlike block_k, the candidate page size changes the INPUT layout
    # (the page pool is built at that granularity), so each candidate is
    # timed end to end on its own cache layout — that IS the decision the
    # token engine makes once at cache construction
    import jax
    import jax.numpy as jnp
    from repro.kernels.decode_attention.ops import paged_decode_attention
    B = cls["BKV"]
    G, hd, S = cls["G"], cls["hd"], cls["S"]
    psz = cand["page_size"]
    npages = max(S // psz, 1)
    P = B * npages
    ks = jax.random.split(jax.random.PRNGKey(3), 3)
    q = jax.random.normal(ks[0], (B, G, hd), jnp.float32).astype(dtype)
    kp = jax.random.normal(ks[1], (P, psz, 1, hd), jnp.float32).astype(dtype)
    vp = jax.random.normal(ks[2], (P, psz, 1, hd), jnp.float32).astype(dtype)
    tbl = jnp.arange(P, dtype=jnp.int32).reshape(B, npages)
    lens = jnp.full((B,), S, jnp.int32)    # worst case: every slot full
    return lambda: paged_decode_attention(q, kp, vp, lens, tbl)


def _ssd_bench(cls: dict, dtype: str, cand: dict) -> Callable:
    import jax
    import jax.numpy as jnp
    from repro.kernels.ssd_scan.ops import ssd_scan
    H, P, N, T = cls["H"], cls["P"], cls["N"], cls["T"]
    ks = jax.random.split(jax.random.PRNGKey(2), 5)
    x = jax.random.normal(ks[0], (1, T, H, P)) * 0.5
    dt = jax.nn.softplus(jax.random.normal(ks[1], (1, T, H)))
    A = -jnp.exp(jax.random.normal(ks[2], (H,)) * 0.5)
    Bm = jax.random.normal(ks[3], (1, T, N)) * 0.5
    Cm = jax.random.normal(ks[4], (1, T, N)) * 0.5
    return lambda: ssd_scan(x, dt, A, Bm, Cm, chunk=cand["chunk"])


_BENCH = {"flash_attention": _flash_bench, "decode_attention": _decode_bench,
          "paged_decode_attention": _paged_bench, "ssd_scan": _ssd_bench}


# ---------------------------------------------------------------------------
# Public API: lookup (cache only, unless tune_on_miss) and tune (search).
# ---------------------------------------------------------------------------
def lookup(kernel: str, dtype, **dims) -> Optional[dict]:
    """Best-known tile config for this call site, or None (caller falls
    back to the hard-coded default).  Cache-only unless `tune_on_miss`."""
    if not _state["enabled"]:
        return None
    cls = shape_class(kernel, **dims)
    key = _key(kernel, _backend(), _dtype_name(dtype), cls)
    entry = _load().get(key)
    if entry is not None:
        _state["hits"] += 1
        return entry["config"]
    _state["misses"] += 1
    if _state["tune_on_miss"]:
        return tune(kernel, _dtype_name(dtype), **dims)["config"]
    return None


def tune(kernel: str, dtype: str = "float32", *, force: bool = False,
         iters: int = 3, prune: bool = True, **dims) -> dict:
    """Search tile configs for one shape class; persist and return the
    cache entry {config, us_per_call, candidates_timed, default_us}."""
    cls = shape_class(kernel, **dims)
    key = _key(kernel, _backend(), dtype, cls)
    mem = _load()
    if not force and key in mem:
        return mem[key]
    _state["tunes"] += 1
    cands = (prune_candidates(kernel, cls, dtype) if prune
             else _KERNELS[kernel][0](cls))
    bench = _BENCH[kernel]
    best, best_t, timed = None, float("inf"), {}
    for cand in cands:
        t = _time_call(bench(cls, dtype, cand), iters=iters)
        timed[json.dumps(cand, sort_keys=True)] = t * 1e6
        if t < best_t:
            best, best_t = cand, t
    default = DEFAULTS[kernel]
    default_us = timed.get(json.dumps(default, sort_keys=True))
    entry = {
        "config": dict(best),
        "us_per_call": best_t * 1e6,
        "default_us": default_us,
        "backend": _backend(),
        "shape_class": cls,
        "candidates_timed": timed,
    }
    mem[key] = entry
    # a new tuning invalidates AOT executables compiled under older tiles:
    # bumping the generation makes RealExecutor's cache key miss them
    _store().bump_generation("autotune")
    _save()
    return entry
