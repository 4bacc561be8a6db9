"""Autotune subsystem + AOT executor tests: cache round-trip (no re-timing),
roofline pruning keeps the measured best, ops fallback with an empty cache,
zero recompiles after RealExecutor warmup, vectorized pricing equivalence,
tail-window equivalence, and HybridScaler surface seeding."""

import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.perf import autotune
from repro.serving import device_model as dm


@pytest.fixture
def tuner(tmp_path):
    """Point the autotuner at a fresh cache dir; restore defaults after."""
    autotune.configure(cache_dir=str(tmp_path), tune_on_miss=False,
                       enabled=True)
    autotune.reset_counters()
    yield autotune
    autotune.configure(cache_dir=autotune.DEFAULT_CACHE_DIR,
                       tune_on_miss=False, enabled=True)
    autotune.reset_counters()


# Small shape classes so the searches stay test-fast.
SEEDED = [
    ("flash_attention", dict(G=2, hd=32, Tq=128, Tk=128, causal=True)),
    ("decode_attention", dict(G=2, hd=32, S=256)),
    ("ssd_scan", dict(P=32, N=32, T=128)),
]


# ---------------------------------------------------------------------------
# Cache round-trip: the second call comes from disk, no re-timing.
# ---------------------------------------------------------------------------
def test_cache_round_trip_no_retiming(tuner):
    kernel, dims = SEEDED[0]
    e1 = tuner.tune(kernel, "float32", iters=2, **dims)
    stats = tuner.cache_stats()
    assert stats["tunes"] == 1 and stats["timings"] > 0
    n_timed = stats["timings"]

    e2 = tuner.tune(kernel, "float32", iters=2, **dims)   # in-memory hit
    assert e2["config"] == e1["config"]
    assert tuner.cache_stats()["timings"] == n_timed

    # drop the in-memory mirror: the entry must come back from DISK
    tuner.configure(cache_dir=tuner.cache_dir())
    e3 = tuner.tune(kernel, "float32", iters=2, **dims)
    assert e3["config"] == e1["config"]
    assert tuner.cache_stats()["timings"] == n_timed      # still no re-timing
    # entries live in the schema-versioned profile store, and the tuning
    # bumped the tuned-tile generation exactly once
    with open(tuner.cache_path()) as f:
        disk = json.load(f)
    from repro.perf import profile_store
    assert disk["schema"] == profile_store.SCHEMA_VERSION
    assert len(disk["autotune"]) == 1
    assert disk["generations"]["autotune"] == 1
    assert tuner.generation() == 1


# ---------------------------------------------------------------------------
# Pruning never discards the measured-best config on the seeded shapes.
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("kernel,dims", SEEDED, ids=lambda x: str(x)[:24])
def test_pruning_keeps_measured_best(tuner, kernel, dims):
    """Pruning must not discard meaningfully better configs.  On these tiny
    CPU-interpret shapes candidate timings differ by less than OS jitter,
    so the 'measured best' config itself is nondeterministic — assert the
    noise-robust property instead: the best config SURVIVING pruning times
    within a small factor of the global measured best."""
    full = tuner.tune(kernel, "float32", force=True, prune=False,
                      iters=3, **dims)
    cls = tuner.shape_class(kernel, **dims)
    kept = tuner.prune_candidates(kernel, cls, "float32")
    timed = {k: v for k, v in full["candidates_timed"].items()}
    best_all = min(timed.values())
    best_kept = min(timed[json.dumps(c, sort_keys=True)] for c in kept)
    assert best_kept <= 1.5 * best_all, (kept, timed)
    assert len(kept) <= len(timed)      # pruning is allowed to prune


def test_pruning_always_keeps_default():
    for kernel, dims in SEEDED:
        cls = autotune.shape_class(kernel, **dims)
        kept = autotune.prune_candidates(kernel, cls, "float32", ratio=1.0)
        cands_fn, _ = autotune._KERNELS[kernel]
        if any(c == autotune.DEFAULTS[kernel] for c in cands_fn(cls)):
            assert autotune.DEFAULTS[kernel] in kept


# ---------------------------------------------------------------------------
# ops default lookup: graceful fallback with an empty cache.
# ---------------------------------------------------------------------------
def test_ops_fallback_empty_cache(tuner):
    from repro.kernels.flash_attention.ops import flash_attention
    from repro.kernels.decode_attention.ops import decode_attention
    from repro.kernels.ssd_scan.ops import ssd_scan

    ks = jax.random.split(jax.random.PRNGKey(0), 5)
    q = jax.random.normal(ks[0], (1, 128, 4, 32))
    k = jax.random.normal(ks[1], (1, 128, 2, 32))
    v = jax.random.normal(ks[2], (1, 128, 2, 32))
    out_default = flash_attention(q, k, v, causal=True)        # miss -> 128s
    out_explicit = flash_attention(q, k, v, causal=True,
                                   block_q=128, block_k=128)
    np.testing.assert_array_equal(np.asarray(out_default),
                                  np.asarray(out_explicit))

    q1 = jax.random.normal(ks[0], (2, 4, 32))
    kc = jax.random.normal(ks[1], (2, 256, 2, 32))
    vc = jax.random.normal(ks[2], (2, 256, 2, 32))
    pos = jnp.asarray(200, jnp.int32)
    np.testing.assert_array_equal(
        np.asarray(decode_attention(q1, kc, vc, pos)),
        np.asarray(decode_attention(q1, kc, vc, pos, block_k=256)))

    x = jax.random.normal(ks[0], (1, 128, 2, 32)) * 0.5
    dt = jax.nn.softplus(jax.random.normal(ks[1], (1, 128, 2)))
    A = -jnp.exp(jax.random.normal(ks[2], (2,)) * 0.5)
    Bm = jax.random.normal(ks[3], (1, 128, 16)) * 0.5
    Cm = jax.random.normal(ks[4], (1, 128, 16)) * 0.5
    y0, s0 = ssd_scan(x, dt, A, Bm, Cm)
    y1, s1 = ssd_scan(x, dt, A, Bm, Cm, chunk=128)
    np.testing.assert_array_equal(np.asarray(y0), np.asarray(y1))
    np.testing.assert_array_equal(np.asarray(s0), np.asarray(s1))

    stats = tuner.cache_stats()
    assert stats["misses"] > 0          # lookups happened and missed
    assert stats["tunes"] == 0          # ...without tuning (tune_on_miss off)


def test_tuned_config_is_used_by_ops(tuner):
    from repro.kernels.flash_attention.ops import flash_attention
    from repro.kernels.flash_attention import ops as fops
    kernel, dims = SEEDED[0]
    entry = tuner.tune(kernel, "float32", iters=1, **dims)
    calls = []
    orig = fops._flash_attention

    def spy(*a, **kw):
        calls.append((kw["block_q"], kw["block_k"]))
        return orig(*a, **kw)

    fops._flash_attention = spy
    try:
        ks = jax.random.split(jax.random.PRNGKey(0), 3)
        q = jax.random.normal(ks[0], (1, 128, 2, 32))
        k = jax.random.normal(ks[1], (1, 128, 1, 32))
        v = jax.random.normal(ks[2], (1, 128, 1, 32))
        flash_attention(q, k, v, causal=True)
    finally:
        fops._flash_attention = orig
    cfg = entry["config"]
    assert calls == [(cfg["block_q"], cfg["block_k"])]


# ---------------------------------------------------------------------------
# RealExecutor AOT: bucketing -> zero recompiles after warmup; compile time
# charged to the engine clock; memory-aware fits.
# ---------------------------------------------------------------------------
def _tiny_executor(**kw):
    from repro.serving.executor import RealExecutor
    w = jax.random.normal(jax.random.PRNGKey(0), (16, 16))

    def fn(params, batch):
        return jnp.tanh(batch["x"] @ params).sum()

    def make_batch(n):
        return {"x": jnp.ones((n, 16), jnp.float32)}

    return RealExecutor(fn, w, make_batch, **kw)


def test_zero_recompiles_after_warmup():
    ex = _tiny_executor()
    probe_points = [(1, 1), (2, 1), (3, 1), (4, 2), (16, 1), (5, 3), (32, 1)]
    for bs, mtl in probe_points:              # warmup: compiles happen here
        ex.run_step(bs, mtl)
    assert ex.cache_stats.misses > 0
    ex.cache_stats.reset_counters()
    for bs, mtl in probe_points * 3:          # steady state: all cache hits
        res = ex.run_step(bs, mtl)
        assert res["compile_time"] == 0.0
    assert ex.cache_stats.misses == 0
    assert ex.cache_stats.hits == len(probe_points) * 3


def test_bucketing_shares_executables():
    ex = _tiny_executor()
    ex.run_step(5, 1)                         # bucket 8
    ex.run_step(7, 1)                         # same bucket -> no compile
    ex.run_step(2, 4)                         # bs*mtl = 8 -> same bucket
    assert ex.cache_stats.misses == 1
    assert ex.cache_stats.hits == 2


def test_compile_time_charged_to_engine_clock():
    from repro.core.controller import StaticController
    from repro.serving.engine import ServingEngine
    ex = _tiny_executor()
    eng = ServingEngine(ex, slo_s=1.0)
    acc = eng.run(StaticController(bs=4, mtl=1), max_steps=5)
    assert acc.compile_stall_s > 0.0          # first step compiled
    assert acc.total_time >= acc.compile_stall_s
    assert acc.summary()["compile_stall_s"] == acc.compile_stall_s


def test_donate_batch_path_runs():
    ex = _tiny_executor(donate_batch=True)
    r1 = ex.run_step(4, 1)
    r2 = ex.run_step(4, 1)
    assert r1["items"] == r2["items"] == 4
    assert r2["compile_time"] == 0.0


def test_fits_memory_aware():
    ex = _tiny_executor()
    assert ex.fits(64, 64) and not ex.fits(4097, 1)     # legacy default
    exm = _tiny_executor(mem_bytes=1e6, act_bytes_per_item=1e4)
    assert exm.fits(1, 1)
    assert not exm.fits(50, 4)                # 200 items * 1e4 B > 1 MB
    # budget big enough for everything the legacy rule rejected
    exl = _tiny_executor(mem_bytes=1e12, act_bytes_per_item=1.0)
    assert exl.fits(4097, 2)


def test_fits_sizes_items_from_compiled_buckets():
    """Under a budget and with no per-item figure given, admission reads
    the compiled buckets' memory analysis: a line through the smallest and
    largest compiled bucket."""
    from repro.serving.executor import PARAM_OVERHEAD
    ex = _tiny_executor(mem_bytes=1e12)
    ex.warmup(1, 1)
    ex.warmup(64, 1)
    f1, f64 = ex._footprints[1], ex._footprints[64]
    assert f64 > f1 > 0
    need_128 = ex.param_bytes * PARAM_OVERHEAD + f1 + 127 * (f64 - f1) / 63
    ex.mem_bytes = need_128 * (1 + 1e-9)
    assert ex.fits(128, 1) and not ex.fits(129, 1)   # 129 pads to 192


# ---------------------------------------------------------------------------
# Vectorized pricing == scalar pricing; fast tail window == np.quantile.
# ---------------------------------------------------------------------------
def test_fit_profile_matches_model_thr_scan():
    """The vectorized `_fit_profile` must stay bit-equivalent to the
    sequential `_model_thr` scan it replaced — any drift between the
    inlined fit algebra and the pricing formulas skews every
    paper_profile-derived benchmark silently."""
    for dnn, dataset in list(dm.TABLE5)[:4]:
        t = np.array(dm.TABLE5[(dnn, dataset)])
        base_ms = 1e3 / t[0]
        flops = dm.NET_SPECS[dnn][1] * 1e9
        best, best_err = None, np.inf
        for host_frac in np.linspace(0.05, 0.95, 46):
            host = base_ms * host_frac
            gpu1 = base_ms - host
            for amort in np.linspace(0.0, 0.95, 39):
                m = np.array(dm._model_thr(host, gpu1, amort, flops,
                                           dm.TESLA_P40))
                err = np.sum(np.log(m / t) ** 2)
                if err < best_err:
                    best, best_err = (host, gpu1, amort), err
        got = dm._fit_profile(dnn, dataset)
        assert got == pytest.approx(best, rel=1e-12), (dnn, dataset)


def test_grid_pricing_matches_scalar():
    prof = dm.paper_profile("inception_v1", "imagenet")
    bs = np.array([1, 2, 7, 32, 128])
    mtls = np.arange(1, 11)
    grid = dm.mt_latency_grid(dm.TESLA_P40, prof, bs, mtls)
    for i, b in enumerate(bs):
        for j, m in enumerate(mtls):
            assert grid[i, j] == pytest.approx(
                dm.mt_latency(dm.TESLA_P40, prof, int(b), int(m)), rel=1e-12)
    bl = dm.batch_latency_grid(dm.TESLA_P40, prof, bs)
    for i, b in enumerate(bs):
        assert bl[i] == pytest.approx(
            dm.batch_latency(dm.TESLA_P40, prof, int(b)), rel=1e-12)


def test_price_surface_matches_mean_latency():
    from repro.serving.executor import SimExecutor
    prof = dm.paper_profile("resnet_v2_50", "imagenet")
    for mesh in (None, (4, 4)):
        ex = SimExecutor(prof, device=dm.TPU_V5E if mesh else dm.TESLA_P40,
                         mesh_shape=mesh)
        bs, mtls = np.array([1, 4, 16]), np.arange(1, 6)
        surf = ex.price_surface(bs, mtls)
        for i, b in enumerate(bs):
            for j, m in enumerate(mtls):
                assert surf[i, j] == pytest.approx(
                    ex.mean_latency(int(b), int(m)), rel=1e-12)


def test_tail_window_matches_np_quantile():
    from repro.serving.metrics import TailLatencyWindow
    rng = np.random.default_rng(0)
    win = TailLatencyWindow(window=50)
    ref: list = []
    for _ in range(30):
        chunk = rng.exponential(1.0, size=rng.integers(1, 40))
        win.add_many(chunk)
        ref.extend(chunk.tolist())
        expect = float(np.quantile(np.asarray(ref[-50:]), 0.95))
        assert win.p95 == pytest.approx(expect, rel=1e-12)
        assert win.mean == pytest.approx(float(np.mean(ref[-50:])), rel=1e-12)
    win.reset()
    assert win.p95 == 0.0 and len(win) == 0


# ---------------------------------------------------------------------------
# HybridScaler surface seeding: model-infeasible frontier pinned up front.
# ---------------------------------------------------------------------------
def test_seed_surface_pins_infeasible_frontier():
    from repro.core.scaler import HybridScaler
    sc = HybridScaler(0.1, max_bs=8, max_mtl=4, decision_interval=1)
    bs_vals = np.arange(1, 9)
    mtl_vals = np.arange(1, 5)
    # latency = bs * mtl * 20ms: infeasible once bs*mtl > 5
    lat = bs_vals[:, None] * mtl_vals[None, :] * 0.02
    pins = sc.seed_surface(bs_vals, mtl_vals, lat)
    assert pins > 0
    assert sc.is_pinned(6, 1) and sc.is_pinned(8, 4)    # deep infeasible
    assert sc.is_pinned(3, 2)                            # just past frontier
    assert not sc.is_pinned(5, 1) and not sc.is_pinned(2, 2)  # feasible
    assert sc._hi <= 5                                   # BS ceiling at mtl=1


def test_hybrid_controller_seeds_from_sim_surface():
    from repro.core.controller import DNNScalerController
    from repro.serving.executor import SimExecutor
    from repro.serving.workload import PAPER_JOBS
    job = PAPER_JOBS[0]
    ctrl = DNNScalerController(SimExecutor(job.profile(), seed=1),
                               job.slo_s, mode="hybrid")
    assert ctrl._surface is not None
    # the scaler must know at least one model-infeasible point up front
    assert len(ctrl.scaler._dom_counts) > 0
    # and a changed SLO re-derives the frontier instead of losing it
    ctrl.set_slo(job.slo_s * 0.5)
    assert len(ctrl.scaler._dom_counts) > 0


# ---------------------------------------------------------------------------
# models/layers.py defers its blockwise-attention tile sizes to the cache
# (ROADMAP autotune follow-up: explicit kwargs win, empty cache falls back).
# ---------------------------------------------------------------------------
def test_model_flash_attention_defers_blocks_to_cache(monkeypatch):
    import jax
    from repro.models import layers
    from repro.perf import autotune as at

    calls = []

    def fake_lookup(kernel, dtype, **dims):
        calls.append((kernel, dims))
        return {"block_q": 64, "block_k": 64}

    monkeypatch.setattr(at, "lookup", fake_lookup)
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (1, 96, 4, 16))
    k = jax.random.normal(ks[1], (1, 96, 2, 16))
    v = jax.random.normal(ks[2], (1, 96, 2, 16))
    out_cached = layers.flash_attention(q, k, v)
    assert calls and calls[0][0] == "flash_attention"
    assert calls[0][1]["Tq"] == 96 and calls[0][1]["G"] == 2
    out_explicit = layers.flash_attention(q, k, v, block_q=64, block_k=64)
    assert len(calls) == 1        # explicit kwargs never consult the cache
    np.testing.assert_allclose(np.asarray(out_cached),
                               np.asarray(out_explicit),
                               rtol=2e-5, atol=2e-5)
    # empty cache: the historical 256/512 defaults
    monkeypatch.setattr(at, "lookup", lambda *a, **kw: None)
    out_default = layers.flash_attention(q, k, v)
    out_legacy = layers.flash_attention(q, k, v, block_q=256, block_k=512)
    np.testing.assert_allclose(np.asarray(out_default),
                               np.asarray(out_legacy), rtol=1e-6)
