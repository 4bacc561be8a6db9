"""Executors: where (simulated or real) inference time comes from.

SimExecutor — analytical device model (device_model.py) + latency noise;
  prices (BS, MTL) for a JobProfile on a Device or a TPU submesh plan.
  ``price_surface`` prices a whole (bs, mtl) grid in one vectorized call
  (HybridScaler seeding), and per-point means are memoized — the serving
  loop stopped recomputing the same closed-form latency every step.

RealExecutor — actually runs a jitted model on this host and measures wall
  clock.  Multi-tenancy is emulated by stacking MTL independent instance
  batches on a leading axis (vmap), which shares the host compute the way
  co-located GPU contexts share SMs.  Used for reduced models in tests,
  examples, and the real-execution benchmarks.

  The executor is an AOT fast path: operating points are lowered and
  compiled ahead of execution (``jit(...).lower().compile()``), batch
  shapes are bucketed so scaler probes of nearby (bs, mtl) points reuse
  one executable instead of recompiling, and every compile's wall time is
  reported in ``result["compile_time"]`` so the engine charges it to the
  service clock like an instance-launch stall.  Cache hit/miss counters
  live in ``metrics.ExecCacheStats``; steady-state probing must show zero
  misses after warmup.
"""

from __future__ import annotations

import time
from typing import Callable, Optional, Sequence

import jax
import numpy as np

from repro.perf import autotune
from repro.serving import device_model as dm
from repro.serving import tenancy
from repro.serving.metrics import ExecCacheStats


class SimExecutor:
    """Closed-loop simulated executor for one job."""

    def __init__(self, profile: dm.JobProfile, device: dm.Device = dm.TESLA_P40,
                 seed: int = 0, mesh_shape: Optional[tuple] = None,
                 partition=None, power_share: float = 1.0):
        self.profile = profile
        self.device = device
        self.sampler = dm.LatencySampler(seed=seed)
        self.mesh_shape = mesh_shape   # TPU mode: tenancy = submesh split
        self.partition = partition     # TenantSlice: spatial slice pricing
        self.power_share = power_share  # time-share fraction for power pricing
        self.clock = 0.0
        self._lat_cache: dict = {}     # (bs, mtl) -> mean latency (exact)
        self._power_cache: dict = {}   # (bs, mtl) -> (total_w, dynamic_w)
        self._tok_cache: dict = {}     # (slots, mtl, prefills) -> mean step

    def set_partition(self, ts) -> None:
        """Resize this executor's spatial slice (MPS set-percentage / MIG
        reconfigure): repricing only, no instance relaunch — the cheapness
        the cluster's resize-instead-of-migrate path exploits."""
        self.partition = ts
        self._lat_cache.clear()
        self._power_cache.clear()
        self._tok_cache.clear()

    # -- pricing ------------------------------------------------------------
    def mean_latency(self, bs: int, mtl: int) -> float:
        key = (bs, mtl)
        lat = self._lat_cache.get(key)
        if lat is None:
            lat = self._price(bs, mtl)
            self._lat_cache[key] = lat
        return lat

    def _price(self, bs: int, mtl: int) -> float:
        if self.partition is not None:
            ts = self.partition
            return dm.part_latency(self.device, self.profile, bs, mtl,
                                   inv_share=ts.inv_share,
                                   tenants=ts.tenants,
                                   isolation=ts.isolation)
        if self.mesh_shape is not None:
            # non-divisor MTLs over-partition (plan_at_least) instead of
            # returning inf — an inf step would poison the engine clock
            # and every downstream metric the moment a scaler probes one
            p = tenancy.plan_at_least(self.mesh_shape, mtl)
            if p is None:
                return float("inf")
            return dm.step_latency(self.device, self.profile, bs,
                                   share=p.share)["t_step"]
        return dm.mt_latency(self.device, self.profile, bs, mtl)

    def price_surface(self, bs_values, mtl_values) -> np.ndarray:
        """Mean-latency surface over the whole (bs, mtl) grid — one
        vectorized call per tenancy plan instead of a Python double loop.
        Shape (len(bs_values), len(mtl_values))."""
        bs_values = np.asarray(bs_values)
        if self.partition is not None:
            ts = self.partition
            return dm.part_latency_grid(self.device, self.profile,
                                        bs_values, mtl_values,
                                        inv_share=ts.inv_share,
                                        tenants=ts.tenants,
                                        isolation=ts.isolation)
        if self.mesh_shape is None:
            return dm.mt_latency_grid(self.device, self.profile,
                                      bs_values, mtl_values)
        cols = []
        for m in mtl_values:
            p = tenancy.plan_at_least(self.mesh_shape, int(m))
            if p is None:
                cols.append(np.full(len(bs_values), np.inf))
            else:
                cols.append(dm.step_latency_grid(
                    self.device, self.profile, bs_values,
                    share=p.share)["t_step"])
        return np.stack(cols, axis=1)

    def fits(self, bs: int, mtl: int) -> bool:
        dev = self.device
        if self.partition is not None:
            # the tenant sees only its memory slice, not the whole HBM
            import dataclasses
            dev = dataclasses.replace(
                dev, hbm_bytes=dev.hbm_bytes * self.partition.mem_fraction)
        return dm.fits_memory(dev, self.profile, bs, mtl)

    def power_terms(self, bs: int, mtl: int) -> tuple:
        """(total_w, dynamic_w) this executor's slice draws at (bs, mtl).

        Per-slice pricing (device_model.slice_power): a partitioned tenant
        draws its share of the idle floor plus share-scaled dynamic power on
        the partition latency law; a time-share tenant draws power_share of
        both.  dynamic_w = total_w - share * idle_w lets the cluster charge
        the idle floor ONCE per powered device instead of once per tenant.
        """
        key = (bs, mtl)
        terms = self._power_cache.get(key)
        if terms is None:
            ts = self.partition
            if ts is not None:
                share = ts.share
                total = dm.slice_power(self.device, self.profile, bs, mtl,
                                       share=share, inv_share=ts.inv_share,
                                       tenants=ts.tenants,
                                       isolation=ts.isolation)
            else:
                share = self.power_share
                total = dm.slice_power(self.device, self.profile, bs, mtl,
                                       share=share)
            terms = (total, total - share * self.device.idle_w)
            self._power_cache[key] = terms
        return terms

    # -- execution ----------------------------------------------------------
    def run_step(self, bs: int, mtl: int) -> dict:
        """Simulate one synchronized step of all MTL instances."""
        mean = self.mean_latency(bs, mtl)
        lat = float(self.sampler.sample(mean, n=1)[0])
        self.clock += lat
        items = bs * mtl
        power, dyn = self.power_terms(bs, mtl)
        return {
            "step_time": lat,
            "items": items,
            "request_latencies": self.sampler.sample(lat, n=min(items, 64)),
            "power_w": power,
            "dynamic_power_w": dyn,
            "throughput": items / lat,
        }

    # -- token engine --------------------------------------------------------
    def token_step_latency(self, live_slots: int, mtl: int = 1,
                           prefill_tenants: int = 0,
                           extra_slots: float = 0.0) -> float:
        """Mean decode-step latency with `live_slots` slots occupied.

        A co-scheduled prefill ("cotenant" prefill mode) is priced as an
        extra spatial tenant on TOP of any configured partition slice —
        the same cross-tenant interference terms the partition model
        calibrates against the paper's MTL curves.

        `extra_slots` ("chunked" prefill mode) piggybacks a prefill chunk
        into the step as fractional decode-token equivalents: the step is
        priced as a batch of `live_slots + extra_slots` on the same grid
        (the grids are float-polymorphic, so 16 + 0.0 prices bit-identical
        to 16 — the default is an exact no-op)."""
        key = (live_slots, mtl, prefill_tenants, extra_slots)
        lat = self._tok_cache.get(key)
        if lat is None:
            ts = self.partition
            lat = float(dm.token_latency_grid(
                self.device, self.profile, [live_slots + extra_slots],
                [mtl],
                inv_share=ts.inv_share if ts is not None else 1.0,
                tenants=(ts.tenants if ts is not None else 1)
                + prefill_tenants,
                isolation=ts.isolation if ts is not None else 0.0)[0, 0])
            self._tok_cache[key] = lat
        return lat

    def run_token_step(self, live_slots: int, mtl: int = 1, *,
                       prefill_tenants: int = 0,
                       extra_slots: float = 0.0) -> dict:
        """Simulate one decode step: every live slot emits one token (a
        nonzero `extra_slots` also advances piggybacked prefill chunks —
        priced into the step, not counted as output tokens)."""
        mean = self.token_step_latency(live_slots, mtl, prefill_tenants,
                                       extra_slots)
        lat = float(self.sampler.sample(mean, n=1)[0])
        self.clock += lat
        tokens = live_slots * mtl
        power, dyn = self.power_terms(live_slots, mtl)
        return {
            "step_time": lat,
            "tokens": tokens,
            "items": tokens,
            "power_w": power,
            "dynamic_power_w": dyn,
            "throughput": tokens / lat,
        }


# Default batch buckets: dense at small sizes (where the scalers live), a
# x1.5 / x2 ladder above — every (bs * mtl) rounds UP to one of these, so a
# probing scaler touches O(log) distinct executables instead of one per point.
DEFAULT_BUCKETS = (1, 2, 4, 8, 12, 16, 24, 32, 48, 64, 96, 128, 192, 256,
                   384, 512, 768, 1024, 1536, 2048, 3072, 4096)

# fits() activation-estimate multiplier: per-item batch bytes amplified
# through the network (activations, workspace, output buffers).
ACT_MULT = 12.0
PARAM_OVERHEAD = 1.3   # optimizer-free serving copy + allocator slack


class RealExecutor:
    """Wall-clock executor over a jitted callable.

    `fn(params, batch)` consumes a batch pytree whose leaves have leading
    dim = instances*bs (instances folded in by the caller via make_batch).

    AOT + bucketing: `run_step(bs, mtl)` rounds bs*mtl up to a bucket,
    compiles that bucket's executable once ahead of time, and reuses it for
    every operating point that lands in the bucket (padding rows are masked
    out of the throughput accounting — only real items count).  With
    `donate_batch=True` input buffers are donated to the executable and a
    fresh device batch is staged per step (the real serving path, where
    every request brings new data); by default the cached device batch is
    reused and nothing is donated.
    """

    def __init__(self, fn: Callable, params, make_batch: Callable,
                 idle_w: float = 50.0, peak_w: float = 250.0, *,
                 mem_bytes: Optional[float] = None,
                 act_bytes_per_item: Optional[float] = None,
                 buckets: Sequence[int] = DEFAULT_BUCKETS,
                 donate_batch: bool = False,
                 aot: bool = True,
                 tile_generation: Optional[Callable[[], int]] = None,
                 kv_bytes_per_item: float = 0.0):
        self.fn = fn
        self.params = params
        self.make_batch = make_batch
        self.idle_w = idle_w
        self.peak_w = peak_w
        self.mem_bytes = mem_bytes
        self.act_bytes_per_item = act_bytes_per_item
        self.kv_bytes_per_item = kv_bytes_per_item
        self.buckets = tuple(sorted(buckets))
        self.donate_batch = donate_batch
        self.aot = aot
        if donate_batch:
            # wrap so donation applies regardless of whether fn is jitted
            self._jfn = jax.jit(lambda p, b: fn(p, b), donate_argnums=(1,))
        elif hasattr(fn, "lower"):
            self._jfn = fn               # already jitted: AOT-lower directly
        else:
            self._jfn = jax.jit(fn)
        # bucket items -> (executable, batch, tuned-tile generation); a
        # generation bump (new tuning persisted) makes resident entries
        # stale — they are evicted and recompiled, never served
        self._exec: dict = {}
        self._tile_generation = tile_generation or autotune.generation
        self._param_bytes: Optional[float] = None
        # bucket items -> bytes beyond the parameters its compiled program
        # needs (memory analysis; kept only under a `mem_bytes` budget)
        self._footprints: dict = {}
        self._raw_act_per_item: Optional[float] = None
        self.cache_stats = ExecCacheStats()
        self._pending_compile = 0.0      # compile seconds not yet charged
        self.partition = None            # TenantSlice: capped-batch proxy
        self.clock = 0.0

    def set_partition(self, ts) -> None:
        """Spatial-partition proxy for a single-process host: this process
        cannot literally run inside an MPS percentage or MIG slice, so a
        slice is emulated by inflating the measured wall clock with the
        slice's calibrated slowdown (`TenantSlice.slowdown`) — the
        capped-compute proxy.  The raw wall measurement is still reported
        (``wall_step_time``) so callers can record the measured
        interference ratio into the profile store."""
        self.partition = ts

    # -- capacity -----------------------------------------------------------
    def bucket(self, n: int) -> int:
        """Smallest bucket >= n (or n itself beyond the largest bucket)."""
        for b in self.buckets:
            if b >= n:
                return b
        return n

    @property
    def device_class(self) -> str:
        """Profile-store class of the device the parameters live on:
        ``host-cpu`` on the CPU backend, else the device kind as a slug
        (``tpu-v5-lite`` for a TPU v5e)."""
        leaf = jax.tree_util.tree_leaves(self.params)[0]
        dev = next(iter(leaf.devices()))
        if dev.platform == "cpu":
            return "host-cpu"
        return dev.device_kind.lower().replace(" ", "-")

    @property
    def param_bytes(self) -> float:
        if self._param_bytes is None:    # fits() runs per scaler candidate
            leaves = jax.tree_util.tree_leaves(self.params)
            self._param_bytes = float(sum(x.size * x.dtype.itemsize
                                          for x in leaves))
        return self._param_bytes

    def _act_bytes(self, n_bucket: int) -> float:
        """Bytes beyond the parameters that a bucket of `n_bucket` items
        needs.  An explicit `act_bytes_per_item` wins.  Otherwise, once
        buckets are compiled, the compiler's memory analysis of them: a
        line through the smallest and largest compiled bucket, or, with one
        compiled, its bytes per item (an upper bound for larger buckets,
        since it charges the fixed workspace to every item).  Before any
        compile, the batch's own bytes times ACT_MULT."""
        if self.act_bytes_per_item is not None:
            return n_bucket * self.act_bytes_per_item
        if self._footprints:
            lo, hi = min(self._footprints), max(self._footprints)
            f_lo, f_hi = self._footprints[lo], self._footprints[hi]
            if lo == hi:
                return n_bucket * f_lo / lo
            slope = max(0.0, (f_hi - f_lo) / (hi - lo))
            return f_lo + (n_bucket - lo) * slope
        if self._raw_act_per_item is None:    # fits() runs per candidate
            leaves = jax.tree_util.tree_leaves(self.make_batch(1))
            raw = sum(np.asarray(x).size * np.asarray(x).dtype.itemsize
                      for x in leaves)
            self._raw_act_per_item = raw * ACT_MULT
        return n_bucket * self._raw_act_per_item

    def fits(self, bs: int, mtl: int) -> bool:
        """Memory-aware admission when a `mem_bytes` budget is configured
        (param bytes + the activation estimate of `_act_bytes` at the
        BUCKETED batch, since that is the shape actually compiled); the
        historical hard cap `bs * mtl <= 4096` when no budget is given.

        Decode-mode profiles additionally charge the paged KV cache:
        `kv_bytes_per_item` per LIVE slot (not bucketed — pages are
        allocated per admitted request, the compiled bucket shape only
        pads activations).  Without it a decode job could over-admit on
        memory the bucket estimate never sees."""
        n = bs * mtl
        if self.mem_bytes is None:
            return n <= 4096
        need = (self.param_bytes * PARAM_OVERHEAD
                + self._act_bytes(self.bucket(n))
                + n * self.kv_bytes_per_item)
        return need <= self.mem_bytes

    # -- executable cache ---------------------------------------------------
    def _get(self, n_bucket: int):
        entry = self._exec.get(n_bucket)
        if entry is not None:
            if entry[2] == int(self._tile_generation()):
                self.cache_stats.hits += 1
                return entry
            # compiled under superseded tile sizes: evict, never serve
            del self._exec[n_bucket]
            self.cache_stats.stale_evictions += 1
        self.cache_stats.misses += 1
        t0 = time.perf_counter()
        batch = self.make_batch(n_bucket)
        if self.donate_batch:
            # host template FIRST: a donating warmup call below would delete
            # the device buffers before they could be read back
            batch = jax.tree_util.tree_map(np.asarray, batch)
        if self.aot:
            executable = self._jfn.lower(self.params, batch).compile()
        else:
            executable = self._jfn
            jax.block_until_ready(
                executable(self.params, self._staged_batch(batch)))
        dt = time.perf_counter() - t0
        if self.aot and self.mem_bytes is not None:
            ma = executable.memory_analysis()
            if ma is not None:
                self._footprints[n_bucket] = (
                    ma.temp_size_in_bytes + ma.output_size_in_bytes
                    + ma.argument_size_in_bytes - self.param_bytes)
        self.cache_stats.compile_time_s += dt
        self._pending_compile += dt
        # tagged with the generation read AFTER compiling — those are the
        # tiles the compile's kernel lookups actually consulted (a
        # tune_on_miss search triggered DURING the compile bumps the
        # generation, and this executable already uses its result)
        entry = (executable, batch, int(self._tile_generation()))
        self._exec[n_bucket] = entry
        return entry

    # -- migration instrumentation -------------------------------------------
    def shutdown(self) -> float:
        """Tear down the resident executables (the 'kill' half of a
        migration's kill+relaunch round) and return the seconds it took.
        The measurement feeds the profile store's migration calibration."""
        t0 = time.perf_counter()
        self._exec.clear()
        self._pending_compile = 0.0
        return time.perf_counter() - t0

    def warmup(self, bs: int, mtl: int) -> float:
        """Compile the bucket executable for (bs, mtl) ahead of serving and
        return the compile seconds (0.0 on a cache hit).  The pending
        compile charge is consumed here so the caller charging this as a
        migration/relaunch stall does not double-charge the next step."""
        self._get(self.bucket(bs * mtl))
        dt = self._pending_compile
        self._pending_compile = 0.0
        return dt

    def _staged_batch(self, batch):
        return jax.device_put(batch) if self.donate_batch else batch

    # -- pricing ------------------------------------------------------------
    def mean_latency(self, bs: int, mtl: int, iters: int = 3) -> float:
        executable, batch, _ = self._get(self.bucket(bs * mtl))
        staged = [self._staged_batch(batch) for _ in range(iters)]
        t0 = time.perf_counter()
        for b in staged:
            out = executable(self.params, b)
        jax.block_until_ready(out)
        wall = (time.perf_counter() - t0) / iters
        if self.partition is not None:
            return wall * self.partition.proxy_slowdown()
        return wall

    # -- execution ----------------------------------------------------------
    def run_step(self, bs: int, mtl: int) -> dict:
        nb = self.bucket(bs * mtl)
        executable, batch, gen = self._get(nb)
        comp = self._pending_compile
        self._pending_compile = 0.0
        staged = self._staged_batch(batch)
        t0 = time.perf_counter()
        out = executable(self.params, staged)
        jax.block_until_ready(out)
        wall = time.perf_counter() - t0
        slowdown = (self.partition.proxy_slowdown()
                    if self.partition is not None else 1.0)
        lat = wall * slowdown
        if gen != int(self._tile_generation()):
            # a tuning landed between the cache lookup and this serve:
            # the step above ran on superseded tiles.  Count it (the
            # invariant steady-state serving asserts is ZERO) and evict
            # so the next step recompiles under the new generation.
            self.cache_stats.stale_hits += 1
            self._exec.pop(nb, None)
        self.clock += lat + comp
        items = bs * mtl                 # bucket padding rows do not count
        return {
            "step_time": lat,
            "items": items,
            "compile_time": comp,
            "bucket_items": nb,
            "wall_step_time": wall,
            "partition_slowdown": slowdown,
            "request_latencies": np.full(min(items, 64), lat),
            "power_w": self.peak_w * 0.6,
            "dynamic_power_w": max(self.peak_w * 0.6 - self.idle_w, 0.0),
            "throughput": items / lat,
        }

    # -- token engine --------------------------------------------------------
    def run_token_step(self, live_slots: int, mtl: int = 1, *,
                       prefill_tenants: int = 0,
                       extra_slots: float = 0.0) -> dict:
        """One measured decode step with `live_slots` slots occupied: the
        jitted callable IS the decode-step function, and the bucketed AOT
        ladder doubles as the slot ladder (a step at 37 live slots runs
        the 48-slot executable; padding slots don't count as tokens).
        A co-resident prefill on this single-process host shares the wall
        clock it is measured on, so no extra pricing term is added.
        Chunked-prefill `extra_slots` widen the measured batch (rounded up
        to whole rows) without counting as output tokens."""
        width = live_slots + int(np.ceil(extra_slots))
        r = self.run_step(width, mtl)
        r["tokens"] = live_slots * mtl
        r["items"] = r["tokens"]
        return r
