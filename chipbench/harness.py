"""Harness of the chip benchmark: finds a cell's files by name, makes its
weights and prompts from the seed, runs its driver, reduces the trace and
prints the result line.

Every file belongs to one configuration, traffic mix, cell or metric, and
the harness finds it by the name that ``BENCHMARK.json`` gives:

  configs/<config>.json     sizes as run, source, plain reference
  traffic/<traffic>.json    driver and its parameters
  checks/<workload>.json    the limit of each number compared for `correct`
  drivers/<driver>.py       one traffic loop:  run(ctx) -> Outcome
  metrics/<metric>.py       one per-layer metric:  read(view) -> float | None
  reference/<name>.py       plain float32 forward of one architecture
  flops/<name>.py           operations and bytes of one architecture

Adding a cell, a configuration or a metric therefore adds files and an
entry in ``BENCHMARK.json``; no file here changes.
"""

from __future__ import annotations

import collections
import dataclasses
import importlib.util
import json
import os
import shutil
import sys
import tempfile
import time
from pathlib import Path
from typing import Any, Callable, Optional

import numpy as np

HERE = Path(__file__).resolve().parent
REPO = HERE.parent
BENCHMARK = REPO / "BENCHMARK.json"
# JAX's persistent compilation cache: a fixed path inside the checkout, so
# that every run from one checkout after the first finds every program
COMPILE_CACHE = REPO / ".jax_cache"

# independent random streams drawn from one --seed
WEIGHTS, PROMPTS, WARMUP, SAMPLE = range(4)


class Refused(SystemExit):
    """A run that cannot measure: exits non-zero and prints no result."""

    def __init__(self, msg: str):
        super().__init__(f"chipbench: {msg}")


# ---------------------------------------------------------------------------
# Files
# ---------------------------------------------------------------------------
def read_json(path: Path) -> dict:
    if not path.is_file():
        raise Refused(f"missing {path}")
    return json.loads(path.read_text())


def load_module(path: Path):
    if not path.is_file():
        raise Refused(f"missing {path}")
    spec = importlib.util.spec_from_file_location(
        f"chipbench_{path.parent.name}_{path.stem.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict            # configs/<config>.json
    traffic: dict           # traffic/<traffic>.json
    check: dict             # checks/<workload>.json
    end_to_end: list        # BENCHMARK.json metrics this cell reports
    per_layer: list
    root: Path


def load_cell(name: str, benchmark: Path = BENCHMARK,
              root: Path = HERE) -> Cell:
    bench = read_json(benchmark)
    work = [w for w in bench["workloads"] if w["name"] == name]
    if not work:
        raise Refused(f"no workload {name!r} in {benchmark}")
    w = work[0]

    def reports(metric):
        return name in metric.get("workloads", [name])

    return Cell(
        name=name, chips=int(w["chips"]),
        config=read_json(root / "configs" / f"{w['config']}.json"),
        traffic=read_json(root / "traffic" / f"{w['traffic']}.json"),
        check=read_json(root / "checks" / f"{name}.json"),
        end_to_end=[m for m in bench["end_to_end"] if reports(m)],
        per_layer=[m for m in bench["per_layer"] if reports(m)],
        root=root)


def field(conf: dict, dotted: str):
    """``conf["a"]["b"]`` for ``"a.b"``."""
    for k in dotted.split("."):
        conf = conf[k]
    return conf


def program_config(conf: dict):
    """The program's ModelConfig, with every field that the configuration
    file maps (``program_fields``: field -> key in the file) set from it."""
    from repro.configs.base import get_config
    fields = {f: field(conf, key) for f, key in conf["program_fields"].items()}
    return get_config(conf["program"]).replace(**fields)


def peaks_for(kind: str, root: Path = HERE) -> dict:
    table = read_json(root / "peaks.json")
    if kind not in table:
        raise Refused(f"no peaks for device kind {kind!r} in peaks.json")
    return table[kind]


# ---------------------------------------------------------------------------
# Seeds, devices, weights
# ---------------------------------------------------------------------------
def seed_words(seed: int, stream: int) -> np.ndarray:
    """Two uint32 words from a seed of any size and a stream number."""
    ss = np.random.SeedSequence([seed % (1 << 64), stream])
    return ss.generate_state(2, np.uint32)


def seed_rng(seed: int, stream: int) -> np.random.Generator:
    return np.random.default_rng(
        np.random.SeedSequence([seed % (1 << 64), stream]))


def prepare_process() -> None:
    """Before JAX is imported: the TPU runtime writes no log files (it
    would write them under /tmp), and every compiled program, however quick
    to compile, goes to the checkout's compilation cache."""
    os.environ["TPU_LOG_DIR"] = "disabled"
    import jax
    jax.config.update("jax_compilation_cache_dir", str(COMPILE_CACHE))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def require_devices(chips: int, platform: str = "tpu"):
    import jax
    devices = jax.devices()
    if devices[0].platform != platform:
        raise Refused(f"JAX found no {platform.upper()} (platform "
                      f"{devices[0].platform!r})")
    if len(devices) < chips:
        raise Refused(f"the cell needs {chips} chips, JAX found "
                      f"{len(devices)}")
    return devices[:chips]


_NORMS = ("norm", "final_norm", "gate_norm", "post_norm", "cross_norm")


def _leaf(name: str, key, shape, dtype):
    import jax
    import jax.numpy as jnp
    r = jax.random
    if name in _NORMS:
        v = 1.0 + 0.1 * r.normal(key, shape)
    elif name == "A_log":               # Mamba2: A = -exp(A_log) in [-16, -1]
        v = jnp.log(r.uniform(key, shape, minval=1.0, maxval=16.0))
    elif name == "D":
        v = r.uniform(key, shape, minval=0.5, maxval=1.5)
    elif name == "dt_bias":             # softplus(dt_bias) log-uniform in
        dt = jnp.exp(r.uniform(key, shape, minval=np.log(1e-3),  # [1e-3, 0.1]
                               maxval=np.log(1e-1)))
        v = dt + jnp.log(-jnp.expm1(-dt))
    elif name == "conv_w":              # depthwise, fan-in = the conv width
        v = r.uniform(key, shape, minval=-0.5, maxval=0.5)
    else:                               # projections, embeddings, biases
        v = 0.02 * r.normal(key, shape)
    return v.astype(dtype)


def make_weights(cfg, seed: int, sharding=None):
    """Random weights in the program's parameter layout, made on the device
    in one jitted call from the seed, each leaf in the type it is served
    in.  The values follow the rules of ``_leaf`` by leaf name, so the
    plain reference reads the same weights without the program's init."""
    import jax
    from repro.models import api
    specs = api.param_specs(cfg)
    leaves, treedef = jax.tree_util.tree_flatten_with_path(specs)

    def build(key):
        out = []
        for i, (path, spec) in enumerate(leaves):
            name = [p.key for p in path if hasattr(p, "key")][-1]
            out.append(_leaf(name, jax.random.fold_in(key, i), spec.shape,
                             spec.dtype))
        return jax.tree_util.tree_unflatten(treedef, out)

    key = jax.numpy.asarray(seed_words(seed, WEIGHTS))
    return jax.jit(build, out_shardings=sharding)(key)


# ---------------------------------------------------------------------------
# Compiles inside the window
# ---------------------------------------------------------------------------
class CompileCounter:
    """Counts traces and backend compiles while ``on`` is set."""
    EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
              "/jax/core/compile/backend_compile_duration")

    def __init__(self):
        import jax
        self.on = False
        self.counts = collections.Counter()
        jax.monitoring.register_event_duration_secs_listener(self._listen)

    def _listen(self, event, duration, **kw):
        if self.on and event in self.EVENTS:
            self.counts[event.rsplit("/", 1)[-1]] += 1


def span(name: str):
    """A host span in the profiler's trace (free when no trace runs)."""
    from jax.profiler import TraceAnnotation
    return TraceAnnotation(name)


# ---------------------------------------------------------------------------
# What a driver gets and gives back
# ---------------------------------------------------------------------------
@dataclasses.dataclass
class Context:
    cell: Cell
    seed: int
    seconds: float
    trace: bool
    t_start: float                      # process start, host clock
    devices: list
    compiles: Any = None                # CompileCounter
    reference: Any = None               # reference/<name>.py module

    @property
    def cfg(self):
        return program_config(self.cell.config)


@dataclasses.dataclass
class Outcome:
    setup_s: float
    end_to_end: dict                    # name -> value (host clock)
    attempted: int
    failed: int
    checks: dict                        # name -> (value, limit)
    memory_peak_bytes: Optional[int]
    facts: dict = dataclasses.field(default_factory=dict)  # for metric readers
    notes: list = dataclasses.field(default_factory=list)


class Tracer:
    """Starts the profiler at the window's opening and stops it at its
    close, when the run asks for a trace."""

    def __init__(self, enabled: bool):
        self.enabled = enabled
        self.dir = (tempfile.mkdtemp(prefix="chipbench_trace_") if enabled
                    else None)
        self.path = None

    def start(self):
        if self.enabled:
            import jax
            jax.profiler.start_trace(self.dir)

    def stop(self):
        if self.enabled:
            import jax
            jax.profiler.stop_trace()
            found = sorted(Path(self.dir).rglob("*.xplane.pb"))
            self.path = str(found[-1]) if found else None

    def cleanup(self):
        if self.dir:
            shutil.rmtree(self.dir, ignore_errors=True)


# ---------------------------------------------------------------------------
# The run
# ---------------------------------------------------------------------------
def run_cell(cell: Cell, seed: int, seconds: float, trace: bool,
             t_start: float, *, platform: str = "tpu",
             patch: Optional[Callable] = None) -> dict:
    """Runs one cell once; returns the result line as a dict.  ``platform``
    and ``patch`` (a hook that may replace parts of the driver) exist for
    the benchmark's own tests on the CPU."""
    devices = require_devices(cell.chips, platform)
    traffic = cell.traffic
    driver = load_module(cell.root / "drivers" / f"{traffic['driver']}.py")
    if patch is not None:
        patch(driver)
    ctx = Context(cell=cell, seed=seed, seconds=seconds, trace=trace,
                  t_start=t_start, devices=devices,
                  compiles=CompileCounter(),
                  reference=load_module(cell.root / "reference"
                                        / f"{cell.config['reference']}.py"))
    tracer = Tracer(trace)
    try:
        out = driver.run(ctx, tracer)
        result = _result(cell, ctx, out, tracer)
    finally:
        tracer.cleanup()
    return result


def _result(cell, ctx, out: Outcome, tracer: Tracer) -> dict:
    dev = ctx.devices[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(ctx.devices),
              "memory_peak_bytes": out.memory_peak_bytes}
    metrics = {}
    breakdown = None
    if not ctx.trace:
        values = dict(out.end_to_end, setup_s=out.setup_s)
        for m in cell.end_to_end:
            if m["name"] in values:
                metrics[m["name"]] = {"value": values[m["name"]],
                                      "unit": m["unit"]}
    else:
        from chipbench import trace as tr
        view = tr.View.load(tracer.path, cell=cell, facts=out.facts,
                            peaks=peaks_for(dev.device_kind, cell.root),
                            work=load_module(cell.root / "flops" /
                                             f"{cell.config['flops']}.py"),
                            chips=len(ctx.devices))
        device["busy_s"] = view.busy_s()
        device["window_s"] = view.window_s()
        for m in cell.per_layer:
            reader = load_module(cell.root / "metrics" / f"{m['name']}.py")
            value = reader.read(view)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        breakdown = view.breakdown()
    correct = all(v <= lim for v, lim in out.checks.values())
    line = {"correct": bool(correct), "attempted": out.attempted,
            "failed": out.failed, "metrics": metrics, "device": device}
    if breakdown is not None:
        line["breakdown"] = breakdown
    line["notes"] = list(out.notes)
    line["checks"] = {k: {"value": v, "limit": lim}
                      for k, (v, lim) in out.checks.items()}
    return line


def main(argv=None, t_start: Optional[float] = None) -> int:
    import argparse
    t_start = time.perf_counter() if t_start is None else t_start
    ap = argparse.ArgumentParser(description="Run one benchmark cell once.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if not (REPO / "src" / "repro").is_dir():
        raise Refused(f"no program source at {REPO / 'src'}")
    sys.path.insert(0, str(REPO / "src"))
    cell = load_cell(args.workload)
    prepare_process()
    line = run_cell(cell, args.seed, args.seconds, bool(args.trace), t_start)
    for note in line.pop("notes"):
        print(note, file=sys.stderr, flush=True)
    for k, c in line["checks"].items():
        print(f"check {k}: {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr, flush=True)
    print(json.dumps(line), flush=True)
    return 0
