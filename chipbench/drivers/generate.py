"""Closed-loop generation: static batches from an offline queue.

Each batch is ``batch`` prompts of ``prompt_len`` token ids drawn from the
seed, prefilled into a cache of ``capacity`` positions by the program's
``launch/steps.make_prefill_step``, then decoded greedily for ``gen_len``
tokens by its ``make_decode_step``, on a one-chip mesh.  Batches run back
to back.  The driver owns only the loop: the greedy argmax, and a host
fetch of every step's token ids as a streaming server makes them.  It
dispatches step i+1 before it fetches step i's ids, so the device does not
wait on the fetch; each token is stamped when its ids reach the host.

End to end, over the window (host clock):
  tokens_per_s   output tokens that reached the host / window seconds
  tpot_p95_ms    95th percentile of every gap between two consecutive
                 tokens of one request
After the window a sample of finished requests, drawn from the seed, is
run through the plain reference (``compare``).
"""

from __future__ import annotations

import gc
import time

import numpy as np

from chipbench import harness
from chipbench.harness import Outcome, span


def greedy(logits):
    """The served token of each row and its logit."""
    import jax.numpy as jnp
    tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    return tok, jnp.take_along_axis(logits, tok[:, None], -1)[:, 0]


class Steps:
    """The program's compiled prefill and decode steps for one traffic
    shape, and the weights they serve."""

    def __init__(self, cfg, traffic: dict, seed: int):
        import jax
        from jax.sharding import NamedSharding, PartitionSpec
        from repro.configs.base import InputShape
        from repro.launch import steps
        from repro.launch.mesh import make_host_mesh
        self.cfg = cfg
        self.B, self.P = traffic["batch"], traffic["prompt_len"]
        self.G, self.C = traffic["gen_len"], traffic["capacity"]
        self.minfo = make_host_mesh(1, 1)
        self.sharding = NamedSharding(self.minfo.mesh, PartitionSpec())
        with self.minfo.mesh:
            self.prefill = steps.make_prefill_step(
                cfg, self.minfo,
                InputShape("prefill", self.P, self.B, "prefill"),
                capacity=self.C)[0]
            self.decode = steps.make_decode_step(
                cfg, self.minfo,
                InputShape("decode", self.C, self.B, "decode"))[0]
        self.greedy = jax.jit(greedy)
        self.weights = harness.make_weights(cfg, seed, self.sharding)

    def prompts(self, rng: np.random.Generator) -> np.ndarray:
        return rng.integers(0, self.cfg.vocab_size, (self.B, self.P),
                            dtype=np.int32)


class Batch:
    """One batch in flight.  ``advance(deadline)`` serves its tokens until
    the batch is done or a token reaches the host at or after the
    deadline; it can be called again to finish the batch."""

    def __init__(self, steps: Steps, prompts: np.ndarray):
        import jax
        s = self.s = steps
        self.prompts = prompts
        self.ids = np.zeros((s.B, s.G), np.int32)
        self.tops = np.zeros((s.B, s.G), np.float32)   # their logits
        self.stamps = np.zeros(s.G)
        self.n = 0                      # tokens fetched per request
        with span("bench.prompts"):
            dev = jax.device_put(prompts, s.sharding)
        with span("bench.prefill"):
            logits, self.cache = s.prefill(s.weights, {"tokens": dev})
            self.tok = s.greedy(logits)

    @property
    def done(self) -> bool:
        return self.n == self.s.G

    def advance(self, deadline=None) -> None:
        import jax
        s = self.s
        while not self.done:
            i = self.n
            nxt = None
            if i + 1 < s.G:
                with span("bench.decode"):
                    logits, self.cache = s.decode(
                        s.weights, self.cache, self.tok[0],
                        np.int32(s.P + i))
                    nxt = s.greedy(logits)
            with span("bench.fetch"):
                self.ids[:, i], self.tops[:, i] = jax.device_get(self.tok)
                self.stamps[i] = time.perf_counter()
            self.tok = nxt
            self.n = i + 1
            if self.done:
                self.cache = None           # free it before the next prefill
            if deadline is not None and self.stamps[i] >= deadline:
                return

    def settle(self) -> None:
        """Waits for the work already dispatched."""
        import jax
        jax.block_until_ready((self.cache, self.tok))

    def release(self) -> None:
        self.settle()
        self.cache = self.tok = None


def serve_window(steps: Steps, rng, seconds: float, tracer=None):
    """Serves batches back to back for ``seconds``; returns the batches and
    the window's (open, close) on the host clock.  The window closes at the
    first token that reaches the host at or after the deadline."""
    batches = []
    # the objects of set-up stay out of the collector's way in the window
    gc.collect()
    gc.freeze()
    gc.disable()
    if tracer is not None:
        tracer.start()
    with span("bench.window"):
        t_open = time.perf_counter()
        deadline = t_open + seconds
        while True:
            b = Batch(steps, steps.prompts(rng))
            batches.append(b)
            b.advance(deadline)
            if b.stamps[b.n - 1] >= deadline:
                break
    t_close = batches[-1].stamps[batches[-1].n - 1]
    batches[-1].settle()
    if tracer is not None:
        tracer.stop()
    gc.enable()
    gc.unfreeze()
    return batches, t_open, t_close


def window_metrics(batches, t_open: float, t_close: float) -> dict:
    B = batches[0].s.B
    tokens = B * sum(b.n for b in batches)
    gaps = np.concatenate([np.diff(b.stamps[:b.n]) for b in batches])
    return {"tokens_per_s": float(tokens / (t_close - t_open)),
            "tpot_p95_ms": float(np.percentile(np.repeat(gaps, B), 95)
                                 * 1e3)}


def sample_requests(batches, n: int, rng: np.random.Generator):
    """``n`` finished requests drawn from the seed: (prompt, served ids,
    their logits)."""
    done = [b for b in batches if b.done]
    picks = []
    for _ in range(n):
        b = done[rng.integers(len(done))]
        r = int(rng.integers(b.s.B))
        picks.append((b.prompts[r], b.ids[r], b.tops[r]))
    return picks


def compare(reference, weights, conf: dict, picks, *, control=False,
            block: int = 4) -> dict:
    """Runs the plain reference over each sampled request (its prompt and
    served tokens) and reads, over all served tokens:

      widest_gap    the largest amount by which a served token's reference
                    logit lies below the reference's best at its position
      logit_error   the largest distance between a served token's logit as
                    served and as the reference computes it

    With ``control`` the reference in the precision below the
    configuration's (fp8 linear layers) takes the program's place: its own
    first-ranked tokens and their logits are read against the float32
    reference."""
    out = {"widest_gap": 0.0, "logit_error": 0.0}
    for lo in range(0, len(picks), block):
        part = picks[lo:lo + block]
        P, G = len(part[0][0]), len(part[0][1])
        seqs = np.stack([np.concatenate([p, ids[:-1]]) for p, ids, _ in part])
        served = np.stack([ids for _, ids, _ in part])
        tops = np.stack([t for _, _, t in part])
        positions = np.arange(P - 1, P + G - 1)
        ref = reference.logits(weights, conf, seqs, positions)
        if control:
            low = reference.logits(weights, conf, seqs, positions,
                                   control=True)
            served = low.argmax(-1)
            tops = np.take_along_axis(low, served[..., None], -1)[..., 0]
        if not (np.isfinite(ref).all() and np.isfinite(tops).all()):
            return {k: float("nan") for k in out}
        got = np.take_along_axis(ref, served[..., None], -1)[..., 0]
        out["widest_gap"] = max(out["widest_gap"],
                                float((ref.max(-1) - got).max()))
        out["logit_error"] = max(out["logit_error"],
                                 float(np.abs(tops - got).max()))
    return out


def warm_up(steps: Steps, seed: int) -> None:
    """Compiles and runs every program the window uses: one prefill and
    three decode steps on prompts from their own stream."""
    b = Batch(steps, steps.prompts(harness.seed_rng(seed, harness.WARMUP)))
    b.advance(deadline=0.0)
    b.advance(deadline=0.0)
    b.advance(deadline=0.0)
    b.release()


def run(ctx, tracer) -> Outcome:
    import jax
    traffic = ctx.cell.traffic
    t_driver = time.perf_counter()
    steps = Steps(ctx.cfg, traffic, ctx.seed)
    t_steps = time.perf_counter()
    with steps.minfo.mesh:
        warm_up(steps, ctx.seed)
        t_warm = time.perf_counter()
        setup_s = t_warm - ctx.t_start

        rng = harness.seed_rng(ctx.seed, harness.PROMPTS)
        ctx.compiles.on = True
        batches, t_open, t_close = serve_window(steps, rng, ctx.seconds,
                                                tracer)
        ctx.compiles.on = False
        compiles = dict(ctx.compiles.counts)
        e2e = window_metrics(batches, t_open, t_close)
        slowest = sorted((round(float(g), 4), k, i)
                         for k, b in enumerate(batches)
                         for i, g in enumerate(np.diff(b.stamps[:b.n])))[-3:]
        facts = {"prefill_calls": len(batches),
                 "decode_live": [steps.P + i + 1 for b in batches
                                 for i in range(min(b.n, steps.G - 1))],
                 "shapes": {"batch": steps.B, "prompt_len": steps.P,
                            "gen_len": steps.G, "capacity": steps.C}}
        if not any(b.done for b in batches):
            batches[-1].advance(None)       # a request to compare, unmeasured
        batches[-1].release()
    stats = ctx.devices[0].memory_stats() or {}
    peak = stats.get("peak_bytes_in_use")

    attempted = steps.B * len(batches)
    in_range = all(np.all((b.ids >= 0) & (b.ids < steps.cfg.vocab_size))
                   for b in batches)
    picks = sample_requests(batches, traffic["check_requests"],
                            harness.seed_rng(ctx.seed, harness.SAMPLE))
    weights = steps.weights
    del steps, batches
    read = compare(ctx.reference, weights, ctx.cell.config, picks)
    checks = {k: (v, ctx.cell.check[k]["limit"]) for k, v in read.items()}
    if not in_range:
        checks["token_ids_out_of_range"] = (1, 0)
    jax.clear_caches()
    notes = [f"set-up (s): imports and devices {t_driver - ctx.t_start:.3f}, "
             f"steps and weights {t_steps - t_driver:.3f}, "
             f"warm-up compiles and runs {t_warm - t_steps:.3f}",
             f"compiles in the window: {sum(compiles.values())} {compiles}",
             f"window: {facts['prefill_calls']} batches, {e2e}",
             f"longest token gaps (s): {slowest}"]
    return Outcome(setup_s=setup_s, end_to_end=e2e, attempted=attempted,
                   failed=0 if in_range else attempted, checks=checks,
                   memory_peak_bytes=peak, facts=facts, notes=notes)


def readings(cell, seeds, control_seeds):
    """For each seed, the numbers compared (``compare``) for one batch
    served by the program at the cell's sizes, through the timed path, and
    for the seeds in ``control_seeds`` those of the control: the reference
    in fp8 put in the program's place.  One process, the steps
    compiled once.  Yields (seed, program's, control's or None)."""
    cfg = harness.program_config(cell.config)
    reference = harness.load_module(
        cell.root / "reference" / f"{cell.config['reference']}.py")
    steps = Steps(cfg, cell.traffic, seeds[0])
    n = cell.traffic["check_requests"]
    for seed in seeds:
        steps.weights = harness.make_weights(cfg, seed, steps.sharding)
        with steps.minfo.mesh:
            batches, _, _ = serve_window(
                steps, harness.seed_rng(seed, harness.PROMPTS), 0.0)
            batches[-1].advance(None)
            batches[-1].release()
        picks = sample_requests(batches, n,
                                harness.seed_rng(seed, harness.SAMPLE))
        program = compare(reference, steps.weights, cell.config, picks)
        control = None
        if seed in control_seeds:
            control = compare(reference, steps.weights, cell.config, picks,
                              control=True)
        yield seed, program, control
