"""Closed-loop generation on a tensor-parallel mesh: the loop of
``generate.py`` (static batches back to back, greedy, each step's ids
fetched as a streaming server makes them, the same end-to-end metrics and
the same comparison with the plain reference after the window), with the
program's ``launch/steps.make_prefill_step`` and ``make_decode_step``
built on a (data 1, model tp) mesh over the cell's chips, tp being the
configuration's ``tensor_parallel``.  The weights are made on the chips
from the seed, each leaf split as the steps' shardings place it (a model
that needs the chips does not fit on one of them whole).
"""

from __future__ import annotations

import functools
from pathlib import Path

from chipbench import harness

# this driver's own instance of the one-chip loop: its ``Steps`` is
# replaced below by the tensor-parallel steps, which its ``run`` then builds
loop = harness.load_module(Path(__file__).resolve().parent / "generate.py")


class Steps(loop.Steps):
    """The program's compiled prefill and decode steps for one traffic
    shape on a (data 1, model ``tp``) mesh, and the weights they serve."""

    def __init__(self, cfg, traffic: dict, seed: int, *, tp: int):
        import jax
        from jax.sharding import NamedSharding, PartitionSpec
        from repro.configs.base import InputShape
        from repro.distributed import sharding as shd
        from repro.launch import steps
        from repro.launch.mesh import make_host_mesh
        from repro.models import api
        self.cfg = cfg
        self.B, self.P = traffic["batch"], traffic["prompt_len"]
        self.G, self.C = traffic["gen_len"], traffic["capacity"]
        self.minfo = make_host_mesh(1, tp)
        self.sharding = NamedSharding(self.minfo.mesh, PartitionSpec())
        with self.minfo.mesh:
            self.prefill = steps.make_prefill_step(
                cfg, self.minfo,
                InputShape("prefill", self.P, self.B, "prefill"),
                capacity=self.C)[0]
            self.decode = steps.make_decode_step(
                cfg, self.minfo,
                InputShape("decode", self.C, self.B, "decode"))[0]
        self.greedy = jax.jit(loop.greedy)
        # the shardings the steps take their weights in
        self.weight_sharding = shd.param_shardings(api.param_specs(cfg), cfg,
                                                   self.minfo, "infer")
        self.weights = harness.make_weights(cfg, seed, self.weight_sharding)

    def reseed(self, seed: int) -> None:
        """Weights from another seed; the last seed's are freed first, as
        two sets do not fit."""
        self.weights = None
        self.weights = harness.make_weights(self.cfg, seed,
                                            self.weight_sharding)


def run(ctx, tracer):
    loop.Steps = functools.partial(Steps,
                                   tp=ctx.cell.config["tensor_parallel"])
    return loop.run(ctx, tracer)


def readings(cell, seeds, control_seeds):
    """As ``generate.readings``: for each seed, the numbers compared for one
    batch served at the cell's sizes through the timed path, and for the
    seeds in ``control_seeds`` those of the control.  One process, the
    steps compiled once.  Yields (seed, program's, control's or None)."""
    cfg = harness.program_config(cell.config)
    reference = harness.load_module(
        cell.root / "reference" / f"{cell.config['reference']}.py")
    steps = Steps(cfg, cell.traffic, seeds[0],
                  tp=cell.config["tensor_parallel"])
    n = cell.traffic["check_requests"]
    for seed in seeds:
        if seed != seeds[0]:
            steps.reseed(seed)
        with steps.minfo.mesh:
            batches, _, _ = loop.serve_window(
                steps, harness.seed_rng(seed, harness.PROMPTS), 0.0)
            batches[-1].advance(None)
            batches[-1].release()
        picks = loop.sample_requests(batches, n,
                                     harness.seed_rng(seed, harness.SAMPLE))
        program = loop.compare(reference, steps.weights, cell.config, picks)
        control = None
        if seed in control_seeds:
            control = loop.compare(reference, steps.weights, cell.config,
                                   picks, control=True)
        yield seed, program, control
