"""What decides `correct`, at tiny sizes on the CPU: the plain references
agree with the program (prefill, then decode through the cache), the
control (the reference in fp8 put in the program's place) reads above the
limit, and a run whose timed path is broken underneath comes out not
correct."""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import harness
from chipbench.tests.conftest import TINY_LIMIT, TINY_LOGIT_LIMIT
from chipbench.tests.test_harness import CELLS, run_tiny

SEEDS = [1, 2, 3]        # the seeds TINY_LIMIT was set from


@pytest.mark.parametrize("name", CELLS)
def test_program_within_and_control_beyond_the_limit(tiny_root, name):
    cell = harness.load_cell(name, tiny_root / "BENCHMARK.json", tiny_root)
    driver = harness.load_module(tiny_root / "drivers" / "generate.py")
    rows = list(driver.readings(cell, SEEDS, set(SEEDS)))
    limits = {"widest_gap": TINY_LIMIT, "logit_error": TINY_LOGIT_LIMIT}
    for seed, program, control in rows:
        assert all(program[k] < lim for k, lim in limits.items()), program
        assert all(control[k] > lim for k, lim in limits.items()), control


def stale_state(driver):
    """The decode step returns the cache it was given."""
    init = driver.Steps.__init__

    def patched(self, *a, **k):
        init(self, *a, **k)
        decode = self.decode

        def unchanged(w, cache, tok, pos):
            logits, _ = decode(w, jax.tree.map(jnp.copy, cache), tok, pos)
            return logits, cache
        self.decode = unchanged
    driver.Steps.__init__ = patched


def altered_token(driver):
    """Every served token is altered where the argmax produces it (the
    check samples a few requests, so every request carries the fault)."""
    greedy = driver.greedy

    def altered(logits):
        tok, top = greedy(logits)
        return (tok + 1) % logits.shape[-1], top
    driver.greedy = altered


@pytest.mark.parametrize("fault", [stale_state, altered_token])
@pytest.mark.parametrize("name", CELLS)
def test_broken_timed_path_is_not_correct(tiny_root, name, fault):
    line = run_tiny(tiny_root, name, patch=fault)
    assert not line["correct"], line["checks"]


@pytest.mark.parametrize("name", CELLS)
def test_reference_matches_program_prefill_then_decode(tiny_root, name):
    """Teacher-forced: the program's prefill logits and every decode
    step's logits through the cache against the reference's full forward,
    for all positions of a short sequence."""
    cell = harness.load_cell(name, tiny_root / "BENCHMARK.json", tiny_root)
    driver = harness.load_module(tiny_root / "drivers" / "generate.py")
    ref = harness.load_module(tiny_root / "reference"
                              / f"{cell.config['reference']}.py")
    cfg = harness.program_config(cell.config)
    steps = driver.Steps(cfg, cell.traffic, 5)
    rng = np.random.default_rng(0)
    prompts = steps.prompts(rng)
    follow = rng.integers(0, cfg.vocab_size, (steps.B, steps.G), np.int32)
    with steps.minfo.mesh:
        logits, cache = steps.prefill(steps.weights, {"tokens": prompts})
        got = [np.asarray(logits)]
        for i in range(steps.G - 1):
            logits, cache = steps.decode(steps.weights, cache, follow[:, i],
                                         np.int32(steps.P + i))
            got.append(np.asarray(logits))
    got = np.stack(got, 1)
    seqs = np.concatenate([prompts, follow[:, :-1]], 1)
    want = ref.logits(steps.weights, cell.config, seqs,
                      np.arange(steps.P - 1, steps.P + steps.G - 1))
    scale = np.abs(want).max()
    err = np.abs(got - want).max()
    # bf16 program against the f32 reference: a few percent of the logits'
    # range at two layers; a missing or wrong term is of the order of 1
    assert err < 0.05 * scale, (err, scale)
