"""mfu.prefill_tp4: ``mfu.prefill`` in a tensor-parallel cell, whose work
model counts one chip's share of the prefill's operations: that share
over the prefill program's device time on the first chip and one chip's
peak, in %."""

from pathlib import Path

from chipbench import harness

read = harness.load_module(Path(__file__).with_name("mfu.prefill.py")).read
