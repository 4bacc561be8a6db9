"""idle_share.generate_tp4: ``idle_share.generate`` in a tensor-parallel
cell: the share of the traced window in which no operation ran, averaged
over the cell's chips, in %."""

from pathlib import Path

from chipbench import harness

read = harness.load_module(
    Path(__file__).with_name("idle_share.generate.py")).read
