"""flash_attention_roofline_tp4: ``flash_attention_roofline`` in a
tensor-parallel cell: the prefill's flash kernels on the first chip,
which attend with that chip's query heads, against one chip's share of
causal attention's operations and bytes (the work model's
``flash_attention``), in % of its roofline."""

from pathlib import Path

from chipbench import harness

read = harness.load_module(
    Path(__file__).with_name("flash_attention_roofline.py")).read
