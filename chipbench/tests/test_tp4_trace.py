"""The four-chip readers on a trace recorded on one TPU v5e 2x2 host: a
--trace 1 run of granite-20b.decode-tp4 (29-s window: two prefills of 64 x
512 tokens, 1024 decode steps), cut by ``chipbench/trim_trace.py`` to the
program executions of the window and the operations of its first prefill
and first four decode steps on each chip."""

from __future__ import annotations

import json
import math
import re
import types

import pytest

from chipbench import harness, trace

DATA = harness.HERE / "tests" / "data" / "decode-tp4.xplane.pb"
CONF = json.loads((harness.HERE / "configs" / "granite-20b.json").read_text())
WORK = harness.load_module(harness.HERE / "flops" / "gpt_bigcode.py")
B, P = 64, 512
# batch one's 1023 decode steps, then the first of batch two's
LIVES = list(range(P + 1, P + 1024)) + [P + 1]
COLLECTIVE = harness.load_module(
    harness.HERE / "metrics" / "collective_share.decode_tp4.py").COLLECTIVE


def view_of(facts_lives):
    return trace.View.load(
        str(DATA), cell=types.SimpleNamespace(config=CONF),
        peaks=harness.peaks_for("TPU v5 lite"), work=WORK, chips=4,
        facts={"shapes": {"batch": B, "prompt_len": P, "gen_len": 1024,
                          "capacity": 1536},
               "decode_live": facts_lives})


@pytest.fixture(scope="module")
def view():
    return view_of(LIVES)


@pytest.fixture(scope="module")
def traced_steps():
    """The view cut to the decode steps whose operations the trace keeps
    (the first four), for the readers of operations."""
    v = view_of(LIVES[:4])
    v.runs = {p: [r for r in rs if r.ops or r.name != "jit_decode"]
              for p, rs in v.runs.items()}
    return v


def read(name, view):
    return harness.load_module(harness.HERE / "metrics" / f"{name}.py").read(
        view)


def test_programs_and_kernels_on_every_chip(view):
    assert len(view.runs) == 4
    for plane in sorted(view.runs):
        runs = [r for r in view.runs[plane] if r.start >= view.window[0]]
        decode = [r for r in runs if r.name == "jit_decode" and r.ops]
        prefill = [r for r in runs if r.name == "jit_prefill_step" and r.ops]
        assert len(decode) == 4 and len(prefill) == 1, plane
        assert {o.name for r in decode for o in r.ops if o.kernel} == {
            "_decode_attention_kvmajor"}
        assert {o.name for o in prefill[0].ops if o.kernel} == {
            "_flash_attention"}


def test_module_readers_match_the_traced_run(view):
    """The module events are all kept: the readers of program times give
    the readings of the traced run's whole window (``mfu.prefill_tp4`` as
    the run printed it; ``roofline_mfu.decode_tp4`` by the work model that
    counts the first chip's own positions, where the run's counted a
    quarter of the live ones and read 54.51%)."""
    assert len(view.runs_of("^jit_decode$")) == len(LIVES)
    assert read("roofline_mfu.decode_tp4", view) == pytest.approx(
        55.110624322871125, rel=1e-9)
    assert read("mfu.prefill_tp4", view) == pytest.approx(
        55.1460513639107, rel=1e-9)
    assert read("flash_attention_roofline_tp4", view) == pytest.approx(
        18.576577229900284, rel=1e-9)


def test_op_readers_on_the_kept_steps(traced_steps):
    share = read("collective_share.decode_tp4", traced_steps)
    assert 10 < share < 25
    assert read("decode_attention_roofline_tp4", traced_steps) == \
        pytest.approx(34.05967641890111, rel=1e-9)
    for name in ("roofline_mfu.decode_tp4", "mfu.prefill_tp4",
                 "flash_attention_roofline_tp4"):
        assert 0 < read(name, traced_steps) <= 100


def test_the_cache_is_read_before_the_decode_kernel(traced_steps):
    """Each decode step on the first chip slices and lays out each layer's
    K and V once (``decode_attention_roofline_tp4``'s ``READS_CACHE``)
    before the kernel, which then takes less time than the K/V it reads
    would take at HBM bandwidth: the kernel alone would read above 100%."""
    reads_cache = harness.load_module(
        harness.HERE / "metrics" / "decode_attention_roofline_tp4.py"
    ).READS_CACHE
    runs = traced_steps.runs_of("^jit_decode$")
    for r in runs:
        names = [o.name for o in r.ops if reads_cache.match(o.name)]
        assert {n: names.count(n) for n in set(names)} == {
            "constant_dynamic-slice_fusion": 104, "copy_bitcast_fusion": 104}
    kernel = traced_steps.kernel_seconds(runs, "decode_attention")
    least = sum(traced_steps.least_s(*WORK.decode_attention(
        CONF, B, WORK.held(CONF, 1536, live))) for live in LIVES[:4])
    assert least / kernel > 1


def test_collectives_by_name(traced_steps):
    """Each decode step on the first chip runs, by name, per layer: the
    gather of the softmax statistics (``all-gather``), the reduce-scatter
    of the attention outputs, the all-reduces after Wo and W2, and the
    gather of q as an async pair of fusions; plus the embedding's
    all-reduce and the logits' gather once.  Two more pieces of the gather
    of q run as plain ``fusion`` ops between the pair, and the reader does
    not count them."""
    runs = traced_steps.runs_of("^jit_decode$")
    for r in runs:
        names = [o.name for o in r.ops if COLLECTIVE.match(o.name)]
        count = {n: names.count(n) for n in set(names)}
        assert count == {"all-gather": 53, "reduce_scatter": 52,
                         "all-reduce": 105, "async-collective-start": 52,
                         "async-collective-done": 52}, count
        # no other op is named after a collective
        others = {o.name for o in r.ops if not COLLECTIVE.match(o.name)}
        assert not [n for n in others
                    if re.search(r"all-|scatter|permute|collective", n)]


def test_no_op_gathers_the_cache():
    """No all-gather in the window is as large as one layer's K cache
    over all four chips (64 x 1536 x 128)."""
    from jax.profiler import ProfileData
    one_layer = B * 1536 * 128
    sizes = [math.prod(int(d) for d in dims.split(",") if d)
             for plane in ProfileData.from_file(str(DATA)).planes
             for line in plane.lines if line.name == "XLA Ops"
             for ev in line.events
             for dims in re.findall(r"= \w+\[([\d,]*)\]\{[^}]*\} all-gather\(",
                                    ev.name)]
    assert sizes and max(sizes) < one_layer
