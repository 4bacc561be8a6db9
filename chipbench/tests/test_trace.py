"""The trace reduction and the per-layer readers on a trace recorded on one
TPU v5e: a --trace 1 run of smollm-360m.decode-long with a 4-second window
(one prefill of 64 x 1792 tokens, then 22 decode steps at live lengths
1793 to 1814)."""

from __future__ import annotations

import json
import types

import pytest

from chipbench import harness, trace

DATA = harness.HERE / "tests" / "data" / "decode-long.xplane.pb"
CONF = json.loads((harness.HERE / "configs" / "smollm-360m.json").read_text())
PEAKS = harness.peaks_for("TPU v5 lite")
WORK = harness.load_module(harness.HERE / "flops" / f"{CONF['flops']}.py")
B, P = 64, 1792
LIVES = list(range(P + 1, P + 23))


@pytest.fixture(scope="module")
def view():
    return trace.View.load(
        str(DATA), cell=types.SimpleNamespace(config=CONF), peaks=PEAKS,
        work=WORK,
        facts={"shapes": {"batch": B, "prompt_len": P, "gen_len": 256,
                          "capacity": 2048},
               "decode_live": LIVES}, chips=1)


def test_window_and_busy(view):
    assert view.window_s() == pytest.approx(4.009283775, abs=1e-9)
    assert view.busy_s() == pytest.approx(4.007488969, abs=1e-6)
    assert 0 < view.idle_share() < 0.01


def test_program_runs_and_kernels(view):
    prefill = view.runs_of("prefill")
    decode = view.runs_of("^jit_decode$")
    assert len(prefill) == 1 and len(decode) == 22
    assert prefill[0].seconds == pytest.approx(2.455982492, abs=1e-9)
    assert sum(r.seconds for r in decode) == pytest.approx(1.623530475,
                                                            abs=1e-6)
    assert view.kernel_seconds(prefill, "flash_attention") == pytest.approx(
        1.755995952, abs=1e-6)
    assert view.kernel_seconds(decode, "decode_attention") == pytest.approx(
        0.936482819, abs=1e-6)
    # no kernel of the other kind in either program
    assert view.kernel_seconds(prefill, "decode_attention") == 0
    assert view.kernel_seconds(decode, "flash_attention") == 0


def read(name, view):
    return harness.load_module(harness.HERE / "metrics" / f"{name}.py").read(
        view)


def test_readers(view):
    peak = PEAKS["bf16_flops_per_s"]
    assert read("mfu.prefill", view) == pytest.approx(
        100 * WORK.prefill(CONF, B, P) / 2.455982492 / peak, rel=1e-6)
    least = view.least_s(*WORK.flash_attention(CONF, B, P))
    assert read("flash_attention_roofline", view) == pytest.approx(
        100 * least / 1.755995952, rel=1e-6)
    least = sum(view.least_s(*WORK.decode(CONF, B, n))
                for n in LIVES)
    assert read("roofline_mfu.decode", view) == pytest.approx(
        100 * least / 1.623530475, rel=1e-6)
    least = sum(view.least_s(*WORK.decode_attention(CONF, B, n))
                for n in LIVES)
    assert read("decode_attention_roofline", view) == pytest.approx(
        100 * least / 0.936482819, rel=1e-6)
    for name in ("mfu.prefill", "flash_attention_roofline",
                 "roofline_mfu.decode", "decode_attention_roofline"):
        assert 0 < read(name, view) <= 100


def test_reader_finds_nothing_without_its_program(view):
    empty = trace.View({}, [], view.window, cell=view.cell, facts=view.facts,
                       peaks=PEAKS, work=WORK)
    for name in ("mfu.prefill", "flash_attention_roofline",
                 "roofline_mfu.decode", "decode_attention_roofline",
                 "idle_share.generate"):
        assert read(name, empty) is None


def test_breakdown(view):
    b = view.breakdown()
    assert b["device_ops"][0][0] == "jit_prefill_step/_flash_attention.6"
    assert len(b["device_ops"]) == 10 and len(b["idle_gaps"]) == 10
    assert all(s > 0 for _, s in b["idle_gaps"])
    assert all(name.startswith("bench.") for name, _ in b["idle_gaps"])
