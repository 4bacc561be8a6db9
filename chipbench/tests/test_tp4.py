"""The four-chip cell's parts on the CPU: the GPT-BigCode work model's
per-chip counts, its readers on a synthetic four-chip trace, and a tiny
tensor-parallel cell run end to end by the harness on four CPU devices
(the Pallas kernels in interpret mode)."""

from __future__ import annotations

import json
import os
import subprocess
import sys
import types

import pytest

from chipbench import harness, trace
from chipbench.tests.conftest import BENCH, REPO, make_tiny_root

GRANITE = json.loads((BENCH / "configs" / "granite-20b.json").read_text())
WORK = harness.load_module(BENCH / "flops" / "gpt_bigcode.py")
PEAKS = harness.peaks_for("TPU v5 lite")
READERS = ("roofline_mfu.decode_tp4", "mfu.prefill_tp4",
           "collective_share.decode_tp4", "decode_attention_roofline_tp4",
           "flash_attention_roofline_tp4", "idle_share.generate_tp4")


def at(tp: int) -> dict:
    return dict(GRANITE, tensor_parallel=tp)


def test_counts_at_tp1_are_the_whole_models():
    from repro.configs.base import get_config
    whole = at(1)
    L, d, H, hd, ff, V = 52, 6144, 48, 128, 24576, 49152
    layer_mm = d * H * hd + 2 * d * hd + H * hd * d + 2 * d * ff
    assert WORK.params(whole) == get_config("granite-20b").param_count()
    B, live, NP = 8, 700, 8192
    ops, byts = WORK.decode(whole, B, 1536, live)
    assert ops == 2 * B * (L * layer_mm + d * V) + L * 4 * B * H * hd * live
    # every weight but the position table, of which one row
    assert byts == ((WORK.params(whole) - NP * d + d) * 2
                    + L * 2 * B * live * hd * 2 + L * 2 * B * hd * 2
                    + B * V * 4)
    P = 16
    assert WORK.prefill(whole, B, P) == (
        2 * B * P * L * layer_mm + L * 4 * B * H * hd * P * (P + 1) // 2
        + 2 * B * d * V)


def test_counts_at_tp4_are_one_chips_share():
    """Four chips hold the split weights once and the replicated ones four
    times; the first chip attends over the live positions of its quarter
    of the cache, and writes the new entries only while they land there."""
    d, hd = 6144, 128
    L, NP = 52, 8192
    replicated = L * (2 * d * hd + 2 * hd + 6 * d) + NP * d + 2 * d
    assert 4 * WORK.params(at(4)) == WORK.params(at(1)) + 3 * replicated
    B, C = 64, 1536
    assert [WORK.held(at(4), C, n) for n in (200, 384, 1024)] == [
        200, 384, 384]
    assert WORK.held(at(1), C, 1024) == 1024
    for live in (200, 1024):
        n = WORK.held(at(4), C, live)
        ops4, byts4 = WORK.decode(at(4), B, C, live)
        ops1, byts1 = WORK.decode(at(1), B, C, live)
        att4 = WORK.decode_attention(at(4), B, n)[0]
        att1 = WORK.decode_attention(at(1), B, live)[0]
        assert 4 * (ops4 - att4) == (ops1 - att1) + 3 * 2 * B * L * 2 * d * hd
        new = L * 2 * B * hd * 2 if live <= C // 4 else 0
        assert byts4 == ((WORK.params(at(4)) - NP * d + d) * 2
                         + L * 2 * B * n * hd * 2 + new
                         + B * 49152 * 4 // 4)


def _op(name, start, end, kernel=False):
    return trace.Op(name=name, label=f"{name}.1", start=start, end=end,
                    module="", kernel=kernel)


def synthetic_view():
    """One prefill of 3 s with a 400-ms flash kernel, then two decode
    steps of 40 ms on the first chip, each with a 12-ms decode kernel, 6
    ms of slicing the cache for it and 8 ms of collectives (two of them
    overlapping by 1 ms); 20 ms idle at the window's end."""
    ms = 1_000_000
    runs = [trace.Run("jit_prefill_step", 0, 3000 * ms, [
        _op("_flash_attention", 0, 400 * ms, kernel=True),
        _op("fusion", 400 * ms, 3000 * ms)])]
    for i in range(2):
        t = 3000 * ms + i * 40 * ms
        runs.append(trace.Run("jit_decode", t, t + 40 * ms, [
            _op("_decode_attention_kvmajor", t, t + 12 * ms, kernel=True),
            _op("all-gather", t + 12 * ms, t + 14 * ms),
            _op("reduce_scatter", t + 14 * ms, t + 16 * ms),
            _op("async-collective-start", t + 16 * ms, t + 18 * ms),
            _op("all-reduce", t + 17 * ms, t + 20 * ms),
            _op("constant_dynamic-slice_fusion", t + 20 * ms, t + 23 * ms),
            _op("copy_bitcast_fusion", t + 23 * ms, t + 26 * ms),
            _op("fusion", t + 26 * ms, t + 40 * ms)]))
    facts = {"shapes": {"batch": 64, "prompt_len": 512, "gen_len": 1024,
                        "capacity": 1536},
             "decode_live": [513, 514]}
    return trace.View({"/device:TPU:0": runs}, [], (0, 3100 * ms),
                      cell=types.SimpleNamespace(config=at(4)), facts=facts,
                      peaks=PEAKS, work=WORK, chips=1)


def read(name, view):
    return harness.load_module(BENCH / "metrics" / f"{name}.py").read(view)


def test_readers_on_a_synthetic_trace():
    view = synthetic_view()
    assert read("collective_share.decode_tp4", view) == pytest.approx(
        100 * 8 / 40)
    least = sum(view.least_s(*WORK.decode(at(4), 64, 1536, n))
                for n in (513, 514))
    assert read("roofline_mfu.decode_tp4", view) == pytest.approx(
        100 * least / 0.080)
    # the first chip holds 384 live positions at both steps
    least = 2 * view.least_s(*WORK.decode_attention(at(4), 64, 384))
    assert read("decode_attention_roofline_tp4", view) == pytest.approx(
        100 * least / 0.036)
    assert read("mfu.prefill_tp4", view) == pytest.approx(
        100 * WORK.prefill(at(4), 64, 512) / 3.0 / PEAKS["bf16_flops_per_s"])
    least = view.least_s(*WORK.flash_attention(at(4), 64, 512))
    assert read("flash_attention_roofline_tp4", view) == pytest.approx(
        100 * least / 0.4)
    assert read("idle_share.generate_tp4", view) == pytest.approx(
        100 * 20 / 3100)
    for name in READERS:
        assert 0 < read(name, view) <= 100, name


def test_readers_show_an_over_count():
    """Work counted a hundred times over reads above 100%, as it is, so
    that the over-count shows and the run is refused."""
    view = synthetic_view()
    view.work = types.SimpleNamespace(
        held=WORK.held,
        decode=lambda c, B, C, n: tuple(
            100 * x for x in WORK.decode(c, B, C, n)),
        prefill=lambda c, B, P: 100 * WORK.prefill(c, B, P),
        decode_attention=lambda c, B, n: tuple(
            100 * x for x in WORK.decode_attention(c, B, n)),
        flash_attention=lambda c, B, P: tuple(
            100 * x for x in WORK.flash_attention(c, B, P)))
    for name in ("roofline_mfu.decode_tp4", "mfu.prefill_tp4",
                 "decode_attention_roofline_tp4",
                 "flash_attention_roofline_tp4"):
        assert read(name, view) > 100, name


def test_readers_find_nothing_without_their_program():
    view = synthetic_view()
    empty = trace.View({}, [], view.window, cell=view.cell, facts=view.facts,
                       peaks=PEAKS, work=WORK)
    for name in READERS:
        assert read(name, empty) is None, name


# a tiny GPT-BigCode, tensor-parallel over four CPU devices
TINY_TP = dict(GRANITE, name="tiny-bigcode", n_layer=2, n_embd=128,
               n_head=8, head_dim=16, n_inner=512, vocab_size=512,
               n_positions=256)
TINY_TP_TRAFFIC = {"driver": "generate_tp", "batch": 4, "prompt_len": 64,
                   "gen_len": 32, "capacity": 128, "check_requests": 4}
# At these sizes (CPU, four devices, seeds 1-2) the program read logit
# error 0.0029 to 0.0042 and the fp8 control 0.027 to 0.043: the limit lies
# between.  Widest gap read 0 for both (the control's first-ranked tokens
# are the reference's at this vocabulary), so the control fails by the
# logit error alone.
TINY_TP_LIMITS = {"widest_gap": 0.02, "logit_error": 0.012}

TP4_RUN = r"""
import json, os, sys, time
from pathlib import Path
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
sys.path[:0] = [{repo!r}, {src!r}]
from chipbench import harness
root = Path({root!r})
cell = harness.load_cell("tiny-bigcode.tiny-gen-tp", root / "BENCHMARK.json",
                         root)
line = harness.run_cell(cell, 2**31 + 12345, 1.0, False, time.perf_counter(),
                        platform="cpu")
print("LINE", json.dumps(line))
driver = harness.load_module(root / "drivers" / "generate_tp.py")
for seed, program, control in driver.readings(cell, [1, 2], {{1, 2}}):
    print("READING", json.dumps([program, control]))
"""


def test_tiny_tensor_parallel_cell(tmp_path):
    """The harness runs a cell it has never seen with the tensor-parallel
    driver on four devices: correct, every end-to-end metric, four
    devices; the control reads beyond the limits the program keeps."""
    root = make_tiny_root(tmp_path)
    cell = "tiny-bigcode.tiny-gen-tp"
    (root / "configs" / "tiny-bigcode.json").write_text(json.dumps(TINY_TP))
    (root / "traffic" / "tiny-gen-tp.json").write_text(
        json.dumps(TINY_TP_TRAFFIC))
    (root / "checks" / f"{cell}.json").write_text(json.dumps(
        {k: {"limit": v} for k, v in TINY_TP_LIMITS.items()}))
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["workloads"].append({"name": cell, "config": "tiny-bigcode",
                               "traffic": "tiny-gen-tp", "chips": 4,
                               "why": "test"})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    prog = TP4_RUN.format(repo=str(REPO), src=str(REPO / "src"),
                          root=str(root))
    r = subprocess.run([sys.executable, "-c", prog], capture_output=True,
                       text=True, timeout=900,
                       env=dict(os.environ, JAX_PLATFORMS="cpu"))
    out = [l.split(" ", 1) for l in r.stdout.splitlines()
           if l.startswith(("LINE", "READING"))]
    assert len(out) == 3, r.stdout + r.stderr
    line = json.loads(out[0][1])
    assert line["correct"], line["checks"]
    assert set(line["metrics"]) == {"tokens_per_s", "tpot_p95_ms", "setup_s"}
    assert line["device"]["count"] == 4 and line["failed"] == 0
    for _, text in out[1:]:
        program, control = json.loads(text)
        assert all(program[k] < v for k, v in TINY_TP_LIMITS.items())
        assert any(control[k] > v for k, v in TINY_TP_LIMITS.items())
