"""The model step's scopes read from a trace's op metadata, on two traces
recorded on one TPU v5e, each a --trace 1 run of smollm-360m.decode-long
with a 4-second window (one prefill of 64 x 1792 tokens, then 22 decode
steps): ``decode-long.xplane.pb`` from a program without ``jax.named_scope``
and ``decode-long-scoped.xplane.pb`` from one with them."""

from __future__ import annotations

import json
import types

import pytest

from chipbench import harness, scopes, trace

DATA = harness.HERE / "tests" / "data"
CONF = json.loads((harness.HERE / "configs" / "smollm-360m.json").read_text())
PEAKS = harness.peaks_for("TPU v5 lite")
WORK = harness.load_module(harness.HERE / "flops" / f"{CONF['flops']}.py")
P = 1792
FACTS = {"shapes": {"batch": 64, "prompt_len": P, "gen_len": 256,
                    "capacity": 2048},
         "decode_live": list(range(P + 1, P + 23))}


def load(name):
    return scopes.ScopedView.load(
        str(DATA / name), cell=types.SimpleNamespace(config=CONF),
        peaks=PEAKS, work=WORK, facts=FACTS, chips=1)


@pytest.fixture(scope="module")
def unscoped():
    return load("decode-long.xplane.pb")


@pytest.fixture(scope="module")
def scoped():
    return load("decode-long-scoped.xplane.pb")


def read(name, view):
    return harness.load_module(harness.HERE / "metrics" / f"{name}.py").read(
        view)


def test_op_metadata_names_scope_and_source(unscoped):
    decode = unscoped.runs_of("^jit_decode$")
    ops = {o.label: o for o in decode[0].ops}
    assert ops["copy.71"].scope == "jit(decode)/while/body/dynamic_slice"
    assert ops["copy.71"].source.endswith("transformer.py:319")
    kernel = ops["_decode_attention_kvmajor.5"]
    assert kernel.scope.endswith("pallas_call")
    assert kernel.source.endswith("decode_attention.py:112")
    # the prefill's ops carry the prefill program's name stack
    names = [o.scope for o in unscoped.runs_of("prefill")[0].ops]
    assert not any(s.startswith("jit(decode)") for s in names)
    assert sum(s.startswith("jit(prefill_step)/") for s in names) > (
        0.9 * len(names))


def test_scoped_view_reduces_as_the_view_does(unscoped):
    plain = trace.View.load(str(DATA / "decode-long.xplane.pb"), chips=1)
    assert unscoped.window == plain.window
    assert unscoped.busy_s() == plain.busy_s()
    assert unscoped.breakdown() == plain.breakdown()
    for plane, runs in plain.runs.items():
        assert [(r.name, r.start, r.end, [(o.label, o.start, o.end, o.kernel)
                                          for o in r.ops]) for r in runs] == [
            (r.name, r.start, r.end, [(o.label, o.start, o.end, o.kernel)
                                      for o in r.ops])
            for r in unscoped.runs[plane]]


def test_cache_share_is_none_before_the_scopes(unscoped):
    assert scopes.cache_share(unscoped) is None
    assert scopes.unscoped_share(unscoped, "decode") == 100.0


def _op(scope, start, end, module="jit_decode"):
    return scopes.ScopedOp(name="op", label="op.1", start=start, end=end,
                           module=module, kernel=False, scope=scope)


def test_cache_share_counts_cache_writes_and_the_scan():
    pre = "jit(decode)/layers/while/body/"
    ops = [
        _op(pre + "dynamic_slice", 0, 300),                        # scan
        _op(pre + "closed_call/attention/jit(f)/pallas_call", 300, 700),
        _op(pre + "closed_call/attention/cache_update/"
            "dynamic_update_slice", 700, 720),                   # fold-in
        _op(pre + "closed_call/mlp/dot_general", 720, 820),
        _op("jit(decode)/layers/while", 820, 830),                 # scan
        _op("jit(decode)/cache_update/dynamic_update_slice", 830, 900),
        _op("jit(decode)/logits/dot_general", 900, 950),
        _op("", 950, 1000),                                        # none
    ]
    run = trace.Run(name="jit_decode", start=0, end=1000, ops=ops)
    other = trace.Run(name="jit_prefill_step", start=1000, end=2000,
                      ops=[_op("jit(prefill_step)/cache_update/x", 1000, 2000,
                               "jit_prefill_step")])
    v = scopes.ScopedView({"/device:TPU:0": [run, other]}, [], (0, 2000))
    assert v.scope_seconds([run], lambda s: "mlp" in s) == pytest.approx(
        100e-9)
    assert scopes.cache_share(v) == pytest.approx(
        100 * (300 + 20 + 10 + 70) / 1000)
    assert scopes.unscoped_share(v, "decode") == pytest.approx(5.0)
    assert scopes.scope_shares(v, "decode") == pytest.approx(
        {"layers": 83.0, "cache_update": 7.0, "logits": 5.0})
    assert scopes.scope_shares(v, "prefill") == {"cache_update": 100.0}


# An XSpace in protobuf text format: two programs whose one operation has
# the same HLO text; each op's metadata names its program by id, the
# second's tf_op by reference to a stat metadata.
TWO_PROGRAMS = """
planes {
  id: 1
  name: "/device:TPU:0"
  lines {
    id: 1 name: "XLA Modules" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 10000000 }
    events { metadata_id: 2 offset_ps: 20000000 duration_ps: 10000000 }
  }
  lines {
    id: 2 name: "XLA Ops" timestamp_ns: 1000
    events { metadata_id: 3 offset_ps: 1000000 duration_ps: 4000000 }
    events { metadata_id: 4 offset_ps: 21000000 duration_ps: 6000000 }
  }
  event_metadata { key: 1 value { id: 1 name: "jit_a(11)" } }
  event_metadata { key: 2 value { id: 2 name: "jit_b(22)" } }
  event_metadata { key: 3 value {
    id: 3 name: "%copy.1 = f32[8]{0} copy(f32[8]{0} %p)"
    stats { metadata_id: 1 uint64_value: 11 }
    stats { metadata_id: 2 str_value: "jit(a)/layers/while/body/dynamic_slice:" }
    stats { metadata_id: 3 str_value: "/src/a.py:7" } } }
  event_metadata { key: 4 value {
    id: 4 name: "%copy.1 = f32[8]{0} copy(f32[8]{0} %p)"
    stats { metadata_id: 1 uint64_value: 22 }
    stats { metadata_id: 2 ref_value: 9 } } }
  stat_metadata { key: 1 value { id: 1 name: "program_id" } }
  stat_metadata { key: 2 value { id: 2 name: "tf_op" } }
  stat_metadata { key: 3 value { id: 3 name: "source" } }
  stat_metadata { key: 9 value {
    id: 9 name: "jit(b)/cache_update/dynamic_update_slice:" } }
}
"""


def test_same_op_text_in_two_programs_keeps_its_own_metadata():
    from jax.profiler import ProfileData
    data = ProfileData.text_proto_to_serialized_xspace(TWO_PROGRAMS)
    pd = ProfileData.from_serialized_xspace(data)
    v = scopes.ScopedView.from_profile(pd)
    v.attach(pd, scopes.op_metadata(data))
    (a, b) = v.runs["/device:TPU:0"]
    assert (a.name, b.name) == ("jit_a", "jit_b")
    assert [(o.label, o.scope, o.source) for o in a.ops] == [
        ("copy.1", "jit(a)/layers/while/body/dynamic_slice", "/src/a.py:7")]
    assert [(o.label, o.scope, o.source) for o in b.ops] == [
        ("copy.1", "jit(b)/cache_update/dynamic_update_slice", "")]
    # with no metadata every scope is empty
    bare = scopes.ScopedView.from_profile(pd)
    bare.attach(pd, {})
    assert [o.scope for r in bare.runs["/device:TPU:0"] for o in r.ops] == [
        "", ""]


def test_scoped_trace_reads_every_metric(scoped):
    for name in ("mfu.prefill", "flash_attention_roofline",
                 "roofline_mfu.decode", "decode_attention_roofline",
                 "idle_share.generate"):
        assert read(name, scoped) is not None
    decode = scoped.runs_of("^jit_decode$")
    assert len(decode) == 22
    ops = {o.label: o for o in decode[0].ops}
    assert ops["copy.71"].scope == "jit(decode)/layers/while/body/dynamic_slice"
    assert ops["_decode_attention_kvmajor.5"].scope.startswith(
        "jit(decode)/layers/while/body/closed_call/attention/")
    assert ops["dynamic_update_slice.12"].scope == (
        "jit(decode)/cache_update/dynamic_update_slice")
    share = scopes.cache_share(scoped)
    assert share == pytest.approx(40.33882918605063, rel=1e-6)
    assert 35 <= share <= 42            # the prediction before the trace


@pytest.mark.parametrize("program,stack", [("decode", "jit(decode)/"),
                                           ("prefill", "jit(prefill_step)/")])
def test_every_op_of_the_program_lies_under_a_scope(scoped, program, stack):
    runs = scoped.runs_of(scopes.PROGRAMS[program])
    ops = [o for r in runs for o in r.ops]
    # every op that JAX staged from the step lies under one of the scopes
    assert all(scopes.parts(o.scope) for o in ops if o.scope.startswith(stack))
    # the rest (async copies, buffer allocation, argument layout copies)
    # take under 0.5% of the program's op time
    assert {o.name for o in ops if not o.scope.startswith(stack)} <= {
        "copy", "copy-start", "copy-done", "slice-start", "slice-done",
        "custom-call"}
    assert scopes.unscoped_share(scoped, program) < 0.5


def test_script_prints_one_line(capsys):
    assert scopes.main([str(DATA / "decode-long-scoped.xplane.pb")]) == 0
    line = json.loads(capsys.readouterr().out)
    assert line["cache_share.decode"] == pytest.approx(40.33882918605063,
                                                       rel=1e-6)
    assert set(line["scope_shares"]["decode"]) == {
        "embed", "layers", "cache_update", "logits"}
    assert line["trace_bytes"] == 1855221 and line["metadata_s"] > 0
