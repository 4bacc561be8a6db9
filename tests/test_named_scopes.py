"""The model step names its parts with ``jax.named_scope``: the compiled
prefill and decode programs carry the scopes in their ``op_name`` metadata
(which a profiler trace reports per op), keep their program names, and are
otherwise the programs they were without the scopes."""

import contextlib
import re

import jax
import pytest

from repro.configs.base import InputShape, get_config
from repro.launch import steps
from repro.launch.mesh import make_host_mesh

B, T, CAP = 2, 16, 32
SCOPES = {"embed", "layers", "attention", "mlp", "cache_update", "logits"}
DEBUG_TABLES = ("FileNames", "FunctionNames", "FileLocations", "StackFrames")


def _step(cfg, kind):
    minfo = make_host_mesh(1, 1)
    with minfo.mesh:
        if kind == "prefill":
            fn, args, _, _ = steps.make_prefill_step(
                cfg, minfo, InputShape("prefill", T, B, "prefill"),
                capacity=CAP)
        else:
            fn, args, _, _ = steps.make_decode_step(
                cfg, minfo, InputShape("decode", CAP, B, "decode"))
        return fn.lower(*args)


def _op_names(hlo: str) -> set:
    return set(re.findall(r'op_name="([^"]*)"', hlo))


def _parts(op_names) -> set:
    return {p for n in op_names for p in n.split("/")}


def _without_debug_info(hlo: str) -> str:
    """Compiled HLO text without ``metadata={...}`` and the tables of files,
    functions and stack frames that the metadata points into, its
    instructions and computations renamed in order of first appearance
    (the CPU compiler numbers some of them differently under other name
    stacks)."""
    out, table = [], False
    for line in hlo.splitlines():
        if line in DEBUG_TABLES:
            table = True
        elif table and re.match(r"^\d+ ", line):
            continue
        else:
            table = False
            out.append(re.sub(r", metadata=\{[^}]*\}", "", line))
    names = {}
    return re.sub(r"%[\w.\-]+",
                  lambda m: names.setdefault(m.group(0), f"%{len(names)}"),
                  "\n".join(out))


@pytest.fixture(scope="module")
def dense():
    return get_config("smollm_360m", tiny=True)


@pytest.mark.parametrize("kind", ["prefill", "decode"])
def test_step_ops_carry_the_scopes(dense, kind):
    names = _op_names(_step(dense, kind).compile().as_text())
    assert SCOPES <= _parts(names)


def test_scan_slicing_lies_in_layers_outside_attention(dense):
    names = _op_names(_step(dense, "decode").compile().as_text())
    slicing = [n for n in names
               if re.search(r"/layers/while/body/dynamic_slice$", n)]
    assert slicing
    assert not any("attention/" in n for n in slicing)
    # the new token's append after the scan is a cache write
    assert any(re.search(r"/cache_update/.*dynamic_update_slice$", n)
               for n in names)


def test_programs_keep_their_names(dense):
    for kind, name in (("prefill", "jit_prefill_step"),
                       ("decode", "jit_decode")):
        lowered = _step(dense, kind)
        assert f"module @{name}" in lowered.as_text()
        assert lowered.compile().as_text().startswith(f"HloModule {name},")


@pytest.mark.parametrize("kind", ["prefill", "decode"])
def test_scopes_change_only_metadata(dense, kind, monkeypatch):
    scoped = _step(dense, kind).compile().as_text()
    monkeypatch.setattr(jax, "named_scope",
                        lambda name: contextlib.nullcontext())
    plain = _step(dense, kind).compile().as_text()
    assert not SCOPES & _parts(_op_names(plain))
    assert _without_debug_info(scoped) == _without_debug_info(plain)


def test_pallas_fold_in_is_a_cache_update_inside_attention(dense):
    """The Pallas decode kernel folds the new token in itself: the kernel
    lies under ``attention``, no cache write does, and the one write of
    the new token is the stacked append after the scan."""
    cfg = dense.replace(kernel_impl="pallas")
    names = _op_names(_step(cfg, "decode").compile().as_text())
    assert any(re.search(r"/layers/while/body/.*attention/.*decode_attention",
                         n) for n in names)
    assert not any("/attention/cache_update/" in n for n in names)
    writes = [n for n in names if "/cache_update/" in n]
    assert writes and not any("/layers/" in n for n in writes)


def test_mamba_steps_carry_ssm():
    cfg = get_config("mamba2_1p3b", tiny=True)
    for kind in ("prefill", "decode"):
        parts = _parts(_op_names(_step(cfg, kind).compile().as_text()))
        assert {"embed", "layers", "ssm", "logits"} <= parts
