"""Compile the main path's Pallas kernels, and whole model steps, for a
described TPU v5e chip at published widths.

Nothing runs: the TPU compiler refuses here what interpret mode accepts (an
unsupported primitive, a misaligned tile, too much VMEM), so these tests
catch it without a chip.  The topology is described inside a fixture, never
at import, and all these tests live in this one file: only one process at a
time may load the TPU library, and it keeps it until it exits.
"""

import contextlib
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs.base import InputShape, get_config
from repro.models import api

SMOLLM = get_config("smollm-360m")
MAMBA2 = get_config("mamba2-1.3b")
GRANITE = get_config("granite-20b").replace(kernel_impl="pallas")


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@contextlib.contextmanager
def _no_persistent_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without one: keep the cache off around it."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    try:
        yield
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        cc.reset_cache()


def _compile(fn, *args):
    with _no_persistent_cache():
        return jax.jit(fn).lower(*args).compile()


def _spec(sharding, shape, dtype=jnp.bfloat16):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _assert_kernel(compiled):
    assert "tpu_custom_call" in compiled.as_text()


def test_flash_attention_compiles(one_chip):
    from repro.kernels.flash_attention.ops import flash_attention
    cfg = SMOLLM
    B, T = 8, 512
    q = _spec(one_chip, (B, T, cfg.num_heads, cfg.head_dim))
    kv = _spec(one_chip, (B, T, cfg.num_kv_heads, cfg.head_dim))
    _assert_kernel(_compile(
        lambda q, k, v: flash_attention(q, k, v, causal=True,
                                        interpret=False), q, kv, kv))


def test_decode_attention_kvmajor_compiles(one_chip):
    """A small cache, and decode-long's (B 64, S 2048): the kernel takes
    the cache as it lies, with no pad around it."""
    from repro.kernels.decode_attention.ops import decode_attention_kvmajor
    cfg = SMOLLM
    for B, S in ((8, 1024), (64, 2048)):
        q = _spec(one_chip, (B, cfg.num_heads, cfg.head_dim))
        kv = _spec(one_chip, (B, cfg.num_kv_heads, S, cfg.head_dim))
        pos = _spec(one_chip, (), jnp.int32)
        compiled = _compile(
            lambda q, k, v, pos: decode_attention_kvmajor(q, k, v, pos,
                                                          interpret=False),
            q, kv, kv, pos)
        _assert_kernel(compiled)
        assert " pad(" not in compiled.as_text(), (B, S)


@pytest.mark.parametrize("page_size", [16, 64, 128])
def test_paged_decode_attention_compiles(one_chip, page_size):
    from repro.kernels.decode_attention.ops import paged_decode_attention
    cfg = SMOLLM
    B, S = 8, 1024
    ns = S // page_size
    q = _spec(one_chip, (B, cfg.num_heads, cfg.head_dim))
    pages = _spec(one_chip, (B * ns, page_size, cfg.num_kv_heads,
                             cfg.head_dim))
    lens = _spec(one_chip, (B,), jnp.int32)
    tables = _spec(one_chip, (B, ns), jnp.int32)
    _assert_kernel(_compile(
        lambda q, k, v, lens, tbl: paged_decode_attention(
            q, k, v, lens, tbl, interpret=False),
        q, pages, pages, lens, tables))


def test_ssd_scan_compiles(one_chip):
    from repro.kernels.ssd_scan.ops import ssd_scan
    from repro.models.mamba import dims
    cfg = MAMBA2
    d_in, H, P, N = dims(cfg)
    B, T = 2, 512
    x = _spec(one_chip, (B, T, H, P))
    dt = _spec(one_chip, (B, T, H), jnp.float32)
    A = _spec(one_chip, (H,), jnp.float32)
    bc = _spec(one_chip, (B, T, N))
    _assert_kernel(_compile(
        lambda x, dt, A, Bm, Cm: ssd_scan(x, dt, A, Bm, Cm,
                                          chunk=cfg.ssm_chunk_size,
                                          interpret=False),
        x, dt, A, bc, bc))


def _on_chip(monkeypatch):
    """The kernels' wrappers ask the default backend (here the CPU) whether
    to interpret; answer as the chip would."""
    from repro.kernels.decode_attention import ops as decode_ops
    from repro.kernels.flash_attention import ops as flash_ops
    from repro.kernels.ssd_scan import ops as ssd_ops
    for ops in (decode_ops, flash_ops, ssd_ops):
        monkeypatch.setattr(ops, "_on_cpu", lambda: False)


def _param_specs(cfg, sharding):
    return jax.tree.map(lambda s: _spec(sharding, s.shape, s.dtype),
                        api.param_specs(cfg))


def test_smollm_decode_step_compiles_with_kernels(one_chip, monkeypatch):
    _on_chip(monkeypatch)
    cfg = SMOLLM.replace(kernel_impl="pallas")
    B, S = 8, 1024
    params = _param_specs(cfg, one_chip)
    cache = jax.tree.map(lambda s: _spec(one_chip, s.shape, s.dtype),
                         jax.eval_shape(lambda: api.init_cache(cfg, B, S)))
    tokens = _spec(one_chip, (B,), jnp.int32)
    pos = _spec(one_chip, (), jnp.int32)
    _assert_kernel(_compile(
        lambda p, c, t, pos: api.decode_step(p, c, t, pos, cfg),
        params, cache, tokens, pos))


def test_smollm_prefill_step_compiles_with_kernels(one_chip, monkeypatch):
    _on_chip(monkeypatch)
    cfg = SMOLLM.replace(kernel_impl="pallas")
    B, T = 2, 512
    params = _param_specs(cfg, one_chip)
    batch = {"tokens": _spec(one_chip, (B, T), jnp.int32)}
    _assert_kernel(_compile(
        lambda p, b: api.prefill(p, b, cfg, 2 * T), params, batch))


# granite-20b.decode-tp4's shapes: batch 64, prompt 512, 1536 positions
TP4_B, TP4_P, TP4_C = 64, 512, 1536


@pytest.fixture(scope="module")
def granite_tp4(topo):
    """granite-20b's prefill and decode programs at published widths,
    tensor-parallel over the four chips of the described 2x2 host, built by
    the program's step builders and compiled once for the tests below."""
    from jax.sharding import AxisType, Mesh
    from repro.distributed.sharding import MeshInfo
    from repro.launch import steps
    mesh = Mesh(np.array(topo.devices).reshape(1, 4), ("data", "model"),
                axis_types=(AxisType.Auto,) * 2)
    minfo = MeshInfo(mesh)
    out = {}
    with pytest.MonkeyPatch.context() as mp, mesh, _no_persistent_cache():
        _on_chip(mp)
        fn, args, _, _ = steps.make_prefill_step(
            GRANITE, minfo, InputShape("p", TP4_P, TP4_B, "prefill"),
            capacity=TP4_C)
        out["prefill"] = fn.lower(*args).compile()
        fn, args, _, _ = steps.make_decode_step(
            GRANITE, minfo, InputShape("d", TP4_C, TP4_B, "decode"))
        out["decode"] = fn.lower(*args).compile()
    return out


@pytest.mark.parametrize("program", ["prefill", "decode"])
def test_granite_tp4_steps_compile_with_kernels(granite_tp4, program):
    """Both kernels run inside the programs on every chip, and a chip holds
    a program's arguments, outputs and temporaries in its 16 GiB."""
    compiled = granite_tp4[program]
    _assert_kernel(compiled)
    ma = compiled.memory_analysis()
    held = (ma.argument_size_in_bytes + ma.output_size_in_bytes
            - ma.alias_size_in_bytes + ma.temp_size_in_bytes)
    assert held < 16 * 2 ** 30, held


@pytest.mark.parametrize("program", ["prefill", "decode"])
def test_granite_tp4_gathers_no_cache(granite_tp4, program):
    """No all-gather in either program is as large as one layer's K cache
    over all four chips: each chip attends over, and writes, its own
    positions of the cache."""
    cfg = GRANITE
    one_layer = TP4_B * cfg.num_kv_heads * TP4_C * cfg.head_dim
    gathered = [int(np.prod([int(d) for d in dims.split(",") if d]))
                for dims in re.findall(
                    r"= \w+\[([\d,]*)\]\{[^}]*\} all-gather(?:-start)?\(",
                    granite_tp4[program].as_text())]
    assert gathered and max(gathered) < one_layer, gathered


# smollm-360m.decode-long's shapes: batch 64, 2048 positions
DL_B, DL_C = 64, 2048


@pytest.fixture(scope="module")
def smollm_decode_long(topo):
    """smollm-360m's decode program at decode-long's shapes on one chip,
    made by `launch.steps.make_decode_step`, as the benchmark makes it."""
    from jax.sharding import AxisType, Mesh
    from repro.distributed.sharding import MeshInfo
    from repro.launch import steps
    mesh = Mesh(np.array(topo.devices[:1]).reshape(1, 1), ("data", "model"),
                axis_types=(AxisType.Auto,) * 2)
    with pytest.MonkeyPatch.context() as mp, mesh, _no_persistent_cache():
        _on_chip(mp)
        fn, args, _, _ = steps.make_decode_step(
            SMOLLM.replace(kernel_impl="pallas"), MeshInfo(mesh),
            InputShape("d", DL_C, DL_B, "decode"))
        return fn.lower(*args).compile()


def _instructions(text):
    """{name: (dtype, dims, opcode)} of every instruction of an HLO text
    that yields one array."""
    return {name: (dt, tuple(int(d) for d in dims.split(",") if d), op)
            for name, dt, dims, op in re.findall(
                r"^\s*(?:ROOT )?%(\S+) = (\w+)\[([\d,]*)\]\{[^}]*\} "
                r"([\w-]+)\(", text, re.M)}


@pytest.mark.parametrize("program,cfg,B,S", [
    ("smollm_decode_long", SMOLLM, DL_B, DL_C),
    ("granite_tp4", GRANITE, TP4_B, TP4_C // 4),
])
def test_decode_kernel_reads_the_stacked_cache(request, program, cfg, B, S):
    """The decode kernel reads each layer's K/V out of the group's stacked
    cache (S: the positions a chip holds): its K and V operands are the
    whole stack, as it lies, and no op in the program slices, stages,
    relays or folds the new token into one layer's K or V, or copies the
    stack."""
    compiled = request.getfixturevalue(program)
    if program == "granite_tp4":
        compiled = compiled["decode"]
    text = compiled.as_text()
    ops = _instructions(text)
    layer = B * cfg.num_kv_heads * S * cfg.head_dim
    stack = cfg.layer_groups[0][1] * layer

    def size(dims):
        return int(np.prod(dims)) if dims else 1

    one_layer = [n for n, (dt, dims, op) in ops.items()
                 if dt == "bf16" and size(dims) == layer
                 and {S, cfg.head_dim} <= set(dims)]
    assert not one_layer, one_layer
    copies = [n for n, (dt, dims, op) in ops.items()
              if op == "copy" and size(dims) == stack]
    assert not copies, copies
    calls = re.findall(r"%(\S*decode_attention\S*) = .*? custom-call\(([^)]*)\)"
                       r", custom_call_target=\"tpu_custom_call\"", text)
    assert calls
    for name, operands in calls:
        names = [o.strip().lstrip("%")
                 for o in re.sub(r"/\*[^*]*\*/", "", operands).split(",")]
        for kv in names[3:5]:            # after pos, the layer index and q
            dt, dims, op = ops[kv]
            assert size(dims) == stack and op in ("bitcast",
                                                  "get-tuple-element"), (
                name, kv, dims, op)
