"""granite-20b (a GPT-BigCode block with multi-query attention) against the
plain float32 reference ``chipbench/reference/gpt_bigcode.py``, at its tiny
size on the CPU with the Pallas kernels in interpret mode: the program's
prefill and greedy decode steps through the cache, on one device and
tensor-parallel over four; the decode kernel over a cache split by
positions; the parameter count; the sharding of the block's leaves."""

import json
import os
import subprocess
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.configs.base import InputShape, get_config
from repro.distributed import sharding as shd
from repro.models import api
from repro.models.layers import shard_weights
from test_distributed import _FakeMeshInfo

REPO = Path(__file__).resolve().parent.parent
TINY = get_config("granite-20b", tiny=True).replace(kernel_impl="pallas")
B, PROMPT, STEPS, CAP = 4, 40, 8, 128
# The program serves bf16 against a float32 reference, so each logit is off
# by a few bf16 roundings of the residual stream: at most 0.0031 of the
# logits' range on one device and 0.0029 on four (seeds 1-3).  Every linear
# layer in fp8 instead (the reference's control, one precision below the
# program's) is off by 0.027 or more, and a program without its position
# table by 0.23 or more: the limit lies between.
LIMIT = 0.008


def tiny_conf(cfg=TINY) -> dict:
    """The configuration file of granite-20b with the program's tiny sizes
    in place of the published ones."""
    conf = json.loads((REPO / "chipbench" / "configs" /
                       "granite-20b.json").read_text())
    for f, key in conf["program_fields"].items():
        conf[key] = getattr(cfg, f)
    return conf


def serve(cfg, minfo, weights, prompts):
    """Prefill, then STEPS greedy decode steps through the cache, by the
    program's step builders on ``minfo``'s mesh: (served ids (B, STEPS),
    their logits (B, STEPS, V))."""
    from repro.launch import steps
    with minfo.mesh:
        prefill = steps.make_prefill_step(
            cfg, minfo, InputShape("p", PROMPT, B, "prefill"),
            capacity=CAP)[0]
        decode = steps.make_decode_step(
            cfg, minfo, InputShape("d", CAP, B, "decode"))[0]
        logits, cache = prefill(weights, {"tokens": prompts})
        out = [np.asarray(logits)]
        for i in range(STEPS - 1):
            tok = jnp.argmax(logits, -1).astype(jnp.int32)
            logits, cache = decode(weights, cache, tok,
                                   np.int32(PROMPT + i))
            out.append(np.asarray(logits))
    logits = np.stack(out, 1)
    return logits.argmax(-1), logits


def reference_error(weights, prompts, served, logits, control=False):
    """The largest distance between the logits served and the reference's
    at every served position, over the logits' range; with ``control``,
    the reference's fp8 control in the program's place."""
    from chipbench.reference import gpt_bigcode as ref
    conf = tiny_conf()
    seqs = np.concatenate([prompts, served[:, :-1]], 1)
    positions = np.arange(PROMPT - 1, PROMPT + STEPS - 1)
    want = ref.logits(weights, conf, seqs, positions)
    if control:
        logits = ref.logits(weights, conf, seqs, positions, control=True)
    return float(np.abs(logits - want).max() / np.ptp(want))


def weights_and_prompts(seed):
    from chipbench import harness
    weights = harness.make_weights(TINY, seed)
    prompts = np.random.default_rng(seed).integers(
        0, TINY.vocab_size, (B, PROMPT), dtype=np.int32)
    return weights, prompts


@pytest.mark.parametrize("seed", [1, 2])
def test_prefill_then_decode_matches_reference(seed):
    from repro.launch.mesh import make_host_mesh
    weights, prompts = weights_and_prompts(seed)
    served, logits = serve(TINY, make_host_mesh(1, 1), weights, prompts)
    err = reference_error(weights, prompts, served, logits)
    control = reference_error(weights, prompts, served, logits, control=True)
    assert err < LIMIT < control, (err, control)


TP4_PROG = r"""
import os, sys
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
sys.path[:0] = [{src!r}, {repo!r}, {tests!r}]
import jax
from repro.launch.mesh import make_host_mesh
from repro.launch.steps import kernel_tp
import test_granite as t

minfo = make_host_mesh(1, 4)
assert kernel_tp(t.TINY, minfo, ("data",)) is not None
weights, prompts = t.weights_and_prompts({seed})
served, logits = t.serve(t.TINY, minfo, weights, prompts)
print("TP4_ERROR", t.reference_error(weights, prompts, served, logits))
"""


@pytest.mark.parametrize("seed", [1])
def test_tensor_parallel_prefill_then_decode_matches_reference(seed):
    """The same on a (data 1, model 4) mesh of CPU devices: both kernels
    run on each shard (heads split; the cache's positions split)."""
    prog = TP4_PROG.format(src=str(REPO / "src"), repo=str(REPO),
                           tests=str(REPO / "tests"), seed=seed)
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run([sys.executable, "-c", prog], capture_output=True,
                       text=True, timeout=600, env=env)
    lines = [l for l in r.stdout.splitlines() if l.startswith("TP4_ERROR")]
    assert lines, r.stdout + r.stderr
    err = float(lines[0].split()[1])
    assert err < LIMIT, err


@pytest.mark.parametrize("pos", [5, 40, 95, 127])
def test_decode_kernel_over_shards_equals_whole_cache(pos):
    """The decode kernel over 4 parts of a cache split by positions,
    combined by log-sum-exp, equals the kernel over the whole cache; a part
    with no live position gives m = -inf, l = 0 and no NaN."""
    from repro.kernels.decode_attention.ops import decode_attention_kvmajor
    Bq, H, S, hd, n = 4, 8, 128, 16, 4
    ks = jax.random.split(jax.random.PRNGKey(pos), 3)
    q = jax.random.normal(ks[0], (Bq, H, hd), jnp.float32)
    k = jax.random.normal(ks[1], (Bq, 1, S, hd), jnp.float32)
    v = jax.random.normal(ks[2], (Bq, 1, S, hd), jnp.float32)
    whole = decode_attention_kvmajor(q, k, v, pos)
    part = S // n
    outs, ms, ls = zip(*(decode_attention_kvmajor(
        q, k[:, :, i * part:(i + 1) * part], v[:, :, i * part:(i + 1) * part],
        pos - i * part, stats=True) for i in range(n)))
    w = shard_weights(jnp.stack(ms), jnp.stack(ls))
    got = jnp.einsum("nbh,nbhd->bhd", w, jnp.stack(outs))
    np.testing.assert_allclose(got, whole, atol=1e-5, rtol=1e-5)
    for i in range(n):
        if i * part > pos:                      # no live position here
            assert bool(jnp.all(ms[i] == -jnp.inf))
            assert bool(jnp.all(ls[i] == 0)) and bool(jnp.all(outs[i] == 0))
    assert bool(jnp.isfinite(got).all())


@pytest.mark.parametrize("pos", [5, 32, 95, 127])
def test_decode_kernel_over_shards_counts_the_new_token_once(pos):
    """With the new token's K/V apart from a stacked cache split by
    positions, only the part that holds its slot counts it, and the
    combined output equals the kernel over the whole cache.  The cache's
    own slot ``pos`` is stale (written after the step) and never read."""
    from repro.kernels.decode_attention.ops import (decode_attention_kvmajor,
                                                    decode_attention_stacked)
    Bq, H, S, hd, n, L = 4, 8, 128, 16, 4, 2
    ks = jax.random.split(jax.random.PRNGKey(pos), 5)
    q = jax.random.normal(ks[0], (Bq, H, hd), jnp.float32)
    k = jax.random.normal(ks[1], (L, Bq, 1, S, hd), jnp.float32)
    v = jax.random.normal(ks[2], (L, Bq, 1, S, hd), jnp.float32)
    new = {"k_new": jax.random.normal(ks[3], (Bq, 1, 1, hd), jnp.float32),
           "v_new": jax.random.normal(ks[4], (Bq, 1, 1, hd), jnp.float32)}
    k, v = k.at[:, :, :, pos].set(50.0), v.at[:, :, :, pos].set(50.0)
    whole = decode_attention_stacked(q, k, v, pos, 1, **new)
    part = S // n
    outs, ms, ls = zip(*(decode_attention_stacked(
        q, k[..., i * part:(i + 1) * part, :],
        v[..., i * part:(i + 1) * part, :], pos - i * part, 1, stats=True,
        **new) for i in range(n)))
    w = shard_weights(jnp.stack(ms), jnp.stack(ls))
    got = jnp.einsum("nbh,nbhd->bhd", w, jnp.stack(outs))
    np.testing.assert_allclose(got, whole, atol=1e-5, rtol=1e-5)
    kl = k[1].at[:, :, pos].set(new["k_new"][:, :, 0])
    vl = v[1].at[:, :, pos].set(new["v_new"][:, :, 0])
    np.testing.assert_allclose(whole, decode_attention_kvmajor(q, kl, vl, pos),
                               atol=1e-5, rtol=1e-5)
    for i in range(n):
        if i * part > pos:                      # no live position here
            assert bool(jnp.all(ms[i] == -jnp.inf))
            assert bool(jnp.all(ls[i] == 0)) and bool(jnp.all(outs[i] == 0))


def test_param_count():
    """20.06 B at the published widths (52 x 379.1 M in the layers, 302 M
    in wte, 50 M in wpe); at the tiny size the count is the leaves'."""
    assert abs(get_config("granite-20b").param_count() / 20.06e9 - 1) < 0.01
    params = api.param_specs(TINY)
    assert sum(x.size for x in jax.tree.leaves(params)) == TINY.param_count()


def test_sharding_of_the_block_leaves():
    """TP 4: the query heads, Wo's heads and the MLP's width are split, the
    tied embedding by vocabulary; the single K/V head, the output biases,
    the norms and their biases and the position table are whole."""
    cfg = get_config("granite-20b")
    specs = shd.param_specs(api.param_specs(cfg), cfg,
                            _FakeMeshInfo({"data": 1, "model": 4}), "tp")
    attn, mlp = specs["groups"][0]["attn"], specs["groups"][0]["mlp"]
    assert attn["wq"] == P(None, None, "model", None)
    assert attn["bq"] == P(None, "model", None)
    assert attn["wo"] == P(None, "model", None, None)
    for name in ("wk", "wv", "bk", "bv", "bo", "norm", "norm_bias"):
        assert attn[name] == P(*[None] * len(attn[name])), name
    assert mlp["wi"] == P(None, None, "model")
    assert mlp["bi"] == P(None, "model")
    assert mlp["wo"] == P(None, "model", None)
    for name in ("bo", "norm", "norm_bias"):
        assert mlp[name] == P(None, None), name
    assert specs["embed"] == P("model", None)
    for name in ("pos_embed", "final_norm", "final_norm_bias"):
        assert specs[name] == P(*[None] * len(specs[name])), name
