"""Distribution tests: sharding resolver rules + a real multi-device pjit run
in a subprocess (8 placeholder CPU devices so the main process keeps 1)."""

import subprocess
import sys

import jax
import pytest
from jax.sharding import PartitionSpec as P

from repro.configs.base import ARCH_IDS, INPUT_SHAPES, get_config
from repro.distributed import sharding as shd
from repro.models import api


class _FakeMeshInfo:
    """MeshInfo stand-in with given axis sizes (no devices needed)."""

    def __init__(self, sizes):
        self._sizes = sizes

    @property
    def axis_sizes(self):
        return dict(self._sizes)

    @property
    def model(self):
        return self._sizes.get("model", 1)

    @property
    def data(self):
        return self._sizes.get("data", 1)

    @property
    def has_pod(self):
        return "pod" in self._sizes

    @property
    def batch_axes(self):
        return ("pod", "data") if self.has_pod else ("data",)

    @property
    def batch_size(self):
        import numpy as np
        return int(np.prod([self._sizes[a] for a in self.batch_axes]))


MINFO = _FakeMeshInfo({"data": 16, "model": 16})


@pytest.mark.parametrize("arch", ARCH_IDS)
@pytest.mark.parametrize("mode", ["train", "infer"])
def test_param_specs_divisible(arch, mode):
    """Every sharded dim must be divisible by its mesh axes product."""
    cfg = get_config(arch)
    abstract = api.param_specs(cfg)
    specs = shd.param_specs(abstract, cfg, MINFO, mode)

    def check(leaf, spec):
        for dim, ax in zip(leaf.shape, spec):
            if ax is None:
                continue
            axes = (ax,) if isinstance(ax, str) else ax
            prod = 1
            for a in axes:
                prod *= MINFO.axis_sizes[a]
            assert dim % prod == 0, (arch, mode, leaf.shape, spec)

    jax.tree.map(check, abstract, specs,
                 is_leaf=lambda x: isinstance(x, P))


def test_train_mode_has_fsdp():
    cfg = get_config("qwen2_72b")
    abstract = api.param_specs(cfg)
    specs = shd.param_specs(abstract, cfg, MINFO, "train")
    flat = [s for s in jax.tree.leaves(specs, is_leaf=lambda x: isinstance(x, P))]
    n_data = sum(1 for s in flat if "data" in [a for ax in s if ax
                                               for a in ((ax,) if isinstance(ax, str) else ax)])
    assert n_data > len(flat) * 0.5  # most params data-sharded (FSDP)


def test_infer_mode_fsdp_only_when_needed():
    big = get_config("mixtral_8x22b")      # 280 GB bf16 -> needs data shard
    small = get_config("gemma2_2b")        # fits TP-only
    for cfg, expect_fsdp in ((big, True), (small, False)):
        abstract = api.param_specs(cfg)
        specs = shd.param_specs(abstract, cfg, MINFO, "infer")
        flat = jax.tree.leaves(specs, is_leaf=lambda x: isinstance(x, P))
        has_data = any("data" in [a for ax in s if ax
                                  for a in ((ax,) if isinstance(ax, str) else ax)]
                       for s in flat)
        assert has_data == expect_fsdp, cfg.name


def test_cache_specs_long_context_seq_sharded():
    cfg = get_config("mamba2_1p3b")
    shape = INPUT_SHAPES["long_500k"]
    cache_abs = jax.eval_shape(lambda: api.init_cache(cfg, 1, shape.seq_len))
    specs = shd.cache_specs_tree(cache_abs, cfg, MINFO, 1, shape.seq_len)
    # mamba states have no seq axis; check a windowed arch instead
    cfg2 = get_config("mixtral_8x22b")
    cache2 = jax.eval_shape(lambda: api.init_cache(cfg2, 1, shape.seq_len))
    specs2 = shd.cache_specs_tree(cache2, cfg2, MINFO, 1, shape.seq_len)
    k_spec = specs2[0]["k"]
    # (count, B, KV, S, hd): sequence axis at index 3
    assert k_spec[3] is not None  # sequence axis sharded


SUBPROCESS_PROG = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, jax.numpy as jnp, numpy as np
from repro.configs.base import get_config, InputShape
from repro.launch import steps as steps_lib
from repro.launch.mesh import make_host_mesh
from repro.models import api

minfo = make_host_mesh(4, 2)
mesh = minfo.mesh
cfg = get_config("smollm_360m", tiny=True).replace(num_heads=4, num_kv_heads=2,
                                                   head_dim=32, d_model=128,
                                                   d_ff=256, vocab_size=512)
shape = InputShape("t", 64, 8, "train")
with mesh:
    fn, arg_specs, in_sh, _ = steps_lib.make_train_step(cfg, minfo, shape,
                                                        num_microbatches=2)
    rng = jax.random.PRNGKey(0)
    params = api.init_params(rng, cfg)
    from repro.training import adamw
    opt = adamw.init(params)
    batch = api.make_batch(rng, cfg, shape)
    params = jax.device_put(params, in_sh[0])
    opt = jax.device_put(opt, in_sh[1])
    batch = jax.device_put(batch, in_sh[2])
    p2, o2, m = fn(params, opt, batch)
    loss1 = float(m["loss"])
    p3, o3, m2 = fn(p2, o2, batch)
    loss2 = float(m2["loss"])
assert np.isfinite(loss1) and np.isfinite(loss2), (loss1, loss2)
assert loss2 < loss1 + 0.5
print("MULTIDEV_OK", loss1, loss2)
"""


def test_multidevice_train_step_executes():
    """Actually executes the sharded train step on 8 placeholder devices."""
    r = subprocess.run([sys.executable, "-c", SUBPROCESS_PROG],
                       capture_output=True, text=True, timeout=600,
                       env={**__import__("os").environ, "PYTHONPATH": "src"})
    assert "MULTIDEV_OK" in r.stdout, r.stdout + r.stderr


DECODE_PROG = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, jax.numpy as jnp, numpy as np
from repro.configs.base import get_config, InputShape
from repro.launch import steps as steps_lib
from repro.launch.mesh import make_host_mesh
from repro.models import api

minfo = make_host_mesh(4, 2)
mesh = minfo.mesh
cfg = get_config("mixtral_8x22b", tiny=True)
B, S = 8, 128
shape = InputShape("d", S, B, "decode")
rng = jax.random.PRNGKey(0)
params = api.init_params(rng, cfg)

# reference: single-logical-device decode via the internal put path
prefix = jax.random.randint(rng, (B, S - 1), 0, cfg.vocab_size, jnp.int32)
_, cache = api.prefill(params, {"tokens": prefix}, cfg, capacity=S)
tok = jax.random.randint(jax.random.PRNGKey(1), (B,), 0, cfg.vocab_size, jnp.int32)
pos = jnp.asarray(S - 1, jnp.int32)
ref_logits, ref_cache = api.decode_step(params, cache, tok, pos, cfg)

# sharded decode step (append-outside-scan + shard_map cache write)
with mesh:
    fn, arg_specs, _, _ = steps_lib.make_decode_step(cfg, minfo, shape)
    logits, new_cache = fn(params, cache, tok, pos)
np.testing.assert_allclose(np.asarray(logits, np.float32),
                           np.asarray(ref_logits, np.float32),
                           atol=5e-2, rtol=5e-2)
for a, b in zip(jax.tree.leaves(new_cache), jax.tree.leaves(ref_cache)):
    np.testing.assert_allclose(np.asarray(a, np.float32),
                               np.asarray(b, np.float32), atol=5e-2, rtol=5e-2)
print("DECODE_SHARDED_OK")
"""


def test_multidevice_decode_matches_reference():
    """The sharded append-decode (shard_map cache write) must equal the
    single-device reference decode bit-for-bit-ish."""
    r = subprocess.run([sys.executable, "-c", DECODE_PROG],
                       capture_output=True, text=True, timeout=600,
                       env={**__import__("os").environ, "PYTHONPATH": "src"})
    assert "DECODE_SHARDED_OK" in r.stdout, r.stdout + r.stderr
