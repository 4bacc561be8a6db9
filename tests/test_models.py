"""Per-architecture smoke tests (reduced same-family configs): one forward /
train step on CPU asserting shapes + finiteness, plus prefill/decode
consistency."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import ARCH_IDS, INPUT_SHAPES, InputShape, get_config
from repro.models import api

SMOKE_SHAPE = InputShape("smoke", seq_len=64, global_batch=2, kind="train")


@pytest.fixture(scope="module")
def rng():
    return jax.random.PRNGKey(0)


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_smoke_train_step(arch, rng):
    cfg = get_config(arch, tiny=True)
    assert cfg.num_layers <= 6 and cfg.d_model <= 512
    assert cfg.num_experts <= 4
    params = api.init_params(rng, cfg)
    batch = api.make_batch(rng, cfg, SMOKE_SHAPE)

    def loss_fn(p):
        loss, m = api.train_loss(p, batch, cfg, remat=False)
        return loss

    loss, grads = jax.jit(jax.value_and_grad(loss_fn))(params)
    assert jnp.isfinite(loss), arch
    assert 0.0 < float(loss) < 20.0
    gnorm = sum(float(jnp.sum(jnp.square(g))) for g in jax.tree.leaves(grads))
    assert np.isfinite(gnorm) and gnorm > 0.0


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_smoke_prefill_decode_shapes(arch, rng):
    cfg = get_config(arch, tiny=True)
    params = api.init_params(rng, cfg)
    batch = api.make_batch(rng, cfg, SMOKE_SHAPE)
    B = SMOKE_SHAPE.global_batch
    logits, cache = jax.jit(
        lambda p, b: api.prefill(p, b, cfg, capacity=96))(params, batch)
    assert logits.shape == (B, cfg.vocab_size)
    assert bool(jnp.isfinite(logits).all())
    tok = jnp.argmax(logits, -1).astype(jnp.int32)
    pos = jnp.asarray(64, jnp.int32)
    logits2, cache2 = jax.jit(
        lambda p, c, t, q: api.decode_step(p, c, t, q, cfg))(
            params, cache, tok, pos)
    assert logits2.shape == (B, cfg.vocab_size)
    assert bool(jnp.isfinite(logits2).all())


def _api_path(cfg, capacity):
    """Prefill and decode on one device (``models.api``)."""
    def prefill(params, tokens):
        return api.prefill(params, {"tokens": tokens}, cfg, capacity=capacity)

    def decode(params, cache, tok, pos):
        return api.decode_step(params, cache, tok, pos, cfg)
    return prefill, decode


def _steps_path(cfg, capacity):
    """Prefill and decode by the chip's step builders (``launch.steps``),
    as the benchmark builds them, on a 1x1 host mesh."""
    from repro.launch import steps
    from repro.launch.mesh import make_host_mesh
    minfo = make_host_mesh(1, 1)

    def prefill(params, tokens):
        B, T = tokens.shape
        with minfo.mesh:
            fn = steps.make_prefill_step(
                cfg, minfo, InputShape("p", T, B, "prefill"),
                capacity=capacity)[0]
            return fn(params, {"tokens": tokens})

    def decode(params, cache, tok, pos):
        with minfo.mesh:
            fn = steps.make_decode_step(
                cfg, minfo, InputShape("d", capacity, tok.shape[0], "decode"))[0]
            return fn(params, cache, tok, pos)
    return prefill, decode


@pytest.mark.parametrize("path", [_api_path, _steps_path],
                         ids=["api", "steps"])
@pytest.mark.parametrize("arch", ["smollm_360m", "gemma2_2b", "mamba2_1p3b",
                                  "zamba2_1p2b", "mixtral_8x22b",
                                  "granite_20b"])
def test_decode_matches_prefill(arch, path, rng):
    """Prefilling [t0..tN] must equal prefilling [t0..tN-2] then decoding
    tN-1 and tN, in the logits and in the cache written, both on one device
    and through the step builders: either way the cache is written by
    ``distributed.cache_update.write``."""
    cfg = get_config(arch, tiny=True)
    params = api.init_params(rng, cfg)
    T = 32
    tokens = jax.random.randint(rng, (1, T), 0, cfg.vocab_size, jnp.int32)
    prefill, decode = path(cfg, T + 4)

    full_logits, full_cache = prefill(params, tokens)
    _, cache = prefill(params, tokens[:, :-2])
    for pos in (T - 2, T - 1):
        step_logits, cache = decode(params, cache, tokens[:, pos],
                                    jnp.asarray(pos, jnp.int32))
    for want, got in zip(jax.tree.leaves(full_cache), jax.tree.leaves(cache)):
        np.testing.assert_allclose(np.asarray(got, np.float32),
                                   np.asarray(want, np.float32),
                                   atol=2e-1, rtol=2e-1)
    np.testing.assert_allclose(np.asarray(step_logits, np.float32),
                               np.asarray(full_logits, np.float32),
                               atol=2e-1, rtol=2e-1)
    # argmax agreement is the serving-relevant property
    assert int(jnp.argmax(step_logits)) == int(jnp.argmax(full_logits))


def test_training_reduces_loss():
    from repro.training.loop import train
    cfg = get_config("smollm_360m", tiny=True)
    out = train(cfg, steps=30, batch_size=4, seq_len=128, log_every=0)
    assert out["losses"][-1] < out["losses"][0] - 0.15


def test_checkpoint_roundtrip(tmp_path, rng):
    from repro.training import adamw, checkpoint
    cfg = get_config("smollm_360m", tiny=True)
    params = api.init_params(rng, cfg)
    opt = adamw.init(params)
    p = str(tmp_path / "ckpt.npz")
    checkpoint.save(p, 7, params, opt)
    step, params2, opt2 = checkpoint.load(p, params, opt)
    assert step == 7
    for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(params2)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_param_count_analytic_close_to_actual():
    for arch in ("smollm_360m", "gemma2_2b", "mamba2_1p3b", "qwen3_moe_30b_a3b"):
        cfg = get_config(arch, tiny=True)
        params = api.init_params(jax.random.PRNGKey(0), cfg)
        actual = sum(x.size for x in jax.tree.leaves(params))
        analytic = cfg.param_count()
        assert abs(actual - analytic) / actual < 0.25, (arch, actual, analytic)


def test_moe_aux_loss_and_capacity():
    from repro.models.moe import capacity
    assert capacity(256, 8, 2) >= 64
    cfg = get_config("qwen3_moe_30b_a3b", tiny=True)
    params = api.init_params(jax.random.PRNGKey(0), cfg)
    batch = api.make_batch(jax.random.PRNGKey(1), cfg, SMOKE_SHAPE)
    loss, metrics = api.train_loss(params, batch, cfg, remat=False)
    assert float(metrics["aux"]) > 0.0  # load-balance loss active


@pytest.mark.parametrize("arch", ["smollm_360m", "mixtral_8x22b",
                                  "mamba2_1p3b", "gemma2_2b", "granite_20b"])
def test_pallas_kernel_path_matches_xla(arch, rng):
    """kernel_impl='pallas' (interpret mode on CPU) must reproduce the XLA
    path end-to-end: prefill logits, then three decode steps, each path
    through its own cache (the Pallas decode kernel reads each layer of
    the stacked cache in place), fed the XLA path's tokens: the same
    logits within bf16 rounding and the same greedy tokens."""
    cfg_x = get_config(arch, tiny=True)
    cfg_p = cfg_x.replace(kernel_impl="pallas")
    params = api.init_params(rng, cfg_x)
    tokens = jax.random.randint(rng, (2, 64), 0, cfg_x.vocab_size, jnp.int32)

    lx, cx = api.prefill(params, {"tokens": tokens}, cfg_x, capacity=96)
    lp, cp = api.prefill(params, {"tokens": tokens}, cfg_p, capacity=96)
    np.testing.assert_allclose(np.asarray(lp, np.float32),
                               np.asarray(lx, np.float32), atol=3e-2, rtol=3e-2)

    tok = jnp.argmax(lx, -1).astype(jnp.int32)
    for i in range(3):
        pos = jnp.asarray(64 + i, jnp.int32)
        dx, cx = api.decode_step(params, cx, tok, pos, cfg_x)
        dp, cp = api.decode_step(params, cp, tok, pos, cfg_p)
        np.testing.assert_allclose(np.asarray(dp, np.float32),
                                   np.asarray(dx, np.float32), atol=5e-2,
                                   rtol=5e-2)
        tok = jnp.argmax(dx, -1).astype(jnp.int32)
        np.testing.assert_array_equal(np.asarray(jnp.argmax(dp, -1)),
                                      np.asarray(tok))
