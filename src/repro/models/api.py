"""Unified model API — dispatches on ``cfg.arch_type``.

All entry points are pure functions usable under ``jax.jit``,
``jax.eval_shape`` (dry-run) and ``jax.grad``:

  init_params(rng, cfg)                      -> params pytree
  train_loss(params, batch, cfg)             -> (loss, metrics)
  prefill(params, batch, cfg, capacity)      -> (last_logits, cache)
  decode_step(params, cache, tokens, pos, cfg) -> (logits, cache)
  init_cache(cfg, batch, capacity)           -> cache pytree
  make_batch / batch_specs                   -> concrete / abstract inputs

The models compute and never write the cache: ``prefill_kv`` and
``decode_kv`` return the K/V (and states) a step produced, and
``distributed.cache_update.write`` puts them into the cache.  ``prefill``
and ``decode_step`` do both, for a cache on one device; the sharded step
builders (``launch.steps``) give the writer the cache's layout.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.configs.base import InputShape, ModelConfig
from repro.distributed import cache_update
from repro.models import encdec, transformer

Array = jax.Array


def _is_encdec(cfg: ModelConfig) -> bool:
    return cfg.is_encoder_decoder


def init_params(rng, cfg: ModelConfig):
    if _is_encdec(cfg):
        return encdec.init_params(rng, cfg)
    return transformer.init_params(rng, cfg)


def train_loss(params, batch, cfg: ModelConfig, *, remat: bool = True,
               bspec=None):
    if _is_encdec(cfg):
        return encdec.train_loss(params, batch, cfg, remat=remat, bspec=bspec)
    return transformer.train_loss(params, batch, cfg, remat=remat, bspec=bspec)


def prefill_kv(params, batch, cfg: ModelConfig, cache, bspec=None,
               seq_axis=None, tp=None):
    """(last_logits, new): the prompt's K/V and states, in ``cache``'s tree,
    for ``cache_update.write`` at position 0."""
    if _is_encdec(cfg):
        return encdec.prefill(params, batch, cfg, bspec=bspec)
    return transformer.prefill(params, batch, cfg, cache, bspec=bspec,
                               seq_axis=seq_axis, tp=tp)


def decode_kv(params, cache, tokens, pos, cfg: ModelConfig, bspec=None,
              tp=None):
    """(logits, new): the tokens' K/V and the new states, in ``cache``'s
    tree, for ``cache_update.write`` at ``pos``."""
    if _is_encdec(cfg):
        return encdec.decode_step(params, cache, tokens, pos, cfg, bspec=bspec)
    return transformer.decode_step(params, cache, tokens, pos, cfg,
                                   bspec=bspec, tp=tp)


def prefill(params, batch, cfg: ModelConfig, capacity: int):
    cache = init_cache(cfg, batch["tokens"].shape[0], capacity)
    logits, new = prefill_kv(params, batch, cfg, cache)
    return logits, cache_update.write(cache, new, 0)


def decode_step(params, cache, tokens, pos, cfg: ModelConfig):
    logits, new = decode_kv(params, cache, tokens, pos, cfg)
    return logits, cache_update.write(cache, new, pos)


def init_cache(cfg: ModelConfig, batch: int, capacity: int):
    if _is_encdec(cfg):
        return encdec.init_cache(cfg, batch, capacity)
    return transformer.init_cache(cfg, batch, capacity)


# ---------------------------------------------------------------------------
# Input construction — concrete batches (smoke/bench) and abstract specs
# (dry-run; ShapeDtypeStruct, no allocation).
# ---------------------------------------------------------------------------
def _text_len(cfg: ModelConfig, seq_len: int) -> int:
    """Text-token length once stub frontend tokens are accounted for."""
    if cfg.frontend == "vision_stub":
        return max(seq_len - cfg.num_frontend_tokens, 1)
    return seq_len


def batch_shapes(cfg: ModelConfig, shape: InputShape) -> dict:
    """{name: (shape, dtype)} for each model input of this (arch, input-shape)."""
    B, S = shape.global_batch, shape.seq_len
    out = {}
    if shape.kind in ("train", "prefill"):
        out["tokens"] = ((B, _text_len(cfg, S)), jnp.int32)
        if cfg.frontend == "vision_stub":
            out["patch_embeds"] = ((B, cfg.num_frontend_tokens, cfg.d_model),
                                   jnp.dtype(cfg.dtype))
        if cfg.frontend == "audio_stub":
            out["audio_embeds"] = ((B, cfg.encoder_seq_len, cfg.d_model),
                                   jnp.dtype(cfg.dtype))
    else:  # decode: one token against a cache of S
        out["tokens"] = ((B,), jnp.int32)
    return out


def make_batch(rng, cfg: ModelConfig, shape: InputShape) -> dict:
    keys = jax.random.split(rng, 4)
    batch = {}
    for i, (name, (shp, dt)) in enumerate(sorted(batch_shapes(cfg, shape).items())):
        if jnp.issubdtype(dt, jnp.integer):
            batch[name] = jax.random.randint(keys[i], shp, 0, cfg.vocab_size, dt)
        else:
            batch[name] = (jax.random.normal(keys[i], shp) * 0.02).astype(dt)
    return batch


def batch_specs(cfg: ModelConfig, shape: InputShape) -> dict:
    return {name: jax.ShapeDtypeStruct(shp, dt)
            for name, (shp, dt) in batch_shapes(cfg, shape).items()}


def cache_specs(cfg: ModelConfig, shape: InputShape):
    """Abstract KV/state cache for decode shapes (capacity = seq_len)."""
    fn = lambda: init_cache(cfg, shape.global_batch, shape.seq_len)
    return jax.eval_shape(fn)


def param_specs(cfg: ModelConfig):
    return jax.eval_shape(lambda: init_params(jax.random.PRNGKey(0), cfg))
