"""Sharded step functions (train / prefill / decode) used by the launcher,
the dry-run, and the examples.

Every builder returns ``(fn, arg_specs, in_shardings, out_shardings)`` where
``arg_specs`` are ShapeDtypeStructs suitable for ``jax.jit(...).lower(...)``
(dry-run, no allocation) and for ``jax.eval_shape``.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs.base import InputShape, ModelConfig
from repro.distributed import cache_update, sharding as shd
from repro.models import api
from repro.models.layers import TensorParallel
from repro.training import adamw

Array = jax.Array


def _named(minfo, spec_tree):
    return jax.tree.map(lambda s: NamedSharding(minfo.mesh, s), spec_tree,
                        is_leaf=lambda x: isinstance(x, P))


def kernel_tp(cfg: ModelConfig, minfo: shd.MeshInfo, bspec,
              cache_specs=None) -> Optional[TensorParallel]:
    """The tensor-parallel layout under which the Pallas attention kernels
    run on each shard (``layers.cp_decode_attention``,
    ``layers.tp_flash_attention``), or None where they run whole: one
    shard, XLA attention, heads that do not split over the model axis, or
    a K/V cache whose positions are not split over exactly that axis."""
    axis = "model"
    if (cfg.kernel_impl != "pallas" or minfo.model == 1
            or not shd.attn_head_tp(cfg, minfo.model)):
        return None
    for s in jax.tree.leaves(cache_specs or [],
                             is_leaf=lambda x: isinstance(x, P)):
        if s[-2] not in (axis, (axis,)):
            return None
    return TensorParallel(minfo.mesh, axis, bspec)


def default_microbatches(cfg: ModelConfig, shape: InputShape,
                         minfo: shd.MeshInfo) -> int:
    """Pick gradient-accumulation so each microbatch has ~<=2 seqs/device."""
    dp = minfo.batch_size
    per_dev = shape.global_batch / dp
    # scale down further for very large models (activation pressure); hybrid
    # archs carry both attention KV and d_in=2*d SSM streams per layer, so
    # they also get 1 seq/device (zamba2: temp 29.0 -> 14.8 GB at <1% bound
    # cost — EXPERIMENTS.md §Dry-run)
    target = 1 if (cfg.param_count() >= 30e9
                   or cfg.arch_type == "hybrid") else 2
    micro = max(1, int(per_dev / target))
    while shape.global_batch % (micro * dp) and micro > 1:
        micro -= 1
    return micro


# ---------------------------------------------------------------------------
# Train step (grad-accumulation microbatching + AdamW)
# ---------------------------------------------------------------------------
def make_train_step(cfg: ModelConfig, minfo: shd.MeshInfo, shape: InputShape,
                    *, num_microbatches: Optional[int] = None,
                    lr: float = 3e-4, remat: bool = True,
                    param_mode: str = "train"):
    if num_microbatches is None:
        num_microbatches = default_microbatches(cfg, shape, minfo)
    nm = num_microbatches

    abstract_params = api.param_specs(cfg)
    p_specs = shd.param_specs(abstract_params, cfg, minfo, param_mode)
    batch_abs = api.batch_specs(cfg, shape)
    b_specs = shd.batch_input_specs(batch_abs, minfo)
    bspec = shd.batch_spec_axes(minfo, shape.global_batch // nm)

    def loss_fn(params, mb):
        loss, metrics = api.train_loss(params, mb, cfg, remat=remat,
                                       bspec=bspec)
        return loss, metrics

    def train_step(params, opt_state, batch):
        def micro(carry, mb):
            gacc, lacc = carry
            (loss, metrics), grads = jax.value_and_grad(loss_fn, has_aux=True)(
                params, mb)
            gacc = jax.tree.map(lambda a, g: a + g.astype(jnp.float32) / nm,
                                gacc, grads)
            return (gacc, lacc + loss / nm), None

        if nm > 1:
            mbs = jax.tree.map(
                lambda x: x.reshape(nm, x.shape[0] // nm, *x.shape[1:]), batch)
            g0 = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params)
            (grads, loss), _ = jax.lax.scan(micro, (g0, 0.0), mbs)
        else:
            (loss, metrics), grads = jax.value_and_grad(loss_fn, has_aux=True)(
                params, batch)
        # keep grads sharded like params
        grads = jax.lax.with_sharding_constraint(grads, p_specs)
        new_params, new_opt, gnorm = adamw.update(grads, opt_state, params,
                                                  lr=lr)
        return new_params, new_opt, {"loss": loss, "grad_norm": gnorm}

    opt_abs = jax.eval_shape(adamw.init, abstract_params)
    opt_specs = adamw.AdamWState(step=P(), mu=p_specs, nu=p_specs)

    in_shardings = (_named(minfo, p_specs), _named(minfo, opt_specs),
                    _named(minfo, b_specs))
    out_shardings = (_named(minfo, p_specs), _named(minfo, opt_specs),
                     _named(minfo, {"loss": P(), "grad_norm": P()}))

    fn = jax.jit(train_step, in_shardings=in_shardings,
                 out_shardings=out_shardings, donate_argnums=(0, 1))
    arg_specs = (abstract_params, opt_abs, batch_abs)
    return fn, arg_specs, in_shardings, out_shardings


# ---------------------------------------------------------------------------
# Prefill step
# ---------------------------------------------------------------------------
def make_prefill_step(cfg: ModelConfig, minfo: shd.MeshInfo,
                      shape: InputShape, *, capacity: Optional[int] = None):
    capacity = capacity or shape.seq_len
    B = shape.global_batch
    abstract_params = api.param_specs(cfg)
    p_specs = shd.param_specs(abstract_params, cfg, minfo, "infer")
    batch_abs = api.batch_specs(cfg, shape)
    b_specs = shd.batch_input_specs(batch_abs, minfo)
    cache_abs = jax.eval_shape(lambda: api.init_cache(cfg, B, capacity))
    c_specs = shd.cache_specs_tree(cache_abs, cfg, minfo, B, capacity)
    logits_spec = P(shd.batch_spec_axes(minfo, B), None)

    bspec = shd.batch_spec_axes(minfo, B)
    # sequence-parallel attention (§Perf): when neither KV-head TP nor q-TP
    # applies, shard the prefill q-block axis over 'model' instead of
    # replicating the attention compute.
    seq_axis = None
    if (not cfg.is_encoder_decoder and cfg.num_heads
            and not shd.attn_head_tp(cfg, minfo.model)
            and cfg.num_heads % minfo.model != 0
            and (shape.seq_len // 256) % minfo.model == 0):
        seq_axis = "model"

    tp = kernel_tp(cfg, minfo, bspec, c_specs)
    # under the kernels' layout each chip writes its own positions of the
    # prompt's K/V; otherwise GSPMD places the one write
    layout = (c_specs, minfo) if tp else ()

    def prefill_step(params, batch):
        cache = api.init_cache(cfg, B, capacity)
        logits, new = api.prefill_kv(params, batch, cfg, cache, bspec=bspec,
                                     seq_axis=seq_axis, tp=tp)
        return logits, cache_update.write(cache, new, 0, *layout)

    fn = jax.jit(prefill_step,
                 in_shardings=(_named(minfo, p_specs), _named(minfo, b_specs)),
                 out_shardings=(NamedSharding(minfo.mesh, logits_spec),
                                _named(minfo, c_specs)))
    return fn, (abstract_params, batch_abs), None, None


# ---------------------------------------------------------------------------
# Decode step (serve_step for decode shapes)
# ---------------------------------------------------------------------------
def make_decode_step(cfg: ModelConfig, minfo: shd.MeshInfo,
                     shape: InputShape, *, param_mode: str = "infer"):
    """param_mode='tp' is the beyond-paper §Perf variant: TP-only inference
    params (no per-layer FSDP all-gathers at decode)."""
    B, S = shape.global_batch, shape.seq_len
    abstract_params = api.param_specs(cfg)
    p_specs = shd.param_specs(abstract_params, cfg, minfo, param_mode)
    cache_abs = jax.eval_shape(lambda: api.init_cache(cfg, B, S))
    c_specs = shd.cache_specs_tree(cache_abs, cfg, minfo, B, S)
    tok_abs = jax.ShapeDtypeStruct((B,), jnp.int32)
    pos_abs = jax.ShapeDtypeStruct((), jnp.int32)
    tok_spec = P(shd.batch_spec_axes(minfo, B))
    logits_spec = P(shd.batch_spec_axes(minfo, B), None)

    bspec = shd.batch_spec_axes(minfo, B)
    tp = kernel_tp(cfg, minfo, bspec, c_specs)

    def decode(params, cache, tokens, pos):
        # append-outside-scan (§Perf): the cache is read-only inside the
        # layer scan; one O(token) write per group after it.
        logits, new = api.decode_kv(params, cache, tokens, pos, cfg,
                                    bspec=bspec, tp=tp)
        return logits, cache_update.write(cache, new, pos, c_specs, minfo)

    fn = jax.jit(
        decode,
        in_shardings=(_named(minfo, p_specs), _named(minfo, c_specs),
                      NamedSharding(minfo.mesh, tok_spec),
                      NamedSharding(minfo.mesh, P())),
        out_shardings=(NamedSharding(minfo.mesh, logits_spec),
                       _named(minfo, c_specs)),
        donate_argnums=(1,),
    )
    arg_specs = (abstract_params, cache_abs, tok_abs, pos_abs)
    return fn, arg_specs, None, None


def make_step(cfg: ModelConfig, minfo: shd.MeshInfo, shape: InputShape,
              **kw):
    if shape.kind == "train":
        return make_train_step(cfg, minfo, shape, **kw)
    if shape.kind == "prefill":
        return make_prefill_step(cfg, minfo, shape, **kw)
    return make_decode_step(cfg, minfo, shape, **kw)
