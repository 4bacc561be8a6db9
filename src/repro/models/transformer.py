"""Decoder-only transformer assembly (dense / MoE / SSM / hybrid / VLM).

A model is a sequence of layer *groups* (``cfg.layer_groups``); each group's
parameters are stacked on a leading axis and executed with ``lax.scan`` so
that 80-layer models lower to a compact HLO.  Three modes:

  train   — full-sequence forward, chunked cross-entropy loss
  prefill — full-sequence forward, returns last-position logits + KV cache
  decode  — one token against the cache (the serving hot path)
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax

from repro.configs.base import ATTN, SWA, MAMBA
from repro.models import layers as L
from repro.models import mamba as M
from repro.models import moe as MOE

Array = jax.Array


# ---------------------------------------------------------------------------
# Parameter init
# ---------------------------------------------------------------------------
def _init_dense_layer(rng, cfg, window_kind: str) -> dict:
    k1, k2 = jax.random.split(rng)
    p = {"attn": L.init_attn_block(k1, cfg)}
    if cfg.num_experts:
        p["moe"] = MOE.init_moe(k2, cfg)
    else:
        p["mlp"] = L.init_mlp(k2, cfg)
    return p


def _stack_init(init_fn, rng, count: int):
    keys = jax.random.split(rng, count)
    return jax.vmap(init_fn)(keys)


def init_group(rng, cfg, kind: str, count: int):
    if kind in (ATTN, SWA):
        return _stack_init(lambda k: _init_dense_layer(k, cfg, kind), rng, count)
    if kind == MAMBA:
        return _stack_init(lambda k: M.init_mamba_block(k, cfg), rng, count)
    if kind == "local_global":
        k1, k2 = jax.random.split(rng)
        return {
            "local": _stack_init(lambda k: _init_dense_layer(k, cfg, SWA), k1, count),
            "global": _stack_init(lambda k: _init_dense_layer(k, cfg, ATTN), k2, count),
        }
    if kind == "hybrid_super":
        k1, k2, k3 = jax.random.split(rng, 3)
        inner = cfg.hybrid_attn_every
        mamba = _stack_init(
            lambda k: _stack_init(lambda kk: M.init_mamba_block(kk, cfg), k, inner),
            k1, count)
        shared = {"attn": L.init_attn_block(k2, cfg), "mlp": L.init_mlp(k3, cfg)}
        return {"mamba": mamba, "shared": shared}
    raise ValueError(kind)


def init_params(rng, cfg) -> dict:
    dt = jnp.dtype(cfg.dtype)
    keys = jax.random.split(rng, len(cfg.layer_groups) + 3)
    params = {
        "embed": (jax.random.normal(keys[0], (cfg.vocab_size, cfg.d_model)) * 0.02
                  ).astype(dt),
        **L.init_norm(cfg, "final_norm"),
        "groups": [init_group(k, cfg, kind, count)
                   for k, (kind, count) in zip(keys[1:], cfg.layer_groups)],
    }
    if cfg.learned_positions:
        params["pos_embed"] = (jax.random.normal(
            jax.random.fold_in(keys[0], 1),
            (cfg.learned_positions, cfg.d_model)) * 0.02).astype(dt)
    if not cfg.tie_embeddings:
        params["lm_head"] = (jax.random.normal(keys[-2], (cfg.d_model, cfg.vocab_size))
                             * 0.02).astype(dt)
    if cfg.frontend == "vision_stub":
        params["vis_proj"] = (jax.random.normal(keys[-1], (cfg.d_model, cfg.d_model))
                              * 0.02).astype(dt)
    return params


# ---------------------------------------------------------------------------
# Layer application
# ---------------------------------------------------------------------------
def dense_layer_apply(lp, x, cfg, *, window, mode, kv=None, cache_pos=None,
                      positions=None, ring=False, seq_axis=None, tp=None,
                      layer=None):
    x, new_kv = L.attn_block_apply(
        lp["attn"], x, cfg, window=window, mode=mode, cache=kv,
        cache_pos=cache_pos, layer=layer, positions=positions, ring=ring,
        seq_axis=seq_axis, tp=tp)
    if "moe" in lp:
        x, aux = MOE.moe_block_apply(lp["moe"], x, cfg)
    else:
        x = L.mlp_apply(lp["mlp"], x, cfg)
        aux = jnp.zeros((), jnp.float32)
    return x, new_kv, aux


def _window(cfg, kind):
    if kind == SWA:
        return cfg.sliding_window
    return None


# ---------------------------------------------------------------------------
# Cache allocation (works under jax.eval_shape for the dry-run)
# ---------------------------------------------------------------------------
def _stacked_mamba_state(cfg, shape_prefix: tuple, batch: int, dt) -> dict:
    d_in, H, P, N = M.dims(cfg)
    conv_dim = d_in + 2 * N
    return {
        "ssm": jnp.zeros((*shape_prefix, batch, H, P, N), jnp.float32),
        "conv": jnp.zeros((*shape_prefix, batch, cfg.ssm_conv_width - 1, conv_dim), dt),
    }


def init_cache(cfg, batch: int, capacity: int, windowed: bool = False) -> list:
    """windowed=True (beyond-paper §Perf): sliding-window layers allocate
    only ``window`` slots (ring buffer) instead of the full context."""
    dt = jnp.dtype(cfg.dtype)
    KV, hd = cfg.num_kv_heads, cfg.head_dim
    wcap = capacity
    if windowed and cfg.sliding_window:
        wcap = min(capacity, cfg.sliding_window)
    caches = []
    for kind, count in cfg.layer_groups:
        if kind in (ATTN, SWA):
            cap = wcap if kind == SWA else capacity
            caches.append({
                "k": jnp.zeros((count, batch, KV, cap, hd), dt),
                "v": jnp.zeros((count, batch, KV, cap, hd), dt),
            })
        elif kind == "local_global":
            caches.append({
                "local": {"k": jnp.zeros((count, batch, KV, wcap, hd), dt),
                          "v": jnp.zeros((count, batch, KV, wcap, hd), dt)},
                "global": {"k": jnp.zeros((count, batch, KV, capacity, hd), dt),
                           "v": jnp.zeros((count, batch, KV, capacity, hd), dt)},
            })
        elif kind == MAMBA:
            caches.append(_stacked_mamba_state(cfg, (count,), batch, dt))
        elif kind == "hybrid_super":
            inner = cfg.hybrid_attn_every
            caches.append({
                "mamba": _stacked_mamba_state(cfg, (count, inner), batch, dt),
                "k": jnp.zeros((count, batch, KV, wcap, hd), dt),
                "v": jnp.zeros((count, batch, KV, wcap, hd), dt),
            })
        else:
            raise ValueError(kind)
    return caches


# ---------------------------------------------------------------------------
# Group execution — one function per mode to keep scan signatures simple.
# ---------------------------------------------------------------------------
def run_group_train(gp, x, cfg, kind, *, positions, remat=False, bspec=None):
    window = cfg.sliding_window

    if kind in (ATTN, SWA):
        def body(carry, lp):
            carry = L.constrain_batch(carry, bspec)
            y, _, aux = dense_layer_apply(lp, carry, cfg, window=_window(cfg, kind),
                                          mode="train", positions=positions)
            return y, aux
        if remat:
            body = jax.checkpoint(body)
        x, auxs = lax.scan(body, x, gp)
        return x, auxs.sum()

    if kind == "local_global":
        def body(carry, lp):
            carry = L.constrain_batch(carry, bspec)
            y, _, a1 = dense_layer_apply(lp["local"], carry, cfg, window=window,
                                         mode="train", positions=positions)
            y, _, a2 = dense_layer_apply(lp["global"], y, cfg, window=None,
                                         mode="train", positions=positions)
            return y, a1 + a2
        if remat:
            body = jax.checkpoint(body)
        x, auxs = lax.scan(body, x, gp)
        return x, auxs.sum()

    if kind == MAMBA:
        def body(carry, lp):
            carry = L.constrain_batch(carry, bspec)
            y, _ = M.mamba_block_apply(lp, carry, cfg, mode="train")
            return y, jnp.zeros((), jnp.float32)
        if remat:
            body = jax.checkpoint(body)
        x, auxs = lax.scan(body, x, gp)
        return x, auxs.sum()

    if kind == "hybrid_super":
        shared = gp["shared"]

        def body(carry, mp_stack):
            y = L.constrain_batch(carry, bspec)
            def inner(c, mp):
                out, _ = M.mamba_block_apply(mp, c, cfg, mode="train")
                return out, None
            y, _ = lax.scan(inner, y, mp_stack)
            y, _, _ = dense_layer_apply(shared, y, cfg, window=window,
                                        mode="train", positions=positions)
            return y, jnp.zeros((), jnp.float32)
        if remat:
            body = jax.checkpoint(body)
        x, auxs = lax.scan(body, x, gp["mamba"])
        return x, auxs.sum()

    raise ValueError(kind)


def run_group_prefill(gp, x, cfg, kind, cache, *, positions, cache_pos=0,
                      seq_axis=None, tp=None):
    """Forward with cache write-back at [cache_pos, cache_pos+T).
    tp: the Pallas kernels' tensor-parallel layout (``L.TensorParallel``)."""
    window = cfg.sliding_window
    T = x.shape[1]

    @L.named_scope("cache_update")
    def put(buf, kv):  # buf (count,B,KV,cap,hd); kv (count,B,T,KV,hd)
        kv = kv.transpose(0, 1, 3, 2, 4)         # -> (count,B,KV,T,hd)
        if tp is not None:  # each shard writes its own positions
            return tp.write(buf, kv, jnp.asarray(cache_pos, jnp.int32))
        return lax.dynamic_update_slice_in_dim(buf, kv.astype(buf.dtype),
                                               cache_pos, axis=3)

    if kind in (ATTN, SWA):
        def body(carry, lp):
            y, kv, aux = dense_layer_apply(lp, carry, cfg, window=_window(cfg, kind),
                                           mode="prefill", positions=positions,
                                           seq_axis=seq_axis, tp=tp)
            return y, (kv["k"], kv["v"], aux)
        with jax.named_scope("layers"):
            x, (ks, vs, auxs) = lax.scan(body, x, gp)
        new_cache = {"k": put(cache["k"], ks), "v": put(cache["v"], vs)}
        return x, new_cache, auxs.sum()

    if kind == "local_global":
        def body(carry, lp):
            y, kv_l, a1 = dense_layer_apply(lp["local"], carry, cfg, window=window,
                                            mode="prefill", positions=positions,
                                            seq_axis=seq_axis)
            y, kv_g, a2 = dense_layer_apply(lp["global"], y, cfg, window=None,
                                            mode="prefill", positions=positions,
                                            seq_axis=seq_axis)
            return y, (kv_l["k"], kv_l["v"], kv_g["k"], kv_g["v"], a1 + a2)
        with jax.named_scope("layers"):
            x, (kl, vl, kg, vg, auxs) = lax.scan(body, x, gp)
        new_cache = {
            "local": {"k": put(cache["local"]["k"], kl),
                      "v": put(cache["local"]["v"], vl)},
            "global": {"k": put(cache["global"]["k"], kg),
                       "v": put(cache["global"]["v"], vg)},
        }
        return x, new_cache, auxs.sum()

    if kind == MAMBA:
        def body(carry, inp):
            lp, st = inp
            y, new_st = M.mamba_block_apply(lp, carry, cfg, state=st, mode="prefill")
            return y, new_st
        with jax.named_scope("layers"):
            x, new_states = lax.scan(body, x, (gp, cache))
        return x, new_states, jnp.zeros((), jnp.float32)

    if kind == "hybrid_super":
        shared = gp["shared"]

        def body(carry, inp):
            mp_stack, mstates = inp
            y = carry
            def inner(c, si):
                mp, st = si
                out, new_st = M.mamba_block_apply(mp, c, cfg, state=st, mode="prefill")
                return out, new_st
            y, new_mstates = lax.scan(inner, y, (mp_stack, mstates))
            y, kv, _ = dense_layer_apply(shared, y, cfg, window=window,
                                         mode="prefill", positions=positions)
            return y, (new_mstates, kv["k"], kv["v"])
        with jax.named_scope("layers"):
            x, (new_m, ks, vs) = lax.scan(body, x, (gp["mamba"],
                                                    cache["mamba"]))
        new_cache = {"mamba": new_m, "k": put(cache["k"], ks),
                     "v": put(cache["v"], vs)}
        return x, new_cache, jnp.zeros((), jnp.float32)

    raise ValueError(kind)


def run_group_decode(gp, x, cfg, kind, cache, *, pos, windowed=False,
                     return_deltas=False, tp=None):
    """One-token step.  pos: scalar int32 — index where the new token lands.
    windowed=True: sliding-window layers use ring-buffer caches.
    tp: the Pallas kernels' tensor-parallel layout (``L.TensorParallel``).

    Attention bodies read the cache and emit (k_new, v_new) deltas; the cache
    is written back with ONE stacked dynamic-update-slice per group after the
    layer scan (append-outside-scan, §Perf — a per-layer in-scan update
    rewrites the full per-layer cache every layer).  The Pallas decode
    kernel reads each layer out of the stacked cache itself, so for it the
    scan runs over the layer indices and nothing slices the cache."""
    window = cfg.sliding_window
    positions = pos[None] if pos.ndim == 0 else pos

    @L.named_scope("cache_update")
    def put(buf, delta, ring):
        # buf (count,B,KV,cap,hd); delta (count,B,KV,1,hd)
        if return_deltas:
            return delta        # caller applies a sharded append (§Perf)
        cap = buf.shape[3]
        slot = (pos % cap) if ring else pos
        return lax.dynamic_update_slice_in_dim(buf, delta.astype(buf.dtype),
                                               slot, axis=3)

    if kind in (ATTN, SWA):
        ring = windowed and kind == SWA
        in_place = cfg.kernel_impl == "pallas" and not ring
        def body(carry, inp):
            if in_place:
                (lp, layer), kv = inp, cache
            else:
                (lp, k_l, v_l), layer = inp, None
                kv = {"k": k_l, "v": v_l}
            y, kv, _ = dense_layer_apply(lp, carry, cfg, window=_window(cfg, kind),
                                         mode="decode", kv=kv, cache_pos=pos,
                                         positions=positions, ring=ring, tp=tp,
                                         layer=layer)
            return y, (kv["k"], kv["v"])
        xs = ((gp, jnp.arange(cache["k"].shape[0], dtype=jnp.int32))
              if in_place else (gp, cache["k"], cache["v"]))
        with jax.named_scope("layers"):
            x, (dk, dv) = lax.scan(body, x, xs)
        return x, {"k": put(cache["k"], dk, ring), "v": put(cache["v"], dv, ring)}

    if kind == "local_global":
        def body(carry, inp):
            lp, kl, vl, kg, vg = inp
            y, kv_l, _ = dense_layer_apply(lp["local"], carry, cfg, window=window,
                                           mode="decode", kv={"k": kl, "v": vl},
                                           cache_pos=pos, positions=positions,
                                           ring=windowed)
            y, kv_g, _ = dense_layer_apply(lp["global"], y, cfg, window=None,
                                           mode="decode", kv={"k": kg, "v": vg},
                                           cache_pos=pos, positions=positions)
            return y, (kv_l["k"], kv_l["v"], kv_g["k"], kv_g["v"])
        with jax.named_scope("layers"):
            x, (dkl, dvl, dkg, dvg) = lax.scan(
                body, x, (gp, cache["local"]["k"], cache["local"]["v"],
                          cache["global"]["k"], cache["global"]["v"]))
        return x, {
            "local": {"k": put(cache["local"]["k"], dkl, windowed),
                      "v": put(cache["local"]["v"], dvl, windowed)},
            "global": {"k": put(cache["global"]["k"], dkg, False),
                       "v": put(cache["global"]["v"], dvg, False)},
        }

    if kind == MAMBA:
        def body(carry, inp):
            lp, st = inp
            y, new_st = M.mamba_block_apply(lp, carry, cfg, state=st, mode="decode")
            return y, new_st
        with jax.named_scope("layers"):
            x, new_states = lax.scan(body, x, (gp, cache))
        return x, new_states

    if kind == "hybrid_super":
        shared = gp["shared"]

        def body(carry, inp):
            mp_stack, mstates, k_l, v_l = inp
            y = carry
            def inner(c, si):
                mp, st = si
                out, new_st = M.mamba_block_apply(mp, c, cfg, state=st, mode="decode")
                return out, new_st
            y, new_mstates = lax.scan(inner, y, (mp_stack, mstates))
            y, kv, _ = dense_layer_apply(shared, y, cfg, window=window,
                                         mode="decode", kv={"k": k_l, "v": v_l},
                                         cache_pos=pos, positions=positions,
                                         ring=windowed)
            return y, (new_mstates, kv["k"], kv["v"])
        with jax.named_scope("layers"):
            x, (new_m, dk, dv) = lax.scan(body, x, (gp["mamba"],
                                                    cache["mamba"],
                                                    cache["k"], cache["v"]))
        return x, {"mamba": new_m, "k": put(cache["k"], dk, windowed),
                   "v": put(cache["v"], dv, windowed)}

    raise ValueError(kind)


# ---------------------------------------------------------------------------
# Embedding / head / loss
# ---------------------------------------------------------------------------
@L.named_scope("embed")
def embed_tokens(params, tokens, cfg, patch_embeds=None, positions=None):
    """positions: the tokens' absolute positions (T,), for a learned
    position table (``cfg.learned_positions``)."""
    x = params["embed"][tokens].astype(jnp.dtype(cfg.dtype))
    if cfg.scale_embeddings:
        x = x * jnp.asarray(cfg.d_model ** 0.5, x.dtype)
    if patch_embeds is not None:
        pe = patch_embeds.astype(x.dtype)
        if "vis_proj" in params:
            pe = pe @ params["vis_proj"]
        x = jnp.concatenate([pe, x], axis=1)
    if cfg.learned_positions:
        x = x + params["pos_embed"][positions].astype(x.dtype)
    return x


def head_matrix(params, cfg):
    if cfg.tie_embeddings:
        return params["embed"].T            # (d, V)
    return params["lm_head"]


def logits_last(params, h_last, cfg):
    """h_last: (B, d) -> (B, V) float32 logits (with final softcap)."""
    w = head_matrix(params, cfg)
    out = jnp.einsum("bd,dv->bv", h_last, w, preferred_element_type=jnp.float32)
    return L.softcap(out, cfg.final_logit_softcap)


def chunked_ce_loss(params, h, labels, mask, cfg, chunk: int = 512):
    """Cross-entropy over (B,T) without materializing (B,T,V) logits."""
    B, T, d = h.shape
    w = head_matrix(params, cfg)
    chunk = min(chunk, T)
    pad = (-T) % chunk
    if pad:
        h = jnp.pad(h, ((0, 0), (0, pad), (0, 0)))
        labels = jnp.pad(labels, ((0, 0), (0, pad)))
        mask = jnp.pad(mask, ((0, 0), (0, pad)))
    nch = h.shape[1] // chunk
    hc = h.reshape(B, nch, chunk, d).transpose(1, 0, 2, 3)
    lc = labels.reshape(B, nch, chunk).transpose(1, 0, 2)
    mc = mask.reshape(B, nch, chunk).transpose(1, 0, 2)

    @jax.checkpoint  # recompute per-chunk logits in the backward pass
    def per_chunk(args):
        hh, ll, mm = args
        logits = jnp.einsum("btd,dv->btv", hh, w,
                            preferred_element_type=jnp.float32)
        logits = L.softcap(logits, cfg.final_logit_softcap)
        lse = jax.nn.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, ll[..., None], axis=-1)[..., 0]
        return jnp.sum((lse - gold) * mm)

    losses = lax.map(per_chunk, (hc, lc, mc))
    return losses.sum() / jnp.maximum(mask.sum(), 1.0)


# ---------------------------------------------------------------------------
# Public entry points
# ---------------------------------------------------------------------------
def forward_full(params, x, cfg, *, mode, positions, remat=False, bspec=None):
    """Train-mode trunk: embeddings -> groups -> final norm."""
    aux_total = jnp.zeros((), jnp.float32)
    for gp, (kind, count) in zip(params["groups"], cfg.layer_groups):
        x, aux = run_group_train(gp, x, cfg, kind, positions=positions,
                                 remat=remat, bspec=bspec)
        aux_total = aux_total + aux
    x = L.norm(params, "final_norm", x, cfg)
    return x, aux_total


def train_loss(params, batch, cfg, *, remat=True, bspec=None):
    """batch: {'tokens': (B,T) int32, optional 'patch_embeds': (B,P,d)}.

    Loss over next-token prediction on the text region.
    """
    tokens = batch["tokens"]
    patches = batch.get("patch_embeds")
    T = tokens.shape[1] + (0 if patches is None else patches.shape[1])
    positions = jnp.arange(T)
    x = L.constrain_batch(embed_tokens(params, tokens, cfg, patch_embeds=patches,
                                       positions=positions), bspec)
    h, aux = forward_full(params, x, cfg, mode="train", positions=positions,
                          remat=remat, bspec=bspec)
    n_text = tokens.shape[1]
    h_text = L.constrain_batch(h[:, T - n_text:], bspec)
    labels = jnp.roll(tokens, -1, axis=1)
    mask = jnp.ones_like(tokens, jnp.float32).at[:, -1].set(0.0)
    ce = chunked_ce_loss(params, h_text, labels, mask, cfg)
    loss = ce + cfg.router_aux_loss_coef * aux
    return loss, {"ce": ce, "aux": aux}


def prefill(params, batch, cfg, capacity: int, bspec=None, seq_axis=None,
            tp=None):
    """Returns (last_logits (B,V) f32, cache) with cache capacity ``capacity``."""
    tokens = batch["tokens"]
    patches = batch.get("patch_embeds")
    B = tokens.shape[0]
    T = tokens.shape[1] + (0 if patches is None else patches.shape[1])
    positions = jnp.arange(T)
    x = L.constrain_batch(embed_tokens(params, tokens, cfg, patch_embeds=patches,
                                       positions=positions), bspec)
    cache = init_cache(cfg, B, capacity)
    new_cache = []
    for gp, c, (kind, count) in zip(params["groups"], cache, cfg.layer_groups):
        x, nc, _ = run_group_prefill(gp, x, cfg, kind, c, positions=positions,
                                     seq_axis=seq_axis, tp=tp)
        new_cache.append(nc)
    with jax.named_scope("logits"):
        x = L.norm(params, "final_norm", x, cfg)
        logits = logits_last(params, x[:, -1], cfg)
    return logits, new_cache


def decode_step(params, cache, tokens, pos, cfg, bspec=None, windowed=False,
                return_deltas=False, tp=None):
    """tokens: (B,) int32 new token ids; pos: scalar int32 slot index.

    Returns (logits (B,V) f32, new_cache) — or, with return_deltas, the
    per-group K/V deltas for a sharded append (distributed.cache_update)."""
    x = L.constrain_batch(embed_tokens(params, tokens[:, None], cfg,
                                       positions=pos[None]), bspec)
    new_cache = []
    for gp, c, (kind, count) in zip(params["groups"], cache, cfg.layer_groups):
        x, nc = run_group_decode(gp, x, cfg, kind, c, pos=pos, windowed=windowed,
                                 return_deltas=return_deltas, tp=tp)
        new_cache.append(nc)
    with jax.named_scope("logits"):
        x = L.norm(params, "final_norm", x, cfg)
        logits = logits_last(params, x[:, 0], cfg)
    return logits, new_cache
