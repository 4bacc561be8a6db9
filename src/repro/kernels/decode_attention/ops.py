"""Jit'd public wrapper for the decode-attention Pallas kernel.

``block_k=None`` consults the autotune cache (``repro.perf.autotune``)
for the best-known tiling of this (shape-class, dtype, backend); an empty
cache falls back to the historical 256 default.  Explicit kwargs win.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels.decode_attention.decode_attention import decode_attention_fwd
from repro.kernels.decode_attention.paged_decode_attention import \
    paged_decode_attention_fwd
from repro.perf import autotune


def _on_cpu() -> bool:
    return jax.default_backend() == "cpu"


DEFAULT_BLOCK_K = autotune.DEFAULTS["decode_attention"]["block_k"]
DEFAULT_PAGE_SIZE = autotune.DEFAULTS["paged_decode_attention"]["page_size"]


def _resolve_block_k(block_k: Optional[int], dtype, BKV: int, G: int,
                     hd: int, S: int) -> int:
    if block_k is not None:
        return block_k
    cfg = autotune.lookup("decode_attention", dtype, BKV=BKV, G=G, hd=hd, S=S)
    return cfg["block_k"] if cfg else DEFAULT_BLOCK_K


def decode_attention(
    q: jax.Array,        # (B, H, hd)
    k_cache: jax.Array,  # (B, S, KV, hd)
    v_cache: jax.Array,  # (B, S, KV, hd)
    pos,                 # scalar int32
    *,
    window: Optional[int] = None,
    logit_cap: Optional[float] = None,
    block_k: Optional[int] = None,
    interpret: Optional[bool] = None,
) -> jax.Array:
    block_k = _resolve_block_k(block_k, q.dtype,
                               q.shape[0] * k_cache.shape[2],
                               q.shape[1] // k_cache.shape[2], q.shape[2],
                               k_cache.shape[1])
    if interpret is None:
        interpret = _on_cpu()
    return _decode_attention(q, k_cache, v_cache, pos, window=window,
                             logit_cap=logit_cap, block_k=block_k,
                             interpret=interpret)


@functools.partial(
    jax.jit,
    static_argnames=("window", "logit_cap", "block_k", "interpret"))
def _decode_attention(
    q: jax.Array,
    k_cache: jax.Array,
    v_cache: jax.Array,
    pos,
    *,
    window: Optional[int],
    logit_cap: Optional[float],
    block_k: int,
    interpret: bool,
) -> jax.Array:
    B, H, hd = q.shape
    _, S, KV, _ = k_cache.shape
    G = H // KV

    block_k = min(block_k, S)
    pad = (-S) % block_k
    if pad:
        k_cache = jnp.pad(k_cache, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v_cache = jnp.pad(v_cache, ((0, 0), (0, pad), (0, 0), (0, 0)))
    Sp = k_cache.shape[1]

    q3 = q.reshape(B, KV, G, hd).reshape(B * KV, G, hd)
    k3 = k_cache.transpose(0, 2, 1, 3).reshape(B * KV, Sp, hd)
    v3 = v_cache.transpose(0, 2, 1, 3).reshape(B * KV, Sp, hd)
    pos_arr = jnp.asarray(pos, jnp.int32).reshape(1)

    out = decode_attention_fwd(q3, k3, v3, pos_arr, window=window,
                               logit_cap=logit_cap, block_k=block_k,
                               interpret=interpret)
    return out.reshape(B, KV, G, hd).reshape(B, H, hd)


def decode_attention_kvmajor(
    q: jax.Array,        # (B, H, hd)
    k_cache: jax.Array,  # (B, KV, S, hd) — the model's attention-native layout
    v_cache: jax.Array,
    pos,
    *,
    window=None,
    logit_cap=None,
    block_k: Optional[int] = None,
    interpret=None,
):
    """Like decode_attention but takes the (B, KV, S, hd) cache layout the
    model uses — a pure reshape, no transpose."""
    block_k = _resolve_block_k(block_k, q.dtype,
                               q.shape[0] * k_cache.shape[1],
                               q.shape[1] // k_cache.shape[1], q.shape[2],
                               k_cache.shape[2])
    if interpret is None:
        interpret = _on_cpu()
    return _decode_attention_kvmajor(q, k_cache, v_cache, pos, window=window,
                                     logit_cap=logit_cap, block_k=block_k,
                                     interpret=interpret)


@functools.partial(
    jax.jit, static_argnames=("window", "logit_cap", "block_k", "interpret"))
def _decode_attention_kvmajor(
    q: jax.Array,
    k_cache: jax.Array,
    v_cache: jax.Array,
    pos,
    *,
    window,
    logit_cap,
    block_k: int,
    interpret: bool,
):
    B, H, hd = q.shape
    _, KV, S, _ = k_cache.shape
    G = H // KV
    block_k = min(block_k, S)
    pad = (-S) % block_k
    if pad:
        k_cache = jnp.pad(k_cache, ((0, 0), (0, 0), (0, pad), (0, 0)))
        v_cache = jnp.pad(v_cache, ((0, 0), (0, 0), (0, pad), (0, 0)))
    Sp = k_cache.shape[2]
    q3 = q.reshape(B * KV, G, hd)
    k3 = k_cache.reshape(B * KV, Sp, hd)
    v3 = v_cache.reshape(B * KV, Sp, hd)
    pos_arr = jnp.asarray(pos, jnp.int32).reshape(1)
    out = decode_attention_fwd(q3, k3, v3, pos_arr, window=window,
                               logit_cap=logit_cap, block_k=block_k,
                               interpret=interpret)
    return out.reshape(B, H, hd)


def resolve_page_size(dtype, *, B: int, H: int, KV: int, hd: int,
                      seq_budget: int,
                      page_size: Optional[int] = None) -> int:
    """Page size for a paged KV cache serving this geometry.

    Unlike ``block_k`` (a tiling knob over fixed inputs), the page size
    changes the cache LAYOUT, so it is resolved once at cache-construction
    time: explicit wins, else the autotune cache's best-known page size for
    the shape class, else the historical default."""
    if page_size is not None:
        return page_size
    cfg = autotune.lookup("paged_decode_attention", dtype, BKV=B * KV,
                          G=H // KV, hd=hd, S=seq_budget)
    return cfg["page_size"] if cfg else DEFAULT_PAGE_SIZE


def paged_decode_attention(
    q: jax.Array,            # (B, H, hd) — one new token per live slot
    k_pages: jax.Array,      # (P, page_size, KV, hd) — shared page pool
    v_pages: jax.Array,      # (P, page_size, KV, hd)
    kv_lens,                 # (B,) int32 — valid cache length per slot
    block_tables,            # (B, ns) int32 — physical page ids per slot
    *,
    window: Optional[int] = None,
    logit_cap: Optional[float] = None,
    interpret: Optional[bool] = None,
) -> jax.Array:
    """Decode attention over a PAGED ragged-batch KV cache (the token
    engine's layout).  Slot ``b`` attends over ``kv_lens[b]`` keys read
    from pages ``block_tables[b, :]`` of the shared pool; slots at
    different sequence positions share one batch, and a freed slot
    (``kv_lens[b] == 0``) returns zeros.  Validated against
    ``ref.decode_attention_ref_ragged``."""
    if interpret is None:
        interpret = _on_cpu()
    return _paged_decode_attention(q, k_pages, v_pages, kv_lens,
                                   block_tables, window=window,
                                   logit_cap=logit_cap, interpret=interpret)


@functools.partial(
    jax.jit, static_argnames=("window", "logit_cap", "interpret"))
def _paged_decode_attention(
    q: jax.Array,
    k_pages: jax.Array,
    v_pages: jax.Array,
    kv_lens,
    block_tables,
    *,
    window: Optional[int],
    logit_cap: Optional[float],
    interpret: bool,
) -> jax.Array:
    B, H, hd = q.shape
    P, psz, KV, _ = k_pages.shape
    G = H // KV
    ns = block_tables.shape[1]

    lens = jnp.asarray(kv_lens, jnp.int32)
    tbl = jnp.asarray(block_tables, jnp.int32)
    # table entries past a slot's length are never read (pl.when skips the
    # page) but their index still reaches the BlockSpec index_map — clamp
    # padding entries into the pool so the prefetch address is always valid
    pages_needed = (lens[:, None] + psz - 1) // psz
    tbl = jnp.where(jnp.arange(ns)[None, :] < pages_needed, tbl, 0)

    # fold KV heads into the page axis (same fold as the dense wrapper):
    # pool page p of kv head k lives at row k*P + p
    q3 = q.reshape(B, KV, G, hd).reshape(B * KV, G, hd)
    k3 = k_pages.transpose(2, 0, 1, 3).reshape(KV * P, psz, hd)
    v3 = v_pages.transpose(2, 0, 1, 3).reshape(KV * P, psz, hd)
    tbl3 = (tbl[:, None, :]
            + (jnp.arange(KV, dtype=jnp.int32) * P)[None, :, None])
    tbl3 = tbl3.reshape(B * KV, ns)
    lens3 = jnp.broadcast_to(lens[:, None], (B, KV)).reshape(B * KV)

    out = paged_decode_attention_fwd(q3, k3, v3, lens3, tbl3, window=window,
                                     logit_cap=logit_cap, interpret=interpret)
    return out.reshape(B, KV, G, hd).reshape(B, H, hd)
