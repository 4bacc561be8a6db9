"""Jit'd public wrappers for the decode-attention Pallas kernels.

The dense kernel's tiling comes from the operand shapes
(``decode_tiling``).  ``rows=None, block_k=None`` first consults the
autotune cache (``repro.perf.autotune``) for a tiling of this (shape-class,
dtype, backend); an explicit value wins and the other is derived around it.
"""

from __future__ import annotations

import functools
import math
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.kernels.decode_attention.decode_attention import decode_attention_fwd
from repro.kernels.decode_attention.paged_decode_attention import \
    paged_decode_attention_fwd
from repro.perf import autotune


def _on_cpu() -> bool:
    return jax.default_backend() == "cpu"


DEFAULT_PAGE_SIZE = autotune.DEFAULTS["paged_decode_attention"]["page_size"]

# A grid step has a fixed cost in the Pallas pipeline (about 0.4 us on a
# TPU v5e), so each step should move enough K+V to hide it behind the HBM
# transfer.  Rows come first: a larger block_k reads more dead positions
# past ``pos``, a larger row block reads none.
STEP_BYTES = 2 << 20        # K+V bytes a step aims to move
MAX_BLOCK_K = 512           # block_k grows past this only when rows cannot


class DecodeTiling(NamedTuple):
    rows: int          # (batch x KV-head) rows per grid step; divides BKV
    block_k: int       # cache positions per grid step; divides S
    steps: int         # grid steps per call
    step_bytes: int    # K+V bytes one step moves
    vmem_bytes: int    # VMEM of one step: double-buffered blocks, scratch
                       # and the f32 scores, padded to (sublane, 128) tiles


def _divisors(n: int) -> list:
    small = [d for d in range(1, math.isqrt(n) + 1) if n % d == 0]
    return sorted({*small, *(n // d for d in small)})


def _round_up(n: int, m: int) -> int:
    return -(-n // m) * m


def _sublanes(dtype) -> int:
    """Rows of one (sublane, 128-lane) VMEM or HBM tile of ``dtype``."""
    return 8 * max(1, 4 // jnp.dtype(dtype).itemsize)


def cache_is_s_minor(S: int, hd: int, dtype) -> bool:
    """Whether a TPU lays a (..., S, hd) cache out with its positions minor.
    Of the two minor orders the TPU takes the one whose (sublane, 128)
    tiles pad less, hd minor on a tie: hd 64 lies S-minor, hd 128 hd-minor.
    The kernel reads the view that matches, so no relayout is made."""
    sub = _sublanes(dtype)
    return (_round_up(hd, sub) * _round_up(S, 128)
            < _round_up(S, sub) * _round_up(hd, 128))


def decode_tiling(BKV: int, S: int, G: int, hd: int, dtype, *,
                  rows: Optional[int] = None,
                  block_k: Optional[int] = None) -> DecodeTiling:
    """Block sizes of the decode kernel for (BKV, S, hd) caches and G query
    heads per KV head.  ``rows`` divides BKV and ``block_k`` divides S, so
    the cache is never padded; a given value is lowered to the nearest
    that does.  Otherwise block_k is the largest legal tile up to
    MAX_BLOCK_K, then rows the fewest that move STEP_BYTES within the
    autotuner's VMEM budget; when all BKV rows move less, block_k grows
    towards S.  A legal tile is whole (sublane, 128) tiles of the cache's
    layout (``cache_is_s_minor``), or the whole of S."""
    sz = jnp.dtype(dtype).itemsize
    budget = autotune.VMEM_BYTES
    sub = _sublanes(dtype)
    lanes = _round_up(hd, 128)                # hd 64 fills half the lanes
    s_minor = cache_is_s_minor(S, hd, dtype)

    def step_bytes(r, bk):
        return 2 * r * bk * hd * sz

    def vmem(r, bk):
        kv = (_round_up(hd, sub) * _round_up(bk, 128) if s_minor
              else _round_up(bk, sub) * lanes)
        rows_io = (2 * _round_up(G, sub) + 2 * sub) * lanes  # q, o, new K/V
        blocks = 2 * r * (2 * kv + rows_io) * sz      # two buffers each
        f32 = 4 * r * _round_up(G, 8) * (2 * 128 + lanes
                                         + 3 * _round_up(bk, 128))
        return blocks + f32

    unit = 128 if s_minor else sub
    tiles = [d for d in _divisors(S) if d % unit == 0] or [S]
    if block_k is not None:
        bk = max([d for d in tiles if d <= block_k] or [min(tiles)])
    else:
        bk = max([d for d in tiles if d <= MAX_BLOCK_K] or [min(tiles)])
    if rows is not None:
        r = max(d for d in _divisors(BKV) if d <= max(rows, 1))
    else:
        fits = [d for d in _divisors(BKV) if vmem(d, bk) <= budget] or [1]
        r = next((d for d in fits if step_bytes(d, bk) >= STEP_BYTES),
                 fits[-1])
        if block_k is None and r == BKV and step_bytes(r, bk) < STEP_BYTES:
            for d in tiles:
                if d > bk and vmem(r, d) <= budget:
                    bk = d
                    if step_bytes(r, d) >= STEP_BYTES:
                        break
    return DecodeTiling(r, bk, (BKV // r) * (S // bk), step_bytes(r, bk),
                        vmem(r, bk))


def _resolve_tiling(rows, block_k, dtype, BKV, G, hd, S) -> DecodeTiling:
    if rows is None and block_k is None:
        cfg = autotune.lookup("decode_attention", dtype, BKV=BKV, G=G, hd=hd,
                              S=S) or {}
        rows, block_k = cfg.get("rows"), cfg.get("block_k")
    return decode_tiling(BKV, S, G, hd, dtype, rows=rows, block_k=block_k)


def decode_attention(
    q: jax.Array,        # (B, H, hd)
    k_cache: jax.Array,  # (B, S, KV, hd)
    v_cache: jax.Array,  # (B, S, KV, hd)
    pos,                 # scalar int32
    **kw,
) -> jax.Array:
    """``decode_attention_kvmajor`` over a (B, S, KV, hd) cache."""
    return decode_attention_kvmajor(q, k_cache.transpose(0, 2, 1, 3),
                                    v_cache.transpose(0, 2, 1, 3), pos, **kw)


def decode_attention_kvmajor(
    q: jax.Array,        # (B, H, hd)
    k_cache: jax.Array,  # (B, KV, S, hd) — the model's attention-native layout
    v_cache: jax.Array,
    pos,
    **kw,
):
    """``decode_attention_stacked`` over one layer's cache."""
    return decode_attention_stacked(q, k_cache[None], v_cache[None], pos, 0,
                                    **kw)


def decode_attention_stacked(
    q: jax.Array,        # (B, H, hd)
    k_stack: jax.Array,  # (L, B, KV, S, hd): a layer group's stacked cache
    v_stack: jax.Array,
    pos,                 # scalar int32: the new token's position
    layer,               # scalar int32: the layer of the stack to read
    *,
    k_new: Optional[jax.Array] = None,   # (B, KV, 1, hd)
    v_new: Optional[jax.Array] = None,
    window=None,
    logit_cap=None,
    rows: Optional[int] = None,
    block_k: Optional[int] = None,
    interpret=None,
    stats: bool = False,
):
    """Attention of one new token per sequence over layer ``layer`` of a
    stacked cache, which the kernel reads in place.  With ``k_new`` and
    ``v_new`` the new token's K/V come apart and the cache's slot ``pos``
    is not read (it is written after the step); without them the cache
    holds that slot.

    ``pos`` may lie outside [0, S): one shard of a cache split by
    positions, with the token's own position counted from the shard's
    first.  With ``stats`` returns (o, m, l): m, l (B, H) float32 are the
    softmax's running max of the scaled scores and its sum of exp(score -
    m), with m = -inf and l = 0 (and o = 0) where no position is live."""
    t = _resolve_tiling(rows, block_k, q.dtype, q.shape[0] * k_stack.shape[2],
                        q.shape[1] // k_stack.shape[2], q.shape[2],
                        k_stack.shape[3])
    if interpret is None:
        interpret = _on_cpu()
    return _decode_attention_stacked(
        q, k_stack, v_stack, pos, layer, k_new, v_new, window=window,
        logit_cap=logit_cap, tiling=t, interpret=interpret, stats=stats)


@functools.partial(
    jax.jit, static_argnames=("window", "logit_cap", "tiling", "interpret",
                              "stats"))
def _decode_attention_stacked(q, k_stack, v_stack, pos, layer, k_new, v_new,
                              *, window, logit_cap, tiling: DecodeTiling,
                              interpret: bool, stats: bool):
    B, H, hd = q.shape
    L, _, KV, S, _ = k_stack.shape
    G = H // KV
    s_minor = cache_is_s_minor(S, hd, k_stack.dtype)

    def view(c):                  # the bytes as the chip lays them out
        c = jnp.swapaxes(c, 3, 4) if s_minor else c
        return c.reshape(L, B * KV, *c.shape[3:])

    new = () if k_new is None else (k_new.reshape(B * KV, 1, hd),
                                    v_new.reshape(B * KV, 1, hd))
    out = decode_attention_fwd(
        q.reshape(B * KV, G, hd), view(k_stack), view(v_stack),
        jnp.asarray(pos, jnp.int32).reshape(1),
        jnp.asarray(layer, jnp.int32).reshape(1), *new, s_minor=s_minor,
        window=window, logit_cap=logit_cap, rows=tiling.rows,
        block_k=tiling.block_k,
        vmem_limit_bytes=max(autotune.VMEM_BYTES, 2 * tiling.vmem_bytes),
        interpret=interpret, stats=stats)
    if not stats:
        return out.reshape(B, H, hd)
    out, st = out
    m, l = st[..., 0].reshape(B, H), st[..., 1].reshape(B, H)
    return out.reshape(B, H, hd), jnp.where(l > 0, m, -jnp.inf), l


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
