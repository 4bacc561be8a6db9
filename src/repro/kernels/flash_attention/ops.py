"""Jit'd public wrapper for the flash-attention Pallas kernel.

Accepts model-layout tensors (B, T, H, hd) / (B, S, KV, hd), handles GQA
folding, padding to block multiples (pad keys are masked via a static
``kv_len``, so non-divisible lengths work for causal AND non-causal
attention), and interpret-mode selection (CPU).

Tile sizes: explicit ``block_q``/``block_k`` kwargs always win; when left
None the autotune cache (``repro.perf.autotune``) supplies the best-known
tiling for this (shape-class, dtype, backend), falling back to the
historical 128/128 defaults on a cache miss.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels.flash_attention.flash_attention import flash_attention_fwd
from repro.perf import autotune


def _on_cpu() -> bool:
    return jax.default_backend() == "cpu"


DEFAULT_BLOCK_Q = autotune.DEFAULTS["flash_attention"]["block_q"]
DEFAULT_BLOCK_K = autotune.DEFAULTS["flash_attention"]["block_k"]


def flash_attention(
    q: jax.Array,                # (B, Tq, H, hd)
    k: jax.Array,                # (B, Tk, KV, hd)
    v: jax.Array,                # (B, Tk, KV, hd)
    *,
    causal: bool = True,
    window: Optional[int] = None,
    logit_cap: Optional[float] = None,
    q_offset: int = 0,
    block_q: Optional[int] = None,
    block_k: Optional[int] = None,
    interpret: Optional[bool] = None,
) -> jax.Array:
    if block_q is None or block_k is None:
        cfg = autotune.lookup(
            "flash_attention", q.dtype, BKV=q.shape[0] * k.shape[2],
            G=q.shape[2] // k.shape[2], hd=q.shape[3],
            Tq=q.shape[1], Tk=k.shape[1], causal=causal)
        if block_q is None:
            block_q = cfg["block_q"] if cfg else DEFAULT_BLOCK_Q
        if block_k is None:
            block_k = cfg["block_k"] if cfg else DEFAULT_BLOCK_K
    if interpret is None:
        interpret = _on_cpu()
    return _flash_attention(q, k, v, causal=causal, window=window,
                            logit_cap=logit_cap, q_offset=q_offset,
                            block_q=block_q, block_k=block_k,
                            interpret=interpret)


@functools.partial(
    jax.jit,
    static_argnames=("causal", "window", "logit_cap", "q_offset",
                     "block_q", "block_k", "interpret"))
def _flash_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool,
    window: Optional[int],
    logit_cap: Optional[float],
    q_offset: int,
    block_q: int,
    block_k: int,
    interpret: bool,
) -> jax.Array:
    B, Tq, H, hd = q.shape
    _, Tk, KV, _ = k.shape
    G = H // KV

    block_q = min(block_q, Tq) if Tq >= 8 else Tq
    block_k = min(block_k, Tk) if Tk >= 8 else Tk

    pad_q = (-Tq) % block_q
    pad_k = (-Tk) % block_k
    qp = jnp.pad(q, ((0, 0), (0, pad_q), (0, 0), (0, 0))) if pad_q else q
    kp = jnp.pad(k, ((0, 0), (0, pad_k), (0, 0), (0, 0))) if pad_k else k
    vp = jnp.pad(v, ((0, 0), (0, pad_k), (0, 0), (0, 0))) if pad_k else v
    Tqp, Tkp = qp.shape[1], kp.shape[1]

    # (B, T, KV, G, hd) -> (B*KV, G, T, hd)
    q4 = qp.reshape(B, Tqp, KV, G, hd).transpose(0, 2, 3, 1, 4).reshape(
        B * KV, G, Tqp, hd)
    k3 = kp.transpose(0, 2, 1, 3).reshape(B * KV, Tkp, hd)
    v3 = vp.transpose(0, 2, 1, 3).reshape(B * KV, Tkp, hd)

    # Padded K positions are masked inside the kernel via the static
    # `kv_len`: pad keys get position >= Tk and a NEG_INF logit, which the
    # online softmax then ignores — correct for causal and non-causal alike
    # (causal alone also guards them when q_offset + Tq <= Tk).
    out = flash_attention_fwd(
        q4, k3, v3, causal=causal, window=window, logit_cap=logit_cap,
        q_offset=q_offset, block_q=block_q, block_k=block_k,
        interpret=interpret, kv_len=Tk if pad_k else None)
    out = out.reshape(B, KV, G, Tqp, hd).transpose(0, 3, 1, 2, 4).reshape(
        B, Tqp, H, hd)
    return out[:, :Tq]
