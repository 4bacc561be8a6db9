"""Pallas TPU flash-attention forward kernel (blockwise online softmax).

TPU-native layout: the GQA group axis is folded into the query-tile rows so
every MXU matmul is (G*block_q, hd) x (hd, block_k) — hardware-aligned when
block_q/block_k are multiples of 128.  Grid = (B*KV, nq, nk); the nk axis is
"arbitrary" (sequential) and accumulates into VMEM scratch; fully-masked
causal / out-of-window K tiles are skipped with ``pl.when``.

Validated on CPU in interpret mode against ``ref.attention_ref``.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_COMPILER_PARAMS = pltpu.CompilerParams(
    dimension_semantics=("parallel", "parallel", "arbitrary"))

NEG_INF = -2.0 ** 30


def _kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
            block_q: int, block_k: int, nk: int, causal: bool,
            window: Optional[int], logit_cap: Optional[float],
            q_offset: int, scale: float, groups: int,
            kv_len: Optional[int]):
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    rows = groups * block_q
    q0 = q_offset + qi * block_q
    k0 = ki * block_k

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # --- tile-level skip for fully-masked K tiles ---------------------------
    run = True
    if causal:
        # last q position in tile vs first k position in tile
        run = jnp.asarray(k0 <= q0 + block_q - 1)
    if window is not None:
        run = jnp.logical_and(run, k0 + block_k - 1 > q0 - window)
    if kv_len is not None and kv_len < nk * block_k:
        # tiles entirely inside the key padding contribute nothing
        run = jnp.logical_and(jnp.asarray(run), k0 < kv_len)

    @pl.when(run if not isinstance(run, bool) else True)
    def _compute():
        q = q_ref[0].reshape(rows, q_ref.shape[-1])          # (G*bq, hd)
        k = k_ref[0]                                          # (bk, hd)
        v = v_ref[0]
        s = lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
        if logit_cap is not None:
            s = logit_cap * jnp.tanh(s / logit_cap)

        qpos = q0 + lax.broadcasted_iota(jnp.int32, (rows, block_k), 0) % block_q
        kpos = k0 + lax.broadcasted_iota(jnp.int32, (rows, block_k), 1)
        mask = jnp.ones((rows, block_k), bool)
        if causal:
            mask = mask & (kpos <= qpos)
        if window is not None:
            mask = mask & (kpos > qpos - window)
        if kv_len is not None and kv_len < nk * block_k:
            mask = mask & (kpos < kv_len)     # zero-padded keys are invalid
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_ref[:, :1]
        m_cur = jnp.max(s, axis=1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_ref[...] = jnp.broadcast_to(
            l_ref[:, :1] * corr + p.sum(axis=1, keepdims=True), l_ref.shape)
        m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)
        pv = lax.dot_general(p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
                             preferred_element_type=jnp.float32)
        acc_ref[...] = acc_ref[...] * corr + pv

    @pl.when(ki == nk - 1)
    def _finalize():
        l = jnp.maximum(l_ref[:, :1], 1e-30)
        out = (acc_ref[...] / l).astype(o_ref.dtype)
        o_ref[0] = out.reshape(o_ref.shape[1:])


def flash_attention_fwd(
    q: jax.Array,                 # (BKV, G, Tq, hd)
    k: jax.Array,                 # (BKV, Tk, hd)
    v: jax.Array,                 # (BKV, Tk, hd)
    *,
    causal: bool,
    window: Optional[int],
    logit_cap: Optional[float],
    q_offset: int,
    block_q: int,
    block_k: int,
    interpret: bool,
    kv_len: Optional[int] = None,
) -> jax.Array:
    BKV, G, Tq, hd = q.shape
    Tk = k.shape[1]
    assert Tq % block_q == 0 and Tk % block_k == 0, (Tq, Tk, block_q, block_k)
    nq, nk = Tq // block_q, Tk // block_k
    rows = G * block_q
    scale = hd ** -0.5

    kernel = functools.partial(
        _kernel, block_q=block_q, block_k=block_k, nk=nk, causal=causal,
        window=window, logit_cap=logit_cap, q_offset=q_offset, scale=scale,
        groups=G, kv_len=kv_len)

    scratch = [
        pltpu.VMEM((rows, 128), jnp.float32),
        pltpu.VMEM((rows, 128), jnp.float32),
        pltpu.VMEM((rows, hd), jnp.float32),
    ]
    return pl.pallas_call(
        kernel,
        grid=(BKV, nq, nk),
        in_specs=[
            pl.BlockSpec((1, G, block_q, hd), lambda b, i, j: (b, 0, i, 0)),
            pl.BlockSpec((1, block_k, hd), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, block_k, hd), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, G, block_q, hd), lambda b, i, j: (b, 0, i, 0)),
        out_shape=jax.ShapeDtypeStruct((BKV, G, Tq, hd), q.dtype),
        scratch_shapes=scratch,
        interpret=interpret,
        compiler_params=_COMPILER_PARAMS,
    )(q, k, v)
