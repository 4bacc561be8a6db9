"""Pallas TPU decode-attention kernel: one new token vs a KV cache.

Grid = (B*KV, ns); the key axis is blocked (block_k) and accumulated with an
online softmax in VMEM scratch.  K tiles entirely beyond ``pos`` (or outside
the sliding window) are skipped with ``pl.when`` on the *traced* position —
on TPU this saves HBM reads of the dead cache region.  The GQA group axis
forms the matmul rows.
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
    dimension_semantics=("parallel", "arbitrary"))

NEG_INF = -2.0 ** 30


def _kernel(pos_ref, q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
            block_k: int, ns: int, window: Optional[int],
            logit_cap: Optional[float], scale: float):
    ki = pl.program_id(1)
    k0 = ki * block_k
    pos = pos_ref[0]

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    run = k0 <= pos
    if window is not None:
        run = jnp.logical_and(run, k0 + block_k - 1 > pos - window)

    @pl.when(run)
    def _compute():
        q = q_ref[0] * scale                                  # (G, hd)
        k = k_ref[0]                                          # (bk, hd)
        v = v_ref[0]
        s = lax.dot_general(q.astype(jnp.float32), k.astype(jnp.float32),
                            (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)  # (G, bk)
        if logit_cap is not None:
            s = logit_cap * jnp.tanh(s / logit_cap)
        kpos = k0 + lax.broadcasted_iota(jnp.int32, s.shape, 1)
        mask = kpos <= pos
        if window is not None:
            mask = mask & (kpos > pos - window)
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_ref[:, :1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_ref[...] = jnp.broadcast_to(
            l_ref[:, :1] * corr + p.sum(axis=1, keepdims=True), l_ref.shape)
        m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)
        pv = lax.dot_general(p, v.astype(jnp.float32),
                             (((1,), (0,)), ((), ())),
                             preferred_element_type=jnp.float32)
        acc_ref[...] = acc_ref[...] * corr + pv

    @pl.when(ki == ns - 1)
    def _finalize():
        l = jnp.maximum(l_ref[:, :1], 1e-30)
        o_ref[0] = (acc_ref[...] / l).astype(o_ref.dtype)


def decode_attention_fwd(
    q: jax.Array,        # (BKV, G, hd)
    k: jax.Array,        # (BKV, S, hd)
    v: jax.Array,        # (BKV, S, hd)
    pos: jax.Array,      # (1,) int32
    *,
    window: Optional[int],
    logit_cap: Optional[float],
    block_k: int,
    interpret: bool,
) -> jax.Array:
    BKV, G, hd = q.shape
    S = k.shape[1]
    assert S % block_k == 0, (S, block_k)
    ns = S // block_k
    scale = hd ** -0.5

    kernel = functools.partial(_kernel, block_k=block_k, ns=ns, window=window,
                               logit_cap=logit_cap, scale=scale)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(BKV, ns),
        in_specs=[
            pl.BlockSpec((1, G, hd), lambda b, j, pos_ref: (b, 0, 0)),
            pl.BlockSpec((1, block_k, hd), lambda b, j, pos_ref: (b, j, 0)),
            pl.BlockSpec((1, block_k, hd), lambda b, j, pos_ref: (b, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, G, hd), lambda b, j, pos_ref: (b, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((G, 128), jnp.float32),
            pltpu.VMEM((G, 128), jnp.float32),
            pltpu.VMEM((G, hd), jnp.float32),
        ],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((BKV, G, hd), q.dtype),
        interpret=interpret,
        compiler_params=_COMPILER_PARAMS,
    )(pos, q, k, v)
