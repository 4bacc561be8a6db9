"""Pallas TPU decode-attention kernel: one new token vs a KV cache.

Grid = (BKV / rows, S / block_k).  Each step takes a block of ``rows``
(batch x KV-head) rows against a ``block_k`` slice of their cache and folds
it into an online softmax kept in VMEM scratch; the GQA group axis forms the
matmul rows of each (batched) product.  ``ops.decode_tiling`` picks the
block sizes from the operand shapes.

The K/V index map is clamped to the live tiles with the scalar-prefetched
``pos``: a step past ``pos`` (or before the sliding window) maps to the
nearest live tile, which the pipeline already holds, so the dead cache
region is never fetched from HBM; ``pl.when`` then skips its compute.

``pos`` may lie outside the cache: past its end every position is live,
below 0 none is (one shard of a cache split by positions), and the output
is 0.  With ``stats`` the kernel also returns the softmax's running max
and sum of exponentials per query head, so that outputs over disjoint
parts of a cache can be combined.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -2.0 ** 30


def _live_tiles(pos, *, block_k: int, ns: int, window: Optional[int]):
    """(first, last) K tile holding a position the token attends to."""
    last = jnp.clip(pos // block_k, 0, ns - 1)
    if window is None:
        return 0, last
    return jnp.maximum(pos - window + 1, 0) // block_k, last


def _kernel(pos_ref, q_ref, k_ref, v_ref, o_ref, *refs, block_k: int, ns: int,
            window: Optional[int], logit_cap: Optional[float], scale: float,
            stats: bool):
    s_ref, m_ref, l_ref, acc_ref = refs if stats else (None, *refs)
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
        q = q_ref[...] * jnp.asarray(scale, q_ref.dtype)      # (rows, G, hd)
        v = v_ref[...]                                        # (rows, bk, hd)
        s = lax.dot_general(q, k_ref[...], (((2,), (2,)), ((0,), (0,))),
                            preferred_element_type=jnp.float32)
        if logit_cap is not None:                             # (rows, G, bk)
            s = logit_cap * jnp.tanh(s / logit_cap)
        kpos = k0 + lax.broadcasted_iota(jnp.int32, s.shape, 2)
        mask = kpos <= pos
        if window is not None:
            mask = mask & (kpos > pos - window)
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_ref[:, :, :1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=2, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_ref[...] = jnp.broadcast_to(
            l_ref[:, :, :1] * corr + p.sum(axis=2, keepdims=True),
            l_ref.shape)
        m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)
        pv = lax.dot_general(p.astype(v.dtype), v,
                             (((2,), (1,)), ((0,), (0,))),
                             preferred_element_type=jnp.float32)
        acc_ref[...] = acc_ref[...] * corr + pv

    @pl.when(ki == ns - 1)
    def _finalize():
        l = jnp.maximum(l_ref[:, :, :1], 1e-30)
        o_ref[...] = (acc_ref[...] / l).astype(o_ref.dtype)
        if stats:                 # lane 0: running max, lane 1: sum of exp
            lane = lax.broadcasted_iota(jnp.int32, s_ref.shape, 2)
            s_ref[...] = jnp.where(lane == 0, m_ref[...], l_ref[...])


def decode_attention_fwd(
    q: jax.Array,        # (BKV, G, hd)
    k: jax.Array,        # (BKV, S, hd)
    v: jax.Array,        # (BKV, S, hd)
    pos: jax.Array,      # (1,) int32
    *,
    window: Optional[int],
    logit_cap: Optional[float],
    rows: int,
    block_k: int,
    vmem_limit_bytes: int,
    interpret: bool,
    stats: bool = False,
):
    """Returns o (BKV, G, hd); with ``stats`` also (BKV, G, 128) float32
    holding each row's running max in lane 0 and its sum of exp in lane 1
    (NEG_INF and 0 where no position is live)."""
    BKV, G, hd = q.shape
    S = k.shape[1]
    assert BKV % rows == 0 and S % block_k == 0, (BKV, rows, S, block_k)
    ns = S // block_k
    scale = hd ** -0.5

    def kv_map(b, j, pos_ref):
        first, last = _live_tiles(pos_ref[0], block_k=block_k, ns=ns,
                                  window=window)
        return b, jnp.minimum(jnp.maximum(j, first), last), 0

    kernel = functools.partial(_kernel, block_k=block_k, ns=ns, window=window,
                               logit_cap=logit_cap, scale=scale, stats=stats)
    out_block = pl.BlockSpec((rows, G, hd), lambda b, j, pos_ref: (b, 0, 0))
    out_shape = jax.ShapeDtypeStruct((BKV, G, hd), q.dtype)
    if stats:
        out_block = [out_block, pl.BlockSpec((rows, G, 128),
                                             lambda b, j, pos_ref: (b, 0, 0))]
        out_shape = [out_shape, jax.ShapeDtypeStruct((BKV, G, 128),
                                                     jnp.float32)]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(BKV // rows, ns),
        in_specs=[
            pl.BlockSpec((rows, G, hd), lambda b, j, pos_ref: (b, 0, 0)),
            pl.BlockSpec((rows, block_k, hd), kv_map),
            pl.BlockSpec((rows, block_k, hd), kv_map),
        ],
        out_specs=out_block,
        scratch_shapes=[
            pltpu.VMEM((rows, G, 128), jnp.float32),
            pltpu.VMEM((rows, G, 128), jnp.float32),
            pltpu.VMEM((rows, G, hd), jnp.float32),
        ],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=out_shape,
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=vmem_limit_bytes),
    )(pos, q, k, v)
