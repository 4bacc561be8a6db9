"""Pallas TPU decode-attention kernel: one new token vs a KV cache.

The kernel reads one layer of a group's stacked cache (L, BKV, S, hd) in
HBM, the layer given by a scalar-prefetched index, so a layer scan hands
it the whole stack and nothing slices, stages or relays a layer for it.
With ``s_minor`` the stack comes as the (L, BKV, hd, S) view: the TPU lays
a cache of narrow heads (hd 64) out with positions minor, and that view is
the same bytes.

Grid = (BKV / rows, S / block_k).  Each step takes a block of ``rows``
(batch x KV-head) rows against a ``block_k`` slice of their cache and folds
it into an online softmax kept in VMEM scratch; the GQA group axis forms the
matmul rows of each (batched) product.  ``ops.decode_tiling`` picks the
block sizes from the operand shapes.

With ``k_new``/``v_new`` the new token's K/V (BKV, 1, hd) come as operands
and open the online softmax; the cache is read-only, its slot ``pos`` is
stale and never read.  Without them the cache already holds slot ``pos``.

The K/V index map is clamped to the live tiles with the scalar-prefetched
``pos``: a step past ``pos`` (or before the sliding window) maps to the
nearest live tile, which the pipeline already holds, so the dead cache
region is never fetched from HBM; ``pl.when`` then skips its compute.

``pos`` may lie outside the cache: past its end every position is live,
below 0 none is (one shard of a cache split by positions), and the new
token counts only where its slot lies in [0, S).  With ``stats`` the kernel
also returns the softmax's running max and sum of exponentials per query
head, so that outputs over disjoint parts of a cache can be combined.
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


def _live_tiles(pos, *, block_k: int, ns: int, window: Optional[int],
                new: bool = False):
    """(first, last) K tile holding a cache position the token attends to:
    positions up to ``pos``, or below it where the new token comes apart."""
    last = jnp.clip((pos - int(new)) // block_k, 0, ns - 1)
    if window is None:
        return 0, last
    return jnp.maximum(pos - window + 1, 0) // block_k, last


def _kernel(pos_ref, layer_ref, q_ref, k_ref, v_ref, *refs, block_k: int,
            ns: int, window: Optional[int], logit_cap: Optional[float],
            scale: float, stats: bool, new: bool, s_minor: bool):
    del layer_ref                 # read by the index maps alone
    kn_ref, vn_ref, *refs = refs if new else (None, None, *refs)
    o_ref, *refs = refs
    s_ref, m_ref, l_ref, acc_ref = refs if stats else (None, *refs)
    ki = pl.program_id(1)
    k0 = ki * block_k
    pos = pos_ref[0]
    end = pos - 1 if new else pos            # the last cache position read

    def scaled_q():
        return q_ref[...] * jnp.asarray(scale, q_ref.dtype)   # (rows, G, hd)

    @pl.when(ki == 0)
    def _init():
        if not new:
            m_ref[...] = jnp.full_like(m_ref, NEG_INF)
            l_ref[...] = jnp.zeros_like(l_ref)
            acc_ref[...] = jnp.zeros_like(acc_ref)
            return
        # the softmax opens on the new token, where its slot lies here
        own = jnp.logical_and(pos >= 0, pos < ns * block_k)
        s = jnp.sum(scaled_q().astype(jnp.float32)
                    * kn_ref[...].astype(jnp.float32), axis=2, keepdims=True)
        if logit_cap is not None:                              # (rows, G, 1)
            s = logit_cap * jnp.tanh(s / logit_cap)
        m_ref[...] = jnp.broadcast_to(jnp.where(own, s, NEG_INF), m_ref.shape)
        l_ref[...] = jnp.full_like(l_ref, jnp.where(own, 1.0, 0.0))
        acc_ref[...] = jnp.where(own, jnp.broadcast_to(
            vn_ref[...].astype(jnp.float32), acc_ref.shape), 0.0)

    run = k0 <= end
    if window is not None:
        run = jnp.logical_and(run, k0 + block_k - 1 > pos - window)

    @pl.when(run)
    def _compute():
        q = scaled_q()
        k, v = k_ref[...], v_ref[...]     # (rows, bk, hd), s_minor (rows, hd, bk)
        kd = 1 if s_minor else 2
        s = lax.dot_general(q, k, (((2,), (kd,)), ((0,), (0,))),
                            preferred_element_type=jnp.float32)
        if logit_cap is not None:                             # (rows, G, bk)
            s = logit_cap * jnp.tanh(s / logit_cap)
        kpos = k0 + lax.broadcasted_iota(jnp.int32, s.shape, 2)
        mask = kpos <= end
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
        vd = 2 if s_minor else 1
        pv = lax.dot_general(p.astype(v.dtype), v,
                             (((2,), (vd,)), ((0,), (0,))),
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
    k: jax.Array,        # (L, BKV, S, hd); s_minor: (L, BKV, hd, S)
    v: jax.Array,        # as k
    pos: jax.Array,      # (1,) int32
    layer: jax.Array,    # (1,) int32: the layer of k and v to read
    k_new: Optional[jax.Array] = None,   # (BKV, 1, hd)
    v_new: Optional[jax.Array] = None,
    *,
    s_minor: bool,
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
    S = k.shape[3] if s_minor else k.shape[2]
    assert BKV % rows == 0 and S % block_k == 0, (BKV, rows, S, block_k)
    ns = S // block_k
    new = k_new is not None
    scale = hd ** -0.5

    def kv_map(b, j, pos_ref, layer_ref):
        first, last = _live_tiles(pos_ref[0], block_k=block_k, ns=ns,
                                  window=window, new=new)
        j = jnp.minimum(jnp.maximum(j, first), last)
        return (layer_ref[0], b, 0, j) if s_minor else (layer_ref[0], b, j, 0)

    def row_map(b, j, pos_ref, layer_ref):
        return b, 0, 0

    kv_block = ((pl.Squeezed(), rows, hd, block_k) if s_minor
                else (pl.Squeezed(), rows, block_k, hd))
    in_specs = [pl.BlockSpec((rows, G, hd), row_map),
                pl.BlockSpec(kv_block, kv_map),
                pl.BlockSpec(kv_block, kv_map)]
    operands = [q, k, v]
    if new:
        in_specs += [pl.BlockSpec((rows, 1, hd), row_map)] * 2
        operands += [k_new, v_new]
    kernel = functools.partial(_kernel, block_k=block_k, ns=ns, window=window,
                               logit_cap=logit_cap, scale=scale, stats=stats,
                               new=new, s_minor=s_minor)
    out_block = pl.BlockSpec((rows, G, hd), row_map)
    out_shape = jax.ShapeDtypeStruct((BKV, G, hd), q.dtype)
    if stats:
        out_block = [out_block, pl.BlockSpec((rows, G, 128), row_map)]
        out_shape = [out_shape, jax.ShapeDtypeStruct((BKV, G, 128),
                                                     jnp.float32)]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(BKV // rows, ns),
        in_specs=in_specs,
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
    )(pos, layer, *operands)
