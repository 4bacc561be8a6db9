"""Pallas TPU paged decode-attention kernel: ragged slots vs a paged KV pool.

Continuous batching keeps each live decode slot's KV cache in fixed-size
PAGES scattered across one shared physical pool instead of a contiguous
per-slot region: slot ``b``'s logical key axis is the concatenation
``pages[tbl[b, 0]], pages[tbl[b, 1]], ...`` truncated at ``kv_lens[b]``.
Admitting a request claims free pages, evict-on-EOS returns them — no
copying, no per-slot max-length reservation.

Grid = (B*KV, ns) with one PAGE per grid step.  The per-slot lengths and
the block table ride scalar prefetch (``num_scalar_prefetch=2``), so the
page index feeds the k/v BlockSpec ``index_map`` directly — the DMA
fetches exactly the physical pages the table names — and pages entirely
beyond a slot's length are skipped with ``pl.when``: a short slot in a
ragged batch costs HBM reads proportional to ITS length, not the batch
maximum.  The online-softmax accumulation in VMEM scratch is exactly the
dense decode kernel's."""

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


def _paged_kernel(lens_ref, tbl_ref, q_ref, k_ref, v_ref, o_ref,
                  m_ref, l_ref, acc_ref, *, page_size: int, ns: int,
                  window: Optional[int], logit_cap: Optional[float],
                  scale: float):
    b = pl.program_id(0)
    ji = pl.program_id(1)
    k0 = ji * page_size
    length = lens_ref[b]            # valid keys for this slot: kpos < length

    @pl.when(ji == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # skip pages entirely past this slot's length (or fully outside the
    # sliding window around its newest token, pos = length - 1)
    run = k0 < length
    if window is not None:
        run = jnp.logical_and(run, k0 + page_size > length - window)

    @pl.when(run)
    def _compute():
        q = q_ref[0] * scale                                  # (G, hd)
        k = k_ref[0]                                          # (psz, hd)
        v = v_ref[0]
        s = lax.dot_general(q.astype(jnp.float32), k.astype(jnp.float32),
                            (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)  # (G, psz)
        if logit_cap is not None:
            s = logit_cap * jnp.tanh(s / logit_cap)
        kpos = k0 + lax.broadcasted_iota(jnp.int32, s.shape, 1)
        mask = kpos < length
        if window is not None:
            mask = mask & (kpos > length - 1 - window)
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

    @pl.when(ji == ns - 1)
    def _finalize():
        l = jnp.maximum(l_ref[:, :1], 1e-30)
        o_ref[0] = (acc_ref[...] / l).astype(o_ref.dtype)


def paged_decode_attention_fwd(
    q: jax.Array,        # (BKV, G, hd)
    k_pages: jax.Array,  # (P, page_size, hd) — shared physical page pool
    v_pages: jax.Array,  # (P, page_size, hd)
    kv_lens: jax.Array,  # (BKV,) int32
    block_tables: jax.Array,  # (BKV, ns) int32 — physical page per slot/step
    *,
    window: Optional[int],
    logit_cap: Optional[float],
    interpret: bool,
) -> jax.Array:
    BKV, G, hd = q.shape
    page_size = k_pages.shape[1]
    ns = block_tables.shape[1]
    scale = hd ** -0.5

    kernel = functools.partial(_paged_kernel, page_size=page_size, ns=ns,
                               window=window, logit_cap=logit_cap,
                               scale=scale)
    # the index_map consults the prefetched block table: grid step (b, j)
    # DMAs physical page tbl[b, j].  Entries past a slot's length are
    # skipped by pl.when but still indexed — the wrapper clamps them into
    # range.
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(BKV, ns),
        in_specs=[
            pl.BlockSpec((1, G, hd),
                         lambda b, j, lens_ref, tbl_ref: (b, 0, 0)),
            pl.BlockSpec((1, page_size, hd),
                         lambda b, j, lens_ref, tbl_ref:
                         (tbl_ref[b, j], 0, 0)),
            pl.BlockSpec((1, page_size, hd),
                         lambda b, j, lens_ref, tbl_ref:
                         (tbl_ref[b, j], 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, G, hd),
                               lambda b, j, lens_ref, tbl_ref: (b, 0, 0)),
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
    )(kv_lens, block_tables, q, k_pages, v_pages)
