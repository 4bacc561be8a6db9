"""Pallas TPU kernel for the Mamba2 SSD chunked scan.

Grid = (B, H, nc): batch and head axes are parallel; the chunk axis is
sequential ("arbitrary") with the running state (P, N) held in VMEM scratch.
Per chunk the kernel computes the intra-chunk quadratic term
(L ⊙ C Bᵀ) · (dt x) plus the inter-chunk contribution C · S_in, then advances
the state — i.e. the state-space-dual form where both heavy products are MXU
matmuls of shape (chunk, N)x(N, chunk) and (chunk, chunk)x(chunk, P).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_COMPILER_PARAMS = pltpu.CompilerParams(
    dimension_semantics=("parallel", "parallel", "arbitrary"))


def _kernel(xdt_ref, dA_ref, b_ref, c_ref, y_ref, state_out_ref, s_ref, *,
            chunk: int, nc: int):
    ci = pl.program_id(2)

    @pl.when(ci == 0)
    def _init():
        s_ref[...] = jnp.zeros_like(s_ref)

    xdt = xdt_ref[0, 0].astype(jnp.float32)       # (chunk, P)  x*dt
    dA = dA_ref[0, 0].astype(jnp.float32)         # (chunk, 1)  dt*A (log decay)
    Bc = b_ref[0].astype(jnp.float32)             # (chunk, N)
    Cc = c_ref[0].astype(jnp.float32)             # (chunk, N)
    P = xdt.shape[1]

    rows = lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    cols = lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    causal = rows >= cols
    # inclusive prefix sum as a matmul with the lower-triangular mask (Mosaic
    # has no cumsum lowering)
    cum = lax.dot_general(causal.astype(jnp.float32), dA,
                          (((1,), (0,)), ((), ())),
                          precision=lax.Precision.HIGHEST,
                          preferred_element_type=jnp.float32)  # (chunk, 1)
    seg = cum - cum.T                             # (chunk, chunk) log decay t<-s
    L = jnp.where(causal, jnp.exp(seg), 0.0)

    scores = lax.dot_general(Cc, Bc, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)
    W = scores * L                                # (chunk, chunk)
    y = lax.dot_general(W, xdt, (((1,), (0,)), ((), ())),
                        preferred_element_type=jnp.float32)   # (chunk, P)

    # inter-chunk: y += exp(cum) * (C @ state^T);  state: (P, N)
    state = s_ref[...]
    y_in = lax.dot_general(Cc, state, (((1,), (1,)), ((), ())),
                           preferred_element_type=jnp.float32)  # (chunk, P)
    y = y + jnp.exp(cum) * y_in
    y_ref[0, 0] = y.astype(y_ref.dtype)

    # state update: S = S * exp(cum[-1]) + xdt^T @ (B * decay_to_end)
    total = cum[chunk - 1:chunk]                  # (1, 1) static slice
    decay_to_end = jnp.exp(total - cum)           # (chunk, 1)
    S_local = lax.dot_general(xdt, Bc * decay_to_end, (((0,), (0,)), ((), ())),
                              preferred_element_type=jnp.float32)  # (P, N)
    # the chunk's total log decay, one copy per state row: Mosaic cannot
    # broadcast a (1, 1) value over sublanes and lanes at once
    total_rows = lax.dot_general(jnp.ones((P, chunk), jnp.float32), dA,
                                 (((1,), (0,)), ((), ())),
                                 precision=lax.Precision.HIGHEST,
                                 preferred_element_type=jnp.float32)  # (P, 1)
    s_ref[...] = state * jnp.exp(total_rows) + S_local

    @pl.when(ci == nc - 1)
    def _final():
        state_out_ref[0, 0] = s_ref[...]


def ssd_scan_fwd(
    xdt: jax.Array,   # (B, H, T, P)  pre-multiplied x * dt
    dA: jax.Array,    # (B, H, T, 1)  dt * A  (negative log-decay)
    Bm: jax.Array,    # (B, T, N)
    Cm: jax.Array,    # (B, T, N)
    *,
    chunk: int,
    interpret: bool,
):
    B, H, T, P = xdt.shape
    N = Bm.shape[-1]
    assert T % chunk == 0, (T, chunk)
    nc = T // chunk

    kernel = functools.partial(_kernel, chunk=chunk, nc=nc)
    y, final_state = pl.pallas_call(
        kernel,
        grid=(B, H, nc),
        in_specs=[
            pl.BlockSpec((1, 1, chunk, P), lambda b, h, c: (b, h, c, 0)),
            pl.BlockSpec((1, 1, chunk, 1), lambda b, h, c: (b, h, c, 0)),
            pl.BlockSpec((1, chunk, N), lambda b, h, c: (b, c, 0)),
            pl.BlockSpec((1, chunk, N), lambda b, h, c: (b, c, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, chunk, P), lambda b, h, c: (b, h, c, 0)),
            pl.BlockSpec((1, 1, P, N), lambda b, h, c: (b, h, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, H, T, P), jnp.float32),
            jax.ShapeDtypeStruct((B, H, P, N), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((P, N), jnp.float32)],
        interpret=interpret,
        compiler_params=_COMPILER_PARAMS,
    )(xdt, dA, Bm, Cm)
    return y, final_state
