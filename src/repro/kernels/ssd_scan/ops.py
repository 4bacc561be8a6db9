"""Jit'd public wrapper for the SSD-scan Pallas kernel (model layout).

``chunk=None`` consults the autotune cache (``repro.perf.autotune``) for
the best-known chunk of this (shape-class, dtype, backend) and degrades
it to the largest divisor of T when the tuned value does not divide the
actual sequence length; an empty cache falls back to the historical 128.
Explicit kwargs win (and must divide T, as before).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels.ssd_scan.ssd_scan import ssd_scan_fwd
from repro.perf import autotune


def _on_cpu() -> bool:
    return jax.default_backend() == "cpu"


DEFAULT_CHUNK = autotune.DEFAULTS["ssd_scan"]["chunk"]


def _largest_dividing_chunk(T: int, chunk: int) -> int:
    chunk = min(chunk, T)
    while T % chunk:
        chunk -= 1
    return chunk


def ssd_scan(
    x: jax.Array,     # (B, T, H, P)
    dt: jax.Array,    # (B, T, H)  (already softplus'd)
    A: jax.Array,     # (H,) negative reals
    Bm: jax.Array,    # (B, T, N)
    Cm: jax.Array,    # (B, T, N)
    *,
    chunk: Optional[int] = None,
    interpret=None,
):
    """Returns (y (B,T,H,P) f32, final_state (B,H,P,N) f32)."""
    if chunk is None:
        cfg = autotune.lookup("ssd_scan", x.dtype, H=x.shape[2],
                              P=x.shape[3], N=Bm.shape[2], T=x.shape[1])
        chunk = _largest_dividing_chunk(
            x.shape[1], cfg["chunk"] if cfg else DEFAULT_CHUNK)
    if interpret is None:
        interpret = _on_cpu()
    return _ssd_scan(x, dt, A, Bm, Cm, chunk=chunk, interpret=interpret)


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def _ssd_scan(
    x: jax.Array,
    dt: jax.Array,
    A: jax.Array,
    Bm: jax.Array,
    Cm: jax.Array,
    *,
    chunk: int,
    interpret: bool,
):
    B, T, H, P = x.shape
    chunk = min(chunk, T)
    assert T % chunk == 0, (T, chunk)

    xdt = (x.astype(jnp.float32) * dt.astype(jnp.float32)[..., None]
           ).transpose(0, 2, 1, 3)                       # (B,H,T,P)
    dA = (dt.astype(jnp.float32) * A).transpose(0, 2, 1)[..., None]  # (B,H,T,1)

    y, final_state = ssd_scan_fwd(xdt, dA, Bm, Cm, chunk=chunk,
                                  interpret=interpret)
    return y.transpose(0, 2, 1, 3), final_state
