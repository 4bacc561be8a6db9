"""Mamba2 block (SSD — state-space duality, arXiv:2405.21060).

Pure-JAX chunked SSD for train/prefill (mirrored by the Pallas kernel in
``repro.kernels.ssd_scan``) and a single-step recurrence for decode.

Layout conventions:
  d_inner = ssm_expand * d_model;  H = d_inner // ssm_head_dim heads
  x_ssm: (B, T, H, P)   P = ssm_head_dim
  B/C:   (B, T, N)      N = ssm_state_size  (single "group", shared across heads)
  state: (B, H, P, N)
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax

from repro.models.layers import named_scope

Array = jax.Array


def dims(cfg):
    d_in = cfg.ssm_expand * cfg.d_model
    H = d_in // cfg.ssm_head_dim
    return d_in, H, cfg.ssm_head_dim, cfg.ssm_state_size


def init_mamba_block(rng, cfg) -> dict:
    d = cfg.d_model
    d_in, H, P, N = dims(cfg)
    conv_dim = d_in + 2 * N
    ks = jax.random.split(rng, 6)
    std = 0.02
    dt = jnp.dtype(cfg.dtype)
    return {
        "norm": jnp.ones((d,), dt),
        # in_proj -> [z (d_in), xBC (conv_dim), dt (H)]
        "in_proj": (jax.random.normal(ks[0], (d, 2 * d_in + 2 * N + H)) * std).astype(dt),
        "conv_w": (jax.random.normal(ks[1], (cfg.ssm_conv_width, conv_dim)) * std).astype(dt),
        "conv_b": jnp.zeros((conv_dim,), dt),
        "A_log": jnp.log(jnp.linspace(1.0, 16.0, H)).astype(jnp.float32),
        "D": jnp.ones((H,), jnp.float32),
        "dt_bias": jnp.log(jnp.expm1(jnp.full((H,), 1e-2))).astype(jnp.float32),
        "gate_norm": jnp.ones((d_in,), dt),
        "out_proj": (jax.random.normal(ks[2], (d_in, d)) * std).astype(dt),
    }


def _split_proj(proj: Array, cfg):
    d_in, H, P, N = dims(cfg)
    z = proj[..., :d_in]
    xBC = proj[..., d_in:d_in + d_in + 2 * N]
    dt_raw = proj[..., -H:]
    return z, xBC, dt_raw


def _causal_conv(xBC: Array, w: Array, b: Array,
                 conv_state: Optional[Array] = None):
    """Depthwise causal conv along T.  xBC: (B, T, Cdim); w: (W, Cdim).

    Returns (out, new_conv_state) where conv_state holds the last W-1 inputs.
    """
    W = w.shape[0]
    if conv_state is None:
        prev = jnp.zeros((xBC.shape[0], W - 1, xBC.shape[-1]), xBC.dtype)
    else:
        prev = conv_state
    xp = jnp.concatenate([prev, xBC], axis=1)           # (B, T+W-1, C)
    out = sum(xp[:, i:i + xBC.shape[1]] * w[i] for i in range(W)) + b
    new_state = xp[:, -(W - 1):]
    return jax.nn.silu(out), new_state


def ssd_chunked(x: Array, dt: Array, A: Array, Bm: Array, Cm: Array,
                chunk: int, init_state: Optional[Array] = None):
    """Chunked SSD, sequential ``lax.scan`` over chunks (bounded live memory:
    the quadratic (chunk x chunk) decay/score tensors exist for one chunk at a
    time — this is the pure-JAX oracle mirrored by kernels/ssd_scan).

    x:  (B, T, H, P) inputs;  dt: (B, T, H) softplus'd step sizes
    A:  (H,) negative reals;  Bm/Cm: (B, T, N)
    Returns (y (B,T,H,P), final_state (B,H,P,N)).
    """
    Bsz, T, H, P = x.shape
    N = Bm.shape[-1]
    nc = max(T // chunk, 1)
    chunk = T // nc
    assert nc * chunk == T, (T, chunk)

    # chunk-major for scan: (nc, B, c, ...)
    xg = x.reshape(Bsz, nc, chunk, H, P).transpose(1, 0, 2, 3, 4)
    dtg = dt.reshape(Bsz, nc, chunk, H).transpose(1, 0, 2, 3)
    Bg = Bm.reshape(Bsz, nc, chunk, N).transpose(1, 0, 2, 3)
    Cg = Cm.reshape(Bsz, nc, chunk, N).transpose(1, 0, 2, 3)

    tri = jnp.tril(jnp.ones((chunk, chunk), bool))
    s0 = (jnp.zeros((Bsz, H, P, N), jnp.float32) if init_state is None
          else init_state.astype(jnp.float32))

    def step(state, inp):
        xc, dtc, Bc, Cc = inp                    # (B,c,H,P) (B,c,H) (B,c,N) (B,c,N)
        dA = dtc * A                             # (B,c,H) log-decays (<=0)
        cum = jnp.cumsum(dA, axis=1)             # inclusive
        # intra-chunk: L[t,s] = exp(cum[t]-cum[s]) for s<=t
        seg = cum[:, :, None, :] - cum[:, None, :, :]       # (B,t,s,H)
        L = jnp.where(tri[None, :, :, None], jnp.exp(seg), 0.0)
        scores = jnp.einsum("btn,bsn->bts", Cc, Bc,
                            preferred_element_type=jnp.float32)
        W = scores[..., None] * L                           # (B,t,s,H)
        xdt = (xc * dtc[..., None]).astype(jnp.float32)     # (B,s,H,P)
        y_c = jnp.einsum("btsh,bshp->bthp", W, xdt)
        # contribution of the state entering this chunk
        decay_from_start = jnp.exp(cum)                     # (B,t,H)
        y_c += jnp.einsum("btn,bhpn,bth->bthp",
                          Cc.astype(jnp.float32), state, decay_from_start)
        # update state to chunk end
        decay_to_end = jnp.exp(cum[:, -1:, :] - cum)        # (B,s,H)
        S_local = jnp.einsum("bsh,bsn,bshp->bhpn",
                             decay_to_end * dtc, Bc.astype(jnp.float32),
                             xc.astype(jnp.float32))
        chunk_decay = jnp.exp(cum[:, -1, :])                # (B,H)
        new_state = state * chunk_decay[:, :, None, None] + S_local
        return new_state, y_c.astype(x.dtype)

    final_state, ys = lax.scan(step, s0, (xg, dtg, Bg, Cg))
    y = ys.transpose(1, 0, 2, 3, 4).reshape(Bsz, T, H, P)
    return y, final_state


def ssd_decode_step(state: Array, x: Array, dt: Array, A: Array,
                    Bm: Array, Cm: Array):
    """One-token recurrence.  state: (B,H,P,N); x: (B,H,P); dt: (B,H);
    Bm/Cm: (B,N).  Returns (y (B,H,P), new_state)."""
    dA = jnp.exp(dt * A)                                  # (B,H)
    upd = jnp.einsum("bh,bhp,bn->bhpn", dt, x.astype(jnp.float32),
                     Bm.astype(jnp.float32))
    new_state = state * dA[:, :, None, None] + upd
    y = jnp.einsum("bhpn,bn->bhp", new_state, Cm.astype(jnp.float32))
    return y.astype(x.dtype), new_state


@named_scope("ssm")
def mamba_block_apply(p: dict, x: Array, cfg, *, state: Optional[dict] = None,
                      mode: str = "train"):
    """Residual Mamba2 block.

    state (decode): {'ssm': (B,H,P,N) f32, 'conv': (B, W-1, conv_dim)}
    Returns (y, new_state) — new_state None for train, carried for
    prefill/decode.
    """
    from repro.models.layers import rmsnorm

    B, T, d = x.shape
    d_in, H, P, N = dims(cfg)
    h = rmsnorm(x, p["norm"], cfg.norm_eps)
    proj = h @ p["in_proj"]
    z, xBC, dt_raw = _split_proj(proj, cfg)
    dt = jax.nn.softplus(dt_raw.astype(jnp.float32) + p["dt_bias"])  # (B,T,H)
    A = -jnp.exp(p["A_log"])                                          # (H,)

    conv_state = state["conv"] if state is not None else None
    xBC_c, new_conv = _causal_conv(xBC, p["conv_w"], p["conv_b"], conv_state)
    xs = xBC_c[..., :d_in].reshape(B, T, H, P)
    Bm = xBC_c[..., d_in:d_in + N]
    Cm = xBC_c[..., d_in + N:]

    if mode == "decode":
        assert T == 1
        y1, new_ssm = ssd_decode_step(state["ssm"], xs[:, 0], dt[:, 0], A,
                                      Bm[:, 0], Cm[:, 0])
        y = y1[:, None]
    elif (mode == "prefill" and getattr(cfg, "kernel_impl", "xla") == "pallas"
          and state is None and T % min(cfg.ssm_chunk_size, T) == 0):
        # Pallas SSD kernel (interpret mode on CPU; TPU target)
        from repro.kernels.ssd_scan.ops import ssd_scan as pl_ssd
        y, new_ssm = pl_ssd(xs, dt, A, Bm, Cm,
                            chunk=min(cfg.ssm_chunk_size, T))
        y = y.astype(x.dtype)
    else:
        init = state["ssm"] if state is not None else None
        y, new_ssm = ssd_chunked(xs, dt, A, Bm, Cm, cfg.ssm_chunk_size, init)

    y = y + xs * p["D"][:, None].astype(x.dtype)
    y = y.reshape(B, T, d_in)
    y = rmsnorm(y * jax.nn.silu(z), p["gate_norm"], cfg.norm_eps)
    out = y @ p["out_proj"]

    new_state = None
    if mode in ("prefill", "decode"):
        new_state = {"ssm": new_ssm, "conv": new_conv}
    return x + out, new_state


def init_mamba_state(cfg, batch: int, dtype=jnp.bfloat16) -> dict:
    d_in, H, P, N = dims(cfg)
    conv_dim = d_in + 2 * N
    return {
        "ssm": jnp.zeros((batch, H, P, N), jnp.float32),
        "conv": jnp.zeros((batch, cfg.ssm_conv_width - 1, conv_dim), dtype),
    }
