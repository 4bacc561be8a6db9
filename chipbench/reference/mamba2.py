"""Plain float32 reference of Mamba2 (arXiv:2405.21060, state-spaces/mamba2),
written from the published description and independent of the program
under test.

  x = embed[tokens]
  per layer:  h = rms(x) * w_norm
              [z | xBC | dt] = h W_in           (d_in | d_in + 2N | H)
              xBC = silu(causal depthwise conv of width W over time + b)
              [xs | B | C] = xBC                (d_in as H heads of P | N | N)
              dt = softplus(dt + dt_bias);  A = -exp(A_log)
              per head, step by step in time:
                  s_t = exp(dt_t A) s_{t-1} + dt_t xs_t B_t^T     (P x N)
                  y_t = s_t C_t + D xs_t
              x = x + (rms(y * silu(z)) * w_gate_norm) W_out
  logits = (rms(x) * w_final_norm) embed^T   (tied)

One group (B and C shared by all heads), as in the published 1.3B model.
The state recurrence runs as written, one time step after another: the
chunked (dual) form that the program uses is not used here.  Everything is
float32 at matmul precision "highest", one layer at a time.  Departures
from the published model: none in the mathematics (its residual stream is
float32, as here); the weights are random from the seed, and only the
logits at the requested positions are formed.

``control=True`` is the control: the input and output projections and
the head take their two operands through float8 e4m3 with one scale per
tensor, float32 accumulation; the convolution and the recurrence stay
float32.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
CONTROL = jnp.float8_e4m3fn      # the control's operand type


def _quant(a, dtype):
    amax = jnp.max(jnp.abs(a))
    scale = jnp.where(amax > 0, amax / float(jnp.finfo(dtype).max), 1.0)
    return (a / scale).astype(dtype).astype(jnp.float32) * scale


def mm(spec: str, a, b, control: bool):
    a, b = a.astype(jnp.float32), b.astype(jnp.float32)
    if control:
        a, b = _quant(a, CONTROL), _quant(b, CONTROL)
    return jnp.einsum(spec, a, b, precision=HIGHEST)


def rms(x, w, eps):
    x = x.astype(jnp.float32)
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * w.astype(jnp.float32)


@functools.partial(jax.jit, static_argnames=("eps", "N", "P", "control"))
def layer(x, w, *, eps, N, P, control):
    n, T, _ = x.shape
    H = w["A_log"].shape[0]
    d_in = H * P
    h = rms(x, w["norm"], eps)
    proj = mm("ntd,de->nte", h, w["in_proj"], control)
    z, xbc, dt = proj[..., :d_in], proj[..., d_in:2 * d_in + 2 * N], \
        proj[..., 2 * d_in + 2 * N:]
    conv_w = w["conv_w"].astype(jnp.float32)                  # (W, channels)
    W = conv_w.shape[0]
    padded = jnp.pad(xbc, ((0, 0), (W - 1, 0), (0, 0)))
    conv = sum(padded[:, j:j + T] * conv_w[j] for j in range(W))
    xbc = jax.nn.silu(conv + w["conv_b"].astype(jnp.float32))
    xs = xbc[..., :d_in].reshape(n, T, H, P)
    Bm, Cm = xbc[..., d_in:d_in + N], xbc[..., d_in + N:]
    dt = jax.nn.softplus(dt + w["dt_bias"].astype(jnp.float32))   # (n,T,H)
    A = -jnp.exp(w["A_log"].astype(jnp.float32))

    def step(s, inp):
        x_t, dt_t, b_t, c_t = inp
        s = (jnp.exp(dt_t * A)[..., None, None] * s
             + (dt_t[..., None] * x_t)[..., None] * b_t[:, None, None, :])
        return s, jnp.einsum("nhpk,nk->nhp", s, c_t, precision=HIGHEST)

    s0 = jnp.zeros((n, H, P, N), jnp.float32)
    time_major = (xs.transpose(1, 0, 2, 3), dt.transpose(1, 0, 2),
                  Bm.transpose(1, 0, 2), Cm.transpose(1, 0, 2))
    _, y = jax.lax.scan(step, s0, time_major)
    y = y.transpose(1, 0, 2, 3) + w["D"].astype(jnp.float32)[:, None] * xs
    y = rms(y.reshape(n, T, d_in) * jax.nn.silu(z), w["gate_norm"], eps)
    return x + mm("nte,ed->ntd", y, w["out_proj"], control)


@functools.partial(jax.jit, static_argnames=("eps", "control"))
def head(x, norm, w_head, *, eps, control):
    return mm("nkd,dv->nkv", rms(x, norm, eps), w_head, control)


def logits(weights, conf: dict, seqs: np.ndarray, positions: np.ndarray,
           control: bool = False) -> np.ndarray:
    """Logits (n, len(positions), vocab) of the token sequences ``seqs``
    (n, T) at ``positions``."""
    ssm = conf["ssm_cfg"]
    if int(ssm["ngroups"]) != 1:
        raise ValueError("the reference holds one group of B and C")
    eps = float(conf["norm_epsilon"])
    embed = weights["embed"]
    x = jnp.take(embed, jnp.asarray(seqs), axis=0).astype(jnp.float32)
    group = weights["groups"][0]
    for i in range(int(conf["n_layer"])):
        w = jax.tree.map(lambda a: a[i], group)
        x = layer(x, w, eps=eps, N=int(ssm["d_state"]),
                  P=int(ssm["headdim"]), control=control)
    w_head = embed.T if conf["tie_embeddings"] else weights["lm_head"]
    out = head(x[:, jnp.asarray(positions)], weights["final_norm"], w_head,
               eps=eps, control=control)
    return np.asarray(out)
