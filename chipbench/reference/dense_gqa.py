"""Plain float32 reference of a dense decoder with grouped-query attention
(Llama family: SmolLM), written from the published description and
independent of the program under test.

  x = embed[tokens]
  per layer:  h = rms(x) * w_attn_norm
              q, k, v = h Wq, h Wk, h Wv;  rotary embedding on q and k
              (rotate-half form, inv_freq = theta^(-2i/head_dim))
              head j attends with key/value head j // (H / KV), causal,
              softmax(q k^T / sqrt(head_dim)) v
              x = x + o Wo
              h = rms(x) * w_mlp_norm
              x = x + (silu(h Wgate) * (h Wup)) Wdown
  logits = (rms(x) * w_final_norm) embed^T   (tied) or  ... W_lm_head

Everything is float32 at matmul precision "highest"; the whole sequence is
run at once (no cache), one layer at a time.  Departures from the
published model: none in the mathematics; the weights are random from the
seed, and only the logits at the requested positions are formed.

``control=True`` is the control: every linear layer and the head take
their two operands through float8 e4m3 with one scale per tensor (the
tensor's largest magnitude mapped to the format's largest value), with
float32 accumulation.  Attention's own products stay float32.
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


def rope(x, theta):
    """x: (n, T, heads, hd) at positions 0..T-1."""
    T, hd = x.shape[1], x.shape[-1]
    half = hd // 2
    inv = theta ** (-2.0 * jnp.arange(half, dtype=jnp.float32) / hd)
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * inv      # (T, half)
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


@functools.partial(jax.jit, static_argnames=("eps", "theta", "control"))
def layer(x, attn, mlp, *, eps, theta, control):
    n, T, _ = x.shape
    H, KV = attn["wq"].shape[1], attn["wk"].shape[1]
    hd = attn["wq"].shape[2]
    h = rms(x, attn["norm"], eps)
    q = rope(mm("ntd,dhk->nthk", h, attn["wq"], control), theta)
    k = rope(mm("ntd,dhk->nthk", h, attn["wk"], control), theta)
    v = mm("ntd,dhk->nthk", h, attn["wv"], control)
    k = jnp.repeat(k, H // KV, axis=2)
    v = jnp.repeat(v, H // KV, axis=2)
    s = jnp.einsum("nthk,nshk->nhts", q, k, precision=HIGHEST) / np.sqrt(hd)
    causal = jnp.tril(jnp.ones((T, T), bool))
    s = jnp.where(causal, s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("nhts,nshk->nthk", p, v, precision=HIGHEST)
    x = x + mm("nthk,hkd->ntd", o, attn["wo"], control)
    h = rms(x, mlp["norm"], eps)
    g = jax.nn.silu(mm("ntd,df->ntf", h, mlp["wg"], control))
    u = mm("ntd,df->ntf", h, mlp["wi"], control)
    return x + mm("ntf,fd->ntd", g * u, mlp["wo"], control)


@functools.partial(jax.jit, static_argnames=("eps", "control"))
def head(x, norm, w_head, *, eps, control):
    return mm("nkd,dv->nkv", rms(x, norm, eps), w_head, control)


def logits(weights, conf: dict, seqs: np.ndarray, positions: np.ndarray,
           control: bool = False) -> np.ndarray:
    """Logits (n, len(positions), vocab) of the token sequences ``seqs``
    (n, T) at ``positions``."""
    eps, theta = float(conf["rms_norm_eps"]), float(conf["rope_theta"])
    embed = weights["embed"]
    x = jnp.take(embed, jnp.asarray(seqs), axis=0).astype(jnp.float32)
    group = weights["groups"][0]
    for i in range(int(conf["num_hidden_layers"])):
        attn = jax.tree.map(lambda a: a[i], group["attn"])
        mlp = jax.tree.map(lambda a: a[i], group["mlp"])
        x = layer(x, attn, mlp, eps=eps, theta=theta, control=control)
    w_head = embed.T if conf["tie_word_embeddings"] else weights["lm_head"]
    out = head(x[:, jnp.asarray(positions)], weights["final_norm"], w_head,
               eps=eps, control=control)
    return np.asarray(out)
