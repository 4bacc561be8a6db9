"""Plain float32 reference of a GPT-BigCode decoder with multi-query
attention (Granite 20B Code), written from the published description
(arXiv:2405.04324 Table 1; the model card's config.json, model_type
gpt_bigcode, multi_query true) and independent of the program under test.

  x = wte[tokens] + wpe[positions]           (positions 0..T-1)
  per layer:  h = LayerNorm(x; g1, b1)       ((x - mean) / sqrt(var + eps))
              q = h Wq + bq   (H heads of hd)
              k = h Wk + bk,  v = h Wv + bv   (one head, shared by all H)
              o = softmax(q k^T / sqrt(hd), causal) v
              x = x + o Wo + bo
              h = LayerNorm(x; g2, b2)
              x = x + gelu_tanh(h W1 + b1') W2 + b2'   (not gated)
  logits = LayerNorm(x; gf, bf) wte^T        (tied head)

  gelu_tanh(u) = u / 2 (1 + tanh(sqrt(2 / pi) (u + 0.044715 u^3)))

Everything is float32 at matmul precision "highest"; the whole sequence is
run at once (no cache), one layer at a time, each layer's weights cast as
it is used.  Departures from the published model: none in the
mathematics; the weights are random from the seed, and only the logits at
the requested positions are formed.

``control=True`` is the control: every linear layer and the head take
their two operands through float8 e4m3 with one scale per tensor (the
tensor's largest magnitude mapped to the format's largest value), with
float32 accumulation.  Attention's own products, the biases and the norms
stay float32.
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


def layer_norm(x, g, b, eps):
    x = x.astype(jnp.float32)
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, -1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps) * g.astype(jnp.float32) \
        + b.astype(jnp.float32)


def gelu_tanh(u):
    return 0.5 * u * (1.0 + jnp.tanh(np.sqrt(2.0 / np.pi)
                                     * (u + 0.044715 * u ** 3)))


def f32(a):
    return a.astype(jnp.float32)


@functools.partial(jax.jit, static_argnames=("eps", "control"))
def layer(x, attn, mlp, *, eps, control):
    n, T, _ = x.shape
    hd = attn["wq"].shape[2]
    h = layer_norm(x, attn["norm"], attn["norm_bias"], eps)
    q = mm("ntd,dhk->nthk", h, attn["wq"], control) + f32(attn["bq"])
    k = mm("ntd,dhk->nthk", h, attn["wk"], control)[:, :, 0] \
        + f32(attn["bk"][0])                                   # (n, T, hd)
    v = mm("ntd,dhk->nthk", h, attn["wv"], control)[:, :, 0] \
        + f32(attn["bv"][0])
    s = jnp.einsum("nthk,nsk->nhts", q, k, precision=HIGHEST) / np.sqrt(hd)
    causal = jnp.tril(jnp.ones((T, T), bool))
    s = jnp.where(causal, s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("nhts,nsk->nthk", p, v, precision=HIGHEST)
    x = x + mm("nthk,hkd->ntd", o, attn["wo"], control) + f32(attn["bo"])
    h = layer_norm(x, mlp["norm"], mlp["norm_bias"], eps)
    u = gelu_tanh(mm("ntd,df->ntf", h, mlp["wi"], control) + f32(mlp["bi"]))
    return x + mm("ntf,fd->ntd", u, mlp["wo"], control) + f32(mlp["bo"])


@functools.partial(jax.jit, static_argnames=("eps", "control"))
def head(x, g, b, wte, *, eps, control):
    return mm("nkd,vd->nkv", layer_norm(x, g, b, eps), wte, control)


def logits(weights, conf: dict, seqs: np.ndarray, positions: np.ndarray,
           control: bool = False) -> np.ndarray:
    """Logits (n, len(positions), vocab) of the token sequences ``seqs``
    (n, T) at ``positions``."""
    eps = float(conf["layer_norm_epsilon"])
    wte = weights["embed"]
    T = seqs.shape[1]
    x = (f32(jnp.take(wte, jnp.asarray(seqs), axis=0))
         + f32(weights["pos_embed"][:T]))
    group = weights["groups"][0]
    for i in range(int(conf["n_layer"])):
        attn = jax.tree.map(lambda a: a[i], group["attn"])
        mlp = jax.tree.map(lambda a: a[i], group["mlp"])
        x = layer(x, attn, mlp, eps=eps, control=control)
    out = head(x[:, jnp.asarray(positions)], weights["final_norm"],
               weights["final_norm_bias"], wte, eps=eps, control=control)
    return np.asarray(out)
