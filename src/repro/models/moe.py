"""Mixture-of-Experts FFN with GShard-style capacity-based einsum dispatch.

Tokens are routed per *group* (``ROUTE_GROUP`` tokens during train/prefill,
the whole local batch during decode) so capacity is a static shape and the
dispatch tensor stays O(group * E * C).  Because top-k indices for a token are
distinct, the K routing slots are reduced away *before* the capacity one-hot:
``dispatch`` is (g, n, E, C) — never (g, n, K, E, C).

Sharding: the group axis follows the batch ('data') axis; the expert axis
follows 'model' when divisible (expert parallelism, e.g. qwen3's 128 experts
over 16), otherwise the per-expert hidden dim is sharded (TP inside each
expert, e.g. mixtral's 8 experts).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from repro.models.layers import named_scope

Array = jax.Array

ROUTE_GROUP = 256  # tokens per routing group (static capacity)


def init_moe(rng, cfg) -> dict:
    d = cfg.d_model
    f = cfg.moe_d_ff or cfg.d_ff
    E = cfg.num_experts
    ks = jax.random.split(rng, 4)
    std = 0.02
    dt = jnp.dtype(cfg.dtype)
    return {
        "router": (jax.random.normal(ks[0], (d, E)) * std).astype(dt),
        "wi": (jax.random.normal(ks[1], (E, d, f)) * std).astype(dt),
        "wg": (jax.random.normal(ks[2], (E, d, f)) * std).astype(dt),
        "wo": (jax.random.normal(ks[3], (E, f, d)) * std).astype(dt),
        "norm": jnp.ones((d,), dt),
    }


def capacity(tokens_per_group: int, num_experts: int, k: int,
             factor: float = 1.25) -> int:
    c = int(tokens_per_group * k / num_experts * factor)
    return max(4, -(-c // 4) * 4)  # round up to a multiple of 4


def _route(hg: Array, p: dict, cfg, C: int):
    """hg: (g, n, d) -> dispatch (g,n,E,C), combine (g,n,E,C), aux scalar."""
    E, K = cfg.num_experts, cfg.num_experts_per_tok
    logits = jnp.einsum("gnd,de->gne", hg, p["router"],
                        preferred_element_type=jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)                        # (g, n, E)
    gate_vals, gate_idx = lax.top_k(probs, K)                      # (g, n, K)
    gate_vals = gate_vals / jnp.maximum(gate_vals.sum(-1, keepdims=True), 1e-9)

    # Slot-major cumulative position inside each expert's capacity buffer
    # (slot 0 has priority, GShard semantics).
    onehot = jax.nn.one_hot(gate_idx, E, dtype=jnp.float32)        # (g, n, K, E)
    g, n = hg.shape[0], hg.shape[1]
    slot_major = onehot.transpose(0, 2, 1, 3).reshape(g, K * n, E)
    pos_sm = jnp.cumsum(slot_major, axis=1) - 1.0
    pos = (pos_sm.reshape(g, K, n, E).transpose(0, 2, 1, 3))       # (g, n, K, E)

    # A token takes at most one slot per expert -> reduce K away first.
    active = onehot > 0
    pos_r = jnp.max(jnp.where(active, pos, -1.0), axis=2)          # (g, n, E)
    gate_r = jnp.sum(jnp.where(active, gate_vals[..., None], 0.0), axis=2)

    dispatch = jax.nn.one_hot(pos_r, C, dtype=jnp.float32)         # 0 if pos<0 or >=C
    combine = dispatch * gate_r[..., None]

    # Switch-transformer load-balance aux loss.
    frac_tokens = onehot.sum(axis=2).mean(axis=1) / K              # (g, E)
    frac_probs = probs.mean(axis=1)
    aux = E * jnp.mean(jnp.sum(frac_tokens * frac_probs, axis=-1))
    return dispatch, combine, aux.astype(jnp.float32)


def moe_apply(p: dict, h: Array, cfg) -> tuple[Array, Array]:
    """h: (B, T, d) normalized input -> (y, aux_loss)."""
    B, T, d = h.shape
    E, K = cfg.num_experts, cfg.num_experts_per_tok

    if T > 1:
        n = ROUTE_GROUP if T % ROUTE_GROUP == 0 else T
        hg = h.reshape(B * T // n, n, d)
    else:
        n = B
        hg = h.reshape(1, B, d)
    C = capacity(n, E, K)

    dispatch, combine, aux = _route(hg, p, cfg, C)

    xin = jnp.einsum("gnec,gnd->gecd", dispatch.astype(h.dtype), hg)
    a = jnp.einsum("gecd,edf->gecf", xin, p["wg"])
    b = jnp.einsum("gecd,edf->gecf", xin, p["wi"])
    out = jnp.einsum("gecf,efd->gecd", jax.nn.silu(a) * b, p["wo"])
    y = jnp.einsum("gnec,gecd->gnd", combine.astype(out.dtype), out)

    return y.reshape(B, T, d), aux


@named_scope("mlp")
def moe_block_apply(p: dict, x: Array, cfg) -> tuple[Array, Array]:
    from repro.models.layers import rmsnorm
    h = rmsnorm(x, p["norm"], cfg.norm_eps)
    y, aux = moe_apply(p, h, cfg)
    return x + y, aux
