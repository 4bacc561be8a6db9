"""Shared neural-net layers: norms, RoPE, attention (flash + decode), MLP.

Everything is a pure function over explicit parameter pytrees.  Attention is
implemented blockwise (online softmax over KV blocks inside a ``lax.scan``,
query blocks via ``lax.map``) so that 32k-token prefill lowers with bounded
live memory — this is the pure-JAX oracle mirrored by the Pallas kernel in
``repro.kernels.flash_attention``.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

Array = jax.Array

NEG_INF = -2.0 ** 30  # large-negative that survives bf16 softmax math in f32


@dataclasses.dataclass(frozen=True)
class TensorParallel:
    """Where a step's tensors lie, for the attention kernels that run under
    ``shard_map``, as the step builder hands it to the model: query heads
    (and the projections around them) are split over the mesh axis
    ``axis``, the decode cache's positions over the same axis, and the
    batch over the mesh axes ``batch`` (None: replicated)."""
    mesh: Mesh
    axis: str
    batch: Optional[tuple] = None

    @property
    def shards(self) -> int:
        return self.mesh.shape[self.axis]


def constrain_batch(x: Array, bspec) -> Array:
    """Pin the leading (batch) axis of an activation to the given mesh axes
    (None = leave to GSPMD).  Without this, propagation through the embedding
    gather can replicate the batch and shard d_model instead — 16x waste."""
    if bspec is None:
        return x
    from jax.sharding import PartitionSpec as P
    spec = P(bspec, *([None] * (x.ndim - 1)))
    return jax.lax.with_sharding_constraint(x, spec)


def named_scope(name: str):
    """Decorator: every op the function stages carries ``name`` in its JAX
    name stack (the ``op_name`` metadata that a profiler trace reports), so
    a trace tells which part of the step an op belongs to.  Enters a fresh
    ``jax.named_scope`` per call, so the function may be traced in several
    threads at once."""
    def wrap(fn):
        @functools.wraps(fn)
        def scoped(*args, **kwargs):
            with jax.named_scope(name):
                return fn(*args, **kwargs)
        return scoped
    return wrap


# ---------------------------------------------------------------------------
# Norms
# ---------------------------------------------------------------------------
def rmsnorm(x: Array, weight: Array, eps: float = 1e-6) -> Array:
    dtype = x.dtype
    x32 = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x32), axis=-1, keepdims=True)
    y = x32 * lax.rsqrt(var + eps)
    return (y * weight.astype(jnp.float32)).astype(dtype)


def layernorm(x: Array, weight: Array, bias: Array, eps: float = 1e-5) -> Array:
    dtype = x.dtype
    x32 = x.astype(jnp.float32)
    mu = jnp.mean(x32, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x32 - mu), axis=-1, keepdims=True)
    y = (x32 - mu) * lax.rsqrt(var + eps)
    return (y * weight.astype(jnp.float32)
            + bias.astype(jnp.float32)).astype(dtype)


def norm(p: dict, name: str, x: Array, cfg) -> Array:
    """The block's norm ``p[name]``: RMSNorm, or LayerNorm with the bias
    ``p[name + "_bias"]``."""
    if cfg.norm_type == "layernorm":
        return layernorm(x, p[name], p[name + "_bias"], cfg.norm_eps)
    return rmsnorm(x, p[name], cfg.norm_eps)


def init_norm(cfg, name: str) -> dict:
    dt = jnp.dtype(cfg.dtype)
    p = {name: jnp.ones((cfg.d_model,), dt)}
    if cfg.norm_type == "layernorm":
        p[name + "_bias"] = jnp.zeros((cfg.d_model,), dt)
    return p


def softcap(x: Array, cap: Optional[float]) -> Array:
    if cap is None:
        return x
    return cap * jnp.tanh(x / cap)


# ---------------------------------------------------------------------------
# RoPE
# ---------------------------------------------------------------------------
def rope(x: Array, positions: Array, theta: float) -> Array:
    """Rotary embedding. x: (..., T, H, hd); positions broadcastable to (..., T)."""
    hd = x.shape[-1]
    half = hd // 2
    freqs = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    angles = positions[..., None].astype(jnp.float32) * freqs          # (..., T, half)
    cos = jnp.cos(angles)[..., None, :]                                # (..., T, 1, half)
    sin = jnp.sin(angles)[..., None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# Blockwise flash attention (pure-JAX) — training / prefill path.
# ---------------------------------------------------------------------------
def _pad_axis(x: Array, axis: int, multiple: int) -> Array:
    n = x.shape[axis]
    pad = (-n) % multiple
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


def _mask_for(qpos, kpos, causal, window, kv_len):
    mask = (kpos[None, :] < kv_len)
    if causal:
        mask = mask & (kpos[None, :] <= qpos[:, None])
    if window is not None:
        mask = mask & (kpos[None, :] > qpos[:, None] - window)
    return mask


def _scores(qblk, kblk, logit_cap, qpos, kpos, causal, window, kv_len):
    """qblk pre-scaled (B,bq,KV,G,hd); kblk (B,bk,KV,hd) ->
    (s_capped, raw) both (B,KV,G,bq,bk) f32, masked with NEG_INF."""
    raw = jnp.einsum("bqkgd,bskd->bkgqs", qblk, kblk,
                     preferred_element_type=jnp.float32)
    s = softcap(raw, logit_cap)
    mask = _mask_for(qpos, kpos, causal, window, kv_len)
    s = jnp.where(mask[None, None, None], s, NEG_INF)
    return s, raw


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _flash(static, q, k, v):
    out, _ = _flash_fwd_res(static, q, k, v)
    return out


def _flash_fwd_res(static, q, k, v):
    """q: (B, nq, bq, KV, G, hd); k/v: (B, nk, bk, KV, hd).
    Returns (out (B,nq,bq,KV,G,hd), lse (B,KV,G,nq,bq)).

    parallel_q (last static field): process q blocks with vmap instead of a
    sequential lax.map — under GSPMD this lets the nq axis shard over the
    'model' mesh axis (sequence-parallel prefill for archs whose head counts
    don't divide it; a lax.map over a sharded axis would gather per step)."""
    causal, window, logit_cap, q_offset, kv_len, parallel_q = static
    B, nq, bq, KV, G, hd = q.shape
    nk, bk = k.shape[1], k.shape[2]
    scale = hd ** -0.5

    def q_block_body(qblk_raw, qi):
        qblk = qblk_raw * scale
        qpos = q_offset + qi * bq + jnp.arange(bq)

        def kv_step(carry, ki):
            m, l, acc = carry
            kpos = ki * bk + jnp.arange(bk)
            s, _ = _scores(qblk, k[:, ki], logit_cap, qpos, kpos,
                           causal, window, kv_len)
            m_new = jnp.maximum(m, s.max(axis=-1))
            p = jnp.exp(s - m_new[..., None])
            corr = jnp.exp(m - m_new)
            l_new = l * corr + p.sum(axis=-1)
            pv = jnp.einsum("bkgqs,bskd->bqkgd", p.astype(v.dtype), v[:, ki],
                            preferred_element_type=jnp.float32)
            acc_new = acc * corr.transpose(0, 3, 1, 2)[..., None] + pv
            return (m_new, l_new, acc_new), None

        m0 = jnp.full((B, KV, G, bq), NEG_INF, jnp.float32)
        l0 = jnp.zeros((B, KV, G, bq), jnp.float32)
        a0 = jnp.zeros((B, bq, KV, G, hd), jnp.float32)
        (m, l, acc), _ = lax.scan(kv_step, (m0, l0, a0), jnp.arange(nk))
        l = jnp.maximum(l, 1e-30)
        out = (acc / l.transpose(0, 3, 1, 2)[..., None]).astype(q.dtype)
        lse = m + jnp.log(l)                       # (B, KV, G, bq)
        return out, lse

    if parallel_q:
        outs, lses = jax.vmap(q_block_body, in_axes=(1, 0))(
            q, jnp.arange(nq))
    else:
        outs, lses = lax.map(lambda qi: q_block_body(q[:, qi], qi),
                             jnp.arange(nq))
    out = outs.transpose(1, 0, 2, 3, 4, 5)             # (B,nq,bq,KV,G,hd)
    lse = lses.transpose(1, 2, 3, 0, 4)                # (B,KV,G,nq,bq)
    return out, lse


def _flash_vjp_fwd(static, q, k, v):
    out, lse = _flash_fwd_res(static, q, k, v)
    return out, (q, k, v, out, lse)


def _flash_vjp_bwd(static, res, dout):
    """Flash-attention backward: recompute scores blockwise (no (bq x bk)
    probability tensors are ever saved — this is why it exists; naive AD of
    the forward scan saves p per block per layer per microbatch).

    Note: parallel_q (sequence-parallel prefill) is forward-only — the
    backward keeps the sequential q-block loop (prefill takes no grads)."""
    causal, window, logit_cap, q_offset, kv_len, _parallel_q = static
    q, k, v, out, lse = res
    B, nq, bq, KV, G, hd = q.shape
    nk, bk = k.shape[1], k.shape[2]
    scale = hd ** -0.5

    # D_i = rowsum(dO * O): (B, KV, G, nq, bq)
    delta = jnp.einsum("bnqkgd,bnqkgd->bkgnq", dout.astype(jnp.float32),
                       out.astype(jnp.float32))

    def ds_block(qblk_scaled, qpos, ki, lse_q, delta_q, dout_q):
        """Recompute p and ds for one (q-block, kv-block) pair.
        Returns (p, ds) both (B,KV,G,bq,bk) f32."""
        kpos = ki * bk + jnp.arange(bk)
        s, raw = _scores(qblk_scaled, k[:, ki], logit_cap, qpos, kpos,
                         causal, window, kv_len)
        p = jnp.exp(s - lse_q[..., None])
        dp = jnp.einsum("bqkgd,bskd->bkgqs", dout_q.astype(jnp.float32),
                        v[:, ki].astype(jnp.float32))
        ds = p * (dp - delta_q[..., None])
        if logit_cap is not None:
            ds = ds * (1.0 - jnp.square(jnp.tanh(raw / logit_cap)))
        return p, ds

    # ---- pass A: dq (q-block major, scan kv blocks) ----
    def dq_block(qi):
        qblk = q[:, qi] * scale
        qpos = q_offset + qi * bq + jnp.arange(bq)
        lse_q, delta_q, dout_q = lse[:, :, :, qi], delta[:, :, :, qi], dout[:, qi]

        def kv_step(dq_acc, ki):
            p, ds = ds_block(qblk, qpos, ki, lse_q, delta_q, dout_q)
            dq_acc = dq_acc + jnp.einsum(
                "bkgqs,bskd->bqkgd", ds, k[:, ki].astype(jnp.float32))
            return dq_acc, None

        dq0 = jnp.zeros((B, bq, KV, G, hd), jnp.float32)
        dq, _ = lax.scan(kv_step, dq0, jnp.arange(nk))
        return dq * scale

    dq = lax.map(dq_block, jnp.arange(nq)).transpose(1, 0, 2, 3, 4, 5)

    # ---- pass B: dk, dv (kv-block major, scan q blocks) ----
    def dkv_block(ki):
        def q_step(carry, qi):
            dk_acc, dv_acc = carry
            qblk = q[:, qi] * scale
            qpos = q_offset + qi * bq + jnp.arange(bq)
            p, ds = ds_block(qblk, qpos, ki, lse[:, :, :, qi],
                             delta[:, :, :, qi], dout[:, qi])
            dv_acc = dv_acc + jnp.einsum(
                "bkgqs,bqkgd->bskd", p, dout[:, qi].astype(jnp.float32))
            dk_acc = dk_acc + jnp.einsum(
                "bkgqs,bqkgd->bskd", ds, q[:, qi].astype(jnp.float32) * scale)
            return (dk_acc, dv_acc), None

        z = jnp.zeros((B, bk, KV, hd), jnp.float32)
        (dk, dv), _ = lax.scan(q_step, (z, z), jnp.arange(nq))
        return dk, dv

    dks, dvs = lax.map(dkv_block, jnp.arange(nk))
    dk = dks.transpose(1, 0, 2, 3, 4)
    dv = dvs.transpose(1, 0, 2, 3, 4)
    return dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype)


_flash.defvjp(_flash_vjp_fwd, _flash_vjp_bwd)


def flash_attention(
    q: Array,                    # (B, Tq, H, hd)
    k: Array,                    # (B, Tk, KV, hd)
    v: Array,                    # (B, Tk, KV, hd)
    *,
    causal: bool = True,
    window: Optional[int] = None,
    logit_cap: Optional[float] = None,
    q_offset: int = 0,           # absolute position of q[0] (prefill continuation)
    kv_valid_len: Optional[int] = None,    # mask k positions >= this
    block_q: Optional[int] = None,
    block_k: Optional[int] = None,
    seq_axis: Optional[str] = None,  # shard q blocks over this mesh axis
) -> Array:
    """Online-softmax attention, O(block_q * Tk) live memory per step,
    custom VJP with blockwise recomputation (differentiable; seq_axis is a
    forward-only sequence-parallel mode for prefill).

    Block sizes left None defer to the autotune cache (the same
    per-(shape-class, dtype, backend) lookup the Pallas wrappers use);
    explicit kwargs always win, and an empty cache falls back to the
    historical 256/512 defaults."""
    B, Tq, H, hd = q.shape
    _, Tk, KV, _ = k.shape
    assert H % KV == 0, (H, KV)
    G = H // KV

    if block_q is None or block_k is None:
        from repro.perf import autotune
        cfg = autotune.lookup("flash_attention", q.dtype, BKV=B * KV, G=G,
                              hd=hd, Tq=max(Tq, 1), Tk=max(Tk, 1),
                              causal=causal)
        if block_q is None:
            block_q = cfg["block_q"] if cfg else 256
        if block_k is None:
            block_k = cfg["block_k"] if cfg else 512

    block_q = min(block_q, max(Tq, 1))
    block_k = min(block_k, max(Tk, 1))

    qp = _pad_axis(q, 1, block_q)
    kp = _pad_axis(k, 1, block_k)
    vp = _pad_axis(v, 1, block_k)
    nq = qp.shape[1] // block_q
    nk = kp.shape[1] // block_k

    qp = qp.reshape(B, nq, block_q, KV, G, hd)
    kp = kp.reshape(B, nk, block_k, KV, hd)
    vp = vp.reshape(B, nk, block_k, KV, hd)

    kv_len = Tk if kv_valid_len is None else kv_valid_len
    if seq_axis is not None:
        # sequence-parallel prefill (§Perf): shard the q-block axis over the
        # given mesh axis; K/V stay replicated (gathered once per layer).
        from jax.sharding import PartitionSpec as P
        qp = jax.lax.with_sharding_constraint(
            qp, P(None, seq_axis, None, None, None, None))
    static = (causal, window, logit_cap, q_offset, kv_len,
              seq_axis is not None)
    out = _flash(static, qp, kp, vp)                   # (B,nq,bq,KV,G,hd)
    out = out.reshape(B, nq * block_q, H, hd)
    return out[:, :Tq].astype(q.dtype)


# ---------------------------------------------------------------------------
# Single-token decode attention against a KV cache (pure-JAX oracle; the
# Pallas kernel in repro.kernels.decode_attention mirrors this).
# ---------------------------------------------------------------------------
def decode_attention(
    q: Array,        # (B, H, hd)  — one new token per sequence
    k_cache: Array,  # (B, KV, S, hd) — attention-native layout (§Perf: the
    v_cache: Array,  #                  (B,S,KV,hd) layout forced a full cache
    pos: Array,      #                  transpose per layer per step)
    *,
    window: Optional[int] = None,
    logit_cap: Optional[float] = None,
    k_new: Array,    # (B, KV, 1, hd) — the new token's K/V, attended
    v_new: Array,    # separately (append-outside-scan decode, §Perf: the
) -> Array:          # cache stays read-only)
    B, H, hd = q.shape
    _, KV, S, _ = k_cache.shape
    G = H // KV
    scale = hd ** -0.5
    qh = q.reshape(B, KV, G, hd) * scale
    s = jnp.einsum("bkgd,bksd->bkgs", qh, k_cache,
                   preferred_element_type=jnp.float32)
    s = softcap(s, logit_cap)
    kpos = jnp.arange(S)
    mask = kpos < pos            # the cache's slot pos is not yet written
    if window is not None:
        mask = mask & (kpos > pos - window)
    s = jnp.where(mask[None, None, None], s, NEG_INF)

    # two-part softmax: combine cache scores (sequence axis may be sharded —
    # a concat would make GSPMD gather the score matrix) with the new token's
    # self-score via explicit max/denominator merging.  Reductions over the
    # sharded S become small (B,KV,G) all-reduces.
    s_self = softcap(jnp.einsum("bkgd,bkxd->bkgx", qh, k_new,
                                preferred_element_type=jnp.float32), logit_cap)
    m = jnp.maximum(s.max(axis=-1, keepdims=True), s_self)     # (B,KV,G,1)
    p_cache = jnp.exp(s - m)
    p_self = jnp.exp(s_self - m)
    denom = p_cache.sum(axis=-1, keepdims=True) + p_self       # (B,KV,G,1)
    out = jnp.einsum("bkgs,bksd->bkgd", p_cache.astype(v_cache.dtype), v_cache,
                     preferred_element_type=jnp.float32)
    out = out + jnp.einsum("bkgx,bkxd->bkgd", p_self.astype(v_new.dtype),
                           v_new, preferred_element_type=jnp.float32)
    out = out / denom
    return out.reshape(B, H, hd).astype(q.dtype)


# ---------------------------------------------------------------------------
# Attention block (pre-norm [+ optional post-norm], GQA, RoPE, residual)
# ---------------------------------------------------------------------------
def init_attn_block(rng, cfg, *, cross: bool = False) -> dict:
    d, H, KV, hd = cfg.d_model, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    ks = jax.random.split(rng, 8)
    std = 0.02
    dt = jnp.dtype(cfg.dtype)
    # Head axes kept explicit (d, H, hd) so TP sharding on the head axis never
    # crosses a reshape (GSPMD propagates cleanly through the einsums).
    p = {
        "wq": (jax.random.normal(ks[0], (d, H, hd)) * std).astype(dt),
        "wk": (jax.random.normal(ks[1], (d, KV, hd)) * std).astype(dt),
        "wv": (jax.random.normal(ks[2], (d, KV, hd)) * std).astype(dt),
        "wo": (jax.random.normal(ks[3], (H, hd, d)) * std).astype(dt),
        **init_norm(cfg, "norm"),
    }
    if cfg.attention_bias:
        p["bq"] = jnp.zeros((H, hd), dt)
        p["bk"] = jnp.zeros((KV, hd), dt)
        p["bv"] = jnp.zeros((KV, hd), dt)
    if cfg.attention_out_bias:
        p["bo"] = jnp.zeros((d,), dt)
    if cfg.post_block_norm:
        p["post_norm"] = jnp.ones((d,), dt)
    if cross:
        p["cross_norm"] = jnp.ones((d,), dt)
    return p


def qkv_proj(p: dict, x: Array, cfg) -> tuple[Array, Array, Array]:
    q = jnp.einsum("btd,dhx->bthx", x, p["wq"])
    k = jnp.einsum("btd,dkx->btkx", x, p["wk"])
    v = jnp.einsum("btd,dkx->btkx", x, p["wv"])
    if cfg.attention_bias:
        q = q + p["bq"]
        k = k + p["bk"]
        v = v + p["bv"]
    return q, k, v


def shard_weights(m: Array, l: Array) -> Array:
    """Weights that combine attention computed over n disjoint parts of the
    keys: m, l (n, ...) are each part's running max of the scores and its
    sum of exp(score - m), as the decode kernel returns them (m = -inf and
    l = 0 for a part with no live position).  The combined output is the
    sum over parts of weight * that part's normalised output
    (log-sum-exp)."""
    w = l * jnp.exp(m - m.max(axis=0))
    return w / w.sum(axis=0)


def cp_decode_attention(q, k_stack, v_stack, k_new, v_new, pos, layer, *,
                        tp: TensorParallel, window=None, logit_cap=None):
    """Decode attention with the heads of q (B, H, hd) and the positions of
    the stacked cache (L, B, KV, S, hd) split over ``tp.axis``.  Each shard
    gathers every head of q and runs the decode kernel over layer
    ``layer`` of its own part of the stack, the new token (B, KV, 1, hd)
    counted on the shard where its slot lies; the shards' partial outputs
    are combined by log-sum-exp in one reduce-scatter over the heads, so
    that each shard ends with the heads of its slice of the output
    projection.  Returns (B, H, hd), heads split like q's."""
    from repro.kernels.decode_attention.ops import decode_attention_stacked
    axis = tp.axis

    def local(q, kc, vc, k_new, v_new, pos, layer):
        with jax.named_scope("attn_combine"):
            q = lax.all_gather(q, axis, axis=1, tiled=True)
        shard = lax.axis_index(axis)
        o, m, l = decode_attention_stacked(
            q, kc, vc, pos - shard * kc.shape[3], layer, k_new=k_new,
            v_new=v_new, window=window, logit_cap=logit_cap, stats=True)
        with jax.named_scope("attn_combine"):
            m, l = lax.all_gather(jnp.stack([m, l]), axis, axis=1)
            w = shard_weights(m, l)[shard]
            o = lax.psum_scatter(o.astype(jnp.float32) * w[..., None],
                                 axis, scatter_dimension=1, tiled=True)
        return o.astype(q.dtype)

    heads = P(tp.batch, axis, None)
    cache = P(None, tp.batch, None, axis, None)
    new = P(tp.batch, None, None, None)
    # check_vma off: a pallas_call's outputs carry no varying-axes record
    return jax.shard_map(local, mesh=tp.mesh,
                         in_specs=(heads, cache, cache, new, new, P(), P()),
                         out_specs=heads, check_vma=False)(
        q, k_stack, v_stack, k_new, v_new, pos, layer)


def tp_flash_attention(q, k, v, *, tp: TensorParallel, window=None,
                       logit_cap=None):
    """The prefill's Pallas flash kernel with the heads of q (B, T, H, hd)
    split over ``tp.axis``: each shard attends with its own query heads;
    K/V heads are split alike when their count divides the shards, else
    (one K/V head, MQA) every shard holds them whole."""
    from repro.kernels.flash_attention.ops import flash_attention as pl_flash
    heads = P(tp.batch, None, tp.axis, None)
    kv = heads if k.shape[2] % tp.shards == 0 else P(tp.batch, None, None,
                                                      None)
    return jax.shard_map(
        lambda q, k, v: pl_flash(q, k, v, causal=True, window=window,
                                 logit_cap=logit_cap),
        mesh=tp.mesh, in_specs=(heads, kv, kv), out_specs=heads,
        check_vma=False)(q, k, v)


@named_scope("attention")
def attn_block_apply(
    p: dict,
    x: Array,                   # (B, T, d)
    cfg,
    *,
    window: Optional[int] = None,
    causal: bool = True,
    positions: Optional[Array] = None,   # (T,) absolute positions
    cache: Optional[dict] = None,        # {'k','v'}: (B, KV, S, hd) — decode only
    cache_pos: Optional[Array] = None,   # scalar int32
    layer: Optional[Array] = None,       # cache is the stack; read this layer
    mode: str = "train",                 # train | prefill | decode
    seq_axis: Optional[str] = None,      # sequence-parallel attention (prefill)
    tp: Optional[TensorParallel] = None,  # Pallas kernels under shard_map
):
    """Returns (y, new_kv) where new_kv is {k, v}: the block's K/V
    (B, T, KV, hd) for prefill, the new token's (B, KV, 1, hd) for decode
    (the cache stays read-only), and None for train.

    layer (Pallas decode): ``cache`` is the group's stacked cache
    (L, B, KV, S, hd) and the kernel reads this layer of it in place.

    tp (with kernel_impl 'pallas'): the kernels run on each shard of a
    tensor-parallel layout (``cp_decode_attention``,
    ``tp_flash_attention``) instead of on arrays GSPMD would gather."""
    B, T, d = x.shape
    h = norm(p, "norm", x, cfg)
    q, k, v = qkv_proj(p, h, cfg)
    if positions is None:
        positions = jnp.arange(T)
    if not cfg.learned_positions:
        q = rope(q, positions, cfg.rope_theta)
        k = rope(k, positions, cfg.rope_theta)
    pallas = getattr(cfg, "kernel_impl", "xla") == "pallas"

    if mode == "decode":
        assert cache is not None and T == 1
        k_new = k.transpose(0, 2, 1, 3).astype(cache["k"].dtype)  # (B,KV,1,hd)
        v_new = v.transpose(0, 2, 1, 3).astype(cache["v"].dtype)
        new_kv = {"k": k_new, "v": v_new}
        if pallas:
            # the kernel reads the cache in place, the new token apart: the
            # cache stays read-only, as on the XLA path
            from repro.kernels.decode_attention.ops import (
                decode_attention_stacked)
            ks, vs = cache["k"], cache["v"]
            if layer is None:
                ks, vs, layer = ks[None], vs[None], 0
            if tp is not None:
                with jax.named_scope("cp_attention"):
                    o = cp_decode_attention(q[:, 0], ks, vs, k_new, v_new,
                                            cache_pos, layer, tp=tp,
                                            window=window,
                                            logit_cap=cfg.attn_logit_softcap)
            else:
                o = decode_attention_stacked(q[:, 0], ks, vs, cache_pos,
                                             layer, k_new=k_new, v_new=v_new,
                                             window=window,
                                             logit_cap=cfg.attn_logit_softcap)
        else:
            # append-outside-scan: the cache is read-only here; the
            # returned (k_new, v_new) is written once per step (one stacked
            # write outside the layer scan instead of a full cache rewrite
            # per layer).
            o = decode_attention(q[:, 0], cache["k"], cache["v"], cache_pos,
                                 window=window,
                                 logit_cap=cfg.attn_logit_softcap,
                                 k_new=k_new, v_new=v_new)
        o = o[:, None]                            # (B, 1, H, hd)
    elif mode == "prefill" and pallas and causal:
        # Pallas flash-attention kernel (interpret mode on CPU; TPU target)
        if tp is not None:
            o = tp_flash_attention(q, k, v, tp=tp, window=window,
                                   logit_cap=cfg.attn_logit_softcap)
        else:
            from repro.kernels.flash_attention.ops import (
                flash_attention as pl_flash)
            o = pl_flash(q, k, v, causal=True, window=window,
                         logit_cap=cfg.attn_logit_softcap)
        new_kv = {"k": k, "v": v}
    else:
        o = flash_attention(q, k, v, causal=causal, window=window,
                            logit_cap=cfg.attn_logit_softcap,
                            seq_axis=seq_axis if mode == "prefill" else None)
        new_kv = {"k": k, "v": v} if mode == "prefill" else None

    y = jnp.einsum("bthx,hxd->btd", o, p["wo"])
    if cfg.attention_out_bias:
        y = y + p["bo"]
    if cfg.post_block_norm:
        y = rmsnorm(y, p["post_norm"], cfg.norm_eps)
    return x + y, new_kv


def cross_attn_apply(p: dict, x: Array, enc_kv: dict, cfg) -> Array:
    """Cross-attention over precomputed encoder K/V (no positions)."""
    h = rmsnorm(x, p["cross_norm"], cfg.norm_eps)
    q = jnp.einsum("btd,dhx->bthx", h, p["wq"])
    if cfg.attention_bias:
        q = q + p["bq"]
    o = flash_attention(q, enc_kv["k"], enc_kv["v"], causal=False,
                        logit_cap=cfg.attn_logit_softcap)
    return x + jnp.einsum("bthx,hxd->btd", o, p["wo"])


def encode_kv(p: dict, enc_out: Array, cfg) -> dict:
    """Precompute cross-attention K/V from encoder output."""
    k = jnp.einsum("bsd,dkx->bskx", enc_out, p["wk"])
    v = jnp.einsum("bsd,dkx->bskx", enc_out, p["wv"])
    if cfg.attention_bias:
        k = k + p["bk"]
        v = v + p["bv"]
    return {"k": k, "v": v}


# ---------------------------------------------------------------------------
# MLP: SwiGLU (gate/up/down), or the plain GELU MLP with biases (GPT-BigCode)
# ---------------------------------------------------------------------------
def init_mlp(rng, cfg, d_ff: Optional[int] = None) -> dict:
    d = cfg.d_model
    f = d_ff or cfg.d_ff
    ks = jax.random.split(rng, 3)
    std = 0.02
    dt = jnp.dtype(cfg.dtype)
    p = {
        "wi": (jax.random.normal(ks[0], (d, f)) * std).astype(dt),
        "wo": (jax.random.normal(ks[2], (f, d)) * std).astype(dt),
        **init_norm(cfg, "norm"),
    }
    if cfg.mlp_type == "gelu":
        p["bi"] = jnp.zeros((f,), dt)
        p["bo"] = jnp.zeros((d,), dt)
    else:
        p["wg"] = (jax.random.normal(ks[1], (d, f)) * std).astype(dt)
    if cfg.post_block_norm:
        p["post_norm"] = jnp.ones((d,), dt)
    return p


@named_scope("mlp")
def mlp_apply(p: dict, x: Array, cfg) -> Array:
    h = norm(p, "norm", x, cfg)
    if cfg.mlp_type == "gelu":
        y = jax.nn.gelu(h @ p["wi"] + p["bi"], approximate=True) @ p["wo"] \
            + p["bo"]
    else:
        y = (jax.nn.silu(h @ p["wg"]) * (h @ p["wi"])) @ p["wo"]
    if cfg.post_block_norm:
        y = rmsnorm(y, p["post_norm"], cfg.norm_eps)
    return x + y
