"""Encoder-decoder transformer (Whisper-style speech backbone).

The mel-spectrogram + conv feature extractor is stubbed per the brief:
``input_specs`` supplies precomputed frame embeddings (B, encoder_seq_len,
d_model).  The encoder is bidirectional; the decoder has causal self-attention
(RoPE, cached at decode) plus cross-attention over per-layer encoder K/V that
are computed once at prefill and stored in the cache (by
``distributed.cache_update``, from what prefill and decode return).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from repro.models import layers as L

Array = jax.Array


def init_params(rng, cfg) -> dict:
    dt = jnp.dtype(cfg.dtype)
    k_embed, k_enc, k_dec, k_head = jax.random.split(rng, 4)

    def enc_layer(k):
        k1, k2 = jax.random.split(k)
        return {"attn": L.init_attn_block(k1, cfg), "mlp": L.init_mlp(k2, cfg)}

    def dec_layer(k):
        k1, k2, k3 = jax.random.split(k, 3)
        return {"attn": L.init_attn_block(k1, cfg),
                "cross": L.init_attn_block(k2, cfg, cross=True),
                "mlp": L.init_mlp(k3, cfg)}

    enc_keys = jax.random.split(k_enc, cfg.encoder_layers)
    dec_keys = jax.random.split(k_dec, cfg.num_layers)
    params = {
        "embed": (jax.random.normal(k_embed, (cfg.vocab_size, cfg.d_model)) * 0.02
                  ).astype(dt),
        "enc_layers": jax.vmap(enc_layer)(enc_keys),
        "dec_layers": jax.vmap(dec_layer)(dec_keys),
        "enc_norm": jnp.ones((cfg.d_model,), dt),
        "final_norm": jnp.ones((cfg.d_model,), dt),
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = (jax.random.normal(k_head, (cfg.d_model, cfg.vocab_size))
                             * 0.02).astype(dt)
    return params


def encode(params, audio_embeds: Array, cfg, bspec=None) -> Array:
    """audio_embeds: (B, S_enc, d) stubbed frontend output -> encoder states."""
    x = L.constrain_batch(audio_embeds.astype(jnp.dtype(cfg.dtype)), bspec)
    positions = jnp.arange(x.shape[1])

    def body(carry, lp):
        y, _ = L.attn_block_apply(lp["attn"], carry, cfg, causal=False,
                                  positions=positions, mode="train")
        y = L.mlp_apply(lp["mlp"], y, cfg)
        return y, None

    x, _ = lax.scan(body, x, params["enc_layers"])
    return L.rmsnorm(x, params["enc_norm"], cfg.norm_eps)


def _decoder_trunk(params, x, cfg, enc_out, *, mode, cache=None, pos=None,
                   positions=None, remat=False, bspec=None):
    """Runs decoder layers.  For prefill/decode the cache is
    {'k','v': (L,B,KV,cap,hd), 'ck','cv': (L,B,S_enc,KV,hd)}."""
    if mode == "train":
        def body(carry, lp):
            carry = L.constrain_batch(carry, bspec)
            y, _ = L.attn_block_apply(lp["attn"], carry, cfg, mode="train",
                                      positions=positions)
            enc_kv = L.encode_kv(lp["cross"], enc_out, cfg)
            y = L.cross_attn_apply(lp["cross"], y, enc_kv, cfg)
            y = L.mlp_apply(lp["mlp"], y, cfg)
            return y, None
        if remat:
            body = jax.checkpoint(body)
        x, _ = lax.scan(body, x, params["dec_layers"])
        return x, None

    if mode == "prefill":
        def body(carry, lp):
            y, kv = L.attn_block_apply(lp["attn"], carry, cfg, mode="prefill",
                                       positions=positions)
            enc_kv = L.encode_kv(lp["cross"], enc_out, cfg)
            y = L.cross_attn_apply(lp["cross"], y, enc_kv, cfg)
            y = L.mlp_apply(lp["mlp"], y, cfg)
            return y, (kv["k"], kv["v"], enc_kv["k"], enc_kv["v"])
        x, (ks, vs, cks, cvs) = lax.scan(body, x, params["dec_layers"])
        return x, (ks, vs, cks, cvs)

    # decode (append-outside-scan: bodies emit K/V deltas)
    def body(carry, inp):
        lp, k_l, v_l, ck, cv = inp
        y, kv = L.attn_block_apply(lp["attn"], carry, cfg, mode="decode",
                                   cache={"k": k_l, "v": v_l}, cache_pos=pos,
                                   positions=pos[None])
        y = L.cross_attn_apply(lp["cross"], y, {"k": ck, "v": cv}, cfg)
        y = L.mlp_apply(lp["mlp"], y, cfg)
        return y, (kv["k"], kv["v"])

    x, (dk, dv) = lax.scan(body, x, (params["dec_layers"], cache["k"], cache["v"],
                                     cache["ck"], cache["cv"]))
    return x, (dk, dv)


def train_loss(params, batch, cfg, *, remat=True, bspec=None):
    """batch: {'tokens': (B,T), 'audio_embeds': (B,S_enc,d)}."""
    from repro.models.transformer import chunked_ce_loss
    tokens = batch["tokens"]
    enc_out = encode(params, batch["audio_embeds"], cfg, bspec)
    x = L.constrain_batch(params["embed"][tokens].astype(jnp.dtype(cfg.dtype)),
                          bspec)
    positions = jnp.arange(tokens.shape[1])
    h, _ = _decoder_trunk(params, x, cfg, enc_out, mode="train",
                          positions=positions, remat=remat, bspec=bspec)
    h = L.rmsnorm(h, params["final_norm"], cfg.norm_eps)
    labels = jnp.roll(tokens, -1, axis=1)
    mask = jnp.ones_like(tokens, jnp.float32).at[:, -1].set(0.0)
    ce = chunked_ce_loss(params, h, labels, mask, cfg)
    return ce, {"ce": ce, "aux": jnp.zeros((), jnp.float32)}


def prefill(params, batch, cfg, bspec=None):
    """Returns (last_logits, new): the cache's tree with the prompt's K/V
    (L, B, KV, T, hd) and the cross K/V whole."""
    from repro.models.transformer import logits_last
    tokens = batch["tokens"]
    enc_out = encode(params, batch["audio_embeds"], cfg, bspec)
    x = L.constrain_batch(params["embed"][tokens].astype(jnp.dtype(cfg.dtype)),
                          bspec)
    positions = jnp.arange(tokens.shape[1])
    h, (ks, vs, cks, cvs) = _decoder_trunk(params, x, cfg, enc_out,
                                           mode="prefill", positions=positions)
    h = L.rmsnorm(h, params["final_norm"], cfg.norm_eps)
    new = {"k": ks.transpose(0, 1, 3, 2, 4), "v": vs.transpose(0, 1, 3, 2, 4),
           "ck": cks, "cv": cvs}
    return logits_last(params, h[:, -1], cfg), new


def init_cache(cfg, batch: int, capacity: int) -> dict:
    dt = jnp.dtype(cfg.dtype)
    KV, hd = cfg.num_kv_heads, cfg.head_dim
    Ld, Se = cfg.num_layers, cfg.encoder_seq_len
    return {
        "k": jnp.zeros((Ld, batch, KV, capacity, hd), dt),
        "v": jnp.zeros((Ld, batch, KV, capacity, hd), dt),
        "ck": jnp.zeros((Ld, batch, Se, KV, hd), dt),
        "cv": jnp.zeros((Ld, batch, Se, KV, hd), dt),
    }


def decode_step(params, cache, tokens, pos, cfg, bspec=None):
    """Returns (logits, new): the cache's tree with the tokens' K/V
    (L, B, KV, 1, hd) and the cross K/V unchanged."""
    from repro.models.transformer import logits_last
    x = L.constrain_batch(params["embed"][tokens[:, None]].astype(jnp.dtype(cfg.dtype)),
                          bspec)
    h, (dk, dv) = _decoder_trunk(params, x, cfg, None, mode="decode",
                                 cache=cache, pos=pos)
    h = L.rmsnorm(h, params["final_norm"], cfg.norm_eps)
    new = {"k": dk, "v": dv, "ck": cache["ck"], "cv": cache["cv"]}
    return logits_last(params, h[:, 0], cfg), new
