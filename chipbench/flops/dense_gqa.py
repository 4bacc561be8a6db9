"""Operations and bytes that a dense decoder with grouped-query attention
(Llama family: SmolLM) needs, from the configuration file and the
traffic's shapes alone (never from the compiled program), for the
per-layer metrics' rooflines.  A configuration names this file by its key
``"flops": "dense_gqa"``.

A multiply-add counts 2 operations.  Work that an implementation may do on
top (padding, recomputation, copies) is not counted; elementwise work,
norms and softmax are left out as small.  Bytes count each tensor that the
step must read or write once: weights, the live cache read, the new cache
entries written, the logits.
"""

from __future__ import annotations

DTYPE_BYTES = {"bfloat16": 2, "float16": 2, "float32": 4}


def dims(conf: dict):
    return (int(conf["num_hidden_layers"]), int(conf["hidden_size"]),
            int(conf["num_attention_heads"]), int(conf["num_key_value_heads"]),
            int(conf["head_dim"]), int(conf["intermediate_size"]),
            int(conf["vocab_size"]))


def layer_params(conf: dict) -> int:
    L, d, H, KV, hd, ff, V = dims(conf)
    return d * H * hd + 2 * d * KV * hd + H * hd * d + 3 * d * ff


def params(conf: dict) -> int:
    L, d, H, KV, hd, ff, V = dims(conf)
    norms = 2 * d * L + d
    head = 0 if conf["tie_word_embeddings"] else d * V
    return L * layer_params(conf) + V * d + head + norms


def causal_pairs(P: int) -> int:
    """(query, key) pairs of causal attention over P positions."""
    return P * (P + 1) // 2


def flash_attention(conf: dict, B: int, P: int) -> tuple:
    """Causal self-attention of a prefill of B x P, every layer:
    (operations, bytes of q, k, v and o)."""
    L, d, H, KV, hd, ff, V = dims(conf)
    ops = L * 4 * B * H * hd * causal_pairs(P)
    byts = L * B * P * (2 * H + 2 * KV) * hd * DTYPE_BYTES[conf["dtype"]]
    return ops, byts


def decode_attention(conf: dict, B: int, live: int) -> tuple:
    """One token per sequence against ``live`` cached positions (the new
    one included), every layer: (operations, bytes of K/V read, q, o)."""
    L, d, H, KV, hd, ff, V = dims(conf)
    ops = L * 4 * B * H * hd * live
    byts = L * B * (2 * KV * live + 2 * H) * hd * DTYPE_BYTES[conf["dtype"]]
    return ops, byts


def prefill(conf: dict, B: int, P: int) -> int:
    """A prefill of B x P: every matmul, causal attention, and the head at
    the last position only."""
    L, d, H, KV, hd, ff, V = dims(conf)
    return (2 * B * P * L * layer_params(conf)
            + flash_attention(conf, B, P)[0] + 2 * B * d * V)


def decode(conf: dict, B: int, live: int) -> tuple:
    """One decode step: (operations, bytes).  Bytes: every weight, the live
    K/V read, the new K/V written, the float32 logits written."""
    L, d, H, KV, hd, ff, V = dims(conf)
    b = DTYPE_BYTES[conf["dtype"]]
    ops = 2 * B * (L * layer_params(conf) + d * V) \
        + decode_attention(conf, B, live)[0]
    byts = (params(conf) * b
            + L * 2 * B * KV * live * hd * b
            + L * 2 * B * KV * hd * b
            + B * V * 4)
    return ops, byts
