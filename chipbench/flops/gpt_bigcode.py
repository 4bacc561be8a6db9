"""Operations and bytes that one chip of a tensor-parallel GPT-BigCode
decoder (Granite 20B Code) needs, from the configuration file and the
traffic's shapes alone (never from the compiled program), for the
per-layer metrics' rooflines.  A configuration names this file by its key
``"flops": "gpt_bigcode"``.

The layout is the configuration's ``tensor_parallel`` (tp, 1 if absent):
every layer is shared by tp chips.  A chip holds and computes a tp-th of
the split weights (Wq and bq, Wo, W1 and b1, W2 by heads or width; the
tied head by vocabulary), and the replicated ones whole (Wk, Wv and their
biases, bo, b2, the norms, the position table): with one K/V head, every
chip computes k and v itself.  The decode cache's positions are split
over the tp chips in contiguous parts of capacity / tp, the first chip's
from position 0.  The readers measure the first chip, so the decode
counts are that chip's: it attends with every query head over the live
positions it holds (``held``) and writes the new entries that land
there.  At tp 1 every count is the whole model's.

A multiply-add counts 2 operations.  Work that an implementation may do on
top (padding, recomputation, copies, collectives) is not counted;
elementwise work, norms and softmax are left out as small.  Bytes count
each tensor that the step must read or write once: weights, the live
cache read, the new cache entries written, the logits.
"""

from __future__ import annotations

DTYPE_BYTES = {"bfloat16": 2, "float16": 2, "float32": 4}


def dims(conf: dict):
    return (int(conf["n_layer"]), int(conf["n_embd"]), int(conf["n_head"]),
            int(conf["num_key_value_heads"]), int(conf["head_dim"]),
            int(conf["n_inner"]), int(conf["vocab_size"]),
            int(conf["n_positions"]), int(conf.get("tensor_parallel", 1)))


def layer_matmul(conf: dict) -> int:
    """Weights of one layer's matmuls that one chip multiplies by."""
    L, d, H, KV, hd, ff, V, NP, tp = dims(conf)
    return (d * H * hd + H * hd * d + 2 * d * ff) // tp + 2 * d * KV * hd


def layer_params(conf: dict) -> int:
    """Parameters of one layer that one chip holds."""
    L, d, H, KV, hd, ff, V, NP, tp = dims(conf)
    split = d * H * hd + H * hd + H * hd * d + 2 * d * ff + ff
    whole = 2 * d * KV * hd + 2 * KV * hd + d + d + 4 * d
    return split // tp + whole


def params(conf: dict) -> int:
    """Parameters one chip holds: its layers, its slice of the tied
    embedding, the position table and the final norm."""
    L, d, H, KV, hd, ff, V, NP, tp = dims(conf)
    return L * layer_params(conf) + V * d // tp + NP * d + 2 * d


def causal_pairs(P: int) -> int:
    """(query, key) pairs of causal attention over P positions."""
    return P * (P + 1) // 2


def flash_attention(conf: dict, B: int, P: int) -> tuple:
    """One chip's causal self-attention in a prefill of B x P, every
    layer, with its H / tp query heads against the K/V head: (operations,
    bytes of q, k, v and o)."""
    L, d, H, KV, hd, ff, V, NP, tp = dims(conf)
    ops = L * 4 * B * H * hd * causal_pairs(P) // tp
    byts = L * B * P * (2 * H // tp + 2 * KV) * hd * DTYPE_BYTES[conf["dtype"]]
    return ops, byts


def held(conf: dict, capacity: int, live: int) -> int:
    """Live positions (the new one included) that the first chip holds of
    a cache of ``capacity`` positions."""
    tp = int(conf.get("tensor_parallel", 1))
    return min(live, capacity // tp)


def decode_attention(conf: dict, B: int, held: int) -> tuple:
    """One chip's attention of one token per sequence against the ``held``
    live positions it holds, every layer and every query head: (operations,
    bytes of K/V read, q, o)."""
    L, d, H, KV, hd, ff, V, NP, tp = dims(conf)
    ops = L * 4 * B * H * hd * held
    byts = L * B * (2 * KV * held + 2 * H) * hd * DTYPE_BYTES[conf["dtype"]]
    return ops, byts


def prefill(conf: dict, B: int, P: int) -> int:
    """One chip's operations in a prefill of B x P: its matmuls, its causal
    attention, and its slice of the head at the last position only."""
    L, d, H, KV, hd, ff, V, NP, tp = dims(conf)
    return (2 * B * P * L * layer_matmul(conf)
            + flash_attention(conf, B, P)[0] + 2 * B * d * V // tp)


def decode(conf: dict, B: int, capacity: int, live: int) -> tuple:
    """The first chip's decode step at ``live`` positions of a cache of
    ``capacity``: (operations, bytes).  Bytes: every weight it holds but
    the position table, of which it reads the one row of the step's
    position (a batch's sequences share it), the live K/V it holds, the
    new K/V if its position lands on this chip, and the float32 logits of
    its vocabulary slice."""
    L, d, H, KV, hd, ff, V, NP, tp = dims(conf)
    b = DTYPE_BYTES[conf["dtype"]]
    n = held(conf, capacity, live)
    ops = 2 * B * (L * layer_matmul(conf) + d * V // tp) \
        + decode_attention(conf, B, n)[0]
    new = L * 2 * B * KV * hd * b if live <= capacity // tp else 0
    byts = ((params(conf) - (NP - 1) * d) * b
            + L * 2 * B * KV * n * hd * b + new + B * V * 4 // tp)
    return ops, byts
