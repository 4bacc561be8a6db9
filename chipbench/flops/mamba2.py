"""Operations and bytes that Mamba2 (arXiv:2405.21060) needs, from the
configuration file and the traffic's shapes alone (never from the compiled
program), for the per-layer metrics' rooflines.  A configuration names
this file by its key ``"flops": "mamba2"``.

A multiply-add counts 2 operations.  The state recurrence counts at its
minimum (state update and readout, one token at a time), not the chunked
form an implementation may use; elementwise work and norms are left out as
small.  Bytes count each tensor that the step must read or write once:
weights, the float32 state and the convolution window read and written,
the logits.
"""

from __future__ import annotations

DTYPE_BYTES = {"bfloat16": 2, "float16": 2, "float32": 4}


def dims(conf: dict):
    s = conf["ssm_cfg"]
    d = int(conf["d_model"])
    d_in = int(s["expand"]) * d
    P, N = int(s["headdim"]), int(s["d_state"])
    G = int(s["ngroups"])
    return (int(conf["n_layer"]), d, d_in, d_in // P, P, N, G,
            int(s["d_conv"]), int(conf["vocab_size"]))


def layer_params(conf: dict) -> int:
    L, d, d_in, H, P, N, G, W, V = dims(conf)
    return d * (2 * d_in + 2 * G * N + H) + d_in * d


def params(conf: dict) -> int:
    L, d, d_in, H, P, N, G, W, V = dims(conf)
    conv = (d_in + 2 * G * N) * (W + 1)
    per_layer = layer_params(conf) + conv + 3 * H + d_in + d
    head = 0 if conf["tie_embeddings"] else d * V
    return L * per_layer + V * d + head + d


def ssd_token(conf: dict) -> int:
    """Operations of one token through one layer's state recurrence at its
    minimum (state update and readout) and its causal convolution."""
    L, d, d_in, H, P, N, G, W, V = dims(conf)
    return 4 * H * P * N + 2 * W * (d_in + 2 * G * N)


def prefill(conf: dict, B: int, P: int) -> int:
    """A prefill of B x P: every projection, the recurrence, and the head at
    the last position only."""
    L, d, d_in, H, Ph, N, G, W, V = dims(conf)
    return (2 * B * P * L * layer_params(conf)
            + B * P * L * ssd_token(conf) + 2 * B * d * V)


def decode(conf: dict, B: int, live: int = 0) -> tuple:
    """One decode step: (operations, bytes).  ``live`` is unused: the state
    does not grow."""
    L, d, d_in, H, P, N, G, W, V = dims(conf)
    ops = 2 * B * (L * layer_params(conf) + d * V) + B * L * ssd_token(conf)
    state = L * B * H * P * N * 4
    conv = L * B * (W - 1) * (d_in + 2 * G * N) * DTYPE_BYTES[conf["dtype"]]
    byts = (params(conf) * DTYPE_BYTES[conf["dtype"]] + 2 * (state + conv)
            + B * V * 4)
    return ops, byts
