"""Granite 20B Code — a GPT-BigCode decoder with multi-query attention.

[arXiv:2405.04324, Table 1; hf:ibm-granite/granite-20b-code-base-8k,
config.json: model_type gpt_bigcode, multi_query true] 52 layers,
d_model 6144, 48 query heads of 128 sharing one K/V head, MLP width 24576,
vocab 49152, 8192 learned absolute positions, tied head.

The block (GPT-BigCode):

    x = wte[tokens] + wpe[positions]
    per layer:  h = LayerNorm(x)                       (weight and bias)
                q, k, v = h Wq + bq, h Wk + bk, h Wv + bv  (48 / 1 / 1 heads)
                x = x + softmax(q k^T / sqrt(128), causal) v Wo + bo
                h = LayerNorm(x)
                x = x + gelu_tanh(h W1 + b1) W2 + b2    (not gated)
    logits = LayerNorm(x) wte^T

Assumed where the sources are silent: GELU in its tanh form
(``gelu_pytorch_tanh``, GPT-BigCode's default; the paper says "GELU") and
LayerNorm epsilon 1e-5.
"""
from repro.configs.base import ModelConfig

CONFIG = ModelConfig(
    name="granite-20b",
    arch_type="dense",
    source="arXiv:2405.04324; hf:ibm-granite/granite-20b-code-base-8k",
    num_layers=52,
    d_model=6144,
    num_heads=48,
    num_kv_heads=1,
    head_dim=128,
    d_ff=24576,
    vocab_size=49152,
    attention_bias=True,
    attention_out_bias=True,
    norm_type="layernorm",
    norm_eps=1e-5,
    mlp_type="gelu",
    learned_positions=8192,
    tie_embeddings=True,
)

TINY = CONFIG.replace(
    name="granite-20b-tiny",
    num_layers=2,
    d_model=128,
    num_heads=8,
    num_kv_heads=1,
    head_dim=16,
    d_ff=512,
    vocab_size=512,
    learned_positions=256,
)
