"""The operation and byte counts of each work model ``flops/<name>.py``
against arithmetic done by hand, one shape each."""

from __future__ import annotations

import json

from chipbench import harness

SMOLLM = json.loads((harness.HERE / "configs" / "smollm-360m.json").read_text())
# mamba2-1.3b's published widths, with the 50280 embedding rows of the
# program's configuration
MAMBA2 = {"n_layer": 48, "d_model": 2048, "vocab_size": 50280,
          "ssm_cfg": {"d_state": 128, "d_conv": 4, "expand": 2,
                      "headdim": 64, "ngroups": 1},
          "tie_embeddings": True, "dtype": "bfloat16"}


def work(name):
    return harness.load_module(harness.HERE / "flops" / f"{name}.py")


dense, mamba2 = work("dense_gqa"), work("mamba2")


def test_dense_decode_one_token():
    # per layer: q 960*15*64 = 921,600; k, v 2*960*5*64 = 614,400;
    # o 921,600; gate/up/down 3*960*2560 = 7,372,800 -> 9,830,400
    # 32 layers 314,572,800 + tied head 49152*960 = 47,185,920
    # ops: 2 * 361,758,720 + attention 4*15*64*1 live*32 = 122,880
    ops, byts = dense.decode(SMOLLM, 1, 1)
    assert ops == 723_517_440 + 122_880
    # weights 361,758,720 + norms 2*960*32 + 960 = 62,400 -> 361,821,120
    # * 2 bytes = 723,642,240; K/V read 32*2*5*1*64*2 = 40,960; K/V
    # written 40,960; float32 logits 49152*4 = 196,608
    assert byts == 723_642_240 + 40_960 + 40_960 + 196_608


def test_flash_attention_prefill_of_four():
    # causal pairs over 4 positions: 10; 4*15*64*10 per layer, 32 layers
    ops, byts = dense.flash_attention(SMOLLM, 1, 4)
    assert ops == 32 * 4 * 15 * 64 * 10 == 1_228_800
    # q and o 2*15 heads, k and v 2*5 heads, 4 positions, 64 wide, 2 bytes
    assert byts == 32 * 4 * (30 + 10) * 64 * 2 == 655_360


def test_mamba2_decode_one_token():
    # in_proj 2048*(2*4096 + 2*128 + 64) = 17,432,576; out_proj
    # 4096*2048 = 8,388,608 -> 25,821,184 a layer, 48 layers
    # 1,239,416,832; tied head 2048*50280 = 102,973,440
    # recurrence 4*64*64*128 = 2,097,152 and conv 2*4*(4096+256) = 34,816
    # a token a layer -> 48 * 2,131,968 = 102,334,464
    ops, byts = mamba2.decode(MAMBA2, 1, 0)
    assert ops == 2 * (1_239_416_832 + 102_973_440) + 102_334_464
    # state 48*64*64*128*4 = 100,663,296 and conv window 48*3*4352*2 =
    # 1,253,376, each read and written; logits 50280*4 = 201,120
    weights = mamba2.params(MAMBA2) * 2
    assert byts == weights + 2 * (100_663_296 + 1_253_376) + 201_120


def test_prefill_counts_last_position_head_only():
    B, P = 2, 8
    assert dense.prefill(SMOLLM, B, P) == (
        2 * B * P * 32 * 9_830_400 + dense.flash_attention(SMOLLM, B, P)[0]
        + 2 * B * 960 * 49152)
