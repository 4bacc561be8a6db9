"""Paged decode-attention vs the ragged-batch oracle, plus the kv-major
wrapper on the ragged-adjacent shapes the paged variant stresses."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.decode_attention.decode_attention import _live_tiles
from repro.kernels.decode_attention.ops import (cache_is_s_minor,
                                                decode_attention_kvmajor,
                                                decode_attention_stacked,
                                                decode_tiling,
                                                paged_decode_attention,
                                                resolve_page_size)
from repro.perf import autotune
from repro.kernels.decode_attention.ref import (decode_attention_ref,
                                                decode_attention_ref_ragged)


def _rand(key, shape, dtype):
    x = jax.random.normal(key, shape, jnp.float32) * 0.5
    return x.astype(dtype)


def _paged_from_dense(k_cache, v_cache, page_size, *, shuffle_key=None):
    """Chop a dense (B, S, KV, hd) cache into a (P, psz, KV, hd) pool and a
    block table; optionally scatter the pages so the table indirection is
    actually exercised."""
    B, S, KV, hd = k_cache.shape
    ns = S // page_size
    P = B * ns
    kp = k_cache.reshape(B, ns, page_size, KV, hd).reshape(P, page_size, KV, hd)
    vp = v_cache.reshape(B, ns, page_size, KV, hd).reshape(P, page_size, KV, hd)
    tbl = jnp.arange(P, dtype=jnp.int32).reshape(B, ns)
    if shuffle_key is not None:
        perm = jax.random.permutation(shuffle_key, P)
        inv = jnp.argsort(perm)
        kp, vp = kp[perm], vp[perm]
        tbl = inv.reshape(B, ns)
    return kp, vp, tbl


PAGED_CASES = [
    # (B, S, H, KV, hd, psz, lens, window, cap)
    (4, 512, 8, 2, 64, 64, (512, 300, 37, 1), None, None),   # ragged
    (1, 256, 4, 1, 128, 64, (200,), None, None),             # single slot, MQA
    (3, 384, 6, 3, 64, 128, (384, 129, 64), None, None),     # non-pow2 heads
    (2, 512, 8, 2, 64, 64, (500, 90), 128, None),            # sliding window
    (2, 256, 4, 4, 32, 32, (250, 31), None, 50.0),           # logit cap
    (3, 256, 8, 2, 64, 64, (256, 0, 10), None, None),        # freed slot
]


@pytest.mark.parametrize("case", PAGED_CASES, ids=str)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_paged_decode_attention_matches_ragged_ref(case, dtype):
    B, S, H, KV, hd, psz, lens, window, cap = case
    ks = jax.random.split(jax.random.PRNGKey(11), 4)
    q = _rand(ks[0], (B, H, hd), dtype)
    k = _rand(ks[1], (B, S, KV, hd), dtype)
    v = _rand(ks[2], (B, S, KV, hd), dtype)
    kp, vp, tbl = _paged_from_dense(k, v, psz, shuffle_key=ks[3])
    lens = jnp.asarray(lens, jnp.int32)
    out = paged_decode_attention(q, kp, vp, lens, tbl,
                                 window=window, logit_cap=cap)
    ref = decode_attention_ref_ragged(q, k, v, lens,
                                      window=window, logit_cap=cap)
    tol = 2e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), atol=tol, rtol=tol)


def test_paged_matches_dense_ref_when_uniform():
    """With every slot at the same length, the ragged path must agree with
    the original positional oracle (cache valid on [0, pos])."""
    B, S, H, KV, hd, psz, pos = 2, 256, 8, 2, 64, 64, 199
    ks = jax.random.split(jax.random.PRNGKey(12), 3)
    q = _rand(ks[0], (B, H, hd), jnp.float32)
    k = _rand(ks[1], (B, S, KV, hd), jnp.float32)
    v = _rand(ks[2], (B, S, KV, hd), jnp.float32)
    kp, vp, tbl = _paged_from_dense(k, v, psz)
    lens = jnp.full((B,), pos + 1, jnp.int32)
    out = paged_decode_attention(q, kp, vp, lens, tbl)
    ref = decode_attention_ref(q, k, v, jnp.asarray(pos, jnp.int32))
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


def test_paged_ignores_garbage_in_unused_pages_and_table_entries():
    """Pages past a slot's length must not leak into the output even when
    the pool holds garbage there and the table points out of range."""
    B, S, H, KV, hd, psz = 2, 256, 4, 2, 64, 64
    ks = jax.random.split(jax.random.PRNGKey(13), 3)
    q = _rand(ks[0], (B, H, hd), jnp.float32)
    k = _rand(ks[1], (B, S, KV, hd), jnp.float32)
    v = _rand(ks[2], (B, S, KV, hd), jnp.float32)
    lens = jnp.asarray([70, 128], jnp.int32)
    ref = decode_attention_ref_ragged(q, k, v, lens)

    kp, vp, tbl = _paged_from_dense(k, v, psz)
    ns = S // psz
    # poison every page at-or-past each slot's length...
    used = (lens + psz - 1) // psz
    page_used = (jnp.arange(ns)[None, :] < used[:, None]).reshape(-1)
    kp = jnp.where(page_used[:, None, None, None], kp, 1e4)
    vp = jnp.where(page_used[:, None, None, None], vp, 1e4)
    # ...and point the unused table entries far out of the pool
    tbl = jnp.where(jnp.arange(ns)[None, :] < used[:, None], tbl, 10_000)
    out = paged_decode_attention(q, kp, vp, lens, tbl)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


def test_resolve_page_size_prefers_explicit_then_default():
    assert resolve_page_size(jnp.float32, B=4, H=8, KV=2, hd=64,
                             seq_budget=1024, page_size=32) == 32
    ps = resolve_page_size(jnp.float32, B=4, H=8, KV=2, hd=64,
                           seq_budget=1024)
    assert ps in (32, 64, 128, 256)


# --- satellite: kv-major wrapper on the shapes the paged variant stresses ---

KVMAJOR_CASES = [
    # (B, S, H, KV, hd, pos, window, cap[, tiling]) — ragged/odd kv_len,
    # non-pow2 heads, single-slot batches; an explicit tiling splits a small
    # cache into several row blocks and K tiles
    (2, 300, 8, 2, 64, 299, None, None),      # odd S: one whole-cache tile
    (3, 300, 6, 3, 64, 150, None, None),      # non-pow2 heads
    (1, 512, 4, 1, 128, 37, None, None),      # single slot, short kv_len
    (1, 640, 12, 3, 64, 633, 128, None),      # single slot + window
    (2, 384, 10, 5, 32, 65, None, 40.0),      # non-pow2 heads + cap
    (1, 256, 8, 2, 64, 0, None, None),        # single slot, first token
    (3, 1024, 9, 3, 64, 700, None, None),     # BKV 9: odd row count
    (2, 512, 6, 3, 64, 300, None, None,       # rows 4 does not divide
     {"rows": 4, "block_k": 128}),            # BKV 6: lowered to 3
    (2, 512, 8, 2, 64, 256, None, None,       # pos on a tile boundary
     {"rows": 2, "block_k": 128}),
    (2, 512, 8, 2, 64, 0, None, None,         # pos 0, three dead tiles
     {"rows": 4, "block_k": 128}),
    (2, 512, 8, 2, 64, 450, None, None,       # pos in the last tile
     {"rows": 2, "block_k": 128}),
    (2, 512, 8, 2, 64, 400, 200, None,        # window opens mid-tile
     {"rows": 4, "block_k": 128}),
    (2, 512, 15, 5, 64, 383, None, None),     # the benchmark's 15/5 x 64
    (2, 512, 15, 5, 64, 383, None, None,      # ...over several row blocks
     {"rows": 2, "block_k": 128}),
]


@pytest.mark.parametrize("case", KVMAJOR_CASES, ids=str)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_decode_attention_kvmajor_matches_ref(case, dtype):
    B, S, H, KV, hd, pos, window, cap, *tiling = case
    ks = jax.random.split(jax.random.PRNGKey(14), 3)
    q = _rand(ks[0], (B, H, hd), dtype)
    k = _rand(ks[1], (B, S, KV, hd), dtype)
    v = _rand(ks[2], (B, S, KV, hd), dtype)
    p = jnp.asarray(pos, jnp.int32)
    # the kv-major entry point takes the model's (B, KV, S, hd) layout
    out = decode_attention_kvmajor(q, k.transpose(0, 2, 1, 3),
                                   v.transpose(0, 2, 1, 3), p,
                                   window=window, logit_cap=cap,
                                   **(tiling[0] if tiling else {}))
    ref = decode_attention_ref(q, k, v, p, window=window, logit_cap=cap)
    tol = 2e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), atol=tol, rtol=tol)


@pytest.mark.parametrize("BKV", [320, 160])
def test_decode_tiling_streams_large_tiles_without_padding(BKV):
    """Both cells' decode shapes (B 64 and 32 x 5 KV heads, a 2048-position
    bf16 cache of 64-wide heads): whole tiles, at least 1 MB of K+V per
    grid step, within the VMEM budget."""
    t = decode_tiling(BKV, 2048, 3, 64, jnp.bfloat16)
    assert BKV % t.rows == 0 and 2048 % t.block_k == 0
    assert t.steps == (BKV // t.rows) * (2048 // t.block_k)
    assert t.step_bytes == 2 * t.rows * t.block_k * 64 * 2
    assert t.step_bytes >= 1 << 20
    assert t.vmem_bytes <= autotune.VMEM_BYTES


def test_decode_tiling_small_shapes_and_given_values():
    one = decode_tiling(1, 2048, 3, 64, jnp.bfloat16)
    assert one.rows == 1 and one.block_k == 2048     # the whole row
    assert decode_tiling(320, 2048, 3, 64, jnp.bfloat16,
                         block_k=256).block_k == 256  # a given value wins
    lowered = decode_tiling(6, 512, 2, 64, jnp.float32, rows=4, block_k=200)
    assert (lowered.rows, lowered.block_k) == (3, 128)
    odd = decode_tiling(4, 300, 2, 64, jnp.float32)   # no 8-aligned divisor
    assert odd.block_k == 300


@pytest.mark.parametrize("pos,window,tiles", [
    (0, None, (0, 0)), (127, None, (0, 0)), (128, None, (0, 1)),
    (511, None, (0, 3)), (400, 200, (1, 3)), (400, 144, (2, 3)),
    (100, 300, (0, 0)),
])
def test_decode_kv_index_map_stops_at_live_tiles(pos, window, tiles):
    """K/V tiles outside [first, last] are never fetched: the index map
    clamps the grid's K axis to them (block_k 128, four tiles)."""
    first, last = _live_tiles(jnp.asarray(pos), block_k=128, ns=4,
                              window=window)
    assert (int(first), int(last)) == tiles


@pytest.mark.parametrize("pos,window,tiles", [
    (128, None, (0, 0)), (129, None, (0, 1)), (0, None, (0, 0)),
    (400, 144, (2, 3)),
])
def test_decode_kv_index_map_ends_below_pos_with_the_new_token_apart(
        pos, window, tiles):
    """With the new token's K/V apart, the cache's live positions end at
    ``pos - 1``: a ``pos`` on a tile's first slot fetches no tile past the
    one before it."""
    first, last = _live_tiles(jnp.asarray(pos), block_k=128, ns=4,
                              window=window, new=True)
    assert (int(first), int(last)) == tiles


# --- the kernel over a layer group's stacked cache, the new token apart ---

STACKED_CASES = [
    # (hd, H, KV, S, pos, window, layer, tiling): hd 64 is read as the
    # S-minor view, hd 128 as the hd-minor one; G 3 (smollm) and G 48
    # (granite's MQA); block_k 128 makes pos 200 fall inside a tile, 255 on
    # a tile's last slot and 256 on the next tile's first
    (64, 15, 5, 512, 200, None, 0, {"rows": 2, "block_k": 128}),
    (64, 15, 5, 512, 255, None, 2, {"rows": 2, "block_k": 128}),
    (64, 15, 5, 512, 256, None, 1, {"rows": 5, "block_k": 128}),
    (64, 15, 5, 512, 511, None, 2, None),     # the cache's last slot
    (128, 48, 1, 384, 200, None, 0, {"rows": 1, "block_k": 128}),
    (128, 48, 1, 384, 255, None, 2, {"rows": 2, "block_k": 128}),
    (128, 48, 1, 384, 0, None, 1, None),      # the first token
    (64, 15, 5, 512, 300, 100, 2, {"rows": 2, "block_k": 128}),  # window
    (128, 48, 1, 384, 300, 200, 0, {"rows": 1, "block_k": 128}),
]


@pytest.mark.parametrize("case", STACKED_CASES, ids=str)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_decode_attention_stacked_matches_ref(case, dtype):
    """The kernel reads layer ``layer`` of a stacked (L, B, KV, S, hd) cache
    and the new token's K/V apart; the cache's slot ``pos`` holds a stale
    value that must not be read.  The plain reference sees that layer with
    the new token in its slot."""
    hd, H, KV, S, pos, window, layer, tiling = case
    B, L = 2, 3
    ks = jax.random.split(jax.random.PRNGKey(pos + layer), 5)
    q = _rand(ks[0], (B, H, hd), dtype)
    k = _rand(ks[1], (L, B, KV, S, hd), dtype)
    v = _rand(ks[2], (L, B, KV, S, hd), dtype)
    k_new = _rand(ks[3], (B, KV, 1, hd), dtype)
    v_new = _rand(ks[4], (B, KV, 1, hd), dtype)
    k = k.at[:, :, :, pos].set(100.0)           # stale: written after the step
    v = v.at[:, :, :, pos].set(100.0)
    p = jnp.asarray(pos, jnp.int32)
    out = decode_attention_stacked(q, k, v, p, jnp.asarray(layer, jnp.int32),
                                   k_new=k_new, v_new=v_new, window=window,
                                   **(tiling or {}))
    kl = k[layer].at[:, :, pos].set(k_new[:, :, 0]).transpose(0, 2, 1, 3)
    vl = v[layer].at[:, :, pos].set(v_new[:, :, 0]).transpose(0, 2, 1, 3)
    ref = decode_attention_ref(q, kl, vl, p, window=window)
    assert cache_is_s_minor(S, hd, dtype) == (hd == 64)
    tol = 2e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), atol=tol, rtol=tol)
