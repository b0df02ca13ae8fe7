"""Flash attention vs naive oracle; decode/cache semantics."""
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.models.attention import (chunk_attend, decode_attend,
                                    flash_attention, init_kv_cache, kv_group,
                                    kv_heads, kv_rows, update_cache,
                                    update_cache_chunk)
from repro.configs.base import AttentionConfig


def naive_attention(q, k, v, *, causal=True, window=None, q_offset=0):
    B, Sq, K, G, hd = q.shape
    Skv = k.shape[1]
    s = jnp.einsum("bqkgh,bskh->bkgqs", q.astype(jnp.float32),
                   k.astype(jnp.float32)) / math.sqrt(hd)
    qpos = q_offset + jnp.arange(Sq)
    kpos = jnp.arange(Skv)
    mask = jnp.ones((Sq, Skv), bool)
    if causal:
        mask &= qpos[:, None] >= kpos[None, :]
    if window is not None:
        mask &= (qpos[:, None] - kpos[None, :]) < window
    s = jnp.where(mask, s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bkgqs,bskh->bkgqh", p, v.astype(jnp.float32))
    return o.transpose(0, 3, 1, 2, 4)


@pytest.mark.parametrize("cfg", [
    dict(B=2, Sq=128, Skv=128, K=2, G=2, hd=16, causal=True, window=None),
    dict(B=1, Sq=256, Skv=256, K=1, G=4, hd=32, causal=True, window=64),
    dict(B=2, Sq=64, Skv=128, K=2, G=1, hd=16, causal=False, window=None),
])
def test_flash_matches_naive(cfg):
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (cfg["B"], cfg["Sq"], cfg["K"], cfg["G"],
                                  cfg["hd"]), jnp.float32)
    k = jax.random.normal(ks[1], (cfg["B"], cfg["Skv"], cfg["K"], cfg["hd"]))
    v = jax.random.normal(ks[2], (cfg["B"], cfg["Skv"], cfg["K"], cfg["hd"]))
    out = flash_attention(q, k, v, causal=cfg["causal"], window=cfg["window"])
    ref = naive_attention(q, k, v, causal=cfg["causal"], window=cfg["window"])
    np.testing.assert_allclose(np.asarray(out, np.float32), np.asarray(ref),
                               rtol=2e-4, atol=2e-4)


def test_flash_grad_matches_naive():
    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    q = jax.random.normal(ks[0], (1, 64, 1, 2, 16), jnp.float32)
    k = jax.random.normal(ks[1], (1, 64, 1, 16))
    v = jax.random.normal(ks[2], (1, 64, 1, 16))
    g1 = jax.grad(lambda q: jnp.sum(flash_attention(q, k, v) ** 2))(q)
    g2 = jax.grad(lambda q: jnp.sum(naive_attention(q, k, v) ** 2))(q)
    np.testing.assert_allclose(np.asarray(g1), np.asarray(g2),
                               rtol=1e-3, atol=1e-3)


def test_decode_attend_matches_full_recompute():
    """Decoding token-by-token == full causal attention row by row."""
    a = AttentionConfig(n_heads=4, n_kv_heads=2, head_dim=16)
    B, S = 2, 12
    ks = jax.random.split(jax.random.PRNGKey(2), 3)
    q = jax.random.normal(ks[0], (B, S, 2, 2, 16), jnp.float32)
    k = jax.random.normal(ks[1], (B, S, 2, 16))
    v = jax.random.normal(ks[2], (B, S, 2, 16))
    full = naive_attention(q, k, v, causal=True)
    cache = init_kv_cache(a, B, S, dtype=jnp.float32)
    for t in range(S):
        pos = jnp.full((B,), t, jnp.int32)
        cache = update_cache(cache, k[:, t], v[:, t], pos)
        out = decode_attend(q[:, t], cache["k"], cache["v"], cache["pos"],
                            pos)
        np.testing.assert_allclose(np.asarray(out), np.asarray(full[:, t]),
                                   rtol=1e-4, atol=1e-4)


def test_ring_buffer_window_decode():
    """Windowed cache is a ring buffer: O(window) memory at any length."""
    a = AttentionConfig(n_heads=2, n_kv_heads=2, head_dim=8, window=4)
    B = 1
    cache = init_kv_cache(a, B, length=100, dtype=jnp.float32)
    assert cache["k"].shape[1] == 4        # ring of `window`, not length
    ks = jax.random.split(jax.random.PRNGKey(3), 2)
    k = jax.random.normal(ks[0], (B, 10, 2, 8))
    v = jax.random.normal(ks[1], (B, 10, 2, 8))
    q = jax.random.normal(jax.random.PRNGKey(4), (B, 10, 2, 1, 8))
    fullq = q
    full = naive_attention(fullq, k, v, causal=True, window=4)
    for t in range(10):
        pos = jnp.full((B,), t, jnp.int32)
        cache = update_cache(cache, k[:, t], v[:, t], pos)
        out = decode_attend(q[:, t], cache["k"], cache["v"], cache["pos"],
                            pos, window=4)
        np.testing.assert_allclose(np.asarray(out), np.asarray(full[:, t]),
                                   rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("K,G,hd,group", [
    (2, 7, 64, 2),      # qwen2-0.5b: both KV heads share one 128-lane row
    (4, 2, 64, 2),      # two rows of two heads
    (2, 1, 128, 1),     # head_dim fills the lanes: one head a row
    (3, 2, 64, 1),      # an odd head count cannot pair up
])
def test_kv_row_groups_match_naive(K, G, hd, group):
    """Cache rows hold ``kv_group`` KV heads side by side; single-token
    decode, the chunk path, and a masked in-place write into a stacked
    cache all read and write each head's own lanes."""
    assert kv_group(K, hd) == group
    a = AttentionConfig(n_heads=K * G, n_kv_heads=K, head_dim=hd)
    B, S = 2, 6
    ks = jax.random.split(jax.random.PRNGKey(6), 3)
    q = jax.random.normal(ks[0], (B, S, K, G, hd), jnp.float32)
    k = jax.random.normal(ks[1], (B, S, K, hd))
    v = jax.random.normal(ks[2], (B, S, K, hd))
    full = np.asarray(naive_attention(q, k, v, causal=True))
    assert np.array_equal(np.asarray(kv_heads(kv_rows(k), K)), np.asarray(k))

    cache = init_kv_cache(a, B, S, dtype=jnp.float32)
    assert cache["k"].shape == (B, S, K // group, group * hd)
    for t in range(S):
        pos = jnp.full((B,), t, jnp.int32)
        cache = update_cache(cache, k[:, t], v[:, t], pos)
        out = decode_attend(q[:, t], cache["k"], cache["v"], cache["pos"],
                            pos)
        np.testing.assert_allclose(np.asarray(out), full[:, t],
                                   rtol=1e-4, atol=1e-4)
    assert np.array_equal(np.asarray(cache["k"]), np.asarray(kv_rows(k)))

    pos = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
    c = update_cache_chunk(init_kv_cache(a, B, S, dtype=jnp.float32),
                           k, v, pos)
    out = chunk_attend(q, c["k"], c["v"], c["pos"], pos)
    np.testing.assert_allclose(np.asarray(out), full, rtol=1e-4, atol=1e-4)

    # layer 1 of a two-layer stack, row 0 active, row 1 masked off
    stack = jax.tree.map(lambda x: jnp.stack([x, x + 1]), cache)
    new = update_cache(stack, -k[:, 0], -v[:, 0], jnp.asarray([2, 3]),
                       layer=jnp.int32(1), active=jnp.asarray([True, False]))
    want = np.asarray(stack["k"]).copy()
    want[1, 0, 2] = np.asarray(kv_rows(-k[:, :1]))[0, 0]
    assert np.array_equal(np.asarray(new["k"]), want)
    assert np.asarray(new["pos"])[1, 0, 2] == 2
    assert np.array_equal(np.asarray(new["pos"])[:, 1],
                          np.asarray(stack["pos"])[:, 1])


@pytest.mark.parametrize("K,hd", [(16, 128), (16, 64), (2, 64)])
def test_attend_flops_grow_with_the_lane_group_not_the_heads(K, hd):
    """The block-diagonal query spans one cache row's ``kv_group`` heads
    only: decode attention costs at most that factor over the plain
    per-head contraction (2 x 2 x B x H x S x hd), whatever K is."""
    B, G, S = 2, 2, 256
    args = (jax.ShapeDtypeStruct((B, K, G, hd), jnp.bfloat16),
            *[jax.ShapeDtypeStruct((B, S, K // kv_group(K, hd),
                                    kv_group(K, hd) * hd), jnp.bfloat16)] * 2,
            jax.ShapeDtypeStruct((B, S), jnp.int32),
            jax.ShapeDtypeStruct((B,), jnp.int32))
    cost = jax.jit(decode_attend).lower(*args).compile().cost_analysis()
    cost = cost[0] if isinstance(cost, list) else cost
    plain = 2 * 2 * B * K * G * S * hd
    assert plain <= cost["flops"] < 1.25 * kv_group(K, hd) * plain
