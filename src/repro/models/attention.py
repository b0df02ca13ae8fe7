"""GQA attention: flash-style chunked training/prefill + KV-cache decode.

Memory discipline matters here: prefill_32k would materialise S^2 logits
(32k^2 x batch) if written naively, and the dry-run's memory_analysis is
the proof-of-fit.  ``flash_attention`` therefore computes an online-softmax
over KV chunks (running max / denominator), i.e. the standard
flash-attention recurrence expressed in jnp; the Pallas kernel path
(kernels/) can replace the inner block later without changing callers.

Sliding-window masks (jamba) and non-causal mode (whisper encoder,
cross-attention) are handled by the same code path.  Decode uses a
single-token attend against the cache; windowed layers keep a ring-buffer
cache (O(window) memory at 500k context).
"""
from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp

from repro.configs.base import AttentionConfig, ModelConfig
from repro.models.layers import Sharder, apply_rope

NEG_INF = -1e30


def split_qkv(cfg: AttentionConfig, qkv: jax.Array,
              bias: Optional[jax.Array]) -> tuple:
    """qkv: (B, S, (H+2K)*hd) -> q (B,S,K,G,hd), k/v (B,S,K,hd)."""
    H, K, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    if bias is not None:
        qkv = qkv + bias.astype(qkv.dtype)
    q, k, v = jnp.split(qkv, [H * hd, (H + K) * hd], axis=-1)
    B, S = q.shape[:2]
    G = H // K
    q = q.reshape(B, S, K, G, hd)
    k = k.reshape(B, S, K, hd)
    v = v.reshape(B, S, K, hd)
    return q, k, v


def _pick_chunk(s: int, target: int = 1024) -> int:
    if s <= target:
        return s
    c = target
    while s % c:
        c //= 2
    return max(c, 1)


def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    causal: bool = True, window: Optional[int] = None,
                    q_offset: int = 0) -> jax.Array:
    """Online-softmax attention.

    q: (B, Sq, K, G, hd); k, v: (B, Skv, K, hd).  Returns (B, Sq, K, G, hd).
    q_offset: absolute position of q[0] relative to k[0] (cross/prefill=0).
    """
    B, Sq, K, G, hd = q.shape
    Skv = k.shape[1]
    scale = 1.0 / math.sqrt(hd)
    cq = _pick_chunk(Sq)
    ck = _pick_chunk(Skv)
    nq, nk = Sq // cq, Skv // ck

    q = q.astype(jnp.bfloat16) if q.dtype == jnp.bfloat16 else q
    qpos_all = q_offset + jnp.arange(Sq, dtype=jnp.int32)
    kpos_all = jnp.arange(Skv, dtype=jnp.int32)

    # (nq, B, cq, K, G, hd)
    qc = q.reshape(B, nq, cq, K, G, hd).transpose(1, 0, 2, 3, 4, 5)
    kc = k.reshape(B, nk, ck, K, hd).transpose(1, 0, 2, 3, 4)
    vc = v.reshape(B, nk, ck, K, hd).transpose(1, 0, 2, 3, 4)

    @jax.checkpoint
    def one_q_chunk(args):
        # checkpointed: backward re-runs the kv scan per q-chunk instead of
        # storing the (cq x ck) probability tiles for the whole sequence.
        qi, qb = args                                    # qb: (B, cq, K, G, hd)
        qpos = jax.lax.dynamic_slice_in_dim(qpos_all, qi * cq, cq)

        def kv_step(carry, kv):
            m, l, acc = carry
            ki, kb, vb = kv
            kpos = jax.lax.dynamic_slice_in_dim(kpos_all, ki * ck, ck)
            # scores: (B, K, G, cq, ck) in f32
            s = jnp.einsum("bqkgh,bskh->bkgqs", qb, kb,
                           preferred_element_type=jnp.float32) * scale
            mask = jnp.ones((cq, ck), bool)
            if causal:
                mask &= qpos[:, None] >= kpos[None, :]
            if window is not None:
                mask &= (qpos[:, None] - kpos[None, :]) < window
            s = jnp.where(mask, s, NEG_INF)
            m_new = jnp.maximum(m, jnp.max(s, axis=-1))
            p = jnp.exp(s - m_new[..., None])
            corr = jnp.exp(m - m_new)
            l_new = l * corr + jnp.sum(p, axis=-1)
            pv = jnp.einsum("bkgqs,bskh->bkgqh", p.astype(vb.dtype), vb,
                            preferred_element_type=jnp.float32)
            acc_new = acc * corr[..., None] + pv
            return (m_new, l_new, acc_new), None

        m0 = jnp.full((B, K, G, cq), NEG_INF, jnp.float32)
        l0 = jnp.zeros((B, K, G, cq), jnp.float32)
        a0 = jnp.zeros((B, K, G, cq, hd), jnp.float32)
        (m, l, acc), _ = jax.lax.scan(
            kv_step, (m0, l0, a0),
            (jnp.arange(nk, dtype=jnp.int32), kc, vc))
        out = acc / jnp.maximum(l, 1e-30)[..., None]     # (B,K,G,cq,hd)
        return out.transpose(0, 3, 1, 2, 4)              # (B,cq,K,G,hd)

    outs = jax.lax.map(one_q_chunk, (jnp.arange(nq, dtype=jnp.int32), qc))
    out = outs.transpose(1, 0, 2, 3, 4, 5).reshape(B, Sq, K, G, hd)
    return out.astype(q.dtype)


LANES = 128     # a TPU tile's minor axis


def kv_group(n_kv_heads: int, head_dim: int) -> int:
    """KV heads stored side by side in one cache row: as many as fill the
    128 lanes and divide the head count (1 once head_dim fills them)."""
    return math.gcd(n_kv_heads, max(1, LANES // head_dim))


def kv_rows(x: jax.Array) -> jax.Array:
    """K/V as projected, (..., S, K, hd), -> the cache's layout
    (..., S, K/r, r*hd) with r = ``kv_group``: r KV heads side by side in
    each lane-dense row.  A decode then writes one position's rows in the
    arena's own layout (a head_dim under 128 lanes would otherwise make
    the device lay the cache out positions-minor, and every write re-lay
    it), and the attention reads them as they are."""
    *lead, S, K, hd = x.shape
    r = kv_group(K, hd)
    return x.reshape(*lead, S, K // r, r * hd)


def kv_heads(x: jax.Array, n_kv_heads: int) -> jax.Array:
    """The cache's (..., S, K/r, r*hd) -> (..., S, K, hd) (``kv_rows``'s
    inverse)."""
    *lead, S, Kr, L = x.shape
    return x.reshape(*lead, S, n_kv_heads, Kr * L // n_kv_heads)


def _block_diag_heads(q: jax.Array, r: int) -> jax.Array:
    """(B, T, K, G, hd) -> (B, T, K/r, r*G, r*hd): within each group of r
    KV heads, every query head sits in the lanes of its own KV head with
    zeros elsewhere, so one product with a (r*hd)-lane cache row contracts
    each query head against its KV head only (the zeros add exact zeros).
    r = 1 is a reshape."""
    B, T, K, G, hd = q.shape
    q = q.reshape(B, T, K // r, r, 1, G, hd)
    own = jnp.eye(r, dtype=bool)[:, :, None, None]          # (r, r, 1, 1)
    qb = jnp.where(own, q, jnp.zeros((), q.dtype))          # (.., r, r, G, hd)
    return jnp.swapaxes(qb, -3, -2).reshape(B, T, K // r, r * G, r * hd)


def chunk_attend(q: jax.Array, k_cache: jax.Array, v_cache: jax.Array,
                 kv_pos: jax.Array, pos: jax.Array, *,
                 window: Optional[int] = None) -> jax.Array:
    """Multi-token attention against the cache (chunked prefill).

    q: (B, T, K, G, hd); k_cache/v_cache: (B, S, K/r, r*hd) (``kv_rows``);
    kv_pos: (B, S) logical position of each slot (-1 = empty);
    pos: (B, T) absolute position of each query token.
    Returns (B, T, K, G, hd).

    One contraction over the full cache axis for every query position,
    with the unnormalised-exp cast discipline below, so each position's
    output is bit-identical to a single-token decode at that position
    (``decode_attend`` is this at T = 1; the chunked-prefill ≡
    whole-prompt invariant of tests/test_serving.py).  The chunk's own
    K/V must already be in the cache; the kv_pos <= pos mask keeps every
    query causal within the chunk.  Where r > 1 (head_dim under 128
    lanes) a cache row holds r KV heads and each query head is read
    through a block-diagonal query: the MXU pads a contraction under 128
    lanes to 128 anyway.
    """
    B, T, K, G, hd = q.shape
    Kr = k_cache.shape[2]
    r = K // Kr
    scale = 1.0 / math.sqrt(hd)
    s = jnp.einsum("btjql,bsjl->btjqs", _block_diag_heads(q, r), k_cache,
                   preferred_element_type=jnp.float32) * scale
    valid = (kv_pos[:, None, :] >= 0) & (kv_pos[:, None, :] <= pos[:, :, None])
    if window is not None:
        valid &= (pos[:, :, None] - kv_pos[:, None, :]) < window
    s = jnp.where(valid[:, :, None, None, :], s, NEG_INF)
    # flash-identical arithmetic (one kv chunk): cast the UNnormalised
    # exp(s - m) to the cache dtype, matmul with f32 accumulation, divide
    # by the denominator afterwards.  Normalising before the bf16 cast
    # rounds differently and makes prefill (flash path) vs decode drift a
    # ulp per layer — enough to flip near-tied argmax logits
    # (test_serving_cache_consistency).
    m = jnp.max(s, axis=-1, keepdims=True)
    p = jnp.exp(s - m)
    l = jnp.sum(p, axis=-1, keepdims=True)
    out = jnp.einsum("btjqs,bsjl->btjql", p.astype(v_cache.dtype), v_cache,
                     preferred_element_type=jnp.float32)
    # each query head keeps the lanes of its own KV head
    out = jnp.diagonal(out.reshape(B, T, Kr, r, G, r, hd), axis1=3, axis2=5)
    out = jnp.moveaxis(out, -1, 3).reshape(B, T, K, G, hd)
    out = out / jnp.maximum(l, 1e-30).reshape(B, T, K, G, 1)
    return out.astype(q.dtype)


def decode_attend(q: jax.Array, k_cache: jax.Array, v_cache: jax.Array,
                  kv_pos: jax.Array, pos: jax.Array, *,
                  window: Optional[int] = None) -> jax.Array:
    """One-token attention against the cache: ``chunk_attend`` at T = 1.

    q: (B, K, G, hd); k_cache/v_cache: (B, S, K/r, r*hd);
    kv_pos: (B, S) logical position of each slot (-1 = empty);
    pos: (B,) current absolute position.  Returns (B, K, G, hd).
    """
    return chunk_attend(q[:, None], k_cache, v_cache, kv_pos, pos[:, None],
                        window=window)[:, 0]


def init_kv_cache(cfg: AttentionConfig, batch: int, length: int,
                  dtype=jnp.bfloat16) -> dict:
    """K/V of (batch, size, K/r, r*hd) (``kv_rows``) and the logical
    position of each of the `size` slots (-1 = empty)."""
    K, hd = cfg.n_kv_heads, cfg.head_dim
    r = kv_group(K, hd)
    size = min(length, cfg.window) if cfg.window else length
    return {
        "k": jnp.zeros((batch, size, K // r, r * hd), dtype),
        "v": jnp.zeros((batch, size, K // r, r * hd), dtype),
        "pos": jnp.full((batch, size), -1, jnp.int32),
    }


def update_cache(cache: dict, k1: jax.Array, v1: jax.Array,
                 pos: jax.Array, *, layer: Optional[jax.Array] = None,
                 active: Optional[jax.Array] = None) -> dict:
    """Insert one token at logical position `pos` (ring-buffered if windowed).

    k1/v1: (B, K, hd), written as the position's (K/r, r*hd) rows;
    pos: (B,) — same position across batch in practice, but kept per-row
    for generality.

    layer: index into a leading layer-group axis, for a cache stacked over
    groups (leaves (n_groups, B, ...)); the position is scattered into
    that layer of the stack, so a donated arena is written in place.
    active: (B,) bool; a row where it is False is not written at all (its
    index is sent out of range and the scatter drops it), so that row of
    the cache stays bit-identical with no select over the arena.
    """
    size = cache["pos"].shape[-1]
    slot = pos % size                                     # ring index (B,)
    if active is not None:
        slot = jnp.where(active, slot, size)
    B, Kr = k1.shape[0], cache["k"].shape[-2]
    idx = (jnp.arange(B), slot)
    if layer is not None:
        idx = (layer,) + idx

    def put(a, x):
        return a.at[idx].set(x.astype(a.dtype), mode="drop")

    return {"k": put(cache["k"], k1.reshape(B, Kr, -1)),
            "v": put(cache["v"], v1.reshape(B, Kr, -1)),
            "pos": put(cache["pos"], pos)}


def update_cache_chunk(cache: dict, k: jax.Array, v: jax.Array,
                       pos: jax.Array) -> dict:
    """Insert T tokens at logical positions `pos` (chunked prefill).

    k/v: (B, T, K, hd); pos: (B, T).  For UN-windowed caches only: there
    the ring spans max_len and positions never wrap, so the T slots of a
    chunk never collide.  Windowed ring caches must insert+attend per
    token instead (``transformer._unit_chunk`` scans those) — a
    vectorised insert would let a later in-chunk token overwrite a ring
    slot an earlier query still needs, silently dropping K/V entries.
    """
    size = cache["pos"].shape[1]
    slot = pos % size                                     # (B, T)
    B, T = pos.shape
    rows = (B, T, cache["k"].shape[2], -1)
    b = jnp.arange(B)[:, None]
    kc = cache["k"].at[b, slot].set(k.reshape(rows).astype(cache["k"].dtype))
    vc = cache["v"].at[b, slot].set(v.reshape(rows).astype(cache["v"].dtype))
    kv_pos = cache["pos"].at[b, slot].set(pos)
    return {"k": kc, "v": vc, "pos": kv_pos}


# ---------------------------------------------------------------------------
# Full attention block (norm -> qkv -> rope -> attend -> out proj)
# ---------------------------------------------------------------------------


def attn_params(cfg: ModelConfig, key, prefix: str = "",
                cross: bool = False) -> dict:
    a = cfg.attention
    assert a is not None
    d = cfg.d_model
    q_out = a.n_heads * a.head_dim
    kv_out = 2 * a.n_kv_heads * a.head_dim
    k1, k2 = jax.random.split(key)
    p = {
        f"{prefix}qkv": jax.random.normal(k1, (d, q_out + kv_out), jnp.float32) * d ** -0.5,
        f"{prefix}o": jax.random.normal(k2, (q_out, d), jnp.float32) * q_out ** -0.5,
    }
    if a.qkv_bias:
        p[f"{prefix}qkv_bias"] = jnp.zeros((q_out + kv_out,), jnp.float32)
    return p


def attention_block(cfg: ModelConfig, x: jax.Array, params: dict,
                    sh: Sharder, *, positions: jax.Array,
                    causal: bool = True, rope: bool = True,
                    op_prefix: str = "attn",
                    kv_source: Optional[jax.Array] = None) -> jax.Array:
    """Training/prefill attention (full sequence).  x: (B, S, d)."""
    a = cfg.attention
    assert a is not None
    src = x if kv_source is None else kv_source
    if kv_source is None:
        qkv = sh.dot(f"{op_prefix}_qkv", x, params["qkv"])
        q, k, v = split_qkv(a, qkv, params.get("qkv_bias"))
    else:
        # cross attention: q from x, k/v from the encoder output; the
        # fused qkv weight is constrained once, then each split half runs
        # through the seam under the same program word.
        H, K, hd = a.n_heads, a.n_kv_heads, a.head_dim
        w_qkv = sh.weight(params["qkv"], f"{op_prefix}_qkv")
        wq, wkv = jnp.split(w_qkv, [H * hd], axis=-1)
        q = sh.dot(f"{op_prefix}_qkv", x, wq,
                   constrain=False).reshape(*x.shape[:2], K, H // K, hd)
        kv = sh.dot(f"{op_prefix}_qkv", src.astype(x.dtype), wkv,
                    constrain=False)
        k, v = jnp.split(kv, 2, axis=-1)
        k = k.reshape(*src.shape[:2], K, hd)
        v = v.reshape(*src.shape[:2], K, hd)
    if rope and kv_source is None:
        B, S = x.shape[:2]
        K_, G, hd = q.shape[2:]
        qf = q.reshape(B, S, K_ * G, hd)
        q = apply_rope(qf, positions, a.rope_theta).reshape(B, S, K_, G, hd)
        k = apply_rope(k, positions, a.rope_theta)
    if sh.mesh is not None:
        # Megatron layout: expand KV to full heads and shard the head dim
        # over `model` (GSPMD pads non-divisible head counts).  Keeps every
        # flash-chunk head-local — no per-chunk resharding.
        B, S = x.shape[:2]
        K_, G, hd = q.shape[2:]
        H = K_ * G
        q = sh.heads(q.reshape(B, S, H, hd)).reshape(B, S, H, 1, hd)
        k = sh.heads(jnp.repeat(k, G, axis=2))
        v = sh.heads(jnp.repeat(v, G, axis=2))
    out = flash_attention(q, k, v, causal=causal,
                          window=a.window if causal else None)
    B, S = out.shape[:2]
    out = out.reshape(B, S, -1)
    return sh.dot(f"{op_prefix}_o", out, params["o"])
