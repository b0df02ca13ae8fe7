"""Pallas megakernel for the fused per-layer decode step.

The per-op DECODE path dispatches one bandwidth matvec PEWord per weight
matmul — every intermediate activation round-trips HBM and every op pays
its own launch.  NeuroTrainer's thesis (§2, §3.3) is the opposite: program
the dataflow so operands are reused on-module.  This kernel is that thesis
applied to the token loop: ONE launch per transformer layer runs

    norm1 -> qkv projection -> RoPE -> KV append into the slot-arena row
    -> paged attention over the arena row -> output projection -> residual
    -> norm2 -> FF block (column-streamed) -> residual

with f32 accumulation on every matmul and the (1, d) intermediates living
entirely in VMEM.  The grid is (B,): one program instance per arena slot,
so masked-arena semantics are free — an inactive row computes garbage the
engine discards (``jnp.where`` on the caller side restores its cache row),
costing FLOPs but never correctness.

The FF block streams the (d, d_ff) weights in ``block_n``-column tiles
inside a ``fori_loop`` — the LoopNest the tuner's ``decode`` kind searches;
the winning tn lands here via the program word's DECODE tiling.  Gated
activations (swiglu/geglu) pair the gate column block with its up block,
so one loop step touches columns [j*tn, (j+1)*tn) of both halves.

Three entry points:

  fused_attn_unit  — the full unit above (attention mixer + dense FF)
  fused_attn_mixer — attention half only (units whose FF is MoE: routing
                     is a VPU word, experts stay per-op)
  fused_ffn        — norm2 + FF + residual only (SSM units: the
                     recurrence is VPU work and stays on its jnp path)

Precision: matches the per-op decode discipline (f32 norms and softmax,
f32-accum matmuls, unnormalised-exp cast before the PV contraction).  The
pallas path is validated allclose against the reference composition; the
BIT-parity contract of the serving stack is carried by the reference
backend, where the fused composition replays the per-op primitive
sequence exactly (models/transformer._unit_decode_fused).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.ops import resolve_interpret

NEG_INF = -1e30


def _clip_block_n(block_n: int, f: int) -> int:
    """Largest divisor of f that is <= block_n (>= 1).

    The FF stream loop has a static trip count; a ragged tail tile would
    read undefined pad bytes (the PR 3 NaN lesson), so the tile is
    snapped to a divisor instead of masked.
    """
    tn = max(1, min(block_n, f))
    while f % tn:
        tn -= 1
    return tn


def _norm_f32(x, scale, bias, kind: str):
    """f32 norm on a (1, d) row; returns x.dtype.  Mirrors models/layers."""
    xf = x.astype(jnp.float32)
    if kind == "rmsnorm":
        y = xf * jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True)
                               + 1e-6)
    else:                                  # layernorm / nonparametric_ln
        mu = jnp.mean(xf, axis=-1, keepdims=True)
        var = jnp.mean((xf - mu) ** 2, axis=-1, keepdims=True)
        y = (xf - mu) * jax.lax.rsqrt(var + 1e-5)
    y = y * scale.astype(jnp.float32) + bias.astype(jnp.float32)
    return y.astype(x.dtype)


def _rope_f32(x, pos, theta: float):
    """RoPE on (H, hd) at scalar position `pos`; returns x.dtype."""
    hd = x.shape[-1]
    i2 = jax.lax.broadcasted_iota(jnp.int32, (1, hd // 2), 1).astype(jnp.float32)
    freqs = 1.0 / (theta ** (2.0 * i2 / hd))
    ang = pos.astype(jnp.float32) * freqs              # (1, hd/2)
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    xf = x.astype(jnp.float32)
    x1, x2 = xf[:, :hd // 2], xf[:, hd // 2:]
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


def _ffn_stream(x, h2, w1_ref, w2_ref, *, act: str, tn: int):
    """Column-streamed FF block: returns x + FF(h2), accumulating f32.

    One fori_loop step loads a tn-column tile of w_in (both gate and up
    tiles for gated acts), applies the activation, and MACs the matching
    tn-row tile of w_out into the resident (1, d) f32 accumulator — the
    decode LoopNest with the reduction kept in VMEM.
    """
    f32 = jnp.float32
    d = x.shape[-1]
    f = w2_ref.shape[0]
    gated = act in ("swiglu", "geglu")
    n_blk = f // tn
    dt = x.dtype

    def body(j, acc):
        c0 = j * tn
        if gated:
            g = jnp.dot(h2, w1_ref[:, pl.ds(c0, tn)],
                        preferred_element_type=f32)
            u = jnp.dot(h2, w1_ref[:, pl.ds(f + c0, tn)],
                        preferred_element_type=f32)
            gate = jax.nn.silu(g) if act == "swiglu" else jax.nn.gelu(g)
            hj = (gate * u).astype(dt)
        else:
            hj = jnp.dot(h2, w1_ref[:, pl.ds(c0, tn)],
                         preferred_element_type=f32)
            if act == "relu_sq":
                r = jax.nn.relu(hj)
                hj = (r * r).astype(dt)
            else:                                      # gelu
                hj = jax.nn.gelu(hj).astype(dt)
        return acc + jnp.dot(hj, w2_ref[pl.ds(c0, tn), :],
                             preferred_element_type=f32)

    acc = jax.lax.fori_loop(0, n_blk, body, jnp.zeros((1, d), f32))
    return x + acc.astype(dt)


# ---------------------------------------------------------------------------
# Kernel bodies
# ---------------------------------------------------------------------------


def _attn_unit_kernel(x_ref, n1s_ref, n1b_ref, qkvw_ref, qkvb_ref, ow_ref,
                      n2s_ref, n2b_ref, w1_ref, w2_ref,
                      kc_in, vc_in, kp_in, pos_ref,
                      y_ref, kc_out, vc_out, kp_out, *,
                      heads: int, kv_heads: int, head_dim: int,
                      rope_theta: float, window, norm_kind: str,
                      act: str, tn: int, with_ffn: bool):
    f32 = jnp.float32
    H, K, hd = heads, kv_heads, head_dim
    x = x_ref[0]                                       # (1, d)
    dt = x.dtype
    p = pos_ref[0, 0, 0]

    # --- qkv projection ---
    h = _norm_f32(x, n1s_ref[...], n1b_ref[...], norm_kind)
    qkv = jnp.dot(h, qkvw_ref[...], preferred_element_type=f32)
    qkv = (qkv + qkvb_ref[...].astype(f32)).astype(dt)
    q = qkv[:, :H * hd].reshape(H, hd)
    k1 = qkv[:, H * hd:(H + K) * hd].reshape(K, hd)
    v1 = qkv[:, (H + K) * hd:].reshape(K, hd)
    q = _rope_f32(q, p, rope_theta)
    k1 = _rope_f32(k1, p, rope_theta)

    # --- KV append into the arena row (ring slot p % S) ---
    S = kc_in.shape[1]
    slot = p % S
    kc_out[...] = kc_in[...]
    vc_out[...] = vc_in[...]
    kc_out[0, pl.ds(slot, 1)] = k1.astype(kc_out.dtype).reshape(1, K, hd)
    vc_out[0, pl.ds(slot, 1)] = v1.astype(vc_out.dtype).reshape(1, K, hd)
    # the position row is lane-dense: write slot `slot` by a select
    kvp = kp_in[0]                                     # (1, S)
    kvp = jnp.where(jax.lax.broadcasted_iota(jnp.int32, kvp.shape, 1) == slot,
                    p, kvp)
    kp_out[0] = kvp

    # --- paged attention over the arena row ---
    kc = kc_out[...][0]                                # (S, K, hd)
    vc = vc_out[...][0]
    scale = 1.0 / math.sqrt(hd)
    qh = q.reshape(K, H // K, hd)
    s = jnp.einsum("kgh,skh->kgs", qh.astype(f32), kc.astype(f32)) * scale
    valid = (kvp >= 0) & (kvp <= p)
    if window is not None:
        valid &= (p - kvp) < window
    s = jnp.where(valid[None], s, NEG_INF)
    m = jnp.max(s, axis=-1, keepdims=True)
    pe = jnp.exp(s - m)
    l = jnp.sum(pe, axis=-1, keepdims=True)
    o = jnp.einsum("kgs,skh->kgh", pe.astype(f32), vc.astype(f32))
    o = (o / jnp.maximum(l, 1e-30)).astype(dt).reshape(1, H * hd)

    # --- output projection + residual ---
    mix = jnp.dot(o, ow_ref[...], preferred_element_type=f32).astype(dt)
    x = x + mix

    # --- FF block ---
    if with_ffn:
        h2 = _norm_f32(x, n2s_ref[...], n2b_ref[...], norm_kind)
        x = _ffn_stream(x, h2, w1_ref, w2_ref, act=act, tn=tn)
    y_ref[0] = x


def _ffn_kernel(x_ref, n2s_ref, n2b_ref, w1_ref, w2_ref, y_ref, *,
                norm_kind: str, act: str, tn: int):
    x = x_ref[0]
    h2 = _norm_f32(x, n2s_ref[...], n2b_ref[...], norm_kind)
    y_ref[0] = _ffn_stream(x, h2, w1_ref, w2_ref, act=act, tn=tn)


# ---------------------------------------------------------------------------
# Public wrappers
# ---------------------------------------------------------------------------


def _row2d(arr, d: int, fill: float, like) -> jax.Array:
    """Materialise an optional (d,) vector as a (1, d) operand block.

    Pallas operand lists are static, so absent norm scales / biases become
    neutral constants (ones / zeros) instead of branching kernels.
    """
    if arr is None:
        return jnp.full((1, d), fill, like)
    return arr.reshape(1, d).astype(like)


def _whole(shape):
    """BlockSpec for an operand every grid row reads in full."""
    nd = len(shape)
    return pl.BlockSpec(shape, lambda b: (0,) * nd)


def _perrow(shape):
    """BlockSpec for a (B, ...) operand sliced one arena row per grid step.

    The block's last two dims must be the array's own (Mosaic tiles them
    by (8, 128) unless they are whole), so a (B, n) operand enters as
    (B, 1, n) — see :func:`_unit_row` — and never as a (1, n) block.
    """
    nd = len(shape)
    return pl.BlockSpec((1,) + tuple(shape[1:]), lambda b: (b,) + (0,) * (nd - 1))


def _unit_row(a):
    """(B, n) -> (B, 1, n): a per-row operand whose row block is whole."""
    return a.reshape(a.shape[0], 1, *a.shape[1:])


def fused_attn_unit(x, cache_k, cache_v, cache_pos, pos, *,
                    norm1_scale, norm1_bias, qkv_w, qkv_bias, o_w,
                    norm2_scale=None, norm2_bias=None, w_in=None, w_out=None,
                    heads: int, kv_heads: int, head_dim: int,
                    rope_theta: float, window=None,
                    norm_kind: str = "rmsnorm", act: str = "swiglu",
                    block_n: int = 256, with_ffn: bool = True,
                    interpret: bool | None = None):
    """One fused-decode launch for a whole attention unit.

    x: (B, d) current hidden rows (one per arena slot);
    cache_k/cache_v: (B, S, K, hd); cache_pos: (B, S); pos: (B,) int32.
    Returns (y (B, d), new_k, new_v, new_pos).  with_ffn=False skips the
    FF block (MoE units keep their experts per-op).
    """
    interp = resolve_interpret(interpret)
    B, d = x.shape
    S, K, hd = cache_k.shape[1:]
    qn = qkv_w.shape[1]
    n1s = _row2d(norm1_scale, d, 1.0, jnp.float32)
    n1b = _row2d(norm1_bias, d, 0.0, jnp.float32)
    n2s = _row2d(norm2_scale, d, 1.0, jnp.float32)
    n2b = _row2d(norm2_bias, d, 0.0, jnp.float32)
    qb = _row2d(qkv_bias, qn, 0.0, jnp.float32)
    if with_ffn:
        f = w_out.shape[0]
        tn = _clip_block_n(block_n, f)
    else:
        # dummy FF operands keep the operand list static
        w_in = jnp.zeros((1, 1), x.dtype)
        w_out = jnp.zeros((1, 1), x.dtype)
        tn = 1
    kernel = functools.partial(
        _attn_unit_kernel, heads=heads, kv_heads=kv_heads, head_dim=head_dim,
        rope_theta=rope_theta, window=window, norm_kind=norm_kind, act=act,
        tn=tn, with_ffn=with_ffn)
    x3, kp3 = _unit_row(x), _unit_row(cache_pos)
    pos3 = pos.astype(jnp.int32).reshape(B, 1, 1)
    y, kc, vc, kp = pl.pallas_call(
        kernel,
        grid=(B,),
        in_specs=[
            _perrow(x3.shape),
            _whole(n1s.shape), _whole(n1b.shape),
            _whole(qkv_w.shape), _whole(qb.shape), _whole(o_w.shape),
            _whole(n2s.shape), _whole(n2b.shape),
            _whole(w_in.shape), _whole(w_out.shape),
            _perrow(cache_k.shape), _perrow(cache_v.shape),
            _perrow(kp3.shape), _perrow(pos3.shape),
        ],
        out_specs=[
            _perrow(x3.shape), _perrow(cache_k.shape),
            _perrow(cache_v.shape), _perrow(kp3.shape),
        ],
        out_shape=[
            jax.ShapeDtypeStruct(x3.shape, x.dtype),
            jax.ShapeDtypeStruct(cache_k.shape, cache_k.dtype),
            jax.ShapeDtypeStruct(cache_v.shape, cache_v.dtype),
            jax.ShapeDtypeStruct(kp3.shape, cache_pos.dtype),
        ],
        interpret=interp,
    )(x3, n1s, n1b, qkv_w, qb, o_w, n2s, n2b, w_in, w_out,
      cache_k, cache_v, kp3, pos3)
    return y.reshape(B, d), kc, vc, kp.reshape(cache_pos.shape)


def fused_ffn(x, *, norm2_scale, norm2_bias, w_in, w_out,
              norm_kind: str = "rmsnorm", act: str = "swiglu",
              block_n: int = 256, interpret: bool | None = None):
    """Fused norm2 + FF + residual for units whose mixer stays per-op.

    x: (B, d) -> (B, d).  SSM recurrences are VPU words (never lowered
    onto the MAC array), so their units fuse only the FF half.
    """
    interp = resolve_interpret(interpret)
    B, d = x.shape
    f = w_out.shape[0]
    tn = _clip_block_n(block_n, f)
    n2s = _row2d(norm2_scale, d, 1.0, jnp.float32)
    n2b = _row2d(norm2_bias, d, 0.0, jnp.float32)
    kernel = functools.partial(_ffn_kernel, norm_kind=norm_kind, act=act,
                               tn=tn)
    x3 = _unit_row(x)
    y = pl.pallas_call(
        kernel,
        grid=(B,),
        in_specs=[
            _perrow(x3.shape),
            _whole(n2s.shape), _whole(n2b.shape),
            _whole(w_in.shape), _whole(w_out.shape),
        ],
        out_specs=_perrow(x3.shape),
        out_shape=jax.ShapeDtypeStruct(x3.shape, x.dtype),
        interpret=interp,
    )(x3, n2s, n2b, w_in, w_out)
    return y.reshape(B, d)
