"""Public jit'd wrappers for the Pallas kernels.

Handles: entropy generation (full-SR vs the paper's LO shared-entropy),
interpret-mode selection (CPU container validates kernel bodies in
interpret mode; TPU is the compile target), and shape plumbing for the
model-facing call sites (e.g. (B, S, H, hd) -> (BH, S, hd) for wkv6).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import outer_accum as _oa
from repro.kernels import sr_matmul as _mm
from repro.kernels import sr_round as _rr
from repro.kernels import wkv6 as _wkv


def resolve_interpret(interpret: bool | None) -> bool:
    """The one interpret-mode choice for every kernel: an explicit value
    wins; otherwise the kernels run in the interpreter on the CPU backend
    only, and compile on every other backend."""
    if interpret is not None:
        return interpret
    return jax.default_backend() == "cpu"


def make_rbits(key: jax.Array, shape: tuple, *, lo: bool = False,
               lo_block: int = 256) -> jax.Array:
    """Entropy for SR.  lo=True reproduces the paper's single-LFSR sharing:
    one fresh 32-bit word per `lo_block` elements, rotated per element."""
    if not lo:
        return jax.random.bits(key, shape, dtype=jnp.uint32)
    n = 1
    for s in shape:
        n *= s
    n_words = -(-n // lo_block)
    words = jax.random.bits(key, (n_words,), dtype=jnp.uint32)
    idx = jnp.arange(n, dtype=jnp.uint32)
    w = words[(idx // lo_block).astype(jnp.int32)]
    rot = idx % 32
    r = (w >> rot) | (w << ((32 - rot) % 32))
    return r.reshape(shape)


@functools.partial(jax.jit, static_argnames=("lo", "interpret"))
def sr_round(x: jax.Array, key: jax.Array, *, lo: bool = False,
             interpret: bool | None = None) -> jax.Array:
    """Stochastically round f32 (M, N) to bf16."""
    interp = resolve_interpret(interpret)
    rbits = make_rbits(key, x.shape, lo=lo)
    return _rr.sr_round(x, rbits, interpret=interp)


@functools.partial(jax.jit,
                   static_argnames=("sr", "lo", "interpret", "block", "trans_b"))
def sr_matmul(a: jax.Array, b: jax.Array, key: jax.Array | None = None, *,
              sr: bool = True, lo: bool = False,
              block: tuple = (256, 256, 512),
              interpret: bool | None = None, trans_b: bool = False) -> jax.Array:
    """bf16 matmul, f32 accumulation, optional fused SR-bf16 writeback.

    trans_b computes a @ b.T via the counter-swept BlockSpec (BP's W^T)."""
    interp = resolve_interpret(interpret)
    rbits = None
    n = b.shape[0] if trans_b else b.shape[1]
    if sr:
        assert key is not None
        rbits = make_rbits(key, (a.shape[0], n), lo=lo)
    return _mm.sr_matmul(a, b, rbits, block=block, interpret=interp,
                         trans_b=trans_b)


@functools.partial(jax.jit,
                   static_argnames=("sr", "lo", "scale", "interpret", "block"))
def outer_accum(x: jax.Array, dy: jax.Array, key: jax.Array | None = None, *,
                scale: float = 1.0, sr: bool = False, lo: bool = False,
                block: tuple = (256, 256, 512),
                interpret: bool | None = None) -> jax.Array:
    """FC-UP: dW = scale * X^T dY (fused minibatch average + SR)."""
    interp = resolve_interpret(interpret)
    rbits = None
    if sr:
        assert key is not None
        rbits = make_rbits(key, (x.shape[1], dy.shape[1]), lo=lo)
    return _oa.outer_accum(x, dy, scale=scale, rbits=rbits, block=block,
                           interpret=interp)


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def wkv6(r: jax.Array, k: jax.Array, v: jax.Array, w: jax.Array,
         u: jax.Array, *, chunk: int = 64,
         interpret: bool | None = None):
    """Chunked WKV6.  Model-facing layout (B, S, H, hd) + u (H, hd)."""
    interp = resolve_interpret(interpret)
    B, S, H, hd = r.shape
    fold = lambda t: t.transpose(0, 2, 1, 3).reshape(B * H, S, hd)
    uu = jnp.tile(u, (B, 1))
    y, sf = _wkv.wkv6(fold(r), fold(k), fold(v), fold(w), uu,
                      chunk=min(chunk, S), interpret=interp)
    y = y.reshape(B, H, S, hd).transpose(0, 2, 1, 3)
    return y, sf.reshape(B, H, hd, hd)
