"""Plain float32 reference of the dense decoder family, in jax.numpy.

Independent of the program under test: it reads only a configuration's
``model`` block and a parameter tree in its own layout, and imports
nothing of ``src/``.  Covered: RMSNorm or non-parametric LayerNorm,
grouped-query attention with rotary embeddings (half-split rotation),
optional q/k/v biases, a SwiGLU feed-forward, a tied or separate LM head,
and mean next-token cross-entropy.  Every matmul runs at
``Precision.HIGHEST`` so that the TPU does not round its operands.

``quant="fp8"`` computes every matmul, forward and backward, on operands
rounded to float8 e4m3 with one scale per tensor: the control that a
comparison has to tell apart from the program.

Layout: ``{"embed": (V, d), "lm_head": (d, V) unless tied,
"final_norm": (d,) unless non-parametric, "layers": [per-layer dict]}``
with per-layer ``wq wk wv (d, heads*hd)``, ``bq bk bv``, ``wo``,
``w_gate w_up (d, f)``, ``w_down (f, d)``, ``norm1 norm2 (d,)``.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
FP8_MAX = 448.0


def _q(x, quant):
    if quant is None:
        return x
    if quant != "fp8":
        raise ValueError(f"unknown quantization {quant!r}")
    s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / FP8_MAX
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _einsum(spec, a, b):
    return jnp.einsum(spec, a, b, precision=HIGHEST,
                      preferred_element_type=jnp.float32)


@partial(jax.custom_vjp, nondiff_argnums=(0, 1))
def _qeinsum(spec, quant, a, b):
    return _einsum(spec, _q(a, quant), _q(b, quant))


def _qeinsum_fwd(spec, quant, a, b):
    return _qeinsum(spec, quant, a, b), (a, b)


def _qeinsum_bwd(spec, quant, res, g):
    a, b = res
    _, vjp = jax.vjp(partial(_einsum, spec), _q(a, quant), _q(b, quant))
    return vjp(_q(g, quant))


_qeinsum.defvjp(_qeinsum_fwd, _qeinsum_bwd)


def einsum(spec, a, b, quant=None):
    if quant is None:
        return _einsum(spec, a, b)
    return _qeinsum(spec, quant, a, b)


def norm(m, x, scale):
    if m["norm"] == "rmsnorm":
        y = x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + m["norm_eps"])
        return y * scale
    if m["norm"] == "nonparametric_ln":
        mu = jnp.mean(x, -1, keepdims=True)
        var = jnp.mean((x - mu) ** 2, -1, keepdims=True)
        return (x - mu) * jax.lax.rsqrt(var + m["norm_eps"])
    raise ValueError(f"unknown norm {m['norm']!r}")


def rope(x, positions, theta):
    """x: (b, T, heads, hd); rotate the two halves of each head."""
    hd = x.shape[-1]
    freqs = 1.0 / (theta ** (np.arange(0, hd, 2, dtype=np.float32) / hd))
    ang = positions[:, None].astype(jnp.float32) * freqs       # (T, hd/2)
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., : hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def layer(m, lp, x, quant=None):
    """One decoder layer over x: (b, T, d) at positions 0..T-1."""
    b, T, _ = x.shape
    H, K, hd = m["n_heads"], m["n_kv_heads"], m["head_dim"]
    pos = jnp.arange(T)
    h = norm(m, x, lp.get("norm1"))
    q = einsum("btd,de->bte", h, lp["wq"], quant)
    k = einsum("btd,de->bte", h, lp["wk"], quant)
    v = einsum("btd,de->bte", h, lp["wv"], quant)
    if m["qkv_bias"]:
        q, k, v = q + lp["bq"], k + lp["bk"], v + lp["bv"]
    q = rope(q.reshape(b, T, H, hd), pos, m["rope_theta"])
    k = rope(k.reshape(b, T, K, hd), pos, m["rope_theta"])
    v = v.reshape(b, T, K, hd)
    q = q.reshape(b, T, K, H // K, hd)                 # head h -> kv head h // G
    s = einsum("bqkgh,bskh->bkgqs", q, k, quant) / np.sqrt(hd)
    s = jnp.where(pos[:, None] >= pos[None, :], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    a = einsum("bkgqs,bskh->bqkgh", p, v, quant).reshape(b, T, H * hd)
    x = x + einsum("bte,ed->btd", a, lp["wo"], quant)
    h = norm(m, x, lp.get("norm2"))
    g = einsum("btd,df->btf", h, lp["w_gate"], quant)
    u = einsum("btd,df->btf", h, lp["w_up"], quant)
    return x + einsum("btf,fd->btd", jax.nn.silu(g) * u, lp["w_down"], quant)


def head_weight(params):
    """(d, V) LM head: the separate head, or the tied embedding."""
    return params["lm_head"] if "lm_head" in params else params["embed"].T


def logits(m, hp, x, quant=None):
    """Final norm and LM head on x: (..., d) -> (..., V)."""
    x = norm(m, x, hp.get("final_norm"))
    return einsum("...d,dv->...v", x, hp["head"], quant)


def nll_sum(m, hp, x, labels, quant=None):
    """Summed next-token cross-entropy of x: (b, T, d) against labels."""
    z = logits(m, hp, x, quant)
    lse = jax.scipy.special.logsumexp(z, -1)
    gold = jnp.take_along_axis(z, labels[..., None], -1)[..., 0]
    return jnp.sum(lse - gold)


def head_params(params):
    hp = {"head": head_weight(params)}
    if "final_norm" in params:
        hp["final_norm"] = params["final_norm"]
    return hp


def forward_logits(m, params, tokens, at, quant=None):
    """Logits at positions `at` of one sequence `tokens` (T,): the whole
    forward pass, no cache."""
    x = params["embed"][tokens][None]
    for lp in params["layers"]:
        x = layer(m, lp, x, quant)
    return logits(m, head_params(params), x[0, at], quant)


def loss(m, params, tokens, labels, quant=None):
    """Mean cross-entropy of a batch (b, T): the whole model in one call
    (small sizes; the chip-sized path is :class:`Trainer`)."""
    x = params["embed"][tokens]
    for lp in params["layers"]:
        x = layer(m, lp, x, quant)
    return nll_sum(m, head_params(params), x, labels, quant) / tokens.size
