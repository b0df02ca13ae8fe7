"""Whisper-style encoder-decoder backbone.

Per the assignment the mel/conv frontend is a STUB: ``input_specs()``
provides precomputed frame embeddings (B, enc_seq, d).  Encoder is a
non-causal transformer with learned positions; decoder adds causal
self-attention (KV cache for decode shapes) and cross-attention to the
fixed encoder output.  Weights tied (embed == lm head), as in Whisper.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models.attention import (attention_block, attn_params,
                                    decode_attend, init_kv_cache, kv_rows,
                                    split_qkv, update_cache)
from repro.models.layers import (Sharder, apply_norm, embed, mlp,
                                 mlp_params, norm_params)


def init(key, cfg: ModelConfig) -> dict:
    d = cfg.d_model
    ks = jax.random.split(key, 8)
    params: dict = {
        "embed": {"table": jax.random.normal(ks[0], (cfg.vocab_size, d),
                                             jnp.float32) * 0.02},
        "enc_pos": jax.random.normal(ks[1], (cfg.enc_seq, d), jnp.float32) * 0.02,
        "dec_pos": jax.random.normal(ks[2], (4096, d), jnp.float32) * 0.02,
        "final_norm": norm_params(cfg, ks[3]),
        "enc_final_norm": norm_params(cfg, ks[3]),
    }

    def enc_group(gkey):
        u = jax.random.split(gkey, 3)
        return {"attn": attn_params(cfg, u[0]),
                "ffn": mlp_params(cfg, u[1]),
                "norm1": norm_params(cfg, u[2]), "norm2": norm_params(cfg, u[2])}

    def dec_group(gkey):
        u = jax.random.split(gkey, 4)
        return {"attn": attn_params(cfg, u[0]),
                "cross": attn_params(cfg, u[1]),
                "ffn": mlp_params(cfg, u[2]),
                "norm1": norm_params(cfg, u[3]),
                "norm_cross": norm_params(cfg, u[3]),
                "norm2": norm_params(cfg, u[3])}

    params["enc_groups"] = jax.vmap(enc_group)(jax.random.split(ks[4], cfg.enc_layers))
    params["dec_groups"] = jax.vmap(dec_group)(jax.random.split(ks[5], cfg.n_layers))
    return params


def param_shapes(cfg: ModelConfig) -> dict:
    return jax.eval_shape(lambda k: init(k, cfg),
                          jax.ShapeDtypeStruct((2,), jnp.uint32))


def param_pspecs(cfg: ModelConfig, program) -> dict:
    from jax.sharding import PartitionSpec as P
    shapes = param_shapes(cfg)

    def spec_for(path, leaf):
        keys = [getattr(p, "key", getattr(p, "name", None)) for p in path]
        if "embed" in keys:
            return program.weight_spec("embed", stacked=False)
        enc = "enc_groups" in keys
        table = {
            ("attn", "qkv"): "enc_attn_qkv" if enc else "attn_qkv",
            ("attn", "o"): "enc_attn_o" if enc else "attn_o",
            ("cross", "qkv"): "cross_qkv", ("cross", "o"): "cross_o",
            ("ffn", "ffn_in"): "enc_ffn_in" if enc else "ffn_in",
            ("ffn", "ffn_out"): "enc_ffn_out" if enc else "ffn_out",
        }
        for (parent, name), op in table.items():
            if parent in keys and keys[-1] == name and op in program.plan.ops:
                return program.weight_spec(op, stacked=True)
        return P()

    return jax.tree_util.tree_map_with_path(spec_for, shapes)


def encode(cfg: ModelConfig, params: dict, audio_embeds: jax.Array,
           sh: Sharder, *, compute_dtype=jnp.bfloat16) -> jax.Array:
    x = audio_embeds.astype(compute_dtype)
    S = x.shape[1]
    x = x + params["enc_pos"][:S].astype(compute_dtype)
    positions = jnp.arange(S, dtype=jnp.int32)

    def step(x, g):
        h = apply_norm(cfg, x, g.get("norm1"))
        x = x + attention_block(cfg, h, g["attn"], sh, positions=positions,
                                causal=False, rope=False,
                                op_prefix="enc_attn")
        h = apply_norm(cfg, x, g.get("norm2"))
        x = x + mlp(cfg, h, g["ffn"]["ffn_in"], g["ffn"]["ffn_out"], sh,
                    prefix="enc_")
        return sh.residual(x), None

    x, _ = jax.lax.scan(step, x, params["enc_groups"])
    return apply_norm(cfg, x, params.get("enc_final_norm"))


def _dec_unit(cfg, x, g, sh, positions, enc_out):
    h = apply_norm(cfg, x, g.get("norm1"))
    x = x + attention_block(cfg, h, g["attn"], sh, positions=positions,
                            causal=True, rope=False)
    h = apply_norm(cfg, x, g.get("norm_cross"))
    x = x + attention_block(cfg, h, g["cross"], sh, positions=positions,
                            causal=False, rope=False, op_prefix="cross",
                            kv_source=enc_out)
    h = apply_norm(cfg, x, g.get("norm2"))
    x = x + mlp(cfg, h, g["ffn"]["ffn_in"], g["ffn"]["ffn_out"], sh)
    return sh.residual(x)


def forward(cfg: ModelConfig, params: dict, tokens: jax.Array,
            audio_embeds: jax.Array, sh: Sharder, *,
            compute_dtype=jnp.bfloat16, remat: str = "none",
            return_hidden: bool = False):
    """Full enc-dec pass.  tokens: (B, S); audio_embeds: (B, enc_seq, d)."""
    enc_out = encode(cfg, params, audio_embeds, sh, compute_dtype=compute_dtype)
    x = embed(tokens, params["embed"]["table"], sh).astype(compute_dtype)
    S = x.shape[1]
    pos_tab = params["dec_pos"]
    x = x + jnp.take(pos_tab, jnp.arange(S) % pos_tab.shape[0],
                     axis=0).astype(compute_dtype)
    positions = jnp.arange(S, dtype=jnp.int32)

    def step(x, g):
        return _dec_unit(cfg, x, g, sh, positions, enc_out), None

    if remat == "block":
        step = jax.checkpoint(step)
    x, _ = jax.lax.scan(step, x, params["dec_groups"])
    x = apply_norm(cfg, x, params.get("final_norm"))
    if return_hidden:
        return x, jnp.zeros((), jnp.float32)
    logits = sh.dot("embed", x, params["embed"]["table"],
                    transpose_w=True).astype(jnp.float32)
    return logits, jnp.zeros((), jnp.float32)


def loss_fn(cfg: ModelConfig, params: dict, batch: dict, sh: Sharder,
            *, compute_dtype=jnp.bfloat16, remat: str = "none",
            aux_weight: float = 0.0):
    from repro.models.layers import lm_loss_chunked
    hidden, _ = forward(cfg, params, batch["tokens"], batch["audio_embeds"],
                        sh, compute_dtype=compute_dtype, remat=remat,
                        return_hidden=True)
    return lm_loss_chunked(cfg, hidden, params, batch["labels"], sh)


# ---------------------------------------------------------------------------
# Decode
# ---------------------------------------------------------------------------


def init_cache(cfg: ModelConfig, params_or_shapes: dict, batch: int,
               max_len: int, *, enc_out: Optional[jax.Array] = None) -> dict:
    """Self-attn ring cache + per-layer cross K/V (computed from enc_out,
    or zeros when building shape stand-ins)."""
    a = cfg.attention
    assert a is not None
    L = cfg.n_layers
    self_c = jax.tree.map(
        lambda x: jnp.broadcast_to(x, (L,) + x.shape),
        init_kv_cache(a, batch, max_len))
    K, hd = a.n_kv_heads, a.head_dim
    Se = cfg.enc_seq
    zeros = kv_rows(jnp.zeros((L, batch, Se, K, hd), jnp.bfloat16))
    cross = {"k": zeros, "v": zeros}
    return {"self": self_c, "cross": cross}


def precompute_cross_kv(cfg: ModelConfig, params: dict, enc_out: jax.Array,
                        sh: Sharder) -> dict:
    a = cfg.attention
    assert a is not None
    H, K, hd = a.n_heads, a.n_kv_heads, a.head_dim

    def one(g):
        w = sh.weight(g["cross"]["qkv"], "cross_qkv")
        kv = sh.dot("cross_qkv", enc_out, w[:, H * hd:], constrain=False)
        k, v = jnp.split(kv, 2, axis=-1)
        B, Se = enc_out.shape[:2]
        return (kv_rows(k.reshape(B, Se, K, hd)).astype(jnp.bfloat16),
                kv_rows(v.reshape(B, Se, K, hd)).astype(jnp.bfloat16))

    ks, vs = jax.lax.map(one, params["dec_groups"])
    return {"k": ks, "v": vs}


def decode_step(cfg: ModelConfig, params: dict, tokens: jax.Array,
                cache: dict, pos: jax.Array, sh: Sharder,
                *, compute_dtype=jnp.bfloat16):
    """tokens: (B, 1); pos: (B,).  Returns (logits, new cache)."""
    a = cfg.attention
    assert a is not None
    x = embed(tokens, params["embed"]["table"], sh).astype(compute_dtype)
    pos_tab = params["dec_pos"]
    x = x + jnp.take(pos_tab, pos[:, None] % pos_tab.shape[0],
                     axis=0).astype(compute_dtype)
    enc_pos = jnp.arange(cfg.enc_seq, dtype=jnp.int32)

    def step(x, scanned):
        g, sc, ck, cv = scanned
        B = x.shape[0]
        h = apply_norm(cfg, x, g.get("norm1"))
        qkv = sh.dot("attn_qkv", h, g["attn"]["qkv"])
        q, k, v = split_qkv(a, qkv, g["attn"].get("qkv_bias"))
        c = update_cache(sc, k[:, 0], v[:, 0], pos)
        out = decode_attend(q[:, 0], c["k"], c["v"], c["pos"], pos)
        x = x + sh.dot("attn_o", out.reshape(B, 1, -1), g["attn"]["o"])
        # cross attention against the precomputed encoder K/V
        h = apply_norm(cfg, x, g.get("norm_cross"))
        wq = sh.weight(g["cross"]["qkv"], "cross_qkv")
        H, K, hd = a.n_heads, a.n_kv_heads, a.head_dim
        qc = sh.dot("cross_qkv", h, wq[:, :H * hd],
                    constrain=False).reshape(B, K, H // K, hd)
        kv_pos = jnp.broadcast_to(enc_pos[None], (B, cfg.enc_seq))
        big = jnp.full((B,), cfg.enc_seq + 1, jnp.int32)
        out = decode_attend(qc, ck, cv, kv_pos, big)
        x = x + sh.dot("cross_o", out.reshape(B, 1, -1), g["cross"]["o"])
        h = apply_norm(cfg, x, g.get("norm2"))
        x = x + mlp(cfg, h, g["ffn"]["ffn_in"], g["ffn"]["ffn_out"], sh)
        return x, c

    x, new_self = jax.lax.scan(
        step, x, (params["dec_groups"], cache["self"],
                  cache["cross"]["k"], cache["cross"]["v"]))
    x = apply_norm(cfg, x, params.get("final_norm"))
    logits = sh.dot("embed", x, params["embed"]["table"],
                    transpose_w=True).astype(jnp.float32)
    return logits, {"self": new_self, "cross": cache["cross"]}
