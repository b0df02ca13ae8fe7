"""Decoder-only LM assembly (dense / MoE / SSM / hybrid / VLM).

Layers are grouped into scan groups: one *period* of the layer pattern
(dense: 1 layer; jamba: 8 layers = 1 attn + 7 mamba, MoE every 2nd) is the
scan body, with parameters stacked over ``n_layers // period`` — keeping
the lowered HLO size O(period), not O(n_layers).

Three entry points:
  forward(...)              — training / prefill (full sequence; can return caches)
  decode_step(...)          — one-token serve step against per-layer caches
  init(...) / param_pspecs  — parameter pytree + dataflow-program layouts
"""
from __future__ import annotations

import math
from dataclasses import dataclass

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs.base import ModelConfig
from repro.engine.dispatch import pe_fused_attn_unit, pe_fused_ffn
from repro.models import ssm as ssm_mod
from repro.models.attention import (attention_block, attn_params,
                                    chunk_attend, decode_attend,
                                    init_kv_cache, kv_heads, kv_rows,
                                    split_qkv, update_cache,
                                    update_cache_chunk)
from repro.models.layers import (Sharder, act_fn, apply_norm, apply_rope,
                                 embed, lm_logits, mlp, mlp_params,
                                 norm_params)
from repro.models.moe import moe_block, moe_params


# ---------------------------------------------------------------------------
# Layer pattern
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class UnitDesc:
    mixer: str            # 'attn' | 'rwkv6' | 'mamba'
    ffn: str              # 'dense' | 'moe'


def layer_pattern(cfg: ModelConfig) -> list:
    m_period = cfg.moe.moe_period if cfg.moe is not None else 1
    period = cfg.attn_period * m_period // math.gcd(cfg.attn_period, m_period)
    if cfg.n_layers % period:
        raise ValueError(f"{cfg.name}: n_layers={cfg.n_layers} not divisible "
                         f"by pattern period {period}")
    units = []
    for i in range(period):
        if cfg.is_attention_layer(i):
            mixer = "attn"
        else:
            assert cfg.ssm is not None
            mixer = cfg.ssm.kind
        units.append(UnitDesc(mixer, "moe" if cfg.is_moe_layer(i) else "dense"))
    return units


def n_groups(cfg: ModelConfig) -> int:
    return cfg.n_layers // len(layer_pattern(cfg))


# ---------------------------------------------------------------------------
# Init
# ---------------------------------------------------------------------------


def _unit_params(cfg: ModelConfig, key, unit: UnitDesc) -> dict:
    ks = jax.random.split(key, 4)
    p: dict = {"norm1": norm_params(cfg, ks[0]), "norm2": norm_params(cfg, ks[1])}
    if unit.mixer == "attn":
        p["attn"] = attn_params(cfg, ks[2])
    elif unit.mixer == "rwkv6":
        p["rwkv"] = ssm_mod.rwkv_params(cfg, ks[2])
    else:
        p["mamba"] = ssm_mod.mamba_params(cfg, ks[2])
    if unit.ffn == "moe":
        p["moe"] = moe_params(cfg, ks[3])
        if cfg.moe is not None and cfg.moe.dense_residual:
            p["ffn"] = mlp_params(cfg, jax.random.fold_in(ks[3], 1))
    else:
        p["ffn"] = mlp_params(cfg, ks[3])
    # norms may be None (olmo): drop for a clean pytree
    return {k: v for k, v in p.items() if v is not None}


def init(key, cfg: ModelConfig) -> dict:
    pattern = layer_pattern(cfg)
    ng = n_groups(cfg)
    k_embed, k_head, k_groups, k_final = jax.random.split(key, 4)
    params: dict = {
        "embed": {"table": jax.random.normal(
            k_embed, (cfg.vocab_size, cfg.d_model), jnp.float32) * 0.02},
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = jax.random.normal(
            k_head, (cfg.d_model, cfg.vocab_size), jnp.float32) * 0.02
    fn = norm_params(cfg, k_final)
    if fn is not None:
        params["final_norm"] = fn
    if cfg.frontend == "vision_stub":
        params["vlm_proj"] = jax.random.normal(
            jax.random.fold_in(k_head, 2), (cfg.d_model, cfg.d_model),
            jnp.float32) * cfg.d_model ** -0.5

    def one_group(gkey):
        uks = jax.random.split(gkey, len(pattern))
        return {f"u{i}": _unit_params(cfg, uks[i], u)
                for i, u in enumerate(pattern)}

    gkeys = jax.random.split(k_groups, ng)
    params["groups"] = jax.vmap(one_group)(gkeys)
    return params


def param_shapes(cfg: ModelConfig) -> dict:
    """ShapeDtypeStruct pytree — the dry-run's no-allocation stand-in."""
    return jax.eval_shape(lambda k: init(k, cfg),
                          jax.ShapeDtypeStruct((2,), jnp.uint32))


# ---------------------------------------------------------------------------
# Param -> dataflow-program layout
# ---------------------------------------------------------------------------

_LEAF_TO_OP = {
    ("attn", "qkv"): "attn_qkv", ("attn", "o"): "attn_o",
    ("rwkv", "rkvg"): "rwkv_rkvg", ("rwkv", "decay"): "rwkv_decay",
    ("rwkv", "o"): "rwkv_o",
    ("mamba", "in"): "mamba_in", ("mamba", "conv"): "mamba_conv",
    ("mamba", "xproj"): "mamba_xproj", ("mamba", "dt"): "mamba_dt",
    ("mamba", "out"): "mamba_out",
    ("ffn", "ffn_in"): "ffn_in", ("ffn", "ffn_out"): "ffn_out",
    ("moe", "router"): "moe_router",
    ("moe", "experts_in"): "moe_experts_in",
    ("moe", "experts_gate"): "moe_experts_gate",
    ("moe", "experts_out"): "moe_experts_out",
    ("enc_attn", "qkv"): "enc_attn_qkv", ("enc_attn", "o"): "enc_attn_o",
    ("enc_ffn", "ffn_in"): "enc_ffn_in", ("enc_ffn", "ffn_out"): "enc_ffn_out",
    ("cross", "qkv"): "cross_qkv", ("cross", "o"): "cross_o",
}


def param_pspecs(cfg: ModelConfig, program) -> dict:
    """Same-structure pytree of PartitionSpecs from the compiled program."""
    shapes = param_shapes(cfg)

    def spec_for(path, leaf):
        keys = [getattr(p, "key", getattr(p, "name", None)) for p in path]
        stacked = "groups" in keys or "enc_groups" in keys or "dec_groups" in keys
        if "embed" in keys:
            return program.weight_spec("embed", stacked=False)
        if "lm_head" in keys:
            return program.weight_spec("lm_head", stacked=False)
        if "vlm_proj" in keys:
            return program.weight_spec("vlm_proj", stacked=False)
        for (parent, name), op in _LEAF_TO_OP.items():
            if parent in keys and keys[-1] == name and op in program.plan.ops:
                return program.weight_spec(op, stacked=stacked)
        return P()    # norms, biases, router state, mixes: replicated

    return jax.tree_util.tree_map_with_path(spec_for, shapes)


# ---------------------------------------------------------------------------
# Forward (train / prefill)
# ---------------------------------------------------------------------------


def _unit_forward(cfg: ModelConfig, x, uparams: dict, unit: UnitDesc,
                  sh: Sharder, positions, collect_cache: bool):
    """Returns (x, aux_loss, cache_contrib)."""
    h = apply_norm(cfg, x, uparams.get("norm1"))
    cache: dict = {}
    if unit.mixer == "attn":
        mix = attention_block(cfg, h, uparams["attn"], sh, positions=positions)
        if collect_cache:
            a = cfg.attention
            qkv = sh.dot("attn_qkv", h, uparams["attn"]["qkv"])
            _, k, v = split_qkv(a, qkv, uparams["attn"].get("qkv_bias"))
            k = apply_rope(k, positions, a.rope_theta)
            size = min(h.shape[1], a.window) if a.window else h.shape[1]
            cache["attn"] = {
                "k": kv_rows(k[:, -size:]).astype(jnp.bfloat16),
                "v": kv_rows(v[:, -size:]).astype(jnp.bfloat16),
                "pos": jnp.broadcast_to(
                    positions[-size:][None].astype(jnp.int32),
                    (h.shape[0], size)),
            }
    elif unit.mixer == "rwkv6":
        if collect_cache:
            st = ssm_mod.rwkv_init_state(cfg, x.shape[0])
            mix, new_st = ssm_mod.rwkv_block(cfg, h, uparams["rwkv"], sh, st)
            cache["rwkv"] = new_st
        else:
            mix, _ = ssm_mod.rwkv_block(cfg, h, uparams["rwkv"], sh)
    else:
        if collect_cache:
            st = ssm_mod.mamba_init_state(cfg, x.shape[0])
            mix, new_st = ssm_mod.mamba_block(cfg, h, uparams["mamba"], sh, st)
            cache["mamba"] = new_st
        else:
            mix, _ = ssm_mod.mamba_block(cfg, h, uparams["mamba"], sh)
    x = x + mix
    h2 = apply_norm(cfg, x, uparams.get("norm2"))
    aux = jnp.zeros((), jnp.float32)
    if unit.ffn == "moe":
        y, aux = moe_block(cfg, h2, uparams["moe"], sh)
        if cfg.moe is not None and cfg.moe.dense_residual:
            y = y + mlp(cfg, h2, uparams["ffn"]["ffn_in"],
                        uparams["ffn"]["ffn_out"], sh)
    else:
        y = mlp(cfg, h2, uparams["ffn"]["ffn_in"], uparams["ffn"]["ffn_out"], sh)
    x = sh.residual(x + y)
    return x, aux, cache


def prologue(cfg: ModelConfig, params: dict, tokens: jax.Array, sh: Sharder,
             *, compute_dtype=jnp.bfloat16, vision_embeds=None):
    """Embedding + modality frontend + residual layout: everything before
    the first layer group.  Pipeline stage 0 runs exactly this (the
    remaining stages receive the residual stream instead)."""
    x = embed(tokens, params["embed"]["table"], sh).astype(compute_dtype)
    if cfg.frontend == "vision_stub":
        assert vision_embeds is not None
        v = sh.dot("vlm_proj", vision_embeds.astype(compute_dtype),
                   params["vlm_proj"])
        x = jnp.concatenate([v, x], axis=1)
    positions = jnp.arange(x.shape[1], dtype=jnp.int32)
    return sh.residual(x), positions


def group_scan(cfg: ModelConfig, x: jax.Array, aux: jax.Array, groups,
               sh: Sharder, positions: jax.Array, *, remat="none",
               collect_cache: bool = False):
    """Scan a contiguous slice of scan groups: the body of `forward`, and
    of one pipeline stage (`groups` then holds that stage's param slice).
    Returns (x, aux, caches) — caches is None unless collect_cache.

    remat: 'none' | 'block' | 'full', or a per-group sequence of modes
    (the memory planner's ``MemoryPolicy.remat``).  A mixed sequence runs
    one scan per contiguous run of equal modes over the matching stacked
    param slice — each group's math is identical to the uniform scan, so
    values are bit-equal; only what autodiff SAVES differs.
    """
    pattern = layer_pattern(cfg)

    def group_step(carry, gparams):
        x, aux = carry
        caches = {}
        for i, u in enumerate(pattern):
            x, a, c = _unit_forward(cfg, x, gparams[f"u{i}"], u, sh,
                                    positions, collect_cache)
            aux = aux + a
            if c:
                caches[f"u{i}"] = c
        return (x, aux), caches if collect_cache else None

    ng = jax.tree.leaves(groups)[0].shape[0]
    if isinstance(remat, str):
        runs = [(remat, 0, ng)]
    else:
        remat = tuple(remat)
        if len(remat) != ng:
            raise ValueError(f"per-group remat has {len(remat)} entries "
                             f"for {ng} scan groups")
        runs = []
        for g, r in enumerate(remat):
            if runs and runs[-1][0] == r:
                runs[-1] = (r, runs[-1][1], g + 1)
            else:
                runs.append((r, g, g + 1))

    cache_parts: list = []
    for mode, g0, g1 in runs:
        body = jax.checkpoint(group_step) if mode in ("block", "full") \
            else group_step
        part = (groups if (g0, g1) == (0, ng)
                else jax.tree.map(lambda a: a[g0:g1], groups))
        (x, aux), caches = jax.lax.scan(body, (x, aux), part)
        if collect_cache:
            cache_parts.append(caches)
    if not collect_cache:
        return x, aux, None
    caches = (cache_parts[0] if len(cache_parts) == 1 else
              jax.tree.map(lambda *cs: jnp.concatenate(cs, axis=0),
                           *cache_parts))
    return x, aux, caches


def forward(cfg: ModelConfig, params: dict, tokens: jax.Array, sh: Sharder,
            *, compute_dtype=jnp.bfloat16, vision_embeds=None,
            return_cache: bool = False, remat="none",
            return_hidden: bool = False):
    """tokens: (B, S_text).  Returns (logits f32 | hidden, aux[, caches])."""
    x, positions = prologue(cfg, params, tokens, sh,
                            compute_dtype=compute_dtype,
                            vision_embeds=vision_embeds)
    x, aux, caches = group_scan(cfg, x, jnp.zeros((), jnp.float32),
                                params["groups"], sh, positions, remat=remat,
                                collect_cache=return_cache)
    x = apply_norm(cfg, x, params.get("final_norm"))
    if return_hidden:
        if return_cache:
            return x, aux, caches
        return x, aux
    logits = lm_logits(x, cfg, params, sh)
    if return_cache:
        return logits, aux, caches
    return logits, aux


def head_loss(cfg: ModelConfig, params: dict, hidden: jax.Array,
              aux: jax.Array, labels: jax.Array, sh: Sharder,
              *, aux_weight: float = 0.01):
    """Loss head on the final-normed hidden states: the tail of `loss_fn`,
    and of the LAST pipeline stage (which receives `aux` accumulated
    across every upstream stage)."""
    if cfg.frontend == "vision_stub":
        # loss on the text positions only
        hidden = hidden[:, -labels.shape[1]:]
    from repro.models.layers import lm_loss_chunked
    return lm_loss_chunked(cfg, hidden, params, labels, sh) \
        + aux_weight * aux


def loss_fn(cfg: ModelConfig, params: dict, batch: dict, sh: Sharder,
            *, compute_dtype=jnp.bfloat16, remat="none",
            aux_weight: float = 0.01):
    hidden, aux = forward(cfg, params, batch["tokens"], sh,
                          compute_dtype=compute_dtype,
                          vision_embeds=batch.get("vision_embeds"),
                          remat=remat, return_hidden=True)
    return head_loss(cfg, params, hidden, aux, batch["labels"], sh,
                     aux_weight=aux_weight)


# ---------------------------------------------------------------------------
# Decode
# ---------------------------------------------------------------------------


def init_cache(cfg: ModelConfig, batch: int, max_len: int) -> dict:
    """Per-group stacked caches for decode."""
    pattern = layer_pattern(cfg)
    ng = n_groups(cfg)

    def one():
        c = {}
        for i, u in enumerate(pattern):
            if u.mixer == "attn":
                c[f"u{i}"] = {"attn": init_kv_cache(cfg.attention, batch, max_len)}
            elif u.mixer == "rwkv6":
                c[f"u{i}"] = {"rwkv": ssm_mod.rwkv_init_state(cfg, batch)}
            else:
                c[f"u{i}"] = {"mamba": ssm_mod.mamba_init_state(cfg, batch)}
        return c

    return jax.tree.map(lambda x: jnp.broadcast_to(x, (ng,) + x.shape), one())


def _layer(stack, g):
    """Layer group `g` of a cache subtree stacked over groups."""
    return jax.tree.map(
        lambda a: jax.lax.dynamic_index_in_dim(a, g, keepdims=False), stack)


def _decode_attend_layer(cfg: ModelConfig, q, k, v, stack: dict, pos,
                         g, active):
    """Write this token's K/V into layer `g` of the stacked attention
    cache (active rows only, in place) and attend over that layer.
    q: (B, 1, K, G, hd); k/v: (B, 1, K, hd).  Returns (out (B, 1, K*G*hd),
    new stack)."""
    c = update_cache(stack, k[:, 0], v[:, 0], pos, layer=g, active=active)
    lc = _layer(c, g)
    out = decode_attend(q[:, 0], lc["k"], lc["v"], lc["pos"], pos,
                        window=cfg.attention.window)
    return out.reshape(q.shape[0], 1, -1), c


def _decode_state_layer(cfg: ModelConfig, h, uparams: dict, unit: UnitDesc,
                        sh: Sharder, stack: dict, g, active):
    """One recurrent step (rwkv6 / mamba) on layer `g` of the stacked
    state.  Rows where `active` is False keep their old state (a row
    select: the state is O(slots x heads x hd^2), not O(max_len))."""
    block = ssm_mod.rwkv_block if unit.mixer == "rwkv6" \
        else ssm_mod.mamba_block
    key = "rwkv" if unit.mixer == "rwkv6" else "mamba"
    old = _layer(stack[key], g)
    mix, st = block(cfg, h, uparams[key], sh, old)
    if active is not None:
        st = jax.tree.map(
            lambda n, o: jnp.where(
                active.reshape((-1,) + (1,) * (n.ndim - 1)), n, o), st, old)
    new = jax.tree.map(
        lambda a, n: jax.lax.dynamic_update_index_in_dim(
            a, n.astype(a.dtype), g, 0), stack[key], st)
    return mix, {**stack, key: new}


def _unit_decode(cfg: ModelConfig, x, uparams: dict, unit: UnitDesc,
                 sh: Sharder, cache: dict, pos: jax.Array, g, active):
    """x: (B, 1, d); pos: (B,) absolute position; cache: this unit's
    caches stacked over groups, `g` the group.  Returns (x, new_cache)."""
    h = apply_norm(cfg, x, uparams.get("norm1"))
    new_cache = dict(cache)
    if unit.mixer == "attn":
        a = cfg.attention
        qkv = sh.dot("attn_qkv", h, uparams["attn"]["qkv"])
        q, k, v = split_qkv(a, qkv, uparams["attn"].get("qkv_bias"))
        posb = pos[:, None]
        B = h.shape[0]
        K_, G, hd = q.shape[2:]
        q = apply_rope(q.reshape(B, 1, K_ * G, hd), posb,
                       a.rope_theta).reshape(B, 1, K_, G, hd)
        k = apply_rope(k, posb, a.rope_theta)
        out, new_cache["attn"] = _decode_attend_layer(
            cfg, q, k, v, cache["attn"], pos, g, active)
        mix = sh.dot("attn_o", out, uparams["attn"]["o"])
    else:
        mix, new_cache = _decode_state_layer(cfg, h, uparams, unit, sh,
                                             cache, g, active)
    x = x + mix
    h2 = apply_norm(cfg, x, uparams.get("norm2"))
    if unit.ffn == "moe":
        y, _ = moe_block(cfg, h2, uparams["moe"], sh)
        if cfg.moe is not None and cfg.moe.dense_residual:
            y = y + mlp(cfg, h2, uparams["ffn"]["ffn_in"],
                        uparams["ffn"]["ffn_out"], sh)
    else:
        y = mlp(cfg, h2, uparams["ffn"]["ffn_in"], uparams["ffn"]["ffn_out"], sh)
    return x + y, new_cache


def _mlp_fused_ref(cfg: ModelConfig, x, w_in, w_out):
    """FFN with the per-op dispatch seam inlined (reference backend).

    ``mlp`` routes through ``sh.dot`` -> ``_reference_dot`` == a plain
    ``@`` against the bf16-cast weight; replaying that literally keeps the
    fused composition bit-identical to the per-op loop.
    """
    h = x @ w_in.astype(x.dtype)
    if cfg.act in ("swiglu", "geglu"):
        g, u = jnp.split(h, 2, axis=-1)
        gate = jax.nn.silu(g) if cfg.act == "swiglu" else jax.nn.gelu(g)
        h = gate * u
    else:
        h = act_fn(cfg.act, h)
    return h @ w_out.astype(h.dtype)


def _unit_decode_fused(cfg: ModelConfig, x, uparams: dict, unit: UnitDesc,
                       sh: Sharder, cache: dict, pos: jax.Array, g, active):
    """Fused per-layer decode: the unit as ONE dispatch, not four.

    On the pallas backend the attention projections, cache append, paged
    attention and dense FF lower onto the ``decode_fused`` megakernel
    (kernels/decode_fused.py) — one launch per layer.  SSM recurrences
    and MoE experts keep their per-op paths (VPU/state words); their
    units fuse only the FF half.

    On the reference backend this replays ``_unit_decode`` with the
    dispatch seam inlined (plain bf16 ``@`` == ``_reference_dot``), so
    the fused path is bit-identical per request to the per-op matvec
    loop — the parity oracle the megakernel is validated against.
    """
    if sh.backend == "pallas":
        return _unit_decode_fused_pallas(cfg, x, uparams, unit, sh, cache,
                                         pos, g, active)
    h = apply_norm(cfg, x, uparams.get("norm1"))
    new_cache = dict(cache)
    if unit.mixer == "attn":
        a = cfg.attention
        qkv = h @ uparams["attn"]["qkv"].astype(h.dtype)
        q, k, v = split_qkv(a, qkv, uparams["attn"].get("qkv_bias"))
        posb = pos[:, None]
        B = h.shape[0]
        K_, G, hd = q.shape[2:]
        q = apply_rope(q.reshape(B, 1, K_ * G, hd), posb,
                       a.rope_theta).reshape(B, 1, K_, G, hd)
        k = apply_rope(k, posb, a.rope_theta)
        out, new_cache["attn"] = _decode_attend_layer(
            cfg, q, k, v, cache["attn"], pos, g, active)
        mix = out @ uparams["attn"]["o"].astype(out.dtype)
    else:
        mix, new_cache = _decode_state_layer(cfg, h, uparams, unit, sh,
                                             cache, g, active)
    x = x + mix
    h2 = apply_norm(cfg, x, uparams.get("norm2"))
    if unit.ffn == "moe":
        y, _ = moe_block(cfg, h2, uparams["moe"], sh)
        if cfg.moe is not None and cfg.moe.dense_residual:
            y = y + _mlp_fused_ref(cfg, h2, uparams["ffn"]["ffn_in"],
                                   uparams["ffn"]["ffn_out"])
    else:
        y = _mlp_fused_ref(cfg, h2, uparams["ffn"]["ffn_in"],
                           uparams["ffn"]["ffn_out"])
    return x + y, new_cache


def _fused_norm_args(cfg: ModelConfig, uparams: dict, key: str):
    """(norm params, kernel norm kind) — nonparametric_ln is a layernorm
    with no affine operands."""
    if cfg.norm == "nonparametric_ln":
        return None, "layernorm"
    return uparams.get(key), cfg.norm


def _unit_decode_fused_pallas(cfg: ModelConfig, x, uparams: dict,
                              unit: UnitDesc, sh: Sharder, cache: dict,
                              pos: jax.Array, g, active):
    """Lower the unit onto the decode_fused megakernel (pallas backend).

    The kernel appends to its own copy of layer `g`'s rows; the appended
    position is then written into the stacked arena like every other
    decode (``update_cache``: active rows only, in place)."""
    new_cache = dict(cache)
    n1, nk = _fused_norm_args(cfg, uparams, "norm1")
    n2, _ = _fused_norm_args(cfg, uparams, "norm2")
    dense = unit.ffn == "dense"
    if unit.mixer == "attn":
        a = cfg.attention
        lc = _layer(cache["attn"], g)
        y2, c = pe_fused_attn_unit(       # the kernel reads (B, S, K, hd)
            x[:, 0], {"k": kv_heads(lc["k"], a.n_kv_heads),
                      "v": kv_heads(lc["v"], a.n_kv_heads),
                      "pos": lc["pos"]}, pos,
            norm1=n1, qkv_w=uparams["attn"]["qkv"],
            qkv_bias=uparams["attn"].get("qkv_bias"),
            o_w=uparams["attn"]["o"],
            norm2=n2 if dense else None,
            w_in=uparams["ffn"]["ffn_in"] if dense else None,
            w_out=uparams["ffn"]["ffn_out"] if dense else None,
            heads=a.n_heads, kv_heads=a.n_kv_heads, head_dim=a.head_dim,
            rope_theta=a.rope_theta, window=a.window,
            norm_kind=nk, act=cfg.act, with_ffn=dense,
            word=sh.word("attn_qkv"), interpret=sh.interpret)
        b = jnp.arange(pos.shape[0])
        slot = pos % c["pos"].shape[-1]
        new_cache["attn"] = update_cache(
            cache["attn"], c["k"][b, slot], c["v"][b, slot], pos,
            layer=g, active=active)
        if dense:
            return y2[:, None], new_cache
        x = y2[:, None]
    else:
        # SSM recurrence: a VPU/state word — stays on its per-op path
        h = apply_norm(cfg, x, uparams.get("norm1"))
        mix, new_cache = _decode_state_layer(cfg, h, uparams, unit, sh,
                                             cache, g, active)
        x = x + mix
    if unit.ffn == "moe":
        h2 = apply_norm(cfg, x, uparams.get("norm2"))
        y, _ = moe_block(cfg, h2, uparams["moe"], sh)
        if cfg.moe is not None and cfg.moe.dense_residual:
            y = y + mlp(cfg, h2, uparams["ffn"]["ffn_in"],
                        uparams["ffn"]["ffn_out"], sh)
        return x + y, new_cache
    y2 = pe_fused_ffn(
        x[:, 0], norm2=n2, w_in=uparams["ffn"]["ffn_in"],
        w_out=uparams["ffn"]["ffn_out"], norm_kind=nk, act=cfg.act,
        word=sh.word("ffn_in"), interpret=sh.interpret)
    return y2[:, None], new_cache


def _unit_chunk(cfg: ModelConfig, x, uparams: dict, unit: UnitDesc,
                sh: Sharder, cache: dict, pos: jax.Array):
    """Chunked-prefill unit step.  x: (B, T, d); pos: (B, T) absolute.

    Mirrors ``_unit_decode`` exactly (same cast discipline, no residual
    re-layout) so each token's math is bit-identical to a single-token
    decode at that position.  Projections run T tokens wide — the
    compute-bound PREFILL program word; the SSM recurrences consume the
    whole chunk from carried state (one scan == T single steps).
    """
    h = apply_norm(cfg, x, uparams.get("norm1"))
    new_cache = dict(cache)
    if unit.mixer == "attn":
        a = cfg.attention
        qkv = sh.dot("attn_qkv", h, uparams["attn"]["qkv"])
        q, k, v = split_qkv(a, qkv, uparams["attn"].get("qkv_bias"))
        B, T = h.shape[:2]
        K_, G, hd = q.shape[2:]
        q = apply_rope(q.reshape(B, T, K_ * G, hd), pos,
                       a.rope_theta).reshape(B, T, K_, G, hd)
        k = apply_rope(k, pos, a.rope_theta)
        if a.window is not None:
            # windowed ring cache: a vectorised chunk insert would let a
            # later in-chunk token overwrite the ring slot an earlier
            # query must still attend (wrap mid-chunk) — sequence the
            # insert+attend per token, exactly the decode path
            def one(c, inp):
                qt, kt, vt, pt = inp
                c = update_cache(c, kt, vt, pt)
                o = decode_attend(qt, c["k"], c["v"], c["pos"], pt,
                                  window=a.window)
                return c, o
            c, out = jax.lax.scan(
                one, cache["attn"],
                (q.transpose(1, 0, 2, 3, 4), k.transpose(1, 0, 2, 3),
                 v.transpose(1, 0, 2, 3), pos.T))
            out = out.transpose(1, 0, 2, 3, 4)            # (B,T,K,G,hd)
        else:
            c = update_cache_chunk(cache["attn"], k, v, pos)
            out = chunk_attend(q, c["k"], c["v"], c["pos"], pos)
        mix = sh.dot("attn_o", out.reshape(B, T, -1), uparams["attn"]["o"])
        new_cache["attn"] = c
    elif unit.mixer == "rwkv6":
        mix, st = ssm_mod.rwkv_block(cfg, h, uparams["rwkv"], sh, cache["rwkv"])
        new_cache["rwkv"] = st
    else:
        mix, st = ssm_mod.mamba_block(cfg, h, uparams["mamba"], sh, cache["mamba"])
        new_cache["mamba"] = st
    x = x + mix
    h2 = apply_norm(cfg, x, uparams.get("norm2"))
    if unit.ffn == "moe":
        y, _ = moe_block(cfg, h2, uparams["moe"], sh)
        if cfg.moe is not None and cfg.moe.dense_residual:
            y = y + mlp(cfg, h2, uparams["ffn"]["ffn_in"],
                        uparams["ffn"]["ffn_out"], sh)
    else:
        y = mlp(cfg, h2, uparams["ffn"]["ffn_in"], uparams["ffn"]["ffn_out"], sh)
    return x + y, new_cache


def chunk_step(cfg: ModelConfig, params: dict, tokens: jax.Array,
               cache: dict, pos0: jax.Array, sh: Sharder,
               *, compute_dtype=jnp.bfloat16):
    """Multi-token serve step: T prompt tokens against the caches.

    tokens: (B, T); pos0: (B,) absolute position of tokens[:, 0].
    Returns (logits (B, T, V) f32, new_cache).  The serving engine's
    chunked prefill: bit-identical to T sequential ``decode_step`` calls
    on the reference backend, but runs the projections T tokens wide
    (the compute-bound PREFILL program word).
    """
    pattern = layer_pattern(cfg)
    B, T = tokens.shape
    pos = pos0[:, None] + jnp.arange(T, dtype=jnp.int32)[None]
    x = embed(tokens, params["embed"]["table"], sh).astype(compute_dtype)

    def group_step(x, scanned):
        gparams, gcache = scanned
        new_c = {}
        for i, u in enumerate(pattern):
            x, c = _unit_chunk(cfg, x, gparams[f"u{i}"], u, sh,
                               gcache[f"u{i}"], pos)
            new_c[f"u{i}"] = c
        return x, new_c

    x, new_caches = jax.lax.scan(group_step, x, (params["groups"], cache))
    x = apply_norm(cfg, x, params.get("final_norm"))
    logits = lm_logits(x, cfg, params, sh)
    return logits, new_caches


def decode_step(cfg: ModelConfig, params: dict, tokens: jax.Array,
                cache: dict, pos: jax.Array, sh: Sharder,
                *, compute_dtype=jnp.bfloat16, fused: bool = False,
                active=None):
    """One serve step.  tokens: (B, 1); pos: (B,).  Returns (logits, cache).

    active: optional (B,) bool row mask; rows where it is False leave
    their cache rows untouched (their logits are garbage).  None writes
    every row.  The stacked cache rides the group loop as carried state
    and each layer's new position is scattered into it in place, so a
    step moves one position per active row, not the cache.

    fused=True routes each unit through the fused-decode path (one
    dispatch per layer — the decode_fused megakernel on the pallas
    backend, its bit-parity inline composition on reference).
    """
    pattern = layer_pattern(cfg)
    unit_fn = _unit_decode_fused if fused else _unit_decode
    x = embed(tokens, params["embed"]["table"], sh).astype(compute_dtype)

    def group_step(carry, gparams):
        x, cache, g = carry
        cache = dict(cache)
        for i, u in enumerate(pattern):
            x, cache[f"u{i}"] = unit_fn(cfg, x, gparams[f"u{i}"], u, sh,
                                        cache[f"u{i}"], pos, g, active)
        return (x, cache, g + 1), None

    (x, cache, _), _ = jax.lax.scan(
        group_step, (x, cache, jnp.zeros((), jnp.int32)), params["groups"])
    x = apply_norm(cfg, x, params.get("final_norm"))
    logits = lm_logits(x, cfg, params, sh)
    return logits, cache
