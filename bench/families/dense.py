"""The dense decoder family: grouped-query attention with rotary
embeddings, a SwiGLU feed-forward, RMSNorm or non-parametric LayerNorm,
a tied or separate LM head (qwen2-0.5b, olmo-1b).

A family module holds everything the benchmark knows of one model
family.  bench/run.py loads ``bench/families/<family>.py`` by path, the
name being the ``family`` key of a configuration's ``model`` block, and
refuses a family without a file.  The kinds, the comparison and the
weights take everything model-specific from it, so a new family is one
new file.  ``m`` is always the ``model`` block.  The interface:

  check_config(cfg, m)
      Raise unless the program's config is the one the file states.
  program_shapes(m), init_std(path, shape)
      {dotted path: shape} of the program's parameter tree, and the
      scale of the normal a weight is drawn from (norm scales, paths
      ending ``.scale``, are 1 + 0.02 z).  bench/weights.py makes the
      seeded weights (``make_params``) and checks the program's own tree
      (``check_layout``) from these.
  to_reference(m, params, xp), LEAF_PARTS
      The program-layout tree as float32 in the reference's layout: its
      top-level leaves, ``embed`` the token table among them, and
      ``layers``, a list of one dict per layer.  LEAF_PARTS maps each
      program leaf to the reference leaves it is made of (``layers.<k>``
      summed over the layers); their squares add to its square.
  embed(m, params, tokens)
      The token table's rows: the reference trainer scatters the
      gradient back into ``params["embed"]`` row by row, so anything
      more than the lookup belongs to layer 0.
  layer(m, i, lp, x, quant)
      Layer i, with its parameters lp, over x (b, T, d) at positions
      0..T-1.  i is a traced int32: layers that differ by index differ
      in the structure of lp (each distinct structure compiles once), or
      select with jax.numpy on i.
  head_params(params), logits(m, hp, x, quant), nll_sum(m, hp, x, labels, quant)
      The leaves the head reads, the logits (..., d) -> (..., V), and the
      summed next-token cross-entropy of x (b, T, d).  The trainer takes
      the head's gradient through head_params back to the leaves.
  aux_loss(m, i, lp, x, quant)   (optional; this family has none)
      Layer i's term of the mean loss beyond the cross-entropy, such as
      a router's balance loss, over x (B, T, d): the inputs of layer i
      for every row of the batch.
  train_flops_per_token(m, T), prefill_chunk_flops(m, pos0, n),
  decode_step_bytes(m, live, rows)
      The counts that the kinds put in the record for the per-layer
      readers: training FLOPs per token, one prompt chunk's forward
      FLOPs, one masked decode step's least HBM bytes.

``quant`` is None or "fp8" (the control: every matmul on float8 e4m3
operands).  The reference math of this family is bench/reference/dense.py.

The program stores a dense decoder as one scan group of stacked layers
(``repro.models.transformer``): ``groups.u0.attn.qkv`` is the fused
(L, d, (H + 2K) hd) projection with q, k, v in that order, and
``groups.u0.ffn.ffn_in`` the (L, d, 2f) gate-then-up projection.
"""
from __future__ import annotations

import jax.numpy as jnp

from bench.flops import BF16
from bench.reference import dense
from bench.weights import flatten

# the program's family that this module covers (repro.configs)
PROGRAM_FAMILY = "dense"


def check_config(cfg, m: dict) -> None:
    """Raise unless the program's config is the one the file states."""
    a = cfg.attention
    got = {"n_layers": cfg.n_layers, "d_model": cfg.d_model, "d_ff": cfg.d_ff,
           "vocab_size": cfg.vocab_size, "n_heads": a.n_heads,
           "n_kv_heads": a.n_kv_heads, "head_dim": a.head_dim,
           "qkv_bias": a.qkv_bias, "rope_theta": a.rope_theta,
           "norm": cfg.norm, "act": cfg.act,
           "tie_embeddings": cfg.tie_embeddings}
    want = {k: m[k] for k in got}
    if got != want or a.window is not None or cfg.family != PROGRAM_FAMILY:
        raise RuntimeError(f"program config {got} (family {cfg.family!r}) is "
                           f"not the file's {want} ({PROGRAM_FAMILY!r})")


def program_shapes(m: dict) -> dict:
    """{dotted path: shape} of the program's parameter tree for `m`."""
    L, d, f, V = m["n_layers"], m["d_model"], m["d_ff"], m["vocab_size"]
    H, K, hd = m["n_heads"], m["n_kv_heads"], m["head_dim"]
    qkv = (H + 2 * K) * hd
    s = {"embed.table": (V, d),
         "groups.u0.attn.qkv": (L, d, qkv),
         "groups.u0.attn.o": (L, H * hd, d),
         "groups.u0.ffn.ffn_in": (L, d, 2 * f),
         "groups.u0.ffn.ffn_out": (L, f, d)}
    if m["qkv_bias"]:
        s["groups.u0.attn.qkv_bias"] = (L, qkv)
    if m["norm"] != "nonparametric_ln":
        s["groups.u0.norm1.scale"] = (L, d)
        s["groups.u0.norm2.scale"] = (L, d)
        s["final_norm.scale"] = (d,)
    if not m["tie_embeddings"]:
        s["lm_head"] = (d, V)
    return s


def init_std(path: str, shape: tuple) -> float:
    """Normal at 1/sqrt(fan-in), 0.02 for the embedding and biases."""
    if path == "embed.table" or path.endswith("qkv_bias"):
        return 0.02
    return shape[-2] ** -0.5            # fan-in of a matmul weight


def to_reference(m: dict, params: dict, xp=jnp) -> dict:
    """The program-layout tree as float32 in the reference's layout; with
    ``xp=numpy``, on the host."""
    p = {k: xp.asarray(v).astype(xp.float32) for k, v in flatten(params).items()}
    H, K, hd, f = m["n_heads"], m["n_kv_heads"], m["head_dim"], m["d_ff"]
    cuts = (H * hd, (H + K) * hd)
    layers = []
    for li in range(m["n_layers"]):
        qkv = p["groups.u0.attn.qkv"][li]
        wq, wk, wv = xp.split(qkv, cuts, axis=-1)
        ffn_in = p["groups.u0.ffn.ffn_in"][li]
        lp = {"wq": wq, "wk": wk, "wv": wv, "wo": p["groups.u0.attn.o"][li],
              "w_gate": ffn_in[:, :f], "w_up": ffn_in[:, f:],
              "w_down": p["groups.u0.ffn.ffn_out"][li]}
        if m["qkv_bias"]:
            lp["bq"], lp["bk"], lp["bv"] = xp.split(
                p["groups.u0.attn.qkv_bias"][li], cuts)
        if m["norm"] != "nonparametric_ln":
            lp["norm1"] = p["groups.u0.norm1.scale"][li]
            lp["norm2"] = p["groups.u0.norm2.scale"][li]
        layers.append(lp)
    out = {"embed": p["embed.table"], "layers": layers}
    if "final_norm.scale" in p:
        out["final_norm"] = p["final_norm.scale"]
    if "lm_head" in p:
        out["lm_head"] = p["lm_head"]
    return out


# program leaf <- the reference leaves it is made of (their squares add)
LEAF_PARTS = {
    "embed.table": ("embed",),
    "lm_head": ("lm_head",),
    "final_norm.scale": ("final_norm",),
    "groups.u0.attn.qkv": ("layers.wq", "layers.wk", "layers.wv"),
    "groups.u0.attn.qkv_bias": ("layers.bq", "layers.bk", "layers.bv"),
    "groups.u0.attn.o": ("layers.wo",),
    "groups.u0.ffn.ffn_in": ("layers.w_gate", "layers.w_up"),
    "groups.u0.ffn.ffn_out": ("layers.w_down",),
    "groups.u0.norm1.scale": ("layers.norm1",),
    "groups.u0.norm2.scale": ("layers.norm2",),
}


def embed(m, params, tokens):
    """The token table's rows."""
    return params["embed"][tokens]


def layer(m, i, lp, x, quant=None):
    """Every layer of the family is the same decoder layer."""
    return dense.layer(m, lp, x, quant)


head_params = dense.head_params
logits = dense.logits
nll_sum = dense.nll_sum


def layer_matmul_params(m: dict) -> int:
    """Weights of one layer's matmuls: fused q/k/v, output, gated FFN."""
    d, hd = m["d_model"], m["head_dim"]
    qkv = d * (m["n_heads"] + 2 * m["n_kv_heads"]) * hd
    o = m["n_heads"] * hd * d
    ffn = d * 2 * m["d_ff"] + m["d_ff"] * d
    return qkv + o + ffn


def matmul_params(m: dict) -> int:
    """N of the PaLM count: every matmul weight, the LM head once (tied or
    not), the embedding lookup not at all."""
    return m["n_layers"] * layer_matmul_params(m) + m["vocab_size"] * m["d_model"]


def small_params(m: dict) -> int:
    """Parameters outside the matmuls that every token reads: norm scales
    and q/k/v biases (the embedding rows a token looks up are not
    counted)."""
    d = m["d_model"]
    per_layer = 0
    if m["norm"] != "nonparametric_ln":
        per_layer += 2 * d
    if m["qkv_bias"]:
        per_layer += (m["n_heads"] + 2 * m["n_kv_heads"]) * m["head_dim"]
    final = d if m["norm"] != "nonparametric_ln" else 0
    return m["n_layers"] * per_layer + final


def train_flops_per_token(m: dict, seq_len: int) -> float:
    """PaLM (arXiv:2204.02311, App. B): 6 N + 12 L H Q T per token."""
    attn = 12 * m["n_layers"] * m["n_heads"] * m["head_dim"] * seq_len
    return 6.0 * matmul_params(m) + attn


def kv_bytes_per_token(m: dict) -> int:
    """bf16 K and V of one position over all layers."""
    return 2 * m["n_layers"] * m["n_kv_heads"] * m["head_dim"] * BF16


def prefill_chunk_flops(m: dict, pos0: int, n_tokens: int) -> float:
    """Forward FLOPs of one prompt chunk of `n_tokens` starting at
    position `pos0`: the weight matmuls plus causal attention over the
    live positions only (QK^T and PV), and the head on the chunk's last
    position, which is all the engine reads."""
    n_layer = m["n_layers"] * layer_matmul_params(m)
    live = n_tokens * pos0 + n_tokens * (n_tokens + 1) // 2
    attn = 4 * m["n_layers"] * m["n_heads"] * m["head_dim"] * live
    head = 2 * m["vocab_size"] * m["d_model"]
    return 2.0 * n_layer * n_tokens + attn + head


def decode_step_bytes(m: dict, live_positions: int, active_rows: int) -> float:
    """Least HBM bytes of one masked decode step: every bf16 weight read
    once, the K/V of the live positions of the active rows read, and one
    new K/V position written per active row."""
    weights = (matmul_params(m) + small_params(m)) * BF16
    kv = kv_bytes_per_token(m)
    return float(weights + kv * live_positions + kv * active_rows)
