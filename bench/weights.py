"""Weights made by the benchmark from the seed, in the program's layout,
and the same weights in the reference's layout.

The program stores a dense decoder as one scan group of stacked layers
(``repro.models.transformer``): ``groups.u0.attn.qkv`` is the fused
(L, d, (H + 2K) hd) projection with q, k, v in that order, and
``groups.u0.ffn.ffn_in`` the (L, d, 2f) gate-then-up projection.  The
benchmark checks that layout against the program's own shapes before it
uses it (``check_layout``), so a change of layout stops the run instead of
feeding the reference other weights.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

STREAMS = {"weights": 1, "data": 2, "traffic": 3, "sample": 4}


def root_key(seed: int, stream: str) -> jax.Array:
    """A key from all bits of a non-negative `seed` (jax.random.key keeps
    only the low 32), separate for each use."""
    if seed < 0:
        raise ValueError(f"seed must be >= 0, got {seed}")
    k = jax.random.key(seed & 0xFFFFFFFF)
    k = jax.random.fold_in(k, (seed >> 32) & 0xFFFFFFFF)
    return jax.random.fold_in(k, STREAMS[stream])


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


def _std(path: str, shape: tuple) -> float:
    if path == "embed.table" or path.endswith("qkv_bias"):
        return 0.02
    return shape[-2] ** -0.5            # fan-in of a matmul weight


def _nest(flat: dict) -> dict:
    out: dict = {}
    for path, v in flat.items():
        node = out
        *parents, leaf = path.split(".")
        for p in parents:
            node = node.setdefault(p, {})
        node[leaf] = v
    return out


def flatten(tree: dict, prefix: str = "") -> dict:
    out = {}
    for k, v in tree.items():
        if isinstance(v, dict):
            out.update(flatten(v, f"{prefix}{k}."))
        else:
            out[f"{prefix}{k}"] = v
    return out


def make_params(m: dict, key: jax.Array, dtype=jnp.bfloat16) -> dict:
    """Seeded weights in the program's layout (call under jit): normal at
    1/sqrt(fan-in), 0.02 for the embedding and biases, norm scales near 1."""
    flat = {}
    for i, (path, shape) in enumerate(sorted(program_shapes(m).items())):
        z = jax.random.normal(jax.random.fold_in(key, i), shape, jnp.float32)
        if path.endswith(".scale"):
            v = 1.0 + 0.02 * z
        else:
            v = z * _std(path, shape)
        flat[path] = v.astype(dtype)
    return _nest(flat)


def check_layout(m: dict, program_tree) -> None:
    """Raise unless the program's parameter tree has exactly the paths and
    shapes that :func:`program_shapes` describes."""
    got = {}
    for path, leaf in jax.tree_util.tree_flatten_with_path(program_tree)[0]:
        got[".".join(str(getattr(p, "key", p)) for p in path)] = tuple(leaf.shape)
    want = program_shapes(m)
    if got != want:
        raise RuntimeError(f"the program's parameter layout changed: "
                           f"{sorted(set(got.items()) ^ set(want.items()))}")


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


def reference_sumsq(ref_tree: dict) -> dict:
    """{reference leaf name: sum of squares over layers} of a tree in the
    reference's layout (call under jit)."""
    out = {}
    for k, v in ref_tree.items():
        if k == "layers":
            for lp in v:
                for name, a in lp.items():
                    out[f"layers.{name}"] = out.get(f"layers.{name}", 0.0) \
                        + jnp.sum(jnp.square(a))
        else:
            out[k] = jnp.sum(jnp.square(v))
    return out


def program_norms(m: dict, ref_sumsq: dict) -> dict:
    """{program leaf: norm} from reference-leaf sums of squares."""
    out = {}
    for leaf in program_shapes(m):
        out[leaf] = float(sum(ref_sumsq[p] for p in LEAF_PARTS[leaf])) ** 0.5
    return out
