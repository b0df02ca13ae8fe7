"""Weights made by the benchmark from the seed, in the program's layout,
and norms of them in the program's leaves.

The layout is the model family's (``fam``, bench/families/<family>.py:
``program_shapes``, ``init_std``, ``LEAF_PARTS``).  The benchmark checks
it against the program's own shapes before it uses it (``check_layout``),
so a change of layout stops the run instead of feeding the reference
other weights.
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


def make_params(fam, m: dict, key: jax.Array, dtype=jnp.bfloat16) -> dict:
    """Seeded weights in the program's layout (call under jit): normal at
    the family's ``init_std``, norm scales near 1."""
    flat = {}
    for i, (path, shape) in enumerate(sorted(fam.program_shapes(m).items())):
        z = jax.random.normal(jax.random.fold_in(key, i), shape, jnp.float32)
        if path.endswith(".scale"):
            v = 1.0 + 0.02 * z
        else:
            v = z * fam.init_std(path, shape)
        flat[path] = v.astype(dtype)
    return _nest(flat)


def check_layout(fam, m: dict, program_tree) -> None:
    """Raise unless the program's parameter tree has exactly the paths and
    shapes that the family's ``program_shapes`` describes."""
    got = {}
    for path, leaf in jax.tree_util.tree_flatten_with_path(program_tree)[0]:
        got[".".join(str(getattr(p, "key", p)) for p in path)] = tuple(leaf.shape)
    want = fam.program_shapes(m)
    if got != want:
        raise RuntimeError(f"the program's parameter layout changed: "
                           f"{sorted(set(got.items()) ^ set(want.items()))}")


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


def program_norms(fam, m: dict, ref_sumsq: dict) -> dict:
    """{program leaf: norm} from reference-leaf sums of squares."""
    out = {}
    for leaf in fam.program_shapes(m):
        out[leaf] = float(sum(ref_sumsq[p] for p in fam.LEAF_PARTS[leaf])) ** 0.5
    return out
