"""The comparison that decides ``correct``: the timed path's output against
the plain reference, number by number, each against its limit
(bench/limits/<cell>.json, set in PERF.md from measured readings).

Training numbers, each the worst over steps or leaves:
  loss_gap    |program loss - reference loss| / reference loss, per step
              (loss0_gap: the first step's alone);
  grad_gap    |program norm - reference norm| of a leaf's first gradient,
              over the larger of the reference's norm of that leaf and of
              the median leaf;
  grad_diff   the norm of the difference of the two first gradients of a
              leaf, over the same (grad_diff_median: the median over the
              leaves of that norm over the reference's norm of the leaf);
  change_gap  the same for a leaf's change over the checked steps, over
              the leaves whose reference gradient is at least a thousandth
              of the median leaf's (the rest move by round-off alone).
Serving number:
  logit_gap   the widest gap by which a served token's reference logit
              lies below the reference's best logit at that position.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from bench import weights
from bench.reference.train import Trainer

EXCLUDE_BELOW = 1e-3        # of the median leaf's reference gradient norm


def judge(readings: dict, limits: dict) -> dict:
    """{name: {value, limit, ok}} for every limit of the cell."""
    out = {}
    for name, limit in limits["limits"].items():
        v = readings[name]
        out[name] = {"value": v, "limit": limit, "ok": bool(v <= limit)}
    return out


def _device_bytes() -> float:
    stats = jax.devices()[0].memory_stats() or {}
    return float(stats.get("bytes_limit", 16e9))


def reference_training(fam, m: dict, traffic: dict, wkey, batches,
                       n_steps: int, *, quant=None, rows=None, first=None,
                       keep_first: bool = False) -> dict:
    """The first `n_steps` of the model family's (`fam`, bench/families/)
    reference from the benchmark's weights and batches: losses and
    per-leaf gradient and change norms in the program's leaf names.
    rows: use only the first `rows` of each batch (a planted fault).
    first: another run's first gradient (reference layout, on the host),
    whose per-leaf distance from this run's is returned as
    ``grad_diffs``; keep_first: return this run's first gradient as
    ``first``."""
    p_ref = jax.jit(lambda k: fam.to_reference(
        m, weights.make_params(fam, m, k)))(wkey)
    nbytes = sum(x.size * 4 for x in jax.tree.leaves(p_ref))
    trainer = Trainer(fam, m, traffic["optimizer"], p_ref, quant=quant,
                      row_block=1, moments_on_host=4 * nbytes > 0.75 * _device_bytes())
    del p_ref
    losses, out = [], {}
    for s in range(n_steps):
        b = batches(s)
        tok, lab = b["tokens"], b["labels"]
        if rows is not None:
            tok, lab = tok[:rows], lab[:rows]
        if s == 0:
            loss, out["grad_norms"], extra = trainer.step(
                tok, lab, against=first, keep=keep_first)
            if first is not None:
                out["grad_diffs"] = weights.program_norms(fam, m, extra)
            elif keep_first:
                out["first"] = extra
        else:
            loss, _, _ = trainer.step(tok, lab)
        losses.append(loss)

    @jax.jit
    def change_sumsq(p, k):
        p0 = fam.to_reference(m, weights.make_params(fam, m, k))
        return weights.reference_sumsq(jax.tree.map(jnp.subtract, p, p0))

    change = {k: float(v) for k, v in change_sumsq(trainer.params, wkey).items()}
    out["grad_norms"] = weights.program_norms(fam, m, out["grad_norms"])
    return dict(out, losses=losses,
                change_norms=weights.program_norms(fam, m, change))


def _worst(prog: dict, ref: dict, leaves) -> tuple:
    med = float(np.median([ref[k] for k in leaves]))
    gaps = {k: abs(prog[k] - ref[k]) / max(ref[k], med) for k in leaves}
    k = max(gaps, key=gaps.get)
    return gaps[k], k


def training_readings(prog: dict, ref: dict) -> dict:
    """prog: {losses, grad_norms, change_norms, grad_diffs} of the run
    judged, ref: the reference's; grad_diffs are the per-leaf norms of
    the difference of the two first gradients."""
    lg = [abs(a - b) / abs(b) for a, b in zip(prog["losses"], ref["losses"])]
    g_ref = ref["grad_norms"]
    med = float(np.median(list(g_ref.values())))
    moved = [k for k, v in g_ref.items() if v >= EXCLUDE_BELOW * med]
    grad_gap, grad_leaf = _worst(prog["grad_norms"], g_ref, list(g_ref))
    diffs = {k: d / max(g_ref[k], med) for k, d in prog["grad_diffs"].items()}
    diff_leaf = max(diffs, key=diffs.get)
    own = [d / g_ref[k] for k, d in prog["grad_diffs"].items() if g_ref[k] > 0]
    change_gap, change_leaf = _worst(prog["change_norms"], ref["change_norms"],
                                     moved)
    return {"loss0_gap": lg[0], "loss_gap": max(lg), "grad_gap": grad_gap,
            "grad_diff": diffs[diff_leaf],
            "grad_diff_median": float(np.median(own)), "change_gap": change_gap,
            "loss_gaps": lg, "grad_leaf": grad_leaf, "diff_leaf": diff_leaf,
            "change_leaf": change_leaf,
            "excluded": sorted(set(g_ref) - set(moved))}


class ServeReference:
    """Reference logits of whole sequences of the model family `fam`,
    layer by layer, with lengths padded to a multiple of `bucket` so that
    few shapes compile."""

    def __init__(self, fam, m: dict, wkey, *, quant=None, bucket: int = 512):
        self.fam, self.m, self.bucket = fam, m, bucket
        self.params = jax.jit(lambda k: fam.to_reference(
            m, weights.make_params(fam, m, k)))(wkey)
        self._layer = jax.jit(partial(fam.layer, m, quant=quant))
        self._logits = jax.jit(lambda hp, x: fam.logits(m, hp, x, quant))
        self.hp = fam.head_params(self.params)

    def logits(self, tokens: np.ndarray, at: np.ndarray) -> jax.Array:
        n = len(tokens)
        pad = -(-n // self.bucket) * self.bucket
        tok = np.zeros(pad, np.int32)
        tok[:n] = tokens
        x = self.fam.embed(self.m, self.params, jnp.asarray(tok))[None]
        for i, lp in enumerate(self.params["layers"]):
            x = self._layer(i, lp, x)
        npos = -(-len(at) // self.bucket) * self.bucket
        idx = np.zeros(npos, np.int32)
        idx[:len(at)] = at
        return self._logits(self.hp, x[0, jnp.asarray(idx)])[:len(at)]


def served_positions(prompt, served) -> tuple:
    """The sequence the reference reads, and the positions whose logits
    chose each served token."""
    seq = np.concatenate([np.asarray(prompt, np.int32),
                          np.asarray(served[:-1], np.int32)])
    at = np.arange(len(prompt) - 1, len(prompt) - 1 + len(served))
    return seq, at


@jax.jit
def _gaps(z, chosen):
    return jnp.max(z, -1) - jnp.take_along_axis(z, chosen[:, None], -1)[:, 0]


def serve_readings(ref: ServeReference, sample: list) -> dict:
    """sample: [(prompt, served tokens)].  The widest gap of a served
    token below the reference's best logit, and the tokens compared."""
    widest, n = 0.0, 0
    for prompt, served in sample:
        seq, at = served_positions(prompt, served)
        z = ref.logits(seq, at)
        g = _gaps(z, jnp.asarray(np.asarray(served, np.int32)))
        widest = max(widest, float(jnp.max(g)))
        n += len(served)
    return {"logit_gap": widest, "tokens_compared": n}


def control_serve_readings(ref: ServeReference, low: ServeReference,
                           sample: list) -> dict:
    """The control of a served cell: at the same positions, the gap in
    the reference's logits of the token the lower precision puts first."""
    widest = 0.0
    for prompt, served in sample:
        seq, at = served_positions(prompt, served)
        z = ref.logits(seq, at)
        pick = jnp.argmax(low.logits(seq, at), -1).astype(jnp.int32)
        widest = max(widest, float(jnp.max(_gaps(z, pick))))
    return {"logit_gap": widest}
