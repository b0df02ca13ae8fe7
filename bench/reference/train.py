"""Reference training steps: a model family's reference loss and
gradients, layer by layer, and AdamW in float32.

The whole batch is never held at once: activations are kept per layer and
block of rows, and each layer's gradient is formed from its own
vector-Jacobian product and applied at once, so the device holds the
float32 parameters, one copy of the activations and one layer's gradient.
The AdamW moments live on the device where the parameters take less than
a quarter of its memory, and on the host otherwise.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np


def adamw_leaf(p, g, m, v, t, *, lr, weight_decay, b1, b2, eps):
    m = b1 * m + (1 - b1) * g
    v = b2 * v + (1 - b2) * g * g
    mh = m / (1 - b1 ** t)
    vh = v / (1 - b2 ** t)
    p = p - lr * (mh / (jnp.sqrt(vh) + eps) + weight_decay * p)
    return p, m, v


class Trainer:
    """AdamW steps of the reference on one device.

    fam: the model family (bench/families/<family>.py), whose reference
    pieces (embed, layer, head_params, nll_sum, aux_loss) make the loss.
    params: the family's reference layout, float32 arrays on the device;
    they are updated in place of the caller's tree.
    opt: {"lr", "weight_decay", "b1", "b2", "eps"}.
    """

    def __init__(self, fam, m: dict, opt: dict, params: dict, *, quant=None,
                 row_block: int = 1, moments_on_host: bool = False):
        self.fam, self.m, self.params, self.quant = fam, m, params, quant
        self.row_block = row_block
        self.t = 0
        self.on_host = moments_on_host
        self.moments: dict = {}
        lay = partial(fam.layer, m, quant=quant)
        self._fwd = jax.jit(lay)
        self._vjp = jax.jit(lambda i, lp, x, g: jax.vjp(partial(lay, i), lp, x)[1](g))
        self._aux = None
        if hasattr(fam, "aux_loss"):
            self._aux = jax.jit(jax.value_and_grad(
                partial(fam.aux_loss, m, quant=quant), argnums=(1, 2)))
        self._adam = jax.jit(partial(adamw_leaf, **{k: opt[k] for k in (
            "lr", "weight_decay", "b1", "b2", "eps")}), donate_argnums=(0, 2, 3))
        self._embed = jax.jit(partial(fam.embed, m))
        self._sumsq = jax.jit(lambda t: jax.tree.map(
            lambda a: jnp.sum(jnp.square(a)), t))

        def head(hp, x, labels, n_tok):
            return fam.nll_sum(m, hp, x, labels, quant) / n_tok

        self._head = jax.jit(jax.value_and_grad(head, argnums=(0, 1)))
        self._scatter = jax.jit(
            lambda acc, tok, dx: acc.at[tok.reshape(-1)].add(
                dx.reshape(-1, dx.shape[-1])))

    def _update(self, key, p, g):
        if key not in self.moments:
            z = np.zeros(p.shape, np.float32)
            self.moments[key] = (z, z.copy()) if self.on_host else (
                jnp.zeros(p.shape, jnp.float32), jnp.zeros(p.shape, jnp.float32))
        mo, ve = self.moments[key]
        p, mo, ve = self._adam(p, g, jnp.asarray(mo), jnp.asarray(ve),
                               jnp.float32(self.t))
        if self.on_host:
            mo, ve = np.asarray(mo), np.asarray(ve)
        self.moments[key] = (mo, ve)
        return p

    def step(self, tokens, labels, against=None, keep: bool = False) -> tuple:
        """One AdamW step on a (B, T) batch.  Returns (mean loss, per-leaf
        sum of squares of the gradient in the reference's names, extra):
        with `against` (a gradient tree in the reference's layout, on the
        host) extra holds the per-leaf sums of squares of the difference,
        with `keep` the gradient tree itself, on the host."""
        self.t += 1
        P = self.params
        B = tokens.shape[0]
        rb = min(self.row_block, B)
        blocks = [(tokens[i:i + rb], labels[i:i + rb]) for i in range(0, B, rb)]
        n_tok = jnp.float32(tokens.size)
        top = {k: v for k, v in P.items() if k != "layers"}
        acts = []
        for tok, _ in blocks:
            xs = [self._embed(top, tok)]
            for i, lp in enumerate(P["layers"]):
                xs.append(self._fwd(i, lp, xs[-1]))
            acts.append(xs)
        hp, unhead = jax.vjp(self.fam.head_params, top)
        loss = 0.0
        dhp = None
        dxs = []
        for (tok, lab), xs in zip(blocks, acts):
            val, (g_hp, dx) = self._head(hp, xs[-1], lab, n_tok)
            loss += float(val)
            dhp = g_hp if dhp is None else jax.tree.map(jnp.add, dhp, g_hp)
            dxs.append(dx)
            xs.pop()
        sumsq: dict = {}
        diffsq: dict = {}
        kept: dict = {"layers": [None] * len(P["layers"])}

        def add(name, tree, other=None):
            for k, v in self._sumsq(tree).items():
                sumsq[f"{name}{k}"] = sumsq.get(f"{name}{k}", 0.0) + float(v)
            if other is not None:
                diff = jax.tree.map(lambda a, b: a - jnp.asarray(b), tree, other)
                for k, v in self._sumsq(diff).items():
                    diffsq[f"{name}{k}"] = diffsq.get(f"{name}{k}", 0.0) + float(v)

        for li in reversed(range(len(P["layers"]))):
            lp = P["layers"][li]
            dlp = dx_aux = None
            if self._aux is not None:       # over the whole batch at once
                val, (dlp, dx_aux) = self._aux(
                    li, lp, jnp.concatenate([xs[-1] for xs in acts]))
                loss += float(val)
            for r, xs in enumerate(acts):
                g_lp, dxs[r] = self._vjp(li, lp, xs.pop(), dxs[r])
                if dx_aux is not None:
                    dxs[r] = dxs[r] + dx_aux[r * rb:(r + 1) * rb]
                dlp = g_lp if dlp is None else jax.tree.map(jnp.add, dlp, g_lp)
            add("layers.", dlp, against and against["layers"][li])
            if keep:
                kept["layers"][li] = jax.device_get(dlp)
            P["layers"][li] = {k: self._update(("layer", li, k), lp[k], dlp[k])
                               for k in lp}
        d_embed = jnp.zeros_like(P["embed"])
        for (tok, _), dx in zip(blocks, dxs):
            d_embed = self._scatter(d_embed, tok, dx)
        (g_top,) = unhead(dhp)              # the head's gradient on the leaves
        g_top["embed"] = d_embed + g_top["embed"]
        for k, g in g_top.items():
            add("", {k: g}, against and {k: against[k]})
            if keep:
                kept[k] = jax.device_get(g)
            P[k] = self._update(k, P[k], g)
        return loss, sumsq, (diffsq if against else kept if keep else None)
