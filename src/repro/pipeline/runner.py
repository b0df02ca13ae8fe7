"""1F1B pipeline executor: per-stage program words fired on schedule.

Executes a :class:`~repro.pipeline.schedule.PipeSchedule` over per-stage
iBuffer programs (`core.program.compile_stage_programs`): every FF event
runs one stage's forward under that stage's :class:`PEContext` (stashing
its ``jax.vjp`` residuals), every BP event pops the vjp and propagates the
boundary cotangent to the left neighbour, and UP fires once per stage at
the 1F1B cooldown with the gradient accumulated in f32 across
microbatches.

Numerics are the point: the event loop reproduces the single-module
microbatched `train_loop` **bit for bit** on the reference backend —

  * microbatches come from the same strided `split_microbatches`,
  * per-microbatch stage cotangents are combined at the native grad dtype
    (disjoint stage slices make this exact; a tied embedding's two
    contributions meet in one commutative bf16 add, same as monolithic
    autodiff),
  * the combined per-microbatch gradient joins the f32 accumulator in
    microbatch order (BP(stage 0, m) completes in m order under both
    GPipe and 1F1B), and the loss sums in the same order on the last
    stage,

so composing per-stage vjps is primitive-for-primitive the monolithic
backward.  tests/test_pipeline.py pins 3-step loss and gradient
bit-equality (params match to the final bit except rare rounding ties
where XLA fuses the identical optimizer math differently across the two
programs).

Stage handoffs: with a ``("stage", "data")`` mesh the boundary tensors
ride a stage-stacked buffer shifted by ``jax.lax.ppermute`` under
``shard_map`` — the Memory Slices activation stream between neighbouring
modules.  Without a stage mesh (virtual stages on one host) the handoff
is the identity; either way the values are untouched.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs.base import ModelConfig, TrainConfig
from repro.core.phases import Phase
from repro.engine import PEContext
from repro.models import transformer as tfm
from repro.optim import make_optimizer
from repro.pipeline.partition import PipelinePlan
from repro.pipeline.schedule import PipeSchedule, make_schedule, validate
from repro.runtime.train_loop import split_microbatches


def _stage_mesh(mesh, num_stages: int):
    """The mesh iff it carries a usable stage axis."""
    if mesh is not None and "stage" in mesh.axis_names \
            and mesh.shape["stage"] == num_stages:
        return mesh
    return None


def _ppermute_shift(tree, mesh, direction: int):
    """Shift a stage-stacked pytree (leading dim = stage) one stage along
    the pipe via ppermute; slot 0 (or S-1) zero-fills, matching ppermute's
    unaddressed-target semantics."""
    S = mesh.shape["stage"]
    perm = [(i, i + direction) for i in range(S) if 0 <= i + direction < S]

    @functools.partial(jax.shard_map, mesh=mesh, in_specs=P("stage"),
                       out_specs=P("stage"))
    def shift(t):
        return jax.tree.map(
            lambda x: jax.lax.ppermute(x, "stage", perm), t)

    return shift(tree)


class _Handoff:
    """Per-tick boundary exchange.  Collects at most one send per stage,
    then delivers: through a ppermute shift of the stage-stacked buffer on
    a stage mesh, or directly (virtual stages).  Values are bit-identical
    either way."""

    def __init__(self, mesh, num_stages: int, direction: int):
        self.mesh = _stage_mesh(mesh, num_stages)
        self.S = num_stages
        self.direction = direction
        self.sends: list = []                 # (src_stage, microbatch, tree)

    def send(self, src: int, microbatch: int, tree) -> None:
        self.sends.append((src, microbatch, tree))

    def deliver(self, inbox: dict) -> None:
        """Move this tick's sends into inbox[(dst_stage, microbatch)]."""
        if not self.sends:
            return
        if self.mesh is None:
            for src, m, tree in self.sends:
                inbox[(src + self.direction, m)] = tree
        else:
            proto = self.sends[0][2]
            slots = [jax.tree.map(jnp.zeros_like, proto)
                     for _ in range(self.S)]
            for src, _, tree in self.sends:
                slots[src] = tree
            stacked = jax.tree.map(lambda *xs: jnp.stack(xs), *slots)
            shifted = _ppermute_shift(stacked, self.mesh, self.direction)
            for src, m, _ in self.sends:
                dst = src + self.direction
                inbox[(dst, m)] = jax.tree.map(lambda x: x[dst], shifted)
        self.sends = []


def make_pipeline_train_step(cfg: ModelConfig, programs: list,
                             pplan: PipelinePlan, train_cfg: TrainConfig,
                             mesh=None, *, schedule: Optional[str] = None,
                             stage_remat: Optional[tuple] = None):
    """Build (step_fn, opt) with the single-module `make_train_step`
    signature: step_fn(state, batch, key) -> (state, metrics), state being
    the ordinary full-model TrainState (checkpoints stay interchangeable).

    cfg/programs/pplan: the model, its per-stage iBuffer programs, and the
    stage map they were compiled from.  The number of microbatches is
    ``max(1, train_cfg.microbatch)``.  ZeRO-1 re-sharding is a
    single-module concern and is not applied here (each stage owns its
    dW outright — the "dedicated vault").

    stage_remat: per-stage remat settings (each a mode string or a
    per-group tuple — ``PipelinePlan.stage_remat`` from a budget-fitted
    partition); None falls back to the global ``train_cfg.remat``.
    Remat never changes values, only what autodiff saves, so parity with
    the monolithic path is unaffected.
    """
    if cfg.family == "audio":
        raise NotImplementedError("pipeline stages are decoder-only")
    S = pplan.num_stages
    assert len(programs) == S, (len(programs), S)
    policy = programs[0].policy
    opt = make_optimizer(train_cfg, policy)
    M = max(1, train_cfg.microbatch)
    sched: PipeSchedule = make_schedule(S, M, schedule)
    validate(sched)
    backend = train_cfg.kernel_backend
    bounds = pplan.group_bounds
    if stage_remat is not None and len(stage_remat) != S:
        raise ValueError(f"stage_remat has {len(stage_remat)} entries for "
                         f"{S} stages")
    stage_remat = (tuple(stage_remat) if stage_remat is not None
                   else (train_cfg.remat,) * S)
    shs = [PEContext(mesh, prog, backend=backend) for prog in programs]

    def loss_and_grads(params: dict, batch: dict, key: jax.Array):
        stage_ctx = [sh.with_key(jax.random.fold_in(key, 1))
                     if backend != "reference" else sh for sh in shs]

        def stage_fn(s):
            """The diff-able function one FF event of stage s runs.  `sp`
            is the stage's OWN param subtree (stage_subtree) — shaped like
            the model dict, so prologue/group_scan/head_loss run on it
            unchanged."""
            sh = stage_ctx[s]

            def body(sp, x, aux, mb):
                if s == 0:
                    x, positions = tfm.prologue(
                        cfg, sp, mb["tokens"], sh,
                        compute_dtype=policy.ff_dtype,
                        vision_embeds=mb.get("vision_embeds"))
                    aux = jnp.zeros((), jnp.float32)
                else:
                    positions = jnp.arange(x.shape[1], dtype=jnp.int32)
                x, aux, _ = tfm.group_scan(cfg, x, aux, sp["groups"], sh,
                                           positions, remat=stage_remat[s])
                if s == S - 1:
                    from repro.models.layers import apply_norm
                    x = apply_norm(cfg, x, sp.get("final_norm"))
                    return tfm.head_loss(cfg, sp, x, aux, mb["labels"], sh)
                return x, aux

            return body

        micro = split_microbatches(batch, M) if M > 1 else \
            jax.tree.map(lambda x: x[None], batch)
        mbs = [jax.tree.map(lambda x: x[m], micro) for m in range(M)]

        fwd_inbox: dict = {}         # (stage, mb) -> (x, aux)
        bwd_inbox: dict = {}         # (stage, mb) -> (dx, daux)
        pending: dict = {}           # (stage, mb) -> vjp_fn
        mb_grads: dict = {}          # mb -> {stage: subtree grads}
        # M > 1 starts from the zero tree and accumulates — exactly the
        # single-module scan's carry init; M == 1 assigns the lone
        # microbatch's gradient directly (the monolithic non-accumulating
        # branch does no zero-add either).
        acc = (jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params)
               if M > 1 else None)
        loss = jnp.zeros((), jnp.float32)

        by_tick: dict = {}
        for e in sched.events:
            by_tick.setdefault(e.t, []).append(e)

        for t in sorted(by_tick):
            fwd_out = _Handoff(mesh, S, +1)
            bwd_out = _Handoff(mesh, S, -1)
            for e in sorted(by_tick[t], key=lambda e: e.stage):
                s, m = e.stage, e.microbatch
                if e.phase == Phase.FF:
                    body = stage_fn(s)
                    if s == 0:
                        x_in = jnp.zeros((), policy.ff_dtype)   # unused
                        aux_in = jnp.zeros((), jnp.float32)
                    else:
                        x_in, aux_in = fwd_inbox.pop((s, m))
                    out, vjp = jax.vjp(
                        lambda p, x, a: body(p, x, a, mbs[m]),
                        stage_subtree(params, s), x_in, aux_in)
                    pending[(s, m)] = vjp
                    if s == S - 1:
                        loss = loss + out                       # mb order
                    else:
                        fwd_out.send(s, m, out)
                elif e.phase == Phase.BP:
                    if s == S - 1:
                        ct = jnp.ones((), jnp.float32)          # dLoss
                    else:
                        ct = bwd_inbox.pop((s, m))
                    dsp, dx, daux = pending.pop((s, m))(ct)
                    if s > 0:
                        bwd_out.send(s, m, (dx, daux))
                    mb_grads.setdefault(m, {})[s] = dsp
                    if s == 0:
                        # microbatch m fully backpropagated: assemble the
                        # full-model gradient from the disjoint stage
                        # subtrees and fold it into the f32 accumulator.
                        # BP(0, m) completes in m order, so this is the
                        # same accumulation order as the single-module
                        # gradient-accumulation scan.
                        gm = _assemble(params, mb_grads.pop(m))
                        acc = jax.tree.map(
                            lambda g: g.astype(jnp.float32), gm) \
                            if acc is None else jax.tree.map(
                                lambda a, g: a + g.astype(jnp.float32),
                                acc, gm)
                else:                                           # Phase.UP
                    pass   # fires once per stage; the fused update is below
            fwd_out.deliver(fwd_inbox)
            bwd_out.deliver(bwd_inbox)
        assert not pending and not mb_grads and not fwd_inbox and not bwd_inbox

        if M > 1:
            loss = loss / M
            grads = jax.tree.map(lambda g: g / M, acc)
        else:
            grads = acc
        return loss, grads

    def stage_subtree(params: dict, s: int) -> dict:
        """The params stage s OWNS (differentiates w.r.t.): its groups
        slice plus the edge leaves of its position.  A tied embedding
        appears on BOTH edge stages; its two cotangents meet in
        `_assemble`.  Keeping the vjp scoped to this subtree is what
        bounds the backward's live gradient memory to O(stage), not
        O(model) x stages."""
        g0, g1 = bounds[s]
        d = {"groups": jax.tree.map(lambda a: a[g0:g1], params["groups"])}
        if s == 0:
            d["embed"] = params["embed"]
            if "vlm_proj" in params:
                d["vlm_proj"] = params["vlm_proj"]
        if s == S - 1:
            for k in ("final_norm", "lm_head"):
                if k in params:
                    d[k] = params[k]
            if cfg.tie_embeddings:
                d.setdefault("embed", params["embed"])
        return d

    def _assemble(params: dict, parts: dict) -> dict:
        """Full-model gradient tree from the per-stage subtree grads of
        one microbatch: groups slices concatenate (disjoint, in stage
        order), edge leaves come from their owning stage — the tied
        embedding's two contributions add at the native grad dtype (one
        commutative add, exactly what monolithic autodiff emits)."""
        out: dict = {}
        for key in params:
            if key == "groups":
                out[key] = jax.tree.map(
                    lambda *xs: jnp.concatenate(xs, axis=0),
                    *[parts[s]["groups"] for s in range(S)])
            else:
                contribs = [parts[s][key] for s in sorted(parts)
                            if key in parts[s]]
                out[key] = (contribs[0] if len(contribs) == 1
                            else jax.tree.map(jnp.add, *contribs))
        return out

    def train_step(state: dict, batch: dict, key: jax.Array):
        params = state["params"]
        loss, grads = loss_and_grads(params, batch, key)
        # UP (the schedule's per-stage cooldown events): every stage's dW
        # is ready, run the optimizer exactly as the single-module step.
        upd_key = key if policy.update_rounding != "nearest" else None
        new_params, new_opt = opt.update(grads, state["opt"], params,
                                         state["step"], upd_key)
        gnorm = jnp.sqrt(sum(jnp.sum(jnp.square(g.astype(jnp.float32)))
                             for g in jax.tree.leaves(grads)))
        metrics = {"loss": loss, "grad_norm": gnorm}
        return ({"params": new_params, "opt": new_opt,
                 "step": state["step"] + 1}, metrics)

    train_step.loss_and_grads = loss_and_grads     # parity-test seam
    return train_step, opt
