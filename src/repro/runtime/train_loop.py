"""Train/serve step builders: where the dataflow program meets autodiff.

``make_train_step`` assembles the paper's three phases into one jitted fn:
  FF+BP — autodiff of the model loss at the policy's compute dtypes,
  UP    — optimizer with SR writeback of persistent state,
with microbatch gradient accumulation (f32) and per-block remat.  The
model forward runs under a ``PEContext`` carrying
``train_cfg.kernel_backend``: 'reference' (plain jnp) or 'pallas' (the
PE kernels executing the iBuffer program — see repro/engine/).

``state_shardings`` emits the full TrainState layout: parameter specs come
from the compiled dataflow program; optimizer moments additionally shard
over the data axis (ZeRO-1) when divisible.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs.base import ModelConfig, ShapeConfig, TrainConfig
from repro.core.phases import Phase
from repro.core.program import Program
from repro.engine import PEContext
from repro.models import encdec
from repro.models import transformer as tfm
from repro.optim import make_optimizer


def model_module(cfg: ModelConfig):
    return encdec if cfg.family == "audio" else tfm


# ---------------------------------------------------------------------------
# Shardings
# ---------------------------------------------------------------------------


def zero1_spec(spec: P, shape: tuple, mesh) -> P:
    """Add data-axis sharding to an optimizer-moment spec (ZeRO-1)."""
    if "data" not in mesh.axis_names:
        return spec
    dsize = mesh.shape["data"]
    parts = list(spec) + [None] * (len(shape) - len(spec))
    used = set()
    for p in parts:
        for a in (p if isinstance(p, tuple) else (p,)):
            if a:
                used.add(a)
    if "data" in used:
        return spec
    for i, (p, s) in enumerate(zip(parts, shape)):
        if p is None and s % dsize == 0 and s >= dsize:
            parts[i] = "data"
            return P(*parts)
    return spec


def param_pspecs(cfg: ModelConfig, program: Program):
    return model_module(cfg).param_pspecs(cfg, program)


def state_shardings(cfg: ModelConfig, program: Program, train_cfg: TrainConfig,
                    mesh, opt) -> dict:
    """Spec pytree matching {'params','opt','step'}."""
    pspecs = param_pspecs(cfg, program)
    shapes = model_module(cfg).param_shapes(cfg)
    if train_cfg.zero1:
        mspecs = jax.tree.map(
            lambda sp, sh: zero1_spec(sp, sh.shape, mesh), pspecs, shapes)
    else:
        mspecs = pspecs
    opt_specs = {k: mspecs for k in _opt_state_keys(opt)}
    return {"params": pspecs, "opt": opt_specs, "step": P()}


def _opt_state_keys(opt) -> tuple:
    probe = opt.init({"x": jnp.zeros((1,))})
    return tuple(probe.keys())


def batch_pspecs(cfg: ModelConfig, shape: ShapeConfig, program: Program) -> dict:
    b = program.plan.batch_spec or None
    specs = {}
    if shape.kind == "decode":
        specs["tokens"] = P(b, None)
        specs["pos"] = P(b)
    else:
        specs["tokens"] = P(b, None)
        if shape.kind == "train":
            specs["labels"] = P(b, None)
    if cfg.frontend == "vision_stub":
        specs["vision_embeds"] = P(b, None, None)
    if cfg.frontend == "audio_stub":
        specs["audio_embeds"] = P(b, None, None)
    return specs


def named(mesh, tree_specs):
    return jax.tree.map(lambda s: NamedSharding(mesh, s), tree_specs,
                        is_leaf=lambda x: isinstance(x, P))


# ---------------------------------------------------------------------------
# Train step
# ---------------------------------------------------------------------------


def cast_params(params, dtype):
    """Persistent storage cast (UP writeback target dtype)."""
    return jax.tree.map(
        lambda p: p.astype(dtype) if p.dtype == jnp.float32 else p, params)


def split_microbatches(batch: dict, nm: int) -> dict:
    """Strided microbatch split: micro-batch m takes rows r with r % nm == m
    so every data shard contributes to every micro-batch.  Leaves become
    (nm, B/nm, ...).  Shared by the single-module gradient-accumulation
    scan and the pipeline runner (repro/pipeline/runner.py) so both paths
    feed bit-identical microbatches."""
    return jax.tree.map(
        lambda x: x.reshape(x.shape[0] // nm, nm, *x.shape[1:]).swapaxes(0, 1),
        batch)


def make_train_step(cfg: ModelConfig, program: Program,
                    train_cfg: TrainConfig, mesh=None):
    policy = program.policy
    opt = make_optimizer(train_cfg, policy)
    backend = train_cfg.kernel_backend
    sh = PEContext(mesh, program, backend=backend)
    mm = model_module(cfg)

    # ZeRO-1: constrain gradients to the optimizer-state sharding before the
    # update (a reduce-scatter over `data`), so every f32 optimizer
    # intermediate is data-sharded — without this the update math runs at
    # the param sharding (measured 33 GB/dev of f32 temps on deepseek-33b).
    zspecs = None
    if mesh is not None and train_cfg.zero1:
        pspecs = param_pspecs(cfg, program)
        shapes = mm.param_shapes(cfg)
        zspecs = jax.tree.map(
            lambda sp, s: NamedSharding(mesh, zero1_spec(sp, s.shape, mesh)),
            pspecs, shapes)

    def train_step(state: dict, batch: dict, key: jax.Array):
        # thread the step's SR-entropy key into the engine (UP-phase dW
        # writeback); the reference backend never consumes it, so the
        # fold is dead code there and the trace is unchanged.
        sh_step = sh.with_key(jax.random.fold_in(key, 1)) \
            if backend != "reference" else sh

        def loss(params, batch):
            return mm.loss_fn(cfg, params, batch, sh_step,
                              compute_dtype=policy.ff_dtype,
                              remat=train_cfg.remat)

        params = state["params"]
        nm = train_cfg.microbatch
        if nm and nm > 1:
            def one_micro(carry, mb):
                l, g = carry
                li, gi = jax.value_and_grad(loss)(params, mb)
                if zspecs is not None:
                    gi = jax.tree.map(jax.lax.with_sharding_constraint,
                                      gi, zspecs)
                gi = jax.tree.map(lambda a, b: a + b.astype(jnp.float32), g, gi)
                return (l + li, gi), None

            micro = split_microbatches(batch, nm)
            g0 = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params)
            if zspecs is not None:
                g0 = jax.tree.map(jax.lax.with_sharding_constraint, g0, zspecs)
            (l, grads), _ = jax.lax.scan(one_micro, (jnp.zeros(()), g0), micro)
            l, grads = l / nm, jax.tree.map(lambda g: g / nm, grads)
        else:
            l, grads = jax.value_and_grad(loss)(params, batch)
            if zspecs is not None:
                # reduce-scatter the LOW-PRECISION grads to the ZeRO-1
                # layout first (half the sync bytes), THEN upcast: the f32
                # grad tree only ever exists data-sharded.
                grads = jax.tree.map(jax.lax.with_sharding_constraint,
                                     grads, zspecs)
            grads = jax.tree.map(lambda g: g.astype(jnp.float32), grads)

        upd_key = key if policy.update_rounding != "nearest" else None
        # ZeRO-1 proper: params enter the update data-SLICED (free — they
        # are data-replicated), so every f32 update temp is 1/dp-sized; the
        # out_shardings then all-gather the 2-byte new params.
        opt_params = params
        if zspecs is not None:
            opt_params = jax.tree.map(jax.lax.with_sharding_constraint,
                                      params, zspecs)
        new_params, new_opt = opt.update(grads, state["opt"], opt_params,
                                         state["step"], upd_key)
        gnorm = jnp.sqrt(sum(jnp.sum(jnp.square(g.astype(jnp.float32)))
                             for g in jax.tree.leaves(grads)))
        metrics = {"loss": l, "grad_norm": gnorm}
        return ({"params": new_params, "opt": new_opt,
                 "step": state["step"] + 1}, metrics)

    return train_step, opt


def init_state(cfg: ModelConfig, program: Program, train_cfg: TrainConfig,
               key: jax.Array, opt=None) -> dict:
    policy = program.policy
    if opt is None:
        opt = make_optimizer(train_cfg, policy)
    params = cast_params(model_module(cfg).init(key, cfg), policy.param_dtype)
    return {"params": params, "opt": opt.init(params),
            "step": jnp.zeros((), jnp.int32)}


def state_shapes(cfg: ModelConfig, program: Program, train_cfg: TrainConfig) -> dict:
    """ShapeDtypeStruct pytree of the full TrainState (dry-run stand-in)."""
    opt = make_optimizer(train_cfg, program.policy)
    return jax.eval_shape(
        partial(init_state, cfg, program, train_cfg, opt=opt),
        jax.ShapeDtypeStruct((2,), jnp.uint32))


# ---------------------------------------------------------------------------
# Serve steps
# ---------------------------------------------------------------------------


def make_prefill_step(cfg: ModelConfig, program: Program, mesh=None,
                      kernel_backend: str = "reference"):
    policy = program.policy
    sh = PEContext(mesh, program,
                   backend=kernel_backend).with_phase(Phase.PREFILL)

    def prefill(params, batch):
        if cfg.family == "audio":
            enc_out = encdec.encode(cfg, params, batch["audio_embeds"], sh,
                                    compute_dtype=policy.ff_dtype)
            hidden, _ = encdec.forward(cfg, params, batch["tokens"],
                                       batch["audio_embeds"], sh,
                                       compute_dtype=policy.ff_dtype,
                                       return_hidden=True)
            logits = sh.dot("embed", hidden[:, -1:],
                            params["embed"]["table"],
                            transpose_w=True).astype(jnp.float32)
            cross = encdec.precompute_cross_kv(cfg, params, enc_out, sh)
            return logits, cross
        hidden, aux, caches = tfm.forward(
            cfg, params, batch["tokens"], sh, compute_dtype=policy.ff_dtype,
            vision_embeds=batch.get("vision_embeds"), return_cache=True,
            return_hidden=True)
        from repro.models.layers import lm_logits
        logits = lm_logits(hidden[:, -1:], cfg, params, sh)
        return logits, caches

    return prefill


def make_decode_step(cfg: ModelConfig, program: Program, mesh=None,
                     kernel_backend: str = "reference"):
    """One-token serve step under the DECODE program word (bandwidth-bound
    matvec, no SR entropy — see engine/dispatch.py)."""
    policy = program.policy
    sh = PEContext(mesh, program,
                   backend=kernel_backend).with_phase(Phase.DECODE)

    def decode(params, cache, tokens, pos, active=None):
        if cfg.family == "audio":
            return encdec.decode_step(cfg, params, tokens, cache, pos, sh,
                                      compute_dtype=policy.ff_dtype)
        return tfm.decode_step(cfg, params, tokens, cache, pos, sh,
                               compute_dtype=policy.ff_dtype, active=active)

    return decode


def make_fused_decode_step(cfg: ModelConfig, program: Program, mesh=None,
                           kernel_backend: str = "reference"):
    """One-token serve step with each layer fused into ONE dispatch.

    The program's ``decode_fused`` words (compile_program(fused_decode=
    True)) lower whole units onto the kernels/decode_fused.py megakernel
    on the pallas backend; on reference the fused composition replays the
    per-op primitive sequence bit-identically (the parity oracle)."""
    if cfg.family == "audio":
        raise NotImplementedError(
            "fused decode targets decoder-only families")
    policy = program.policy
    sh = PEContext(mesh, program,
                   backend=kernel_backend).with_phase(Phase.DECODE)

    def decode(params, cache, tokens, pos, active=None):
        return tfm.decode_step(cfg, params, tokens, cache, pos, sh,
                               compute_dtype=policy.ff_dtype, fused=True,
                               active=active)

    return decode


def make_draft_step(cfg: ModelConfig, program: Program, mesh=None,
                    kernel_backend: str = "reference"):
    """The DRAFT program word: the speculative draft model's width-1 step.

    Identical flow to DECODE (bandwidth matvec) but issued under
    Phase.DRAFT so a speculative program can map the draft model's ops
    independently of the big model's decode words."""
    if cfg.family == "audio":
        raise NotImplementedError(
            "speculative decoding targets decoder-only families")
    policy = program.policy
    sh = PEContext(mesh, program,
                   backend=kernel_backend).with_phase(Phase.DRAFT)

    def draft(params, cache, tokens, pos, active=None):
        return tfm.decode_step(cfg, params, tokens, cache, pos, sh,
                               compute_dtype=policy.ff_dtype, active=active)

    return draft


def make_chunk_step(cfg: ModelConfig, program: Program, mesh=None,
                    kernel_backend: str = "reference"):
    """Multi-token cache step under the PREFILL program word.

    Processes a (B, T) prompt chunk against the caches — the serving
    engine's chunked prefill.  Bit-identical to T sequential decode steps
    on the reference backend (tests/test_serving.py)."""
    if cfg.family == "audio":
        raise NotImplementedError(
            "chunked prefill targets decoder-only families; the audio "
            "encoder prefills via encdec.precompute_cross_kv")
    policy = program.policy
    sh = PEContext(mesh, program,
                   backend=kernel_backend).with_phase(Phase.PREFILL)

    def chunk(params, cache, tokens, pos0):
        return tfm.chunk_step(cfg, params, tokens, cache, pos0, sh,
                              compute_dtype=policy.ff_dtype)

    return chunk


def cache_shapes(cfg: ModelConfig, batch: int, max_len: int):
    if cfg.family == "audio":
        return jax.eval_shape(
            lambda: encdec.init_cache(cfg, {}, batch, max_len))
    return jax.eval_shape(lambda: tfm.init_cache(cfg, batch, max_len))


def cache_pspecs(cfg: ModelConfig, program: Program, batch: int,
                 max_len: int):
    """Cache layout: batch dim sharded; one feature-ish dim over `model`
    when divisible (kv-heads first, then hidden dims)."""
    shapes = cache_shapes(cfg, batch, max_len)
    tp = program.mesh_spec.tp
    b = program.plan.batch_spec or None

    def spec_for(path, leaf):
        sh = leaf.shape
        # leading stacking dim (layer groups), then batch
        parts: list = [None] * len(sh)
        if len(sh) >= 2:
            parts[1] = b
        # one more dim over `model`: heads/hidden dims (NEVER the cache
        # sequence dim 2 — a seq-sharded ring buffer makes every decode
        # insert an involuntary reshard)
        if jnp.issubdtype(leaf.dtype, jnp.floating):
            for i in range(3, len(sh)):
                if sh[i] % tp == 0 and sh[i] >= tp:
                    parts[i] = "model"
                    break
        return P(*parts)

    return jax.tree_util.tree_map_with_path(spec_for, shapes)
