"""Training cells: the program's own step and loop, timed over a window.

Set-up builds one object, the jitted step from ``make_train_step`` with
its state (the benchmark's seeded weights), and drives it through
``run_with_recovery`` for the traffic file's ``check_steps`` first steps;
the first of them compiles.  The window then drives that same loop and
state on for as many whole steps as fill ``--seconds``, with about
``AHEAD_S`` seconds of steps dispatched ahead: the loop's read-back of a
step's metrics is handed those of the step that many before it, so that
the chip stays fed while the host stands still.  When the last step is
sent, the window waits for every step sent and then reads the clock:

    train_tokens_per_s = tokens of the window's steps
                         / (end of its last step - start of its first)

After the window the program's state is freed and the plain reference
of the model's family (bench/families/, bench/reference/train.py) repeats
the first steps from the same weights and batches.  Compared
(bench/compare.py): each step's loss, each leaf's first gradient as the
optimizer got it (its first moment after one step, over 1 - b1; its norm,
and its distance from the reference's), and each leaf's change over the
checked steps.
"""
from __future__ import annotations

import sys
import time
from collections import deque
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from bench import compare, roofline, trace_reduce, weights
from bench.system import program_config

AHEAD_S = 6.0   # seconds of steps the window keeps queued on the device


class _NoCheckpoint:
    """Checkpointer stand-in: the benchmark writes no checkpoint."""

    def save(self, *a, **k):
        pass

    def wait(self):
        pass

    def latest_step(self):
        return None


@partial(jax.jit, static_argnums=(2, 3, 4))
def batch_rows(key: jax.Array, step, B: int, T: int, V: int) -> dict:
    """{"tokens", "labels"} (B, T) int32 of one step.  The key is an
    argument, not a constant of the program, so that every seed runs the
    same compiled program and finds it in the compile cache."""
    u = jax.random.uniform(jax.random.fold_in(key, step), (B, T + 1))
    ids = jnp.floor(jnp.exp(u * jnp.log(jnp.float32(V)))).astype(jnp.int32) - 1
    ids = jnp.clip(ids, 0, V - 1)
    return {"tokens": ids[:, :-1], "labels": ids[:, 1:]}


def make_batches(m: dict, traffic: dict, key: jax.Array):
    """batches(step) -> {"tokens", "labels"} (B, T) int32 on the device.

    Each row is its own draw from a Zipf(1)-shaped unigram over the
    vocabulary (rank = floor(V ** u) - 1), so no two rows agree and the
    loss has something to learn."""
    B, T, V = traffic["batch"], traffic["seq_len"], m["vocab_size"]
    return lambda step: batch_rows(key, jnp.int32(step), B, T, V)


def build_program(ctx):
    """The system under test: config, program, jitted step, optimizer."""
    from repro.configs.base import ShapeConfig, TrainConfig
    from repro.core import MeshSpec, compile_program
    from repro.runtime import train_loop as tl

    m, tr, fam = ctx.model, ctx.traffic, ctx.family
    cfg = program_config(fam, ctx.config)
    shape = ShapeConfig("bench", seq_len=tr["seq_len"],
                        global_batch=tr["batch"], kind="train")
    o = tr["optimizer"]
    tc = TrainConfig(optimizer=o["name"], lr=o["lr"],
                     weight_decay=o["weight_decay"], precision=tr["precision"],
                     kernel_backend=tr["kernel_backend"], remat=tr["remat"],
                     seed=0)
    program = compile_program(cfg, shape,
                              MeshSpec(axis_sizes={"data": 1, "model": 1}),
                              precision=tc.precision, remat=tc.remat)
    step_fn, opt = tl.make_train_step(cfg, program, tc, None)
    weights.check_layout(fam, m, jax.eval_shape(
        lambda k: tl.model_module(cfg).init(k, cfg), jax.random.PRNGKey(0)))
    return jax.jit(step_fn, donate_argnums=(0,)), opt


def _leaf_norms(tree) -> dict:
    return {k: jnp.sqrt(jnp.sum(jnp.square(v.astype(jnp.float32))))
            for k, v in weights.flatten(tree).items()}


def run(ctx) -> dict:
    from repro.runtime.fault_tolerance import run_with_recovery

    m, tr, seed, fam = ctx.model, ctx.traffic, ctx.seed, ctx.family
    B, T = tr["batch"], tr["seq_len"]
    n_check = tr["check_steps"]
    b1 = tr["optimizer"]["b1"]
    jstep, opt = build_program(ctx)
    wkey = weights.root_key(seed, "weights")
    params = jax.jit(partial(weights.make_params, fam, m))(wkey)
    state = {"params": params, "opt": jax.jit(opt.init)(params),
             "step": jnp.zeros((), jnp.int32)}
    batches = make_batches(m, tr, weights.root_key(seed, "data"))
    key = weights.root_key(seed, "sample")
    ckpt = _NoCheckpoint()
    record = []

    def step(state, batch, k):
        with trace_reduce.span("train.step"):
            return jstep(state, batch, k)

    def batch(i):
        with trace_reduce.span("train.batch"):
            return batches(i)

    def loop(state, n_steps, on_metrics, step_fn=step):
        return run_with_recovery(step_fn=step_fn, state=state, batches=batch,
                                 ckpt=ckpt, meta={}, n_steps=n_steps,
                                 checkpoint_every=1 << 30, key=key,
                                 max_failures=0, on_metrics=on_metrics)

    def keep(step, metrics, dt):
        record.append((float(metrics["loss"]), dt))

    # the first steps: compile, warm up, and give the readings compared
    state = loop(state, 1, keep)
    grad_norms = {k: float(v) / (1 - b1) for k, v in
                  jax.jit(_leaf_norms)(state["opt"]["m"]).items()}
    first_moment = jax.device_get(state["opt"]["m"])     # bf16, on the host
    state = loop(state, n_check, keep)

    @jax.jit
    def change_norms(p, k):
        p0 = weights.make_params(fam, m, k)
        return _leaf_norms(jax.tree.map(
            lambda a, b: a.astype(jnp.float32) - b.astype(jnp.float32), p, p0))

    change = {k: float(v) for k, v in change_norms(state["params"], wkey).items()}
    losses = [loss for loss, _ in record]
    warm = [dt for _, dt in record[1:]] or [record[0][1]]
    step_s = sum(warm) / len(warm)
    n_window = max(2, round(ctx.seconds / step_s))
    depth = max(1, min(n_window, round(AHEAD_S / step_s)))
    pending = deque()

    def step_ahead(state, batch, k):
        state, metrics = step(state, batch, k)
        pending.append(metrics)
        return state, pending.popleft() if len(pending) > depth else {}

    setup_s = time.monotonic() - ctx.t_start
    print(f"set-up {setup_s:.3f} s; check steps {losses}; window of "
          f"{n_window} steps, {depth} ahead", file=sys.stderr, flush=True)

    n_compiles = len(ctx.compiles)
    tracer = trace_reduce.Tracer() if ctx.trace else None
    window = []
    if tracer:
        tracer.start()
    t0 = time.monotonic()
    with trace_reduce.span("window"):
        state = loop(state, n_check + n_window,
                     lambda s, mt, dt: window.append(dt), step_ahead)
        jax.block_until_ready((state, list(pending)))
    t1 = time.monotonic()
    if tracer:
        tracer.stop()
    in_window = len(ctx.compiles) - n_compiles
    tokens_per_s = n_window * B * T / (t1 - t0)
    waits = window[depth:] or window    # past the first steps, a step's time
    print(f"window {t1 - t0:.6f} s, {n_window} steps, {tokens_per_s:.3f} "
          f"tokens/s, step {min(waits):.4f}-{max(waits):.4f} s, "
          f"{in_window} compilations inside", file=sys.stderr, flush=True)
    peak = (jax.devices()[0].memory_stats() or {}).get("peak_bytes_in_use", 0)
    del state, params

    rec = {"kind": "train", "model": m, "traffic": tr,
           "device_kind": jax.devices()[0].device_kind,
           "tokens_per_s": tokens_per_s,
           "flops_per_token": fam.train_flops_per_token(m, T),
           "window_s": t1 - t0}
    if tracer:
        rec["trace"] = tracer.reduce()
        tracer.cleanup()
        print("kernel calls by bound: " + "; ".join(
            f"{k} {roofline.bound_by(rec, k)}" for k in roofline.kernels()),
            file=sys.stderr, flush=True)

    t_ref = time.monotonic()
    first_grad = fam.to_reference(m, jax.tree.map(
        lambda v: np.asarray(v, np.float32) / (1 - b1), first_moment), xp=np)
    ref = compare.reference_training(fam, m, tr, wkey, batches, n_check,
                                     first=first_grad)
    readings = compare.training_readings(
        {"losses": losses[:n_check], "grad_norms": grad_norms,
         "change_norms": change, "grad_diffs": ref["grad_diffs"]}, ref)
    print(f"reference {time.monotonic() - t_ref:.3f} s, losses "
          f"{ref['losses']}; readings {readings}", file=sys.stderr, flush=True)
    checks = compare.judge(readings, ctx.limits)
    return {"correct": all(c["ok"] for c in checks.values()),
            "attempted": n_check + n_window, "failed": 0,
            "end_to_end": {"train_tokens_per_s": tokens_per_s,
                           "setup_s": setup_s},
            "record": rec, "memory_peak_bytes": int(peak),
            "readings": readings, "losses": losses,
            "checks": {k: {"value": c["value"], "limit": c["limit"]}
                       for k, c in checks.items()}}
