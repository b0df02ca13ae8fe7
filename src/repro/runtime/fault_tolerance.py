"""Fault tolerance: restart-exact recovery and elastic re-mesh.

At 1000+ nodes the failure model is hard node loss -> restart from the
latest checkpoint, possibly on a smaller mesh.

What is implemented and TESTED here (CPU container, scaled down honestly):

  * ``run_with_recovery`` — the driver loop: catches step failures,
    restores the latest checkpoint, optionally re-plans the dataflow
    program for a new mesh (elastic), and resumes bit-exactly (the data
    pipeline is stateless-by-step).  Each iteration is a profiler step
    span ``train_loop.step`` (``step_num``), so a trace pins a slow step
    to its number and to the phase it spent the time in.
  * ``elastic_replan`` — recompile the dataflow program for a surviving
    mesh and re-place the host-state under the new shardings.  Because the
    planner (core/dataflow.py) is a pure function of (ops, mesh), the SAME
    model re-plans for any mesh shape — this is the homogeneous-substrate
    property of the paper doing fault-tolerance work.  The same property
    covers losing a whole MEMORY MODULE: ``surviving_topology`` shrinks
    the :class:`~repro.core.dataflow.ModuleTopology` by the dead modules
    and ``elastic_replan(topology=...)`` re-plans with the survivor's
    hop-class costs while the checkpoint reshards onto the smaller mesh.
"""
from __future__ import annotations

import sys
import time
from dataclasses import replace
from typing import Any, Callable, Optional

import jax
from jax.profiler import StepTraceAnnotation, TraceAnnotation

from repro.checkpoint import Checkpointer, replace_on_mesh
from repro.core.dataflow import ModuleTopology


def surviving_topology(topology: ModuleTopology,
                       lost: int = 1) -> ModuleTopology:
    """The module cloud after `lost` whole modules die.

    Modules are homogeneous (the paper's premise), so WHICH module died
    does not matter — only how many survive.  Link bandwidths and
    PEs/module carry over unchanged; raises when no module survives.
    """
    if lost < 0:
        raise ValueError(f"lost must be >= 0, got {lost}")
    if lost >= topology.n_modules:
        raise ValueError(f"losing {lost} of {topology.n_modules} modules "
                         f"leaves nothing to replan onto")
    return replace(topology, n_modules=topology.n_modules - lost)


def elastic_replan(cfg, shape, new_mesh, host_state, train_cfg,
                   precision: str,
                   topology: Optional[ModuleTopology] = None):
    """Re-plan + re-place state for a changed mesh (elastic scaling).

    topology: the SURVIVING module topology (see ``surviving_topology``)
    — the replanned program prices its collectives against the smaller
    module cloud's hop classes.
    """
    from repro.core import compile_program
    from repro.launch.mesh import mesh_spec_for
    from repro.runtime import train_loop as tl

    program = compile_program(cfg, shape,
                              mesh_spec_for(new_mesh, topology=topology),
                              precision=precision)
    opt = None
    step_fn, opt = tl.make_train_step(cfg, program, train_cfg, new_mesh)
    specs = tl.state_shardings(cfg, program, train_cfg, new_mesh, opt)
    state = replace_on_mesh(host_state, specs, new_mesh)
    return program, step_fn, state, specs


def run_with_recovery(*, step_fn: Callable, state: Any, batches: Callable,
                      ckpt: Checkpointer, meta: dict, n_steps: int,
                      checkpoint_every: int = 50,
                      key: Optional[jax.Array] = None,
                      max_failures: int = 3,
                      on_metrics: Optional[Callable] = None,
                      fail_injector: Optional[Callable] = None) -> Any:
    """The production driver loop, minus the cluster scheduler.

    batches: step -> batch (pure).  fail_injector: step -> None or raise
    (test hook).  Restores from the latest checkpoint on failure and
    replays from the stored step — restart-exact because batches(step) is
    stateless.  Every caught failure is printed to stderr with its step;
    max_failures=0 re-raises the first one.
    """
    key = key if key is not None else jax.random.PRNGKey(0)
    failures = 0
    step = int(jax.device_get(state["step"]))
    while step < n_steps:
        try:
            with StepTraceAnnotation("train_loop.step", step_num=step):
                if fail_injector is not None:
                    fail_injector(step)
                t0 = time.monotonic()
                with TraceAnnotation("train_loop.batch"):
                    batch = batches(step)
                    step_key = jax.random.fold_in(key, step)
                with TraceAnnotation("train_loop.dispatch"):
                    state, metrics = step_fn(state, batch, step_key)
                with TraceAnnotation("train_loop.wait"):
                    metrics = jax.device_get(metrics)
                dt = time.monotonic() - t0
                if on_metrics is not None:
                    on_metrics(step, metrics, dt)
                step += 1
                if step % checkpoint_every == 0:
                    with TraceAnnotation("train_loop.checkpoint"):
                        ckpt.save(step, state, meta)
        except Exception as e:
            failures += 1
            print(f"run_with_recovery: step {step} failed "
                  f"({failures}/{max_failures} tolerated): "
                  f"{type(e).__name__}: {e}", file=sys.stderr, flush=True)
            if failures > max_failures:
                raise
            with TraceAnnotation("train_loop.restore"):
                ckpt.wait()                # a save may still be writing
                latest = ckpt.latest_step()
                if latest is None:
                    raise
                # the template only names the tree's paths: a step that
                # donated `state` may have deleted its buffers, so never
                # read them
                host_state, step, _ = ckpt.restore(
                    jax.tree.map(lambda _: 0, state))
                state = jax.tree.map(jax.numpy.asarray, host_state)
                step = int(step)
    ckpt.save(n_steps, state, meta, blocking=True)
    return state
