"""Fault tolerance: restart-exact recovery, elastic re-mesh, stragglers.

At 1000+ nodes the failure model is: (a) hard node loss -> restart from the
latest checkpoint, possibly on a smaller mesh; (b) stragglers -> detect via
step-time outliers, mitigate with synchronous-with-spares or by excluding
the slow host at the next restart boundary.

What is implemented and TESTED here (CPU container, scaled down honestly):

  * ``run_with_recovery`` — the driver loop: catches step failures,
    restores the latest checkpoint, optionally re-plans the dataflow
    program for a new mesh (elastic), and resumes bit-exactly (the data
    pipeline is stateless-by-step).
  * ``elastic_replan`` — recompile the dataflow program for a surviving
    mesh and re-place the host-state under the new shardings.  Because the
    planner (core/dataflow.py) is a pure function of (ops, mesh), the SAME
    model re-plans for any mesh shape — this is the homogeneous-substrate
    property of the paper doing fault-tolerance work.  The same property
    covers losing a whole MEMORY MODULE: ``surviving_topology`` shrinks
    the :class:`~repro.core.dataflow.ModuleTopology` by the dead modules
    and ``elastic_replan(topology=...)`` re-plans with the survivor's
    hop-class costs while the checkpoint reshards onto the smaller mesh.
  * ``StepTimer`` — straggler detection by robust z-score on step times;
    in production the hook triggers spare promotion, here it records.
"""
from __future__ import annotations

import sys
import time
from dataclasses import dataclass, field, replace
from typing import Any, Callable, Optional

import jax
import numpy as np

from repro.checkpoint import Checkpointer, replace_on_mesh
from repro.core.dataflow import ModuleTopology


@dataclass
class StepTimer:
    window: int = 50
    threshold: float = 3.0          # robust z-score
    times: list = field(default_factory=list)
    stragglers: list = field(default_factory=list)

    def record(self, step: int, dt: float) -> bool:
        self.times.append(dt)
        hist = self.times[-self.window:]
        if len(hist) < 10:
            return False
        med = float(np.median(hist))
        mad = float(np.median(np.abs(np.array(hist) - med))) + 1e-9
        z = (dt - med) / (1.4826 * mad)
        if z > self.threshold:
            self.stragglers.append((step, dt, z))
            return True
        return False


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
    timer = StepTimer()
    failures = 0
    step = int(jax.device_get(state["step"]))
    while step < n_steps:
        try:
            if fail_injector is not None:
                fail_injector(step)
            t0 = time.monotonic()
            state, metrics = step_fn(state, batches(step),
                                     jax.random.fold_in(key, step))
            metrics = jax.device_get(metrics)
            dt = time.monotonic() - t0
            timer.record(step, dt)
            if on_metrics is not None:
                on_metrics(step, metrics, dt)
            step += 1
            if step % checkpoint_every == 0:
                ckpt.save(step, state, meta)
        except Exception as e:
            failures += 1
            print(f"run_with_recovery: step {step} failed "
                  f"({failures}/{max_failures} tolerated): "
                  f"{type(e).__name__}: {e}", file=sys.stderr, flush=True)
            if failures > max_failures:
                raise
            ckpt.wait()                    # a save may still be writing
            latest = ckpt.latest_step()
            if latest is None:
                raise
            # the template only names the tree's paths: a step that donated
            # `state` may have deleted its buffers, so never read them
            host_state, step, _ = ckpt.restore(jax.tree.map(lambda _: 0, state))
            state = jax.tree.map(jax.numpy.asarray, host_state)
            step = int(step)
    ckpt.save(n_steps, state, meta, blocking=True)
    return state
