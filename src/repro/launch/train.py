"""End-to-end training driver.

    PYTHONPATH=src python -m repro.launch.train --arch qwen2-0.5b \
        --reduced --steps 200 --batch 8 --seq 128

Runs on whatever devices exist (CPU smoke -> TPU pod): builds the dataflow
program for the real mesh, jits the train step with the program's
shardings, and drives the fault-tolerant loop (checkpoint/restart,
per-step profiler spans, stateless-by-step data pipeline).
"""
from __future__ import annotations

import argparse
import os
import tempfile

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint import Checkpointer
from repro.configs import SHAPES, get_config, get_reduced
from repro.configs.base import ShapeConfig, TrainConfig
from repro.core import ModuleTopology, compile_program
from repro.core.dataflow import ICI_BW
from repro.data import PipelineConfig, SyntheticLM
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import (make_host_mesh, make_module_mesh,
                               mesh_spec_for, module_mesh_spec)
from repro.runtime import train_loop as tl
from repro.runtime.fault_tolerance import run_with_recovery


def main(argv=None, *, max_failures: int = 3, on_metrics=None, on_done=None):
    """CLI entry.  In-process callers may also pass max_failures (step
    failures tolerated, see ``run_with_recovery``), on_metrics(step,
    metrics, seconds) called after every step, and on_done(state) called
    with the final train state."""
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2-0.5b")
    ap.add_argument("--reduced", action="store_true",
                    help="use the smoke-scale config (CPU)")
    ap.add_argument("--shape", default=None, help="named shape (else custom)")
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--optimizer", default="adamw")
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--precision", default="paper_sr_bf16")
    ap.add_argument("--kernel-backend", default="reference",
                    choices=("reference", "pallas"),
                    help="engine matmul path: reference jnp or the Pallas "
                         "PE kernels (interpret mode on CPU)")
    ap.add_argument("--tuned", action="store_true",
                    help="run the mapping autotuner and execute the tuned "
                         "strategy/tiling winners (repro/tuner)")
    ap.add_argument("--microbatch", type=int, default=0)
    ap.add_argument("--modules", type=int, default=1,
                    help="memory modules in the cloud: plans collectives "
                         "with hop-class (intra/inter-module) bandwidths "
                         "and lays devices out one module row per mesh "
                         "axis; 1 = a single big module (flat costs)")
    ap.add_argument("--inter-bw-gbs", type=float, default=None,
                    help="inter-module link GB/s for --modules "
                         "(default: intra bandwidth / 8)")
    ap.add_argument("--pipeline-stages", type=int, default=1,
                    help="inter-module pipeline stages (layer groups on "
                         "memory-module stages, 1F1B microbatch schedule); "
                         "1 = single-module training")
    ap.add_argument("--pipeline-schedule", default="1f1b",
                    choices=("1f1b", "gpipe"))
    ap.add_argument("--remat", default="block")
    ap.add_argument("--auto-memory", action="store_true",
                    help="let the memory planner (repro/memory) choose "
                         "per-scan-group remat and the microbatch count "
                         "to fit the module HBM budget, and print the "
                         "memory plan (overrides --remat/--microbatch; "
                         "with --pipeline-stages, fits each stage)")
    ap.add_argument("--hbm-budget-gb", type=float, default=None,
                    help="per-module HBM budget for --auto-memory "
                         "(default: 90%% of the v5e 16GB)")
    ap.add_argument("--ckpt-dir",
                    default=os.path.join(tempfile.gettempdir(), "repro_train"))
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--resume", action="store_true")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    enable_compile_cache()

    cfg = get_reduced(args.arch) if args.reduced else get_config(args.arch)
    if args.shape:
        shape = SHAPES[args.shape]
    else:
        shape = ShapeConfig("custom", seq_len=args.seq,
                            global_batch=args.batch, kind="train")
    mesh = make_host_mesh()
    topology = None
    if args.modules > 1:
        n_dev = len(jax.devices())
        topology = ModuleTopology(
            n_modules=args.modules,
            pes_per_module=max(1, n_dev // args.modules),
            inter_bw=(args.inter_bw_gbs * 1e9 if args.inter_bw_gbs
                      else ICI_BW / 8))
        mmesh = make_module_mesh(topology)   # warns when devices can't
        if mmesh is not None:
            mesh = mmesh
            spec = mesh_spec_for(mesh, topology=topology)
        else:
            # plan the module cloud, execute on whatever devices exist
            spec = module_mesh_spec(topology)
        print(f"module cloud: {topology.n_modules} modules x "
              f"{topology.pes_per_module} PEs, inter-module link at "
              f"1/{topology.inter_penalty:.0f} intra bandwidth")
    else:
        spec = mesh_spec_for(mesh)
    tuning = None
    if args.tuned:
        from repro.core import extract_ops
        from repro.tuner import tune_program
        tuning = tune_program(extract_ops(cfg), spec,
                              global_batch=shape.global_batch,
                              seq_len=shape.seq_len, kind=shape.kind,
                              backend=args.kernel_backend,
                              microbatch=max(1, args.microbatch))
        print(tuning.describe())
    remat, microbatch = args.remat, args.microbatch
    budget = (args.hbm_budget_gb * 1e9 if args.hbm_budget_gb else None)
    if args.auto_memory and args.pipeline_stages <= 1:
        from repro.memory import choose_policy
        from repro.memory.policy import DEFAULT_BUDGET
        pol = choose_policy(cfg, shape, spec,
                            hbm_budget=budget or DEFAULT_BUDGET,
                            precision=args.precision, tuning=tuning)
        print(pol.describe())
        print(pol.plan.render())
        print(pol.plan.table())
        if not pol.fits:
            raise SystemExit(f"--auto-memory: no (remat, microbatch) point "
                             f"fits {pol.budget / 1e9:.2f}GB; best plan "
                             f"peaks at {pol.peak_bytes / 1e9:.2f}GB")
        remat, microbatch = pol.remat, pol.microbatch
    program = compile_program(cfg, shape, spec,
                              precision=args.precision, tuning=tuning,
                              microbatch=max(1, microbatch), remat=remat)
    print(program.describe())

    train_cfg = TrainConfig(optimizer=args.optimizer, lr=args.lr,
                            precision=args.precision, remat=remat,
                            kernel_backend=args.kernel_backend,
                            microbatch=microbatch, seed=args.seed,
                            steps=args.steps,
                            checkpoint_dir=args.ckpt_dir,
                            checkpoint_every=args.ckpt_every)

    use_mesh = mesh if mesh.devices.size > 1 else None
    if args.pipeline_stages > 1:
        from repro.core.program import compile_stage_programs
        from repro.launch.mesh import make_pipeline_mesh, pipeline_mesh_spec
        from repro.pipeline import (make_pipeline_train_step, make_schedule,
                                    partition_model)
        nm = max(1, args.microbatch)
        pmesh = make_pipeline_mesh(args.pipeline_stages)
        # per-stage programs must see the PER-STAGE data shard count (the
        # pipeline mesh divides the devices), not the undivided host mesh
        sspec = (mesh_spec_for(pmesh) if pmesh
                 else pipeline_mesh_spec(args.pipeline_stages))
        if args.auto_memory:
            from repro.memory.policy import DEFAULT_BUDGET
            pplan = partition_model(cfg, args.pipeline_stages,
                                    global_batch=shape.global_batch,
                                    seq_len=shape.seq_len,
                                    hbm_budget=budget or DEFAULT_BUDGET,
                                    mesh_spec=sspec, microbatch=nm,
                                    precision=args.precision,
                                    topology=topology)
            if not pplan.fits:
                for n in pplan.notes:
                    print(f"note: {n}")
                raise SystemExit("--auto-memory: a stage busts its module "
                                 "budget even with full remat; add stages "
                                 "or microbatches")
        else:
            pplan = partition_model(cfg, args.pipeline_stages,
                                    global_batch=shape.global_batch,
                                    seq_len=shape.seq_len,
                                    topology=topology)
        print(pplan.table())
        sched = make_schedule(args.pipeline_stages, nm,
                              args.pipeline_schedule)
        print(sched.render())
        stage_remat = pplan.stage_remat if args.auto_memory else None
        sprogs = compile_stage_programs(cfg, shape, sspec, pplan.layer_bounds,
                                        precision=args.precision, tuning=tuning,
                                        microbatch=nm,
                                        remat=(list(stage_remat)
                                               if stage_remat else remat))
        step_fn, opt = make_pipeline_train_step(
            cfg, sprogs, pplan, train_cfg, pmesh,
            schedule=args.pipeline_schedule, stage_remat=stage_remat)
        print(f"pipeline: {args.pipeline_stages} stages x {nm} microbatches, "
              f"{'ppermute mesh' if pmesh else 'virtual stages'}, "
              f"bubble={sched.bubble_fraction():.1%}")
    else:
        step_fn, opt = tl.make_train_step(cfg, program, train_cfg, use_mesh)
    state = tl.init_state(cfg, program, train_cfg, jax.random.PRNGKey(args.seed), opt)

    ckpt = Checkpointer(args.ckpt_dir)
    meta = {"arch": cfg.name, "shape": shape.name, "precision": args.precision}
    if args.resume and ckpt.latest_step() is not None:
        host, step, _ = ckpt.restore(jax.device_get(state))
        state = jax.tree.map(jnp.asarray, host)
        print(f"resumed from step {step}")

    if use_mesh is not None and args.pipeline_stages <= 1:
        # place the state (params by the program, moments ZeRO-1) and the
        # batch on the mesh, and keep that layout from step to step
        sspecs = tl.named(mesh, tl.state_shardings(cfg, program, train_cfg,
                                                   mesh, opt))
        bspecs = tl.named(mesh, tl.batch_pspecs(cfg, shape, program))
        jstep = jax.jit(step_fn, in_shardings=(sspecs, bspecs, None),
                        out_shardings=(sspecs, None), donate_argnums=(0,))
        state = jax.device_put(state, sspecs)
    else:
        jstep = jax.jit(step_fn, donate_argnums=(0,))

    pipe = SyntheticLM(cfg, shape, PipelineConfig(seed=args.seed))
    losses = []

    def log_metrics(step, metrics, dt):
        losses.append(float(metrics["loss"]))
        if step % args.log_every == 0:
            print(f"step {step:5d} loss={metrics['loss']:.4f} "
                  f"gnorm={metrics['grad_norm']:.3f} {dt*1e3:.0f}ms",
                  flush=True)
        if on_metrics is not None:
            on_metrics(step, metrics, dt)

    state = run_with_recovery(
        step_fn=jstep, state=state, batches=pipe.batch_at, ckpt=ckpt,
        meta=meta, n_steps=args.steps,
        checkpoint_every=args.ckpt_every, max_failures=max_failures,
        key=jax.random.key(args.seed), on_metrics=log_metrics)
    print(f"done: {args.steps} steps; loss {losses[0]:.4f} -> "
          f"{np.mean(losses[-10:]):.4f}")
    if on_done is not None:
        on_done(state)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
