import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

# Multi-pod dry-run: .lower().compile() every (arch x shape x mesh) cell on
# the production mesh built from 512 placeholder host devices, and record
# memory_analysis / cost_analysis / static-HLO roofline terms.
#
#   PYTHONPATH=src python -m repro.launch.dryrun --arch all --mesh both
#
# Artifacts: artifacts/dryrun/<mesh>/<arch>__<shape>.json

import argparse      # noqa: E402
import json          # noqa: E402
import time          # noqa: E402
import traceback     # noqa: E402

import jax           # noqa: E402
import jax.numpy as jnp  # noqa: E402

from repro.analysis import roofline as RL                        # noqa: E402
from repro.analysis.hlo_stats import analyze                     # noqa: E402
from repro.configs import (ASSIGNED_ARCHS, SHAPES, get_config,   # noqa: E402
                           shape_applicable)
from repro.configs.base import TrainConfig                       # noqa: E402
from repro.core import compile_program                           # noqa: E402
from repro.launch.compile_cache import enable_compile_cache      # noqa: E402
from repro.launch.mesh import make_production_mesh, mesh_spec_for  # noqa: E402
from repro.runtime import train_loop as tl                       # noqa: E402
from repro.runtime.inputs import input_specs, key_spec           # noqa: E402


def _named(mesh, specs):
    from jax.sharding import NamedSharding, PartitionSpec as P
    return jax.tree.map(lambda s: NamedSharding(mesh, s), specs,
                        is_leaf=lambda x: isinstance(x, P))


def tune_cell(arch: str, shape_name: str, mesh, *,
              train_cfg: TrainConfig | None = None):
    """Run the mapping autotuner (cost model only) for one cell, under the
    SAME microbatch/backend the cell will compile with (GATHER comm scales
    with microbatch, so tuning under a different one skews the search)."""
    from repro.core import extract_ops
    from repro.tuner import tune_program

    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    tc = train_cfg or TrainConfig()
    return tune_program(extract_ops(cfg), mesh_spec_for(mesh),
                        global_batch=shape.global_batch,
                        seq_len=shape.seq_len, kind=shape.kind,
                        backend=tc.kernel_backend,
                        microbatch=max(1, tc.microbatch))


def lower_cell(arch: str, shape_name: str, mesh, *, precision: str,
               train_cfg: TrainConfig, overrides=None, tuning=None):
    """Build program + jit + lower for one cell.  Returns (lowered, program,
    extra) without compiling (so callers can reuse for perf iteration)."""
    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    program = compile_program(cfg, shape, mesh_spec_for(mesh),
                              precision=precision, overrides=overrides,
                              tuning=tuning, remat=train_cfg.remat,
                              microbatch=max(1, train_cfg.microbatch))
    batch_specs = _named(mesh, tl.batch_pspecs(cfg, shape, program))
    bshapes = input_specs(cfg, shape)

    if shape.kind == "train":
        step_fn, opt = tl.make_train_step(cfg, program, train_cfg, mesh)
        sshapes = tl.state_shapes(cfg, program, train_cfg)
        sspecs = _named(mesh, tl.state_shardings(cfg, program, train_cfg,
                                                 mesh, opt))
        jitted = jax.jit(step_fn,
                         in_shardings=(sspecs, batch_specs, None),
                         out_shardings=(sspecs, None),
                         donate_argnums=(0,))
        lowered = jitted.lower(sshapes, bshapes, key_spec())
    elif shape.kind == "prefill":
        step_fn = tl.make_prefill_step(cfg, program, mesh)
        pshapes = jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(
                x.shape, jnp.bfloat16 if x.dtype == jnp.float32 else x.dtype),
            tl.model_module(cfg).param_shapes(cfg))
        pspecs = _named(mesh, tl.param_pspecs(cfg, program))
        jitted = jax.jit(step_fn, in_shardings=(pspecs, batch_specs))
        lowered = jitted.lower(pshapes, bshapes)
    else:  # decode
        step_fn = tl.make_decode_step(cfg, program, mesh)
        pshapes = jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(
                x.shape, jnp.bfloat16 if x.dtype == jnp.float32 else x.dtype),
            tl.model_module(cfg).param_shapes(cfg))
        pspecs = _named(mesh, tl.param_pspecs(cfg, program))
        cshapes = tl.cache_shapes(cfg, shape.global_batch, shape.seq_len)
        cspecs = _named(mesh, tl.cache_pspecs(cfg, program,
                                              shape.global_batch, shape.seq_len))
        jitted = jax.jit(step_fn,
                         in_shardings=(pspecs, cspecs, batch_specs["tokens"],
                                       batch_specs["pos"]),
                         out_shardings=(None, cspecs),
                         donate_argnums=(1,))
        lowered = jitted.lower(pshapes, cshapes, bshapes["tokens"],
                               bshapes["pos"])
    return lowered, program


def pipeline_summary(arch: str, shape_name: str, num_stages: int,
                     microbatch: int, mesh_spec=None,
                     precision: str = "paper_sr_bf16") -> dict:
    """Stage table + 1F1B bubble + per-stage memory headroom for one cell.

    Pure host-side arithmetic — no lowering: the stage map is the
    partitioner's, the bubble is the schedule's, and the per-stage
    planned peak comes from the memory planner fitting each stage to the
    module budget (remat chosen per scan group).  A stage that busts the
    arena even fully rematted fails the cell with a message naming the
    first op past the budget — not a bare assert.
    """
    from repro.core.dataflow import HBM_BYTES, MeshSpec
    from repro.memory.arena import MemoryBudgetError
    from repro.pipeline import make_schedule, partition_model, summarize

    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    budget = 0.9 * HBM_BYTES
    ms = mesh_spec or MeshSpec(axis_sizes={"data": 1, "model": 1})
    try:
        pplan = partition_model(cfg, num_stages,
                                global_batch=shape.global_batch,
                                seq_len=shape.seq_len, kind=shape.kind,
                                hbm_budget=budget, mesh_spec=ms,
                                microbatch=max(1, microbatch),
                                precision=precision)
    except ValueError as e:
        return {"status": "skip", "reason": str(e)}
    except MemoryBudgetError as e:
        return {"status": "error", "error": f"stage memory plan: {e}"}
    headroom = [{"stage": s.index, "peak_bytes": s.peak_bytes,
                 "budget": budget, "headroom_bytes": budget - s.peak_bytes,
                 "remat": list(s.remat), "fits": s.fits}
                for s in pplan.stages]
    if not pplan.fits:
        worst = min(headroom, key=lambda h: h["headroom_bytes"])
        return {"status": "error",
                "error": (f"stage {worst['stage']} planned peak "
                          f"{worst['peak_bytes'] / 1e9:.2f}GB exceeds the "
                          f"{budget / 1e9:.2f}GB module budget even with "
                          f"full remat ({'; '.join(pplan.notes)})"),
                "plan": pplan.to_dict(), "stage_memory": headroom}
    nm = max(2 * num_stages, microbatch)     # enough microbatches to fill
    sched = make_schedule(num_stages, nm)
    return {"status": "ok", "plan": pplan.to_dict(),
            "table": pplan.table(), "schedule": summarize(sched),
            "timeline": sched.render(), "stage_memory": headroom}


def run_cell(arch: str, shape_name: str, mesh, mesh_name: str, *,
             precision: str, train_cfg: TrainConfig, overrides=None,
             tuned: bool = False) -> dict:
    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    ok, why = shape_applicable(cfg, shape)
    if not ok:
        return {"arch": arch, "shape": shape_name, "mesh": mesh_name,
                "status": "skip", "reason": why}
    tuning = (tune_cell(arch, shape_name, mesh, train_cfg=train_cfg)
              if tuned else None)
    t0 = time.monotonic()
    lowered, program = lower_cell(arch, shape_name, mesh, precision=precision,
                                  train_cfg=train_cfg, overrides=overrides,
                                  tuning=tuning)
    t_lower = time.monotonic() - t0
    t0 = time.monotonic()
    compiled = lowered.compile()
    t_compile = time.monotonic() - t0

    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis()
    text = compiled.as_text()
    stats = analyze(text)
    chips = mesh.devices.size
    mem_d = {k: int(getattr(mem, k)) for k in
             ("argument_size_in_bytes", "output_size_in_bytes",
              "temp_size_in_bytes", "alias_size_in_bytes",
              "generated_code_size_in_bytes")}
    roof = RL.build(cfg, shape, mesh_name, chips, stats=stats, cost=cost,
                    memory=mem_d, notes="; ".join(program.plan.notes))
    per_dev_bytes = (mem_d["argument_size_in_bytes"]
                     + mem_d["temp_size_in_bytes"])
    return {
        "arch": arch, "shape": shape_name, "mesh": mesh_name,
        "status": "ok", "chips": chips,
        "lower_s": round(t_lower, 1), "compile_s": round(t_compile, 1),
        "memory": mem_d,
        "per_device_bytes": per_dev_bytes,
        "cost_analysis": {k: float(v) for k, v in cost.items()
                          if k in ("flops", "bytes accessed",
                                   "transcendentals")},
        "hlo": {"flops": stats.flops,
                "collective_bytes": stats.collective_bytes,
                "collective_counts": stats.collective_counts,
                "trip_counts": stats.trip_counts[:16]},
        "roofline": roof.to_dict(),
        "plan": [program.plan.ops[k].describe()
                 for k in sorted(program.plan.ops)],
        "plan_notes": program.plan.notes,
        "precision": precision,
        "ibuffer_bytes": program.ibuffer_size_bytes(),
        "memory_plan": _memory_artifact(program),
    }


def _memory_artifact(program) -> dict | None:
    """The planner's view of the cell: plan table + ASCII timeline +
    per-phase peaks, next to XLA's measured memory_analysis."""
    mp = program.memory_plan()
    if mp is None:
        return None
    return {**mp.to_dict(), "table": mp.table(), "timeline": mp.render()}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="all")
    ap.add_argument("--shape", default="all")
    ap.add_argument("--mesh", default="both", choices=["single", "multi", "both"])
    ap.add_argument("--precision", default="paper_sr_bf16")
    ap.add_argument("--out", default="artifacts/dryrun")
    ap.add_argument("--remat", default="block")
    ap.add_argument("--microbatch", type=int, default=0)
    ap.add_argument("--skip-existing", action="store_true")
    ap.add_argument("--tuned", action="store_true",
                    help="run the mapping autotuner per cell; the plan "
                         "table then shows the chosen tilings")
    ap.add_argument("--pipeline-stages", type=int, default=0,
                    help="also render the inter-module stage table + 1F1B "
                         "bubble fraction for this many stages per cell")
    args = ap.parse_args()

    enable_compile_cache()

    archs = ASSIGNED_ARCHS if args.arch == "all" else args.arch.split(",")
    shapes = list(SHAPES) if args.shape == "all" else args.shape.split(",")
    meshes = {"single": [False], "multi": [True], "both": [False, True]}[args.mesh]
    train_cfg = TrainConfig(precision=args.precision, remat=args.remat,
                            microbatch=args.microbatch)

    results = []
    pipe_cache: dict = {}
    for multi in meshes:
        mesh = make_production_mesh(multi_pod=multi)
        mesh_name = "pod2x16x16" if multi else "pod16x16"
        outdir = os.path.join(args.out, mesh_name)
        os.makedirs(outdir, exist_ok=True)
        for arch in archs:
            for shape_name in shapes:
                path = os.path.join(outdir, f"{arch}__{shape_name}.json")
                if args.skip_existing and os.path.exists(path):
                    print(f"[cached] {mesh_name} {arch} {shape_name}")
                    continue
                try:
                    r = run_cell(arch, shape_name, mesh, mesh_name,
                                 precision=args.precision,
                                 train_cfg=train_cfg, tuned=args.tuned)
                except Exception as e:
                    r = {"arch": arch, "shape": shape_name, "mesh": mesh_name,
                         "status": "error", "error": f"{type(e).__name__}: {e}",
                         "traceback": traceback.format_exc()[-4000:]}
                if args.pipeline_stages > 1:
                    # compute (and print) once per (arch, shape, mesh):
                    # the per-stage memory fit depends on the mesh shards
                    ck = (arch, shape_name, mesh_name)
                    if ck not in pipe_cache:
                        p = pipeline_summary(arch, shape_name,
                                             args.pipeline_stages,
                                             max(1, args.microbatch),
                                             mesh_spec=mesh_spec_for(mesh),
                                             precision=args.precision)
                        pipe_cache[ck] = p
                        if p["status"] == "ok":
                            print(p["table"])
                            print(f"  1F1B bubble="
                                  f"{p['schedule']['bubble_fraction']:.1%} "
                                  f"(M={p['schedule']['num_microbatches']}) "
                                  f"imbalance={p['plan']['imbalance']:.3f}",
                                  flush=True)
                            for h in p["stage_memory"]:
                                print(f"  stage {h['stage']}: planned peak "
                                      f"{h['peak_bytes'] / 1e9:5.2f}GB / "
                                      f"budget {h['budget'] / 1e9:.1f}GB "
                                      f"(headroom "
                                      f"{h['headroom_bytes'] / 1e9:+.2f}GB, "
                                      f"remat "
                                      f"{sum(x == 'block' for x in h['remat'])}"
                                      f"/{len(h['remat'])} groups)",
                                      flush=True)
                        elif p["status"] == "error":
                            print(f"[ERR] pipeline {arch} {shape_name}: "
                                  f"{p['error']}", flush=True)
                    r["pipeline"] = pipe_cache[ck]
                with open(path, "w") as f:
                    json.dump(r, f, indent=1)
                if r["status"] == "ok":
                    roof = r["roofline"]
                    print(f"[ok] {mesh_name} {arch:<24} {shape_name:<12} "
                          f"compile={r['compile_s']:6.1f}s "
                          f"mem/dev={r['per_device_bytes']/1e9:6.2f}GB "
                          f"dom={roof['dominant']:<10} "
                          f"roofline={roof['roofline_fraction']:.1%}",
                          flush=True)
                elif r["status"] == "skip":
                    print(f"[skip] {mesh_name} {arch:<24} {shape_name:<12} "
                          f"{r['reason']}", flush=True)
                else:
                    print(f"[ERR] {mesh_name} {arch:<24} {shape_name:<12} "
                          f"{r['error'][:200]}", flush=True)
                results.append(r)
    n_ok = sum(1 for r in results if r["status"] == "ok")
    n_skip = sum(1 for r in results if r["status"] == "skip")
    n_err = sum(1 for r in results if r["status"] == "error")
    print(f"\nDry-run: {n_ok} ok, {n_skip} skip, {n_err} errors")
    return 1 if n_err else 0


if __name__ == "__main__":
    raise SystemExit(main())
