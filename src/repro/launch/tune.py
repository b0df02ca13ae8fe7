"""Mapping-autotuner CLI: tune a config, fit the cost model, inspect.

    # tune one cell (cost model only; fast, no devices needed)
    PYTHONPATH=src python -m repro.launch.tune --arch qwen2-0.5b \
        --shape train_4k --mesh single

    # log evaluations while tuning, then fit the learned cost model
    PYTHONPATH=src python -m repro.launch.tune --arch qwen2-0.5b --log
    PYTHONPATH=src python -m repro.launch.tune --fit

    # guided search: the fitted model proposes top-K, the scorer only
    # prices those (exhaustive fallback on disagreement, logged)
    PYTHONPATH=src python -m repro.launch.tune --arch qwen2-0.5b --guided

    # corpus / model / cache inspection
    PYTHONPATH=src python -m repro.launch.tune --report
    PYTHONPATH=src python -m repro.launch.tune --show

    # refine the top-K candidates by on-host kernel timing
    PYTHONPATH=src python -m repro.launch.tune --arch qwen2-0.5b \
        --shape train_4k --measure --top-k 3

Winners persist in a JSON cache (``--cache``, default
``artifacts/tuner/cache.json``) keyed by op shape/phase/mesh (topology
included)/backend; logged evaluations append to JSONL under
``benchmarks/tuning_data/`` (``--data``); the fitted model serializes to
``--model`` (default ``artifacts/tuner/model.json``).  ``--emit``
additionally writes the per-op ProgramTuning JSON that
``compile_program(tuning=...)`` consumes.
"""
from __future__ import annotations

import argparse
import json
import os
import time

from repro.configs import SHAPES, get_config, get_reduced
from repro.core import compile_program, extract_ops
from repro.core.dataflow import MeshSpec
from repro.tuner import (DEFAULT_CACHE_PATH, DEFAULT_DATA_DIR,
                         DEFAULT_MODEL_PATH, FEATURE_VERSION, CostModel,
                         ExhaustiveSearch, GuidedSearch, TuningCache,
                         TuningDataset, describe_records, fit_records,
                         fit_report, load_records, tune_program)

MESHES = {
    "single": MeshSpec(axis_sizes={"data": 16, "model": 16},
                       batch_axes=("data",)),
    "multi": MeshSpec(axis_sizes={"pod": 2, "data": 16, "model": 16},
                      batch_axes=("pod", "data")),
    "host": MeshSpec(axis_sizes={"data": 1, "model": 1},
                     batch_axes=("data",)),
}


def make_measure(interpret: bool | None = None):
    """tile -> seconds on THIS host: times the real sr_matmul at a probe
    shape capped to the tile (full problem sizes are minutes in interpret
    mode; relative tile cost is what the refinement needs).  interpret=None
    compiles the kernel on an accelerator and interprets it on the CPU."""
    import jax
    import jax.numpy as jnp

    from repro.kernels import ops as kops

    def measure(tile, *, m=None, n=None, k=None, iters=3):
        tm, tn, tk = tile
        m = m or min(2 * tm, 512)
        n = n or min(2 * tn, 512)
        k = k or min(2 * tk, 1024)
        key = jax.random.PRNGKey(0)
        a = jax.random.normal(key, (m, k), jnp.bfloat16)
        b = jax.random.normal(jax.random.fold_in(key, 1), (k, n),
                              jnp.bfloat16)
        jax.block_until_ready(kops.sr_matmul(a, b, None, sr=False, block=tile,
                                             interpret=interpret))
        ts = []
        for _ in range(iters):
            t0 = time.monotonic()
            jax.block_until_ready(kops.sr_matmul(a, b, None, sr=False,
                                                 block=tile,
                                                 interpret=interpret))
            ts.append(time.monotonic() - t0)
        return min(ts)

    return measure


def _fit(args) -> int:
    records = load_records(args.data, feature_version=FEATURE_VERSION)
    print(describe_records(records))
    try:
        model = fit_records(records)
    except ValueError as e:
        print(f"fit failed: {e}")
        print("log a corpus first: python -m repro.launch.tune --log "
              "(or run python -m benchmarks.tuner_search)")
        return 1
    print(fit_report(model, records))
    path = model.save(args.model)
    print(f"model -> {path}")
    return 0


def _report(args) -> int:
    records = load_records(args.data, feature_version=FEATURE_VERSION)
    print(describe_records(records))
    if os.path.exists(args.model):
        model = CostModel.load(args.model)
        if records:
            print(fit_report(model, records))
        else:
            print(model.describe())
    else:
        print(f"no fitted model at {args.model} "
              f"(run python -m repro.launch.tune --fit)")
    return 0


def _make_search(args):
    """Build the search + optional dataset log the tuning run will use."""
    log = None
    if args.log:
        os.makedirs(args.data, exist_ok=True)
        log = TuningDataset(os.path.join(args.data, "tune_cli.jsonl"))
    if not args.guided:
        return ExhaustiveSearch(log=log), log
    if not os.path.exists(args.model):
        print(f"--guided: no fitted model at {args.model}; "
              f"falling back to exhaustive search "
              f"(fit one with python -m repro.launch.tune --fit)")
        return ExhaustiveSearch(log=log), log
    model = CostModel.load(args.model)
    return GuidedSearch(model, top_k=args.guided_k,
                        tolerance=args.tolerance, log=log), log


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", default="qwen2-0.5b")
    ap.add_argument("--shape", default="train_4k", choices=list(SHAPES))
    ap.add_argument("--mesh", default="single", choices=list(MESHES))
    ap.add_argument("--backend", default="pallas")
    ap.add_argument("--cache", default=DEFAULT_CACHE_PATH)
    ap.add_argument("--no-cache", action="store_true",
                    help="search fresh, do not read or write the cache")
    ap.add_argument("--measure", action="store_true",
                    help="refine top-K candidates by on-host kernel timing")
    ap.add_argument("--top-k", type=int, default=3)
    ap.add_argument("--reduced", action="store_true",
                    help="tune the reduced (smoke) config variant")
    ap.add_argument("--emit", default="",
                    help="write the ProgramTuning JSON here")
    ap.add_argument("--show", action="store_true",
                    help="print the cache contents and exit")
    ap.add_argument("--program", action="store_true",
                    help="also compile + print the tuned program table")
    ap.add_argument("--guided", action="store_true",
                    help="use the learned cost model to propose top-K "
                         "candidates; score only those (exhaustive fallback "
                         "on disagreement)")
    ap.add_argument("--guided-k", type=int, default=4,
                    help="how many model-proposed candidates to score")
    ap.add_argument("--tolerance", type=float, default=0.02,
                    help="guided certificate: max analytic-cost excess over "
                         "the grid floor before falling back")
    ap.add_argument("--fit", action="store_true",
                    help="fit the cost model from the logged corpus and exit")
    ap.add_argument("--report", action="store_true",
                    help="describe the corpus + model fit quality and exit")
    ap.add_argument("--log", action="store_true",
                    help="append every search evaluation to the corpus")
    ap.add_argument("--data", default=DEFAULT_DATA_DIR,
                    help="tuning-dataset JSONL directory")
    ap.add_argument("--model", default=DEFAULT_MODEL_PATH,
                    help="learned cost model JSON path")
    args = ap.parse_args()

    if args.fit:
        return _fit(args)
    if args.report:
        return _report(args)
    if args.show:
        if not os.path.exists(args.cache):
            print(f"no cache at {args.cache}")
            return 1
        print(TuningCache(args.cache).describe())
        return 0

    cfg = get_reduced(args.arch) if args.reduced else get_config(args.arch)
    shape = SHAPES[args.shape]
    mesh = MESHES[args.mesh]
    cache = None if args.no_cache else TuningCache(args.cache)
    measure = make_measure() if args.measure else None
    search, log = _make_search(args)

    t0 = time.monotonic()
    tuning = tune_program(
        extract_ops(cfg), mesh, global_batch=shape.global_batch,
        seq_len=shape.seq_len, kind=shape.kind, backend=args.backend,
        cache=cache, measure=measure, top_k=args.top_k, search=search)
    dt = time.monotonic() - t0
    print(tuning.describe())
    print(f"tuned {len(tuning.ops)} ops in {dt:.2f}s")

    if cache is not None:
        path = cache.save()
        print(f"cache: {len(cache)} entries -> {path} "
              f"(hits={cache.hits} misses={cache.misses})")
    if log is not None:
        print(f"logged {len(log)} evaluations -> {log.path}")
    if args.emit:
        d = os.path.dirname(args.emit)
        if d:
            os.makedirs(d, exist_ok=True)
        with open(args.emit, "w") as f:
            json.dump(tuning.to_dict(), f, indent=1)
        print(f"tuning -> {args.emit}")
    if args.program:
        prog = compile_program(cfg, shape, mesh, tuning=tuning)
        print(prog.describe())
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
