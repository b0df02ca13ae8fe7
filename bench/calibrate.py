"""Readings that the limits of a cell's check are set from, in one process.

    python3 bench/calibrate.py --workload qwen2-0.5b.train \
        --seeds 11,12,13 --control-seeds 21,22,23 --seconds 5 \
        --out calibrate.jsonl

For each of ``--seeds`` it makes the cell's own run (the program on the
timed path, a short window, the reference after it) and writes the
numbers compared.  For each of ``--control-seeds`` it puts the reference,
computed with float8 (e4m3) matmuls, in the program's place and reads the
same numbers (the control); a training cell also reads the reference on
half of each batch, the mean taken over the rest (a planted fault).  Each
reading is judged against the cell's limits (bench/limits/<cell>.json) as
the harness judges a run: ``correct`` has to come out false for the
control.  One JSON object per line.  The benchmark's own runs never run
this.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

BENCH = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(BENCH))

from bench import run  # noqa: E402


def _ints(s: str) -> list:
    return [int(x) for x in s.split(",") if x]


def control_train(ctx) -> dict:
    from bench import compare, weights
    from bench.kinds.train import make_batches
    m, tr, seed, fam = ctx.model, ctx.traffic, ctx.seed, ctx.family
    wkey = weights.root_key(seed, "weights")
    batches = make_batches(m, tr, weights.root_key(seed, "data"))
    n = tr["check_steps"]
    ref = compare.reference_training(fam, m, tr, wkey, batches, n,
                                     keep_first=True)
    out = {}
    ctl = compare.reference_training(fam, m, tr, wkey, batches, n, quant="fp8",
                                     first=ref["first"])
    out["control_fp8"] = compare.training_readings(ctl, ref)
    half = compare.reference_training(fam, m, tr, wkey, batches, n,
                                      rows=tr["batch"] // 2, first=ref["first"])
    out["fault_half_batch"] = compare.training_readings(half, ref)
    out["reference_losses"] = ref["losses"]
    return out


def judged(readings: dict, limits: dict) -> dict:
    """The harness's verdict on `readings` under the cell's limits."""
    from bench import compare
    checks = compare.judge(readings, limits)
    return {"correct": all(c["ok"] for c in checks.values()),
            "checks": {k: [c["value"], c["limit"]] for k, c in checks.items()}}


def control_serve(ctx, kind) -> dict:
    from bench import compare, weights
    res = kind.run(ctx)
    wkey = weights.root_key(ctx.seed, "weights")
    ref = compare.ServeReference(ctx.family, ctx.model, wkey)
    low = compare.ServeReference(ctx.family, ctx.model, wkey, quant="fp8")
    return {"program": res["readings"],
            "control_fp8": compare.control_serve_readings(ref, low, res["sample"])}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=_ints, default=[])
    ap.add_argument("--control-seeds", type=_ints, default=[])
    ap.add_argument("--seconds", type=float, default=5.0)
    ap.add_argument("--out", required=True)
    args = ap.parse_args(argv)
    bench = run._load_json(os.path.join(run.ROOT, "BENCHMARK.json"))
    sys.path.insert(0, os.path.join(run.ROOT, "src"))
    cell, config, traffic, limits = run.cell_files(bench, args.workload)
    run.enable_compile_cache()
    try:
        run.device_info(True, cell["chips"])
    except run.Refused as e:
        print(f"calibrate: refused: {e}", file=sys.stderr)
        return 2
    kind = run.load_module(os.path.join(BENCH, "kinds", f"{traffic['kind']}.py"),
                           f"bench_kind_{traffic['kind']}")
    os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)

    def emit(obj):
        with open(args.out, "a") as f:
            f.write(json.dumps(obj) + "\n")
        print(json.dumps(obj), flush=True)

    for seed in args.seeds:
        ctx = run.Ctx(cell, config, traffic, limits, seed, args.seconds, False,
                      run.time.monotonic())
        res = kind.run(ctx)
        emit({"workload": args.workload, "seed": seed, "what": "program",
              "readings": res["readings"], "end_to_end": res["end_to_end"],
              "memory_peak_bytes": res["memory_peak_bytes"],
              "judged": judged(res["readings"], limits)})
    for seed in args.control_seeds:
        ctx = run.Ctx(cell, config, traffic, limits, seed, args.seconds, False,
                      run.time.monotonic())
        got = control_train(ctx) if traffic["kind"] == "train" \
            else control_serve(ctx, kind)
        verdicts = {k: judged(v, limits) for k, v in got.items()
                    if k.startswith(("control_", "fault_"))}
        emit({"workload": args.workload, "seed": seed, "what": "control",
              **got, "judged": verdicts})
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
