"""Offered-load sweep of a serving cell, to find the highest rate the
engine sustains without a growing queue (the knee).

    python3 bench/sweep.py --workload qwen2-0.5b.serve --rates 6,9,12,15 \
        --seconds 20 --seed 1

One process, one engine: for each rate, in the order given, it serves
the cell's traffic at that rate for ``warmup_s`` and then ``--seconds``,
and prints what was offered and served, the waiting queue at the start
and end of the window, the median time to first token of the requests
due in each half of the window, and the tails.  A queue that grew by
more than ``--grow`` requests ends the sweep; otherwise the engine is
drained before the next rate.  The benchmark's own runs never run this;
the chosen rate is written into the traffic file as a number.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

BENCH = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(BENCH))

import numpy as np  # noqa: E402

from bench import run  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--grow", type=int, default=5)
    args = ap.parse_args(argv)
    bench = run._load_json(os.path.join(run.ROOT, "BENCHMARK.json"))
    sys.path.insert(0, os.path.join(run.ROOT, "src"))
    cell, config, traffic, limits = run.cell_files(bench, args.workload)
    run.enable_compile_cache()
    try:
        run.device_info(True, cell["chips"])
    except run.Refused as e:
        print(f"sweep: refused: {e}", file=sys.stderr)
        return 2
    from bench.kinds import serve
    from bench.traffic.openloop import schedule
    ctx = run.Ctx(cell, config, traffic, limits, args.seed, args.seconds, False,
                  time.monotonic())
    eng, _ = serve.build_engine(ctx)
    V = ctx.model["vocab_size"]
    for k, rate in enumerate(float(r) for r in args.rates.split(",")):
        tr = dict(traffic, rate_per_s=rate)
        rec = serve.Recorder()
        t = time.monotonic()
        warm = schedule(tr, args.seed, tr["warmup_s"], V, stream=10 + k)
        rec.serve(eng, [(t + a.due_s, a) for a in warm], t + tr["warmup_s"],
                  f"w{k}-")
        q0 = len(eng.sched.queue)
        w0 = time.monotonic()
        arr = schedule(tr, args.seed, args.seconds, V, stream=20 + k)
        w1 = rec.serve(eng, [(w0 + a.due_s, a) for a in arr], w0 + args.seconds,
                       f"r{k}-")
        q1 = len(eng.sched.queue)
        due = [r for r in rec.due if r.startswith(f"r{k}-")]
        ttft = [((rec.tokens[r][0][2] if rec.tokens[r] else w1) - rec.due[r])
                for r in due]
        n_tok = sum(1 for toks in rec.tokens.values() for x in toks
                    if w0 <= x[2] <= w1)
        steps = [(b - a) * 1e3 for a, b in rec.step_s if a >= w0]
        mid = w0 + args.seconds / 2
        half = [[(rec.tokens[r][0][2] - rec.due[r]) * 1e3 for r in due
                 if rec.tokens[r] and (rec.due[r] < mid) == first]
                for first in (True, False)]
        print(json.dumps({
            "rate_per_s": rate, "offered": len(due),
            "first_tokens": sum(1 for r in due if rec.tokens[r]),
            "queue_start": q0, "queue_end": q1,
            "active_end": len(eng.sched.active),
            "output_tokens_per_s": n_tok / (w1 - w0),
            "ttft_p50_ms": float(np.median(ttft)) * 1e3,
            "ttft_p95_ms": float(np.percentile(ttft, 95)) * 1e3,
            "ttft_p50_ms_halves": [float(np.median(h)) if h else None
                                   for h in half],
            "step_ms_p50": float(np.median(steps)) if steps else None}),
            flush=True)
        if q1 > q0 + args.grow:
            break
        while not eng.sched.idle:          # drain before the next rate
            eng.step()
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
