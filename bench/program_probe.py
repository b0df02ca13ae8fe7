"""A traced run of one cell that also reads the program's own spans,
request stamps and engine counters (bench/program_trace.py).

    python3 bench/program_probe.py --workload qwen2-0.5b.serve --seed 7

It runs the cell as ``bench/run.py --trace 1`` does, and prints as the
last line of stdout one JSON object with the result line's fields and:

    serve   queue_wait_ms_p95, prefill_ms_p95 (admission to first token),
            forced_row_share (% of the masked decode's active rows that
            fed a prompt tail), decode_occupancy (% of slots active in a
            decode step), engine_host_ms_p50 (``serve.step`` less its
            ``*.wait`` spans), the counters' change over the window
    train   loop_host_ms_p50 (``train_loop.step`` less ``train_loop.wait``),
            and each step of the window by ``step_num`` with the time
            under each ``train_loop.*`` span
    both    the traced run's end-to-end numbers, and the window's device
            idle time named by the innermost program span (``serve.*``,
            ``train_loop.*``, else ``window``)

The kinds do not hand the engine or the profile to the metric readers,
so this tool reaches them by wrapping the benchmark's ``Tracer`` and the
engine's constructor for the one run it makes.  The benchmark's own runs
never run it.
"""
from __future__ import annotations

import argparse
import glob
import json
import os
import statistics
import sys
import time

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

import numpy as np  # noqa: E402

from bench import program_trace, run, trace_reduce  # noqa: E402

PROGRAM = ("serve.", "train_loop.")


def _idle_by_program_span(pd) -> dict:
    (lo, hi), evs = program_trace.window_line(pd)
    host = trace_reduce.HostSpans(
        [(n, s, t) for n, s, t, _ in evs
         if n.startswith(PROGRAM) or n == trace_reduce.WINDOW])
    idle = []
    for plane in pd.planes:
        for line in plane.lines:
            if plane.name.startswith("/device:") \
                    and line.name == trace_reduce.OPS_LINE:
                iv = [(int(e.start_ns), int(e.start_ns + e.duration_ns))
                      for e in line.events]
                idle += trace_reduce.gaps(
                    trace_reduce.union(trace_reduce.clip(iv, lo, hi)), lo, hi)
    return trace_reduce.name_gaps(idle, host, top=len(idle))


def _train_steps(pd) -> list:
    (lo, hi), evs = program_trace.window_line(pd)
    out = []
    for n, s, t, stats in evs:
        if n != "train_loop.step" or s < lo or t > hi:
            continue
        row = {"step_num": stats.get("step_num"), "ms": (t - s) * 1e-6}
        for m, a, b, _ in evs:
            if m.startswith("train_loop.") and m != n and s <= a and b <= t:
                row[m] = row.get(m, 0.0) + (b - a) * 1e-6
        out.append(row)
    return out


class Probe:
    """Wraps the benchmark's Tracer and the engine's constructor for one
    run, and keeps what they see."""

    def __init__(self):
        self.engines, self.seen = [], {}

    def __enter__(self):
        from repro.serving.engine import ServingEngine
        T, probe = trace_reduce.Tracer, self
        self._saved = [(T, k, getattr(T, k)) for k in ("start", "stop", "reduce")]
        self._saved.append((ServingEngine, "__init__", ServingEngine.__init__))
        self._saved.append((run, "load_module", run.load_module))
        init, load = ServingEngine.__init__, run.load_module
        start, stop, reduce = T.start, T.stop, T.reduce

        def engine_init(eng, *a, **k):
            init(eng, *a, **k)
            probe.engines.append(eng)

        def tracer_start(tracer):
            start(tracer)
            if probe.engines:
                probe.seen["before"] = dict(probe.engines[-1].counters)

        def tracer_stop(tracer):
            probe.seen["close"] = time.monotonic()
            if probe.engines:
                probe.seen["after"] = dict(probe.engines[-1].counters)
            stop(tracer)

        def tracer_reduce(tracer):
            from jax.profiler import ProfileData
            (path,) = glob.glob(os.path.join(tracer.dir, "**", "*.xplane.pb"),
                                recursive=True)
            pd = ProfileData.from_file(path)
            probe.seen.update(
                serve_host=program_trace.step_host_ms(pd, "serve.step"),
                train_host=program_trace.step_host_ms(pd, "train_loop.step"),
                train_steps=_train_steps(pd),
                idle=_idle_by_program_span(pd))
            return reduce(tracer)

        def load_module(path, name):
            mod = load(path, name)
            if name.startswith("bench_kind_"):
                kind_run = mod.run

                def kept(ctx):
                    out = kind_run(ctx)
                    probe.seen["end_to_end"] = out["end_to_end"]
                    return out
                mod.run = kept
            return mod

        ServingEngine.__init__ = engine_init
        T.start, T.stop, T.reduce = tracer_start, tracer_stop, tracer_reduce
        run.load_module = load_module
        return self

    def __exit__(self, *exc):
        for owner, name, value in self._saved:
            setattr(owner, name, value)
        return False

    def numbers(self) -> dict:
        s = self.seen
        out = {"end_to_end_traced": s["end_to_end"],
               "idle_by_program_span": s["idle"]}
        if not self.engines:
            th = s["train_host"]
            out.update({"loop_host_ms_p50.train": statistics.median(th),
                        "train_steps": s["train_steps"]})
            return out
        eng = self.engines[-1]
        d = program_trace.counter_delta(s["before"], s["after"])
        sched = eng.sched
        states = [st for st in (list(sched.finished.values())
                                + list(sched.active.values()) + list(sched.queue))
                  if st.req.rid.startswith("req-")]
        wait, first = program_trace.stamps_ms(states, s["close"])
        out.update({
            "queue_wait_ms_p95.serve": float(np.percentile(wait, 95)),
            "prefill_ms_p95.serve": float(np.percentile(first, 95)),
            "forced_row_share.serve": 100.0 * d["forced_rows"] / d["decode_rows"],
            "decode_occupancy.serve":
                100.0 * d["decode_rows"] / (d["decode_steps"] * eng.n_slots),
            "engine_host_ms_p50.serve": statistics.median(s["serve_host"]),
            "counters": d, "requests": len(states), "admitted": len(first)})
        return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=None,
                    help="window (default: BENCHMARK.json's run_seconds)")
    args = ap.parse_args(argv)
    bench = run._load_json(os.path.join(ROOT, "BENCHMARK.json"))
    seconds = args.seconds or bench["run_seconds"]
    with Probe() as probe:
        res = run.run_cell(bench, args.workload, args.seed, seconds, True)
    out = {"workload": args.workload, "seed": args.seed,
           "correct": res["correct"], "device": res["device"],
           "per_layer": {k: v["value"] for k, v in res["metrics"].items()},
           "breakdown": res["breakdown"], **probe.numbers()}
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
