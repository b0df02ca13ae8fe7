"""Serving cells: open-loop traffic on the wall clock into the program's
continuous-batching engine (``ServingEngine.step``).

Set-up builds the engine over the benchmark's seeded weights, compiles
its programs with one request, and then serves ``warmup_s`` seconds of the
cell's own traffic, so that the window opens on a busy arena.  The
window serves ``--seconds`` more of it: each request is submitted when it
falls due, whatever the engine is doing, and every token is stamped on
the host clock when ``step()`` hands it back.

    serve_output_tokens_per_s  tokens handed back in the window / window
    ttft_p95_ms   first token - due time, over every request due in the
                  window; one still without a token at the close counts
                  close - due
    itl_p95_ms    gaps between consecutive tokens of a request, both
                  inside the window

After the window the engine is freed, and the plain reference reads a
sample of the finished requests drawn from the seed, the longest among
them: its logits at every position that produced a served token
(bench/compare.py, ``logit_gap``).
"""
from __future__ import annotations

import sys
import time
from functools import partial

import jax
import numpy as np

from bench import compare, trace_reduce, weights
from bench.system import program_config
from bench.traffic.openloop import schedule


def p95(values) -> float:
    return float(np.percentile(np.asarray(values, np.float64), 95))


def build_engine(ctx):
    from repro.configs.base import ShapeConfig
    from repro.core import MeshSpec, compile_program
    from repro.runtime import train_loop as tl
    from repro.serving.engine import ServingEngine

    m, tr, fam = ctx.model, ctx.traffic, ctx.family
    cfg = program_config(fam, ctx.config)
    shape = ShapeConfig("bench", seq_len=tr["max_len"],
                        global_batch=tr["slots"], kind="decode")
    program = compile_program(cfg, shape,
                              MeshSpec(axis_sizes={"data": 1, "model": 1}))
    weights.check_layout(fam, m, jax.eval_shape(
        lambda k: tl.model_module(cfg).init(k, cfg), jax.random.PRNGKey(0)))
    wkey = weights.root_key(ctx.seed, "weights")
    params = jax.jit(partial(weights.make_params, fam, m))(wkey)
    eng = ServingEngine(cfg, program, params, n_slots=tr["slots"],
                        max_len=tr["max_len"], prefill_chunk=tr["prefill_chunk"],
                        kernel_backend=tr["kernel_backend"])
    return eng, wkey


class Recorder:
    """Benchmark-side stamps: when each request fell due and was
    submitted, and when each of its tokens came back."""

    def __init__(self):
        self.due, self.sent, self.prompt = {}, {}, {}
        self.tokens: dict = {}          # rid -> [(index, token, t)]
        self.step_s: list = []

    def serve(self, eng, arrivals, t_end, rid_prefix):
        """Run the engine until t_end, submitting `arrivals` (absolute due
        times) as they fall due."""
        from repro.serving.scheduler import Request
        reqs = [(due, Request(rid=f"{rid_prefix}{i}", prompt=a.prompt,
                              max_new_tokens=a.output_len), a.prompt)
                for i, (due, a) in enumerate(arrivals)]
        i = 0
        now = time.monotonic()
        while now < t_end:
            while i < len(reqs) and reqs[i][0] <= now:
                due, req, prompt = reqs[i]
                eng.submit(req)
                self.due[req.rid], self.sent[req.rid] = due, now
                self.prompt[req.rid] = prompt
                self.tokens[req.rid] = []
                i += 1
            if eng.sched.idle:
                nxt = reqs[i][0] if i < len(reqs) else t_end
                time.sleep(max(0.0, min(nxt, t_end) - time.monotonic()))
                now = time.monotonic()
                continue
            t0 = time.monotonic()
            with trace_reduce.span("engine.step"):
                evs = eng.step()
            now = time.monotonic()
            self.step_s.append((t0, now))
            for e in evs:
                self.tokens[e.rid].append((e.index, e.token, now))
        for due, req, prompt in reqs[i:]:
            if due < now:               # fell due, but the window closed first
                self.due[req.rid], self.sent[req.rid] = due, None
                self.prompt[req.rid], self.tokens[req.rid] = prompt, []
        return now


def _wrap(eng, calls):
    """Record the arguments of the engine's two compiled programs, and
    put a host span around each dispatch (trace runs only); the arguments
    are read back after the window, so no sync is added."""
    chunk, decode = eng._chunk, eng._decode

    def _chunk(params, cache, tokens, pos0, slot):
        calls["chunk"].append(pos0)
        with trace_reduce.span("engine.chunk"):
            return chunk(params, cache, tokens, pos0, slot)

    def _decode(params, cache, tok, pos, active):
        calls["decode"].append((pos, active))
        with trace_reduce.span("engine.decode"):
            return decode(params, cache, tok, pos, active)

    eng._chunk, eng._decode = _chunk, _decode


def run(ctx) -> dict:
    m, tr, seed, fam = ctx.model, ctx.traffic, ctx.seed, ctx.family
    V = m["vocab_size"]
    eng, wkey = build_engine(ctx)
    rec = Recorder()

    # compile: one request that takes a prompt chunk, a teacher-forced
    # tail and a decoded token; then the cell's own traffic
    from repro.serving.scheduler import Request
    eng.submit(Request(rid="compile-0", max_new_tokens=2, prompt=tuple(
        range(tr["prefill_chunk"] + 1))))
    while not eng.sched.idle:
        eng.step()
    t_warm = time.monotonic()
    warm = schedule(tr, seed, tr["warmup_s"], V, stream=1)
    rec.serve(eng, [(t_warm + a.due_s, a) for a in warm],
              t_warm + tr["warmup_s"], "warm-")
    w0 = time.monotonic()
    setup_s = w0 - ctx.t_start
    n_compiles = len(ctx.compiles)
    arrivals = schedule(tr, seed, ctx.seconds, V, stream=2)
    print(f"set-up {setup_s:.3f} s; warm-up served {len(warm)} requests; "
          f"window offers {len(arrivals)} requests", file=sys.stderr, flush=True)

    calls = {"chunk": [], "decode": []}
    tracer = None
    if ctx.trace:
        _wrap(eng, calls)
        tracer = trace_reduce.Tracer()
        tracer.start()
    n_steps0 = len(rec.step_s)
    with trace_reduce.span("window"):
        w1 = rec.serve(eng, [(w0 + a.due_s, a) for a in arrivals],
                       w0 + ctx.seconds, "req-")
    if tracer:
        tracer.stop()
    in_window = len(ctx.compiles) - n_compiles
    window_s = w1 - w0

    due_in = [r for r in rec.due if r.startswith("req-")]
    ttft, itl, n_tok = [], [], 0
    for rid in due_in:
        toks = rec.tokens[rid]
        ttft.append((toks[0][2] if toks else w1) - rec.due[rid])
    for rid, toks in rec.tokens.items():
        inside = [t for t in toks if w0 <= t[2] <= w1]
        n_tok += len(inside)
        itl += [b[2] - a[2] for a, b in zip(inside, inside[1:])
                if b[0] == a[0] + 1]
    late = [rec.sent[r] - rec.due[r] for r in due_in if rec.sent[r] is not None]
    print(f"window {window_s:.6f} s: {len(due_in)} requests due, "
          f"{sum(1 for r in due_in if rec.tokens[r])} with a first token, "
          f"{n_tok} tokens, {len(itl)} gaps; generator late by p50 "
          f"{np.median(late) * 1e3:.3f} ms, max {max(late) * 1e3:.3f} ms; "
          f"{in_window} compilations inside", file=sys.stderr, flush=True)
    peak = (jax.devices()[0].memory_stats() or {}).get("peak_bytes_in_use", 0)

    step_ms = [(b - a) * 1e3 for a, b in rec.step_s[n_steps0:]]
    record = {"kind": "serve", "model": m, "traffic": tr,
              "device_kind": jax.devices()[0].device_kind,
              "window_s": window_s, "engine_step_ms": step_ms}
    if tracer:
        record["chunk_flops"] = [
            fam.prefill_chunk_flops(m, int(np.asarray(p)[0]),
                                    tr["prefill_chunk"])
            for p in calls["chunk"]]
        record["decode_bytes"] = []
        for pos, active in calls["decode"]:
            pos, active = np.asarray(pos), np.asarray(active)
            record["decode_bytes"].append(fam.decode_step_bytes(
                m, int(pos[active].sum()), int(active.sum())))
        record["trace"] = tracer.reduce()
        tracer.cleanup()

    finished = {rid: st for rid, st in eng.sched.finished.items()
                if rid in rec.tokens and len(rec.tokens[rid]) == len(st.generated)}
    eng.cache, eng.params = None, None
    del eng

    sample = pick_sample(finished, rec, tr["check"], seed)
    t_ref = time.monotonic()
    ref = compare.ServeReference(fam, m, wkey)
    readings = compare.serve_readings(ref, sample)
    print(f"reference {time.monotonic() - t_ref:.3f} s: compared "
          f"{readings['tokens_compared']} served tokens of {len(sample)} "
          f"requests", file=sys.stderr, flush=True)
    checks = compare.judge(readings, ctx.limits)
    return {"correct": all(c["ok"] for c in checks.values()),
            "attempted": len(due_in), "failed": 0,
            "end_to_end": {"serve_output_tokens_per_s": n_tok / window_s,
                           "ttft_p95_ms": p95(ttft) * 1e3,
                           "itl_p95_ms": p95(itl) * 1e3,
                           "setup_s": setup_s},
            "record": record, "memory_peak_bytes": int(peak),
            "readings": readings, "sample": sample,
            "checks": {k: {"value": c["value"], "limit": c["limit"]}
                       for k, c in checks.items()}}


def pick_sample(finished: dict, rec: Recorder, check: dict, seed: int) -> list:
    """[(prompt, served tokens)]: the longest finished request, then
    others drawn from the seed, until there are ``min_requests`` of them
    and ``min_tokens`` served tokens, or ``max_requests``."""
    rids = sorted(finished, key=lambda r: (len(rec.prompt[r])
                                           + len(finished[r].generated), r))
    if not rids:
        raise RuntimeError("no request finished: nothing to compare")
    rng = np.random.default_rng(np.random.SeedSequence([seed, 99]))
    order = [rids[-1]] + list(rng.permutation(rids[:-1]))
    out, n = [], 0
    for rid in order:
        enough = n >= check["min_tokens"] and len(out) >= check["min_requests"]
        if enough or len(out) >= check["max_requests"]:
            break
        served = [tok for _, tok, _ in sorted(rec.tokens[rid])]
        out.append((rec.prompt[rid], served))
        n += len(served)
    return out
