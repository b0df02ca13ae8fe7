"""Serving throughput benchmark: mixed Poisson trace through the engine.

Reports aggregate tokens/s (generated and total) plus p50/p99 per-token
(inter-token) latency for a mixed trace — by default ≥32 concurrent
requests with prompt lengths 16–512 and chunked prefill interleaved into
the decode batch (the ISSUE-2 acceptance trace, on the reduced config).

``bench_pred`` adds the CI-gated DETERMINISTIC rows: per-step scheduling
comes from a real (reference-backend) engine run — arrival, preemption
and speculative accept/rollback decisions are bit-stable given the seed —
and the step clock comes from the tuner's fused-decode cost model, so
``pred_tok_s`` / ``pred_p99_ms`` / ``pred_accept_per_verify`` never move
with runner load.  The bursty overload row drives more concurrent
requests than arena slots through ``bursty_trace`` with a tight eviction
patience, so its p99 prices the preemption tail.

    PYTHONPATH=src python -m benchmarks.serve_throughput [--smoke]

``--smoke`` is the CI variant: tiny trace, seconds on CPU.
"""
from __future__ import annotations

import argparse
import time

from benchmarks.common import row
from repro.configs import get_reduced
from repro.core.program import extract_ops
from repro.serving import (build_engine, bursty_trace, latency_stats,
                           poisson_trace)
from repro.tuner import tune_fused_decode


def bench(arch: str, *, requests: int, prompt_lens: tuple, gen: int,
          slots: int, chunk: int, seed: int = 0, tag: str = "") -> str:
    cfg = get_reduced(arch)
    max_len = prompt_lens[1] + gen
    engine = build_engine(cfg, n_slots=slots, max_len=max_len,
                          prefill_chunk=chunk, seed=seed)
    trace = poisson_trace(requests, vocab_size=cfg.vocab_size,
                          prompt_lens=prompt_lens, gen_tokens=gen,
                          mean_interarrival_steps=1.0, seed=seed)
    t0 = time.monotonic()
    engine.run(trace)
    wall = time.monotonic() - t0
    stats = latency_stats(engine.events)
    n_prompt = sum(len(r.prompt) for r in trace)
    total = n_prompt + stats["tokens"]
    us_per_tok = wall / max(1, stats["tokens"]) * 1e6
    # the tag keeps smoke rows distinguishable from the full trace in the
    # merged CSV (same arch, incomparable workloads)
    return row(
        f"serve_throughput/{arch}{tag}", us_per_tok,
        f"gen_tok_s={stats['tokens']/wall:.1f} total_tok_s={total/wall:.1f} "
        f"p50_ms={stats['p50_ms']:.2f} p99_ms={stats['p99_ms']:.2f} "
        f"steps={engine.step_count} requests={requests} slots={slots} "
        f"chunk={chunk}")


def _p99_step_gap(events) -> float:
    """p99 inter-token gap in ENGINE STEPS (deterministic; wall-clock-free).

    Mirrors ``latency_stats`` but over ``TokenEvent.step`` — preemption or
    a starved decode batch shows up as a multi-step gap between one
    request's consecutive tokens.
    """
    by_rid: dict = {}
    for e in events:
        by_rid.setdefault(e.rid, []).append(e)
    gaps: list = []
    for evs in by_rid.values():
        evs = sorted(evs, key=lambda e: e.index)
        gaps += [b.step - a.step for a, b in zip(evs, evs[1:])]
    if not gaps:
        return 0.0
    gaps.sort()
    return float(gaps[min(len(gaps) - 1, int(0.99 * len(gaps)))])


def bench_pred(arch: str, *, requests: int, prompt_lens: tuple, gen: int,
               slots: int, chunk: int, spec_k: int = 3, seed: int = 0,
               tag: str = "") -> None:
    """The three gated serving rows (see module docstring): steady-state
    Poisson, bursty overload, and the self-draft speculative oracle."""
    cfg = get_reduced(arch)
    fd = tune_fused_decode(extract_ops(cfg), tokens=slots)
    step_s = fd["fused_s"] * cfg.n_layers   # modeled fused decode step
    max_len = prompt_lens[1] + gen
    mk = dict(n_slots=slots, max_len=max_len, prefill_chunk=chunk, seed=seed)

    # steady state: fused-decode engine over the smoke Poisson trace
    eng = build_engine(cfg, fused_decode=True, **mk)
    eng.run(poisson_trace(requests, vocab_size=cfg.vocab_size,
                          prompt_lens=prompt_lens, gen_tokens=gen,
                          mean_interarrival_steps=1.0, seed=seed))
    toks = len(eng.events)
    row(f"serve_pred/{arch}{tag}", step_s * 1e6,
        f"pred_tok_s={toks / eng.step_count / step_s:.1f} "
        f"pred_p99_ms={_p99_step_gap(eng.events) * step_s * 1e3:.4f} "
        f"pred_speedup={fd['pred_speedup']:.3f} "
        f"steps={eng.step_count} tokens={toks}")

    # overload: one burst of 2x the arena, tight eviction patience — the
    # p99 inter-token gap prices the preempt/re-prefill tail
    eng = build_engine(cfg, fused_decode=True, evict_patience=4, **mk)
    eng.run(bursty_trace(2 * slots, vocab_size=cfg.vocab_size,
                         prompt_lens=prompt_lens, gen_tokens=gen,
                         burst_size=2 * slots, burst_gap_steps=8, seed=seed))
    toks = len(eng.events)
    row(f"serve_pred/{arch}/bursty{tag}", step_s * 1e6,
        f"pred_p99_ms={_p99_step_gap(eng.events) * step_s * 1e3:.4f} "
        f"pred_tok_s={toks / eng.step_count / step_s:.1f} "
        f"steps={eng.step_count} tokens={toks}")

    # speculative: self-draft (same config + params) accepts every
    # proposal, so accepted-per-verify isolates the scheduler's commit
    # budgeting — any drop means the accept/rollback loop regressed
    eng = build_engine(cfg, speculative=spec_k, draft_cfg=cfg,
                       draft_seed=seed, **mk)
    eng.run(poisson_trace(requests, vocab_size=cfg.vocab_size,
                          prompt_lens=prompt_lens, gen_tokens=gen,
                          mean_interarrival_steps=1.0, seed=seed))
    c = eng.counters
    row(f"serve_pred/{arch}/spec{tag}", step_s * 1e6,
        f"pred_accept_per_verify={c['accepted'] / max(1, c['verifies']):.3f} "
        f"verifies={c['verifies']} accepted={c['accepted']} k={spec_k}")


def run(smoke: bool = True) -> None:
    """Harness entry (benchmarks.run): the smoke-sized trace — the full
    acceptance trace (32+ slots, prompts 16-512) is minutes on CPU, so the
    figure/table harness carries the smoke row only; run this module
    directly (no --smoke) for the full numbers."""
    if smoke:
        bench("qwen2-0.5b", requests=8, prompt_lens=(8, 48), gen=8,
              slots=4, chunk=8, tag="/smoke")
        bench_pred("qwen2-0.5b", requests=8, prompt_lens=(8, 48), gen=8,
                   slots=4, chunk=8, spec_k=3, tag="/smoke")
    else:
        bench("qwen2-0.5b", requests=48, prompt_lens=(16, 512), gen=32,
              slots=32, chunk=32)
        bench("jamba-v0.1-52b", requests=16, prompt_lens=(16, 128), gen=16,
              slots=8, chunk=16)
        bench_pred("qwen2-0.5b", requests=48, prompt_lens=(16, 512), gen=32,
                   slots=32, chunk=32, spec_k=4)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="CI-sized trace (seconds on CPU)")
    args = ap.parse_args()
    print("name,us_per_call,derived")
    run(smoke=args.smoke)


if __name__ == "__main__":
    main()
