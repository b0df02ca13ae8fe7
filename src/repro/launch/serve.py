"""Serving CLI: the continuous-batching engine (default), a replica
fleet (``--replicas N``), or the legacy single-shot fixed-batch loop
(``--single-shot`` — the parity oracle, and the only path for the audio
family).

    # continuous batching over a synthetic Poisson trace
    PYTHONPATH=src python -m repro.launch.serve --arch qwen2-0.5b --reduced \
        --requests 32 --prompt-lens 16,512 --gen 32 --slots 32 --chunk 32

    # a 4-replica fleet: planned-bytes router, shared prefix cache,
    # batch work shed under overload (diurnal/heavy-tail trace)
    PYTHONPATH=src python -m repro.launch.serve --arch qwen2-0.5b --reduced \
        --replicas 4 --trace diurnal --prefix-cache 8 --batch-frac 0.5 \
        --max-backlog 16

    # elastic fleet: the autoscaler rides the diurnal curve between 1
    # and 4 replicas; --kill-at injects a replica death mid-run
    PYTHONPATH=src python -m repro.launch.serve --arch qwen2-0.5b --reduced \
        --trace diurnal --autoscale --min-replicas 1 --max-replicas 4 \
        --kill-at 64

    # legacy single-shot (one fixed batch, teacher-forced prefill)
    PYTHONPATH=src python -m repro.launch.serve --arch rwkv6-1.6b --reduced \
        --single-shot --batch 4 --prompt-len 32 --gen 16
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config, get_reduced
from repro.configs.base import ShapeConfig
from repro.core import compile_program
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_host_mesh, mesh_spec_for
from repro.models import encdec
from repro.models import transformer as tfm
from repro.models.layers import PEContext
from repro.runtime import train_loop as tl
from repro.serving import (AdmissionPolicy, Autoscaler, ElasticFleet,
                           build_engine, build_fleet, bursty_trace,
                           diurnal_trace, latency_stats, poisson_trace,
                           slo_stats)


def run_single_shot(args, cfg, mesh, use_mesh):
    """The pre-engine fixed-batch loop: every request same length, per-run
    cache allocation, teacher-forced prefill through the decode path."""
    B, P, G = args.batch, args.prompt_len, args.gen
    max_len = P + G
    shape = ShapeConfig("serve", seq_len=max_len, global_batch=B, kind="decode")
    program = compile_program(cfg, shape, mesh_spec_for(mesh))
    decode = jax.jit(tl.make_decode_step(cfg, program, use_mesh,
                                         kernel_backend=args.kernel_backend),
                     donate_argnums=(1,))

    key = jax.random.PRNGKey(args.seed)
    mm = tl.model_module(cfg)
    params = tl.cast_params(mm.init(key, cfg), jnp.bfloat16)
    sh = PEContext(use_mesh, program, backend=args.kernel_backend)

    # ---- prefill ----
    t0 = time.monotonic()
    if cfg.family == "audio":
        audio = jax.random.normal(key, (B, cfg.enc_seq, cfg.d_model))
        enc_out = encdec.encode(cfg, params, audio, sh)
        cache = encdec.init_cache(cfg, params, B, max_len)
        cache["cross"] = encdec.precompute_cross_kv(cfg, params, enc_out, sh)
        tok = jnp.ones((B, 1), jnp.int32)
        pos = jnp.zeros((B,), jnp.int32)
    else:
        prompt = jax.random.randint(key, (B, P), 0, cfg.vocab_size)
        cache = tfm.init_cache(cfg, B, max_len)
        tok = prompt[:, :1]
        pos = jnp.zeros((B,), jnp.int32)
        # teacher-forced prefill through the decode path (exercises the
        # cache exactly as production does)
        for t in range(P):
            logits, cache = decode(params, cache, prompt[:, t:t + 1], pos)
            pos = pos + 1
        tok = jnp.argmax(logits, -1).astype(jnp.int32)
    t_prefill = time.monotonic() - t0

    # ---- decode ----
    out_tokens = []
    t0 = time.monotonic()
    for _ in range(G):
        logits, cache = decode(params, cache, tok, pos)
        tok = jnp.argmax(logits, -1).astype(jnp.int32)
        out_tokens.append(jax.device_get(tok)[:, 0])
        pos = pos + 1
    t_decode = time.monotonic() - t0
    tps = B * G / t_decode
    print(f"arch={cfg.name} batch={B} prompt={P} gen={G}")
    print(f"prefill {t_prefill*1e3:.0f}ms  decode {t_decode*1e3:.0f}ms "
          f"({tps:.1f} tok/s aggregate)")
    print("sample token ids:", [int(t[0]) for t in out_tokens][:16])
    return 0


def make_trace(args, cfg, lo, hi):
    """The synthetic workload for engine/fleet mode (--trace)."""
    base = dict(vocab_size=cfg.vocab_size, prompt_lens=(lo, hi),
                gen_tokens=args.gen, seed=args.seed)
    if args.trace == "poisson":
        return poisson_trace(args.requests,
                             mean_interarrival_steps=args.rate, **base)
    if args.trace == "bursty":
        return bursty_trace(args.requests, burst_size=args.slots,
                            burst_gap_steps=max(1, int(args.rate * 8)),
                            **base)
    prefix_len = min(2 * args.chunk, hi - 1) if args.prefix_cache else 0
    return diurnal_trace(args.requests, batch_frac=args.batch_frac,
                         prefix_pool=args.prefix_pool if prefix_len else 0,
                         prefix_len=prefix_len, **base)


def run_fleet(args, cfg):
    """N replicas behind the planned-bytes router (single host: replicas
    are logical engines; the router math is the multi-module story)."""
    lo, hi = (int(x) for x in args.prompt_lens.split(","))
    max_len = args.max_len or hi + args.gen
    admission = (AdmissionPolicy(max_backlog=args.max_backlog)
                 if args.max_backlog is not None else None)
    autoscaler = None
    if args.autoscale:
        autoscaler = Autoscaler(
            min_replicas=args.min_replicas,
            max_replicas=args.max_replicas or max(args.replicas,
                                                  args.min_replicas))
    fleet = build_fleet(
        cfg, replicas=args.replicas, n_slots=args.slots, max_len=max_len,
        prefill_chunk=args.chunk, kernel_backend=args.kernel_backend,
        seed=args.seed, fused_decode=args.fused_decode,
        prefix_entries=args.prefix_cache, admission=admission,
        evict_patience=args.evict_patience, autoscaler=autoscaler,
        elastic=args.kill_at is not None)
    trace = make_trace(args, cfg, lo, hi)
    t0 = time.monotonic()
    if isinstance(fleet, ElasticFleet):
        chaos = [(args.kill_at, None)] if args.kill_at is not None else ()
        fleet.run(trace, chaos=chaos)
    else:
        fleet.run(trace)
    wall = time.monotonic() - t0
    stats = latency_stats(fleet.events)
    per_class = slo_stats(fleet)
    print(f"arch={cfg.name} replicas={args.replicas} trace={args.trace} "
          f"requests={args.requests} slots={args.slots}/replica "
          f"chunk={args.chunk}")
    print(f"steps={fleet.step_count} generated={stats['tokens']} "
          f"wall={wall * 1e3:.0f}ms "
          f"({stats['tokens'] / wall:.1f} tok/s generated)")
    for slo, c in per_class.items():
        print(f"  {slo:<12} submitted={c['submitted']} shed={c['shed']} "
              f"completed={c['completed']} tokens={c['tokens']} "
              f"p99_gap={c['p99_step_gap']:.0f} steps")
    if fleet.prefix is not None:
        px = fleet.prefix.stats()
        print(f"  prefix cache: {px['hits']}/{px['lookups']} hits "
              f"({px['hit_rate']:.1%}), {px['evictions']} evictions, "
              f"{px['entries']}/{px['capacity']} rows")
    counts = [0] * len(fleet.engines)
    for r in fleet.placement.values():
        counts[r] += 1
    print(f"  placement: {counts} requests/replica "
          f"(backlog high water {fleet.backlog_high_water})")
    if isinstance(fleet, ElasticFleet):
        print(f"  elastic: states={fleet.state} "
              f"replica_steps={fleet.replica_steps} "
              f"high_water={fleet.replica_high_water} "
              f"recovered={len(fleet.recovered)}")
        for step, what, r in fleet.scale_events:
            print(f"    step {step:>5}  {what:<7} replica {r}")
    return 0


def run_engine(args, cfg, mesh, use_mesh, on_done=None):
    """Continuous batching: slot arena + chunked prefill + masked decode."""
    lo, hi = (int(x) for x in args.prompt_lens.split(","))
    max_len = args.max_len or hi + args.gen
    engine = build_engine(
        cfg, n_slots=args.slots, max_len=max_len, prefill_chunk=args.chunk,
        kernel_backend=args.kernel_backend, mesh=use_mesh,
        mesh_spec=mesh_spec_for(mesh) if use_mesh is not None else None,
        seed=args.seed, evict_patience=args.evict_patience,
        fused_decode=args.fused_decode, speculative=args.speculative)
    trace = poisson_trace(args.requests, vocab_size=cfg.vocab_size,
                          prompt_lens=(lo, hi), gen_tokens=args.gen,
                          mean_interarrival_steps=args.rate, seed=args.seed)
    t0 = time.monotonic()
    results = engine.run(trace)
    wall = time.monotonic() - t0
    stats = latency_stats(engine.events)
    n_prompt = sum(len(r.prompt) for r in trace)
    print(f"arch={cfg.name} requests={args.requests} prompts=[{lo},{hi}] "
          f"gen={args.gen} slots={args.slots} chunk={args.chunk}")
    print(f"steps={engine.step_count} prompt_tokens={n_prompt} "
          f"generated={stats['tokens']} wall={wall*1e3:.0f}ms")
    print(f"throughput {stats['tokens']/wall:.1f} tok/s (generated), "
          f"{(n_prompt+stats['tokens'])/wall:.1f} tok/s (total); "
          f"per-token latency p50={stats['p50_ms']:.1f}ms "
          f"p99={stats['p99_ms']:.1f}ms")
    c = engine.counters
    done = engine.sched.finished.values()
    wait = [st.t_admit - st.t_submit for st in done]
    to_first = [st.t_first - st.t_admit for st in done]
    print(f"queue wait p95={np.percentile(wait, 95) * 1e3:.1f}ms; "
          f"admit to first token p95={np.percentile(to_first, 95) * 1e3:.1f}ms; "
          f"forced rows {c['forced_rows']}/{c['decode_rows']} decode rows "
          f"({c['forced_rows'] / max(1, c['decode_rows']):.1%})")
    if args.speculative:
        v = max(1, c["verifies"])
        print(f"speculative k={args.speculative}: "
              f"verifies={c['verifies']} accepted={c['accepted']} "
              f"({c['accepted'] / v:.2f} accepted/verify)")
    first = trace[0].rid
    print(f"sample ({first}):", results[first][:16])
    if on_done is not None:
        on_done(trace, results)
    return 0


def main(argv=None, *, on_done=None):
    """CLI entry.  In engine mode an in-process caller may also pass
    on_done(trace, results): the requests served and {rid: generated
    token ids}."""
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2-0.5b")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--kernel-backend", default="reference",
                    choices=("reference", "pallas"))
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--gen", type=int, default=16)
    # engine mode
    ap.add_argument("--requests", type=int, default=32)
    ap.add_argument("--prompt-lens", default="16,512",
                    help="lo,hi prompt-length band of the trace")
    ap.add_argument("--slots", type=int, default=32,
                    help="cache arena rows (max concurrent requests)")
    ap.add_argument("--chunk", type=int, default=32,
                    help="prefill chunk width (tokens per chunk step)")
    ap.add_argument("--rate", type=float, default=2.0,
                    help="mean request inter-arrival in engine steps")
    ap.add_argument("--max-len", type=int, default=0,
                    help="cache length per slot (0 = hi + gen)")
    ap.add_argument("--evict-patience", type=int, default=None,
                    help="steps a queued request starves before preemption")
    ap.add_argument("--fused-decode", action="store_true",
                    help="run the per-layer decode megakernel words")
    ap.add_argument("--speculative", type=int, default=0, metavar="K",
                    help="speculative decoding: draft K tokens per verify "
                         "(0 = off)")
    # fleet mode
    ap.add_argument("--replicas", type=int, default=1,
                    help="engine replicas behind the planned-bytes router "
                         "(>1, --prefix-cache, --max-backlog or a "
                         "non-poisson --trace selects fleet mode)")
    ap.add_argument("--trace", default="poisson",
                    choices=("poisson", "bursty", "diurnal"),
                    help="arrival process of the synthetic workload")
    ap.add_argument("--prefix-cache", type=int, default=0, metavar="E",
                    help="shared prefix cache rows fleet-wide (0 = off)")
    ap.add_argument("--prefix-pool", type=int, default=4,
                    help="[diurnal] distinct shared prompt heads in the "
                         "trace")
    ap.add_argument("--batch-frac", type=float, default=0.0,
                    help="[diurnal] fraction of requests in the batch SLO "
                         "class")
    ap.add_argument("--max-backlog", type=int, default=None,
                    help="SLO admission control: batch requests queue up "
                         "to this backlog and are shed past it (default: "
                         "no admission control)")
    ap.add_argument("--autoscale", action="store_true",
                    help="elastic fleet: an autoscaler rides backlog + "
                         "planned free-arena pressure between "
                         "--min-replicas and --max-replicas")
    ap.add_argument("--min-replicas", type=int, default=1,
                    help="[autoscale] replica floor")
    ap.add_argument("--max-replicas", type=int, default=0,
                    help="[autoscale] replica ceiling (0 = --replicas)")
    ap.add_argument("--kill-at", type=int, default=None, metavar="STEP",
                    help="chaos: kill the busiest replica at this fleet "
                         "step (in-flight requests recover elsewhere, "
                         "bit-identically)")
    # single-shot mode
    ap.add_argument("--single-shot", action="store_true",
                    help="legacy fixed-batch loop (parity oracle / audio)")
    ap.add_argument("--batch", type=int, default=None,
                    help="[single-shot] fixed batch size (default 4)")
    ap.add_argument("--prompt-len", type=int, default=None,
                    help="[single-shot] uniform prompt length (default 32)")
    args = ap.parse_args(argv)
    enable_compile_cache()

    cfg = get_reduced(args.arch) if args.reduced else get_config(args.arch)
    fleet_mode = (args.replicas > 1 or args.prefix_cache
                  or args.max_backlog is not None or args.trace != "poisson"
                  or args.autoscale or args.kill_at is not None)
    mesh = make_host_mesh()
    use_mesh = mesh if mesh.devices.size > 1 else None
    if args.single_shot or cfg.family == "audio":
        if args.fused_decode or args.speculative or fleet_mode:
            ap.error("--fused-decode/--speculative/--replicas/--trace apply "
                     "to engine/fleet mode only")
        args.batch = 4 if args.batch is None else args.batch
        args.prompt_len = 32 if args.prompt_len is None else args.prompt_len
        return run_single_shot(args, cfg, mesh, use_mesh)
    if args.batch is not None or args.prompt_len is not None:
        # don't silently run a very different workload than the user asked
        ap.error("--batch/--prompt-len apply to --single-shot only; "
                 "engine mode sizes the trace with --requests/--prompt-lens")
    if fleet_mode:
        if args.speculative:
            ap.error("--speculative applies to single-engine mode only")
        return run_fleet(args, cfg)
    return run_engine(args, cfg, mesh, use_mesh, on_done=on_done)


if __name__ == "__main__":
    raise SystemExit(main())
