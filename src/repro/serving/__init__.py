"""Continuous-batching serving engine (paper §2 phase decomposition).

Serving is two more phases of the same homogeneous substrate: PREFILL
(compute-bound prompt chunks) and DECODE (bandwidth-bound per-token
matvec).  This package schedules both onto one fixed cache arena:

- :mod:`slots` — the slot-based paged state pool: a fixed arena of
  KV/SSM/RNN cache rows; requests lease a slot row, retire releases it,
  ``reset_slots`` re-initialises rows in place (works for all three
  cache families).
- :mod:`scheduler` — admission queue + per-request state machine
  (QUEUED -> PREFILL -> DECODE -> FINISHED, with eviction back to
  QUEUED under arena pressure); chunked prefill is interleaved with
  decode so long prompts never stall the decode batch.
- :mod:`engine` — the array work: one jitted masked decode over every
  arena row per step (only active rows' new positions are written, in
  place) plus per-slot prefill chunk steps, both routed
  through ``PEContext`` under the PREFILL/DECODE program words.
- :mod:`trace` — synthetic request traces (Poisson, bursty and
  diurnal/heavy-tail arrivals) for examples and the throughput
  benchmarks.
- :mod:`fleet` — the scale-out layer (PR 8): N engine replicas behind a
  planned-free-bytes router, a shared prefix cache (common prompt heads
  prefill once, fleet-wide), and SLO-aware admission control
  (interactive vs batch, backlog + shedding under overload).  PR 9 made
  the fleet elastic: ``ElasticFleet`` + ``Autoscaler`` scale the replica
  set with the diurnal curve (drain → release the arena back through
  the planner) and survive replica death (in-flight requests re-prefill
  elsewhere from prompt + generated, bit-identically).

Two opt-in fast paths (PR 6): ``build_engine(fused_decode=True)`` runs
the per-layer decode megakernel words, ``build_engine(speculative=k)``
runs the draft/verify loop under the DRAFT phase — both bit-identical
per request to the per-op, non-speculative loop on the reference
backend.
"""
from repro.serving.engine import (ServingEngine, TokenEvent, build_engine,
                                  draft_config_for, latency_stats)
from repro.serving.fleet import (ACTIVE, DEAD, DRAINING, RETIRED,
                                 AdmissionPolicy, Autoscaler, ElasticFleet,
                                 Fleet, PrefixCache, build_fleet, prefix_key,
                                 slo_stats)
from repro.serving.scheduler import (BATCH, INTERACTIVE, SLO_CLASSES,
                                     Request, RequestState, Scheduler)
from repro.serving.slots import (SlotPool, plan_cache_arena, reset_slots,
                                 slot_bytes)
from repro.serving.trace import bursty_trace, diurnal_trace, poisson_trace

__all__ = ["ServingEngine", "TokenEvent", "build_engine", "draft_config_for",
           "latency_stats", "Request", "RequestState", "Scheduler",
           "SlotPool", "plan_cache_arena", "slot_bytes", "reset_slots",
           "poisson_trace", "bursty_trace", "diurnal_trace",
           "Fleet", "PrefixCache", "AdmissionPolicy", "build_fleet",
           "prefix_key", "slo_stats", "INTERACTIVE", "BATCH", "SLO_CLASSES",
           "ElasticFleet", "Autoscaler", "ACTIVE", "DRAINING", "RETIRED",
           "DEAD"]
