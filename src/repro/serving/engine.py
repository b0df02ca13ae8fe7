"""The serving engine: scheduler policy meets the jitted array work.

Two compiled step functions, both routed through ``PEContext`` under the
serving program words (core/phases.py):

- ``_decode``: one masked width-1 decode over every arena row (fixed
  shape, always the same jit).  Inactive rows compute garbage that is
  never written: ``decode_step`` takes the ``active`` mask and scatters
  each layer's new K/V position into the donated arena for active rows
  only (an inactive row's index is out of range and dropped), and
  recurrent states keep their old rows by a per-layer row select — so a
  step moves one position per active row, not the arena, and masked rows
  cost only FLOPs, never correctness.  Runs the DECODE word:
  bandwidth-oriented matvec, no SR entropy.
- ``_chunk``: one ``prefill_chunk``-wide prompt chunk for a single slot
  (dynamic slice on the arena's batch axis, slot index traced — one
  compile covers every slot).  Runs the compute-bound PREFILL word.

Both are bit-identical, per request, to the single-shot teacher-forced
decode loop on the reference backend (tests/test_serving.py) — the
engine changes *scheduling*, never *math*.

Two opt-in fast paths preserve that contract:

- fused decode (``build_engine(fused_decode=True)``): the program's
  DECODE words select the per-layer megakernel (kernels/decode_fused.py)
  and ``_decode`` runs one dispatch per LAYER instead of one per op.
  Masked-arena semantics are unchanged — inactive rows still compute
  garbage that is never written to the arena.
- speculative decoding (``build_engine(speculative=k)``): a small draft
  model proposes k-1 tokens under the DRAFT program word, the big model
  verifies all k feeds in ONE PREFILL-shaped chunk (``make_chunk_step``
  — PR 2's chunk≡sequential invariant makes it a verifier for free), and
  the accepted prefix is replayed into the slot arena.  Greedy argmax +
  that invariant make the committed tokens bit-identical to the
  non-speculative loop; acceptance only changes how many steps it takes.
"""
from __future__ import annotations

import time
from dataclasses import dataclass
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from repro.configs.base import ModelConfig
from repro.core.program import Program
from repro.runtime import train_loop as tl
from repro.serving.scheduler import DECODE, PREFILL, Request, Scheduler
from repro.serving.slots import (SlotPool, plan_cache_arena, reset_slots,
                                 slot_bytes)


@dataclass(frozen=True)
class TokenEvent:
    """One generated token: (request, token id, index within the request's
    output, engine step, wall-clock seconds)."""
    rid: str
    token: int
    index: int
    step: int
    t: float


class ServingEngine:
    """Continuous-batching engine over a fixed slot arena.

    cfg/program/params as compiled for a decode-kind ShapeConfig with
    ``seq_len=max_len`` and ``global_batch=n_slots``.  ``max_len`` bounds
    prompt + generated tokens per request.
    """

    def __init__(self, cfg: ModelConfig, program: Program, params,
                 *, n_slots: int, max_len: int, prefill_chunk: int = 32,
                 kernel_backend: str = "reference", mesh=None,
                 max_prefill_chunks_per_step: int = 1,
                 evict_patience: Optional[int] = None,
                 speculative: int = 0, draft_cfg: Optional[ModelConfig] = None,
                 draft_program: Optional[Program] = None, draft_params=None,
                 admit_hook=None, chunk_hook=None):
        if cfg.family == "audio":
            raise NotImplementedError(
                "the serving engine targets decoder-only families; audio "
                "serves via launch/serve.py --single-shot")
        if mesh is not None and cfg.moe is not None:
            # the sharded MoE path (_moe_sharded) drops tokens over expert
            # capacity, so the masked arena rows' garbage tokens would
            # COMPETE with active rows for capacity — batch rows stop
            # being independent and the parity invariant breaks silently.
            # Refuse rather than be quietly wrong; single-shard MoE
            # (mesh=None) is dropless and safe.
            raise NotImplementedError(
                "serving MoE models over a mesh routes through the "
                "capacity-dropping a2a path, which couples arena rows; "
                "use mesh=None (single-shard, dropless) or "
                "launch/serve.py --single-shot")
        self.cfg = cfg
        self.program = program
        self.params = params
        self.n_slots = n_slots
        self.max_len = max_len
        self.prefill_chunk = prefill_chunk
        # the slot arena comes from the same allocator the training
        # planner uses: pool.plan carries deterministic per-row offsets
        _, arena_plan = plan_cache_arena(cfg, max_len=max_len,
                                         n_slots=n_slots)
        self.pool = SlotPool(n_slots, plan=arena_plan)
        self.sched = Scheduler(
            self.pool, prefill_chunk=prefill_chunk,
            max_prefill_chunks_per_step=max_prefill_chunks_per_step,
            evict_patience=evict_patience)
        self.cache = tl.model_module(cfg).init_cache(cfg, n_slots, max_len)
        self.step_count = 0
        self.events: list = []
        # always-on work counts (Python ints; docs/ARCHITECTURE.md):
        # decode_rows sums the masked decode's active rows, forced_rows
        # the PREFILL-phase (teacher-forced prompt-tail) part of them
        self.counters = dict.fromkeys(
            ("steps", "chunk_steps", "decode_steps", "decode_rows",
             "forced_rows", "admitted", "evicted", "verifies", "accepted"),
            0)
        # fleet seams (serving/fleet.py): admit_hook(engine, state) runs
        # after a newly admitted request's arena row is reset (a prefix
        # cache may seed the row and skip prefill), chunk_hook(engine,
        # state) after every consumed prefill chunk (it may snapshot the
        # row at a prefix boundary).  Both default to None — the engine
        # alone never calls out.
        self.admit_hook = admit_hook
        self.chunk_hook = chunk_hook
        self._row_bytes = slot_bytes(cfg, max_len)

        make_decode = tl.make_fused_decode_step if program.fused_decode \
            else tl.make_decode_step
        decode_fn = make_decode(cfg, program, mesh,
                                kernel_backend=kernel_backend)
        chunk_fn = tl.make_chunk_step(cfg, program, mesh,
                                      kernel_backend=kernel_backend)

        def _decode(params, cache, tok, pos, active):
            logits, cache = decode_fn(params, cache, tok, pos, active)
            return jnp.argmax(logits[:, 0], -1).astype(jnp.int32), cache

        def _chunk(params, cache, tokens, pos0, slot):
            row = jax.tree.map(
                lambda a: jax.lax.dynamic_slice_in_dim(a, slot, 1, axis=1),
                cache)
            logits, new_row = chunk_fn(params, row, tokens, pos0)
            cache = jax.tree.map(
                lambda a, r: jax.lax.dynamic_update_slice_in_dim(
                    a, r, slot, axis=1),
                cache, new_row)
            return jnp.argmax(logits[0, -1], -1).astype(jnp.int32), cache

        self._decode = jax.jit(_decode, donate_argnums=(1,))
        self._chunk = jax.jit(_chunk, donate_argnums=(1,))
        self._reset = jax.jit(
            lambda cache, slot: reset_slots(cache, jnp.reshape(slot, (1,))),
            donate_argnums=(0,))
        # single-row get/put over the arena: the speculative loop's draft
        # snapshot/restore and the fleet's prefix-cache seed/capture both
        # move one slot row at a time (jit is lazy — unused paths never
        # compile)
        self._row_get = jax.jit(
            lambda cache, slot: jax.tree.map(
                lambda a: jax.lax.dynamic_slice_in_dim(
                    a, slot, 1, axis=1), cache))
        self._row_put = jax.jit(
            lambda cache, row, slot: jax.tree.map(
                lambda a, r: jax.lax.dynamic_update_slice_in_dim(
                    a, r, slot, axis=1), cache, row),
            donate_argnums=(0,))

        # --- speculative machinery (opt-in) ---
        self.speculative = int(speculative)
        if self.speculative:
            if draft_program is None or draft_cfg is None \
                    or draft_params is None:
                raise ValueError(
                    "speculative>0 needs a draft (cfg, program, params) — "
                    "build_engine(speculative=k) assembles one")
            self.draft_cfg = draft_cfg
            self.draft_params = draft_params
            self.draft_cache = tl.model_module(draft_cfg).init_cache(
                draft_cfg, n_slots, max_len)
            self._draft_pos: dict = {}   # rid -> seq tokens in draft cache
            draft_fn = tl.make_draft_step(draft_cfg, draft_program, mesh,
                                          kernel_backend=kernel_backend)

            def _draft(params, cache, tok, pos, active):
                logits, cache = draft_fn(params, cache, tok, pos, active)
                return (jnp.argmax(logits[:, 0], -1).astype(jnp.int32),
                        cache)

            def _verify(params, cache, tokens, pos0, slot):
                # PREFILL-shaped chunk over the request's arena row; the
                # cache writes are DISCARDED (no donation) — acceptance
                # decides what gets replayed into the arena
                row = jax.tree.map(
                    lambda a: jax.lax.dynamic_slice_in_dim(
                        a, slot, 1, axis=1),
                    cache)
                logits, _ = chunk_fn(params, row, tokens, pos0)
                return jnp.argmax(logits[0], -1).astype(jnp.int32)

            self._draft = jax.jit(_draft, donate_argnums=(1,))
            self._verify = jax.jit(_verify)

    # --- request intake ----------------------------------------------------

    def submit(self, req: Request) -> None:
        self._validate(req)
        self.sched.submit(req, self.step_count)

    def _validate(self, req: Request) -> None:
        if len(req.prompt) + req.max_new_tokens > self.max_len:
            raise ValueError(
                f"{req.rid}: prompt({len(req.prompt)}) + "
                f"max_new({req.max_new_tokens}) exceeds max_len={self.max_len}")

    # --- fleet seams (router metrics + prefix-cache row moves) --------------

    @property
    def arena_row_bytes(self) -> int:
        """Planned bytes of one slot row (the allocator's row size)."""
        return self._row_bytes

    @property
    def free_arena_bytes(self) -> int:
        """PLANNED free slot-arena bytes: (free slots - queued
        admissions) x the allocator's row bytes — the deterministic
        load-balance metric the fleet router ranks replicas by (PR 5's
        plan sized the arena, so this is plan math, not a runtime
        guess).  Queued requests are netted out because they hold a
        claim on a row before the next step leases it; the value goes
        negative on an oversubscribed replica, which is exactly the
        ranking the router wants."""
        return (self.pool.free_count - len(self.sched.queue)) \
            * self._row_bytes

    @property
    def queue_depth(self) -> int:
        return len(self.sched.queue)

    def row_snapshot(self, slot: int):
        """The arena row of `slot` as a standalone pytree (leaves shaped
        (n_groups, 1, ...)) — what the prefix cache stores."""
        return self._row_get(self.cache, jnp.int32(slot))

    def seed_row(self, st, row, pos: int) -> None:
        """Install a cached row into `st`'s slot and fast-forward its
        prefill cursor: the row must hold exactly the cache state after
        ``st.seq[:pos]`` (the chunk==sequential invariant then makes the
        remaining prefill bit-identical to having run the head here)."""
        if not 0 <= pos <= len(st.req.prompt) - 1:
            raise ValueError(
                f"{st.req.rid}: seed pos {pos} outside prompt "
                f"(len {len(st.req.prompt)}; one token must remain to feed)")
        self.cache = self._row_put(self.cache, row, jnp.int32(st.slot))
        st.pos = pos

    # --- elastic-fleet seams (drain / replica death) ------------------------

    @property
    def released(self) -> bool:
        """True once the slot arena has been given back (retired replica)."""
        return self.cache is None

    def eject_states(self) -> list:
        """Pull every in-flight request out of this replica (death or
        forced drain): slots are released and the states requeued with
        their generated tokens intact — re-admission elsewhere
        re-prefills prompt + generated, so the handoff is bit-invisible
        (the eviction contract, fleet-wide).  Speculative draft cursors
        are dropped; the draft catches up from the true sequence on
        re-admission."""
        states = self.sched.eject(self.step_count)
        if self.speculative:
            for st in states:
                self._draft_pos.pop(st.req.rid, None)
        return states

    def release_arena(self) -> None:
        """Give the slot arena back (drained replica retiring): the cache
        rows are freed and the fleet's planner ledger stops counting
        ``pool.plan.arena_bytes``.  Only legal once the scheduler is
        idle — residents must finish or be ejected first."""
        if not self.sched.idle:
            raise RuntimeError(
                f"release_arena with {len(self.sched.active)} residents + "
                f"{len(self.sched.queue)} queued; drain or eject first")
        self.cache = None

    # --- one engine iteration ----------------------------------------------

    def step(self) -> list:
        """One continuous-batching iteration: evict / admit / chunk-prefill
        / masked arena decode.  Returns the TokenEvents of this step.

        Each phase runs under a ``serve.*`` profiler span (see
        docs/ARCHITECTURE.md, "Tracing a running engine"); the two
        read-backs the host already waits on are ``serve.prefill.wait``
        and ``serve.decode.wait``."""
        if self.released:
            raise RuntimeError("stepping a retired replica (arena released)")
        step = self.step_count
        self.step_count += 1
        new_events: list = []
        c = self.counters
        c["steps"] += 1

        with TraceAnnotation("serve.step", step=step):
            with TraceAnnotation("serve.schedule"):
                c["evicted"] += len(self.sched.plan_evictions(step))
                joined = self.sched.admit(step)
                c["admitted"] += len(joined)
                for st in joined:
                    self.cache = self._reset(self.cache, jnp.int32(st.slot))
                    if self.speculative:
                        self.draft_cache = self._reset(self.draft_cache,
                                                       jnp.int32(st.slot))
                        self._draft_pos[st.req.rid] = 0
                    if self.admit_hook is not None:
                        self.admit_hook(self, st)

            # chunked prefill: bounded work per step, interleaved with decode
            chunked = self.sched.chunk_candidates()
            c["chunk_steps"] += bool(chunked)
            for st in chunked:
                with TraceAnnotation("serve.prefill", rid=st.req.rid,
                                     pos=st.pos):
                    toks = np.asarray(
                        st.seq[st.pos:st.pos + self.prefill_chunk],
                        np.int32)[None]
                    last, self.cache = self._chunk(
                        self.params, self.cache, jnp.asarray(toks),
                        jnp.asarray([st.pos], jnp.int32), jnp.int32(st.slot))
                    with TraceAnnotation("serve.prefill.wait"):
                        last = int(last)
                    appended, _ = self.sched.consume_chunk(
                        st, self.prefill_chunk, last)
                    if self.chunk_hook is not None:
                        self.chunk_hook(self, st)
                    if appended:
                        new_events.append(self._event(st, step))

            # masked width-1 decode over the whole arena: DECODE-phase rows
            # feed their last generated token, sub-chunk PREFILL tails are
            # teacher-forced (continuous batching: one iteration, all phases)
            rows = self.sched.decode_rows(chunked)
            spec_rows: list = []
            if self.speculative:
                # DECODE-phase rows take the draft/verify path; PREFILL tails
                # stay teacher-forced on the masked decode (nothing to draft)
                spec_rows = [s for s in rows if s.phase == DECODE]
                rows = [s for s in rows if s.phase != DECODE]
            if rows:
                with TraceAnnotation("serve.decode"):
                    tok = np.zeros((self.n_slots, 1), np.int32)
                    pos = np.zeros((self.n_slots,), np.int32)
                    active = np.zeros((self.n_slots,), bool)
                    for st in rows:
                        tok[st.slot, 0] = self.sched.feed_token(st)
                        pos[st.slot] = st.pos
                        active[st.slot] = True
                        c["forced_rows"] += st.phase == PREFILL
                    c["decode_steps"] += 1
                    c["decode_rows"] += len(rows)
                    nxt, self.cache = self._decode(
                        self.params, self.cache, jnp.asarray(tok),
                        jnp.asarray(pos), jnp.asarray(active))
                    with TraceAnnotation("serve.decode.wait"):
                        nxt = np.asarray(nxt)
                with TraceAnnotation("serve.commit"):
                    for st in rows:
                        appended, _ = self.sched.consume(st, int(nxt[st.slot]))
                        if appended:
                            new_events.append(self._event(st, step))

            for st in spec_rows:
                with TraceAnnotation("serve.spec", rid=st.req.rid):
                    new_events.extend(self._spec_round(st, step))

        self.events.extend(new_events)
        return new_events

    # --- speculative round --------------------------------------------------

    def _draft_step_one(self, tok: int, pos: int, slot: int) -> int:
        """One masked width-1 DRAFT step for a single arena row."""
        tokv = np.zeros((self.n_slots, 1), np.int32)
        posv = np.zeros((self.n_slots,), np.int32)
        act = np.zeros((self.n_slots,), bool)
        tokv[slot, 0] = tok
        posv[slot] = pos
        act[slot] = True
        nxt, self.draft_cache = self._draft(
            self.draft_params, self.draft_cache, jnp.asarray(tokv),
            jnp.asarray(posv), jnp.asarray(act))
        return int(np.asarray(nxt)[slot])

    def _spec_round(self, st, step: int) -> list:
        """Draft k-1 proposals, verify all k feeds in one chunk, commit
        the accepted prefix.

        Greedy + the chunk≡sequential invariant make every committed
        token bit-identical to the non-speculative loop: chunk logits at
        position i depend only on feeds <= i, and a proposal is only
        accepted when it equals the big model's own argmax at that
        position — so the accepted feeds ARE the sequential feeds.
        Rollback is by construction: verify never writes the arena
        (cache writes discarded), the accepted feeds are replayed as one
        teacher-forced chunk; the draft row is snapshot/restored and
        caught up from the true sequence next round (SSM draft states
        cannot be partially rolled back, so the draft never keeps
        speculative state).
        """
        k = self.speculative
        rid, slot, p = st.req.rid, st.slot, st.pos
        seq = st.seq
        feed = seq[p]                    # remaining == 1 in DECODE phase

        # draft catch-up: teacher-force the suffix the draft hasn't seen
        for q in range(self._draft_pos.get(rid, 0), p):
            self._draft_step_one(seq[q], q, slot)
        self._draft_pos[rid] = p
        snap = self._row_get(self.draft_cache, jnp.int32(slot))

        # k-1 greedy proposals under the DRAFT word
        props: list = []
        cur = feed
        for i in range(k - 1):
            cur = self._draft_step_one(cur, p + i, slot)
            props.append(cur)

        # one PREFILL-shaped verify chunk over [feed, d1..d_{k-1}]
        toks = np.asarray([feed] + props, np.int32)[None]
        vt = [int(t) for t in np.asarray(self._verify(
            self.params, self.cache, jnp.asarray(toks),
            jnp.asarray([p], jnp.int32), jnp.int32(slot)))]

        # accepted prefix: proposal i survives iff it IS the big model's
        # argmax at its position; position 0 (teacher-forced) always lands
        a = 0
        while a < len(props) and props[a] == vt[a]:
            a += 1
        commit = vt[:a + 1][:st.req.max_new_tokens - len(st.generated)]

        # replay the accepted feeds into the arena (the committed write)
        replay = ([feed] + commit[:-1])[:len(commit)]
        _, self.cache = self._chunk(
            self.params, self.cache, jnp.asarray(
                np.asarray(replay, np.int32)[None]),
            jnp.asarray([p], jnp.int32), jnp.int32(slot))

        # restore the draft row: proposals were speculative state
        self.draft_cache = self._row_put(self.draft_cache, snap,
                                         jnp.int32(slot))

        appended, fin = self.sched.consume_spec(st, commit)
        self.counters["verifies"] += 1
        self.counters["accepted"] += appended
        if fin:
            self._draft_pos.pop(rid, None)
        base = len(st.generated) - appended
        return [TokenEvent(rid=rid, token=st.generated[base + j],
                           index=base + j, step=step, t=time.monotonic())
                for j in range(appended)]

    def _event(self, st, step: int) -> TokenEvent:
        return TokenEvent(rid=st.req.rid, token=st.generated[-1],
                          index=len(st.generated) - 1, step=step,
                          t=time.monotonic())

    # --- drive to completion ------------------------------------------------

    def run(self, requests=(), max_steps: int = 1_000_000) -> dict:
        """Feed `requests` at their arrival steps and run until drained.

        Returns {rid: generated token list}.
        """
        pending = sorted(requests, key=lambda r: (r.arrival_step, r.rid))
        for r in pending:
            self._validate(r)       # fail BEFORE any compute, not mid-run
        i = 0
        for _ in range(max_steps):
            while i < len(pending) \
                    and pending[i].arrival_step <= self.step_count:
                self.submit(pending[i])
                i += 1
            if i == len(pending) and self.sched.idle:
                return self.sched.results()
            self.step()
        raise RuntimeError(f"engine did not drain in {max_steps} steps")

    @property
    def concurrency(self) -> int:
        return len(self.sched.active)


def draft_config_for(cfg: ModelConfig) -> ModelConfig:
    """The default speculative draft: one scan group of the big model.

    Shares the big model's token space and layer-pattern period (the two
    things the speculative loop actually requires) while dropping every
    repeated group — the smallest config the stack can run unchanged.
    """
    import dataclasses

    from repro.models.transformer import layer_pattern
    period = len(layer_pattern(cfg))
    return dataclasses.replace(cfg, name=cfg.name + "-draft",
                               n_layers=period)


def build_engine(cfg: ModelConfig, *, n_slots: Optional[int] = None,
                 max_len: int,
                 prefill_chunk: int = 32, kernel_backend: str = "reference",
                 mesh=None, mesh_spec=None, seed: int = 0,
                 hbm_budget: Optional[float] = None,
                 fused_decode: bool = False, speculative: int = 0,
                 draft_cfg: Optional[ModelConfig] = None,
                 draft_seed: Optional[int] = None,
                 **engine_kwargs) -> ServingEngine:
    """One-stop constructor: compile the serve-kind program, init bf16
    params, build the engine — the shared setup of the serve CLI, the
    examples, and the throughput benchmark (keep them in lockstep here).

    mesh_spec is required when `mesh` is given (the CLI passes
    ``mesh_spec_for(mesh)``); single-device callers omit both.

    n_slots=None sizes the arena from ``hbm_budget`` via the memory
    allocator (``serving.slots.plan_cache_arena``), reserving the bf16
    parameter bytes the engine also holds.

    fused_decode=True compiles the program with the ``decode_fused``
    megakernel words; speculative=k enables the draft/verify loop with a
    k-token speculation window (``draft_cfg`` defaults to one scan group
    of `cfg` — see :func:`draft_config_for` — with its own seed+1 init;
    ``draft_seed`` overrides that, and draft_cfg=cfg with
    draft_seed=seed makes the draft the big model itself: the
    full-acceptance oracle the benchmark gates accepted-per-verify on).
    """
    from repro.configs.base import ShapeConfig
    from repro.core.dataflow import MeshSpec
    from repro.core.program import compile_program
    if mesh_spec is None:
        if mesh is not None:
            raise ValueError("pass mesh_spec alongside mesh")
        mesh_spec = MeshSpec(axis_sizes={"data": 1, "model": 1})
    if n_slots is None:
        n_slots, _ = plan_cache_arena(
            cfg, max_len=max_len, hbm_budget=hbm_budget,
            reserve_bytes=2.0 * cfg.param_count())
    shape = ShapeConfig("serve", seq_len=max_len, global_batch=n_slots,
                        kind="decode")
    program = compile_program(cfg, shape, mesh_spec,
                              fused_decode=fused_decode,
                              speculative=bool(speculative))
    params = tl.cast_params(
        tl.model_module(cfg).init(jax.random.PRNGKey(seed), cfg),
        jnp.bfloat16)
    if speculative:
        draft_cfg = draft_cfg or draft_config_for(cfg)
        draft_shape = ShapeConfig("serve-draft", seq_len=max_len,
                                  global_batch=n_slots, kind="decode")
        engine_kwargs.update(
            speculative=speculative, draft_cfg=draft_cfg,
            draft_program=compile_program(draft_cfg, draft_shape, mesh_spec,
                                          speculative=True),
            draft_params=tl.cast_params(
                tl.model_module(draft_cfg).init(
                    jax.random.PRNGKey(seed + 1 if draft_seed is None
                                       else draft_seed), draft_cfg),
                jnp.bfloat16))
    return ServingEngine(cfg, program, params, n_slots=n_slots,
                         max_len=max_len, prefill_chunk=prefill_chunk,
                         kernel_backend=kernel_backend, mesh=mesh,
                         **engine_kwargs)


def latency_stats(events) -> dict:
    """Aggregate throughput + per-token latency over a run's TokenEvents.

    Per-token latency is the wall-clock gap between a request's
    consecutive tokens (inter-token latency; arrivals are step-quantised
    so time-to-first-token is not meaningful here).
    """
    if not events:
        return {"tokens": 0, "wall_s": 0.0, "tok_s": 0.0,
                "p50_ms": 0.0, "p99_ms": 0.0}
    by_rid: dict = {}
    for e in events:
        by_rid.setdefault(e.rid, []).append(e)
    gaps = []
    for evs in by_rid.values():
        evs = sorted(evs, key=lambda e: e.index)
        gaps += [b.t - a.t for a, b in zip(evs, evs[1:])]
    wall = max(e.t for e in events) - min(e.t for e in events)
    n = len(events)
    gaps.sort()
    pick = (lambda q: gaps[min(len(gaps) - 1, int(q * len(gaps)))]) if gaps \
        else (lambda q: 0.0)
    return {"tokens": n, "wall_s": wall,
            "tok_s": n / wall if wall > 0 else float("inf"),
            "p50_ms": pick(0.50) * 1e3, "p99_ms": pick(0.99) * 1e3}
