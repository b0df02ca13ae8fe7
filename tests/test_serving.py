"""Serving-engine invariants: scheduling changes, math doesn't.

Everything here asserts BIT-identity on the reference backend — the
continuous-batching engine (slot arena, chunked prefill, masked decode,
eviction) must be invisible in the outputs relative to the single-shot
teacher-forced decode loop (the pre-engine serve.py path).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_reduced
from repro.configs.base import ShapeConfig
from repro.core import Phase, compile_program
from repro.core.dataflow import MeshSpec
from repro.models import transformer as tfm
from repro.models.layers import PEContext
from repro.runtime import train_loop as tl
from repro.serving import Request, ServingEngine, SlotPool, reset_slots

MESH1 = MeshSpec(axis_sizes={"data": 1, "model": 1}, batch_axes=("data",))


def build(arch: str, *, n_slots: int, max_len: int):
    cfg = get_reduced(arch)
    shape = ShapeConfig("serve", seq_len=max_len, global_batch=n_slots,
                        kind="decode")
    program = compile_program(cfg, shape, MESH1)
    params = tl.cast_params(tfm.init(jax.random.PRNGKey(0), cfg),
                            jnp.bfloat16)
    return cfg, program, params


def single_shot(cfg, program, params, prompt, gen: int, max_len: int):
    """The oracle: per-request width-1 teacher-forced decode at B=1
    (exactly the legacy serve.py loop)."""
    decode = jax.jit(tl.make_decode_step(cfg, program, None))
    cache = tfm.init_cache(cfg, 1, max_len)
    pos = jnp.zeros((1,), jnp.int32)
    seq = list(prompt)
    out = []
    t = 0
    while len(out) < gen:
        logits, cache = decode(params, cache,
                               jnp.asarray([[seq[t]]], jnp.int32), pos)
        pos = pos + 1
        t += 1
        if t == len(seq):
            nxt = int(jnp.argmax(logits[0, 0], -1))
            out.append(nxt)
            seq.append(nxt)
    return out


def mixed_prompts(cfg, lens, seed=0):
    rng = np.random.default_rng(seed)
    return [tuple(int(x) for x in rng.integers(0, cfg.vocab_size, size=l))
            for l in lens]


# ---------------------------------------------------------------------------
# Slot pool
# ---------------------------------------------------------------------------


def test_slot_pool_lease_release_deterministic():
    pool = SlotPool(3)
    assert [pool.lease(f"r{i}") for i in range(3)] == [0, 1, 2]
    assert pool.lease("r3") is None                  # arena full
    pool.release(1)
    assert pool.lease("r4") == 1                     # lowest free first
    assert pool.newest_leased() == 1                 # most recent lease
    with pytest.raises(KeyError):
        pool.release(1 + 10)


def test_reset_slots_reinitialises_all_cache_families():
    cfg = get_reduced("jamba-v0.1-52b")              # attn + mamba + moe
    cache = tfm.init_cache(cfg, 3, 16)
    dirty = jax.tree.map(lambda a: a + jnp.asarray(7, a.dtype), cache)
    clean = reset_slots(dirty, [1])
    for init, got in zip(jax.tree.leaves(cache), jax.tree.leaves(clean)):
        # row 1 back to init values, rows 0/2 untouched (still dirty)
        assert np.array_equal(np.asarray(got[:, 1]), np.asarray(init[:, 1]))
        assert not np.array_equal(np.asarray(got[:, 0]),
                                  np.asarray(init[:, 0]))


# ---------------------------------------------------------------------------
# Chunked prefill == whole-prompt prefill == token-by-token decode
# ---------------------------------------------------------------------------


def test_chunked_prefill_bit_identical_to_whole_prompt():
    MAX_LEN = 32
    cfg, program, params = build("qwen2-0.5b", n_slots=1, max_len=MAX_LEN)
    P = 12
    prompt = jnp.asarray(mixed_prompts(cfg, [P])[0], jnp.int32)[None]
    chunk = jax.jit(tl.make_chunk_step(cfg, program, None))
    decode = jax.jit(tl.make_decode_step(cfg, program, None))

    # whole-prompt: one chunk of size P
    cache = tfm.init_cache(cfg, 1, MAX_LEN)
    whole, cache_whole = chunk(params, cache, prompt,
                               jnp.zeros((1,), jnp.int32))

    # chunked: 5 + 4 + 3
    cache = tfm.init_cache(cfg, 1, MAX_LEN)
    pos, parts = 0, []
    for a, b in ((0, 5), (5, 9), (9, 12)):
        lg, cache = chunk(params, cache, prompt[:, a:b],
                          jnp.asarray([pos], jnp.int32))
        pos = b
        parts.append(lg)
    chunked = jnp.concatenate(parts, 1)
    assert np.array_equal(np.asarray(chunked), np.asarray(whole))

    # token-by-token decode path
    cache = tfm.init_cache(cfg, 1, MAX_LEN)
    seq_logits = []
    p = jnp.zeros((1,), jnp.int32)
    for t in range(P):
        lg, cache = decode(params, cache, prompt[:, t:t + 1], p)
        p = p + 1
        seq_logits.append(lg[:, 0])
    assert np.array_equal(np.asarray(jnp.stack(seq_logits, 1)),
                          np.asarray(whole))
    # and the caches agree bit-for-bit with the whole-prompt cache
    for a, b in zip(jax.tree.leaves(cache), jax.tree.leaves(cache_whole)):
        assert np.array_equal(np.asarray(a), np.asarray(b))


# ---------------------------------------------------------------------------
# Engine == single-shot, mixed trace
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("arch", ["qwen2-0.5b", "jamba-v0.1-52b"])
def test_engine_matches_single_shot(arch):
    """Continuous batching (ragged joins, chunked prefill, slot reuse) is
    bit-invisible per request vs the legacy fixed-batch loop."""
    MAX_LEN, GEN = 48, 8
    cfg, program, params = build(arch, n_slots=3, max_len=MAX_LEN)
    lens = [17, 4, 23, 9, 31, 6]                     # > n_slots: forces reuse
    prompts = mixed_prompts(cfg, lens, seed=1)
    reqs = [Request(rid=f"r{i}", prompt=p, max_new_tokens=GEN,
                    arrival_step=2 * i)
            for i, p in enumerate(prompts)]
    engine = ServingEngine(cfg, program, params, n_slots=3, max_len=MAX_LEN,
                           prefill_chunk=8)
    results = engine.run(reqs)
    assert set(results) == {r.rid for r in reqs}
    for r in reqs:
        want = single_shot(cfg, program, params, r.prompt, GEN, MAX_LEN)
        assert results[r.rid] == want, r.rid


def test_windowed_ring_wrap_chunked_prefill_matches_single_shot():
    """Sliding-window ring caches wrap mid-chunk (window < prompt len):
    the regime _unit_chunk's per-token scan exists for.  A vectorised
    chunk insert would overwrite ring slots earlier in-chunk queries
    still attend — this parity case pins the scan path."""
    import dataclasses
    MAX_LEN, GEN, WINDOW = 40, 6, 8
    base = get_reduced("qwen2-0.5b")
    cfg = dataclasses.replace(
        base, attention=dataclasses.replace(base.attention, window=WINDOW))
    shape = ShapeConfig("serve", seq_len=MAX_LEN, global_batch=2,
                        kind="decode")
    program = compile_program(cfg, shape, MESH1)
    params = tl.cast_params(tfm.init(jax.random.PRNGKey(0), cfg),
                            jnp.bfloat16)
    prompts = mixed_prompts(cfg, [25, 19], seed=4)     # >> window: wraps
    reqs = [Request(rid=f"r{i}", prompt=p, max_new_tokens=GEN)
            for i, p in enumerate(prompts)]
    engine = ServingEngine(cfg, program, params, n_slots=2, max_len=MAX_LEN,
                           prefill_chunk=6)            # chunk crosses wraps
    results = engine.run(reqs)
    for r in reqs:
        want = single_shot(cfg, program, params, r.prompt, GEN, MAX_LEN)
        assert results[r.rid] == want, r.rid


def _masked_decode_config(arch: str):
    """Reduced `arch`; "qwen2-0.5b-window" is qwen2 with an 8-position
    sliding-window ring cache."""
    import dataclasses
    if arch != "qwen2-0.5b-window":
        return get_reduced(arch)
    base = get_reduced("qwen2-0.5b")
    return dataclasses.replace(
        base, attention=dataclasses.replace(base.attention, window=8))


@pytest.mark.parametrize("arch", ["qwen2-0.5b", "jamba-v0.1-52b",
                                  "qwen2-0.5b-window"])
def test_masked_decode_writes_active_rows_only(arch):
    """The engine's decode writes the new position of each ACTIVE row in
    place: every inactive row's cache leaves come back bit-identical, and
    the active rows equal an every-row decode whose inactive rows are then
    restored by a row select."""
    MAX_LEN, SLOTS = 16, 4
    cfg = _masked_decode_config(arch)
    shape = ShapeConfig("serve", seq_len=MAX_LEN, global_batch=SLOTS,
                        kind="decode")
    program = compile_program(cfg, shape, MESH1)
    params = tl.cast_params(tfm.init(jax.random.PRNGKey(0), cfg),
                            jnp.bfloat16)
    engine = ServingEngine(cfg, program, params, n_slots=SLOTS,
                           max_len=MAX_LEN)
    # a full arena: random K/V and states, positions in [-1, MAX_LEN)
    leaves, tree = jax.tree.flatten(engine.cache)
    keys = jax.random.split(jax.random.PRNGKey(5), len(leaves))
    cache = jax.tree.unflatten(tree, [
        jax.random.randint(k, a.shape, -1, MAX_LEN, a.dtype)
        if jnp.issubdtype(a.dtype, jnp.integer)
        else jax.random.normal(k, a.shape, a.dtype)
        for k, a in zip(keys, leaves)])
    tok = jnp.asarray([[3], [17], [29], [41]], jnp.int32)
    pos = jnp.asarray([3, 7, 12, 9], jnp.int32)       # wraps the window ring
    active = np.asarray([True, False, True, False])

    logits, every_row = jax.jit(tl.make_decode_step(cfg, program, None))(
        params, cache, tok, pos)
    want = jax.tree.map(
        lambda new, old: jnp.where(
            active.reshape((1, SLOTS) + (1,) * (new.ndim - 2)), new, old),
        every_row, cache)
    nxt, got = engine._decode(params, jax.tree.map(jnp.copy, cache), tok,
                              pos, jnp.asarray(active))

    for g, w, old in zip(jax.tree.leaves(got), jax.tree.leaves(want),
                         jax.tree.leaves(cache)):
        g, w, old = np.asarray(g), np.asarray(w), np.asarray(old)
        assert np.array_equal(g[:, ~active], old[:, ~active])
        assert np.array_equal(g[:, active], w[:, active])
    assert not all(np.array_equal(np.asarray(a)[:, active],
                                  np.asarray(b)[:, active])
                   for a, b in zip(jax.tree.leaves(got),
                                   jax.tree.leaves(cache)))
    assert np.array_equal(np.asarray(nxt)[active],
                          np.asarray(jnp.argmax(logits[:, 0], -1))[active])


def test_slot_reuse_after_retire():
    """More requests than slots: retired slots are re-leased and the
    reset rows carry no state from the previous tenant."""
    MAX_LEN, GEN = 24, 5
    cfg, program, params = build("qwen2-0.5b", n_slots=2, max_len=MAX_LEN)
    prompts = mixed_prompts(cfg, [7, 5, 9, 4, 11, 6], seed=2)
    reqs = [Request(rid=f"r{i}", prompt=p, max_new_tokens=GEN)
            for i, p in enumerate(prompts)]
    engine = ServingEngine(cfg, program, params, n_slots=2, max_len=MAX_LEN,
                           prefill_chunk=4)
    results = engine.run(reqs)
    # all six ran on two slots => every slot served multiple tenants
    assert engine.pool.free_count == 2
    for r in reqs:
        want = single_shot(cfg, program, params, r.prompt, GEN, MAX_LEN)
        assert results[r.rid] == want, r.rid


def test_eviction_under_arena_pressure():
    """Starved queue preempts the newest resident; evicted requests
    resume via re-prefill of prompt+generated, outputs unchanged."""
    MAX_LEN, GEN = 32, 10
    cfg, program, params = build("qwen2-0.5b", n_slots=2, max_len=MAX_LEN)
    prompts = mixed_prompts(cfg, [13, 8, 11, 5], seed=3)
    reqs = [Request(rid=f"r{i}", prompt=p, max_new_tokens=GEN,
                    arrival_step=0)
            for i, p in enumerate(prompts)]
    engine = ServingEngine(cfg, program, params, n_slots=2, max_len=MAX_LEN,
                           prefill_chunk=4, evict_patience=3)
    results = engine.run(reqs)
    n_evictions = sum(st.evictions
                      for st in engine.sched.finished.values())
    assert n_evictions > 0, "pressure test never evicted"
    for r in reqs:
        want = single_shot(cfg, program, params, r.prompt, GEN, MAX_LEN)
        assert results[r.rid] == want, r.rid


# ---------------------------------------------------------------------------
# Program words
# ---------------------------------------------------------------------------


def test_serving_program_words():
    """A serve-kind program compiles PREFILL/DECODE words: decode is the
    bandwidth matvec with no SR; state-role ops stay on the VPU."""
    cfg = get_reduced("jamba-v0.1-52b")
    shape = ShapeConfig("serve", seq_len=64, global_batch=4, kind="decode")
    program = compile_program(cfg, shape, MESH1, precision="paper_sr_bf16")
    entries = program.ibuffer_entries()
    assert {e["phase"] for e in entries} == {"PREFILL", "DECODE"}
    for e in entries:
        state_op = program.op_spec(e["op"]).role == "state"
        if e["phase"] == "DECODE":
            assert e["kernel"] == ("vpu" if state_op else "matvec"), e
        else:
            assert e["kernel"] == ("vpu" if state_op else "sr_matmul"), e
        assert e["rounding"] == "nearest", e         # no SR in serving
    word = program.pe_word("attn_qkv")
    assert word.kernel_for(Phase.DECODE) == "matvec"
    assert word.kernel_for(Phase.PREFILL) == "sr_matmul"
    # train programs unchanged
    tr = compile_program(cfg, ShapeConfig("t", seq_len=32, global_batch=2,
                                          kind="train"), MESH1)
    assert {e["phase"] for e in tr.ibuffer_entries()} == {"FF", "BP", "UP"}


def test_decode_phase_context_threads_through_engine_dispatch():
    """PEContext.with_phase(DECODE) reaches pe_dot: the pallas backend
    takes the matvec path (f32 accum), and the reference backend stays
    bit-identical to the phase-less context."""
    cfg, program, params = build("qwen2-0.5b", n_slots=2, max_len=16)
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 1, cfg.d_model),
                          jnp.bfloat16)
    w = params["groups"]["u0"]["ffn"]["ffn_in"][0]
    base = PEContext(None, program)
    dec = base.with_phase(Phase.DECODE)
    assert np.array_equal(
        np.asarray(base.dot("ffn_in", x, w)),
        np.asarray(dec.dot("ffn_in", x, w)))
    pal = PEContext(None, program, backend="pallas",
                    interpret=True).with_phase(Phase.DECODE)
    got = pal.dot("ffn_in", x, w)
    want = jnp.matmul(x, w.astype(x.dtype),
                      preferred_element_type=jnp.float32).astype(x.dtype)
    assert np.array_equal(np.asarray(got), np.asarray(want))
