"""Decode megakernel + speculative DRAFT loop: fast paths change nothing.

Two families of invariant, mirroring tests/test_serving.py:

- BIT-identity on the reference backend: the fused-decode composition and
  the speculative draft/verify/rollback loop must be invisible per
  request relative to the per-op, non-speculative engine.
- allclose on the pallas backend (interpret): the one-launch-per-layer
  megakernel accumulates in f32, so it matches the reference decode step
  to bf16 tolerance and its cache writes land on the same arena rows.

Plus the plumbing that carries tuner winners into the kernel's
BlockSpecs, the DRAFT program words, and the bursty trace generator.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_reduced
from repro.configs.base import ShapeConfig
from repro.core import Phase, compile_program
from repro.core.dataflow import MeshSpec
from repro.core.program import extract_ops
from repro.engine.dispatch import fused_block_n
from repro.models import transformer as tfm
from repro.runtime import train_loop as tl
from repro.serving import Request, build_engine, bursty_trace
from repro.tuner import (FUSED_DECODE_OPS, tune_fused_decode, tune_program)

MESH1 = MeshSpec(axis_sizes={"data": 1, "model": 1}, batch_axes=("data",))


def mixed_requests(cfg, lens, gen, seed=0):
    rng = np.random.default_rng(seed)
    return [Request(rid=f"r{i}",
                    prompt=tuple(int(x) for x in
                                 rng.integers(0, cfg.vocab_size, size=l)),
                    max_new_tokens=gen, arrival_step=i)
            for i, l in enumerate(lens)]


def run_engine(cfg, reqs, max_len, **kw):
    eng = build_engine(cfg, n_slots=3, max_len=max_len, prefill_chunk=6,
                       seed=0, **kw)
    return eng.run(reqs), eng


# ---------------------------------------------------------------------------
# Fused decode == per-op decode (reference backend, bit-exact)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("arch", ["qwen2-0.5b", "rwkv6-1.6b",
                                  "jamba-v0.1-52b"])
def test_fused_decode_bit_identical(arch):
    """All three cache families (attn KV ring, RWKV state, mamba conv+ssm
    + MoE) through the fused words: same tokens, request for request."""
    cfg = get_reduced(arch)
    reqs = mixed_requests(cfg, [9, 4, 13, 7], gen=6, seed=1)
    r_ref, _ = run_engine(cfg, reqs, max_len=24)
    r_fused, eng = run_engine(cfg, reqs, max_len=24, fused_decode=True)
    assert eng.program.fused_decode
    assert r_fused == r_ref


def test_fused_decode_windowed_attention_bit_identical():
    """Sliding-window masking inside the megakernel's paged attention:
    prompts longer than the window force ring wrap + window clipping."""
    base = get_reduced("qwen2-0.5b")
    cfg = dataclasses.replace(
        base, attention=dataclasses.replace(base.attention, window=8))
    reqs = mixed_requests(cfg, [21, 13], gen=6, seed=2)
    r_ref, _ = run_engine(cfg, reqs, max_len=32)
    r_fused, _ = run_engine(cfg, reqs, max_len=32, fused_decode=True)
    assert r_fused == r_ref


# ---------------------------------------------------------------------------
# Speculative loop == sequential loop (bit-exact accepted tokens)
# ---------------------------------------------------------------------------


def test_speculative_bit_identical_random_draft():
    """Default draft (one scan group, different init) mostly disagrees
    with the big model — every verify exercises reject + rollback, and
    the committed stream must still be the sequential greedy stream."""
    cfg = get_reduced("qwen2-0.5b")
    reqs = mixed_requests(cfg, [9, 4, 13, 7], gen=8, seed=3)
    r_ref, _ = run_engine(cfg, reqs, max_len=32)
    r_spec, eng = run_engine(cfg, reqs, max_len=32, speculative=3)
    assert eng.counters["verifies"] > 0
    assert r_spec == r_ref
    # the request budget is exact even when a verify over-proposes
    for r in reqs:
        assert len(r_spec[r.rid]) == r.max_new_tokens


def test_speculative_self_draft_accepts_everything():
    """draft == big model: every proposal verifies, so accepted-per-verify
    hits the k-token window (minus end-of-request truncation) — the
    deterministic full-acceptance oracle the benchmark gates."""
    cfg = get_reduced("qwen2-0.5b")
    reqs = mixed_requests(cfg, [9, 4], gen=7, seed=4)
    r_ref, _ = run_engine(cfg, reqs, max_len=24)
    r_spec, eng = run_engine(cfg, reqs, max_len=24, speculative=3,
                             draft_cfg=cfg, draft_seed=0)
    assert r_spec == r_ref
    s = eng.counters
    # gen=7: prefill emits token 0, spec commits 3+3 then hits the budget
    assert s["accepted"] == sum(r.max_new_tokens - 1 for r in reqs)
    assert s["accepted"] / s["verifies"] > 2.0


def test_speculative_with_fused_decode_combined():
    cfg = get_reduced("qwen2-0.5b")
    reqs = mixed_requests(cfg, [9, 4, 6], gen=5, seed=5)
    r_ref, _ = run_engine(cfg, reqs, max_len=16)
    r_both, _ = run_engine(cfg, reqs, max_len=16, speculative=2,
                           fused_decode=True)
    assert r_both == r_ref


# ---------------------------------------------------------------------------
# Pallas megakernel (interpret) ~= reference decode step
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("arch", ["qwen2-0.5b", "rwkv6-1.6b"])
def test_megakernel_interpret_allclose(arch):
    """One launch per layer (attn unit fused; SSM mixers keep per-op words
    + the fused FFN) vs the per-op reference, over several cache-append
    steps."""
    cfg = get_reduced(arch)
    B, MAX_LEN = 2, 16
    shape = ShapeConfig("serve", seq_len=MAX_LEN, global_batch=B,
                        kind="decode")
    prog = compile_program(cfg, shape, MESH1, fused_decode=True)
    params = tl.cast_params(tfm.init(jax.random.PRNGKey(0), cfg),
                            jnp.bfloat16)
    ref = jax.jit(tl.make_decode_step(cfg, prog, None,
                                      kernel_backend="reference"))
    fus = jax.jit(tl.make_fused_decode_step(cfg, prog, None,
                                            kernel_backend="pallas"))
    c0, c1 = tfm.init_cache(cfg, B, MAX_LEN), tfm.init_cache(cfg, B, MAX_LEN)
    key = jax.random.PRNGKey(7)
    for t in range(3):
        tok = jax.random.randint(jax.random.fold_in(key, t), (B, 1), 0,
                                 cfg.vocab_size)
        pos = jnp.full((B,), t, jnp.int32)
        l0, c0 = ref(params, c0, tok, pos)
        l1, c1 = fus(params, c1, tok, pos)
        np.testing.assert_allclose(np.asarray(l0, np.float32),
                                   np.asarray(l1, np.float32),
                                   atol=2e-2, rtol=2e-2)
    # cache entries are single bf16 dot products (no averaging): a near-
    # cancelling sum can differ by a few ulp-of-the-terms between the f32
    # accumulator and the reference bf16 chain, so the atol is looser
    for a, b in zip(jax.tree.leaves(c0), jax.tree.leaves(c1)):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32),
                                   atol=6e-2, rtol=6e-2)


# ---------------------------------------------------------------------------
# Program words + tuner plumbing
# ---------------------------------------------------------------------------


def test_fused_and_draft_program_words():
    cfg = get_reduced("qwen2-0.5b")
    shape = ShapeConfig("serve", seq_len=32, global_batch=2, kind="decode")
    prog = compile_program(cfg, shape, MESH1, fused_decode=True,
                           speculative=True)
    assert {e["phase"] for e in prog.ibuffer_entries()} \
        == {"PREFILL", "DECODE", "DRAFT"}
    w = prog.pe_word("attn_qkv")
    assert w.kernel_for(Phase.DECODE) == "decode_fused"
    assert w.kernel_for(Phase.DRAFT) == "matvec"   # draft model: per-op
    # norms/router stay off the MAC array; mlp joins the fused unit
    assert prog.pe_word("ffn_in").kernel_for(Phase.DECODE) == "decode_fused"
    # default programs are untouched (opt-in flags only)
    d = compile_program(cfg, shape, MESH1)
    assert d.pe_word("attn_qkv").kernel_for(Phase.DECODE) == "matvec"
    assert {e["phase"] for e in d.ibuffer_entries()} == {"PREFILL", "DECODE"}


def test_tuner_fused_winner_reaches_blockspecs():
    """tune_fused_decode -> tune_program(fused_decode=True) ->
    compile_program(tuning=...) -> PEWord.tiling -> fused_block_n: the
    searched shared tile is what the kernel's BlockSpecs see."""
    cfg = get_reduced("qwen2-0.5b")
    ops = extract_ops(cfg)
    fd = tune_fused_decode(ops, tokens=4)
    assert fd is not None and fd["pred_speedup"] > 1.0
    assert set(fd["ops"]) <= set(FUSED_DECODE_OPS)
    tuning = tune_program(ops, MESH1, global_batch=4, seq_len=32,
                          kind="decode", fused_decode=True)
    assert tuning.fused_decode["tile"] == fd["tile"]
    shape = ShapeConfig("serve", seq_len=32, global_batch=4, kind="decode")
    prog = compile_program(cfg, shape, MESH1, fused_decode=True,
                           tuning=tuning.to_dict())
    for name in fd["ops"]:
        w = prog.pe_word(name)
        assert tuple(w.tiling_for(Phase.DECODE)) == tuple(fd["tile"])
        assert fused_block_n(w) == fd["tile"][1]
    # pure-SSM decode has no fused attention unit to search
    assert tune_fused_decode(
        [op for op in ops if op.name not in FUSED_DECODE_OPS],
        tokens=4) is None


def test_fused_block_n_defaults_without_tuning():
    cfg = get_reduced("qwen2-0.5b")
    shape = ShapeConfig("serve", seq_len=32, global_batch=2, kind="decode")
    prog = compile_program(cfg, shape, MESH1, fused_decode=True)
    assert fused_block_n(prog.pe_word("ffn_in")) == 256
    assert fused_block_n(None) == 256


# ---------------------------------------------------------------------------
# Bursty trace
# ---------------------------------------------------------------------------


def test_bursty_trace_shape_and_determinism():
    cfg = get_reduced("qwen2-0.5b")
    a = bursty_trace(12, vocab_size=cfg.vocab_size, prompt_lens=(8, 32),
                     gen_tokens=4, burst_size=4, burst_gap_steps=16, seed=9)
    b = bursty_trace(12, vocab_size=cfg.vocab_size, prompt_lens=(8, 32),
                     gen_tokens=4, burst_size=4, burst_gap_steps=16, seed=9)
    assert [(r.rid, r.prompt, r.arrival_step) for r in a] \
        == [(r.rid, r.prompt, r.arrival_step) for r in b]
    steps = [r.arrival_step for r in a]
    # whole bursts land on one step, gaps separate them
    from collections import Counter
    counts = Counter(steps)
    assert set(counts.values()) == {4} and len(counts) == 3
    assert all(y - x >= 1 for x, y in zip(sorted(counts), sorted(counts)[1:]))
    for r in a:
        assert 8 <= len(r.prompt) <= 32
        assert all(0 <= t < cfg.vocab_size for t in r.prompt)
