"""The program's own instrumentation: request stamps and engine counters,
the profiler spans of ``ServingEngine.step`` and ``run_with_recovery``
(recorded on the CPU), and bench/program_trace.py's reduction of them."""
import glob
import os
import sys
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import pytest
from jax.profiler import ProfileData, TraceAnnotation

from repro.checkpoint import Checkpointer
from repro.runtime.fault_tolerance import run_with_recovery
from repro.configs import get_reduced
from repro.serving import Request, ServingEngine, build_engine
from repro.serving.scheduler import DECODE
from test_serving import build, mixed_prompts

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from bench import program_trace  # noqa: E402

CHUNK = 4


def _engine(n_slots=3, max_len=32, **kw):
    cfg, program, params = build("qwen2-0.5b", n_slots=n_slots,
                                 max_len=max_len)
    return cfg, ServingEngine(cfg, program, params, n_slots=n_slots,
                              max_len=max_len, prefill_chunk=CHUNK, **kw)


def _count_decode_rows(eng):
    """Wrap the engine's masked decode to sum its active rows per call."""
    seen = {"calls": 0, "rows": 0}
    decode = eng._decode

    def counted(params, cache, tok, pos, active):
        seen["calls"] += 1
        seen["rows"] += int(active.sum())
        return decode(params, cache, tok, pos, active)

    eng._decode = counted
    return seen


# ---------------------------------------------------------------------------
# Stamps and counters
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("lens,arrivals", [
    ([9, 4, 13, 7], [0, 0, 0, 0]),          # four requests on three slots
    ([5, 8, 2], [0, 3, 6]),                 # staggered arrivals
])
def test_request_stamps_are_ordered(lens, arrivals):
    cfg, eng = _engine()
    reqs = [Request(rid=f"r{i}", prompt=p, max_new_tokens=3, arrival_step=a)
            for i, (p, a) in enumerate(zip(mixed_prompts(cfg, lens), arrivals))]
    eng.run(reqs)
    assert eng.counters["evicted"] == 0
    assert set(eng.sched.finished) == {r.rid for r in reqs}
    for st in eng.sched.finished.values():
        assert st.t_submit <= st.t_admit <= st.t_first, st.req.rid
    # the first token is stamped no later than its TokenEvent
    first_event = {}
    for e in eng.events:
        first_event.setdefault(e.rid, e.t)
    for rid, st in eng.sched.finished.items():
        assert st.t_first <= first_event[rid]


@pytest.mark.parametrize("lens", [[9, 4, 13, 7], [8, 12, 3]])
def test_forced_and_decode_row_counters(lens):
    """forced_rows is the teacher-forced prompt tails, sum(P mod chunk);
    decode_rows the masked decode's active rows summed over its calls."""
    cfg, eng = _engine()
    seen = _count_decode_rows(eng)
    reqs = [Request(rid=f"r{i}", prompt=p, max_new_tokens=4)
            for i, p in enumerate(mixed_prompts(cfg, lens, seed=1))]
    eng.run(reqs)
    c = eng.counters
    assert c["forced_rows"] == sum(n % CHUNK for n in lens)
    assert c["decode_rows"] == seen["rows"]
    assert c["decode_steps"] == seen["calls"]
    assert c["steps"] == eng.step_count
    assert c["admitted"] == len(reqs) and c["evicted"] == 0
    # one chunk a step at most (max_prefill_chunks_per_step=1)
    assert c["chunk_steps"] == sum(n // CHUNK for n in lens)
    assert c["verifies"] == c["accepted"] == 0


def test_eviction_counter_and_first_admission_stamp():
    """Evictions are counted; a re-admitted request keeps its first
    admission and first token stamps."""
    cfg, eng = _engine(n_slots=2, evict_patience=3)
    reqs = [Request(rid=f"r{i}", prompt=p, max_new_tokens=10)
            for i, p in enumerate(mixed_prompts(cfg, [13, 8, 11, 5], seed=3))]
    eng.run(reqs)
    states = eng.sched.finished.values()
    n_evictions = sum(st.evictions for st in states)
    assert n_evictions > 0
    assert eng.counters["evicted"] == n_evictions
    assert eng.counters["admitted"] == len(reqs) + n_evictions
    for st in states:
        assert st.t_submit <= st.t_admit <= st.t_first


def test_adopted_request_keeps_its_submit_stamp():
    """A request moved to another engine (fleet drain or replica death)
    keeps the time it was first submitted."""
    cfg, a = _engine()
    _, b = _engine()
    req = Request(rid="r0", prompt=mixed_prompts(cfg, [6])[0],
                  max_new_tokens=2)
    a.submit(req)
    a.step()
    (st,) = a.eject_states()
    t_submit, t_admit = st.t_submit, st.t_admit
    b.sched.adopt(st, b.step_count)
    b.run()
    done = b.sched.finished["r0"]
    assert (done.t_submit, done.t_admit) == (t_submit, t_admit)
    assert done.t_first >= t_admit


# ---------------------------------------------------------------------------
# Profiler spans, recorded on the CPU
# ---------------------------------------------------------------------------


def _profile(tmp_path, fn):
    """Run fn() under the profiler inside a host span ``window``; returns
    the window line's events as (name, start, end, stats)."""
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        with TraceAnnotation("window"):
            fn()
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(os.path.join(str(tmp_path), "**", "*.xplane.pb"),
                        recursive=True)
    pd = ProfileData.from_file(path)
    return pd, program_trace.window_line(pd)[1]


def _inside(outer, evs, name):
    _, s, t, _ = outer
    return [e for e in evs if e[0] == name and s <= e[1] and e[2] <= t]


def _host_ms(step, evs):
    waits = [e for e in evs if e[0].endswith(".wait")]
    return (step[2] - step[1]
            - sum(w[2] - w[1] for w in waits
                  if step[1] <= w[1] and w[2] <= step[2])) * 1e-6


def test_engine_step_spans(tmp_path):
    """One step that admits two requests, runs a prompt chunk of one and
    teacher-forces the other's tail on the masked decode."""
    cfg, eng = _engine()
    long_p, short_p = mixed_prompts(cfg, [CHUNK + 1, 2])
    eng.submit(Request(rid="a", prompt=long_p, max_new_tokens=2))
    eng.submit(Request(rid="b", prompt=short_p, max_new_tokens=2))
    pd, evs = _profile(tmp_path, eng.step)

    (step,) = [e for e in evs if e[0] == "serve.step"]
    assert step[3]["step"] == 0
    (sched,) = _inside(step, evs, "serve.schedule")
    (prefill,) = _inside(step, evs, "serve.prefill")
    assert prefill[3]["rid"] == "a" and prefill[3]["pos"] == 0
    (pwait,) = _inside(prefill, evs, "serve.prefill.wait")
    (decode,) = _inside(step, evs, "serve.decode")
    (dwait,) = _inside(decode, evs, "serve.decode.wait")
    (commit,) = _inside(step, evs, "serve.commit")
    assert sched[2] <= prefill[1] and prefill[2] <= decode[1] \
        and decode[2] <= commit[1]
    assert not [e for e in evs if e[0] == "serve.spec"]
    c = eng.counters
    assert (c["steps"], c["admitted"], c["chunk_steps"], c["decode_steps"],
            c["decode_rows"], c["forced_rows"]) == (1, 2, 1, 1, 1, 1)

    got = program_trace.step_host_ms(pd, "serve.step")
    assert got == pytest.approx([_host_ms(step, evs)], abs=1e-9)
    assert got[0] == pytest.approx(
        (step[2] - step[1] - (pwait[2] - pwait[1]) - (dwait[2] - dwait[1]))
        * 1e-6, abs=1e-9)


def test_speculative_round_span(tmp_path):
    """A DECODE-phase row of a speculative engine takes one draft/verify
    round, under one ``serve.spec`` span."""
    cfg = get_reduced("qwen2-0.5b")
    eng = build_engine(cfg, n_slots=2, max_len=24, prefill_chunk=CHUNK,
                       speculative=3)
    eng.submit(Request(rid="a", prompt=mixed_prompts(cfg, [3])[0],
                       max_new_tokens=6))
    while not any(st.phase == DECODE for st in eng.sched.active.values()):
        eng.step()
    verifies = eng.counters["verifies"]
    _, evs = _profile(tmp_path, eng.step)
    (step,) = [e for e in evs if e[0] == "serve.step"]
    (spec,) = _inside(step, evs, "serve.spec")
    assert spec[3]["rid"] == "a"
    assert eng.counters["verifies"] == verifies + 1
    assert not _inside(step, evs, "serve.decode")


def test_train_loop_step_spans(tmp_path):
    """Two steps of run_with_recovery, the second failing once and
    restored from the checkpoint the first wrote."""
    @jax.jit
    def step_fn(state, batch, key):
        new = {"step": state["step"] + 1, "w": state["w"] + batch}
        return new, {"loss": jnp.sum(new["w"])}

    ck = Checkpointer(str(tmp_path / "ckpt"))
    state = {"step": jnp.zeros((), jnp.int32), "w": jnp.zeros((4,))}
    armed = [True]

    def injector(step):
        if step == 1 and armed[0]:
            armed[0] = False
            raise RuntimeError("synthetic failure")

    def go():
        run_with_recovery(step_fn=step_fn, state=state,
                          batches=lambda i: jnp.full((4,), float(i)),
                          ckpt=ck, meta={}, n_steps=2, checkpoint_every=1,
                          fail_injector=injector)

    pd, evs = _profile(tmp_path / "trace", go)
    steps = [e for e in evs if e[0] == "train_loop.step"]
    assert [e[3]["step_num"] for e in steps] == [0, 1, 1]
    for full in (steps[0], steps[2]):
        parts = [_inside(full, evs, n) for n in (
            "train_loop.batch", "train_loop.dispatch", "train_loop.wait",
            "train_loop.checkpoint")]
        assert [len(p) for p in parts] == [1, 1, 1, 1]
        ends = [(p[0][1], p[0][2]) for p in parts]
        assert all(a[1] <= b[0] for a, b in zip(ends, ends[1:]))
    assert not _inside(steps[1], evs, "train_loop.batch")   # failed first
    (restore,) = [e for e in evs if e[0] == "train_loop.restore"]
    assert steps[1][2] <= restore[1] <= restore[2] <= steps[2][1]

    got = program_trace.step_host_ms(pd, "train_loop.step")
    assert got == pytest.approx([_host_ms(s, evs) for s in steps], abs=1e-9)


# ---------------------------------------------------------------------------
# The reduction, on hand-made events and states
# ---------------------------------------------------------------------------


def _pd(*lines):
    def line(evs):
        return SimpleNamespace(name="python", events=[
            SimpleNamespace(name=n, start_ns=s, duration_ns=t - s, stats=[])
            for n, s, t in evs])
    return SimpleNamespace(planes=[
        SimpleNamespace(name="/device:TPU:0", lines=[]),
        SimpleNamespace(name="/host:CPU", lines=[line(e) for e in lines])])


def test_step_host_ms_by_hand():
    other = [("serve.step", 0, 10_000_000)]            # another thread
    main = [("window", 100, 9_000_100),
            ("serve.step", 0, 1_000_000),               # starts before window
            ("serve.step", 1_000_000, 4_000_000),
            ("serve.prefill.wait", 1_500_000, 2_000_000),
            ("serve.decode.wait", 3_000_000, 3_250_000),
            ("serve.step", 5_000_000, 6_000_000),       # no wait inside
            ("serve.decode.wait", 6_500_000, 6_600_000),  # outside a step
            ("serve.step", 8_000_000, 9_500_000)]       # ends after window
    got = program_trace.step_host_ms(_pd(other, main), "serve.step")
    assert got == pytest.approx([3.0 - 0.5 - 0.25, 1.0])
    with pytest.raises(RuntimeError, match="window"):
        program_trace.step_host_ms(_pd(other), "serve.step")


def test_stamps_ms_by_hand():
    def st(submit, admit=None, first=None):
        return SimpleNamespace(t_submit=submit, t_admit=admit, t_first=first)

    states = [st(1.0, 1.5, 2.0),        # admitted, first token
              st(2.0, 2.25),            # admitted, no first token yet
              st(3.0),                  # still queued at the close
              st(4.0, 11.0, 12.0)]      # admitted after the close
    wait, first = program_trace.stamps_ms(states, close=10.0)
    assert wait == pytest.approx([500.0, 250.0, 7000.0, 6000.0])
    assert first == pytest.approx([500.0, 7750.0])
    assert program_trace.counter_delta(
        {"steps": 3, "decode_rows": 10}, {"steps": 5, "decode_rows": 17}) \
        == {"steps": 2, "decode_rows": 7}
