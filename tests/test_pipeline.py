"""Pipelines: the data pipeline (determinism, host sharding, prefetch,
modality stubs) and the inter-module pipeline parallelism stack
(repro/pipeline: partitioner, 1F1B/GPipe schedules, runner parity)."""
import os
import subprocess
import sys

import numpy as np
import pytest

from repro.configs import get_config, get_reduced
from repro.configs.base import ShapeConfig
from repro.data import Prefetcher, SyntheticLM


SMOKE = ShapeConfig("smoke", seq_len=32, global_batch=4, kind="train")


def test_stateless_determinism():
    pipe = SyntheticLM(get_reduced("qwen2-0.5b"), SMOKE)
    a = pipe.batch_at(7)
    b = pipe.batch_at(7)
    np.testing.assert_array_equal(a["tokens"], b["tokens"])
    c = pipe.batch_at(8)
    assert not np.array_equal(a["tokens"], c["tokens"])


def test_tokens_in_vocab_and_shifted_labels():
    cfg = get_reduced("olmo-1b")
    pipe = SyntheticLM(cfg, SMOKE)
    b = pipe.batch_at(0)
    assert b["tokens"].min() >= 0 and b["tokens"].max() < cfg.vocab_size
    assert b["tokens"].shape == b["labels"].shape == (4, 32)


def test_host_slices_differ():
    pipe = SyntheticLM(get_reduced("qwen2-0.5b"), SMOKE)
    h0 = pipe.batch_at(0, host_id=0, n_hosts=2)
    h1 = pipe.batch_at(0, host_id=1, n_hosts=2)
    assert h0["tokens"].shape[0] == 2
    assert not np.array_equal(h0["tokens"], h1["tokens"])


def test_modality_stubs():
    vlm = get_reduced("llava-next-mistral-7b")
    b = SyntheticLM(vlm, SMOKE).batch_at(0)
    assert b["vision_embeds"].shape == (4, vlm.n_vision_tokens, vlm.d_model)
    assert b["tokens"].shape[1] == 32 - vlm.n_vision_tokens
    aud = get_reduced("whisper-medium")
    b = SyntheticLM(aud, SMOKE).batch_at(0)
    assert b["audio_embeds"].shape == (4, aud.enc_seq, aud.d_model)


def test_decode_shape_batches():
    pipe = SyntheticLM(get_reduced("rwkv6-1.6b"),
                       ShapeConfig("d", 64, 2, "decode"))
    b = pipe.batch_at(0)
    assert b["tokens"].shape == (2, 1) and b["pos"].shape == (2,)


def test_prefetcher_in_order():
    pipe = SyntheticLM(get_reduced("qwen2-0.5b"), SMOKE)
    pf = Prefetcher(pipe, start_step=0)
    try:
        for want in range(4):
            step, batch = pf.next()
            assert step == want
            np.testing.assert_array_equal(batch["tokens"],
                                          pipe.batch_at(want)["tokens"])
    finally:
        pf.close()


# ---------------------------------------------------------------------------
# Inter-module pipeline parallelism (repro/pipeline)
# ---------------------------------------------------------------------------


PP = ShapeConfig("pp", seq_len=16, global_batch=4, kind="train")


def _plan(cfg, num_stages, **kw):
    from repro.pipeline import partition_model
    return partition_model(cfg, num_stages, **kw)


def test_partition_single_stage_owns_everything():
    cfg = get_config("qwen2-0.5b")
    p = _plan(cfg, 1)
    assert len(p.stages) == 1
    s = p.stages[0]
    assert (s.start_layer, s.end_layer) == (0, cfg.n_layers)
    assert s.has_embed and s.has_head
    assert p.imbalance == 1.0


def test_partition_contiguous_cover_and_balance():
    cfg = get_config("qwen2-0.5b")          # 24 layers, heavy tied head
    for S in (2, 3, 4, 6):
        p = _plan(cfg, S, global_batch=32, seq_len=1024)
        # contiguous, covering, monotone
        assert p.stages[0].start_layer == 0
        assert p.stages[-1].end_layer == cfg.n_layers
        for a, b in zip(p.stages, p.stages[1:]):
            assert a.end_layer == b.start_layer
            assert a.end_group == b.start_group
        assert all(s.n_layers >= 1 for s in p.stages)
        # balanced by COST, not layer count (the tied head is worth ~9
        # layers here and pulls the last boundary hard left — that
        # asymmetry is the point; it also floors the imbalance once the
        # indivisible head alone exceeds the ideal stage share)
        assert 1.0 <= p.imbalance < 2.0
        # embed on stage 0 only, head on the last only
        assert [s.has_embed for s in p.stages] == [True] + [False] * (S - 1)
        assert [s.has_head for s in p.stages] == [False] * (S - 1) + [True]


def test_partition_uniform_net_splits_evenly():
    # negligible head/embed (tiny vocab): stages get near-equal groups
    from repro.configs.base import AttentionConfig, ModelConfig
    cfg = ModelConfig(name="uniform", family="dense", n_layers=12,
                      d_model=256, d_ff=1024, vocab_size=64,
                      attention=AttentionConfig(n_heads=4, n_kv_heads=4,
                                                head_dim=64))
    for S in (2, 3, 4, 6):
        p = _plan(cfg, S)
        sizes = [s.end_group - s.start_group for s in p.stages]
        assert max(sizes) - min(sizes) <= 1, sizes
        assert p.imbalance < 1.1


def test_partition_more_stages_than_groups_raises():
    cfg = get_reduced("qwen2-0.5b")         # 2 scan groups
    with pytest.raises(ValueError, match="stages > .* scan groups"):
        _plan(cfg, 5)


def test_partition_respects_pattern_period():
    cfg = get_config("jamba-v0.1-52b")      # 8-layer pattern period
    p = _plan(cfg, 4)
    assert p.unit_layers == 8
    for s in p.stages:
        assert s.start_layer % 8 == 0 and s.end_layer % 8 == 0


def test_partition_imbalanced_net_biases_boundary():
    # layers get uniform cost but the tied head (priced at all three
    # train phases + the V x d table read) lands on the LAST stage: the
    # greedy must give that stage strictly fewer layer groups than the
    # first (a naive equal split ignores the edges)
    cfg = get_config("qwen2-0.5b")
    p = _plan(cfg, 4, global_batch=64, seq_len=4096)
    first, last = p.stages[0], p.stages[-1]
    assert (last.end_group - last.start_group) < \
        (first.end_group - first.start_group)
    # the head here outweighs an ideal stage share, so the greedy must
    # shrink the head stage to the minimum — a single layer group
    assert last.end_group - last.start_group == 1


def test_schedule_invariants_and_bubble():
    from repro.pipeline import (build_schedule, ideal_bubble, validate)
    for kind in ("1f1b", "gpipe"):
        for S, M in ((1, 1), (2, 2), (3, 5), (4, 8), (4, 1)):
            sched = build_schedule(kind, S, M)
            validate(sched)
            assert sched.bubble_fraction() == pytest.approx(
                ideal_bubble(S, M))


def test_1f1b_bounds_in_flight_activations():
    from repro.pipeline import build_schedule
    S, M = 4, 8
    fb = build_schedule("1f1b", S, M)
    gp = build_schedule("gpipe", S, M)
    for s in range(S):
        assert fb.peak_in_flight(s) == min(M, S - s)
        assert gp.peak_in_flight(s) == M
    assert fb.makespan == gp.makespan        # same bubble, less memory


def test_1f1b_event_order():
    from repro.core.phases import Phase
    from repro.pipeline import build_schedule
    sched = build_schedule("1f1b", 3, 4)
    t_of = {(e.phase, e.stage, e.microbatch): e.t for e in sched.events
            if e.phase != Phase.UP}
    # forward wavefront moves right, backward wavefront moves left
    for m in range(4):
        assert t_of[(Phase.FF, 0, m)] < t_of[(Phase.FF, 1, m)] \
            < t_of[(Phase.FF, 2, m)]
        assert t_of[(Phase.BP, 2, m)] < t_of[(Phase.BP, 1, m)] \
            < t_of[(Phase.BP, 0, m)]
    # BP completes in microbatch order on every stage (the runner's f32
    # accumulation order depends on this)
    for s in range(3):
        bps = [t_of[(Phase.BP, s, m)] for m in range(4)]
        assert bps == sorted(bps)
    # UP fires once per stage, strictly after that stage's last BP
    ups = [e for e in sched.events if e.phase == Phase.UP]
    assert len(ups) == 3
    for e in ups:
        assert e.t > max(t_of[(Phase.BP, e.stage, m)] for m in range(4))


def test_stage_programs_scope_the_ibuffer():
    from repro.core import MeshSpec
    from repro.core.program import compile_stage_programs
    cfg = get_config("olmo-1b")
    p = _plan(cfg, 2)
    ms = MeshSpec(axis_sizes={"data": 1, "model": 1})
    progs = compile_stage_programs(cfg, PP, ms, p.layer_bounds)
    assert len(progs) == 2
    assert "embed" in progs[0].plan.ops
    assert "lm_head" not in progs[0].plan.ops
    assert "lm_head" in progs[1].plan.ops
    assert "embed" not in progs[1].plan.ops
    # per-stage layer scoping: each stage's attn op covers only its layers
    n0 = progs[0].op_spec("attn_qkv").n_layers
    n1 = progs[1].op_spec("attn_qkv").n_layers
    assert n0 + n1 == cfg.n_layers
    # a tied model keeps the embed spec alive on the head stage
    tied = get_config("qwen2-0.5b")
    tprogs = compile_stage_programs(tied, PP, ms, _plan(tied, 2).layer_bounds)
    assert "embed" in tprogs[1].plan.ops


def _pipeline_vs_single(arch: str, num_stages: int, microbatch: int,
                        steps: int = 3, schedule: str = "1f1b"):
    import jax
    import jax.numpy as jnp
    from repro.configs.base import TrainConfig
    from repro.core import MeshSpec, compile_program
    from repro.core.program import compile_stage_programs
    from repro.engine import PEContext
    from repro.models import transformer as tfm
    from repro.pipeline import make_pipeline_train_step, partition_model
    from repro.runtime import train_loop as tl

    cfg = get_reduced(arch)
    ms = MeshSpec(axis_sizes={"data": 1, "model": 1})
    tc = TrainConfig(optimizer="adamw", lr=2e-3, microbatch=microbatch)
    prog = compile_program(cfg, PP, ms, microbatch=max(1, microbatch))
    step1, opt1 = tl.make_train_step(cfg, prog, tc, None)
    pplan = partition_model(cfg, num_stages,
                            global_batch=PP.global_batch, seq_len=PP.seq_len)
    sprogs = compile_stage_programs(cfg, PP, ms, pplan.layer_bounds,
                                    microbatch=max(1, microbatch))
    step2, opt2 = make_pipeline_train_step(cfg, sprogs, pplan, tc, None,
                                           schedule=schedule)

    # the single-module gradient computation, exactly as make_train_step
    # accumulates it (microbatch scan, f32 accumulation in m order)
    policy = prog.policy
    sh = PEContext(None, prog, backend="reference")

    def mono_grads(params, batch):
        def loss(p, mb):
            return tfm.loss_fn(cfg, p, mb, sh, compute_dtype=policy.ff_dtype,
                               remat=tc.remat)
        nm = max(1, microbatch)
        if nm == 1:
            l, g = jax.value_and_grad(loss)(params, batch)
            return l, jax.tree.map(lambda x: x.astype(jnp.float32), g)

        def one_micro(carry, mb):
            l, g = carry
            li, gi = jax.value_and_grad(loss)(params, mb)
            gi = jax.tree.map(lambda a, b: a + b.astype(jnp.float32), g, gi)
            return (l + li, gi), None

        micro = tl.split_microbatches(batch, nm)
        g0 = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params)
        (l, grads), _ = jax.lax.scan(one_micro, (jnp.zeros(()), g0), micro)
        return l / nm, jax.tree.map(lambda g: g / nm, grads)

    jg1 = jax.jit(mono_grads)
    jg2 = jax.jit(step2.loss_and_grads)
    s1 = tl.init_state(cfg, prog, tc, jax.random.PRNGKey(0), opt1)
    s2 = tl.init_state(cfg, prog, tc, jax.random.PRNGKey(0), opt2)
    j1, j2 = jax.jit(step1), jax.jit(step2)
    pipe = SyntheticLM(cfg, PP)
    losses = []
    for i in range(steps):
        b = pipe.batch_at(i)
        k = jax.random.key(i)
        # the pipeline's composed per-stage vjps == the monolithic
        # backward, bit for bit, on each path's own evolving state
        lg1, g1 = jg1(s1["params"], b)
        lg2, g2 = jg2(s2["params"], b, k)
        assert float(lg1) == float(lg2), f"step {i} grad-pass loss"
        geq = jax.tree.map(lambda a, b: bool(jnp.array_equal(a, b)), g1, g2)
        bad = [p for p, ok in
               jax.tree_util.tree_flatten_with_path(geq)[0] if not ok]
        assert not bad, f"step {i} grads diverged: {bad}"
        s1, m1 = j1(s1, b, k)
        s2, m2 = j2(s2, b, k)
        losses.append((float(m1["loss"]), float(m2["loss"])))
        assert float(m1["grad_norm"]) == float(m2["grad_norm"]), (i, m1, m2)
    for i, (l1, l2) in enumerate(losses):
        assert l1 == l2, f"step {i}: {l1} != {l2}"
    # After the last update, params match to the final bit for most leaves;
    # the identical optimizer math compiled inside two DIFFERENT programs
    # may round a rare tie differently (XLA fusion/FMA), so allow ulp-level
    # jitter on a handful of elements rather than chase the compiler.
    p1 = jnp.concatenate([x.astype(jnp.float32).ravel()
                          for x in jax.tree.leaves(s1["params"])])
    p2 = jnp.concatenate([x.astype(jnp.float32).ravel()
                          for x in jax.tree.leaves(s2["params"])])
    ndiff = int(jnp.sum(p1 != p2))
    assert ndiff <= max(8, p1.size // 10_000), f"{ndiff}/{p1.size} differ"
    np.testing.assert_allclose(np.asarray(p1), np.asarray(p2),
                               rtol=0.02, atol=1e-6)
    assert losses[-1][0] < losses[0][0] + 0.5   # sane training signal


def test_pipeline_loss_parity_untied():
    # olmo: untied head, nonparametric LN — 2 stages x 2 microbatches,
    # bit-for-bit loss/grad-norm/params over 3 steps incl. SR writeback
    _pipeline_vs_single("olmo-1b", num_stages=2, microbatch=2)


def test_pipeline_loss_parity_tied_embeddings():
    # qwen2 ties the head to the embedding: its dW meets contributions
    # from BOTH edge stages (one commutative bf16 add — still exact)
    _pipeline_vs_single("qwen2-0.5b", num_stages=2, microbatch=2)


def test_pipeline_parity_single_microbatch_gpipe():
    # M=1 degenerates to a sequential handoff chain; gpipe schedule
    _pipeline_vs_single("olmo-1b", num_stages=2, microbatch=0,
                        schedule="gpipe")


@pytest.mark.slow
def test_pipeline_parity_moe_three_stages():
    # router aux loss crosses stage boundaries (carried with the
    # activation, summed into the last stage's loss)
    _pipeline_vs_single("granite-moe-1b-a400m", num_stages=2, microbatch=4)


_PPERMUTE_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = ("--xla_force_host_platform_device_count=2 "
                           "--xla_allow_excess_precision=false")
import jax
import jax.numpy as jnp
import numpy as np
from repro.configs import get_reduced
from repro.configs.base import ShapeConfig, TrainConfig
from repro.core import MeshSpec, compile_program
from repro.core.program import compile_stage_programs
from repro.data import SyntheticLM
from repro.launch.mesh import auto_mesh, pipeline_mesh_spec
from repro.pipeline import make_pipeline_train_step, partition_model
from repro.runtime import train_loop as tl

cfg = get_reduced("olmo-1b")
shape = ShapeConfig("pp", seq_len=16, global_batch=4, kind="train")
mesh = auto_mesh((2, 1, 1), ("stage", "data", "model"))
sspec = pipeline_mesh_spec(2)
assert sspec.pp == 2
tc = TrainConfig(optimizer="adamw", lr=2e-3, microbatch=2)
pplan = partition_model(cfg, 2, global_batch=4, seq_len=16)
sprogs = compile_stage_programs(cfg, shape, sspec, pplan.layer_bounds,
                                microbatch=2)
pstep, opt = make_pipeline_train_step(cfg, sprogs, pplan, tc, mesh)
ms = MeshSpec(axis_sizes={"data": 1, "model": 1})
prog = compile_program(cfg, shape, ms, microbatch=2)
vstep, _ = tl.make_train_step(cfg, prog, tc, None)
sp = tl.init_state(cfg, prog, tc, jax.random.PRNGKey(0), opt)
sv = tl.init_state(cfg, prog, tc, jax.random.PRNGKey(0), opt)
jp, jv = jax.jit(pstep), jax.jit(vstep)
pipe = SyntheticLM(cfg, shape)
for i in range(3):
    b = pipe.batch_at(i)
    k = jax.random.key(i)
    sp, mp = jp(sp, b, k)
    sv, mv = jv(sv, b, k)
    assert float(mp["loss"]) == float(mv["loss"]), (i, mp, mv)
print("PPERMUTE_OK", float(mp["loss"]))
"""


@pytest.mark.slow
def test_pipeline_ppermute_handoff_subprocess():
    """Real ("stage", "data", "model") mesh: boundary tensors ride
    jax.lax.ppermute and still bit-match the single-module loop."""
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    env.pop("XLA_FLAGS", None)
    r = subprocess.run([sys.executable, "-c", _PPERMUTE_SCRIPT], env=env,
                       capture_output=True, text=True, timeout=900)
    assert r.returncode == 0, r.stderr[-3000:]
    assert "PPERMUTE_OK" in r.stdout
