"""Checkpoint/restore: roundtrip, async, GC, restart-exact recovery."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint import Checkpointer
from repro.configs import get_reduced
from repro.configs.base import ShapeConfig, TrainConfig
from repro.core import MeshSpec, compile_program
from repro.data import SyntheticLM
from repro.runtime import train_loop as tl
from repro.runtime.fault_tolerance import run_with_recovery

MESH1 = MeshSpec(axis_sizes={"data": 1, "model": 1}, batch_axes=("data",))
SMOKE = ShapeConfig("smoke", seq_len=16, global_batch=2, kind="train")


def _setup(tmpdir):
    cfg = get_reduced("qwen2-0.5b")
    program = compile_program(cfg, SMOKE, MESH1, precision="fp32")
    tc = TrainConfig(optimizer="sgdm", lr=1e-2, precision="fp32",
                     checkpoint_dir=str(tmpdir))
    step_fn, opt = tl.make_train_step(cfg, program, tc, mesh=None)
    state = tl.init_state(cfg, program, tc, jax.random.PRNGKey(0), opt)
    pipe = SyntheticLM(cfg, SMOKE)
    return cfg, tc, jax.jit(step_fn), state, pipe


def test_roundtrip_exact(tmp_path):
    _, _, step_fn, state, pipe = _setup(tmp_path)
    ck = Checkpointer(str(tmp_path))
    state, _ = step_fn(state, pipe.batch_at(0), jax.random.key(0))
    ck.save(1, state, {"arch": "test"}, blocking=True)
    restored, step, meta = ck.restore(jax.device_get(state))
    assert step == 1 and meta["arch"] == "test"
    for a, b in zip(jax.tree.leaves(state), jax.tree.leaves(restored)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_gc_keeps_last_k(tmp_path):
    _, _, _, state, _ = _setup(tmp_path)
    ck = Checkpointer(str(tmp_path), keep=2)
    for s in (1, 2, 3, 4):
        ck.save(s, {"x": jnp.ones((2,)) * s})
    ck.wait()
    assert ck.all_steps() == [3, 4]


def test_restart_exactness(tmp_path):
    """Train 6 steps straight == train 3, restore, train 3 more."""
    _, _, step_fn, state0, pipe = _setup(tmp_path)

    def run(state, start, n):
        for i in range(start, start + n):
            state, _ = step_fn(state, pipe.batch_at(i), jax.random.key(i))
        return state

    ref = run(state0, 0, 6)
    ck = Checkpointer(str(tmp_path))
    mid = run(state0, 0, 3)
    ck.save(3, mid, blocking=True)
    restored, step, _ = ck.restore(jax.device_get(mid))
    restored = jax.tree.map(jnp.asarray, restored)
    final = run(restored, 3, 3)
    for a, b in zip(jax.tree.leaves(ref), jax.tree.leaves(final)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_run_with_recovery_survives_injected_failure(tmp_path):
    cfg, tc, step_fn, state, pipe = _setup(tmp_path)
    ck = Checkpointer(str(tmp_path))
    ck.save(0, state, blocking=True)
    boom = {"armed": True}

    def injector(step):
        if step == 4 and boom["armed"]:
            boom["armed"] = False
            raise RuntimeError("synthetic node failure")

    seen = []
    final = run_with_recovery(
        step_fn=step_fn, state=state, batches=pipe.batch_at, ckpt=ck,
        meta={}, n_steps=6, checkpoint_every=2,
        on_metrics=lambda s, m, dt: seen.append(s),
        fail_injector=injector)
    assert int(jax.device_get(final["step"])) == 6
    assert 4 in seen                      # the failed step was replayed
    assert ck.latest_step() == 6


def test_recovery_reports_failure_and_restores_donated_state(tmp_path,
                                                             capsys):
    """A step that fails after its state was donated still recovers (the
    restore never reads the donated buffers), and the failure is reported
    on stderr with its step."""
    cfg = get_reduced("qwen2-0.5b")
    program = compile_program(cfg, SMOKE, MESH1, precision="fp32")
    tc = TrainConfig(optimizer="sgdm", lr=1e-2, precision="fp32")
    step_fn, opt = tl.make_train_step(cfg, program, tc, mesh=None)
    donating = jax.jit(step_fn, donate_argnums=(0,))
    state = tl.init_state(cfg, program, tc, jax.random.PRNGKey(0), opt)
    pipe = SyntheticLM(cfg, SMOKE)
    boom = {"armed": True}

    def step(state, batch, key):
        out = donating(state, batch, key)          # `state` is gone now
        if int(jax.device_get(out[0]["step"])) == 3 and boom["armed"]:
            boom["armed"] = False
            raise RuntimeError("synthetic failure after the step ran")
        return out

    ck = Checkpointer(str(tmp_path))
    final = run_with_recovery(step_fn=step, state=state,
                              batches=pipe.batch_at, ckpt=ck, meta={},
                              n_steps=4, checkpoint_every=2)
    assert int(jax.device_get(final["step"])) == 4
    err = capsys.readouterr().err
    assert "step 2 failed" in err and "synthetic failure" in err


def test_recovery_with_no_tolerance_raises_the_first_failure(tmp_path,
                                                              capsys):
    _, _, step_fn, state, pipe = _setup(tmp_path)
    ck = Checkpointer(str(tmp_path))
    ck.save(0, state, blocking=True)

    def injector(step):
        if step == 1:
            raise RuntimeError("synthetic node failure")

    with pytest.raises(RuntimeError, match="synthetic node failure"):
        run_with_recovery(step_fn=step_fn, state=state,
                          batches=pipe.batch_at, ckpt=ck, meta={},
                          n_steps=3, max_failures=0, fail_injector=injector)
    assert "step 1 failed (1/0 tolerated)" in capsys.readouterr().err
