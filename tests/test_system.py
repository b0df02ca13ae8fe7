"""End-to-end behaviour tests for the paper's system."""
import jax
import jax.numpy as jnp

from repro.configs import get_reduced
from repro.configs.base import ShapeConfig, TrainConfig
from repro.core import MeshSpec, compile_program
from repro.data import SyntheticLM
from repro.runtime import train_loop as tl

MESH1 = MeshSpec(axis_sizes={"data": 1, "model": 1}, batch_axes=("data",))


def test_end_to_end_training_reduces_loss():
    """The whole stack: program -> pipeline -> train step -> SR writeback."""
    cfg = get_reduced("qwen2-0.5b")
    shape = ShapeConfig("e2e", seq_len=64, global_batch=4, kind="train")
    program = compile_program(cfg, shape, MESH1)
    tc = TrainConfig(optimizer="adamw", lr=2e-3)
    step_fn, opt = tl.make_train_step(cfg, program, tc, mesh=None)
    jstep = jax.jit(step_fn)
    state = tl.init_state(cfg, program, tc, jax.random.PRNGKey(0), opt)
    pipe = SyntheticLM(cfg, shape)
    losses = []
    for i in range(25):
        state, m = jstep(state, pipe.batch_at(i), jax.random.key(i))
        losses.append(float(m["loss"]))
    first, last = sum(losses[:5]) / 5, sum(losses[-5:]) / 5
    assert last < first - 0.05, (first, last)


def test_program_phases_and_precision_are_wired():
    """The iBuffer carries the paper's FF/BP/UP precision ladder."""
    cfg = get_reduced("olmo-1b")
    shape = ShapeConfig("e2e", seq_len=32, global_batch=2, kind="train")
    program = compile_program(cfg, shape, MESH1, precision="paper_sr_bf16")
    entries = program.ibuffer_entries()
    phases = {e["phase"] for e in entries}
    assert phases == {"FF", "BP", "UP"}
    ff = [e for e in entries if e["phase"] == "FF"]
    up = [e for e in entries if e["phase"] == "UP"]
    assert all(e["dtype"] == "bfloat16" for e in ff)
    assert all(e["rounding"] == "sr" for e in up)
    assert program.ibuffer_size_bytes() < 16 * 1024     # paper: 16 KB iBuffer


def test_serving_cache_consistency():
    """Prefill-then-decode == decoding the whole prompt token by token."""
    import numpy as np
    from repro.models import transformer as tfm
    from repro.models.layers import Sharder
    cfg = get_reduced("jamba-v0.1-52b")
    params = tfm.init(jax.random.PRNGKey(0), cfg)
    B, P = 1, 9
    prompt = jax.random.randint(jax.random.PRNGKey(1), (B, P), 0,
                                cfg.vocab_size)
    sh = Sharder()
    full, _ = tfm.forward(cfg, params, prompt, sh)
    cache = tfm.init_cache(cfg, B, 32)
    pos = jnp.zeros((B,), jnp.int32)
    for t in range(P):
        logits, cache = tfm.decode_step(cfg, params, prompt[:, t:t + 1],
                                        cache, pos, sh)
        pos = pos + 1
    # bf16 forward: a tail of logits can differ by ~1 bf16 ulp through the
    # two computation orders; require 98% close + matching argmax
    close = np.isclose(np.asarray(logits[:, 0]), np.asarray(full[:, -1]),
                       rtol=5e-2, atol=5e-2)
    assert close.mean() > 0.98, close.mean()
    assert (np.argmax(np.asarray(logits[:, 0]), -1)
            == np.argmax(np.asarray(full[:, -1]), -1)).all()


def test_compile_cache_dir(monkeypatch):
    """JAX_COMPILATION_CACHE_DIR wins untouched; otherwise the cache sits
    at the checkout's fixed .jax_cache (found from the package path)."""
    import os

    from repro.launch import compile_cache as cc
    calls = []
    monkeypatch.setattr(jax.config, "update", lambda *a: calls.append(a))
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere")
    assert cc.enable_compile_cache() == "/elsewhere" and calls == []
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    path = cc.enable_compile_cache()
    assert calls == [("jax_compilation_cache_dir", path)]
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    assert path == os.path.join(root, ".jax_cache")
