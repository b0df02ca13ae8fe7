"""The plain reference against the program's own `reference` backend, at
reduced sizes on the CPU, for both configurations."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import run, weights
from bench.reference import dense
from bench.reference.train import Trainer, adamw_leaf
from bench.system import program_config
from bench.tests import small

OPT = small.TRAIN["optimizer"]
FAM = run.load_family("dense")


def program_loss(cfg, params, tokens, labels):
    from repro.configs.base import ShapeConfig
    from repro.core import MeshSpec, compile_program
    from repro.engine import PEContext
    from repro.models import transformer as tfm
    shape = ShapeConfig("t", seq_len=tokens.shape[1], global_batch=tokens.shape[0],
                        kind="train")
    program = compile_program(cfg, shape, MeshSpec(axis_sizes={"data": 1, "model": 1}),
                              precision="fp32")
    sh = PEContext(None, program, backend="reference")
    return tfm.loss_fn(cfg, params, {"tokens": tokens, "labels": labels}, sh,
                       compute_dtype=jnp.float32)


def batch(m, key, b=2, t=24):
    ids = jax.random.randint(key, (b, t + 1), 0, m["vocab_size"])
    return ids[:, :-1], ids[:, 1:]


@pytest.mark.parametrize("arch", ["qwen2-0.5b", "olmo-1b"])
def test_loss_and_gradients_match_program(arch):
    c = small.config(arch)
    m = c["model"]
    cfg = program_config(FAM, c)
    p = weights.make_params(FAM, m, weights.root_key(3, "weights"), jnp.float32)
    tok, lab = batch(m, jax.random.PRNGKey(1))
    lp, gp = jax.value_and_grad(program_loss, argnums=1)(cfg, p, tok, lab)
    ref = FAM.to_reference(m, p)
    lr, gr = jax.value_and_grad(lambda q: dense.loss(m, q, tok, lab))(ref)
    np.testing.assert_allclose(float(lr), float(lp), rtol=1e-5)
    want = weights.program_norms(FAM, m, weights.reference_sumsq(gr))
    got = {k: float(jnp.linalg.norm(v)) for k, v in weights.flatten(gp).items()}
    assert set(got) == set(want)
    for k in want:
        np.testing.assert_allclose(got[k], want[k], rtol=1e-4, atol=1e-7, err_msg=k)


@pytest.mark.parametrize("on_host", [False, True])
def test_trainer_matches_whole_model_adamw(on_host):
    """Layer-by-layer gradients and updates equal jax.grad of the whole
    reference loss followed by AdamW."""
    c = small.config("qwen2-0.5b")
    m = c["model"]
    key = weights.root_key(5, "weights")
    make = lambda: FAM.to_reference(m, weights.make_params(FAM, m, key))
    direct = make()
    tr = Trainer(FAM, m, OPT, make(), row_block=1, moments_on_host=on_host)
    mom = jax.tree.map(lambda a: (jnp.zeros_like(a), jnp.zeros_like(a)), direct)
    for s in range(2):
        tok, lab = batch(m, jax.random.PRNGKey(10 + s), b=3)
        loss, g = jax.value_and_grad(lambda q: dense.loss(m, q, tok, lab))(direct)
        got, sumsq, _ = tr.step(tok, lab)
        np.testing.assert_allclose(got, float(loss), rtol=1e-5)
        want = weights.reference_sumsq(g)
        for k in want:
            np.testing.assert_allclose(sumsq[k], float(want[k]), rtol=1e-4, err_msg=k)
        new = jax.tree.map(lambda p, gg, mv: adamw_leaf(
            p, gg, mv[0], mv[1], float(s + 1), **{k: OPT[k] for k in (
                "lr", "weight_decay", "b1", "b2", "eps")}),
            direct, g, mom, is_leaf=lambda x: isinstance(x, tuple))
        direct = jax.tree.map(lambda t: t[0], new, is_leaf=lambda x: isinstance(x, tuple))
        mom = jax.tree.map(lambda t: (t[1], t[2]), new, is_leaf=lambda x: isinstance(x, tuple))
    for a, b in zip(jax.tree.leaves(direct), jax.tree.leaves(tr.params)):
        # Adam divides by |g|: elements with near-zero gradients may move by a
        # fraction of lr differently when the sums run in another order
        np.testing.assert_allclose(np.asarray(b), np.asarray(a), rtol=1e-4, atol=2e-5)


def test_control_quantizes():
    x = jnp.linspace(-3.0, 3.0, 101)
    q = dense._q(x, "fp8")
    assert float(jnp.max(jnp.abs(q - x))) > 0
    assert float(jnp.max(jnp.abs(q - x) / jnp.maximum(jnp.abs(x), 1e-3))) < 0.07
