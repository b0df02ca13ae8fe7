"""Model families are files: the harness takes everything model-specific
from bench/families/<family>.py, so a family copied under a new name runs
the small cells with the dense family's readings, with no file edited."""
import os
import re
import shutil

import jax
import numpy as np
import pytest

from bench import compare, run, weights
from bench.reference import dense
from bench.reference.train import Trainer
from bench.tests import small

SEED = 2**33 + 17
BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _copy_family(tmp_path, name: str, extra: str = "") -> str:
    shutil.copy(os.path.join(run.FAMILIES, "dense.py"), tmp_path / f"{name}.py")
    with open(tmp_path / f"{name}.py", "a") as f:
        f.write(extra)
    return str(tmp_path)


def _run(kind, traffic, limits, family, seconds):
    f = small.files("qwen2-0.5b", traffic, limits)
    f[1]["model"]["family"] = family
    return run.run_cell(small.bench(f[0]["name"], kind), f[0]["name"], SEED,
                        seconds, False, require_tpu=False, files=f)


def _kept(monkeypatch, name: str) -> list:
    """Record what compare.<name> returns in the kinds."""
    seen, orig = [], getattr(compare, name)

    def keep(*a):
        seen.append((a, orig(*a)))
        return seen[-1][1]

    monkeypatch.setattr(compare, name, keep)
    return seen


def test_copied_family_trains_as_dense(tmp_path, monkeypatch):
    seen = _kept(monkeypatch, "training_readings")
    want = _run("train", small.TRAIN, small.TRAIN_LIMITS, "dense", 1.5)
    monkeypatch.setattr(run, "FAMILIES", _copy_family(tmp_path, "dense_copy"))
    got = _run("train", small.TRAIN, small.TRAIN_LIMITS, "dense_copy", 1.5)
    assert got["correct"] and got["checks"] == want["checks"]
    assert seen[1][1] == seen[0][1]


def test_copied_family_serves_as_dense(tmp_path, monkeypatch):
    fam = run.load_family("dense")
    seen = _kept(monkeypatch, "serve_readings")
    monkeypatch.setattr(run, "FAMILIES", _copy_family(tmp_path, "dense_copy"))
    got = _run("serve", small.SERVE, small.SERVE_LIMITS, "dense_copy", 3.0)
    assert got["correct"], got["checks"]
    [((_, sample), readings)] = seen
    m = small.config("qwen2-0.5b")["model"]
    want = compare.ServeReference(fam, m, weights.root_key(SEED, "weights"))
    assert readings == compare.serve_readings(want, sample)


def test_unknown_family_is_refused():
    path = os.path.join(run.FAMILIES, "no_such_family.py")
    with pytest.raises(run.Refused, match=re.escape(path)):
        _run("train", small.TRAIN, small.TRAIN_LIMITS, "no_such_family", 1.0)


AUX = '''

def aux_loss(m, i, lp, x, quant=None):
    """A term of the loss over the whole batch that depends on the layer's
    index, parameters and inputs."""
    z = jnp.tanh(jnp.einsum("btd,de->bte", x, lp["wq"][:, :4]))
    return 1e-2 * (i + 1) * jnp.mean(z * z) * jnp.mean(x[..., 0])
'''


def test_trainer_adds_a_family_loss_term(tmp_path):
    """A family's aux_loss enters the trainer's loss and gradients as it
    enters jax.grad of the whole model's loss, with rows in blocks."""
    fam = run.load_module(os.path.join(_copy_family(tmp_path, "aux", AUX),
                                       "aux.py"), "bench_family_aux")
    m = small.config("qwen2-0.5b")["model"]
    key = weights.root_key(5, "weights")
    params = fam.to_reference(m, weights.make_params(fam, m, key))
    ids = jax.random.randint(jax.random.PRNGKey(3), (3, 17), 0, m["vocab_size"])
    tok, lab = ids[:, :-1], ids[:, 1:]

    def loss(q):
        x, aux = q["embed"][tok], 0.0
        for i, lp in enumerate(q["layers"]):
            aux = aux + fam.aux_loss(m, i, lp, x)
            x = dense.layer(m, lp, x)
        return dense.nll_sum(m, dense.head_params(q), x, lab) / tok.size + aux

    want, g = jax.value_and_grad(loss)(params)
    assert float(want - dense.loss(m, params, tok, lab)) != 0.0
    tr = Trainer(fam, m, small.TRAIN["optimizer"], params, row_block=1)
    got, sumsq, _ = tr.step(tok, lab)
    np.testing.assert_allclose(got, float(want), rtol=1e-5)
    for k, v in weights.reference_sumsq(g).items():
        np.testing.assert_allclose(sumsq[k], float(v), rtol=1e-4, err_msg=k)


def test_harness_names_no_dense_layout():
    """Outside bench/families/ and bench/reference/ (and these tests), no
    module names the dense decoder's parameter paths or its reference."""
    pat = re.compile(r"groups\.u0|bench\.reference\.dense|"
                     r"from bench\.reference import dense")
    hits = []
    for d, dirs, files in os.walk(BENCH):
        dirs[:] = [x for x in dirs if x not in ("families", "reference", "tests")]
        for f in files:
            if f.endswith(".py"):
                with open(os.path.join(d, f)) as fh:
                    if pat.search(fh.read()):
                        hits.append(os.path.relpath(os.path.join(d, f), BENCH))
    assert hits == []
