"""A whole run on the CPU at a small size, the chip check skipped: sound,
`correct` is true; with the timed path broken underneath, it is false.

Faults: a train step that returns its state unchanged; a train step that
leaves out half of the batch and takes the mean over the rest; a served
token altered where the decode step produces it.  The exchange between
chips does not exist in these one-chip cells."""
import jax.numpy as jnp
import pytest

from bench import run
from bench.tests import small

TRAIN_LIMITS, SERVE_LIMITS = small.TRAIN_LIMITS, small.SERVE_LIMITS
SEED = 2**33 + 11


def _train_fault(monkeypatch, fault):
    from repro.runtime import train_loop as tl
    orig = tl.make_train_step

    def make(*a, **k):
        step, opt = orig(*a, **k)

        def broken(state, batch, key):
            if fault == "unchanged":
                return state, step(state, batch, key)[1]
            half = {n: v[: v.shape[0] // 2] for n, v in batch.items()}
            return step(state, half, key)

        return broken, opt

    monkeypatch.setattr(tl, "make_train_step", make)


def _serve_fault(monkeypatch):
    from repro.serving import engine as eng
    orig = eng.ServingEngine.__init__

    def init(self, cfg, *a, **k):
        orig(self, cfg, *a, **k)
        decode = self._decode

        def altered(*args):
            nxt, cache = decode(*args)
            return (nxt + 1) % cfg.vocab_size, cache

        self._decode = altered

    monkeypatch.setattr(eng.ServingEngine, "__init__", init)


def _run(kind, traffic, limits, seconds=1.5):
    f = small.files("qwen2-0.5b", traffic, limits)
    return run.run_cell(small.bench(f[0]["name"], kind), f[0]["name"], SEED,
                        seconds, False, require_tpu=False, files=f)


@pytest.mark.parametrize("fault", [None, "unchanged", "half_batch"])
def test_train(monkeypatch, fault):
    if fault:
        _train_fault(monkeypatch, fault)
    res = _run("train", small.TRAIN, TRAIN_LIMITS)
    assert res["correct"] is (fault is None), res["checks"]
    assert res["metrics"]["train_tokens_per_s"]["value"] > 0


@pytest.mark.parametrize("fault", [None, "token_altered"])
def test_serve(monkeypatch, fault):
    if fault:
        _serve_fault(monkeypatch)
    res = _run("serve", small.SERVE, SERVE_LIMITS, seconds=3.0)
    assert res["correct"] is (fault is None), res["checks"]
    assert res["attempted"] > 0
