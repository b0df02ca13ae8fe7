"""The set-up's own programs are the same for every seed: the seed's keys
are arguments, never constants of a program, so a run with a new seed
finds every program in the compile cache."""
from functools import partial

import jax
import jax.numpy as jnp

from bench import run, weights
from bench.kinds.train import batch_rows
from bench.tests import small


def _hlo(fn, *args) -> str:
    return fn.lower(*args).as_text()


def test_batches_program_is_seed_free():
    a, b = (_hlo(batch_rows, weights.root_key(s, "data"), jnp.int32(0), 2, 8, 64)
            for s in (3, 2**33 + 7))
    assert a == b


def test_weights_program_is_seed_free():
    m = small.config("olmo-1b")["model"]
    make = jax.jit(partial(weights.make_params, run.load_family("dense"), m))
    assert _hlo(make, weights.root_key(3, "weights")) == _hlo(
        make, weights.root_key(2**33 + 7, "weights"))
