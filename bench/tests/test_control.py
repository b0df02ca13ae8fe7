"""The control: the reference, computed with float8 matmuls, put in the
program's place, has to come out as not correct.  At the small size on
the CPU; on the chip at the cell's size it is run by bench/calibrate.py."""
import pytest

from bench import compare, run, weights
from bench.kinds.train import make_batches
from bench.tests import small

FAM = run.load_family("dense")


@pytest.mark.parametrize("arch", ["qwen2-0.5b", "olmo-1b"])
@pytest.mark.parametrize("seed", [1, 2**33 + 3])
def test_train_control_fails(arch, seed):
    m, tr = small.config(arch)["model"], small.TRAIN
    wkey = weights.root_key(seed, "weights")
    batches = make_batches(m, tr, weights.root_key(seed, "data"))
    ref = compare.reference_training(FAM, m, tr, wkey, batches, tr["check_steps"],
                                     keep_first=True)
    ctl = compare.reference_training(FAM, m, tr, wkey, batches, tr["check_steps"],
                                     quant="fp8", first=ref["first"])
    checks = compare.judge(compare.training_readings(ctl, ref),
                           {"limits": small.TRAIN_LIMITS})
    assert not all(c["ok"] for c in checks.values()), checks


def test_serve_control_fails():
    """On served sequences, the float8 model's first choice lies further
    below the reference's best logit than the limit allows."""
    import numpy as np
    m = small.config("qwen2-0.5b")["model"]
    wkey = weights.root_key(4, "weights")
    ref = compare.ServeReference(FAM, m, wkey, bucket=32)
    low = compare.ServeReference(FAM, m, wkey, quant="fp8", bucket=32)
    rng = np.random.default_rng(0)
    sample = [(rng.integers(0, m["vocab_size"], n), rng.integers(0, m["vocab_size"], 20))
              for n in (10, 40)]
    got = compare.control_serve_readings(ref, low, sample)
    assert got["logit_gap"] > small.SERVE_LIMITS["logit_gap"], got
