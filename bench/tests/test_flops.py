"""FLOP and byte counts from shapes, pinned to hand counts."""
import json
import os

import pytest

from bench import flops, roofline, run
from bench.peaks import peaks_for

CONFIGS = os.path.join(os.path.dirname(os.path.dirname(__file__)), "configs")
dense = run.load_family("dense")
KERNELS = roofline.kernels()


def model(name):
    with open(os.path.join(CONFIGS, f"{name}.json")) as f:
        return json.load(f)["model"]


def test_qwen2_train_flops_per_token():
    m = model("qwen2-0.5b")
    # N = 24 x 14,909,440 + 151,936 x 896 = 493,961,216
    assert dense.matmul_params(m) == 493_961_216
    # 6N = 2.9638e9; 12 L H Q T = 12 x 24 x 896 x 2048 = 0.5285e9
    assert dense.train_flops_per_token(m, 2048) == pytest.approx(3.492e9, rel=5e-4)


def test_olmo_train_flops_per_token():
    m = model("olmo-1b")
    # N = 16 x 67,108,864 + 50,304 x 2048 = 1,176,764,416 (tied head once)
    assert dense.matmul_params(m) == 1_176_764_416
    assert dense.train_flops_per_token(m, 2048) == pytest.approx(7.866e9, rel=5e-4)


def test_kv_bytes_per_token():
    assert dense.kv_bytes_per_token(model("qwen2-0.5b")) == 12_288
    assert dense.kv_bytes_per_token(model("olmo-1b")) == 131_072


def test_kernel_costs():
    assert KERNELS["sr_matmul"].matmul_cost(4, 3, 2) == (48.0, 2.0 * (12 + 6 + 8))
    assert KERNELS["outer_accum"].outer_cost(5, 2, 3) == (60.0, 2.0 * (10 + 15 + 6))
    p = peaks_for("TPU v5 lite")
    t, bound = flops.least_seconds(197e12, 1.0, p)
    assert t == pytest.approx(1.0) and bound == "compute"
    t, bound = flops.least_seconds(1.0, 819e9, p)
    assert t == pytest.approx(1.0) and bound == "memory"


def test_prefill_and_decode_counts():
    m = model("qwen2-0.5b")
    n_layer = m["n_layers"] * dense.layer_matmul_params(m)
    # chunk of 256 at position 512: live pairs 256*512 + 256*257/2
    want = (2 * n_layer * 256 + 4 * 24 * 14 * 64 * (256 * 512 + 256 * 257 // 2)
            + 2 * 151936 * 896)
    assert dense.prefill_chunk_flops(m, 512, 256) == want
    w = (dense.matmul_params(m) + dense.small_params(m)) * 2
    assert dense.decode_step_bytes(m, 1000, 3) == w + 12_288 * 1003


def test_unknown_device_is_an_error():
    with pytest.raises(KeyError):
        peaks_for("cpu")
