"""Per-layer readers on hand-made records: each ratio from its base, and
nothing where there is nothing to read."""
import os

import pytest

from bench import roofline, run

METRICS = os.path.join(os.path.dirname(os.path.dirname(__file__)), "metrics")
KIND = "TPU v5 lite"


def reader(name):
    return run.load_module(os.path.join(METRICS, f"{name}.py"), f"m_{name}")


def trace(**kw):
    t = {"window_s": 2.0, "busy_s": 1.5, "ops": {}, "modules": {}, "kernels": [],
         "breakdown": {"device_ops": [], "idle_gaps": []}}
    t.update(kw)
    return t


def test_mfu_and_idle():
    rec = {"kind": "train", "device_kind": KIND, "flops_per_token": 3.492e9,
           "tokens_per_s": 5313.0, "trace": trace()}
    assert reader("mfu.train").read(rec) == pytest.approx(100 * 3.492e9 * 5313 / 197e12)
    assert reader("device_idle_share.train").read(rec) == pytest.approx(25.0)
    assert reader("device_idle_share.serve").read(rec) is None


def test_kernel_roofline_from_shapes():
    # (16384 x 896) @ (896 x 1152): 2 M K N FLOPs, compute-bound
    call = {"kernel": "sr_matmul", "seconds": 0.001,
            "shapes": [("f32", (16384, 1152)), ("bf16", (16384, 896)),
                       ("bf16", (896, 1152)), ("u32", (16384, 1152))]}
    rec = {"kind": "train", "device_kind": KIND, "trace": trace(kernels=[call])}
    least = 2 * 16384 * 896 * 1152 / 197e12
    assert reader("sr_matmul_roofline.train").read(rec) == pytest.approx(100 * least / 0.001)
    assert roofline.bound_by(rec, "sr_matmul") == {"compute": 1}
    assert reader("outer_accum_roofline.train").read(rec) is None


def test_outer_accum_cost():
    shapes = [("bf16", (896, 1152)), ("bf16", (16384, 896)), ("bf16", (16384, 1152))]
    outer = roofline.kernels()["outer_accum"]
    assert outer.cost(shapes) == outer.outer_cost(16384, 896, 1152)


def test_serving_readers():
    m = {"n_layers": 2, "d_model": 8, "n_heads": 2, "n_kv_heads": 1, "head_dim": 4,
         "d_ff": 16, "vocab_size": 32, "norm": "rmsnorm", "qkv_bias": False}
    rec = {"kind": "serve", "device_kind": KIND, "model": m,
           "engine_step_ms": [3.0, 1.0, 2.0],
           "chunk_flops": [1e9, 3e9], "decode_bytes": [819e6],
           "trace": trace(modules={"jit__chunk": [0.5, 0.5], "jit__decode": [0.01]})}
    assert reader("engine_step_ms_p50.serve").read(rec) == 2.0
    assert reader("prefill_mfu.serve").read(rec) == pytest.approx(100 * 4e9 / 1.0 / 197e12)
    assert reader("decode_roofline.serve").read(rec) == pytest.approx(100 * 1e-3 / 0.01)
    rec["trace"]["modules"] = {}
    assert reader("prefill_mfu.serve").read(rec) is None
