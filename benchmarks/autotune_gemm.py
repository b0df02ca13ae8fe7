"""Autotuned vs default tilings on the sr_matmul MAC-array kernel.

    PYTHONPATH=src python -m benchmarks.autotune_gemm [--smoke]

For each gemm (an FC op, a conv im2col lowering per the paper's Fig 6,
and a transformer FFN) the mapping autotuner picks a tiling against its
bytes-moved + roofline model; both the tuned and the default
``(256, 256, 512)`` tiles then run on the actual kernel.  Rows carry the
model's DETERMINISTIC numbers (``pred_speedup``, ``pred_bytes_ratio``) —
what benchmarks/gate.py gates in CI (wall time in interpret mode on a CI
runner is recorded but too noisy to gate) — alongside the measured time.

``--smoke`` is the CI variant: small shapes, seconds on CPU.
"""
from __future__ import annotations

import argparse
import functools

from benchmarks.common import row, time_fn
from repro.tuner import (GemmShape, conv_im2col_gemm, default_tile_for,
                         tune_gemm)

# (name, GemmShape): FC from the paper's MLP0, conv2 of AlexNet as the
# Fig 6 im2col gemm, and the qwen2 FFN projection at train tokens.
FULL_SHAPES = (
    ("mlp0_fc", GemmShape(m=2560, n=2560, k=2560)),
    ("alexnet_conv2", conv_im2col_gemm(batch=32, out_hw=27, kernel=5,
                                       in_ch=96, out_ch=256)),
    ("qwen_ffn_in", GemmShape(m=4096, n=4864, k=896)),
)
SMOKE_SHAPES = (
    ("fc_smoke", GemmShape(m=256, n=320, k=384)),
    ("conv_smoke", conv_im2col_gemm(batch=2, out_hw=13, kernel=3,
                                    in_ch=64, out_ch=128)),
)


def bench_shape(name: str, shape: GemmShape, *, iters: int = 3) -> None:
    import jax
    import jax.numpy as jnp

    from repro.kernels import ops as kops

    tuned = tune_gemm(shape)
    t_cost = tuned.best
    d_cost = default_tile_for(shape)
    pred_speedup = d_cost.time_s / max(t_cost.time_s, 1e-30)
    bytes_ratio = t_cost.hbm_bytes / max(d_cost.hbm_bytes, 1.0)

    key = jax.random.PRNGKey(0)
    a = jax.random.normal(key, (shape.m, shape.k), jnp.bfloat16)
    b = jax.random.normal(jax.random.fold_in(key, 1), (shape.k, shape.n),
                          jnp.bfloat16)

    def run_tile(tile):
        return kops.sr_matmul(a, b, None, sr=False, block=tile)

    us_d = time_fn(functools.partial(run_tile, d_cost.tile), iters=iters)
    us_t = time_fn(functools.partial(run_tile, t_cost.tile), iters=iters)

    def fmt(t):
        return "x".join(map(str, t))

    row(f"autotune_gemm/{name}/default", us_d,
        f"tile={fmt(d_cost.tile)} pred_us={d_cost.time_s*1e6:.1f} "
        f"hbm_mb={d_cost.hbm_bytes/1e6:.2f}")
    row(f"autotune_gemm/{name}/tuned", us_t,
        f"tile={fmt(t_cost.tile)} pred_us={t_cost.time_s*1e6:.1f} "
        f"hbm_mb={t_cost.hbm_bytes/1e6:.2f} "
        f"pred_speedup={pred_speedup:.4f} pred_bytes_ratio={bytes_ratio:.4f} "
        f"candidates={tuned.n_candidates}")


def run(smoke: bool = True) -> None:
    """Harness entry (benchmarks.run): smoke shapes — the full shapes are
    minutes in interpret mode; run this module directly for those."""
    shapes = SMOKE_SHAPES if smoke else FULL_SHAPES
    for name, shape in shapes:
        bench_shape(name, shape)


def predict_only() -> None:
    """Model numbers for the full shapes without running kernels."""
    for name, shape in FULL_SHAPES:
        tuned = tune_gemm(shape)
        d = default_tile_for(shape)
        row(f"autotune_gemm/{name}/model", tuned.best.time_s * 1e6,
            f"tile={'x'.join(map(str, tuned.best.tile))} "
            f"default_pred_us={d.time_s*1e6:.1f} "
            f"pred_speedup={d.time_s/max(tuned.best.time_s, 1e-30):.4f}")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="CI variant: small shapes, seconds on CPU")
    ap.add_argument("--predict-only", action="store_true",
                    help="print cost-model numbers for the full shapes "
                         "without running kernels")
    args = ap.parse_args()
    print("name,us_per_call,derived")
    if args.predict_only:
        predict_only()
        return
    run(smoke=args.smoke)


if __name__ == "__main__":
    main()
