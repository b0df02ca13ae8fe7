"""Record the small device trace that test_trace_reduce.py reads.

    python3 bench/tests/record_trace.py --out bench/tests/data/small.xplane.pb

Runs on a TPU.  Inside a host span ``window`` it sleeps 20 ms (the
device's clock in a trace runs a little behind the host's), runs an
elementwise program, sleeps 50 ms under the host span ``host.sleep``,
runs one ``sr_matmul`` Pallas call of (512 x 256) @ (256 x 384) and one
``outer_accum`` call of (256 x 512)^T (256 x 384), each waited for, and
sleeps 20 ms more.  The programs are compiled before the trace starts.
"""
from __future__ import annotations

import argparse
import glob
import os
import shutil
import sys
import tempfile
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--out", required=True)
    args = ap.parse_args()
    import jax
    import jax.numpy as jnp

    from bench.trace_reduce import span
    from repro.kernels.outer_accum import outer_accum
    from repro.kernels.sr_matmul import sr_matmul

    if jax.devices()[0].platform != "tpu":
        print("record_trace: needs a TPU", file=sys.stderr)
        return 2
    x = jnp.arange(1024 * 1024, dtype=jnp.float32).reshape(1024, 1024)
    a = jnp.ones((512, 256), jnp.bfloat16)
    b = jnp.ones((256, 384), jnp.bfloat16)
    ew = jax.jit(lambda v: v * 2.0 + 1.0)
    dy = jnp.ones((256, 384), jnp.bfloat16)
    mm = jax.jit(lambda p, q: sr_matmul(p, q))
    up = jax.jit(lambda p, q: outer_accum(p, q))
    ew(x).block_until_ready()
    mm(a, b).block_until_ready()
    xt = jnp.ones((256, 512), jnp.bfloat16)
    up(xt, dy).block_until_ready()
    tmp = tempfile.mkdtemp(prefix="bench_trace_")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(tmp, profiler_options=opts)
    with span("window"):
        time.sleep(0.02)
        ew(x).block_until_ready()
        with span("host.sleep"):
            time.sleep(0.05)
        mm(a, b).block_until_ready()
        up(xt, dy).block_until_ready()
        time.sleep(0.02)
    jax.profiler.stop_trace()
    (path,) = glob.glob(os.path.join(tmp, "**", "*.xplane.pb"), recursive=True)
    os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
    shutil.copy(path, args.out)
    shutil.rmtree(tmp, ignore_errors=True)
    print(f"wrote {args.out}: {os.path.getsize(args.out)} bytes")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
