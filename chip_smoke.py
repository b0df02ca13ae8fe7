"""Chip smoke test: train and serve qwen2-0.5b at its published widths on
a TPU, through the normal entry points (``repro.launch.train.main`` and
``repro.launch.serve.main``), in this one process.

    python chip_smoke.py                # one chip: train + serve
    python chip_smoke.py --four-chips   # a four-chip host: data-parallel
                                        # training checked against one device

Phases, in order (one chip):

  (a) device check: JAX version, platform, device kind and count; exits
      non-zero unless the platform is ``tpu`` (it never falls back to the
      CPU);
  (b) training: 5 steps of 8 x 2048 tokens on the Pallas PE kernels; every
      loss finite, the last below the first; step 0 re-run on the
      reference backend with the same seed agrees within 1% relative;
  (c) serving: 16 requests (prompts 64..1024 tokens, 64 generated each)
      through the continuous-batching engine on the Pallas backend; every
      request finishes with 64 tokens.

``--four-chips`` runs only the data-parallel path: training on the
(data=4, model=1) host mesh on the reference backend (16 x 1024 tokens, 5
steps), step 0 compared with the same step jitted on one device (1e-2
relative), and the params and ZeRO-1 optimizer moments checked to span
all four devices.

Weights and data come from ``--seed``; nothing outside the repository is
read.  Wall times printed on the way are smoke timings, not metrics.  The
last line of stdout is one JSON object:
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
"""
from __future__ import annotations

import argparse
import json
import math
import os
import sys
import tempfile

ROOT = os.path.dirname(os.path.abspath(__file__))
SRC = os.path.join(ROOT, "src")

ARCH = "qwen2-0.5b"
REDUCED = False            # the tests flip this to run the phases at CPU size
TRAIN = {"batch": 8, "seq": 2048, "steps": 5, "lr": 3e-4}
SERVE = {"requests": 16, "prompt_lens": "64,1024", "gen": 64, "slots": 16,
         "chunk": 256}
FOUR = {"batch": 16, "seq": 1024, "steps": 5, "lr": 3e-4}
TRAIN_REL_TOL = 1e-2       # pallas vs reference step-0 loss
FOUR_REL_TOL = 1e-2        # four-device vs one-device step-0 loss


class SmokeFailure(RuntimeError):
    """A phase's check did not hold."""


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


def device_info() -> dict:
    import jax
    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def _arch_args() -> list:
    return ["--arch", ARCH] + (["--reduced"] if REDUCED else [])


def _train(backend: str, job: dict, seed: int, *, steps: int = 0,
           on_done=None) -> list:
    """One train CLI run of `job` in a fresh checkpoint directory (steps=0:
    the job's own count); returns the per-step (loss, seconds)."""
    from repro.launch import train
    steps = steps or job["steps"]
    record = []
    with tempfile.TemporaryDirectory(prefix="chip_smoke_ckpt_") as ckpt:
        rc = train.main(
            _arch_args() + ["--kernel-backend", backend,
                            "--batch", str(job["batch"]),
                            "--seq", str(job["seq"]), "--lr", str(job["lr"]),
                            "--steps", str(steps), "--log-every", "1",
                            "--seed", str(seed), "--ckpt-dir", ckpt],
            max_failures=0, on_done=on_done,
            on_metrics=lambda s, m, dt: record.append(
                (float(m["loss"]), dt)))
    check(rc == 0, f"train.main exited {rc}")
    check(len(record) == steps, f"{len(record)} of {steps} steps reported")
    return record


def _print_times(label: str, record: list) -> None:
    times = [dt for _, dt in record]
    later = (f"; later steps {', '.join(f'{t:.3f}' for t in times[1:])} s"
             if len(times) > 1 else "")
    print(f"smoke wall time (not a metric) {label}: step 0 incl. compile "
          f"{times[0]:.3f} s{later}", flush=True)


def phase_train(seed: int) -> None:
    b, s, n = TRAIN["batch"], TRAIN["seq"], TRAIN["steps"]
    print(f"== (b) training: {ARCH} pallas {b}x{s}, {n} steps", flush=True)
    rec = _train("pallas", TRAIN, seed)
    losses = [loss for loss, _ in rec]
    _print_times("pallas", rec)
    check(all(math.isfinite(x) for x in losses),
          f"non-finite pallas loss: {losses}")
    check(losses[-1] < losses[0],
          f"pallas loss did not fall: {losses[0]} -> {losses[-1]}")
    print("== (b) step 0 again on the reference backend", flush=True)
    ref = _train("reference", TRAIN, seed, steps=1)
    _print_times("reference", ref)
    rel = abs(losses[0] - ref[0][0]) / abs(ref[0][0])
    print(f"train ok: pallas losses {losses}; step-0 pallas {losses[0]!r} "
          f"vs reference {ref[0][0]!r} (rel {rel:.3e}, limit "
          f"{TRAIN_REL_TOL})", flush=True)
    check(rel <= TRAIN_REL_TOL, f"step-0 pallas vs reference rel {rel}")


def phase_serve(seed: int) -> None:
    from repro.launch import serve
    c = SERVE
    print(f"== (c) serving: {ARCH} pallas engine, {c['requests']} requests",
          flush=True)
    seen = {}

    def on_done(trace, results):
        seen["trace"], seen["results"] = trace, results

    rc = serve.main(_arch_args() + [
        "--kernel-backend", "pallas", "--requests", str(c["requests"]),
        "--prompt-lens", c["prompt_lens"], "--gen", str(c["gen"]),
        "--slots", str(c["slots"]), "--chunk", str(c["chunk"]),
        "--seed", str(seed)], on_done=on_done)
    check(rc == 0, f"serve.main exited {rc}")
    check("results" in seen, "serve.main ran no engine")
    trace, results = seen["trace"], seen["results"]
    done = [r.rid for r in trace if len(results.get(r.rid, ())) == c["gen"]]
    print(f"serve ok: {len(done)}/{len(trace)} requests finished with "
          f"{c['gen']} tokens", flush=True)
    check(len(trace) == c["requests"] and len(done) == len(trace),
          f"only {len(done)} of {c['requests']} requests finished with "
          f"{c['gen']} tokens")


def _one_device_step0(seed: int, job: dict) -> float:
    """Step 0 of the same training job, jitted on one device, no mesh."""
    import jax

    from repro.configs import get_config, get_reduced
    from repro.configs.base import ShapeConfig, TrainConfig
    from repro.core import MeshSpec, compile_program
    from repro.data import PipelineConfig, SyntheticLM
    from repro.runtime import train_loop as tl

    cfg = get_reduced(ARCH) if REDUCED else get_config(ARCH)
    shape = ShapeConfig("custom", seq_len=job["seq"],
                        global_batch=job["batch"], kind="train")
    tc = TrainConfig(kernel_backend="reference", lr=job["lr"], seed=seed)
    program = compile_program(cfg, shape,
                              MeshSpec(axis_sizes={"data": 1, "model": 1}),
                              precision=tc.precision, remat=tc.remat)
    step_fn, opt = tl.make_train_step(cfg, program, tc, None)
    dev = jax.devices()[0]
    state = jax.device_put(
        tl.init_state(cfg, program, tc, jax.random.PRNGKey(seed), opt), dev)
    batch0 = jax.device_put(
        SyntheticLM(cfg, shape, PipelineConfig(seed=seed)).batch_at(0), dev)
    key0 = jax.random.fold_in(jax.random.key(seed), 0)
    _, metrics = jax.jit(step_fn)(state, batch0, key0)
    return float(metrics["loss"])


def phase_four_chips(seed: int, n_devices: int) -> None:
    import jax
    b, s, n = FOUR["batch"], FOUR["seq"], FOUR["steps"]
    print(f"== four chips: {ARCH} reference, (data={n_devices}, model=1) "
          f"mesh, {b}x{s}, {n} steps", flush=True)
    spans = {}

    def on_done(state):
        for part in ("params", "opt"):
            leaves = jax.tree.leaves(state[part])
            spans[part] = (
                min(len(x.sharding.device_set) for x in leaves),
                sum(not x.sharding.is_fully_replicated for x in leaves),
                len(leaves))

    rec = _train("reference", FOUR, seed, on_done=on_done)
    losses = [loss for loss, _ in rec]
    _print_times(f"{n_devices}-device", rec)
    check(all(math.isfinite(x) for x in losses), f"non-finite loss {losses}")
    check(losses[-1] < losses[0],
          f"loss did not fall: {losses[0]} -> {losses[-1]}")
    for part, (least, sharded, total) in spans.items():
        print(f"{part}: every leaf spans >= {least} devices; {sharded}/"
              f"{total} leaves sharded", flush=True)
        check(least == n_devices,
              f"a {part} leaf spans only {least} of {n_devices} devices")
    check(spans["opt"][1] > 0, "no optimizer moment is ZeRO-1 sharded")
    one = _one_device_step0(seed, FOUR)
    rel = abs(losses[0] - one) / abs(one)
    print(f"four chips ok: losses {losses}; step-0 {n_devices}-device "
          f"{losses[0]!r} vs one-device {one!r} (rel {rel:.3e}, limit "
          f"{FOUR_REL_TOL})", flush=True)
    check(rel <= FOUR_REL_TOL, f"step-0 mesh vs one device rel {rel}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the data-parallel path on a 4-chip host")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    if not os.path.isdir(os.path.join(SRC, "repro")):
        print(f"chip_smoke: FAIL: no repro package under {SRC}",
              file=sys.stderr)
        return 2
    if SRC not in sys.path:
        sys.path.insert(0, SRC)

    import jax

    from repro.launch.compile_cache import enable_compile_cache

    dev = device_info()
    print(f"== (a) device: jax {jax.__version__} platform={dev['platform']} "
          f"kind={dev['kind']} count={dev['count']}", flush=True)
    if dev["platform"] != "tpu":
        print(f"chip_smoke: FAIL: no TPU found (JAX platform "
              f"{dev['platform']!r}); this smoke test runs only on a TPU",
              file=sys.stderr)
        return 2
    want = 4 if args.four_chips else 1
    if dev["count"] != want:
        print(f"chip_smoke: FAIL: this mode runs on exactly {want} chip(s), "
              f"{dev['count']} found", file=sys.stderr)
        return 2
    print(f"compile cache: {enable_compile_cache()}", flush=True)

    try:
        if args.four_chips:
            phase_four_chips(args.seed, dev["count"])
        else:
            phase_train(args.seed)
            phase_serve(args.seed)
    except SmokeFailure as e:
        print(f"chip_smoke: FAIL: {e}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": dev}))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
