"""Benchmark entry: one run of one cell, on the chips of this machine.

    python3 bench/run.py --workload qwen2-0.5b.train --seed 7 --seconds 30 --trace 0

A cell of BENCHMARK.json names a configuration (bench/configs/<name>.json)
and a traffic mix (bench/traffic/<name>.json); the mix's ``kind`` picks
the module (bench/kinds/<kind>.py), which sets up, measures for
``--seconds`` and checks the timed path's output against the plain
reference (bench/reference/).  The configuration's model ``family`` picks
the module that knows the model's layout, reference and counts
(bench/families/<family>.py).  The limits of that check are in
bench/limits/<cell>.json.  With ``--trace 1`` the window is profiled
and each per-layer metric of the cell is read by bench/metrics/<name>.py;
the Pallas kernels are told apart and counted by bench/kernels/<name>.py.

The last line of stdout is one JSON object (correct, attempted, failed,
metrics, device[, breakdown, pallas_unmatched], checks).  The numbers
compared, each beside its limit, are also the last lines of stderr.  The
traced run's ``pallas_unmatched`` counts the Pallas calls, and their
device seconds, that neither kernel roofline reads.  Without a TPU, or with
fewer chips than the cell asks for, it prints no result and exits 2.
"""
from __future__ import annotations

import time

T_START = time.monotonic()

import argparse  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from dataclasses import dataclass, field  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
FAMILIES = os.path.join(BENCH, "families")


class Refused(RuntimeError):
    """The run cannot be made here (no chip, no program, unknown cell)."""


@dataclass
class Ctx:
    """What a kind's module gets: the cell's files and the run's options."""
    cell: dict
    config: dict            # bench/configs/<config>.json
    traffic: dict           # bench/traffic/<traffic>.json
    limits: dict            # bench/limits/<cell>.json
    seed: int
    seconds: float
    trace: bool
    t_start: float
    compiles: list = field(default_factory=list)
    family: object = field(init=False)     # bench/families/<model family>.py

    def __post_init__(self):
        self.family = load_family(self.model["family"])

    @property
    def model(self) -> dict:
        return self.config["model"]


def _load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_module(path: str, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_family(name: str):
    """The module of a model family (bench/families/<name>.py)."""
    path = os.path.join(FAMILIES, f"{name}.py")
    if not os.path.isfile(path):
        raise Refused(f"unknown model family {name!r}: no file {path}")
    return load_module(path, f"bench_family_{name}")


def cell_files(bench: dict, workload: str) -> tuple:
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise Refused(f"unknown workload {workload!r}; known: {sorted(cells)}")
    cell = cells[workload]
    conf = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    config = _load_json(os.path.join(ROOT, conf["file"]))
    traffic = _load_json(os.path.join(BENCH, "traffic", f"{cell['traffic']}.json"))
    limits = _load_json(os.path.join(BENCH, "limits", f"{workload}.json"))
    return cell, config, traffic, limits


def cell_metrics(entries: list, cell: str, e2e: set) -> list:
    """The entries that apply to `cell`: listed for it, or listing no cells
    and (per-layer) moving an end-to-end metric the cell reports."""
    out = []
    for e in entries:
        if "workloads" in e:
            if cell in e["workloads"]:
                out.append(e)
        elif "moves" not in e or e["moves"] in e2e:
            out.append(e)
    return out


def enable_compile_cache() -> str:
    """JAX's persistent cache: $JAX_COMPILATION_CACHE_DIR, else the
    checkout's .jax_cache (a fixed path, so later runs hit)."""
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or os.path.join(
        ROOT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def device_info(require_tpu: bool, chips: int) -> dict:
    import jax
    devs = jax.devices()
    info = {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}
    print(f"device: platform={info['platform']} kind={info['kind']} "
          f"count={info['count']}", file=sys.stderr, flush=True)
    if require_tpu and info["platform"] != "tpu":
        raise Refused(f"no TPU: JAX platform is {info['platform']!r}")
    if info["count"] < chips:
        raise Refused(f"the cell needs {chips} chips, {info['count']} found")
    return info


def count_compiles(ctx: Ctx) -> None:
    import jax

    def listen(event, duration, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            ctx.compiles.append(time.monotonic())

    jax.monitoring.register_event_duration_secs_listener(listen)


def run_cell(bench: dict, workload: str, seed: int, seconds: float,
             trace: bool, *, require_tpu: bool = True, files=None) -> dict:
    """One run of one cell; returns the result object (see module doc).
    files: (cell, config, traffic, limits) in place of the cell's files."""
    if not os.path.isdir(os.path.join(ROOT, "src", "repro")):
        raise Refused(f"no program under {ROOT}/src/repro")
    if ROOT not in sys.path:
        sys.path.insert(0, ROOT)
    if os.path.join(ROOT, "src") not in sys.path:
        sys.path.insert(0, os.path.join(ROOT, "src"))
    cell, config, traffic, limits = files or cell_files(bench, workload)
    ctx = Ctx(cell, config, traffic, limits, seed, seconds, trace, T_START)
    enable_compile_cache()
    dev = device_info(require_tpu, cell["chips"])
    count_compiles(ctx)
    kind = load_module(os.path.join(BENCH, "kinds", f"{traffic['kind']}.py"),
                       f"bench_kind_{traffic['kind']}")
    out = kind.run(ctx)

    e2e = cell_metrics(bench["end_to_end"], workload, set())
    names = {e["name"] for e in e2e}
    metrics = {}
    if not trace:
        for e in e2e:
            metrics[e["name"]] = {"value": out["end_to_end"][e["name"]],
                                  "unit": e["unit"]}
    else:
        for e in cell_metrics(bench["per_layer"], workload, names):
            reader = load_module(os.path.join(BENCH, "metrics", f"{e['name']}.py"),
                                 f"bench_metric_{e['name'].replace('.', '_')}")
            value = reader.read(out["record"])
            if value is not None:
                metrics[e["name"]] = {"value": value, "unit": e["unit"]}
    device = dict(dev, memory_peak_bytes=out["memory_peak_bytes"])
    if trace:
        device.update(busy_s=out["record"]["trace"]["busy_s"],
                      window_s=out["record"]["trace"]["window_s"])
    result = {"correct": out["correct"], "attempted": out["attempted"],
              "failed": out["failed"], "metrics": metrics, "device": device}
    if trace:
        result["breakdown"] = out["record"]["trace"]["breakdown"]
        result["pallas_unmatched"] = out["record"]["trace"]["pallas_unmatched"]
        print(f"pallas calls that fit no kernel: "
              f"{result['pallas_unmatched']}", file=sys.stderr, flush=True)
    result["checks"] = out["checks"]
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        bench = _load_json(os.path.join(ROOT, "BENCHMARK.json"))
        result = run_cell(bench, args.workload, args.seed, args.seconds,
                          bool(args.trace))
    except Refused as e:
        print(f"bench: refused: {e}", file=sys.stderr, flush=True)
        return 2
    for name, c in result["checks"].items():
        print(f"check {name}: {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
