"""Roofline and device-time arithmetic shared by the per-layer readers."""
from __future__ import annotations

import glob
import os

from bench import flops
from bench.peaks import peaks_for
from bench.run import load_module

KERNELS_DIR = os.path.join(os.path.dirname(os.path.abspath(__file__)), "kernels")


def kernels(directory: str | None = None) -> dict:
    """{name: module} of every Pallas kernel file, bench/kernels/<name>.py:
    ``match(shapes)`` tells its calls apart, ``cost(shapes)`` counts one."""
    out = {}
    for path in sorted(glob.glob(os.path.join(directory or KERNELS_DIR, "*.py"))):
        name = os.path.basename(path)[:-3]
        out[name] = load_module(path, f"bench_kernel_{name}")
    return out


def _calls(rec: dict, kernel: str) -> list:
    return [k for k in (rec.get("trace") or {}).get("kernels", [])
            if k["kernel"] == kernel]


def share(rec: dict, kernel: str):
    """Sum of least times over sum of traced durations of `kernel`, in %;
    None where the trace holds no such call."""
    calls = _calls(rec, kernel)
    if not calls:
        return None
    cost, peaks = kernels()[kernel].cost, peaks_for(rec["device_kind"])
    least = sum(flops.least_seconds(*cost(k["shapes"]), peaks)[0] for k in calls)
    spent = sum(k["seconds"] for k in calls)
    return 100.0 * least / spent if spent > 0 else None


def bound_by(rec: dict, kernel: str) -> dict:
    """{compute|memory: calls} of `kernel` in the trace."""
    out: dict = {}
    cost, peaks = kernels()[kernel].cost, peaks_for(rec["device_kind"])
    for k in _calls(rec, kernel):
        b = flops.least_seconds(*cost(k["shapes"]), peaks)[1]
        out[b] = out.get(b, 0) + 1
    return out


def module_seconds(rec: dict, program: str) -> float:
    """Device seconds of the compiled program whose module name holds
    `program`, over the traced window."""
    mods = rec.get("trace", {}).get("modules", {})
    return sum(sum(v) for k, v in mods.items() if program in k)
