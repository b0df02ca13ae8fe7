"""Roofline and device-time arithmetic shared by the per-layer readers."""
from __future__ import annotations

from bench import flops
from bench.peaks import peaks_for

KERNELS = ("sr_matmul", "outer_accum")


def call_cost(kernel: str, shapes: list) -> tuple:
    """(FLOPs, bytes) of one kernel call from its HLO shapes, result
    first: sr_matmul (M, N) <- (M, K) x (K, N) or (N, K); outer_accum
    (D, F) <- (T, D) x (T, F)."""
    (_, out), (_, a) = shapes[0], shapes[1]
    if kernel == "sr_matmul":
        return flops.matmul_cost(out[0], a[1], out[1])
    if kernel == "outer_accum":
        return flops.outer_cost(a[0], out[0], out[1])
    raise KeyError(kernel)


def share(rec: dict, kernel: str):
    """Sum of least times over sum of traced durations of `kernel`, in %;
    None where the trace holds no such call."""
    t = rec.get("trace")
    calls = [k for k in (t or {}).get("kernels", []) if k["kernel"] == kernel]
    if not calls:
        return None
    least = sum(flops.least_seconds(*call_cost(kernel, k["shapes"]),
                                    peaks_for(rec["device_kind"]))[0] for k in calls)
    spent = sum(k["seconds"] for k in calls)
    return 100.0 * least / spent if spent > 0 else None


def bound_by(rec: dict, kernel: str) -> dict:
    """{compute|memory: calls} of `kernel` in the trace."""
    out: dict = {}
    for k in rec.get("trace", {}).get("kernels", []):
        if k["kernel"] == kernel:
            b = flops.least_seconds(*call_cost(kernel, k["shapes"]),
                                    peaks_for(rec["device_kind"]))[1]
            out[b] = out.get(b, 0) + 1
    return out


def module_seconds(rec: dict, program: str) -> float:
    """Device seconds of the compiled program whose module name holds
    `program`, over the traced window."""
    mods = rec.get("trace", {}).get("modules", {})
    return sum(sum(v) for k, v in mods.items() if program in k)
