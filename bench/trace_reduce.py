"""From a profiler trace of the measured window to the numbers the
per-layer metrics read.

The window is the host span ``window`` that the kinds open around the
measured loop (``span``).  On each device plane, busy time is the union
of the intervals in which an operation of the "XLA Ops" line runs, cut
to the window; the idle share is one less busy over the window.  The
device's clock in the trace runs about a millisecond and a half behind
the host's (TPU v5e), which moves either edge of the window by that much.

An op event's name is its HLO instruction (``%fusion.3 = f32[...]
fusion(...)``); ops are grouped by the instruction's name without its
instance number, and ranked by self time (a ``while`` op's events
enclose those of its body).  Idle gaps are named by the innermost host
span that covers the middle of the gap.  Device time per compiled
program comes from the "XLA Modules" line.

The Pallas kernels are the ``tpu_custom_call`` ops.  Their instruction
names come from the caller's jit, not from the kernel, so each call is
told apart by its result and operand shapes (``classify``): every kernel
file, bench/kernels/<kernel>.py, says by ``match`` whether a call is its
own, and a call that two files claim is an error unless one yields it to
the other (``YIELDS_TO``).  ``sr_matmul`` computes
(M, N) from (M, K) and (K, N), or (N, K) with ``trans_b``; ``outer_accum``
computes (D, F) from (T, D) and (T, F).  Both read only the result and
the first two operands, so a kernel without the uint32 entropy operand
that both take today is still recognised.  A Pallas call that fits no
kernel is counted, with its device time, under ``pallas_unmatched``.
"""
from __future__ import annotations

import glob
import os
import re
import shutil
import tempfile

import jax
import numpy as np

from bench import roofline

WINDOW = "window"
OPS_LINE, MODULES_LINE = "XLA Ops", "XLA Modules"
PALLAS_TARGET = 'custom_call_target="tpu_custom_call"'


def span(name: str):
    """A host span in the profiler's trace (free when not tracing)."""
    return jax.profiler.TraceAnnotation(name)


class Tracer:
    """Profile one window into a private temporary directory.  The Python
    tracer is off: it would slow the host path that the window times."""

    def __init__(self):
        self.dir = tempfile.mkdtemp(prefix="bench_trace_")

    def start(self):
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(self.dir, profiler_options=opts)

    def stop(self):
        jax.profiler.stop_trace()

    def reduce(self) -> dict:
        files = glob.glob(os.path.join(self.dir, "**", "*.xplane.pb"),
                          recursive=True)
        if len(files) != 1:
            raise RuntimeError(f"expected one trace file, found {files}")
        return reduce_file(files[0])

    def cleanup(self):
        shutil.rmtree(self.dir, ignore_errors=True)


def union(intervals) -> list:
    """Merge (start, end) intervals; returns them sorted and disjoint."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [tuple(x) for x in out]


def clip(intervals, lo, hi) -> list:
    return [(max(s, lo), min(e, hi)) for s, e in intervals if e > lo and s < hi]


def gaps(busy, lo, hi) -> list:
    """The idle (start, end) stretches of [lo, hi] between busy intervals."""
    out, t = [], lo
    for s, e in busy:
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if hi > t:
        out.append((t, hi))
    return out


def self_times(events) -> list:
    """Self time of each (start, end) event, in input order: its duration
    less the events it encloses directly."""
    order = sorted(range(len(events)), key=lambda i: (events[i][0], -events[i][1]))
    own = [e - s for s, e in events]
    stack: list = []
    for i in order:
        s, e = events[i]
        while stack and events[stack[-1]][1] <= s:
            stack.pop()
        if stack and e <= events[stack[-1]][1]:
            own[stack[-1]] -= e - s
        stack.append(i)
    return own


class HostSpans:
    """Host spans (name, start, end) of the benchmark's thread, to name what
    the host was doing at a time."""

    def __init__(self, spans):
        self.names = [n for n, _, _ in spans]
        self.start = np.array([s for _, s, _ in spans], np.int64)
        self.end = np.array([e for _, _, e in spans], np.int64)

    def innermost(self, t: int) -> str:
        """Name of the shortest span covering t."""
        hit = np.nonzero((self.start <= t) & (self.end >= t))[0]
        if not len(hit):
            return "(no host span)"
        return self.names[hit[np.argmin(self.end[hit] - self.start[hit])]]


def name_gaps(idle, host: HostSpans, top: int = 500) -> dict:
    """{host span: idle seconds}: the `top` longest gaps each named by the
    span covering its middle, the rest summed as "(shorter gaps)"."""
    idle = sorted(idle, key=lambda g: g[0] - g[1])
    out: dict = {}
    for s, e in idle[:top]:
        who = host.innermost((s + e) // 2)
        out[who] = out.get(who, 0.0) + (e - s) * 1e-9
    rest = sum(e - s for s, e in idle[top:])
    if rest:
        out["(shorter gaps)"] = rest * 1e-9
    return out


def op_name(text: str) -> str:
    """The instruction name of an op event (``%fusion.3 = ...`` ->
    ``fusion.3``)."""
    return text.split(" = ", 1)[0].lstrip("%")


_NUM = re.compile(r"[._]\d+$")


def op_family(text: str) -> str:
    """A name stable across compiles: the instruction without its number."""
    return _NUM.sub("", op_name(text))


_SHAPE = re.compile(r"\b(bf16|f32|f16|u32|s32|u8|s8|pred|f8e4m3fn)\[([\d,]*)\]")


def hlo_shapes(text: str) -> list:
    """[(dtype, shape)] in the order they appear in an HLO instruction."""
    return [(d, tuple(int(x) for x in s.split(",") if x))
            for d, s in _SHAPE.findall(text)]


def classify(shapes: list, kernels: dict | None = None):
    """The kernel of a Pallas call from its [result, a, b, ...] shapes, or
    None when they fit no kernel; a file that claims the call yields it
    to a claimant named in its ``YIELDS_TO``.  kernels: {name: module} as
    ``roofline.kernels`` loads them (the default)."""
    kernels = roofline.kernels() if kernels is None else kernels
    hits = [name for name, k in kernels.items() if k.match(shapes)]
    hits = [h for h in hits
            if not set(getattr(kernels[h], "YIELDS_TO", ())) & set(hits)]
    if len(hits) > 1:
        raise RuntimeError(f"a Pallas call of shapes {shapes} fits more than "
                           f"one kernel file: {hits}")
    return hits[0] if hits else None


def kernel_event(text: str, seconds: float, kernels: dict | None = None):
    """{kernel, seconds, shapes} for a Pallas op (kernel None where its
    shapes fit no kernel), else None."""
    if PALLAS_TARGET not in text:
        return None
    shapes = hlo_shapes(text.split(PALLAS_TARGET, 1)[0])
    return {"kernel": classify(shapes, kernels), "seconds": seconds,
            "shapes": shapes[:3]}


def _planes(pd):
    device = [p for p in pd.planes if p.name.startswith("/device:")
              and any(line.name == OPS_LINE for line in p.lines)]
    host = [p for p in pd.planes if p.name.startswith("/host:")]
    return device, host


def reduce_file(path: str) -> dict:
    from jax.profiler import ProfileData
    return reduce_profile(ProfileData.from_file(path))


def _top(d: dict, n: int = 10) -> list:
    return [[k, v] for k, v in sorted(d.items(), key=lambda kv: -kv[1])[:n]]


def reduce_profile(pd) -> dict:
    """{window_s, busy_s, ops (family: self seconds), modules (program:
    [seconds]), kernels, pallas_unmatched ({calls, seconds} of the Pallas
    calls that fit no kernel), breakdown} of the traced window; busy_s is
    the mean over the device planes."""
    device, host = _planes(pd)
    spans, window = [], None
    for plane in host:
        for line in plane.lines:
            evs = [(e.name, int(e.start_ns), int(e.start_ns + e.duration_ns))
                   for e in line.events]
            win = [x for x in evs if x[0] == WINDOW]
            if win:
                window = (win[0][1], win[0][2])
                spans = evs
    if window is None:
        raise RuntimeError("no host span 'window' in the trace")
    if not device:
        raise RuntimeError("no device plane with an 'XLA Ops' line")
    lo, hi = window
    files = roofline.kernels()
    busy_ns, ops, modules, kernels, idle = [], {}, {}, [], []
    unmatched = {"calls": 0, "seconds": 0.0}
    for plane in device:
        for line in plane.lines:
            if line.name == MODULES_LINE:
                for e in line.events:
                    if lo <= e.start_ns < hi:
                        modules.setdefault(op_family(e.name), []).append(
                            e.duration_ns * 1e-9)
            if line.name != OPS_LINE:
                continue
            names, iv = [], []
            for e in line.events:
                s, t = int(e.start_ns), int(e.start_ns + e.duration_ns)
                if t <= lo or s >= hi:
                    continue
                names.append(e.name)
                iv.append((s, t))
                k = kernel_event(e.name, e.duration_ns * 1e-9, files)
                if k is not None and k["kernel"] is None:
                    unmatched["calls"] += 1
                    unmatched["seconds"] += k["seconds"]
                elif k is not None:
                    kernels.append(k)
            for name, own in zip(names, self_times(iv)):
                fam = op_family(name)
                ops[fam] = ops.get(fam, 0.0) + own * 1e-9
            b = union(clip(iv, lo, hi))
            busy_ns.append(sum(e - s for s, e in b))
            idle += gaps(b, lo, hi)
    return {"window_s": (hi - lo) * 1e-9,
            "busy_s": sum(busy_ns) / len(busy_ns) * 1e-9,
            "ops": ops, "modules": modules, "kernels": kernels,
            "pallas_unmatched": unmatched,
            "breakdown": {"device_ops": _top(ops),
                          "idle_gaps": _top(name_gaps(idle, HostSpans(spans)))}}
