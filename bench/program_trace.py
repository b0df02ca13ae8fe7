"""The program's own spans, stamps and counters, reduced to per-layer
numbers of the serving engine, its scheduler and the train loop.

The serving engine (``ServingEngine.step``) opens a profiler span
``serve.step`` around each iteration, and the train loop
(``run_with_recovery``) a step span ``train_loop.step``.  Inside them,
``serve.prefill.wait``, ``serve.decode.wait`` and ``train_loop.wait`` wrap
the read-backs on which the host waits for the device.  An iteration's
host time is its span's duration less the wait spans nested in it
(``step_host_ms``), read from the host line that holds the span
``window``, the line ``trace_reduce.reduce_profile`` takes the window from.

The engine also stamps each request (``RequestState.t_submit``,
``t_admit``, ``t_first``, on ``time.monotonic``) and keeps
``ServingEngine.counters``, Python ints that count from its start;
``counter_delta`` of two snapshots gives the counts of a window.
"""
from __future__ import annotations

import numpy as np

from bench.trace_reduce import WINDOW

WAIT = ".wait"


def window_line(pd) -> tuple:
    """((start_ns, end_ns) of the span ``window``, [(name, start_ns,
    end_ns, {stat: value})] of every event on its host line; a span's
    arguments are its stats)."""
    for plane in pd.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            if not any(e.name == WINDOW for e in line.events):
                continue
            evs = [(e.name, int(e.start_ns), int(e.start_ns + e.duration_ns),
                    dict(e.stats)) for e in line.events]
            return next((s, t) for n, s, t, _ in evs if n == WINDOW), evs
    raise RuntimeError("no host span 'window' in the trace")


def step_host_ms(pd, step: str) -> list:
    """For each span named `step` inside the window, in order: its
    duration less the ``*.wait`` spans nested in it, in ms."""
    (lo, hi), evs = window_line(pd)
    waits = sorted((s, t) for n, s, t, _ in evs if n.endswith(WAIT))
    w_start = np.array([s for s, _ in waits], np.int64)
    w_cum = np.concatenate([[0], np.cumsum([t - s for s, t in waits])])
    out = []
    for n, s, t, _ in sorted(evs, key=lambda e: e[1]):
        if n != step or s < lo or t > hi:
            continue
        # waits on one thread do not overlap: those starting inside
        # [s, t) end inside it
        i, j = np.searchsorted(w_start, [s, t])
        out.append((t - s - int(w_cum[j] - w_cum[i])) * 1e-6)
    return out


def stamps_ms(states, close: float) -> tuple:
    """(queue waits, admission to first token), in ms, of request states:
    ``t_admit - t_submit`` of each, one not admitted by `close` counting
    ``close - t_submit``; ``t_first - t_admit`` of each admitted by
    `close`, one with no first token by then counting ``close - t_admit``."""
    def by_close(t):
        return close if t is None or t > close else t

    wait, first = [], []
    for st in states:
        admit = by_close(st.t_admit)
        wait.append((admit - st.t_submit) * 1e3)
        if st.t_admit is not None and st.t_admit <= close:
            first.append((by_close(st.t_first) - admit) * 1e3)
    return wait, first


def counter_delta(before: dict, after: dict) -> dict:
    """The counts between two snapshots of ``ServingEngine.counters``."""
    return {k: after[k] - before[k] for k in after}
