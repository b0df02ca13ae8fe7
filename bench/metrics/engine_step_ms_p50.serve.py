"""Median host-clock time of one ServingEngine.step() in the traced
window, in ms, stamped by the benchmark around each call."""
import statistics


def read(rec):
    if rec["kind"] != "serve" or not rec["engine_step_ms"]:
        return None
    return statistics.median(rec["engine_step_ms"])
