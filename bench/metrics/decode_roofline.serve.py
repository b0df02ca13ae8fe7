"""Roofline share of the engine's masked decode program (_decode), in %:
the least bytes of every decode step in the traced window (bf16 weights
once, K/V of the live positions of the active rows: the model family's
``decode_step_bytes``, bench/families/) over HBM bandwidth, over the
device time of the decode program's executions."""
from bench import roofline
from bench.peaks import peaks_for


def read(rec):
    if rec["kind"] != "serve" or not rec.get("decode_bytes"):
        return None
    dev = roofline.module_seconds(rec, "_decode")
    if not dev:
        return None
    bw = peaks_for(rec["device_kind"])["hbm_bytes_per_s"]
    return 100.0 * sum(rec["decode_bytes"]) / bw / dev
