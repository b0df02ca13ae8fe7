"""FLOP utilization of the engine's prefill chunk program (_chunk), in %:
the chunks' model FLOPs in the traced window (the model family's
``prefill_chunk_flops``, bench/families/: matmuls, causal attention over
live positions, the head on the last position) over the device time of
the chunk program's executions, over peak."""
from bench import roofline
from bench.peaks import peaks_for


def read(rec):
    if rec["kind"] != "serve" or not rec.get("chunk_flops"):
        return None
    dev = roofline.module_seconds(rec, "_chunk")
    if not dev:
        return None
    peak = peaks_for(rec["device_kind"])["bf16_flops"]
    return 100.0 * sum(rec["chunk_flops"]) / dev / peak
