"""Model FLOP utilization of the training step: PaLM FLOPs per token
(the model family's ``train_flops_per_token``, bench/families/) times the
traced window's tokens per second, over the chip's bf16 peak, in %.
Recomputation does not count."""
from bench.peaks import peaks_for


def read(rec):
    if rec["kind"] != "train":
        return None
    peak = peaks_for(rec["device_kind"])["bf16_flops"]
    return 100.0 * rec["flops_per_token"] * rec["tokens_per_s"] / peak
