"""Roofline share of the FF/BP matmul kernel (kernels/sr_matmul.py) in
the traced training window, in %: the least time of every call (the
larger of its FLOPs over peak and its bytes over bandwidth,
bench/kernels/sr_matmul.py: bf16 operands and result, no padding, no
entropy operand) over the summed device durations of its events."""
from bench import roofline


def read(rec):
    if rec["kind"] != "train":
        return None
    return roofline.share(rec, "sr_matmul")
