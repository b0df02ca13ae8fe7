"""Roofline share of the UP outer-product kernel (kernels/outer_accum.py)
in the traced training window, in %: the least time of every call
(bench/kernels/outer_accum.py, bf16 in and out) over the summed device
durations of its events."""
from bench import roofline


def read(rec):
    if rec["kind"] != "train":
        return None
    return roofline.share(rec, "outer_accum")
