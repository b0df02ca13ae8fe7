"""Arithmetic shared by the counts of operations and bytes.

Every count is of the work the algorithm needs at the precision the
configuration states (bf16 operands and activations): no tile padding,
no recomputation, and no auxiliary operand such as an all-zero entropy
array.  A model family's counts are in bench/families/<family>.py, a
Pallas kernel's in bench/kernels/<kernel>.py.
"""
from __future__ import annotations

BF16 = 2


def least_seconds(flops: float, nbytes: float, peaks: dict) -> tuple:
    """(least time, which bound) of work on a chip with `peaks`."""
    tc = flops / peaks["bf16_flops"]
    tm = nbytes / peaks["hbm_bytes_per_s"]
    return (tc, "compute") if tc >= tm else (tm, "memory")
