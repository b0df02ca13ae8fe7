"""``sr_matmul`` (kernels/sr_matmul.py), the FF and BP passes: (M, N) from
(M, K) and (K, N), or from (M, K) and (N, K) with ``trans_b``.

Each file of bench/kernels/ names one Pallas kernel.  ``match(shapes)``
says whether a ``tpu_custom_call`` is this kernel's, from the [(dtype,
shape)] of its result and operands in that order (bench/trace_reduce.py;
a call that two files claim is an error, unless one of them names the
other in its ``YIELDS_TO``, where shapes cannot tell the two kernels'
calls apart and their costs agree).  ``cost(shapes)`` gives the
(FLOPs, bytes) that one call requires, from the result and its first two
operands (bench/roofline.py): bf16 operands and result, no tile padding,
no auxiliary operand such as the uint32 entropy array.
"""
from bench.flops import BF16


def match(shapes) -> bool:
    if len(shapes) < 3 or any(len(s) != 2 for _, s in shapes[:3]):
        return False
    (_, out), (_, a), (_, b) = shapes[:3]
    return a[0] == out[0] and (b == (a[1], out[1]) or b == (out[1], a[1]))


def matmul_cost(mm: int, kk: int, nn: int) -> tuple:
    """(FLOPs, bytes) of (mm x kk) @ (kk x nn) with bf16 operands and a
    bf16 result."""
    return 2.0 * mm * kk * nn, float(BF16 * (mm * kk + kk * nn + mm * nn))


def cost(shapes) -> tuple:
    (_, out), (_, a) = shapes[0], shapes[1]
    return matmul_cost(out[0], a[1], out[1])
