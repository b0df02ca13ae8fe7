"""``outer_accum`` (kernels/outer_accum.py), the UP pass: dW (D, F) =
X^T dY from X (T, D) and dY (T, F).  The interface of a kernel file is
described in bench/kernels/sr_matmul.py."""
from bench.flops import BF16

# Where the tokens of a step equal d_ff (olmo-1b at 4 x 2048), the FF pass
# of ffn_out, (T, f) @ (f, d), and its UP pass, h^T dY, have the same
# shapes and the same cost: such a call is sr_matmul's.
YIELDS_TO = ("sr_matmul",)


def match(shapes) -> bool:
    if len(shapes) < 3 or any(len(s) != 2 for _, s in shapes[:3]):
        return False
    (_, out), (_, a), (_, b) = shapes[:3]
    return a[0] == b[0] and (a[1], b[1]) == out


def outer_cost(tt: int, dd: int, ff: int) -> tuple:
    """(FLOPs, bytes) of dW = X^T dY, X (tt x dd), dY (tt x ff), bf16 in
    and out."""
    return 2.0 * tt * dd * ff, float(BF16 * (tt * dd + tt * ff + dd * ff))


def cost(shapes) -> tuple:
    (_, out), (_, a) = shapes[0], shapes[1]
    return outer_cost(a[0], out[0], out[1])
