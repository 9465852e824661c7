"""``spmm_back_roofline``: the fused SpMM kernel's share of its roofline
in the backprojection ``A^T``, %: least time of every apply of ``A^T``
in the window over the self seconds of the kernel calls tagged ``back``
(``chipbench.per_op``)."""
from chipbench import per_op


def read(record):
    return per_op.read(record, "back")
