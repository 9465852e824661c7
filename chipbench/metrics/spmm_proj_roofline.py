"""``spmm_proj_roofline``: the fused SpMM kernel's share of its roofline
in the projection ``A``, %: least time of every apply of ``A`` in the
window over the self seconds of the kernel calls tagged ``proj``
(``chipbench.per_op``)."""
from chipbench import per_op


def read(record):
    return per_op.read(record, "proj")
