"""``spmm_roofline``: the fused SpMM kernel's share of its roofline, %.

Least time over kernel time.  Kernel time is the summed device duration
of the kernel's events in the window's trace; least time is the larger of
flops over the chip's peak FLOP/s and bytes over its HBM bandwidth, for
the algorithm's work of every apply of ``A`` and ``A^T`` the window ran
(``chipbench.work``).  At these shapes the bytes bound it.
"""
from chipbench import work

# The kernel's operations in the trace (``chipbench.trace.op_name``): the
# program's only Pallas kernel, one call per chunk of row blocks.
KERNEL = "[tpu_custom_call]"


def kernel_seconds(ops: dict) -> float:
    return sum(v[0] for name, v in ops.items() if KERNEL in name)


def read(record):
    red, pk = record["trace"], record["peaks"]
    if not red or not pk:
        return None
    kernel_s = kernel_seconds(red["ops"])
    if kernel_s <= 0:
        return None
    applies = record["work"]["applies"] * record["slabs"]
    least, _ = work.least_seconds(applies, pk)
    return 100.0 * least / kernel_s
