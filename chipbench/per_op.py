"""The SpMM kernel's share of its roofline in one operator, ``A``
(``proj``) or ``A^T`` (``back``).

The program names each kernel call after its operator: the trace's
``ops`` (``chipbench.trace.reduce``) hold ``xct_spmm_proj.<k>
[tpu_custom_call]`` and ``xct_spmm_back.<k> [tpu_custom_call]``, whose
HLO text carries ``kernel_metadata`` ``{"kernel":"xct_spmm","op":..}``.
Least time is that of every apply of the operator the window ran.
``chipbench.work`` prices an apply of ``A`` and one of ``A^T`` alike
(the same nonzeros, the same two vectors, read once and written once),
so each operator's applies are half of the window's ``applies``.
"""
import re

from chipbench import work

TARGET = "[tpu_custom_call]"


def seconds(ops: dict, tag: str) -> float:
    """Self seconds of the kernel calls tagged ``tag``."""
    head = re.compile(rf"xct_spmm_{tag}(\.\d+)? ")
    return sum(v[0] for name, v in ops.items()
               if TARGET in name and head.match(name))


def read(record, tag: str):
    red, pk = record["trace"], record["peaks"]
    if not red or not pk:
        return None
    kernel_s = seconds(red["ops"], tag)
    if kernel_s <= 0:
        return None
    least, _ = work.least_seconds(
        record["work"]["applies"] * (0.5 * record["slabs"]), pk
    )
    return 100.0 * least / kernel_s
