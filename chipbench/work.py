"""The algorithm's operations and bytes, from the problem's shapes.

These count what the paper's algorithm must do, not what one
implementation does: the system matrix's true nonzeros (no ELL or lane
padding, no window rows fetched twice), each stored as a 2-byte index
and a value of the rung's width (the paper's packing, Sec. III-C2), and
every vector read or written once per use.  A change that removes
padding or refetches therefore shows as a higher share of the roofline.

One apply of ``A`` (or ``A^T``) to ``F`` fused slices:
  flops  2 * nnz * F
  bytes  nnz * (2 + value_bytes) + (rows_in + rows_out) * F * vector_bytes

One CGNR solve of ``F`` slices with ``iters`` iterations (x0 = 0, as the
program starts): ``iters + 1`` applies of ``A`` and of ``A^T``, plus per
iteration the updates ``x += a p``, ``r -= a q``, ``p = s + b p`` and the
dots ``q.q``, ``s.s``, ``r.r``: 6 (n_vox + n_rays) F flops, and x, p, r
read and written, s and q read once: (5 n_vox + 3 n_rays) F vectors.
The data ``y`` is read and the volume ``x`` written once, in float32.
"""
from __future__ import annotations

import dataclasses

INDEX_BYTES = 2


@dataclasses.dataclass(frozen=True)
class Work:
    flops: float
    bytes: float

    def __add__(self, other: "Work") -> "Work":
        return Work(self.flops + other.flops, self.bytes + other.bytes)

    def __mul__(self, k: float) -> "Work":
        return Work(self.flops * k, self.bytes * k)

    __rmul__ = __mul__


def apply(nnz: int, rows_in: int, rows_out: int, slices: int,
          value_bytes: int, vector_bytes: int) -> Work:
    """One sparse-matrix apply to ``slices`` fused slices."""
    return Work(
        flops=2.0 * nnz * slices,
        bytes=float(nnz * (INDEX_BYTES + value_bytes)
                    + (rows_in + rows_out) * slices * vector_bytes),
    )


def cgnr(nnz: int, n_vox: int, n_rays: int, slices: int, iters: int,
         value_bytes: int, vector_bytes: int) -> Work:
    """One whole CGNR solve of ``slices`` fused slices."""
    a = apply(nnz, n_vox, n_rays, slices, value_bytes, vector_bytes)
    at = apply(nnz, n_rays, n_vox, slices, value_bytes, vector_bytes)
    vec = Work(
        flops=6.0 * (n_vox + n_rays) * slices,
        bytes=float((5 * n_vox + 3 * n_rays) * slices * vector_bytes),
    )
    io = Work(0.0, float((n_rays + n_vox) * slices * 4))
    return (iters + 1) * (a + at) + iters * vec + io


def least_seconds(work: Work, peaks) -> tuple[float, str]:
    """The least time the chip could take, and which bound sets it."""
    compute = work.flops / peaks.flops
    memory = work.bytes / peaks.hbm_bytes_per_s
    return (memory, "memory") if memory >= compute else (compute, "compute")
