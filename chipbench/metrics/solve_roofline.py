"""``solve_roofline``: a slab's whole CGNR solve as a share of its
roofline, %.

The algorithm's work of every slab solved in the window (its applies of
``A`` and ``A^T``, the CG vector updates and dots, ``chipbench.work``)
at the chip's peaks, over the summed ``recon/solve`` spans, which the
program fences with ``block_until_ready``.
"""
from chipbench import work


def read(record):
    pk, spans = record["peaks"], record["spans"]
    if not pk or not spans:
        return None
    solve_s = sum(s["t1"] - s["t0"] for s in spans
                  if s["kind"] == "span" and s["name"] == "recon/solve")
    if solve_s <= 0:
        return None
    least, _ = work.least_seconds(
        record["work"]["solve"] * record["slabs"], pk
    )
    return 100.0 * least / solve_s
