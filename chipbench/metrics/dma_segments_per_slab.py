"""``dma_segments_per_slab``: window DMAs the SpMM kernel issues a slab.

The window's increments of the program's ``spmm_dma_segments_total``
counter over the slabs solved.  The program adds each solve's count, the
operators' real window segments an apply times the solve's applies, to
the span that dispatches the solve, as its ``dma_segments`` attr
(``recon/dispatch``), so the window's spans hold the window's increments.
"""


def read(record):
    spans = record["spans"]
    if not spans or not record["slabs"]:
        return None
    issued = [s["attrs"]["dma_segments"] for s in spans
              if s["kind"] == "span" and "dma_segments" in s["attrs"]]
    if not issued:
        return None
    return sum(issued) / record["slabs"]
