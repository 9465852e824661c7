"""``grid_steps_per_slab``: grid steps the SpMM kernel runs a slab.

The window's increments of the program's ``spmm_grid_steps_total``
counter over the slabs solved.  The program adds each solve's count, the
operators' grid steps an apply times the solve's applies, to the span
that dispatches the solve, as its ``grid_steps`` attr
(``recon/dispatch``), so the window's spans hold the window's increments.
Kernel seconds over this count give the time a grid step costs.
"""


def read(record):
    spans = record["spans"]
    if not spans or not record["slabs"]:
        return None
    steps = [s["attrs"]["grid_steps"] for s in spans
             if s["kind"] == "span" and "grid_steps" in s["attrs"]]
    if not steps:
        return None
    return sum(steps) / record["slabs"]
