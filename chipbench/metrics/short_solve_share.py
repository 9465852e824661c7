"""``short_solve_share``: the share of the window's solve seconds spent
on solves that carry padding, %.

The summed ``recon/solve`` spans whose ``recon/dispatch`` carries
``pad_slices`` above 0 (a scan's short last slab, padded to whole fused
passes) over all the window's ``recon/solve`` spans
(``chipbench.padding``).  The padded pass costs a whole pass, so this
reads what the short slab costs against the slices it holds.
"""
from chipbench import padding


def read(record):
    found = padding.solves(record)
    if not found:
        return None
    total = sum(t for t, _, _ in found)
    short = sum(t for t, _, p in found if p > 0)
    return 100.0 * short / total if total > 0 else None
