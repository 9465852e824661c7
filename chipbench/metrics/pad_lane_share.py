"""``pad_lane_share``: the share of the window's solved lanes that are
padding, %.

The zero lanes that fill a short slab to whole fused passes
(``pad_slices`` on ``recon/dispatch``, the program's
``padded_slices_total``) over every lane the window's solves carried,
real slices plus padding (``chipbench.padding``).  A 262-slice scan in
128-slice slabs reads 122 / 384.
"""
from chipbench import padding


def read(record):
    found = padding.solves(record)
    if not found:
        return None
    pad = sum(p for _, _, p in found)
    lanes = sum(n + p for _, n, p in found)
    return 100.0 * pad / lanes if lanes else None
