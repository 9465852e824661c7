"""``h2d_bytes_per_slab``: host-to-device bytes a slab costs, B.

The window's increments of the program's ``h2d_bytes_total`` counter
over the slabs solved.  The program adds each counted transfer to the
span that makes it, as its ``h2d_bytes`` attr: ``recon/dispatch`` (the
operator's host arrays and ``x0``, every solve) and ``recon/stage``
(the sinogram), so the window's spans hold the window's increments.
"""


def read(record):
    spans = record["spans"]
    if not spans or not record["slabs"]:
        return None
    moved = [s["attrs"]["h2d_bytes"] for s in spans
             if s["kind"] == "span" and "h2d_bytes" in s["attrs"]]
    if not moved:
        return None
    return sum(moved) / record["slabs"]
