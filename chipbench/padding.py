"""The window's solves and the zero lanes each carries, for the readers
of ``pad_lane_share`` and ``short_solve_share``.

The program pads a slab whose slice count is not a multiple of its
fused pass with zero lanes up to whole passes, and ``recon/dispatch``,
inside each ``recon/solve``, carries their number as its
``pad_slices`` attr (0 for a full slab); ``recon/solve`` carries the
real slices as ``slices``.
"""


def solves(record):
    """``[(seconds, slices, pad_slices)]`` of every ``recon/solve`` span
    in the window, or ``None`` where no ``recon/dispatch`` carries
    ``pad_slices``: a program that does not pad."""
    spans = record["spans"]
    if not spans:
        return None
    done = [s for s in spans if s["kind"] == "span"]
    pad = {s["parent_id"]: s["attrs"]["pad_slices"] for s in done
           if s["name"] == "recon/dispatch" and "pad_slices" in s["attrs"]}
    if not pad:
        return None
    return [(s["t1"] - s["t0"], s["attrs"]["slices"], pad.get(s["id"], 0))
            for s in done if s["name"] == "recon/solve"]
