"""The comparison that decides ``correct``.

For a sample of slices drawn from the seed, every volume the timed
drains wrote is compared with the plain float64 CGNR of the same slices
and iterations (``reference.cgnr``), on the benchmark's own system
matrix.  These numbers are read; a cell compares those that its limits
file (``chipbench/limits/<cell>.json``) names, each against its limit:

``res_gap``    the widest relative gap between the residual norms the
               program reported for the first ``min(EARLY, iters)``
               iterations and the float64 CGNR's.  Early iterates are
               well-conditioned, so this reads the precision of the
               solver and kernel.
``traj_gap``   the widest gap, over every iteration, between the
               residual norm the program reported and the float64
               CGNR's, over ``||y||``.  It sees the whole solve: a CG
               state that stops moving, or a solve cut short, reads as
               much as the residual that was still to fall.
``claim_gap``  the widest relative gap between ``||y - A x||``, the
               misfit of the delivered volume under the reference's
               float64 ``A``, and the final residual the program
               reported.  It reads the volume as written to the store,
               and any operator error in the program's ``A``.
``misfit_gap`` the relative gap between that misfit and the float64
               CGNR's.
``vol_gap``    ``max |x - x64| / max |x64|`` of each slice.

A program that reports other than one residual an iteration reads
``inf`` on every number.
"""
from __future__ import annotations

import numpy as np

EARLY = 5
NUMBERS = ("res_gap", "traj_gap", "claim_gap", "misfit_gap", "vol_gap")


def numbers(op, y, x, res, x64, res64) -> dict:
    """Gaps of one scan's sampled slices.  ``y`` [rows, k] the data,
    ``x`` [n_vox, k] and ``res`` [iters, k] the program's answers,
    ``x64``/``res64`` the float64 CGNR's."""
    x = np.asarray(x, np.float64)
    res = np.asarray(res, np.float64)
    if res.shape != res64.shape:
        return dict.fromkeys(NUMBERS, float("inf"))
    e = min(EARLY, res.shape[0])
    with np.errstate(divide="ignore", invalid="ignore"):
        misfit = np.linalg.norm(op.matvec(x) - y, axis=0)
        misfit64 = np.linalg.norm(op.matvec(x64) - y, axis=0)
        out = {
            "res_gap": np.abs(res[:e] / res64[:e] - 1).max(),
            "traj_gap": (np.abs(res - res64)
                         / np.linalg.norm(y, axis=0)).max(),
            "claim_gap": np.abs(misfit / res[-1] - 1).max(),
            "misfit_gap": np.abs(misfit / misfit64 - 1).max(),
            "vol_gap": (np.abs(x - x64).max(0) / np.abs(x64).max(0)).max(),
        }
    return {k: float(v) if np.isfinite(v) else float("inf")
            for k, v in out.items()}


def widest(rows: list) -> dict:
    """Each number's widest reading over scans."""
    return {k: max(r[k] for r in rows) for k in rows[0]}


def verdict(found: dict, limits: dict) -> tuple[bool, dict]:
    """``(correct, {name: {"value", "limit"}})`` over the numbers that
    ``limits`` names; a number that is not finite, or not read, fails."""
    shown = {k: {"value": found.get(k, float("inf")), "limit": v}
             for k, v in limits.items()}
    ok = bool(shown) and all(
        np.isfinite(v["value"]) and v["value"] <= v["limit"]
        for v in shown.values())
    return ok, shown
