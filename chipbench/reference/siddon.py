"""Parallel-beam Siddon system matrix, in float64 and plain NumPy.

Entry ``(k * channels + c, iy * n + ix)`` is the length of the ray of
angle ``pi * k / angles`` and detector channel ``c`` inside voxel
``(ix, iy)`` of an ``n x n`` grid of unit voxels centred on the origin.
The ray of channel ``c`` passes the origin at offset
``(c - (channels - 1) / 2)`` along the detector axis.
"""
from __future__ import annotations

import numpy as np
import scipy.sparse as sp


def _one_angle(n: int, channels: int, theta: float):
    """``(channel, voxel, length)`` of every ray of one angle."""
    half = n / 2.0
    grid = np.arange(n + 1) - half  # grid-line coordinates
    ux, uy = np.cos(theta), np.sin(theta)  # along the ray
    ex, ey = -uy, ux  # along the detector
    t = np.arange(channels) - (channels - 1) / 2.0
    far = 2.0 * n  # start every ray outside the grid
    ox = t * ex - far * ux
    oy = t * ey - far * uy

    # Arc lengths at which each ray crosses each grid line (|u| = 1).
    cross, lo, hi = [], np.full(channels, -np.inf), np.full(channels, np.inf)
    hits = np.ones(channels, bool)
    for o, u in ((ox, ux), (oy, uy)):
        if abs(u) > 1e-12:
            a = (grid[None, :] - o[:, None]) / u
            cross.append(a)
            lo = np.maximum(lo, np.minimum(a[:, 0], a[:, -1]))
            hi = np.minimum(hi, np.maximum(a[:, 0], a[:, -1]))
        else:  # parallel to these lines: inside the slab or missing it
            hits &= (o >= grid[0]) & (o <= grid[-1])
    hi = np.where(hits, hi, lo)
    a = np.concatenate(cross + [lo[:, None], hi[:, None]], axis=1)
    a = np.sort(np.clip(a, lo[:, None], hi[:, None]), axis=1)
    length = np.diff(a, axis=1)
    mid = 0.5 * (a[:, 1:] + a[:, :-1])
    ix = np.floor(ox[:, None] + mid * ux + half).astype(np.int64)
    iy = np.floor(oy[:, None] + mid * uy + half).astype(np.int64)
    keep = (length > 1e-9) & (ix >= 0) & (ix < n) & (iy >= 0) & (iy < n)
    chan = np.broadcast_to(np.arange(channels)[:, None], length.shape)
    return chan[keep], (iy * n + ix)[keep], length[keep]


def system_matrix(n: int, angles: int, channels: int | None = None):
    """The ``[angles * channels, n * n]`` float64 CSR system matrix."""
    channels = n if channels is None else channels
    rows, cols, vals = [], [], []
    for k in range(angles):
        c, v, ln = _one_angle(n, channels, np.pi * k / angles)
        rows.append(c + k * channels)
        cols.append(v)
        vals.append(ln)
    a = sp.coo_matrix(
        (np.concatenate(vals),
         (np.concatenate(rows), np.concatenate(cols))),
        shape=(angles * channels, n * n),
    ).tocsr()
    a.sum_duplicates()
    return a
