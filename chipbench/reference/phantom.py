"""Seeded 3D ellipse phantom (Shepp-Logan-like slices that shrink and
drift along the rotation axis), in plain NumPy.

The seed moves each ellipse's drift, so every seed gives a different
volume of the same size and the same kind.
"""
from __future__ import annotations

from concurrent.futures import ThreadPoolExecutor

import numpy as np

# (intensity, x0, y0, semi-axis a, semi-axis b, rotation in degrees)
ELLIPSES = (
    (1.0, 0.0, 0.0, 0.69, 0.92, 0.0),
    (-0.8, 0.0, -0.0184, 0.6624, 0.874, 0.0),
    (-0.2, 0.22, 0.0, 0.11, 0.31, -18.0),
    (-0.2, -0.22, 0.0, 0.16, 0.41, 18.0),
    (0.1, 0.0, 0.35, 0.21, 0.25, 0.0),
    (0.1, 0.0, 0.1, 0.046, 0.046, 0.0),
    (0.1, -0.08, -0.605, 0.046, 0.023, 0.0),
    (0.1, 0.06, -0.605, 0.023, 0.046, 0.0),
)


def phantom(n: int, slices: int, seed: int, threads: int = 8) -> np.ndarray:
    """``[n * n, slices]`` float32 volume, slice ``s`` in column ``s``."""
    drift = np.random.default_rng(seed).normal(0, 0.02, (len(ELLIPSES), 2))
    coord = ((np.arange(n) - (n - 1) / 2) / (n / 2)).astype(np.float32)
    x, y = coord[None, :], coord[:, None]
    z = (np.arange(slices) + 0.5) / slices - 0.5  # [-0.5, 0.5]
    shrink = np.sqrt(np.maximum(1e-3, 1.0 - (2 * z) ** 2))
    vol = np.zeros((slices, n, n), np.float32)
    rotated = []
    for amp, x0, y0, a, b, deg in ELLIPSES:
        c, s = np.cos(np.radians(deg)), np.sin(np.radians(deg))
        rotated.append((x * c + y * s, y * c - x * s, c, s))  # [n, n] grids

    def one_slice(k):
        for (amp, x0, y0, a, b, _), (dx, dy), (u0, v0, c, s) in zip(
                ELLIPSES, drift, rotated):
            cx, cy = x0 + dx * z[k] * 4, y0 + dy * z[k] * 4
            du = np.float32(cx * c + cy * s)
            dv = np.float32(cy * c - cx * s)
            ua, vb = np.float32(a * shrink[k]), np.float32(b * shrink[k])
            inside = ((u0 - du) / ua) ** 2 + ((v0 - dv) / vb) ** 2 <= 1
            vol[k] += np.float32(amp) * inside

    with ThreadPoolExecutor(threads) as pool:
        list(pool.map(one_slice, range(slices)))
    np.clip(vol, 0, None, out=vol)
    return np.ascontiguousarray(vol.reshape(slices, n * n).T)
