"""Paper Fig. 13: convergence vs precision on noisy (Chip-like) data.

Runs in a subprocess with JAX_ENABLE_X64=1 so the "double" policy is a
real f64 baseline.  The child runs on the CPU (``JAX_PLATFORMS=cpu``):
a TPU has no f64, and a child cannot share the chip a parent that has
touched JAX holds.  Rows are labelled ``device=cpu``.  Derived: relative residual after the fixed iteration
budget per precision -- the paper's claim is that half/mixed track
double/single because the numerical noise floor sits below measurement
noise.
"""
from __future__ import annotations

import os
import subprocess
import sys

from .common import emit

_SCRIPT = """
import numpy as np, jax
from repro.core.geometry import XCTGeometry, build_system_matrix
from repro.core.partition import PartitionConfig, build_plan
from repro.core.recon import ReconConfig, Reconstructor
from repro.data.phantom import phantom_slices, simulate_measurements
n, iters = {n}, {iters}
geo = XCTGeometry(n=n, n_angles=n)
a = build_system_matrix(geo)
plan = build_plan(geo, PartitionConfig(n_data=1, tile=8,
                  rows_per_block=16, nnz_per_stage=16), a=a)
x_true = phantom_slices(n, 2)
sino = simulate_measurements(a, x_true, noise=0.02, seed=1)
for prec in ("double", "single", "half", "mixed"):
    rec = Reconstructor(plan,
        cfg=ReconConfig(precision=prec, comm_mode="rs", fuse=2))
    import time
    t0 = time.perf_counter()
    x, res = rec.reconstruct(sino, iters=iters)
    dt = time.perf_counter() - t0
    rel = res[-1, 0] / res[0, 0]
    err = float(np.linalg.norm(x - x_true) / np.linalg.norm(x_true))
    print(f"ROW {{prec}} {{dt:.3f}} {{rel:.6f}} {{err:.4f}}")
"""


def run(n: int = 48, iters: int = 16, quick: bool = False):
    if quick:
        n, iters = 32, 8
    env = dict(os.environ)
    env["JAX_ENABLE_X64"] = "1"
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = os.path.join(
        os.path.dirname(__file__), "..", "src"
    )
    r = subprocess.run(
        [sys.executable, "-c", _SCRIPT.format(n=n, iters=iters)],
        capture_output=True, text=True, env=env, timeout=1200,
    )
    if r.returncode != 0:
        raise RuntimeError(r.stderr[-800:])
    for line in r.stdout.splitlines():
        if line.startswith("ROW"):
            _, prec, dt, rel, err = line.split()
            emit(
                f"convergence/{prec}", float(dt) * 1e6,
                f"device=cpu rel_residual={rel} recon_err={err} "
                f"iters={iters}",
            )


if __name__ == "__main__":
    run()
