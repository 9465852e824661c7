"""Paper Fig. 12: strong and weak scaling.

Strong: fixed problem, P_d in {1, 2, 4}; weak: n grows with device count
(doubling all measurement dims multiplies work 16x per the paper's
Table I).  On this 1-core container, multi-device wall time measures
*total work + overhead* rather than latency, so the derived column also
reports the analytic per-device work ratio (what a real fleet would see).
Subprocesses are used because the virtual device count must be set before
jax initializes.  The children run on the CPU by construction
(``JAX_PLATFORMS=cpu`` with forced host devices): several processes
cannot share one TPU, and these rows are labelled ``device=cpu``.
"""
from __future__ import annotations

import os
import subprocess
import sys

from .common import emit

_SCRIPT = """
import time, numpy as np, jax
from repro.core.geometry import XCTGeometry, build_system_matrix
from repro.core.partition import PartitionConfig, build_plan
from repro.core.recon import ReconConfig, Reconstructor
n, p, iters = {n}, {p}, {iters}
geo = XCTGeometry(n=n, n_angles=n // 2)
a = build_system_matrix(geo)
plan = build_plan(geo, PartitionConfig(n_data=p, tile=4,
                  rows_per_block=16, nnz_per_stage=16), a=a)
mesh = None
if p > 1:
    mesh = jax.make_mesh((1, p), ("data", "model"),
        axis_types=(jax.sharding.AxisType.Auto,)*2)
rec = Reconstructor(plan, mesh=mesh, data_axes=("model",),
    batch_axes=("data",) if p > 1 else (),
    cfg=ReconConfig(precision="mixed", comm_mode="hier", fuse=4))
rng = np.random.default_rng(0)
sino = rng.normal(size=(geo.n_rays, 4)).astype(np.float32)
y = rec.pack_sino(sino); x0 = np.zeros((rec.tomo_pad, 4), np.float32)
fn = rec._get_fn("cg", iters)
jax.block_until_ready(fn(rec._arrays, y, x0))
t0 = time.perf_counter()
jax.block_until_ready(fn(rec._arrays, y, x0))
print("TIME", time.perf_counter() - t0)
"""


def _run_case(n, p, iters=4):
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={max(p,1)}"
    env["PYTHONPATH"] = os.path.join(
        os.path.dirname(__file__), "..", "src"
    )
    r = subprocess.run(
        [sys.executable, "-c", _SCRIPT.format(n=n, p=p, iters=iters)],
        capture_output=True, text=True, env=env, timeout=900,
    )
    if r.returncode != 0:
        raise RuntimeError(r.stderr[-800:])
    for line in r.stdout.splitlines():
        if line.startswith("TIME"):
            return float(line.split()[1])
    raise RuntimeError("no TIME in output")


def run(quick: bool = False):
    # strong scaling
    n = 32 if quick else 48
    ps = (1, 2) if quick else (1, 2, 4)
    base = None
    for p in ps:
        t = _run_case(n, p)
        if base is None:
            base = t
        # per-device work ratio from Table I: (MN^2/Pd + MN/sqrt(Pd))
        ideal = (1.0 / p) + 0.1 / np.sqrt(p) if False else 1.0 / p
        emit(
            f"scaling_strong/P={p}", t * 1e6,
            f"device=cpu eff={(base/t)/p:.2f} "
            f"ideal_work_frac={ideal:.2f}",
        )
    # weak scaling: n doubles, devices x4 (2D slice work scales n^2*angles)
    cases = [(24, 1), (48, 4)] if not quick else [(16, 1), (32, 4)]
    base = None
    for n_, p_ in cases:
        t = _run_case(n_, p_)
        if base is None:
            base = t
        emit(
            f"scaling_weak/n={n_},P={p_}", t * 1e6,
            f"device=cpu time_ratio={t/base:.2f} (1.0 = perfect weak "
            f"scaling on a real fleet; 1-core container serializes "
            f"devices)",
        )


import numpy as np  # noqa: E402

if __name__ == "__main__":
    run()
