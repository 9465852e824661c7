import os
import sys

# Tests must see the default (single) CPU device -- only the dry-run forces
# 512 placeholder devices.  Keep compile parallelism low: 1 core.
os.environ.setdefault("JAX_PLATFORMS", "cpu")

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import numpy as np  # noqa: E402
import pytest  # noqa: E402


@pytest.fixture(scope="session")
def small_system():
    """Shared small geometry + system matrix + plan (memoized)."""
    from repro.core.geometry import XCTGeometry, build_system_matrix
    from repro.core.partition import PartitionConfig, build_plan

    # Crowther criterion: K >= ~pi/2 * n angles for a well-posed inverse
    geo = XCTGeometry(n=32, n_angles=48)
    a = build_system_matrix(geo)
    cfg = PartitionConfig(
        n_data=1, tile=4, rows_per_block=16, nnz_per_stage=16
    )
    plan = build_plan(geo, cfg, a=a)
    return geo, a, plan


@pytest.fixture(scope="session")
def phantom32(small_system):
    from repro.data.phantom import phantom_slices

    geo, a, _ = small_system
    x = phantom_slices(geo.n, 4)
    y = (a @ x).astype(np.float32)
    return x, y


@pytest.fixture()
def fresh_obs():
    """Isolated metrics + tracer so counter asserts see only this test."""
    from repro.obs import metrics as obs_metrics
    from repro.obs import trace as obs_trace

    old_t = obs_trace.set_tracer(obs_trace.Tracer(enabled=True))
    old_m = obs_metrics.set_metrics(obs_metrics.Metrics())
    try:
        yield obs_trace.get_tracer(), obs_metrics.get_metrics()
    finally:
        obs_trace.set_tracer(old_t)
        obs_metrics.set_metrics(old_m)
