"""Petascale XCT reproduction: distributed 3D image reconstruction in JAX.

Subpackages:
  core     -- geometry, partitioning, precision, solver, reconstruction
  dist     -- topology-aware hierarchical communication (Topology/CommPlan)
  kernels  -- Pallas blocked-ELL SpMM + pure-jnp oracles
  stream   -- out-of-core slab streaming (volumes larger than memory)
  serve    -- multi-tenant reconstruction-as-a-service (plan cache,
              admission control, batching, progressive previews)
  models   -- LM substrate exercising the same communication machinery
  launch   -- drivers: recon, train, lm_serve, dry-run lowering, sweeps
"""

__version__ = "0.1.0"
