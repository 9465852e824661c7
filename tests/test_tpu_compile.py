"""Chip-compiler rehearsal: the fused SpMM kernel and the CG solve,
compiled for a described (not attached) TPU v5e with ``interpret=False``.

Nothing runs: each test only asks the TPU compiler, which is installed
with jax, to accept the program -- what Mosaic refuses here (unaligned
DMAs, gathers it cannot lower, SMEM overflow) costs no chip time.  The
topology is described inside a module fixture, never at import: only one
process may hold the TPU library, and every test worker imports this
file.
"""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, SingleDeviceSharding

from repro.core.geometry import XCTGeometry
from repro.core.partition import PartitionConfig, build_plan
from repro.core.recon import ReconConfig, Reconstructor
from repro.dist import Topology
from repro.kernels.xct_spmm import (
    _dma_classes,
    spmm_block_ell,
    spmm_block_ell_staged,
)

# deployment widths: R and K of PartitionConfig, the projection window
# and segment capacity of the n=256 / 384-angle plan, one chip's column
# share, F = one 128-lane vreg of fused slices
B, S, R, K, BUF, NSEG, C, F = 16, 13, 32, 32, 608, 256, 65536, 128
V5E_HBM = 16 * 10**9


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        desc = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # noqa: BLE001
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip cannot be read back from the
    # persistent cache without one: keep it out of the cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _kernel_compiled(one_chip, dma, vals_dtype, x_dtype, compute,
                     quantized=False):
    def sds(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    ncls = len(_dma_classes(BUF)) + 1
    args = [
        sds((B, S, R, K), jnp.int16), sds((B, S, R, K), vals_dtype),
        sds((B, S, BUF), jnp.int32), sds((C, F), x_dtype),
        sds((B, S, NSEG, 3), jnp.int32), sds((B, S, ncls), jnp.int32),
        sds((B, S), jnp.int32),
    ]

    def fn(inds, vals, winmap, x, segs, off, scl):
        return spmm_block_ell(
            inds, vals, winmap, x, compute_dtype=compute,
            interpret=False,
            winsegs=None if dma == "per_row" else segs,
            segoff=off if dma == "sorted" else None,
            scales=scl if quantized else None,
        )

    return jax.jit(fn).lower(*args).compile()


@pytest.mark.parametrize("dma", ["sorted", "coalesced", "per_row"])
def test_kernel_variants_compile(one_chip, dma):
    """Every shipped window-DMA variant lowers through Mosaic on the
    mixed rung (f16 storage, f32 compute): class-sorted coalesced (the
    default), unsorted coalesced and per-row."""
    compiled = _kernel_compiled(
        one_chip, dma, jnp.float16, jnp.float16, jnp.float32
    )
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize(
    "vals_dtype,x_dtype,compute,quantized",
    [
        (jnp.float32, jnp.float32, jnp.float32, False),  # single
        (jnp.float16, jnp.float16, jnp.float16, False),  # half
        (jnp.bfloat16, jnp.bfloat16, jnp.bfloat16, False),  # bf16
        (jnp.int8, jnp.float16, jnp.float32, True),  # q8
        (jnp.float8_e4m3fn, jnp.float16, jnp.float32, True),  # fp8
    ],
    ids=["single", "half", "bf16", "q8", "fp8"],
)
def test_storage_rungs_compile(one_chip, vals_dtype, x_dtype, compute,
                               quantized):
    """The other storage rungs through the default kernel: 32-bit, both
    16-bit float tiles (decoded in-kernel), and int8 / fp8 values with
    the scalar-prefetched dequant exponents."""
    compiled = _kernel_compiled(
        one_chip, "sorted", vals_dtype, x_dtype, compute, quantized
    )
    assert "tpu_custom_call" in compiled.as_text()


def test_staged_gather_arm_compiles(one_chip):
    """The legacy two-pass arm (``apply_operator(staging="gather")``):
    BlockSpec delivers each stage's pre-staged ``[BUF, F]`` window."""

    def sds(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    compiled = jax.jit(
        lambda i, v, w: spmm_block_ell_staged(i, v, w, interpret=False)
    ).lower(
        sds((B, S, R, K), jnp.int16), sds((B, S, R, K), jnp.float16),
        sds((B, S, BUF, F), jnp.float16),
    ).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.fixture(scope="module")
def plans():
    """n=64 / 96-angle plans at the default kernel layout (R = K = 32):
    one chip, and 4-way data parallel laid out for 2-chip sockets."""
    geo = XCTGeometry(n=64, n_angles=96)
    return {
        p: build_plan(
            geo, PartitionConfig(n_data=p, socket=2 if p == 4 else 1)
        )
        for p in (1, 4)
    }


def _lower_cg(plan, mesh, data_axes, batch_axes, comm, fuse=16):
    rec = Reconstructor(
        plan,
        topology=Topology.from_mesh(
            mesh, data_axes=data_axes, batch_axes=batch_axes
        ),
        cfg=ReconConfig(precision="mixed", comm_mode=comm, fuse=fuse,
                        interpret=False),
        abstract=True,
    )
    lowered, compiled = rec.lower_cg(2 * fuse, iters=3)
    mem = compiled.memory_analysis()
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             + mem.temp_size_in_bytes)
    assert 0 < total < V5E_HBM
    assert "tpu_custom_call" in compiled.as_text()
    return lowered.as_text(), compiled.as_text()


def test_lower_cg_one_chip(topo, plans):
    mesh = Mesh(np.array(topo.devices[:1]).reshape(1, 1),
                ("data", "model"))
    _lower_cg(plans[1], mesh, ("model",), ("data",), "hier")


@pytest.mark.parametrize("comm", ["hier", "hier-sparse"])
def test_lower_cg_2x2(topo, plans, comm):
    """The 4-way data-parallel solve on the 2x2 mesh: the hier ladder
    reduce-scatters level by level, hier-sparse exchanges footprints
    all-to-all; both keep the kernel."""
    mesh = Mesh(np.array(topo.devices).reshape(2, 2), ("data", "model"))
    lowered, compiled = _lower_cg(
        plans[4], mesh, ("model", "data"), (), comm
    )
    if comm == "hier":
        assert "reduce_scatter" in lowered
    else:
        assert "all-to-all" in compiled


def test_lower_cg_names_each_operator(topo, plans):
    """Every kernel call of the solve carries its operator's tag into
    the HLO text a device trace shows: the instruction is named
    ``xct_spmm_<op>`` and its ``kernel_metadata`` holds the op."""
    mesh = Mesh(np.array(topo.devices[:1]).reshape(1, 1),
                ("data", "model"))
    _, compiled = _lower_cg(plans[1], mesh, ("model",), ("data",), "hier")
    calls = [ln for ln in compiled.splitlines()
             if 'custom_call_target="tpu_custom_call"' in ln]
    heads = {ln.split(" = ", 1)[0].strip().lstrip("%").split(".")[0]
             for ln in calls}
    assert heads == {"xct_spmm_proj", "xct_spmm_back"}
    for op in ("proj", "back"):
        assert f'kernel_metadata={{\n"kernel":"xct_spmm",\n"op":"{op}"' \
            in compiled
