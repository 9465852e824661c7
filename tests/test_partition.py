"""Blocked-ELL partitioning: exact reconstruction + exchange tables."""
import numpy as np
import pytest

from repro.core.geometry import XCTGeometry, build_system_matrix
from repro.core.partition import (
    PartitionConfig, build_hier_sparse_exchange, build_plan,
    build_sparse_exchange, default_socket, estimate_hier_sparse,
    estimate_plan,
)


def _materialize(op, n_rows, n_cols):
    """Rebuild the dense matrix a device set represents (virtual rows of
    a split matrix row sum into the same global row)."""
    p_, b, s, r, k = op.inds.shape
    dense = np.zeros((n_rows, n_cols), np.float64)
    for p in range(p_):
        c0 = p * op.cols_per_dev
        for bi in range(b):
            for si in range(s):
                win = op.winmap[p, bi, si]
                for ri in range(r):
                    gr = op.row_map[p, bi, ri]
                    if gr >= n_rows:
                        continue
                    for ki in range(k):
                        v = op.vals[p, bi, si, ri, ki]
                        if v != 0.0:
                            gc = c0 + win[op.inds[p, bi, si, ri, ki]]
                            dense[gr, gc] += v
    return dense


@pytest.mark.parametrize("slot_order", ["runs", "first_seen"])
@pytest.mark.parametrize("p", [1, 3, 4])
def test_blocked_ell_reconstructs_matrix(p, slot_order):
    geo = XCTGeometry(n=16, n_angles=12)
    a = build_system_matrix(geo)
    cfg = PartitionConfig(
        n_data=p, tile=4, rows_per_block=8, nnz_per_stage=8,
        slot_order=slot_order,
    )
    plan = build_plan(geo, cfg, a=a)
    ap = a[plan.row_perm][:, plan.col_perm]
    dense = _materialize(plan.proj, geo.n_rays, plan.proj.n_cols_pad)
    assert np.allclose(
        dense[:, : geo.n_vox], ap.toarray(), atol=1e-6
    )
    # transpose operator too
    dense_t = _materialize(plan.back, geo.n_vox, plan.back.n_cols_pad)
    assert np.allclose(
        dense_t[:, : geo.n_rays], ap.T.toarray(), atol=1e-6
    )


def test_sparse_exchange_tables_complete():
    """Every footprint row appears in exactly one (sender, owner) slot."""
    geo = XCTGeometry(n=24, n_angles=16)
    a = build_system_matrix(geo)
    plan = build_plan(
        geo,
        PartitionConfig(n_data=4, tile=4, rows_per_block=8,
                        nnz_per_stage=8),
        a=a,
    )
    for op in (plan.proj, plan.back):
        send, recv, v = build_sparse_exchange(op)
        p = send.shape[0]
        for pp in range(p):
            rows = op.foot_rows[pp]
            n_valid = int((send[pp] < op.flat_rows).sum())
            # >=: split (virtual) rows occupy one slot per fragment
            assert n_valid >= rows.size
            # every valid slot refers to a real virtual-row position
            rm = op.row_map[pp].reshape(-1)
            n_vrows = int((rm < op.n_rows_pad).sum())
            assert n_valid == n_vrows
            # receivers: recv table entries for this sender must be
            # consistent chunk-local ids
            for q in range(p):
                mask = send[pp, q] < op.flat_rows
                assert (recv[q, pp][mask] < op.rows_per_dev).all()
                assert (recv[q, pp][~mask] == op.rows_per_dev).all()


def test_nnz_conserved(small_system):
    geo, a, plan = small_system
    assert plan.proj.nnz == a.nnz
    assert plan.back.nnz == a.nnz
    # padding overhead should be bounded (Hilbert locality keeps ELL tight)
    assert plan.proj.padded_nnz < 25 * a.nnz


def test_estimate_plan_shapes_cover_reality():
    """Analytic dry-run estimates must cover the real shapes (no gross
    undersizing) for the dimensions that drive memory."""
    geo = XCTGeometry(n=64, n_angles=48)
    a = build_system_matrix(geo)
    cfg = PartitionConfig(
        n_data=8, tile=8, rows_per_block=32, nnz_per_stage=32
    )
    real = build_plan(geo, cfg, a=a)
    est = estimate_plan(geo, cfg)
    for name in ("proj", "back"):
        r, e = getattr(real, name), getattr(est, name)
        # stage capacity: estimated slots per row >= real max usage
        assert e.inds.shape[2] * 1.6 >= r.inds.shape[2], name
        assert e.n_rows_pad == r.n_rows_pad
        assert e.n_cols_pad == r.n_cols_pad
        # total slot capacity within 4x of real padded allocation
        assert 0.25 < e.padded_nnz / r.padded_nnz < 6.0, name


def test_socket_layout_reconstructs_matrix():
    """socket=G relabels both vector spaces device-major (stored block p
    = Hilbert chunk sigma[p]); the blocked-ELL shards must reconstruct
    exactly the relabeled operator, and the layout maps must be the
    block permutation they claim to be."""
    from repro.core.partition import socket_chunk_layout

    geo = XCTGeometry(n=16, n_angles=12)
    a = build_system_matrix(geo)
    cfg = PartitionConfig(
        n_data=4, tile=4, rows_per_block=8, nnz_per_stage=8, socket=2
    )
    plan = build_plan(geo, cfg, a=a)
    sigma = socket_chunk_layout(4, 2)
    # socket t = slots {t, 2 + t} (fast-major, n_slow = 2) owns
    # consecutive Hilbert chunks {2t, 2t + 1}
    assert sigma.tolist() == [0, 2, 1, 3]
    # layout maps are bijections on the padded spaces
    for pos, pad in (
        (plan.row_pos, plan.proj.n_rows_pad),
        (plan.col_pos, plan.proj.n_cols_pad),
    ):
        assert pos.shape == (pad,)
        assert np.array_equal(np.sort(pos), np.arange(pad))
    # shards reconstruct the relabeled matrix
    ap = a[plan.row_perm][:, plan.col_perm].tocsr()
    dense = _materialize(
        plan.proj, plan.proj.n_rows_pad, plan.proj.n_cols_pad
    )
    want = np.zeros_like(dense)
    rows = plan.row_pos[: geo.n_rays]
    cols = plan.col_pos[: geo.n_vox]
    want[np.ix_(rows, cols)] = ap.toarray()
    assert np.allclose(dense, want, atol=1e-6)


def test_socket_layout_requires_divisibility():
    from repro.core.partition import socket_chunk_layout

    with pytest.raises(ValueError):
        socket_chunk_layout(4, 3)


@pytest.mark.parametrize(
    "n,angles,p,g", [(32, 24, 4, 2), (64, 48, 8, 4)]
)
def test_estimate_hier_sparse_adjacent_calibrated(n, angles, p, g):
    """ROADMAP item: the hier-sparse estimate assumed socket members'
    footprints were independent draws, overstating W for socket-aware
    plans.  The adjacent-chunk model (union ~ one merged subdomain's
    sqrt-law footprint, constant 1.9 calibrated like estimate_plan's)
    must cover the measured W without gross oversizing."""
    geo = XCTGeometry(n=n, n_angles=angles)
    a = build_system_matrix(geo)
    cfg = PartitionConfig(
        n_data=p, tile=4, rows_per_block=16, nnz_per_stage=16, socket=g
    )
    plan = build_plan(geo, cfg, a=a)
    est = estimate_plan(geo, cfg)
    n_slow = p // g
    for name in ("proj", "back"):
        real_op = getattr(plan, name)
        _, _, _, w_real, _ = build_hier_sparse_exchange(real_op, g)
        # est_socket attached by estimate_plan selects the model
        w_est, _ = estimate_hier_sparse(getattr(est, name), g, n_slow)
        assert 0.9 <= w_est / w_real <= 1.6, (name, w_est, w_real)


def test_estimate_hier_sparse_adjacent_tighter_at_scale():
    """At xct-brain scale the adjacent-chunk union is strictly below the
    independent-draw union (the overstatement the ROADMAP flagged)."""
    geo = XCTGeometry(n=11008, n_angles=4096)
    base = dict(n_data=512, tile=32, rows_per_block=64, nnz_per_stage=64)
    legacy = estimate_plan(geo, PartitionConfig(**base, socket=1))
    aware = estimate_plan(geo, PartitionConfig(**base, socket=16))
    for name in ("proj", "back"):
        w_ind, v2_ind = estimate_hier_sparse(
            getattr(legacy, name), 16, 32
        )
        w_adj, v2_adj = estimate_hier_sparse(
            getattr(aware, name), 16, 32
        )
        assert w_adj < w_ind, name
        assert v2_adj <= v2_ind, name
        # explicit override matches the inferred selection
        assert w_adj == estimate_hier_sparse(
            getattr(legacy, name), 16, 32, socket_aware=True
        )[0]


def test_default_socket_prefers_socket_aware():
    """The dry-run sweep's winner: socket=fast whenever it divides."""
    assert default_socket(512, 16) == 16
    assert default_socket(256, 16) == 16
    assert default_socket(4, 4) == 4
    assert default_socket(510, 16) == 1  # not divisible -> legacy
    assert default_socket(8, 1) == 1  # no fast level


def test_hbm_bytes_counts_resident_operator_only(small_system):
    """Regression: ``hbm_bytes`` crashed on a phantom ``block_rows``
    attribute; it must count packed nnz + int32 metadata and nothing
    staging-related (in-kernel staging has no HBM window tensor)."""
    _, _, plan = small_system
    op = plan.proj
    want = op.padded_nnz * 4 + (
        op.winmap.size + op.winsegs.size + op.segoff.size
        + op.row_map.size
    ) * 4
    assert op.hbm_bytes() == want


def test_hbm_bytes_prices_mixed_width_shard(small_system):
    """Satellite (ISSUE 8): ``value_bytes=None`` reads the vals width
    off the array itself, so a shard already packed narrow (int8 vals
    next to int16 indices) prices correctly -- including the per-(block,
    stage) int32 scale table the quantized tier carries -- instead of
    assuming vals width == vector storage width."""
    import dataclasses

    import jax.numpy as jnp

    from repro.core.precision import quantize_block_vals

    _, _, plan = small_system
    op = plan.proj
    q, _ = quantize_block_vals(jnp.asarray(op.vals), jnp.int8)
    packed = dataclasses.replace(op, vals=np.asarray(q))
    meta = (
        op.winmap.size + op.winsegs.size + op.segoff.size
        + op.row_map.size
    ) * 4
    scale_table = int(np.prod(op.inds.shape[:3])) * 4
    assert packed.hbm_bytes(value_bytes=None) == (
        op.padded_nnz * (1 + 2) + scale_table + meta
    )
    # explicit width still wins over the array dtype (the shards
    # normally hold the f32 master copy priced at the policy's width)
    assert packed.hbm_bytes(value_bytes=2) == op.hbm_bytes()
    # the master-copy f32 shard under None prices 4-byte vals, no table
    assert op.hbm_bytes(value_bytes=None) == (
        op.padded_nnz * (4 + 2) + meta
    )


# --------------------------------------------------------------------- #
# plan_key: the serve layer's cache fingerprint
# --------------------------------------------------------------------- #
def test_plan_key_deterministic_and_kwargs_order_free():
    from repro.core.partition import plan_key
    from repro.core.recon import ReconConfig

    geo = XCTGeometry(n=32, n_angles=48)
    cfg = PartitionConfig(n_data=2, tile=8)
    a = plan_key(geo, cfg, precision="mixed", comm_mode="hier")
    b = plan_key(geo, cfg, comm_mode="hier", precision="mixed")
    assert a == b  # kwargs reordering must not change the key
    assert a.startswith("xct-") and len(a) == 4 + 16
    # dataclasses fingerprint by field values, not identity
    assert plan_key(geo, cfg, recon=ReconConfig(fuse=4)) == \
        plan_key(geo, PartitionConfig(n_data=2, tile=8),
                 recon=ReconConfig(fuse=4))


def test_plan_key_equivalent_geometries_collide():
    from repro.core.partition import plan_key

    # n_det=None is an alias for n_det=n: same scan, same cold path
    assert plan_key(XCTGeometry(n=32, n_angles=48)) == \
        plan_key(XCTGeometry(n=32, n_angles=48, n_det=32))
    # dtype spellings name the same packing
    assert plan_key(XCTGeometry(32, 48),
                    PartitionConfig(value_dtype=np.float16)) == \
        plan_key(XCTGeometry(32, 48),
                 PartitionConfig(value_dtype=np.dtype("float16")))


def test_plan_key_near_misses_do_not_collide():
    from repro.core.partition import plan_key
    from repro.core.recon import ReconConfig

    geo = XCTGeometry(n=32, n_angles=48)
    base = plan_key(geo, PartitionConfig(),
                    recon=ReconConfig(precision="mixed"))
    others = [
        plan_key(XCTGeometry(n=32, n_angles=64), PartitionConfig(),
                 recon=ReconConfig(precision="mixed")),
        plan_key(XCTGeometry(n=32, n_angles=48, vox=2.0),
                 PartitionConfig(), recon=ReconConfig(precision="mixed")),
        plan_key(geo, PartitionConfig(n_data=2),
                 recon=ReconConfig(precision="mixed")),
        plan_key(geo, PartitionConfig(rows_per_block=64),
                 recon=ReconConfig(precision="mixed")),
        plan_key(geo, PartitionConfig(value_dtype=np.float32),
                 recon=ReconConfig(precision="mixed")),
        plan_key(geo, PartitionConfig(socket=2),
                 recon=ReconConfig(precision="mixed")),
        plan_key(geo, PartitionConfig(),
                 recon=ReconConfig(precision="half")),
        plan_key(geo, PartitionConfig(),
                 recon=ReconConfig(precision="mixed", comm_mode="rs")),
        plan_key(geo, PartitionConfig(),
                 recon=ReconConfig(precision="mixed", dma="per_row")),
        plan_key(geo, PartitionConfig(),
                 recon=ReconConfig(precision="mixed", fuse=4)),
    ]
    keys = [base] + others
    assert len(set(keys)) == len(keys), keys


def test_plan_key_rejects_unstable_values():
    from repro.core.partition import plan_key

    geo = XCTGeometry(n=32, n_angles=48)
    with pytest.raises(TypeError, match="cannot fingerprint"):
        plan_key(geo, PartitionConfig(), junk=object())
    # int 1 and float 1.0 must not collide (dtype-ladder style knobs)
    assert plan_key(geo, x=1) != plan_key(geo, x=1.0)


# --------------------------------------------------------------------- #
# slot reordering (ISSUE 7): the run-extension layout's DMA regression
# pin + cache-key coverage
# --------------------------------------------------------------------- #
def test_plan_key_slot_order_distinct():
    """slot_order is part of the layout, so it must be part of the
    serve layer's cache fingerprint -- a near-miss config cannot reuse
    a differently-ordered resident operator."""
    from repro.core.partition import plan_key

    geo = XCTGeometry(n=32, n_angles=48)
    assert plan_key(geo, PartitionConfig(slot_order="runs")) != \
        plan_key(geo, PartitionConfig(slot_order="first_seen"))


def test_slot_order_validated():
    geo = XCTGeometry(n=16, n_angles=12)
    with pytest.raises(ValueError, match="slot_order"):
        build_plan(geo, PartitionConfig(slot_order="alphabetical"))


def test_slot_reordering_regression_pin():
    """Acceptance pin (ISSUE 7), at the committed bench geometry
    (benchmarks/bench_spmm: n=64, n_angles=32, tile=8, R=32, K=32).

    The run-extension slot order must (a) strictly beat a fresh
    first-seen plan on both mean copy length and issue count, (b) beat
    the COMMITTED pre-reorder baseline by the issue margins the ISSUE
    demands: mean copy length >= 4x up, DMA issues >= 2x down.  The
    legacy order is also pinned to reproduce the committed baseline
    bit-for-bit -- the A/B arm stays an honest control.
    """
    from repro.kernels.ops import dma_issue_count

    # committed benchmarks/baseline/BENCH_spmm_fusing.json, pre-reorder:
    # 105176 issues over 153600 winmap entries (BUF=600) on device 0
    BASE_ISSUES, BASE_ENTRIES = 105176, 153600
    geo = XCTGeometry(n=64, n_angles=32)
    a = build_system_matrix(geo)
    stats = {}
    for so in ("runs", "first_seen"):
        plan = build_plan(
            geo,
            PartitionConfig(n_data=1, tile=8, rows_per_block=32,
                            nnz_per_stage=32, slot_order=so),
            a=a,
        )
        op = plan.proj
        issues = dma_issue_count(op.winsegs)
        stats[so] = (issues, op.winmap.size / issues)
    # (a) strict A/B
    assert stats["runs"][0] < stats["first_seen"][0]
    assert stats["runs"][1] > stats["first_seen"][1]
    # (b) margins vs the committed baseline
    assert stats["runs"][1] >= 4 * (BASE_ENTRIES / BASE_ISSUES)
    assert 2 * stats["runs"][0] <= BASE_ISSUES
    # legacy arm reproduces the committed baseline exactly
    assert stats["first_seen"][0] == BASE_ISSUES
    assert stats["first_seen"][1] == BASE_ENTRIES / BASE_ISSUES


# --------------------------------------------------------------------- #
# gap-bridged stage windows: each window takes the rows between its
# columns while it fits the BUF of the plan
# --------------------------------------------------------------------- #
def _window_layout(op):
    """Per stage window: the slots its nonzeros name (``used [P, B, S,
    BUF]``) and its height (one past the highest named slot)."""
    used = np.zeros(op.winmap.shape, bool)
    nz = np.nonzero(op.vals)
    used[(*nz[:3], op.inds[nz])] = True
    buf = op.winmap.shape[-1]
    height = np.where(used.any(-1), buf - np.argmax(used[..., ::-1], -1), 0)
    return used, height


# the bench geometry's plans before gap bridging: (BUF, DMA issues) of
# A and A^T at P=1 and P=2
UNBRIDGED = {1: {"proj": (424, 9786), "back": (128, 10831)},
             2: {"proj": (504, 14999), "back": (296, 15247)}}


@pytest.fixture(scope="module")
def bench_plans():
    geo = XCTGeometry(n=64, n_angles=32)
    a = build_system_matrix(geo)
    return {p: build_plan(geo, PartitionConfig(
        n_data=p, tile=8, rows_per_block=32, nnz_per_stage=32), a=a)
        for p in UNBRIDGED}


@pytest.mark.parametrize("p", sorted(UNBRIDGED))
@pytest.mark.parametrize("name", ["proj", "back"])
def test_bridged_windows_fit_the_unbridged_buf(bench_plans, p, name):
    """Every stage window holds its columns in ascending order with its
    gaps' rows between them, below a height that fits BUF; the tail
    keeps the ``arange`` pad; and BUF is still the widest window's
    count of distinct columns, padded to 8."""
    op = getattr(bench_plans[p], name)
    buf = op.winmap.shape[-1]
    used, height = _window_layout(op)
    assert buf == UNBRIDGED[p][name][0]
    assert buf == -(-int(used.sum(-1).max()) // 8) * 8
    assert height.max() <= buf
    slot = np.arange(buf)
    inside = slot < height[..., None]
    # ascending and consecutive wherever a slot no nonzero names lies
    # below the height: a bridged gap row, between two real columns
    step = np.diff(op.winmap, axis=-1)
    assert (step[inside[..., 1:]] > 0).all()
    gap = inside & ~used
    assert gap.any()
    assert (step[gap[..., 1:]] == 1).all()
    np.testing.assert_array_equal(
        op.winmap[~inside], np.broadcast_to(slot, op.winmap.shape)[~inside]
    )
    assert (op.winmap < op.cols_per_dev).all()


def test_bridged_windows_cut_dma_issues(bench_plans):
    """The bridged plans issue at least 2x fewer window DMAs than the
    unbridged ones, for both operators at P=2 and for A at P=1.  A^T's
    128-row BUF at P=1 leaves little room: fewer, not 2x fewer."""
    from repro.kernels.ops import dma_issue_count

    issues = {(p, name): dma_issue_count(getattr(plan, name).winsegs)
              for p, plan in bench_plans.items()
              for name in ("proj", "back")}
    for (p, name), n in issues.items():
        before = UNBRIDGED[p][name][1]
        assert n < before, (p, name)
        if (p, name) != (1, "back"):
            assert 2 * n <= before, (p, name, n)
