"""CommPlan wire-volume model: launch-layer parity + sparse dedup.

Everything the launch layer reports about communication volume must be a
view over ``dist.CommPlan`` -- these tests pin the two unification
points:

  * ``launch.xct_perf.comm_volume`` returns exactly what the resolved
    plans model, per link class, for every mode (regression for the old
    hand-rolled ``direct`` branch that double-counted DCI with a 2x
    all-reduce factor on top of the pod fan-out);
  * the hierarchical sparse exchange's socket-level dedup strictly
    reduces modeled DCI bytes vs the flat ``sparse`` all-to-all, both on
    a real small plan (exact tables) and at xct-brain scale (analytic
    estimates).
"""
import math

import numpy as np
import pytest

from repro.configs.xct_datasets import DATASETS
from repro.core.geometry import XCTGeometry, build_system_matrix
from repro.core.partition import (
    PartitionConfig,
    build_hier_sparse_exchange,
    build_plan,
    build_sparse_exchange,
    estimate_plan,
    exchange_volume_params,
)
from repro.dist import MODES, Topology
from repro.launch.xct_perf import comm_volume, sweep_topology


@pytest.fixture(scope="module")
def small_plan():
    geo = XCTGeometry(n=32, n_angles=24)
    a = build_system_matrix(geo)
    return build_plan(
        geo,
        PartitionConfig(n_data=4, tile=4, rows_per_block=16,
                        nnz_per_stage=16),
        a=a,
    )


def test_comm_volume_matches_commplan_all_modes(small_plan):
    """comm_volume is a pure view over CommPlan -- per-link parity."""
    topo = Topology.from_sizes(
        [("model", 2, "ici"), ("data", 2, "dci")]
    )
    fuse, cb = 4, 2
    for mode in MODES:
        got = comm_volume(small_plan, mode, fuse, cb, topo)
        want = {"ici": 0.0, "dci": 0.0}
        for op in (small_plan.proj, small_plan.back):
            dense = float(op.n_rows_pad) * fuse * cb
            cp = topo.plan(mode, **exchange_volume_params(op, topo))
            for link, b in cp.wire_bytes_by_link(dense).items():
                want[link] += b
        assert got == pytest.approx(want), mode


def test_direct_dci_not_double_counted(small_plan):
    """Regression: the old hand-rolled ``direct`` branch charged DCI a
    2x all-reduce factor on top of the pod fan-out.  In the paper's
    reduce-semantics accounting (Table IV) the flat all-reduce reduces
    the full dense partial at the global rung: DCI bytes == the dense
    partial, once, same as ``rs``."""
    topo = Topology.from_sizes(
        [("model", 2, "ici"), ("data", 2, "dci")]
    )
    fuse, cb = 4, 2
    dense_total = sum(
        float(op.n_rows_pad) * fuse * cb
        for op in (small_plan.proj, small_plan.back)
    )
    direct = comm_volume(small_plan, "direct", fuse, cb, topo)
    assert direct["dci"] == pytest.approx(dense_total)
    assert direct == pytest.approx(
        comm_volume(small_plan, "rs", fuse, cb, topo)
    )


def test_socket_dedup_strictly_reduces_dci_exact(small_plan):
    """Exact tables: hier-sparse DCI < flat sparse DCI, because the
    socket members' overlapping footprints are merged before crossing
    the slow link (and the merged band is strictly smaller than the sum
    of the members' bands)."""
    topo = Topology.from_sizes(
        [("model", 2, "ici"), ("data", 2, "dci")]
    )
    for op in (small_plan.proj, small_plan.back):
        params = exchange_volume_params(op, topo)
        dense = float(op.n_rows_pad)
        flat = topo.plan("sparse", **params).wire_bytes_by_link(dense)
        hs = topo.plan("hier-sparse", **params).wire_bytes_by_link(dense)
        assert hs["dci"] < flat["dci"]
        # ... and the model mirrors the real table capacities
        _, _, v = build_sparse_exchange(op)
        _, _, _, w, v2 = build_hier_sparse_exchange(op, 2)
        assert params["pair_slots"] == v
        assert params["merged_rows"] == 2 * w
        assert params["cross_rows"] == 2 * v2
        # dedup in rows, not just padding: the merged band is smaller
        # than the stacked member bands
        foot_sum = sum(r.size for r in op.foot_rows)
        assert params["merged_rows"] <= foot_sum


def test_socket_dedup_reduces_dci_at_brain_scale():
    """Acceptance: modeled DCI bytes of hier-sparse at xct-brain scale
    (P_d = 512 over two pods) are strictly below flat sparse."""
    ds = DATASETS["xct-brain"]
    geo = XCTGeometry(n=ds.n, n_angles=ds.k)
    plan = estimate_plan(
        geo,
        PartitionConfig(n_data=512, tile=32, rows_per_block=64,
                        nnz_per_stage=64),
    )
    topo = sweep_topology(512)
    assert [lv.link for lv in topo.levels] == ["ici", "ici", "dci"]
    flat = comm_volume(plan, "sparse", 16, 2, topo)
    hs = comm_volume(plan, "hier-sparse", 16, 2, topo)
    direct = comm_volume(plan, "direct", 16, 2, topo)
    assert hs["dci"] < flat["dci"]
    assert hs["dci"] < direct["dci"]


def test_hier_sparse_level_fracs_shape():
    """Per-link accounting of the new mode: the socket rung carries the
    merged band, every slower rung the cross-socket slots."""
    topo = Topology.from_sizes(
        [("model", 4, "ici"), ("data", 4, "ici"), ("pod", 2, "dci")]
    )
    cp = topo.plan(
        "hier-sparse", dense_rows=1000, merged_rows=400, cross_rows=80
    )
    assert cp.level_fracs == pytest.approx((0.4, 0.08, 0.08))
    assert [s.op for s in cp.steps] == ["reduce_scatter", "all_to_all"]
    assert cp.steps[0].axes == ("model",)
    assert cp.steps[1].axes == ("data", "pod")
    by_link = cp.wire_bytes_by_link(1000.0)
    assert by_link["ici"] == pytest.approx(400.0 + 80.0)
    assert by_link["dci"] == pytest.approx(80.0)
    # without the table capacities the volume model is NaN, never wrong
    assert math.isnan(topo.plan("hier-sparse").level_fracs[0])


def test_hier_sparse_tables_route_every_partial(small_plan):
    """Host-side replay of the three stages: scatter into the merged
    band, fast-axis reduce-scatter, slow-axis all-to-all, owner
    scatter-add -- must equal the dense reduction exactly."""
    G, n_slow = 2, 2
    for op in (small_plan.proj, small_plan.back):
        smap, send2, recv2, w, v2 = build_hier_sparse_exchange(op, G)
        P, rpd = 4, op.rows_per_dev
        rng = np.random.default_rng(0)
        bands = rng.standard_normal((P, op.flat_rows))
        dense = np.zeros(op.n_rows_pad)
        for p in range(P):
            rm = op.row_map[p].reshape(-1)
            valid = rm < op.n_rows_pad
            bands[p][~valid] = 0.0
            np.add.at(dense, rm[valid], bands[p][valid])
        out = np.zeros((P, rpd))
        for t in range(n_slow):
            merged = np.zeros(G * w + 1)
            for f in range(G):
                np.add.at(merged, smap[f * n_slow + t],
                          bands[f * n_slow + t])
            merged = merged[:-1]
            for f in range(G):
                src = f * n_slow + t
                mine = np.append(merged[f * w:(f + 1) * w], 0.0)
                for t2 in range(n_slow):
                    q = f * n_slow + t2
                    tgt = np.zeros(rpd + 1)
                    np.add.at(tgt, recv2[q, t], mine[send2[src, t2]])
                    out[q] += tgt[:rpd]
        np.testing.assert_allclose(out.reshape(-1), dense, atol=1e-12)


def test_hilbert_socket_layout_improves_dedup(small_plan):
    """ROADMAP item: socket-aware chunk linearization.  Under the default
    fast-axis-major order, a socket's members own Hilbert chunks that are
    ``n_slow`` apart on the curve; with ``PartitionConfig(socket=G)`` they
    own *consecutive* chunks, whose band footprints shadow each other --
    the measured per-socket union (what the hier-sparse merged band
    ships) must strictly shrink."""
    geo = small_plan.geo
    a = build_system_matrix(geo)
    cfg = small_plan.cfg
    aware = build_plan(
        geo,
        PartitionConfig(
            n_data=cfg.n_data, tile=cfg.tile,
            rows_per_block=cfg.rows_per_block,
            nnz_per_stage=cfg.nnz_per_stage, socket=2,
        ),
        a=a,
    )

    def union_rows(op, fast):
        p = op.inds.shape[0]
        n_slow = p // fast
        total = 0
        for t in range(n_slow):
            rows = np.concatenate(
                [op.row_map[f * n_slow + t].reshape(-1)
                 for f in range(fast)]
            )
            total += np.unique(rows[rows < op.n_rows_pad]).size
        return total

    for name in ("proj", "back"):
        legacy = union_rows(getattr(small_plan, name), 2)
        hilbert = union_rows(getattr(aware, name), 2)
        assert hilbert < legacy, (name, legacy, hilbert)


def test_q8_operator_pricing_at_brain_scale():
    """Acceptance (ISSUE 8): the q8 tier halves the operator *value*
    stream at xct-brain scale -- 1 B/nnz + the per-(block, stage) scale
    table vs f16's 2 B/nnz -- and every byte-accounting consumer sees
    it: ``hbm_bytes`` drops by the vals share (indices stay 2 B, so the
    total lands at ~0.80x) and ``spmm_traffic`` prices a strictly
    smaller operator stream / higher arithmetic intensity."""
    from repro.kernels.traffic import op_segments_per_stage, spmm_traffic

    ds = DATASETS["xct-brain"]
    geo = XCTGeometry(n=ds.n, n_angles=ds.k)
    plan = estimate_plan(
        geo,
        PartitionConfig(n_data=512, tile=32, rows_per_block=64,
                        nnz_per_stage=64),
    )
    op = plan.proj
    h_f16 = op.hbm_bytes(value_bytes=2)
    h_q8 = op.hbm_bytes(value_bytes=1)
    meta = op.hbm_bytes(value_bytes=0)  # indices + winmap/row_map only
    # the value stream itself halves (scale table is B*S int32s against
    # B*S*R*K packed slots: < 0.1% overhead at the 64x64 block)
    assert 0.5 <= (h_q8 - meta) / (h_f16 - meta) <= 0.501
    assert 0.79 <= h_q8 / h_f16 <= 0.81
    traffic = {}
    for vb in (2, 1):
        _, b, s, r, k = op.inds.shape
        traffic[vb] = spmm_traffic(
            b, s, r, k, op.winmap.shape[-1], 16,
            storage_bytes=2, vals_bytes=vb,
            segments_per_stage=op_segments_per_stage(op),
            cols=op.cols_per_dev,
        )
    assert traffic[1]["operator_bytes"] < traffic[2]["operator_bytes"]
    assert traffic[1]["hbm_bytes"] < traffic[2]["hbm_bytes"]
    ai = {vb: t["flops"] / t["hbm_bytes"] for vb, t in traffic.items()}
    assert ai[1] > ai[2]


def test_q8_wire_halves_hier_sparse_dci():
    """Acceptance (ISSUE 8): int8 wire compression halves the
    hier-sparse slow hop at xct-brain scale -- each crossing row ships
    1 B instead of ``comm_bytes=2``, plus one f32 inv-scale per
    (slow-peer, fused slice) -- and ``comm_volume`` (the launch-layer
    view over ``CommPlan``) prices exactly that."""
    from repro.core.partition import hier_sparse_wire_bytes

    ds = DATASETS["xct-brain"]
    geo = XCTGeometry(n=ds.n, n_angles=ds.k)
    plan = estimate_plan(
        geo,
        PartitionConfig(n_data=512, tile=32, rows_per_block=64,
                        nnz_per_stage=64),
    )
    topo = sweep_topology(512)
    native = comm_volume(plan, "hier-sparse", 16, 2, topo)
    q8 = comm_volume(plan, "hier-sparse", 16, 2, topo, wire="q8")
    # the slow-axis all-to-all spans the node ICI rung and the DCI rung:
    # its payload compresses on both, the socket reduce-scatter (the
    # bulk of ICI) stays native -- so DCI halves, ICI dips slightly
    assert 0.5 < q8["dci"] / native["dci"] <= 0.51
    assert native["ici"] * 0.9 < q8["ici"] < native["ici"]
    # ... and the closed form agrees with the CommPlan pricing per op
    n_slow = math.prod(lv.size for lv in topo.levels[1:])
    want = {"native": 0.0, "q8": 0.0}
    for op in (plan.proj, plan.back):
        params = exchange_volume_params(op, topo)
        v2 = params["cross_rows"] // n_slow
        for wire in ("native", "q8"):
            want[wire] += hier_sparse_wire_bytes(
                v2, n_slow, 16, comm_bytes=2, wire=wire
            )
    assert native["dci"] == pytest.approx(want["native"])
    assert q8["dci"] == pytest.approx(want["q8"])


def test_q8_wire_rejected_off_the_hier_sparse_ladder():
    """wire="q8" compresses the hier-sparse slow-axis all-to-all; the
    dense ladders have no such hop, so the plan must refuse rather than
    silently price uncompressed wire."""
    topo = Topology.from_sizes(
        [("model", 2, "ici"), ("data", 2, "dci")]
    )
    with pytest.raises(ValueError, match="wire"):
        topo.plan("hier", wire="q8")
    with pytest.raises(ValueError, match="wire"):
        topo.plan("hier-sparse", wire="fp4")


def test_xct_analytic_fused_staging_eliminates_hbm_term(small_plan):
    """Acceptance: the dry-run cost model drops the staged-window HBM
    round trip on the fused path -- strictly less memory traffic and
    strictly higher arithmetic intensity at the paper's F=16."""
    from repro.core.recon import ReconConfig
    from repro.launch.dryrun import xct_analytic

    topo = Topology.from_sizes(
        [("model", 2, "ici"), ("data", 2, "dci")]
    )
    fused = xct_analytic(
        small_plan, ReconConfig(precision="mixed", comm_mode="hier"),
        topo, fuse=16, iters=1,
    )
    gather = xct_analytic(
        small_plan,
        ReconConfig(precision="mixed", comm_mode="hier",
                    staging="gather"),
        topo, fuse=16, iters=1,
    )
    assert fused["flops_dev"] == gather["flops_dev"]
    assert fused["hbm_dev"] < gather["hbm_dev"]
    ai_fused = fused["flops_dev"] / fused["hbm_dev"]
    ai_gather = gather["flops_dev"] / gather["hbm_dev"]
    assert ai_fused > ai_gather


def test_socket_sweep_picks_socket_aware_layout():
    """ROADMAP open item closed: the dry-run sweep comparing
    PartitionConfig(socket=1) vs socket=fast at xct-brain scale must
    pick the socket-aware layout (consecutive Hilbert chunks per socket
    shrink the hier-sparse merged band), which is what
    core.partition.default_socket now hands every driver."""
    from repro.core.partition import default_socket
    from repro.launch.dryrun import socket_sweep

    sw = socket_sweep()
    fast = sw["fast"]
    assert sw[f"socket={fast}"]["dci"] < sw["socket=1"]["dci"]
    assert sw[f"socket={fast}"]["ici"] < sw["socket=1"]["ici"]
    assert sw["winner"] == fast == default_socket(sw["p_data"], fast)


def test_sweep_coalesced_dma_issues_strictly_drop():
    """Acceptance (ISSUE 5): at xct-brain scale, the modeled DMA-issue
    count of the coalesced window staging is strictly below the
    per-row baseline in every cell of the §Perf sweep, and the
    dominant-cost memory term reflects the issue overhead
    (kernels.traffic.dma_issue_seconds)."""
    from repro.launch.xct_perf import sweep

    coal = sweep(iters=2)
    per = sweep(iters=2, dma="per_row")
    assert len(coal) == len(per) > 0
    for c, p in zip(coal, per):
        assert c["dma_issues"] < p["dma_issues"], (c["mode"], c["fuse"])
        # same bytes, fewer issues -> the memory term can only improve
        assert c["t_memory"] < p["t_memory"]


def test_xct_analytic_carries_dma_issue_term(small_plan):
    """The dry-run cost model prices window-DMA issues: coalesced
    (measured winsegs capacity) strictly below per-row, and the field
    is present for abstract consumers (lower_xct_cell rooflines)."""
    from repro.core.recon import ReconConfig
    from repro.launch.dryrun import xct_analytic

    topo = Topology.from_sizes([("model", 2, "ici"), ("data", 2, "dci")])
    coal = xct_analytic(
        small_plan, ReconConfig(precision="mixed", comm_mode="hier"),
        topo, fuse=16, iters=1,
    )
    per = xct_analytic(
        small_plan,
        ReconConfig(precision="mixed", comm_mode="hier", dma="per_row"),
        topo, fuse=16, iters=1,
    )
    assert coal["dma_issues_dev"] < per["dma_issues_dev"]
    # descriptor pricing differs (12 B/segment vs 4 B/row) but stays a
    # small fraction of the total memory term
    assert abs(coal["hbm_dev"] - per["hbm_dev"]) < 0.2 * per["hbm_dev"]
