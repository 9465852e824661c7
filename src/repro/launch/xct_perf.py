"""XCT §Perf sweep: comm ladder x fusing factor at Brain/Charcoal scale.

Iterates the paper's own levers on the paper's own workload using the
slot-exact cost model (launch/dryrun.xct_analytic) -- no compile needed,
so the full design space is swept: communication mode
(direct / rs / hier / sparse / hier-sparse) x fusing factor F x
precision.

  PYTHONPATH=src python -m repro.launch.xct_perf

Wire volumes are not computed here: every byte count flows from
``dist.CommPlan``'s per-link-class volume model (see docs/dist_api.md
for the formulas), with the sparse-mode table capacities supplied by
``core.partition.exchange_volume_params``.  ``sweep_topology`` builds
the meshless production ladder (16-wide minor ICI "socket", 16-wide
major ICI "node", DCI across pods of 256) that
``launch.mesh.make_production_mesh`` realizes with devices attached.

Example -- per-device wire bytes of one fused reduction at xct-brain
scale (P_d = 512 across two pods), per link class:

>>> from repro.configs.xct_datasets import DATASETS
>>> from repro.core.geometry import XCTGeometry
>>> from repro.core.partition import PartitionConfig, estimate_plan
>>> ds = DATASETS["xct-brain"]
>>> plan = estimate_plan(
...     XCTGeometry(n=ds.n, n_angles=ds.k),
...     PartitionConfig(n_data=512, tile=32, rows_per_block=64,
...                     nnz_per_stage=64),
... )
>>> topo = sweep_topology(512)
>>> print(topo.describe())
Topology over 512 devices
  socket: axis 'model' x16 (ici)
    node: axis 'data' x16 (ici)
  global: axis 'pod' x2 (dci)
>>> direct = comm_volume(plan, "direct", fuse=16, comm_bytes=2, topo=topo)
>>> hier = comm_volume(plan, "hier", fuse=16, comm_bytes=2, topo=topo)
>>> hs = comm_volume(plan, "hier-sparse", fuse=16, comm_bytes=2,
...                  topo=topo)
>>> round(direct["dci"] / 2**30, 2)  # full dense partial crosses DCI
5.31
>>> round(hier["dci"] / 2**30, 4)  # ladder: 1/(socket*node) crosses
0.0207
>>> hs["dci"] < direct["dci"]  # socket dedup beats dense over DCI
True
"""
from __future__ import annotations

import json

from ..configs.xct_datasets import DATASETS
from ..core.geometry import XCTGeometry
from ..core.partition import (
    PartitionConfig,
    default_socket,
    estimate_plan,
    exchange_volume_params,
)
from ..dist import MODES, Topology
from ..kernels.traffic import (
    dma_issue_seconds,
    op_segments_per_stage,
    spmm_traffic,
)
from .hlo_analysis import HW

__all__ = ["comm_volume", "sweep_topology", "sweep"]


def sweep_topology(p_data: int, fast: int = 16, pod: int = 256) -> Topology:
    """Meshless production ladder for ``p_data`` in-slice devices.

    Mirrors ``launch.mesh.make_production_mesh``: a ``fast``-wide minor
    ICI socket, a major ICI node level filling the pod, and a DCI level
    across pods when ``p_data`` spills past one pod.
    """
    f = min(fast, p_data)
    mid = max(1, min(p_data // f, pod // f))
    rest = p_data // (f * mid)
    if f * mid * rest != p_data:
        raise ValueError(
            f"p_data={p_data} does not factor into the production "
            f"ladder (fast={fast}, pod={pod}); got {f}x{mid}x{rest}"
        )
    sizes = [("model", f, "ici")]
    if mid > 1:
        sizes.append(("data", mid, "ici"))
    if rest > 1:
        sizes.append(("pod", rest, "dci"))
    return Topology.from_sizes(sizes)


def comm_volume(plan, mode: str, fuse: int, comm_bytes: int,
                topo: Topology, wire: str = "native") -> dict:
    """Per-device wire bytes per reduction, by link class, from CommPlan.

    Sums the proj and back operators' per-link volumes under ``topo``'s
    ladder; the table capacities for the sparse modes come from
    ``core.partition.exchange_volume_params`` (exact when the plan holds
    real shards, analytic for ``estimate_plan`` abstractions).
    ``wire="q8"`` (hier-sparse only) prices the int8-compressed slow-axis
    hop of ``dist.collectives.sparse_exchange``.
    """
    out = {"ici": 0.0, "dci": 0.0}
    for op in (plan.proj, plan.back):
        dense = float(op.n_rows_pad) * fuse * comm_bytes
        # the dense modes ignore the table capacities -- skip building
        # the (possibly exact, O(P^2 V)) exchange tables for them
        params = (
            exchange_volume_params(op, topo)
            if mode in ("sparse", "hier-sparse") else {}
        )
        cp = topo.plan(mode, wire=wire, comm_bytes=comm_bytes, **params)
        for link, b in cp.wire_bytes_by_link(dense).items():
            out[link] = out.get(link, 0.0) + b
    return out


def sweep(dataset="xct-brain", p_data=512, iters=30, staging="fused",
          dma="coalesced", precision="mixed", wire="native"):
    """Full mode x fuse sweep of the analytic cost model.

    ``staging`` selects the SpMM memory-traffic model: the default
    in-kernel staging moves each window row over HBM once; the legacy
    ``"gather"`` baseline pays the extra staged-window round trip
    (``kernels.traffic.spmm_traffic`` is the shared formula).  ``dma``
    selects the window-DMA issue model: the default run-length
    coalescing issues O(NSEG) copies per stage, the ``"per_row"``
    baseline O(BUF) -- the memory term prices both as
    ``issues x per_copy_overhead + bytes / bw``
    (``kernels.traffic.dma_issue_seconds``), so the sweep shows the
    issue-overhead win at production scale.  ``precision`` names the
    policy whose storage/vals/comm widths price the traffic (the
    quantized ``"q8"`` tier shrinks the dominant operator stream);
    ``wire="q8"`` additionally compresses the hier-sparse slow hop
    (skipped for modes without one).
    """
    from ..core.precision import get_policy

    ds = DATASETS[dataset]
    geo = XCTGeometry(n=ds.n, n_angles=ds.k)
    topo = sweep_topology(p_data)
    pcfg = PartitionConfig(
        n_data=p_data, tile=32, rows_per_block=64, nnz_per_stage=64,
        socket=default_socket(p_data, topo.levels[0].size),
    )
    plan = estimate_plan(geo, pcfg)
    pol = get_policy(precision)
    rows = []
    nnz_total = geo.n_rays * 1.195 * ds.n
    for mode in MODES:
        mode_wire = wire if mode == "hier-sparse" else "native"
        for fuse in (1, 4, 16, 64):
            sb = pol.storage_bytes  # mixed default: f16 storage + wire
            flops = 0.0
            hbm = 0.0
            issues = 0.0
            for op in (plan.proj, plan.back):
                _, b, s, r, k = op.inds.shape
                t = spmm_traffic(
                    b, s, r, k, op.winmap.shape[-1], fuse,
                    storage_bytes=sb, vals_bytes=pol.vals_bytes,
                    staging=staging, dma=dma,
                    segments_per_stage=op_segments_per_stage(op),
                    cols=op.cols_per_dev,
                )
                flops += iters * t["flops"]
                hbm += iters * t["hbm_bytes"]
                issues += iters * t["dma_issues"]
            cv = comm_volume(
                plan, mode, fuse, pol.comm_bytes, topo, wire=mode_wire
            )
            t_comp = flops / HW.peak_flops
            t_mem = dma_issue_seconds(issues, hbm, HW.hbm_bw)
            t_coll = iters * (
                cv["ici"] / HW.ici_bw + cv["dci"] / HW.dci_bw
            )
            useful = 4.0 * nnz_total * fuse * iters / p_data
            t_step = max(t_comp, t_mem, t_coll)
            rows.append({
                "dataset": dataset, "mode": mode, "fuse": fuse,
                "t_compute": t_comp, "t_memory": t_mem,
                "t_collective": t_coll, "dma_issues": issues,
                "dominant": max(
                    (("compute", t_comp), ("memory", t_mem),
                     ("collective", t_coll)), key=lambda kv: kv[1],
                )[0],
                "t_per_slice_ms": 1e3 * t_step / fuse,
                "roofline_fraction": (
                    useful / HW.peak_flops
                ) / t_step,
            })
    return rows


def main():
    rows = sweep()
    with open("results/xct_perf_sweep.json", "w") as f:
        json.dump(rows, f, indent=1)
    hdr = (f"{'mode':12s} {'F':>3s} {'comp_s':>8s} {'mem_s':>8s} "
           f"{'coll_s':>8s} {'dom':>10s} {'ms/slice':>9s} {'frac':>6s}")
    print(hdr)
    for r in rows:
        print(
            f"{r['mode']:12s} {r['fuse']:3d} {r['t_compute']:8.3f} "
            f"{r['t_memory']:8.3f} {r['t_collective']:8.3f} "
            f"{r['dominant']:>10s} {r['t_per_slice_ms']:9.2f} "
            f"{r['roofline_fraction']:6.3f}"
        )


if __name__ == "__main__":
    main()
