"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

No arrays are allocated: parameters, optimizer state, caches and batches
are ShapeDtypeStructs with NamedShardings; ``.lower().compile()`` proves
the distribution config is coherent (sharding match, collectives legal,
per-device memory known) and yields the cost/memory/collective numbers the
roofline analysis consumes.

Usage:
  python -m repro.launch.dryrun --arch qwen3-4b --shape train_4k
  python -m repro.launch.dryrun --all [--multi-pod] [--out results.json]
  python -m repro.launch.dryrun --xct xct-brain [--multi-pod]

The XCT cells pair the compiled-HLO numbers with ``xct_analytic``, a
slot-exact cost model over the static blocked-ELL shapes.  Its wire
volumes are not hand-rolled here: they flow from ``dist.CommPlan``'s
per-link-class model, resolved against the cell's ``dist.Topology`` (so
the dry-run, the §Perf sweep and ``benchmarks/bench_comms.py`` can never
disagree about what a mode ships over ICI vs DCI).

Example -- the analytic model is pure accounting, usable without any
devices attached (a meshless two-level ladder, one CG iteration):

>>> from repro.core.geometry import XCTGeometry
>>> from repro.core.partition import PartitionConfig, estimate_plan
>>> from repro.core.recon import ReconConfig
>>> from repro.dist import Topology
>>> plan = estimate_plan(
...     XCTGeometry(n=512, n_angles=256),
...     PartitionConfig(n_data=16, tile=32, rows_per_block=64,
...                     nnz_per_stage=64),
... )
>>> topo = Topology.from_sizes([("model", 8, "ici"), ("data", 2, "dci")])
>>> an = xct_analytic(
...     plan, ReconConfig(precision="mixed", comm_mode="hier"), topo,
...     fuse=4, iters=1,
... )
>>> sorted(an) == ['dci_dev', 'dma_issues_dev', 'flops_dev', 'hbm_dev',
...                'ici_dev']
True
>>> an["dci_dev"] == an["ici_dev"] / 8  # ladder: 1/|socket| crosses DCI
True
"""
# The two lines below MUST precede any jax import: jax locks the device
# count on first init, and only the dry-run wants 512 placeholder devices.
import os  # noqa: E402

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

import argparse
import dataclasses
import json
import time
import traceback
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from ..configs import ARCH_NAMES, SHAPES, get_config
from ..dist.sharding import batch_specs, cache_specs, param_specs, shardings
from ..models.lm import decode_step, loss_fn, make_train_step, prefill
from ..models.transformer import init_cache, init_params
from ..opt.adam import AdamW
from .hlo_analysis import analytic_min_hbm, analyze_collectives, roofline
from .mesh import make_production_mesh

DP_AXES = ("pod", "data")


def _sds_tree(tree, specs, mesh):
    return jax.tree.map(
        lambda leaf, spec: jax.ShapeDtypeStruct(
            np.shape(leaf),
            leaf.dtype if hasattr(leaf, "dtype") else jnp.float32,
            sharding=NamedSharding(mesh, spec),
        ),
        tree,
        specs,
        is_leaf=lambda x: hasattr(x, "dtype") or hasattr(x, "shape"),
    )


def _abstract_params(cfg, mesh):
    params = jax.eval_shape(partial(init_params, cfg), jax.random.key(0))
    specs = param_specs(params, mesh)
    return _sds_tree(params, specs, mesh), specs


def _useful_flops(cfg, shape_kind, tokens, n_dev):
    n_active = cfg.active_param_count()
    mult = 6.0 if shape_kind == "train" else 2.0
    return mult * n_active * tokens / n_dev


def _recurrent_flops_correction(cfg, kind, batch, seq) -> float:
    """Per-device extra FLOPs for time-scanned recurrent cells.

    ``cost_analysis`` counts a while-loop body once; the layer stack is
    unrolled for the cost pass, but the *time* recurrence of mLSTM/sLSTM
    cannot be (T up to 512k), so the missing (T-1) body repetitions are
    added analytically.  RG-LRU uses an associative scan (tree-expanded in
    HLO) and needs no correction.  State tensors are modeled VMEM-resident
    (no HBM-byte correction; recorded in EXPERIMENTS.md notes).
    """
    if kind == "decode":
        return 0.0
    per_tok = 0.0
    d = cfg.d_model
    for k in cfg.pattern_kinds:
        if k == "mlstm":
            dn = cfg.mlstm_expansion * d
            hd = dn // cfg.n_heads
            per_tok += cfg.n_heads * (5 * hd * hd + 6 * hd)
        elif k == "slstm":
            per_tok += 8 * d * d + 25 * d
    mult = 3.0 if kind == "train" else 1.0  # fwd + ~2x bwd
    return per_tok * batch * (seq - 1) * mult


def _build_cell(cfg, kind, seq, batch, mesh, dp):
    """Assemble (jitted fn, abstract args, token count) for one cell."""
    params_sds, pspecs_tree = _abstract_params(cfg, mesh)
    ndp = int(np.prod([mesh.shape[a] for a in dp])) if dp else 1
    bspec = P(dp) if dp and batch % ndp == 0 else P()

    if cfg.embed_inputs:
        inputs = jax.ShapeDtypeStruct(
            (batch, seq), jnp.int32, sharding=NamedSharding(mesh, bspec)
        )
    else:
        espec = P(*(tuple(bspec) + (None, None))) if len(bspec) else P()
        inputs = jax.ShapeDtypeStruct(
            (batch, seq, cfg.d_model), jnp.bfloat16,
            sharding=NamedSharding(mesh, espec),
        )
    labels = jax.ShapeDtypeStruct(
        (batch, seq), jnp.int32, sharding=NamedSharding(mesh, bspec)
    )

    if kind == "train":
        opt = AdamW()
        opt_sds = jax.eval_shape(opt.init, params_sds)
        opt_specs = {"m": pspecs_tree, "v": pspecs_tree, "count": P()}
        opt_sds = _sds_tree(opt_sds, opt_specs, mesh)
        step = make_train_step(cfg, opt)
        fn = jax.jit(step, donate_argnums=(0, 1))
        args = (params_sds, opt_sds, {"inputs": inputs, "labels": labels})
        tokens = batch * seq
    elif kind == "prefill":
        fn = jax.jit(lambda p, i: prefill(p, cfg, i))
        args = (params_sds, inputs)
        tokens = batch * seq
    else:  # decode
        cache = jax.eval_shape(lambda: init_cache(cfg, batch))
        cspecs = cache_specs(cache, cfg, mesh, dp)
        cache_sds = _sds_tree(cache, cspecs, mesh)
        if cfg.embed_inputs:
            token = jax.ShapeDtypeStruct(
                (batch, 1), jnp.int32, sharding=NamedSharding(mesh, bspec)
            )
        else:
            espec = (
                P(*(tuple(bspec) + (None, None))) if len(bspec) else P()
            )
            token = jax.ShapeDtypeStruct(
                (batch, 1, cfg.d_model), jnp.bfloat16,
                sharding=NamedSharding(mesh, espec),
            )
        pos = jax.ShapeDtypeStruct((), jnp.int32)
        fn = jax.jit(
            lambda p, c, t, q: decode_step(p, cfg, c, t, q),
            donate_argnums=(1,),
        )
        args = (params_sds, cache_sds, token, pos)
        tokens = batch
    return fn, args, tokens


def _hint_overrides(arch, dp, kind: str = "train"):
    """Sharding-hint config for the optimized (§Perf) variants.

    Score-sharding choice, from the §Perf measurements (iterations 3/5/7):
    kv divides the model axis -> shard kv; MQA (kv=1) and *prefill* cells
    -> query-time (context parallel; no backward resharding cost); train
    cells with total heads divisible -> merged-heads; else query-time.
    """
    cfg = get_config(arch)
    kv_ok = cfg.n_kv_heads % 16 == 0
    h_ok = cfg.n_heads % 16 == 0
    if kv_ok:
        q_shard, merge = False, False
    elif kind == "prefill" or cfg.n_kv_heads == 1:
        q_shard, merge = True, False
    elif h_ok:
        q_shard, merge = False, True
    else:
        q_shard, merge = True, False
    return {
        "shard_hints": True,
        "attn_heads_merge": merge,
        "attn_q_shard": q_shard,
        "dp_axes": dp,
    }


def _cost_numbers(arch, shape, multi_pod, n_layers, overrides=None):
    """FLOPs/bytes/collectives of a small *unrolled* variant (FD probe)."""
    seq, batch, kind = SHAPES[shape]
    mesh = make_production_mesh(multi_pod=multi_pod)
    dp = tuple(a for a in DP_AXES if a in mesh.shape)
    cfg = get_config(
        arch, max_cache=seq, scan_layers=False, n_layers=n_layers,
        remat="full" if kind == "train" else "none",
        **(overrides or {}),
    )
    fn, args, _ = _build_cell(cfg, kind, seq, batch, mesh, dp)
    with mesh:
        compiled = fn.lower(*args).compile()
    cost = compiled.cost_analysis()
    coll = analyze_collectives(
        compiled.as_text(), pod_size=256 if multi_pod else 0
    )
    return np.array([
        float(cost.get("flops", 0.0)),
        float(sum(v for k, v in cost.items()
                  if k.startswith("bytes accessed"))),
        float(coll["ici_bytes"]),
        float(coll["dci_bytes"]),
    ])


def lower_lm_cell(
    arch: str, shape: str, multi_pod: bool, fd_cost: bool = True,
    overrides: dict | None = None,
) -> dict:
    seq, batch, kind = SHAPES[shape]
    mesh = make_production_mesh(multi_pod=multi_pod)
    n_dev = mesh.size
    dp = tuple(a for a in DP_AXES if a in mesh.shape)
    cfg = get_config(
        arch,
        max_cache=seq,
        remat="full" if kind == "train" else "none",
        **(overrides or {}),
    )
    if kind == "decode" and not cfg.sub_quadratic and shape == "long_500k":
        return {
            "status": "skipped(full-attention)",
            "arch": arch, "shape": shape,
            "mesh": "2x16x16" if multi_pod else "16x16",
        }

    fn, args, tokens = _build_cell(cfg, kind, seq, batch, mesh, dp)

    t0 = time.time()
    with mesh:
        lowered = fn.lower(*args)
        t1 = time.time()
        compiled = lowered.compile()
    t2 = time.time()

    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis()
    pod_size = 256 if multi_pod else 0
    coll = analyze_collectives(compiled.as_text(), pod_size=pod_size)

    # --- finite-difference cost correction ----------------------------
    # The full model is lowered with scanned layers (compact HLO, fast
    # compile, true memory analysis), but cost_analysis counts a scan body
    # once.  Two small UNROLLED probes give per-period cost exactly:
    #   total = F(1 period [+rem]) + (n_periods - 1) * [F(2p) - F(1p)]
    period = len(cfg.block_pattern)
    n_per, rem = divmod(cfg.n_layers, period)
    if fd_cost and n_per >= 1:
        f1 = _cost_numbers(
            arch, shape, multi_pod, period + rem, overrides
        )
        f2 = _cost_numbers(
            arch, shape, multi_pod, 2 * period + rem, overrides
        )
        # clamp: near-zero per-layer deltas can FD to small negatives
        totals = np.maximum(f1 + (n_per - 1) * (f2 - f1), 0.0)
        flops_dev, hbm_dev = float(totals[0]), float(totals[1])
        ici_b, dci_b = float(totals[2]), float(totals[3])
        cost_source = "fd(unrolled 1p/2p)"
    else:
        flops_dev = float(cost.get("flops", 0.0))
        hbm_dev = float(
            sum(v for k, v in cost.items()
                if k.startswith("bytes accessed"))
        )
        ici_b, dci_b = coll["ici_bytes"], coll["dci_bytes"]
        cost_source = "scanned(body-once)"
    flops_dev += _recurrent_flops_correction(cfg, kind, batch, seq) / n_dev

    rf = roofline(
        flops_dev,
        hbm_dev,
        ici_b,
        dci_b,
        _useful_flops(cfg, kind, tokens, n_dev),
        hbm_bytes_analytic=analytic_min_hbm(cfg, kind, batch, seq, mesh),
    )
    return {
        "cost_source": cost_source,
        "status": "ok",
        "arch": arch,
        "shape": shape,
        "mesh": "2x16x16" if multi_pod else "16x16",
        "lower_s": round(t1 - t0, 1),
        "compile_s": round(t2 - t1, 1),
        "params": cfg.param_count(),
        "active_params": cfg.active_param_count(),
        "memory": {
            "temp_bytes": getattr(mem, "temp_size_in_bytes", None),
            "arg_bytes": getattr(mem, "argument_size_in_bytes", None),
            "out_bytes": getattr(mem, "output_size_in_bytes", None),
        },
        "flops_per_dev": flops_dev,
        "hbm_bytes_per_dev": hbm_dev,
        "ici_bytes_per_dev": ici_b,
        "dci_bytes_per_dev": dci_b,
        "collectives": coll,
        "roofline": rf,
    }


def lower_xct_cell(dataset: str, multi_pod: bool, iters: int = 2) -> dict:
    """Dry-run the XCT CG step at full dataset scale (abstract shards)."""
    from ..configs.xct_datasets import DATASETS
    from ..core.geometry import XCTGeometry
    from ..core.partition import (
        PartitionConfig, default_socket, estimate_plan,
    )
    from ..core.recon import ReconConfig, Reconstructor

    from ..dist import Topology

    ds = DATASETS[dataset]
    mesh = make_production_mesh(multi_pod=multi_pod)
    n_dev = mesh.size
    # Paper's optimal strategy: data-parallel only until memory fits; the
    # remaining axes carry batch parallelism over slices.
    p_data = min(ds.p_data, n_dev)
    if multi_pod and p_data >= 512:
        data_axes, batch_axes = ("model", "data", "pod"), ()
    elif multi_pod:
        data_axes, batch_axes = ("model", "data"), ("pod",)
    else:
        data_axes, batch_axes = ("model", "data"), ()
        p_data = min(p_data, 256)
    geo = XCTGeometry(n=ds.n, n_angles=ds.k)
    pcfg = PartitionConfig(
        n_data=p_data, tile=32, rows_per_block=64, nnz_per_stage=64,
        socket=default_socket(p_data, mesh.shape["model"]),
    )
    plan = estimate_plan(geo, pcfg)
    # the jnp oracle stands in for the kernel on the host-device dry
    # run (interpreting Pallas at 512 devices is too slow); a TPU
    # compiles the kernel itself
    rcfg = ReconConfig(precision="mixed_bf16", comm_mode="hier", fuse=16,
                       use_ref=jax.default_backend() != "tpu")
    topo = Topology.from_mesh(
        mesh, data_axes=data_axes, batch_axes=batch_axes
    )
    rec = Reconstructor(plan, topology=topo, cfg=rcfg, abstract=True)
    n_batch = rec.n_batch
    y_slices = rcfg.fuse * n_batch  # one fused I/O batch per batch group
    t0 = time.time()
    lowered, compiled = rec.lower_cg(y_slices, iters=iters)
    t1 = time.time()
    mem = compiled.memory_analysis()
    coll = analyze_collectives(
        compiled.as_text(), pod_size=256 if multi_pod else 0
    )
    an = xct_analytic(plan, rcfg, topo, y_slices // n_batch, iters)
    # useful flops: 2 flops/nnz * 2 ops (proj+back) * fuse slices * iters
    nnz_total = geo.n_rays * 1.195 * ds.n
    useful = 4.0 * nnz_total * (y_slices // n_batch) * iters / p_data
    rf = roofline(
        an["flops_dev"], an["hbm_dev"],
        an["ici_dev"] if not multi_pod else an["ici_dev"],
        an["dci_dev"] if multi_pod else 0.0,
        useful,
        hbm_bytes_analytic=an["hbm_dev"],
    )
    return {
        "status": "ok", "arch": dataset, "shape": f"cg{iters}x{y_slices}sl",
        "mesh": "2x16x16" if multi_pod else "16x16",
        "comm_mode": rcfg.comm_mode,
        "compile_s": round(t1 - t0, 1),
        "p_data": p_data,
        "memory": {
            "temp_bytes": getattr(mem, "temp_size_in_bytes", None),
            "arg_bytes": getattr(mem, "argument_size_in_bytes", None),
        },
        "flops_per_dev": an["flops_dev"],
        "hbm_bytes_per_dev": an["hbm_dev"],
        "ici_bytes_per_dev": an["ici_dev"],
        "dci_bytes_per_dev": an["dci_dev"] if multi_pod else 0.0,
        "collectives_hlo": coll,
        "analytic": an,
        "roofline": rf,
    }


def socket_sweep(
    dataset: str = "xct-brain",
    p_data: int = 512,
    fuse: int = 16,
    comm_bytes: int = 2,
) -> dict:
    """ROADMAP sweep: ``PartitionConfig(socket=1)`` vs ``socket=fast``.

    Compares the modeled hier-sparse wire volume of the legacy scattered
    chunk layout (socket members' footprints ~ independent draws) against
    the socket-aware layout (members own consecutive Hilbert chunks;
    adjacent-chunk union model, ``core.partition.estimate_hier_sparse``)
    at production scale, on the production ladder
    (``xct_perf.sweep_topology``).  The winner is what
    ``core.partition.default_socket`` hands every driver.

    >>> sw = socket_sweep()
    >>> sw["fast"]
    16
    >>> sw["socket=16"]["dci"] < sw["socket=1"]["dci"]
    True
    >>> sw["winner"]
    16
    """
    from ..configs.xct_datasets import DATASETS
    from ..core.geometry import XCTGeometry
    from ..core.partition import PartitionConfig, estimate_plan
    from .xct_perf import comm_volume, sweep_topology

    ds = DATASETS[dataset]
    geo = XCTGeometry(n=ds.n, n_angles=ds.k)
    topo = sweep_topology(p_data)
    fast = topo.levels[0].size
    out = {"dataset": dataset, "p_data": p_data, "fast": fast}
    for socket in (1, fast):
        plan = estimate_plan(
            geo,
            PartitionConfig(
                n_data=p_data, tile=32, rows_per_block=64,
                nnz_per_stage=64, socket=socket,
            ),
        )
        out[f"socket={socket}"] = comm_volume(
            plan, "hier-sparse", fuse, comm_bytes, topo
        )
    key = "dci" if out[f"socket={fast}"]["dci"] else "ici"
    out["winner"] = (
        fast
        if out[f"socket={fast}"][key] < out["socket=1"][key]
        else 1
    )
    return out


def xct_analytic(plan, rcfg, topo, fuse: int, iters: int) -> dict:
    """Slot-exact per-device cost model for the XCT CG step.

    The minibatch pipeline and CG loop are lax.scans (counted once by
    cost_analysis), so FLOPs/bytes are computed from the static blocked-ELL
    shapes instead, via the shared ``kernels.traffic.spmm_traffic`` model
    (2 FLOPs per nnz slot per fused slice, 4 B/slot operator reads, and
    the staging term matching ``rcfg.staging`` -- the default in-kernel
    staging has no HBM window round trip, so modeled arithmetic intensity
    is strictly higher than the legacy gather baseline).  The exchange
    volume per reduction is whatever ``topo.plan(rcfg.comm_mode)`` models
    for each link class -- one source of truth shared with the runtime
    collectives and ``benchmarks/bench_comms.py``.

    ``dma_issues_dev`` counts the window-staging copies the kernel
    issues (one per run-length segment under the default
    ``rcfg.dma="coalesced"``, one per winmap row under ``"per_row"``)
    so rooflines can price the fixed per-copy overhead with
    ``kernels.traffic.dma_issue_seconds``.
    """
    from ..core.partition import exchange_volume_params
    from ..core.precision import get_policy
    from ..kernels.traffic import op_segments_per_stage, spmm_traffic

    pol = get_policy(rcfg.precision)
    sb, cb = pol.storage_bytes, pol.comm_bytes
    wire = getattr(rcfg, "wire", "native")
    out = {"flops_dev": 0.0, "hbm_dev": 0.0, "ici_dev": 0.0,
           "dci_dev": 0.0, "dma_issues_dev": 0.0}
    for op in (plan.proj, plan.back):
        _, b, s, r, k = op.inds.shape
        segs = op_segments_per_stage(op)
        t = spmm_traffic(
            b, s, r, k, op.winmap.shape[-1], fuse, storage_bytes=sb,
            vals_bytes=pol.vals_bytes,
            staging=getattr(rcfg, "staging", "fused"),
            dma=getattr(rcfg, "dma", "coalesced"),
            segments_per_stage=segs, cols=op.cols_per_dev,
        )
        out["flops_dev"] += iters * t["flops"]
        out["hbm_dev"] += iters * t["hbm_bytes"]
        out["dma_issues_dev"] += iters * t["dma_issues"]
        dense = float(op.n_rows_pad) * fuse * cb
        params = (
            exchange_volume_params(op, topo)
            if rcfg.comm_mode in ("sparse", "hier-sparse") else {}
        )
        wl = topo.plan(
            rcfg.comm_mode, wire=wire, comm_bytes=cb, **params
        ).wire_bytes_by_link(dense)
        out["ici_dev"] += iters * wl.get("ici", 0.0)
        out["dci_dev"] += iters * wl.get("dci", 0.0)
    return out


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCH_NAMES)
    ap.add_argument("--shape", choices=tuple(SHAPES))
    ap.add_argument("--xct")
    ap.add_argument(
        "--socket-sweep", action="store_true",
        help="socket=1 vs socket=fast hier-sparse volume at xct scale",
    )
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument(
        "--hints", action="store_true",
        help="apply §Perf sharding hints (optimized variant)",
    )
    ap.add_argument("--out", default=None)
    args = ap.parse_args()

    mesh0 = make_production_mesh(multi_pod=args.multi_pod)
    dp0 = tuple(a for a in DP_AXES if a in mesh0.shape)

    def ov(arch, shape):
        if not args.hints:
            return None
        return _hint_overrides(arch, dp0, SHAPES[shape][2])

    results = []

    def run(fn, *a):
        try:
            r = fn(*a)
        except Exception as e:  # noqa: BLE001 -- record & continue
            r = {
                "status": f"error: {type(e).__name__}: {e}",
                "trace": traceback.format_exc()[-2000:],
            }
        results.append(r)
        print(json.dumps(r, default=str))
        if args.out:
            with open(args.out, "w") as f:
                json.dump(results, f, indent=1, default=str)

    if args.socket_sweep:
        run(socket_sweep, args.xct or "xct-brain")
    elif args.xct:
        run(lower_xct_cell, args.xct, args.multi_pod)
    elif args.all:
        for arch in ARCH_NAMES:
            for shape in SHAPES:
                print(f"--- {arch} x {shape} ---", flush=True)
                run(
                    lower_lm_cell, arch, shape, args.multi_pod, True,
                    ov(arch, shape),
                )
    else:
        run(
            lower_lm_cell, args.arch, args.shape, args.multi_pod, True,
            ov(args.arch, args.shape),
        )


if __name__ == "__main__":
    main()
