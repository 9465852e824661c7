"""Paper Fig. 9: XCT-optimized SpMM speedup + roofline vs fusing factor.

Sweeps the minibatch (slice-fusing) size F across precision policies on a
real blocked-ELL shard -- the ladder now runs down to the quantized
``q8`` rung (int8 vals + per-block power-of-two scales dequantized
inline; rows carry the measured resident ``hbm_bytes`` of the shard at
each width, which the CI gate guards downward) -- for the staging x DMA
A/B ladder: ``fused`` (the
kernel streams each stage's window HBM -> VMEM itself with run-length
*coalesced* copies -- the production path), ``fused-perrow`` (same
kernel, one copy per window row -- the DMA-issue baseline the coalescing
refactor beats) and the legacy ``gather`` baseline (XLA gather
materializes the window tensor in HBM first -- one extra full pass over
the staged data).  CPU wall time measures the *relative* effect of
fusing (operator elements amortized over F slices -- the paper's
register reuse); the derived column reports arithmetic intensity, the
projected TPU-roofline GFLOP/s per chip, and the modeled DMA issue
count, all straight from the shared traffic model
``repro.kernels.traffic.spmm_traffic``.  The fused rows also carry the
*measured* segments-per-stage statistics of the shard's real winmap
(``ops.winmap_segments``): mean segments per stage and the copy-length
histogram, so the JSON artifact records how long the Hilbert runs
actually are.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.geometry import XCTGeometry, build_system_matrix
from repro.core.partition import PartitionConfig, build_plan
from repro.core.precision import quantize_block_vals
from repro.kernels.ops import (
    apply_operator,
    dma_issue_count,
    segment_histogram,
    sort_segments_by_class,
    winmap_segments,
)
from repro.kernels.traffic import spmm_traffic
from repro.launch.hlo_analysis import PEAKS
from repro.obs import export as obs_export
from repro.obs import trace as obs_trace
from repro.obs.trace import span as obs_span

from .common import emit, timeit

# the modeled roofline column prices the TPU v5e at each row's
# arithmetic intensity (a model, not a measurement of this device)
V5E = PEAKS["TPU v5 lite"]


def _seg_stats(op):
    """Measured DMA statistics of a shard's winmap run-length tables.

    Returns ``(per_stage_mean, mean_len, issues, hist_tok)``:
    segments-per-stage mean (what the traffic model consumes), mean
    copy LENGTH in winmap entries per issued copy (the ``segs_mean``
    column the CI gate guards upward -- longer runs = better
    coalescing), the total issue count of device 0's shard
    (``dma_issues``, guarded downward), and the length histogram.
    """
    segs = op.winsegs[0]  # [B, S, NSEG, 3] of device 0
    per_stage = (segs[..., 2] > 0).sum(axis=-1)  # [B, S]
    issues = dma_issue_count(segs)
    mean_len = op.winmap[0].size / max(issues, 1)
    hist = segment_histogram(segs)
    # leading "L" keeps benchmarks.common._parse_derived from mangling
    # the token into a float
    hist_tok = "|".join(
        f"L{ln}:{ct}" for ln, ct in sorted(hist.items())
    )
    return float(per_stage.mean()), float(mean_len), issues, hist_tok


def calibrate_per_copy_overhead(
    buf: int = 256, b: int = 4, s: int = 2, r: int = 32, k: int = 32,
    f: int = 8, reps: int = 3,
):
    """Measure PER_COPY_OVERHEAD_S with a controlled micro-sweep.

    Two synthetic winmaps with IDENTICAL shape and byte volume but
    opposite run structure drive the same fused kernel: ``contig``
    (arange -> a handful of power-of-two runs) vs ``strided``
    (lo/hi interleave -> every run is length 1, ~BUF issues per
    window).  Same bytes moved, so the wall-clock delta divided by the
    issue-count delta isolates the fixed cost of issuing one copy:

        per_copy_overhead = (t_hi - t_lo) / (issues_hi - issues_lo)

    On a real accelerator this calibrates the DMA-engine dispatch cost
    the traffic model's constant stands in for; under Pallas interpret
    mode (any CPU run) the copies are emulated element loops, so the
    number is an *emulator* artifact -- it is still returned (the
    calibration plumbing is exercised end to end, and the autotuner's
    passport records it) but tagged ``overhead_source=
    "measured-interpret"``, and the traffic model is told timings were
    taken under interpret so it can warn against ranking dma modes on
    them (``spmm_traffic(..., interpret_timed=True)``).

    Returns a dict with ``per_copy_overhead_s``, ``overhead_source``,
    and the raw sweep points.
    """
    import jax

    rng = np.random.default_rng(0)
    inds = jnp.asarray(
        rng.integers(0, buf, size=(b, s, r, k)).astype(np.int16)
    )
    vals = jnp.asarray(
        rng.random(size=(b, s, r, k)).astype(np.float16)
    )
    x = jnp.asarray(rng.normal(size=(buf, f)).astype(np.float32))
    contig = np.broadcast_to(
        np.arange(buf, dtype=np.int32), (b, s, buf)
    ).copy()
    half = buf // 2
    strided = np.empty(buf, np.int32)
    strided[0::2] = np.arange(half, dtype=np.int32)
    strided[1::2] = half + np.arange(buf - half, dtype=np.int32)
    strided = np.broadcast_to(strided, (b, s, buf)).copy()
    pts = {}
    for tag, wm in (("contig", contig), ("strided", strided)):
        segs, off = sort_segments_by_class(winmap_segments(wm), buf)
        fn = jax.jit(
            lambda xx, i=inds, v=vals, w=jnp.asarray(wm),
            sg=jnp.asarray(segs), so=jnp.asarray(off):
            apply_operator(i, v, w, xx, staging="fused",
                           dma="coalesced", winsegs=sg, segoff=so)
        )
        pts[tag] = {
            "issues": dma_issue_count(segs),
            "seconds": timeit(fn, x, reps=reps),
        }
    d_issues = pts["strided"]["issues"] - pts["contig"]["issues"]
    d_t = pts["strided"]["seconds"] - pts["contig"]["seconds"]
    overhead = max(d_t, 0.0) / max(d_issues, 1)
    # the kernel runs interpreted everywhere but on a TPU (xct_spmm)
    interpret = jax.default_backend() != "tpu"
    if interpret:
        # fires the shared model's interpret-timing warning exactly
        # once per calibration: these seconds must not rank dma modes
        spmm_traffic(b, s, r, k, buf, f, cols=buf,
                     interpret_timed=True)
    return {
        "per_copy_overhead_s": float(overhead),
        "overhead_source": (
            "measured-interpret" if interpret else "measured"
        ),
        **{f"{t}_{m}": pts[t][m] for t in pts for m in pts[t]},
    }


def run(n: int = 64, fusings=(1, 2, 4, 8, 16, 32), quick: bool = False,
        ab: bool = True, trace: bool = False):
    if trace:
        obs_trace.enable()
    geo = XCTGeometry(n=n, n_angles=n // 2)
    a = build_system_matrix(geo)
    plan = build_plan(
        geo,
        PartitionConfig(n_data=1, tile=8, rows_per_block=32,
                        nnz_per_stage=32),
        a=a,
    )
    op = plan.proj
    inds = jnp.asarray(op.inds[0])
    vals = jnp.asarray(op.vals[0])
    winmap = jnp.asarray(op.winmap[0])
    winsegs = jnp.asarray(op.winsegs[0])
    segoff = jnp.asarray(op.segoff[0])
    q_vals, q_scales = quantize_block_vals(vals, jnp.int8)
    segs_stage, segs_mean, _, segs_hist = _seg_stats(op)
    _, b, s, r, k = op.inds.shape
    buf = op.winmap.shape[-1]
    rng = np.random.default_rng(0)
    if quick:
        fusings = tuple(fusings)[:3]
    base_t = None
    # the quantized rung: int8 vals + per-block scales through the same
    # kernel (scales ride scalar prefetch); vectors stay f16
    policies = (
        [("single", jnp.float32), ("mixed", jnp.float16),
         ("q8", jnp.float16)]
        if quick
        else [
            ("double", jnp.float32),  # f64 n/a on TPU; f32 stands in
            ("single", jnp.float32),
            ("half", jnp.float16),
            ("mixed", jnp.float16),
            ("q8", jnp.float16),
        ]
    )
    # the A/B ladder: (row tag, staging, dma)
    paths = [("fused", "fused", "coalesced")]
    if ab:
        paths += [
            ("fused-perrow", "fused", "per_row"),
            ("gather", "gather", "coalesced"),
        ]
    for prec, sdt in policies:
        cdt = jnp.float16 if prec == "half" else jnp.float32
        quant = prec == "q8"
        v_run = q_vals if quant else vals
        sc_run = q_scales if quant else None
        vb = 1 if quant else jnp.dtype(sdt).itemsize
        # measured resident footprint of the real shard at this width
        # (value stream + scale table for the quantized rung)
        op_hbm = op.hbm_bytes(value_bytes=vb)
        for f in fusings:
            x = jnp.asarray(
                rng.normal(size=(op.cols_per_dev, f)).astype(np.float32)
            )
            for tag, staging, dma in paths:
                if quant and staging != "fused":
                    continue  # gather baseline dequantizes eagerly
                fn = jax.jit(
                    lambda xx, i=inds, v=v_run, w=winmap, sg=winsegs,
                    so=segoff, sd=sdt, cd=cdt, st=staging, dm=dma,
                    sc=sc_run:
                    apply_operator(i, v, w, xx, storage_dtype=sd,
                                   compute_dtype=cd, staging=st,
                                   dma=dm, winsegs=sg, segoff=so,
                                   scales=sc)
                )
                # the span wraps the timed cell, never the kernel
                # inner loop: with tracing off this is two clock reads
                # per CELL (the no-overhead acceptance)
                with obs_span(
                    f"spmm/{prec}/{tag}", f=f
                ):
                    t = timeit(fn, x, reps=3 if not quick else 1)
                tr = spmm_traffic(
                    b, s, r, k, buf, f,
                    storage_bytes=jnp.dtype(sdt).itemsize,
                    vals_bytes=vb,
                    staging=staging, dma=dma,
                    segments_per_stage=segs_stage,
                    cols=op.cols_per_dev,
                )
                flops = tr["flops"]
                if base_t is None:
                    base_t = t / flops  # s/flop at the F=1 baseline
                ai = tr["intensity"]
                v5e_gflops = min(V5E.peak_flops, ai * V5E.hbm_bw) / 1e9
                extra = ""
                if staging == "fused":
                    extra = (
                        f" dma_issues={tr['dma_issues']:.0f}"
                        f" segs_mean={segs_mean:.1f}"
                        f" seg_hist={segs_hist}"
                    )
                emit(
                    f"spmm_fusing/{prec}/{tag}/F={f}",
                    t * 1e6,
                    # throughput speedup per unit work (Fig. 9a metric)
                    f"speedup={base_t / (t / flops):.2f}x "
                    f"ai={ai:.2f}flop/B "
                    f"hbm_bytes={op_hbm} "
                    f"v5e_roofline={v5e_gflops:.0f}GF/s" + extra,
                )
    if trace:
        obs_export.write_chrome_trace("TRACE_spmm_fusing.json")
        print("trace written to TRACE_spmm_fusing.json")


if __name__ == "__main__":
    import argparse

    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    ap.add_argument(
        "--no-ab", dest="ab", action="store_false",
        help="skip the per-row / gather baseline arms",
    )
    ap.add_argument(
        "--trace", action="store_true",
        help="record repro.obs spans; writes TRACE_spmm_fusing.json",
    )
    args = ap.parse_args()
    run(quick=args.quick, ab=args.ab, trace=args.trace)
