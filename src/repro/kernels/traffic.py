"""One HBM-traffic model for the blocked-ELL SpMM, shared by every layer.

Historically four call sites hand-rolled the same byte accounting --
``ops.apply_operator`` (staging-chunk sizing), ``benchmarks/bench_spmm``
(arithmetic intensity), ``launch/xct_perf.sweep`` and
``launch/dryrun.xct_analytic`` (roofline memory term) -- and they had
already drifted (the chunk sizing assumed 4-byte windows while windows
are staged in the 2-byte storage dtype).  This module is now the single
source of truth.

Per minibatch of ``F`` fused slices, one device's shard moves:

  operator     B*S*R*K slots x (2 B index + ``sb`` B value)  -- one pass
  descriptors  what the window staging reads to address its copies:
               B*S*BUF window ids x 4 B (per-row DMA path and the
               gather baseline's XLA gather), or B*S*NSEG x 12 B
               ``{src, dst, len}`` segments (coalesced path -- with the
               run-extension slot order and bridged gaps NSEG ~ 9.2
               BUF**0.08, so this is LESS descriptor traffic on top of
               the issue-count win; under the legacy
               ``slot_order="first_seen"`` layout NSEG ~ 0.62 BUF and
               the segment table was slightly MORE
               descriptor traffic, the price of cutting the issue count;
               both terms are priced honestly)
  window       staging="fused":  B*S*BUF*Fp*wb  (each window row crosses
               HBM once: DMA'd straight into VMEM by the kernel)
               staging="gather": 2 x B*S*BUF*Fp*wb  (the XLA gather
               writes the [B, S, BUF, Fp] tensor to HBM, the kernel reads
               it back -- the extra full pass the fused path deletes)
               Rows are what the kernel moves: ``wb = max(4, sb)``
               (16-bit rows widen to f32, see ``xct_spmm.window_slab``)
               and ``Fp`` is F padded to the 128-lane vreg width
  slab         C*(F*sb + Fp*wb) for a shard of ``cols=C`` input rows
               that need widening or padding: the one pass that builds
               the kernel's 32-bit lane-padded view of the input slab
  band out     B*R*Fp x 4 B fp32, written by the kernel and read by the
               reduction scatter

Bytes alone do not price the buffer-load loop: every issued copy also
pays a fixed descriptor/issue overhead, which is why the kernel
coalesces run-length segments (one strided copy per run) instead of
copying row by row.  ``dma_issues`` counts the copies and
:func:`dma_issue_seconds` prices the whole transfer as

    t = issues * per_copy_overhead + bytes / bandwidth

Doctest -- the fused path strictly raises arithmetic intensity (the
acceptance criterion of the in-kernel-staging refactor; both at
``dma="per_row"`` so the descriptor terms match):

>>> g = spmm_traffic(8, 2, 64, 64, 768, 16, cols=4096,
...                  storage_bytes=2, staging="gather", dma="per_row")
>>> u = spmm_traffic(8, 2, 64, 64, 768, 16, cols=4096,
...                  storage_bytes=2, staging="fused", dma="per_row")
>>> u["hbm_bytes"] < g["hbm_bytes"]
True
>>> u["intensity"] > g["intensity"]
True
>>> g["hbm_bytes"] - u["hbm_bytes"] == g["window_bytes"] // 2
True
>>> u["window_bytes"] == 8 * 2 * 768 * 128 * 4.0  # f32 rows, 128 lanes
True
>>> u["slab_bytes"] == 4096 * (16 * 2 + 128 * 4.0)  # the widening pass
True
>>> u["mxu_flops"] == 2.0 * 8 * 2 * 64 * 768 * 128  # dense W @ window
True

and coalescing strictly drops the modeled issue count (the acceptance
criterion of the coalesced-DMA refactor); slot reordering drops it
further still (the acceptance criterion of the run-extension layout):

>>> c = spmm_traffic(8, 2, 64, 64, 768, 16, cols=4096, storage_bytes=2)
>>> c["dma_issues"] < u["dma_issues"]
True
>>> u["dma_issues"] == 8 * 2 * 768.0
True
>>> c["winmap_bytes"] == 8 * 2 * est_segments_per_stage(768) * 12.0
True
>>> legacy = spmm_traffic(8, 2, 64, 64, 768, 16, cols=4096,
...                       storage_bytes=2, slot_order="first_seen")
>>> c["dma_issues"] < legacy["dma_issues"]
True

Quantized operator values (``vals_bytes=1``: int8/fp8 + the int32
per-(block, stage) scale table) shrink the dominant operator stream --
3 B/nnz slot vs 4 B at f16 -- and raise intensity accordingly:

>>> q = spmm_traffic(8, 2, 64, 64, 768, 16, cols=4096,
...                  storage_bytes=2, vals_bytes=1)
>>> q["operator_bytes"] == 8 * 2 * 64 * 64 * 3.0 + 8 * 2 * 4.0
True
>>> q["operator_bytes"] < c["operator_bytes"]
True
>>> q["intensity"] > c["intensity"]
True
"""
from __future__ import annotations

import math

__all__ = [
    "LANES",
    "lane_pad",
    "window_row_bytes",
    "spmm_traffic",
    "staged_window_bytes",
    "dma_issue_seconds",
    "est_segments_per_stage",
    "op_segments_per_stage",
    "DMA_MODES",
    "PER_COPY_OVERHEAD_S",
]

STAGINGS = ("fused", "gather")
DMA_MODES = ("coalesced", "per_row")

# Fixed cost of issuing one async copy (descriptor setup + DMA engine
# dispatch).  A model parameter, O(100 ns) class on current parts -- the
# same order as the CUDA per-load index overhead Listing 1's buffer-load
# loop amortizes.  At F=16 a per-row window copy moves only ~32 B, so
# the staging loop is issue-bound at ANY plausible overhead; the sweeps
# expose exactly that (and what run-length coalescing claws back).
PER_COPY_OVERHEAD_S = 1e-7
# Vreg lane width: the kernel's fused-slice axis F is padded to it.
LANES = 128


def lane_pad(f: int) -> int:
    """F rounded up to whole 128-lane vregs (what the kernel moves)."""
    return -(-int(f) // LANES) * LANES


def window_row_bytes(f: int, storage_bytes: int) -> int:
    """Bytes of one window row as the kernel stages it: at least
    32-bit (a 16-bit row at a dynamic offset is not a sublane-aligned
    DMA), F padded to 128 lanes."""
    return lane_pad(f) * max(4, storage_bytes)


def staged_window_bytes(s: int, buf: int, f: int,
                        storage_bytes: int) -> int:
    """Transient HBM bytes of ONE row-block's gathered windows.

    Only the legacy gather path allocates this ``[S, BUF, Fp]`` tensor
    of kernel rows (per row-block of the scan chunk); the fused kernel's
    staging lives in VMEM (see ``xct_spmm.vmem_bytes``).
    """
    return s * buf * window_row_bytes(f, storage_bytes)


def est_segments_per_stage(buf: int, slot_order: str = "runs") -> int:
    """Analytic decomposed-segment count for one stage's window.

    For abstract plans (``estimate_plan``) no winmap exists to run-length
    encode, so the sweeps need a model.  The count depends on the plan's
    ``slot_order`` (see ``core.partition.PartitionConfig``):

    ``"runs"``
        Slots are assigned by greedy run extension over the
        Hilbert-sorted column set, and each stage window bridges its
        small column gaps up to BUF rows (``core.partition.
        _bridge_gaps``), so winmap entries form a few long
        ``{src, dst, len}`` runs and the segment count hardly grows with
        the window: measured means on built plans at n in [32, 96] sit
        on ``~9.2 x BUF**0.08`` (30 plan shapes, BUF 56-520, est/real in
        [0.44, 1.62]; the small plan is pinned to [0.5, 2] by
        ``tests/test_kernel_spmm.py::test_est_segments_calibrated``).

    ``"first_seen"``
        Legacy CSR-position layout: a stage samples its columns strided
        (slot position, not curve position), so runs stay short --
        measured means are 0.40-0.75 x BUF; the model uses the measured
        mid-band 0.62 x BUF.
    """
    if slot_order == "first_seen":
        return int(min(buf, max(1, math.ceil(0.62 * buf))))
    if slot_order != "runs":
        raise ValueError(
            f"unknown slot_order {slot_order!r}; one of ('runs', 'first_seen')"
        )
    return int(min(buf, max(1, math.ceil(9.2 * buf ** 0.08))))


def op_segments_per_stage(op) -> float | None:
    """Segments-per-stage of an operator shard, for the issue model.

    Real shards carry ``winsegs`` tables (``ops.winmap_segments``): the
    *measured mean* non-pad segment count per stage.  Abstract shards
    (``estimate_plan``) carry only the table shape: its capacity, which
    came from :func:`est_segments_per_stage`.  Returns ``None`` when the
    operator predates the tables (falls back to the analytic model).
    """
    ws = getattr(op, "winsegs", None)
    if ws is None:
        return None
    try:
        import numpy as _np

        arr = _np.asarray(ws)
    except TypeError:  # ShapeDtypeStruct and friends
        return float(ws.shape[-2])
    if arr.dtype == object or arr.ndim < 2:
        return float(ws.shape[-2])
    return float((arr[..., 2] > 0).sum(axis=-1).mean())


def dma_issue_seconds(
    issues: float,
    bytes_: float,
    bandwidth: float,
    per_copy_overhead: float = PER_COPY_OVERHEAD_S,
) -> float:
    """Seconds to move ``bytes_`` in ``issues`` async copies:
    ``issues x per_copy_overhead + bytes / bandwidth``.  The first term
    is what run-length coalescing shrinks (issues: B*S*BUF per-row ->
    B*S*NSEG) without touching the second."""
    return float(issues) * per_copy_overhead + float(bytes_) / bandwidth


def spmm_traffic(
    b: int,
    s: int,
    r: int,
    k: int,
    buf: int,
    f: int,
    *,
    cols: int,
    storage_bytes: int = 2,
    vals_bytes: int | None = None,
    staging: str = "fused",
    dma: str = "coalesced",
    segments_per_stage: float | None = None,
    slot_order: str = "runs",
    interpret_timed: bool = False,
) -> dict:
    """HBM bytes + FLOPs of one fused-minibatch SpMM over one shard.

    Returns a dict with the per-term byte counts, their sum
    (``hbm_bytes``), the useful FLOPs (``flops`` = 2 per nnz slot per
    slice), the arithmetic intensity of that useful work
    (``intensity``, FLOP/B), the FLOPs the MXU executes on the dense
    local operator block (``mxu_flops`` = 2 * R * BUF * Fp per stage:
    zeros of ``W[R, BUF]`` and pad lanes included), and the
    DMA issue count of the window staging (``dma_issues``): one copy
    per winmap row (``dma="per_row"``), one per run-length segment
    (``dma="coalesced"``; measured ``segments_per_stage`` from
    ``ops.winmap_segments`` when available, else the analytic
    :func:`est_segments_per_stage` for the plan's ``slot_order``), or
    one BlockSpec tile per stage for the gather baseline (XLA stages
    its windows in bulk).  Window and output rows are priced as the
    kernel moves them (:func:`window_row_bytes`, F lane-padded);
    ``cols`` (the shard's input rows C) prices the pass that builds the
    kernel's 32-bit lane-padded view of the input slab (``slab_bytes``,
    zero only when the rows are already 32-bit and 128-lane).

    ``vals_bytes`` is the width of the packed operator *values*
    (``Precision.vals_bytes``); ``None`` means same as the vector
    ``storage_bytes`` (every pre-quantization policy).  A 1-byte width
    adds the int32 per-(block, stage) dequantization-scale table to the
    descriptor stream (4 B per stage -- the scales ride scalar
    prefetch, but they still cross HBM once).

    ``interpret_timed=True`` declares that any wall-clock numbers the
    caller plans to compare against this model came from Pallas
    interpret mode, where async copies are emulated element loops and
    per-copy overhead is an artifact of the emulator, not the DMA
    engine.  The model warns once per call: do not RANK dma modes on
    interpret timings -- :func:`dma_issue_seconds` over the modeled
    issue counts is the authority (the autotuner's modeled tier does
    exactly that).
    """
    if staging not in STAGINGS:
        raise ValueError(
            f"unknown staging {staging!r}; one of {STAGINGS}"
        )
    if dma not in DMA_MODES:
        raise ValueError(f"unknown dma {dma!r}; one of {DMA_MODES}")
    if interpret_timed:
        import warnings

        warnings.warn(
            "spmm_traffic: timings taken in Pallas interpret mode emulate "
            "async copies as element loops -- per-copy cost there is an "
            "emulator artifact.  Do not rank dma modes on those timings; "
            "use dma_issue_seconds over the modeled issue counts instead.",
            RuntimeWarning,
            stacklevel=2,
        )
    slots = float(b) * s * r * k
    win_entries = float(b) * s * buf
    passes = 1 if staging == "fused" else 2
    seg = (
        float(segments_per_stage)
        if segments_per_stage is not None
        else float(est_segments_per_stage(buf, slot_order))
    )
    if staging == "gather":
        issues = float(b) * s  # one [BUF, F] BlockSpec tile per stage
        desc_bytes = win_entries * 4  # XLA gather reads the winmap
    elif dma == "per_row":
        issues = win_entries
        desc_bytes = win_entries * 4  # int32 winmap prefetch
    else:
        issues = float(b) * s * seg
        desc_bytes = float(b) * s * seg * 12  # {src, dst, len} int32
    vb = storage_bytes if vals_bytes is None else vals_bytes
    scale_bytes = float(b) * s * 4 if vb == 1 else 0.0
    row = window_row_bytes(f, storage_bytes)
    slab = 0.0
    if row != f * storage_bytes:
        slab = float(cols) * (f * storage_bytes + row)
    out = {
        "operator_bytes": slots * (2 + vb) + scale_bytes,
        "winmap_bytes": desc_bytes,
        "window_bytes": win_entries * row * passes,
        "slab_bytes": slab,
        "out_bytes": float(b) * r * lane_pad(f) * 4 * 2,
        "flops": 2.0 * slots * f,
        "mxu_flops": 2.0 * b * s * r * buf * lane_pad(f),
        "dma_issues": issues,
    }
    out["hbm_bytes"] = (
        out["operator_bytes"] + out["winmap_bytes"]
        + out["window_bytes"] + out["slab_bytes"] + out["out_bytes"]
    )
    out["intensity"] = out["flops"] / out["hbm_bytes"]
    return out
