"""jit'd wrappers around the XCT SpMM kernel + the window-DMA builder.

``apply_operator`` is the single-device (shard-local) fused
projection/backprojection.  The default path (``staging="fused"``,
``dma="coalesced"``) hands the whole local slab to the Pallas kernel,
which streams each stage's window from HBM into VMEM itself (the
paper's Listing 1 buffer-load loop) -- one HBM pass over operator data
per minibatch, no staged window tensor, no transient-budget chunking --
and issues one strided copy per *run-length segment* of consecutive
source rows instead of one per row (``winmap_segments`` below;
Hilbert-ordered columns make the runs long, so DMA issue overhead is
amortized like Listing 1 amortizes index loads).

``staging="gather"`` keeps the legacy two-pass emulation for A/B
benchmarking: an XLA gather materializes the ``[B, S, BUF, F]`` windows
in HBM before the kernel runs, bounded by a ~64 MB transient budget
(chunked over row-blocks with ``lax.scan``).  ``dma="per_row"`` keeps
the one-copy-per-window-row fused path for the same purpose.  The
oracle equivalent lives in ``ref.py``; ``use_ref=True`` swaps it in so
every higher layer can be validated against pure jnp with one flag.
"""
from __future__ import annotations

import numpy as np

import jax
import jax.numpy as jnp

from . import ref
from .traffic import DMA_MODES, STAGINGS, staged_window_bytes
from .xct_spmm import (
    _dma_classes,
    spmm_block_ell,
    spmm_block_ell_staged,
    window_slab,
)

__all__ = [
    "apply_operator",
    "winmap_segments",
    "sort_segments_by_class",
    "segment_histogram",
    "dma_issue_count",
]


def winmap_segments(winmap, pad_to: int = 8) -> np.ndarray:
    """Run-length encode a ``[..., BUF]`` winmap into DMA segments.

    Every maximal run of *consecutive* source rows in a stage's window
    (``winmap[..., j+1] == winmap[..., j] + 1``) becomes one coalesced
    copy ``x[src : src+len] -> win[dst : dst+len]``; runs are then split
    into power-of-two pieces (largest first) because Pallas DMA extents
    are static -- the kernel unrolls over the possible length classes
    and issues each piece with one ``pl.when``-guarded copy.  Hilbert
    ordering (``core.partition``) keeps runs long, so a production
    stage's window moves in O(NSEG) issues instead of O(BUF).

    Args:
      winmap: ``[..., BUF]`` int array of device-local input column ids
        (any leading batch dims; the shards use ``[B, S, BUF]``).
      pad_to: pad the per-stage segment capacity to a multiple of this.

    Returns:
      ``[..., NSEG, 3]`` int32: ``{src_start, dst_start, len}`` per
      segment, ``len`` a power of two; pad slots have ``len == 0`` (the
      kernel skips them).  NSEG is the max decomposed-segment count over
      all leading indices, padded to ``pad_to``.
    """
    wm = np.asarray(winmap)
    if wm.ndim < 1:
        raise ValueError("winmap must have a trailing BUF dimension")
    lead, buf = wm.shape[:-1], wm.shape[-1]
    flat = wm.reshape(-1, buf).astype(np.int64)
    n = flat.shape[0]
    if n == 0:
        return np.zeros((*lead, pad_to, 3), np.int32)
    # fully vectorized (plan builds call this for every shard): run
    # boundaries, then one fill pass per power-of-two length class
    isbrk = np.ones((n, buf), bool)
    if buf > 1:
        isbrk[:, 1:] = np.diff(flat, axis=1) != 1
    row_id, st = np.nonzero(isbrk)  # runs, row-major order
    en = np.empty_like(st)
    en[:-1] = st[1:]
    en[-1] = buf
    en[np.flatnonzero(np.diff(row_id))] = buf  # last run of each row
    length = en - st
    src0 = flat[row_id, st]
    nbits = int(buf).bit_length()
    counts = np.zeros_like(length)  # popcount = decomposed pieces/run
    for b in range(nbits):
        counts += (length >> b) & 1
    # piece slot = (pieces of prior runs in the row) + (larger pieces
    # of this run): largest-first order, matching the kernel's classes
    cum = np.cumsum(counts) - counts
    firsts = np.concatenate(([0], np.flatnonzero(np.diff(row_id)) + 1))
    runs_per_row = np.diff(np.append(firsts, row_id.size))
    run_off = cum - np.repeat(cum[firsts], runs_per_row)
    totals = np.add.reduceat(counts, firsts)
    nseg = pad_to * -(-int(totals.max()) // pad_to)
    out = np.zeros((n, nseg, 3), np.int32)
    for b in range(nbits):
        sel = ((length >> b) & 1) == 1
        if not sel.any():
            continue
        ln = length[sel]
        off = (ln >> (b + 1)) << (b + 1)  # sum of the larger pieces
        rank = np.zeros_like(ln)
        for b2 in range(b + 1, nbits):
            rank += (ln >> b2) & 1
        slot = run_off[sel] + rank
        out[row_id[sel], slot, 0] = src0[sel] + off
        out[row_id[sel], slot, 1] = st[sel] + off
        out[row_id[sel], slot, 2] = 1 << b
    return out.reshape(*lead, nseg, 3)


def sort_segments_by_class(
    winsegs, buf: int
) -> tuple[np.ndarray, np.ndarray]:
    """Sort every stage's segment table by descending copy length and
    build the per-class offset table the fused kernel consumes.

    ``winmap_segments`` emits power-of-two pieces in run order; the
    kernel, whose DMA extents must be static, would then have to test
    every slot against every length class (O(classes x NSEG) issue work
    per window -- the interpret-mode 10x inversion ``bench_spmm``
    measured).  Grouping slots by class instead lets the kernel run one
    ``fori_loop`` per class with *dynamic bounds* ``[off[c], off[c+1])``
    over exactly that class's slots: total issue work is O(real
    segments), unconditionally.

    Args:
      winsegs: ``[..., NSEG, 3]`` table from :func:`winmap_segments`.
      buf: the window height (``winmap.shape[-1]``) -- fixes the static
        class list ``xct_spmm._dma_classes(buf)`` the offsets index.

    Returns:
      ``(sorted_segs [..., NSEG, 3], offsets [..., NCLS+1])`` int32:
      slots ``[offsets[i], offsets[i+1])`` hold exactly the segments of
      length ``classes_desc[i]`` (classes in descending order);
      ``offsets[-1]`` ends the real segments, pad slots (len 0) follow.
    """
    segs = np.asarray(winsegs)
    lead, nseg = segs.shape[:-2], segs.shape[-2]
    flat = segs.reshape(-1, nseg, 3)
    order = np.argsort(-flat[..., 2], axis=1, kind="stable")
    srt = np.take_along_axis(flat, order[..., None], axis=1)
    classes = _dma_classes(buf)[::-1]
    lens = srt[..., 2]
    off = np.empty((flat.shape[0], len(classes) + 1), np.int32)
    for i, ln in enumerate(classes):
        off[:, i] = (lens > ln).sum(axis=1)
    off[:, -1] = (lens > 0).sum(axis=1)
    return (
        srt.astype(np.int32).reshape(*lead, nseg, 3),
        off.reshape(*lead, len(classes) + 1),
    )


def dma_issue_count(winsegs) -> int:
    """Copies the coalesced kernel issues per window pass: one per
    non-pad segment (pad slots have ``len == 0``)."""
    return int((np.asarray(winsegs)[..., 2] > 0).sum())


def segment_histogram(winsegs) -> dict:
    """``{copy_len: count}`` over the non-pad segments of a table --
    the measured segments-per-stage histogram ``bench_spmm`` reports."""
    lens = np.asarray(winsegs)[..., 2].ravel()
    lens = lens[lens > 0]
    uniq, cnt = np.unique(lens, return_counts=True)
    return {int(u): int(c) for u, c in zip(uniq, cnt)}


def _gather_blocks_per_call(b, s, buf, f, bytes_per, budget=64 << 20):
    """Row-blocks whose gathered windows fit a ~64 MB transient budget.

    Only the legacy gather path needs this: it materializes
    ``[bpc, S, BUF, F]`` windows per inner-scan step in the *storage*
    dtype (``bytes_per`` is that dtype's itemsize -- sizing from 4 bytes
    under-chunked by 2x in half/mixed modes).  Must divide ``b`` (B is
    padded to a multiple of 8 by the partitioner).
    """
    per_block = staged_window_bytes(s, buf, f, bytes_per)
    want = max(1, budget // max(1, per_block))
    if want >= b:
        return b
    for d in range(min(want, b), 0, -1):
        if b % d == 0:
            return d
    return 1


def apply_operator(
    inds,
    vals,
    winmap,
    x_loc,
    *,
    storage_dtype=jnp.float16,
    compute_dtype=jnp.float32,
    use_ref: bool = False,
    interpret: bool | None = None,
    staging: str = "fused",
    dma: str = "coalesced",
    winsegs=None,
    segoff=None,
    smem_budget: int | None = None,
    blocks_per_call: int | None = None,
    scales=None,
    op: str | None = None,
):
    """Shard-local fused SpMM: returns the fp32 partial rows [B*R, F].

    Args:
      inds: [B, S, R, K] int16 window-local indices.
      vals: [B, S, R, K] float32 master lengths (cast to ``storage_dtype``
        here -- the 2-byte HBM representation of the paper's packing --
        unless already narrow).
      winmap: [B, S, BUF] device-local input column ids.
      x_loc: [C, F] local input slab (any float dtype; cast to
        ``storage_dtype``, computed in ``compute_dtype``).
      staging: "fused" (default) stages windows inside the kernel --
        double-buffered HBM->VMEM copies, no intermediate tensor;
        "gather" is the legacy two-pass XLA-gather path (A/B baseline).
      dma: "coalesced" (default) issues one strided copy per run-length
        segment of the winmap; "per_row" keeps the one-copy-per-row
        A/B baseline.  Fused staging only.
      winsegs: precomputed ``winmap_segments(winmap)``; required when
        ``winmap`` is a traced value (e.g. inside ``shard_map`` --
        ``OperatorShards.winsegs`` carries it), computed here otherwise.
      segoff: per-class offsets into a class-sorted ``winsegs`` (from
        ``sort_segments_by_class``; ``OperatorShards.segoff``).  When
        given, the kernel loops each length class over exactly its own
        slots (O(segments) issue work); when omitted with a concrete
        ``winmap``, both tables are built here; a traced ``winsegs``
        without ``segoff`` falls back to the per-slot class-test kernel.
      smem_budget: per-call SMEM budget for the scalar prefetch; the
        kernel chunks row-blocks to fit (see ``xct_spmm``).
      blocks_per_call: [deprecated -- only the gather path chunks]
        row-blocks per inner scan step; auto-sized when None.
      scales: [B, S] int32 per-block dequantization exponents
        (``core.precision.quantize_block_vals``).  When given, ``vals``
        is already-packed int8/fp8 and is passed through untouched; the
        fused kernel dequantizes inline in its FMA loop, the ref/gather
        paths widen to f32 up front (same arithmetic, one extra HBM
        round trip -- A/B baselines only).
      op: the operator's tag, ``"proj"`` (A) or ``"back"`` (A^T): names
        every kernel call so a device trace tells the two apart.
    """
    if staging not in STAGINGS:
        raise ValueError(
            f"unknown staging {staging!r}; one of {STAGINGS}"
        )
    if dma not in DMA_MODES:
        raise ValueError(f"unknown dma {dma!r}; one of {DMA_MODES}")
    quantized = scales is not None
    vals_s = vals if quantized else vals.astype(storage_dtype)
    x_s = x_loc.astype(storage_dtype)
    b, s, r, k = inds.shape
    buf = winmap.shape[-1]
    f = x_loc.shape[-1]

    if quantized and (use_ref or staging != "fused"):
        from repro.core.precision import dequantize_block_vals

        vals_s = dequantize_block_vals(vals, scales, jnp.float32)

    if use_ref:
        return ref.spmm_ref(
            inds, vals_s, winmap, x_s, compute_dtype=compute_dtype
        ).astype(jnp.float32)

    if staging == "fused":
        if dma == "coalesced" and winsegs is None:
            try:
                winsegs, segoff = sort_segments_by_class(
                    winmap_segments(winmap), buf
                )
            except jax.errors.TracerArrayConversionError as e:
                raise ValueError(
                    "dma='coalesced' under tracing needs precomputed "
                    "segments: pass winsegs=winmap_segments(winmap) "
                    "(OperatorShards.winsegs carries them per shard)"
                ) from e
        out = spmm_block_ell(
            inds, vals_s, winmap, x_s,
            compute_dtype=compute_dtype, interpret=interpret,
            winsegs=winsegs if dma == "coalesced" else None,
            segoff=segoff if dma == "coalesced" else None,
            smem_budget=smem_budget,
            scales=scales,
            op=op,
        )
        return out.reshape(b * r, f)

    # --- legacy gather staging (A/B benchmarking baseline) -------------
    x_rows = window_slab(x_s)  # the kernel's 32-bit lane-padded rows

    def one_chunk(ic, vc, wc):
        window = jnp.take(x_rows, wc, axis=0)  # staging gather (HBM)
        return spmm_block_ell_staged(
            ic, vc, window, compute_dtype=compute_dtype,
            interpret=interpret, op=op,
        )[..., :f]

    bpc = blocks_per_call or _gather_blocks_per_call(
        b, s, buf, f, jnp.dtype(storage_dtype).itemsize
    )
    if bpc >= b:
        return one_chunk(inds, vals_s, winmap).reshape(b * r, f)

    n_chunk = b // bpc

    def step(_, args):
        return None, one_chunk(*args)

    _, outs = jax.lax.scan(
        step,
        None,
        (
            inds.reshape(n_chunk, bpc, s, r, k),
            vals_s.reshape(n_chunk, bpc, s, r, k),
            winmap.reshape(n_chunk, bpc, s, buf),
        ),
    )
    return outs.reshape(b * r, f)
