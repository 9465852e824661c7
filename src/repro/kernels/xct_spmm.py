"""XCT-optimized fused SpMM as a Pallas TPU kernel.

TPU re-derivation of the paper's Listing 1 (Sec. III-B), including the
*buffer-load loop* (lines 15-20): the kernel itself streams each stage's
window of input rows from HBM into on-chip memory, so no staged window
tensor ever exists in HBM.  The CUDA kernel's mechanisms map as follows:

  =============================  =======================================
  Listing 1 (CUDA)               this kernel (Pallas TPU)
  =============================  =======================================
  shared-memory 3D input buffer  VMEM scratch ``win[2, BUF, Fp]``
  buffer-load loop (l. 15-20)    async DMAs HBM -> VMEM, driven by the
                                 scalar-prefetched window descriptors
                                 (flat 1-D SMEM tables,
                                 ``PrefetchScalarGridSpec``): one copy
                                 per run-length *segment* of consecutive
                                 source rows (default), or one per row
                                 (``winsegs=None`` A/B)
  coalesced gmem loads           ``ops.winmap_segments`` run-length
                                 encodes the winmap host-side (Hilbert
                                 ordering makes runs long); each segment
                                 is one strided multi-row copy, so DMA
                                 issue overhead is amortized the same
                                 way Listing 1 amortizes index loads
  multi-stage buffering          stages looped inside one grid step per
                                 row block; fp32 carry (the step's
                                 fixed cost is paid once per row block,
                                 not once per stage)
  __syncthreads() double-buffer  two window slots + DMA semaphores:
                                 stage ``n+1``'s loads are issued before
                                 stage ``n``'s FMAs run (overlap)
  per-thread indexed FMA         the stage's ``(index, length)`` slots
  (``buffer[ind] * len``)        are assembled into a dense local
                                 operator block ``W[R, BUF]`` on the VPU
                                 (one-hot compare per slot column), then
                                 one ``W @ window`` product runs on the
                                 MXU -- Mosaic has no gather across
                                 vregs, the MXU does the row selection
  register reuse across FFACTOR  the fused-slice dim ``F`` is the minor
                                 (lane) dimension, padded to the 128-lane
                                 vreg width; one block row drives all F
  {uint16, half} 4-byte packing  int16 index tile + fp16/bf16 value tile
                                 (4 B/nnz in HBM); upcast in-VREG
  fp32 FMA on fp16 data          explicit astype(compute_dtype) before
                                 the product, fp32 accumulation
  =============================  =======================================

The input slab ``x`` is handed to the kernel whole, in ``ANY`` (compiler
-chosen, HBM at size) memory space, as 32-bit lane-padded rows
(:func:`window_slab`): a DMA of one 16-bit row at a dynamic offset is
not sublane-aligned, and a row narrower than 128 lanes is not
lane-aligned.  Each window row crosses HBM exactly once per stage.  The
legacy two-pass path -- XLA gather materializing ``[B, S, BUF, Fp]``
windows in HBM, then :func:`spmm_block_ell_staged` -- is kept for A/B
benchmarking under ``ops.apply_operator(staging="gather")``.

Scalar prefetch is *chunked*: the descriptors (``winsegs`` or the raw
``winmap``) for at most ``smem_budget`` bytes of row-blocks are
prefetched per inner ``pallas_call``, and an outer ``lax.scan`` walks
the B-chunks.  Every table is flattened to 1-D before the call: SMEM
pads the minor dimension of a multi-dimensional table to a whole tile,
which blew the ``[.., NSEG, 3]`` segment table up more than tenfold.
``smem_bytes``/``seg_smem_bytes`` size one chunk's flat tables and raise
a named ``ValueError`` when even a single row-block cannot fit.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .traffic import lane_pad, window_row_bytes

__all__ = [
    "spmm_block_ell",
    "spmm_block_ell_staged",
    "vmem_bytes",
    "smem_bytes",
    "seg_smem_bytes",
    "window_slab",
    "SMEM_BUDGET",
    "VMEM_BUDGET",
]

# Per-call scalar-memory budget for the prefetched window descriptors
# (half of v5e's 1 MiB of SMEM; Mosaic keeps its own scalars in the
# rest).  One chunk's tables must fit; the outer scan
# covers the rest.
SMEM_BUDGET = 512 << 10
# SMEM allocation granule of a flat int32 table, in words.
_SMEM_WORDS = 128
# Per-grid-step on-chip working set ceiling (v5e VMEM is 16 MiB per core;
# the Mosaic default scoped limit is lower, so stay well inside).
VMEM_BUDGET = 16 << 20
KERNEL = "xct_spmm"


def _kernel_tag(op: str | None) -> dict:
    """``pallas_call`` keywords that name the kernel per operator: the
    name and ``kernel_metadata`` land in the custom call's HLO text, so
    a device trace tells the projection (``op="proj"``) from the
    backprojection (``op="back"``)."""
    if op is None:
        return {"name": KERNEL, "metadata": {"kernel": KERNEL}}
    return {"name": f"{KERNEL}_{op}",
            "metadata": {"kernel": KERNEL, "op": op}}


def window_slab(x):
    """The kernel's view of an input slab: 32-bit, lane-padded rows.

    16-bit rows widen to f32 (exact), so every window DMA moves whole
    32-bit sublanes; F pads with zeros to a multiple of 128 lanes.  The
    kernel result's first F lanes are the real slices.
    """
    wide = x.astype(jnp.float32) if x.dtype.itemsize < 4 else x
    f = x.shape[-1]
    pad = ((0, 0),) * (x.ndim - 1) + ((0, lane_pad(f) - f),)
    return jnp.pad(wide, pad)


def _stage_block(inds, vals, buf: int):
    """One stage's local operator block ``W[R, BUF]``.

    ``W[r, j] = sum_k vals[r, k] * (inds[r, k] == j)``: the ELL slots
    scattered densely over the window, built with one compare-select-add
    per slot column (K static, so the loop unrolls).  Repeated indices
    in a row sum in slot order.
    """
    r, k = inds.shape
    cols = jax.lax.broadcasted_iota(jnp.int32, (r, buf), 1)
    w = jnp.zeros((r, buf), vals.dtype)
    for j in range(k):
        w = w + jnp.where(cols == inds[:, j:j + 1], vals[:, j:j + 1], 0)
    return w


def _widen(vals, bits16):
    """Exact f32 view of a value tile.

    16-bit float tiles arrive as their int16 bit patterns (``bits16`` is
    the float dtype's name): Mosaic cannot load an f16 tile on v5e, so
    the kernel decodes the bits with integer ops instead -- bf16 is the
    top half of an f32, f16 is re-biased (subnormals via one exact
    multiply).
    """
    if bits16 is None:
        wide = jnp.float64 if vals.dtype == jnp.float64 else jnp.float32
        return vals.astype(wide)
    h = vals.astype(jnp.int32) & 0xFFFF
    if bits16 == "bfloat16":
        return jax.lax.bitcast_convert_type(h << 16, jnp.float32)
    sign = (h >> 15) << 31
    e = (h >> 10) & 0x1F
    m = h & 0x3FF
    normal = jax.lax.bitcast_convert_type(
        sign | ((e + 112) << 23) | (m << 13), jnp.float32
    )
    special = jax.lax.bitcast_convert_type(
        sign | 0x7F800000 | (m << 13), jnp.float32
    )
    sub = m.astype(jnp.float32) * 2.0 ** -24
    sub = jnp.where(sign != 0, -sub, sub)
    return jnp.where(e == 0, sub, jnp.where(e == 31, special, normal))


def _value_bits(vals):
    """``(tile, bits16)``: 16-bit float values travel as their int16
    bit patterns (decoded in-kernel by :func:`_widen`); others as is."""
    if vals.dtype.itemsize == 2 and jnp.issubdtype(vals.dtype, jnp.floating):
        return jax.lax.bitcast_convert_type(vals, jnp.int16), vals.dtype.name
    return vals, None


def _fma_block(inds, vals, window, compute_dtype, scale=None,
               bits16=None):
    """out[R, Fp] = sum_k vals[:, k] * window[inds[:, k]] for one stage.

    The block ``W`` is assembled in f32 from the exactly widened values
    (times ``scale``, a [1, 1] f32 vector from the scalar-prefetched
    per-block exponents of the quantized tier: dequantization costs no
    extra HBM stream and no extra pass).  ``W`` and the window are then
    rounded to ``compute_dtype`` and multiplied on the MXU with fp32
    accumulation.  v5e's MXU takes no f16, so an f16 ``compute_dtype``
    multiplies in f32: the product of two f16-valued operands is exact
    there, which is what an f16 MXU pass with fp32 accumulation gives.
    """
    vals = _widen(vals, bits16)
    if scale is not None:
        vals = vals * scale
    w = _stage_block(inds.astype(jnp.int32), vals, window.shape[0])
    mxu = jnp.dtype(compute_dtype)
    if mxu == jnp.float16:
        mxu = jnp.dtype(jnp.float32)
    return jnp.dot(
        w.astype(mxu), window.astype(mxu),
        preferred_element_type=jnp.float32,
        precision=(
            jax.lax.Precision.HIGHEST if mxu.itemsize >= 4 else None
        ),
    )


def _dma_classes(buf: int) -> tuple:
    """Static power-of-two copy lengths a decomposed segment can have.

    ``ops.winmap_segments`` splits every run into power-of-two pieces,
    so the kernel can issue fixed-size copies (Pallas DMAs need static
    extents) while still moving one *run* in O(log) issues instead of
    O(len) per-row issues.
    """
    classes = []
    ln = 1
    while ln <= max(1, buf):
        classes.append(ln)
        ln *= 2
    return tuple(classes)


def _block_scale(scl_ref, step):
    """Dequant factor ``2**exp`` of linear stage ``step`` as a [1, 1]
    f32 vector, built from the exponent bits so it is exact (a power of
    two) for every normal exponent."""
    e = jnp.full((1, 1), scl_ref[step], jnp.int32)
    return jax.lax.bitcast_convert_type((e + 127) << 23, jnp.float32)


def _window_dma_fn(mode, tables, x_ref, win, sems, *, buf, nseg, classes):
    """Build ``window_dma(which, slot, op)``: issue (``op="start"``) or
    await (``"wait"``) the buffer-load loop of linear stage ``which``
    into window slot ``slot`` (Listing 1 lines 15-20).  Start and wait
    walk the same descriptors, so semaphore counts always balance.

    ``mode`` picks the descriptor table:
      ``"per_row"``    flat winmap, one row copy per window entry;
      ``"coalesced"``  flat ``{src, dst, len}`` triples, every slot
                       tested against every static length class (pad
                       slots have ``len == 0`` and issue nothing);
      ``"sorted"``     class-sorted triples + per-class offsets: each
                       class loops over exactly its own slots with
                       dynamic ``fori_loop`` bounds (O(real segments)).
    """

    def copy(slot, src, dst, ln, op):
        dma = pltpu.make_async_copy(
            x_ref.at[pl.ds(src, ln)],
            win.at[slot, pl.ds(dst, ln)],
            sems.at[slot],
        )
        getattr(dma, op)()

    if mode == "per_row":
        (winmap_ref,) = tables

        def window_dma(which, slot, op):
            base = which * buf

            def one_row(j, carry):
                copy(slot, winmap_ref[base + j], j, 1, op)
                return carry

            jax.lax.fori_loop(0, buf, one_row, None)

        return window_dma

    segs_ref = tables[0]

    def seg_loop(which, slot, op, ln, lo, hi, guarded):
        def one_seg(j, carry):
            t = (which * nseg + j) * 3
            if guarded:

                @pl.when(segs_ref[t + 2] == ln)
                def _copy():
                    copy(slot, segs_ref[t], segs_ref[t + 1], ln, op)

            else:
                copy(slot, segs_ref[t], segs_ref[t + 1], ln, op)
            return carry

        jax.lax.fori_loop(lo, hi, one_seg, None)

    if mode == "coalesced":

        def window_dma(which, slot, op):
            for ln in classes:  # static unroll: DMA extents must be static
                seg_loop(which, slot, op, ln, 0, nseg, guarded=True)

        return window_dma

    off_ref = tables[1]
    ncls = len(classes) + 1

    def window_dma(which, slot, op):
        for ci, ln in enumerate(classes):  # descending, = segoff's axis
            lo = off_ref[which * ncls + ci]
            hi = off_ref[which * ncls + ci + 1]
            seg_loop(which, slot, op, ln, lo, hi, guarded=False)

    return window_dma


def _spmm_fused_kernel(
    *refs,
    mode: str,
    n_tables: int,
    quantized: bool,
    compute_dtype,
    bits16,
    buf: int,
    nseg: int,
    classes: tuple,
):
    """One row-block grid step of the fused kernel: its S stages run as
    an in-kernel loop, so the step's fixed cost (grid bookkeeping, the
    tile pipeline, the output write-back) is paid once per row block.

    Refs: ``n_tables`` flat int32 descriptor tables (SMEM, see
    :func:`_window_dma_fn`), then with ``quantized`` the flat int32
    dequant-exponent table ``scl [Bc*S]`` (SMEM), then inds [1,S,R,K]
    int16, vals [1,S,R,K] (int8/fp8 when quantized), x [C,Fp] (ANY),
    out [1,R,Fp], the window scratch and the DMA semaphores.
    """
    tables, rest = refs[:n_tables], refs[n_tables:]
    scl_ref = None
    if quantized:
        scl_ref, rest = rest[0], rest[1:]
    inds_ref, vals_ref, x_ref, out_ref, win, sems = rest
    i = pl.program_id(0)
    n_s = inds_ref.shape[1]
    n_steps = pl.num_programs(0) * n_s
    window_dma = _window_dma_fn(
        mode, tables, x_ref, win, sems, buf=buf, nseg=nseg,
        classes=classes,
    )

    @pl.when(i == 0)
    def _prologue():  # no stage before the first: load it synchronously
        window_dma(0, 0, "start")

    def one_stage(s, acc):
        step = i * n_s + s  # linear stage counter = flat table row

        @pl.when(step + 1 < n_steps)
        def _prefetch():  # overlap stage step+1's loads with these FMAs
            window_dma(step + 1, (step + 1) % 2, "start")

        window_dma(step, step % 2, "wait")
        scale = _block_scale(scl_ref, step) if quantized else None
        return acc + _fma_block(
            inds_ref[0, s], vals_ref[0, s], win[step % 2], compute_dtype,
            scale, bits16,
        )

    out_ref[0] = jax.lax.fori_loop(
        0, n_s, one_stage, jnp.zeros(out_ref.shape[1:], jnp.float32)
    )


def _spmm_staged_kernel(
    inds_ref, vals_ref, win_ref, out_ref, *, compute_dtype, bits16
):
    """Legacy step: windows pre-staged in HBM, delivered by BlockSpec."""
    s = pl.program_id(1)

    @pl.when(s == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    out_ref[0] += _fma_block(
        inds_ref[0, 0], vals_ref[0, 0], win_ref[0, 0], compute_dtype,
        bits16=bits16,
    )


def vmem_bytes(
    r: int,
    k: int,
    buf: int,
    f: int,
    store_bytes: int = 2,
    stages_buffered: int = 2,
    budget: int | None = None,
    win_bytes: int | None = None,
    stages: int = 1,
) -> int:
    """Per-grid-step VMEM footprint of the fused kernel.

    The fused path holds ``stages_buffered`` window slots (double
    buffering: stage ``s+1`` streams in while stage ``s`` computes);
    the staging memory is O(VMEM), not an O(64 MB) HBM transient.
    Window rows are what :func:`window_slab` hands the kernel: at least
    32-bit, F padded to 128 lanes.  The index/value tiles and the fp32
    output block are double-buffered by the Pallas pipeline; the local
    operator block ``W[R, BUF]`` is built in fp32.

    ``store_bytes`` sizes the value tile; ``win_bytes`` the input-vector
    storage dtype (``None``: same as ``store_bytes``).  ``stages`` is
    the stages a grid step's index/value tiles hold: the fused kernel
    takes a row block's S stages in one step.

    With ``budget=`` the request is validated: a footprint above the
    budget raises a ``ValueError`` naming the dominant dimension to
    shrink, instead of letting Mosaic fail opaquely at lower time.
    """
    wb = store_bytes if win_bytes is None else win_bytes
    fp = lane_pad(f)
    terms = {
        "S*R*K (inds, int16)": 2 * stages * r * k * 2,
        "S*R*K (vals)": 2 * stages * r * k * store_bytes,
        "BUF*F (window slots)": (
            stages_buffered * buf * window_row_bytes(f, wb)
        ),
        "R*BUF (operator block)": r * buf * 4,
        "R*F (fp32 output block)": 2 * r * fp * 4,
    }
    total = sum(terms.values())
    if budget is not None and total > budget:
        worst = max(terms, key=terms.get)  # type: ignore[arg-type]
        raise ValueError(
            f"kernel working set {total} B exceeds the {budget} B VMEM "
            f"budget (S={stages}, R={r}, K={k}, BUF={buf}, F={f}); the "
            f"dominant term is {worst} = {terms[worst]} B -- shrink that "
            "dimension (rows_per_block / nnz_per_stage / window / fuse)"
        )
    return total


def _smem_table_bytes(words: int) -> int:
    """SMEM bytes of one flat int32 table (allocated in whole granules)."""
    return -(-int(words) // _SMEM_WORDS) * _SMEM_WORDS * 4


def smem_bytes(
    b: int, s: int, buf: int, budget: int | None = None,
    scales: bool = False,
) -> int:
    """Scalar-memory footprint of a prefetched per-row ``winmap`` chunk
    (flat int32), for ``b`` row-blocks; ``scales`` adds the quantized
    tier's flat per-(block, stage) exponent table.

    ``spmm_block_ell`` chunks the prefetch over row-blocks so only one
    chunk's descriptors sit in SMEM at a time; pass ``budget=`` to
    validate a chunk -- a single row-block that cannot fit raises a
    named ``ValueError``.
    """
    total = _smem_table_bytes(b * s * buf) + (
        _smem_table_bytes(b * s) if scales else 0
    )
    if budget is not None and total > budget:
        raise ValueError(
            f"winmap chunk of {b} row-block(s) needs {total} B of SMEM "
            f"(B_chunk={b} x S={s} x BUF={buf} x 4 B) but the budget is "
            f"{budget} B; the offending dimensions are S*BUF = "
            f"{s * buf} entries per row-block -- reduce the window "
            "(BUF) or stage count (S), or raise smem_budget"
        )
    return total


def seg_smem_bytes(
    b: int, s: int, nseg: int, budget: int | None = None,
    noff: int = 0, scales: bool = False,
) -> int:
    """Scalar-memory footprint of a prefetched ``winsegs`` chunk (flat
    int32 ``{src, dst, len}`` triples), for ``b`` row-blocks.
    ``noff`` adds the per-class offset table of the class-sorted path
    (``NCLS+1`` int32 per (row-block, stage)); ``scales`` the quantized
    tier's exponent table."""
    total = _smem_table_bytes(b * s * nseg * 3)
    if noff:
        total += _smem_table_bytes(b * s * noff)
    if scales:
        total += _smem_table_bytes(b * s)
    if budget is not None and total > budget:
        raise ValueError(
            f"winsegs chunk of {b} row-block(s) needs {total} B of SMEM "
            f"(B_chunk={b} x S={s} x NSEG={nseg} x 12 B) but the budget "
            f"is {budget} B; the offending dimensions are S*NSEG = "
            f"{s * nseg} segments per row-block -- a more fragmented "
            "winmap (shorter runs) raises NSEG; reduce S/BUF or raise "
            "smem_budget"
        )
    return total


def _prefetch_chunk_blocks(b: int, fits) -> int:
    """Largest divisor of ``b`` whose descriptor chunk ``fits``."""
    for d in range(b, 0, -1):
        if b % d == 0 and fits(d):
            return d
    return 1


@functools.partial(
    jax.jit,
    static_argnames=("compute_dtype", "interpret", "smem_budget", "op"),
)
def spmm_block_ell(
    inds,
    vals,
    winmap,
    x,
    *,
    compute_dtype=jnp.float32,
    interpret: bool | None = None,
    winsegs=None,
    segoff=None,
    smem_budget: int | None = None,
    scales=None,
    op: str | None = None,
):
    """Fused multi-stage SpMM over one device's blocked-ELL shard, with
    the window staging done *inside* the kernel (paper Listing 1).

    Args:
      inds:   [B, S, R, K] int16 window-local indices.
      vals:   [B, S, R, K] storage-dtype lengths.
      winmap: [B, S, BUF] int32 device-local input column ids (per-row
              DMA path; ignored when ``winsegs`` is given).
      x:      [C, F] local input slab (storage dtype).  Stays whole in
              HBM (as :func:`window_slab`'s 32-bit lane-padded rows);
              the kernel double-buffers each stage's BUF-row window
              into VMEM with async copies.  No ``[B, S, BUF, F]`` tensor
              is ever materialized.
      compute_dtype: product dtype (fp32 for the paper's mixed mode);
              accumulation is fp32.
      interpret: force Pallas interpret mode; defaults to True off-TPU.
      winsegs: [B, S, NSEG, 3] int32 run-length segments from
              ``ops.winmap_segments``; when given, the kernel issues one
              coalesced multi-row copy per segment instead of one copy
              per ``winmap`` row (the default production path -- see
              ``ops.apply_operator(dma=...)``).
      segoff: [B, S, NCLS+1] int32 per-length-class offsets into a
              class-sorted ``winsegs`` (``ops.sort_segments_by_class``);
              when given, each class loops over exactly its own slots
              (O(segments) issue work); when omitted the kernel tests
              every slot against every class (legacy unsorted tables).
      smem_budget: per-call scalar-memory budget for the prefetched
              descriptors; the prefetch is chunked over row-blocks to
              fit (outer ``lax.scan``), so shards of any B run.
              Defaults to ``SMEM_BUDGET``.
      scales: [B, S] int32 per-block *dequantization* exponents
              (``core.precision.quantize_block_vals``); when given,
              ``vals`` is int8/fp8 and the kernel multiplies each
              block's values by ``2.0**scales[b, s]`` inline.  The table
              rides the scalar-prefetch path next to winmap/segoff.
      op:     the operator's tag (``"proj"`` or ``"back"``), carried
              into the kernel's name and metadata (:func:`_kernel_tag`).

    Returns:
      [B, R, F] fp32 partial output band blocks.
    """
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    budget = SMEM_BUDGET if smem_budget is None else smem_budget
    b, s, r, k = inds.shape
    buf = winmap.shape[-1]
    f = x.shape[-1]
    xk = window_slab(x)
    vals, bits16 = _value_bits(vals)
    vmem_bytes(
        r, k, buf, f, jnp.dtype(vals.dtype).itemsize,
        win_bytes=jnp.dtype(x.dtype).itemsize, budget=VMEM_BUDGET,
        stages=s,
    )
    quantized = scales is not None
    if winsegs is None:
        mode, nseg = "per_row", 0
        tables = (winmap,)

        def chunk_smem(d, limit=None):
            return smem_bytes(d, s, buf, budget=limit, scales=quantized)

    else:
        nseg = winsegs.shape[-2]
        mode = "coalesced" if segoff is None else "sorted"
        tables = (winsegs,) if segoff is None else (winsegs, segoff)
        noff = 0 if segoff is None else segoff.shape[-1]

        def chunk_smem(d, limit=None):
            return seg_smem_bytes(
                d, s, nseg, budget=limit, noff=noff, scales=quantized
            )

    classes = _dma_classes(buf)
    if mode == "sorted":
        classes = classes[::-1]  # descending, = segoff's axis
        if segoff.shape[-1] != len(classes) + 1:
            raise ValueError(
                f"segoff carries {segoff.shape[-1] - 1} length classes "
                f"but BUF={buf} implies {len(classes)} "
                "(sort_segments_by_class(winsegs, buf) with the same buf)"
            )
    chunk_smem(1, budget)  # a single over-budget row-block raises here
    bpc = _prefetch_chunk_blocks(b, lambda d: chunk_smem(d) <= budget)
    # one flat row per row-block: HBM tiles pad a short minor dimension
    # (the 3 of {src, dst, len}) to 128 lanes, so never carry the
    # tables, or scan over them, in their 4-D shape
    tables = tuple(t.reshape(b, -1) for t in tables)

    def one_call(ic, vc, tc, qc):
        pre = tuple(t.reshape(-1).astype(jnp.int32) for t in tc)
        if quantized:
            pre += (qc.astype(jnp.int32).reshape(-1),)
        kernel = functools.partial(
            _spmm_fused_kernel, mode=mode, n_tables=len(tc),
            quantized=quantized, compute_dtype=compute_dtype,
            bits16=bits16, buf=buf,
            nseg=nseg, classes=classes,
        )
        return pl.pallas_call(
            kernel,
            grid_spec=_fused_grid_spec(
                ic.shape[0], s, r, k, buf, xk.shape[-1], xk.dtype,
                num_scalar_prefetch=len(pre),
            ),
            out_shape=jax.ShapeDtypeStruct(
                (ic.shape[0], r, xk.shape[-1]), jnp.float32
            ),
            # cross-step window prefetch orders the whole grid
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("arbitrary",),
            ),
            interpret=interpret,
            **_kernel_tag(op),
        )(*pre, ic, vc, xk)

    if bpc >= b:
        out = one_call(inds, vals, tables, scales)
    else:
        n_chunk = b // bpc

        def chunks(a):
            return a.reshape(n_chunk, bpc, *a.shape[1:])

        _, outs = jax.lax.scan(
            lambda _, args: (None, one_call(*args)),
            None,
            (
                chunks(inds),
                chunks(vals),
                tuple(chunks(t) for t in tables),
                chunks(scales) if quantized else None,
            ),
        )
        out = outs.reshape(b, r, xk.shape[-1])
    return out[..., :f]


def _fused_grid_spec(b, s, r, k, buf, fp, x_dtype,
                     num_scalar_prefetch: int = 1):
    # index maps take the grid indices plus one trailing arg per
    # scalar-prefetch operand; *refs absorbs either arity
    return pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=num_scalar_prefetch,
        grid=(b,),
        in_specs=[
            pl.BlockSpec((1, s, r, k), lambda i, *refs: (i, 0, 0, 0)),
            pl.BlockSpec((1, s, r, k), lambda i, *refs: (i, 0, 0, 0)),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec((1, r, fp), lambda i, *refs: (i, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((2, buf, fp), x_dtype),
            pltpu.SemaphoreType.DMA((2,)),
        ],
    )


@functools.partial(
    jax.jit, static_argnames=("compute_dtype", "interpret", "op")
)
def spmm_block_ell_staged(
    inds,
    vals,
    window,
    *,
    compute_dtype=jnp.float32,
    interpret: bool | None = None,
    op: str | None = None,
):
    """Legacy two-pass SpMM: consumes HBM-pre-staged windows.

    Kept for A/B benchmarking against the fused path
    (``ops.apply_operator(staging="gather")``): the caller materializes
    ``window[B, S, BUF, F]`` with an XLA gather (one extra HBM round
    trip) and BlockSpec delivers one ``[BUF, Fp]`` tile per grid step
    (F lane-padded here).

    Returns [B, R, F] fp32 partial output band blocks.
    """
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    b, s, r, k = inds.shape
    buf, f = window.shape[-2:]
    fp = lane_pad(f)
    window = window_slab(window)  # [B, S, BUF, Fp], 32-bit rows
    vals, bits16 = _value_bits(vals)
    kernel = functools.partial(
        _spmm_staged_kernel, compute_dtype=compute_dtype, bits16=bits16
    )
    out = pl.pallas_call(
        kernel,
        grid=(b, s),
        in_specs=[
            pl.BlockSpec((1, 1, r, k), lambda i, j: (i, j, 0, 0)),
            pl.BlockSpec((1, 1, r, k), lambda i, j: (i, j, 0, 0)),
            pl.BlockSpec((1, 1, buf, fp), lambda i, j: (i, j, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, r, fp), lambda i, j: (i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((b, r, fp), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
        ),
        interpret=interpret,
        **_kernel_tag(op),
    )(inds, vals, window)
    return out[..., :f]
