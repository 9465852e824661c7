"""Pallas kernel vs pure-jnp oracle: fused in-kernel staging vs the
legacy gather baseline, shape/dtype sweeps, property tests, and the
no-staged-window jaxpr pin."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.kernels.ops import (
    apply_operator,
    dma_issue_count,
    segment_histogram,
    sort_segments_by_class,
    winmap_segments,
)
from repro.kernels.ref import spmm_ref
from repro.kernels.traffic import est_segments_per_stage, spmm_traffic
from repro.kernels.xct_spmm import (
    seg_smem_bytes,
    smem_bytes,
    spmm_block_ell,
    spmm_block_ell_staged,
    vmem_bytes,
    VMEM_BUDGET,
)


def _seed(*parts) -> int:
    """Stable cross-process seed (hash() of str is salted per run)."""
    import zlib

    return zlib.crc32(repr(parts).encode())


def _random_ell(rng, b, s, r, k, buf, c, f):
    inds = rng.integers(0, buf, size=(b, s, r, k)).astype(np.int16)
    vals = (rng.random((b, s, r, k)) * (rng.random((b, s, r, k)) > 0.3)
            ).astype(np.float32)
    winmap = rng.integers(0, c, size=(b, s, buf)).astype(np.int32)
    x = rng.normal(size=(c, f)).astype(np.float32)
    return inds, vals, winmap, x


SWEEP = [
    # (B, S, R, K, BUF, C, F) -- deliberately includes non-divisible
    # B/S combinations (3, 5) and non-power-of-two BUF
    (1, 1, 8, 8, 16, 64, 1),
    (2, 2, 16, 8, 32, 128, 4),
    (3, 1, 32, 16, 64, 256, 8),
    (2, 3, 8, 32, 40, 96, 16),
    (5, 2, 16, 16, 24, 64, 2),
]


@pytest.mark.parametrize("shape", SWEEP)
@pytest.mark.parametrize(
    "storage", [jnp.float32, jnp.float16, jnp.bfloat16]
)
def test_fused_kernel_matches_oracle(shape, storage):
    """The in-kernel-staging path against the unstaged-interface oracle."""
    b, s, r, k, buf, c, f = shape
    rng = np.random.default_rng(_seed(shape, storage))
    inds, vals, winmap, x = _random_ell(rng, b, s, r, k, buf, c, f)
    vals_s = jnp.asarray(vals).astype(storage)
    x_s = jnp.asarray(x).astype(storage)
    out = spmm_block_ell(
        jnp.asarray(inds), vals_s, jnp.asarray(winmap), x_s,
        compute_dtype=jnp.float32,
    )
    ref = spmm_ref(
        jnp.asarray(inds), vals_s, jnp.asarray(winmap), x_s,
        compute_dtype=jnp.float32,
    )
    tol = 1e-5 if storage == jnp.float32 else 2e-2
    np.testing.assert_allclose(
        np.asarray(out).reshape(b * r, f), np.asarray(ref),
        rtol=tol, atol=tol,
    )


@pytest.mark.parametrize("shape", SWEEP[:3])
def test_staged_kernel_matches_oracle(shape):
    """The legacy pre-staged-window kernel stays correct (A/B baseline)."""
    b, s, r, k, buf, c, f = shape
    rng = np.random.default_rng(hash(shape) % 2**31)
    inds, vals, winmap, x = _random_ell(rng, b, s, r, k, buf, c, f)
    window = jnp.take(jnp.asarray(x), jnp.asarray(winmap), axis=0)
    out = spmm_block_ell_staged(
        jnp.asarray(inds), jnp.asarray(vals), window
    )
    ref = spmm_ref(
        jnp.asarray(inds), jnp.asarray(vals), jnp.asarray(winmap),
        jnp.asarray(x),
    )
    np.testing.assert_allclose(
        np.asarray(out).reshape(b * r, f), np.asarray(ref),
        rtol=1e-5, atol=1e-5,
    )


# property-style sweep (real hypothesis when installed, deterministic
# shim otherwise): fused staging across the precision ladder x shapes,
# including B/S the grid does not divide evenly into anything
@settings(max_examples=20, deadline=None)
@given(
    st.integers(1, 5), st.integers(1, 3), st.sampled_from([8, 16]),
    st.sampled_from([8, 16]), st.integers(1, 8),
    st.sampled_from(["f32", "f16", "bf16"]),
    st.sampled_from(["f32", "f16"]),
    st.integers(0, 10_000),
)
def test_fused_matches_oracle_hypothesis(
    b, s, r, k, f, storage, compute, seed
):
    sdt = {"f32": jnp.float32, "f16": jnp.float16,
           "bf16": jnp.bfloat16}[storage]
    cdt = {"f32": jnp.float32, "f16": jnp.float16}[compute]
    buf, c = 3 * k, 64
    rng = np.random.default_rng(seed)
    inds, vals, winmap, x = _random_ell(rng, b, s, r, k, buf, c, f)
    vals_s = jnp.asarray(vals).astype(sdt)
    x_s = jnp.asarray(x).astype(sdt)
    out = spmm_block_ell(
        jnp.asarray(inds), vals_s, jnp.asarray(winmap), x_s,
        compute_dtype=cdt,
    )
    ref = spmm_ref(
        jnp.asarray(inds), vals_s, jnp.asarray(winmap), x_s,
        compute_dtype=cdt,
    )
    wide = sdt == jnp.float32 and cdt == jnp.float32
    tol = 1e-5 if wide else 5e-2
    np.testing.assert_allclose(
        np.asarray(out).reshape(b * r, f),
        np.asarray(ref).astype(np.float32),
        rtol=tol, atol=tol,
    )


@pytest.mark.parametrize("storage", [jnp.float32, jnp.float16])
def test_fused_equals_gather_equals_oracle(storage):
    """The three apply_operator paths agree within mixed tolerance."""
    rng = np.random.default_rng(9)
    b, s, r, k, buf, c, f = 4, 2, 16, 16, 48, 96, 8
    inds, vals, winmap, x = _random_ell(rng, b, s, r, k, buf, c, f)
    args = tuple(
        jnp.asarray(v) for v in (inds, vals, winmap, x)
    )
    outs = {
        name: np.asarray(
            apply_operator(*args, storage_dtype=storage, **kw)
        )
        for name, kw in (
            ("fused", {}),
            ("gather", {"staging": "gather"}),
            ("oracle", {"use_ref": True}),
        )
    }
    tol = 1e-5 if storage == jnp.float32 else 2e-2
    np.testing.assert_allclose(
        outs["fused"], outs["gather"], rtol=tol, atol=tol
    )
    np.testing.assert_allclose(
        outs["fused"], outs["oracle"], rtol=tol, atol=tol
    )


def test_gather_chunked_equals_unchunked():
    rng = np.random.default_rng(7)
    b, s, r, k, buf, c, f = 8, 2, 16, 8, 32, 128, 4
    inds, vals, winmap, x = _random_ell(rng, b, s, r, k, buf, c, f)
    args = tuple(jnp.asarray(v) for v in (inds, vals, winmap, x))
    full = apply_operator(
        *args, storage_dtype=jnp.float32, staging="gather",
        blocks_per_call=8,
    )
    chunked = apply_operator(
        *args, storage_dtype=jnp.float32, staging="gather",
        blocks_per_call=2,
    )
    np.testing.assert_allclose(
        np.asarray(full), np.asarray(chunked), rtol=1e-6
    )


def _walk_avals(jaxpr, shapes):
    """Collect every intermediate/output aval shape in a jaxpr tree."""
    for eqn in jaxpr.eqns:
        for v in eqn.outvars:
            if hasattr(v, "aval"):
                shapes.add(tuple(getattr(v.aval, "shape", ())))
        for p in eqn.params.values():
            for sub in jax.tree.leaves(
                p, is_leaf=lambda x: hasattr(x, "eqns")
            ):
                if hasattr(sub, "eqns"):
                    _walk_avals(sub, shapes)
                elif hasattr(sub, "jaxpr"):
                    _walk_avals(sub.jaxpr, shapes)
    return shapes


def _window_shapes(staging):
    b, s, r, k, buf, c, f = 4, 2, 16, 16, 48, 96, 8
    rng = np.random.default_rng(3)
    inds, vals, winmap, x = _random_ell(rng, b, s, r, k, buf, c, f)
    segs = winmap_segments(winmap)  # traced winmap cannot be RLE'd

    def fn(i, v, w, xx):
        return apply_operator(
            i, v, w, xx, storage_dtype=jnp.float16, staging=staging,
            winsegs=segs,
        )

    jaxpr = jax.make_jaxpr(fn)(
        jnp.asarray(inds), jnp.asarray(vals), jnp.asarray(winmap),
        jnp.asarray(x),
    )
    shapes = _walk_avals(jaxpr.jaxpr, set())
    # any intermediate carrying a [*, S, BUF, F] window tensor (the scan
    # -chunked gather stages [bpc, S, BUF, Fp] blocks of the kernel's
    # lane-padded rows)
    from repro.kernels.traffic import lane_pad

    return {
        sh for sh in shapes
        if len(sh) == 4 and sh[1:] in ((s, buf, f), (s, buf, lane_pad(f)))
    }


def test_fused_jaxpr_has_no_staged_window():
    """Acceptance pin: the default path's jaxpr materializes no
    [B, S, BUF, F] window tensor anywhere (the gather baseline does)."""
    assert _window_shapes("fused") == set()
    assert _window_shapes("gather") != set()


def test_winmap_smem_budget_at_suite_scale(small_system):
    """The fused kernel scalar-prefetches the *whole* [B, S, BUF] winmap
    to SMEM (unlike the per-step VMEM working set).  Pin that the shards
    this suite and the quick bench actually run stay deep inside scalar
    memory; production-B shards need the prefetch chunked first (see
    smem_bytes docstring + ROADMAP on-TPU item)."""
    _, _, plan = small_system
    for op in (plan.proj, plan.back):
        _, b, s, _, _ = op.inds.shape
        assert smem_bytes(b, s, op.winmap.shape[-1]) < 256 << 10, (
            op.winmap.shape
        )


def test_vmem_budget_within_paper_shared_memory():
    """The double-buffered production tile (R=64, K=64, BUF=768, F=16,
    2-byte storage) at the kernel's TPU layout.  The paper's ~96 KB
    shared-memory class no longer holds it: window rows are 32-bit and
    padded to 128 lanes, and the local operator block W[R, BUF] adds
    R*BUF*4.  Pin the exact footprint and keep it far below VMEM."""
    assert vmem_bytes(64, 64, 768, 16) == (
        2 * 64 * 64 * 2 + 2 * 64 * 64 * 2  # inds + vals, double-buffered
        + 2 * 768 * 128 * 4  # two 32-bit lane-padded window slots
        + 64 * 768 * 4  # operator block
        + 2 * 64 * 128 * 4  # fp32 output block, double-buffered
    ) == 1081344
    assert vmem_bytes(64, 64, 768, 16) < VMEM_BUDGET // 8
    # single-slot legacy footprint is smaller still
    assert vmem_bytes(64, 64, 768, 16, stages_buffered=1) < vmem_bytes(
        64, 64, 768, 16
    )


# --------------------------------------------------------------------- #
# run-length coalesced window DMAs (ISSUE 5 tentpole)
# --------------------------------------------------------------------- #
def _winmap_from_runs(rng, buf, c, run_lo, run_hi):
    """A window made of random-length runs of consecutive source rows."""
    row = []
    while len(row) < buf:
        st = int(rng.integers(0, max(1, c - run_hi)))
        ln = int(rng.integers(run_lo, run_hi + 1))
        row.extend(range(st, st + min(ln, buf - len(row))))
    return np.asarray(row[:buf], np.int32)


def test_winmap_segments_known():
    """Exact RLE + binary decomposition on a hand-written winmap, and
    the issue count the kernel will pay (acceptance pin: one DMA per
    run-length segment)."""
    # runs: [5..9] (len 5 -> 4+1), [20] (1), [9,10,11] (len 3 -> 2+1)
    wm = np.array([[[5, 6, 7, 8, 9, 20, 9, 10, 11]]], np.int32)
    segs = winmap_segments(wm)
    want = [
        (5, 0, 4), (9, 4, 1),  # run of 5, largest-first decomposition
        (20, 5, 1),
        (9, 6, 2), (11, 8, 1),  # run of 3
    ]
    got = [tuple(t) for t in segs[0, 0] if t[2] > 0]
    assert got == want
    assert dma_issue_count(segs) == 5  # vs 9 per-row copies
    assert segment_histogram(segs) == {1: 3, 2: 1, 4: 1}
    # pad slots are len == 0 and the capacity is padded to 8
    assert segs.shape[-2] % 8 == 0
    assert (segs[0, 0, 5:, 2] == 0).all()


def test_winmap_segments_tile_window():
    """Property: the dst ranges of a table tile [0, BUF) exactly and
    replay the winmap -- so the coalesced copies deliver bit-identical
    window contents to the per-row path, for ANY winmap."""
    rng = np.random.default_rng(11)
    for trial in range(5):
        buf, c = 64, 256
        wm = _winmap_from_runs(rng, buf, c, 1, 9)
        segs = winmap_segments(wm[None, None])[0, 0]
        rebuilt = np.full(buf, -1, np.int64)
        covered = np.zeros(buf, bool)
        for src, dst, ln in segs:
            if ln == 0:
                continue
            assert not covered[dst:dst + ln].any()  # no overlap
            covered[dst:dst + ln] = True
            rebuilt[dst:dst + ln] = np.arange(src, src + ln)
        assert covered.all()  # no hole
        np.testing.assert_array_equal(rebuilt, wm)


ADVERSARIAL = {
    # every run length 1 (worst case: coalescing degenerates to per-row)
    "single-row-runs": lambda rng, buf, c: rng.permutation(
        np.arange(0, 2 * buf, 2)[:buf]
    ).astype(np.int32),
    # one full-window run (best case: a single strided copy chain)
    "one-full-run": lambda rng, buf, c: (
        np.arange(buf, dtype=np.int32) + int(rng.integers(0, c - buf))
    ),
    # shuffled Hilbert order: consecutive chunks, random order + lengths
    "shuffled-hilbert": lambda rng, buf, c: _winmap_from_runs(
        rng, buf, c, 1, 13
    ),
}


@pytest.mark.parametrize("kind", sorted(ADVERSARIAL))
@pytest.mark.parametrize(
    "storage,compute",
    [
        (jnp.float32, jnp.float32),
        (jnp.float16, jnp.float32),
        (jnp.bfloat16, jnp.float32),
        (jnp.float16, jnp.float16),
    ],
)
def test_coalesced_bitexact_vs_per_row(kind, storage, compute):
    """Acceptance pin: coalesced and per-row DMA paths are BIT-exact
    across the storage x compute ladder on adversarial winmaps, and
    the issue count is never worse than per-row."""
    rng = np.random.default_rng(_seed(kind, storage, compute))
    b, s, r, k, buf, c, f = 3, 2, 16, 8, 40, 128, 4  # ragged B/S
    inds = rng.integers(0, buf, size=(b, s, r, k)).astype(np.int16)
    vals = rng.random((b, s, r, k)).astype(np.float32)
    wm = np.stack([
        np.stack([ADVERSARIAL[kind](rng, buf, c) for _ in range(s)])
        for _ in range(b)
    ])
    x = rng.normal(size=(c, f)).astype(np.float32)
    args = tuple(jnp.asarray(v) for v in (inds, vals, wm, x))
    out = {
        dma: np.asarray(apply_operator(
            *args, storage_dtype=storage, compute_dtype=compute,
            dma=dma,
        ))
        for dma in ("coalesced", "per_row")
    }
    np.testing.assert_array_equal(out["coalesced"], out["per_row"])
    issues = dma_issue_count(winmap_segments(wm))
    assert issues <= b * s * buf
    if kind == "one-full-run":
        # BUF=40 = 32+8: two copies per stage instead of 40
        assert issues == 2 * b * s


@settings(max_examples=15, deadline=None)
@given(
    st.integers(1, 5), st.integers(1, 3), st.sampled_from([8, 16]),
    st.integers(1, 6), st.integers(1, 16),
    st.sampled_from(["f32", "f16", "bf16"]),
    st.sampled_from(["f32", "f16"]),
    st.integers(0, 10_000),
)
def test_coalesced_property_sweep(b, s, r, f, run_hi, storage, compute,
                                  seed):
    """Property sweep (satellite): coalesced == per-row bit-exact for
    random run mixtures across dtypes and ragged (non-divisible) B/S."""
    sdt = {"f32": jnp.float32, "f16": jnp.float16,
           "bf16": jnp.bfloat16}[storage]
    cdt = {"f32": jnp.float32, "f16": jnp.float16}[compute]
    k, buf, c = 8, 24, 96
    rng = np.random.default_rng(seed)
    inds = rng.integers(0, buf, size=(b, s, r, k)).astype(np.int16)
    vals = rng.random((b, s, r, k)).astype(np.float32)
    wm = np.stack([
        np.stack([
            _winmap_from_runs(rng, buf, c, 1, run_hi) for _ in range(s)
        ])
        for _ in range(b)
    ])
    x = rng.normal(size=(c, f)).astype(np.float32)
    args = tuple(jnp.asarray(v) for v in (inds, vals, wm, x))
    out = {
        dma: np.asarray(apply_operator(
            *args, storage_dtype=sdt, compute_dtype=cdt, dma=dma,
        ))
        for dma in ("coalesced", "per_row")
    }
    np.testing.assert_array_equal(out["coalesced"], out["per_row"])


@pytest.mark.parametrize("dma", ["coalesced", "per_row"])
def test_chunked_prefetch_matches_single_shot(dma):
    """Acceptance pin: a shard whose B overflows the single-shot SMEM
    budget runs correctly -- the outer scan over row-block chunks is
    bit-exact vs the unchunked call."""
    rng = np.random.default_rng(23)
    b, s, r, k, buf, c, f = 8, 2, 8, 8, 16, 64, 4
    inds = rng.integers(0, buf, size=(b, s, r, k)).astype(np.int16)
    vals = rng.random((b, s, r, k)).astype(np.float32)
    wm = np.stack([
        np.stack([_winmap_from_runs(rng, buf, c, 1, 5)
                  for _ in range(s)])
        for _ in range(b)
    ])
    x = rng.normal(size=(c, f)).astype(np.float32)
    args = tuple(jnp.asarray(v) for v in (inds, vals, wm, x))
    full = apply_operator(*args, storage_dtype=jnp.float32, dma=dma)
    # budget fits ~2 row-blocks of descriptors -> 4 scan chunks
    nseg = winmap_segments(wm).shape[-2]
    budget = (
        seg_smem_bytes(2, s, nseg)
        if dma == "coalesced"
        else smem_bytes(2, s, buf)
    )
    assert budget < (smem_bytes(b, s, buf) if dma == "per_row"
                     else seg_smem_bytes(b, s, nseg))
    chunked = apply_operator(
        *args, storage_dtype=jnp.float32, dma=dma, smem_budget=budget
    )
    np.testing.assert_array_equal(np.asarray(full), np.asarray(chunked))


@pytest.mark.parametrize("dma", ["coalesced", "per_row"])
@pytest.mark.parametrize("chunked", [False, True])
def test_quantized_kernel_matches_dequantized_reference(dma, chunked):
    """Tentpole pin (ISSUE 8): int8 vals + per-block scales through the
    fused kernel -- the scales ride scalar prefetch and are applied
    inline in the FMA loop -- are BIT-exact vs running the same kernel
    on eagerly dequantized f32 vals, on every DMA/chunking path.
    (Power-of-two scales in f32 compute make dequant exact, so any
    difference is a kernel wiring bug, not rounding.)"""
    from repro.core.precision import (
        dequantize_block_vals,
        quantize_block_vals,
    )

    rng = np.random.default_rng(_seed("q8", dma, chunked))
    b, s, r, k, buf, c, f = 6, 2, 8, 8, 16, 64, 4
    inds = rng.integers(0, buf, size=(b, s, r, k)).astype(np.int16)
    # spread block magnitudes over ~12 octaves so per-block scaling
    # actually varies (a single global scale would also pass otherwise)
    vals = (
        rng.random((b, s, r, k))
        * np.exp2(rng.integers(-6, 7, size=(b, s, 1, 1)))
    ).astype(np.float32)
    wm = np.stack([
        np.stack([_winmap_from_runs(rng, buf, c, 1, 5)
                  for _ in range(s)])
        for _ in range(b)
    ])
    x = rng.normal(size=(c, f)).astype(np.float32)
    q, exp = quantize_block_vals(jnp.asarray(vals), jnp.int8)
    wide = dequantize_block_vals(q, exp, jnp.float32)
    kw = dict(storage_dtype=jnp.float16, compute_dtype=jnp.float32,
              dma=dma)
    if chunked:
        from repro.kernels.xct_spmm import _dma_classes

        # apply_operator class-sorts the segments: count their offsets
        nseg = winmap_segments(wm).shape[-2]
        kw["smem_budget"] = (
            seg_smem_bytes(2, s, nseg, noff=len(_dma_classes(buf)) + 1,
                           scales=True)
            if dma == "coalesced"
            else smem_bytes(2, s, buf, scales=True)
        )
    args = (jnp.asarray(inds), jnp.asarray(wm), jnp.asarray(x))
    out_q = apply_operator(args[0], q, args[1], args[2],
                           scales=exp, **kw)
    # reference path: f32 storage so the dequantized vals pass through
    # the kernel unrounded; x pre-cast to the quantized path's f16
    # window values (f16 -> f32 is exact) so vals are the ONLY delta
    kw["storage_dtype"] = jnp.float32
    out_ref = apply_operator(
        args[0], wide, args[1], args[2].astype(jnp.float16), **kw
    )
    np.testing.assert_array_equal(
        np.asarray(out_q), np.asarray(out_ref)
    )
    # the oracle path dequantizes eagerly and must agree too
    out_oracle = apply_operator(
        args[0], q, args[1], args[2], scales=exp,
        storage_dtype=jnp.float16, compute_dtype=jnp.float32,
        use_ref=True,
    )
    np.testing.assert_allclose(
        np.asarray(out_q), np.asarray(out_oracle), rtol=1e-6, atol=1e-6
    )


def test_budget_guards_name_offending_dimension():
    """Satellite: over-budget blocks raise a named ValueError instead of
    sizing silently (Mosaic would fail opaquely)."""
    with pytest.raises(ValueError, match="BUF"):
        smem_bytes(1, 4, 512, budget=64)
    with pytest.raises(ValueError, match="NSEG"):
        seg_smem_bytes(1, 4, 512, budget=64)
    with pytest.raises(ValueError, match="window slots"):
        vmem_bytes(64, 64, 768, 16, budget=8 << 10)
    # end to end: a kernel call whose single row-block overflows
    rng = np.random.default_rng(3)
    b, s, r, k, buf, c, f = 1, 1, 8, 8, 16, 64, 2
    inds, vals, wm, x = _random_ell(rng, b, s, r, k, buf, c, f)
    with pytest.raises(ValueError, match="SMEM"):
        apply_operator(
            jnp.asarray(inds), jnp.asarray(vals), jnp.asarray(wm),
            jnp.asarray(x), storage_dtype=jnp.float32, dma="per_row",
            smem_budget=16,
        )


def test_traffic_dma_issue_model():
    """The traffic model's issue term: coalesced < per-row strictly,
    measured segment counts plug in, and the gather baseline is priced
    as bulk tiles."""
    per = spmm_traffic(8, 2, 64, 64, 768, 16, cols=4096, dma="per_row")
    coal = spmm_traffic(8, 2, 64, 64, 768, 16, cols=4096,
                        dma="coalesced")
    meas = spmm_traffic(
        8, 2, 64, 64, 768, 16, cols=4096, dma="coalesced",
        segments_per_stage=37,
    )
    gath = spmm_traffic(8, 2, 64, 64, 768, 16, cols=4096, staging="gather")
    assert per["dma_issues"] == 8 * 2 * 768
    assert coal["dma_issues"] < per["dma_issues"]
    assert meas["dma_issues"] == 8 * 2 * 37
    assert gath["dma_issues"] == 8 * 2
    # descriptor bytes are priced per mode: 4 B/winmap row vs
    # 12 B/segment -- the small byte premium coalescing pays for the
    # big issue-count cut (window/operator terms are mode-invariant)
    assert per["winmap_bytes"] == 8 * 2 * 768 * 4
    assert meas["winmap_bytes"] == 8 * 2 * 37 * 12
    assert coal["window_bytes"] == per["window_bytes"]
    assert coal["operator_bytes"] == per["operator_bytes"]


def test_est_segments_calibrated(small_system):
    """The analytic segments-per-stage model tracks the measured
    ``winmap_segments`` tables of real plans (est/real in [0.5, 2] --
    the same calibration discipline as ``estimate_plan``)."""
    _, _, plan = small_system
    for op in (plan.proj, plan.back):
        buf = op.winmap.shape[-1]
        per_stage = (op.winsegs[..., 2] > 0).sum(axis=-1)
        real = float(per_stage.mean())
        est = est_segments_per_stage(buf)
        assert 0.5 <= est / max(real, 1.0) <= 2.0, (buf, real, est)


def test_plan_winsegs_replay_winmap(small_system):
    """The shard-attached tables (built by core.partition) replay every
    device's winmap exactly -- same property as the unit test above but
    on the real Hilbert-ordered operators the suite solves with."""
    _, _, plan = small_system
    op = plan.back
    p, b_, s_, buf = op.winmap.shape
    segs = op.winsegs
    for pi in (0,):
        for bi in range(min(2, b_)):
            for si in range(s_):
                rebuilt = np.full(buf, -1, np.int64)
                for src, dst, ln in segs[pi, bi, si]:
                    if ln:
                        rebuilt[dst:dst + ln] = np.arange(src, src + ln)
                np.testing.assert_array_equal(
                    rebuilt, op.winmap[pi, bi, si]
                )


# --------------------------------------------------------------------- #
# slot reordering (ISSUE 7): layout permutation invariance + the
# class-sorted segment tables the reordered kernel consumes
# --------------------------------------------------------------------- #
def _permute_layout(rng, inds, winmap):
    """Rename every (b, s) window's slots by an independent random
    permutation: ``winmap'[j] = winmap[perm[j]]``, ``inds' =
    perm^-1[inds]`` -- the same-values-different-slots transform slot
    reordering applies at plan build.  Returns the perms too."""
    b, s, buf = winmap.shape
    wm2 = np.empty_like(winmap)
    inds2 = np.empty_like(inds)
    perms = np.empty_like(winmap)
    for bi in range(b):
        for si in range(s):
            perm = perms[bi, si] = rng.permutation(buf)
            inv = np.argsort(perm)
            wm2[bi, si] = winmap[bi, si][perm]
            inds2[bi, si] = inv[inds[bi, si]].astype(inds.dtype)
    return inds2, wm2, perms


@settings(max_examples=10, deadline=None)
@given(
    st.integers(1, 4), st.integers(1, 3), st.sampled_from([8, 16]),
    st.integers(1, 8),
    st.sampled_from(["f32", "f16", "bf16"]),
    st.sampled_from(["f32", "f16"]),
    st.sampled_from(["coalesced", "per_row"]),
    st.integers(0, 10_000),
)
def test_slot_permutation_bitexact(
    b, s, r, f, storage, compute, dma, seed
):
    """Tentpole property: a window-slot layout is a pure renaming.  For
    ANY per-stage slot permutation, the local operator block the kernel
    assembles permutes its columns with the slots BIT-exactly (each
    (row, k) slot lands the same value, repeated indices still sum in
    slot order), and the window rows permute the same way -- so the
    ``W @ window`` product sees the same value pairs, summed by the MXU
    in slot order: outputs agree to within that reassociation (1e-5 of
    each element's sum of |terms|) on every storage x compute rung,
    under both DMA modes.
    This is the invariance that lets ``core.partition`` reorder slots
    for long runs."""
    from repro.kernels.xct_spmm import _stage_block

    sdt = {"f32": jnp.float32, "f16": jnp.float16,
           "bf16": jnp.bfloat16}[storage]
    cdt = {"f32": jnp.float32, "f16": jnp.float16}[compute]
    k, buf, c = 8, 24, 96
    rng = np.random.default_rng(seed)
    inds, vals, winmap, x = _random_ell(rng, b, s, r, k, buf, c, f)
    inds2, wm2, perms = _permute_layout(rng, inds, winmap)
    wide = jnp.asarray(vals).astype(sdt).astype(jnp.float32)
    for bi in range(b):
        for si in range(s):
            w0 = _stage_block(jnp.asarray(inds[bi, si], jnp.int32),
                              wide[bi, si], buf)
            w1 = _stage_block(jnp.asarray(inds2[bi, si], jnp.int32),
                              wide[bi, si], buf)
            np.testing.assert_array_equal(
                np.asarray(w1), np.asarray(w0)[:, perms[bi, si]]
            )
    out = [
        np.asarray(apply_operator(
            jnp.asarray(i), jnp.asarray(vals), jnp.asarray(w),
            jnp.asarray(x), storage_dtype=sdt, compute_dtype=cdt,
            dma=dma,
        ))
        for i, w in ((inds, winmap), (inds2, wm2))
    ]
    # every rung here multiplies in f32 (f16 compute maps to f32) on
    # exactly widened operands, so a permutation can only reassociate a
    # stage's sum: bound it by 1e-5 of the sum of |terms| per element
    mag = np.asarray(apply_operator(
        jnp.asarray(inds), jnp.asarray(np.abs(vals)), jnp.asarray(winmap),
        jnp.asarray(np.abs(x)), storage_dtype=sdt, compute_dtype=cdt,
        dma=dma,
    ))
    assert np.all(np.abs(out[0] - out[1]) <= 1e-5 * mag)


@settings(max_examples=10, deadline=None)
@given(
    st.integers(4, 64), st.integers(1, 12), st.integers(0, 10_000)
)
def test_winmap_segments_roundtrip_property(buf, run_hi, seed):
    """Satellite property (ISSUE 7): for ANY winmap the run-length
    table covers every window row exactly once with power-of-two
    lengths and no overlaps, and the class-sorted table preserves the
    cover while its offsets bracket exact length classes -- the
    contract the sorted coalesced kernel's per-class loops rely on."""
    from repro.kernels.xct_spmm import _dma_classes

    rng = np.random.default_rng(seed)
    wm = _winmap_from_runs(rng, buf, 4 * buf, 1, run_hi)[None, None]
    segs = winmap_segments(wm)
    srt, off = sort_segments_by_class(segs, buf)
    for table in (segs, srt):
        covered = np.zeros(buf, bool)
        rebuilt = np.full(buf, -1, np.int64)
        for src, dst, ln in table[0, 0]:
            if ln == 0:
                continue
            assert ln & (ln - 1) == 0, ln  # power-of-two pieces only
            assert not covered[dst:dst + ln].any()  # no overlap
            covered[dst:dst + ln] = True
            rebuilt[dst:dst + ln] = np.arange(src, src + ln)
        assert covered.all()  # no hole: every row delivered once
        np.testing.assert_array_equal(rebuilt, wm[0, 0])
    lens = srt[0, 0, :, 2]
    assert (np.diff(lens) <= 0).all()  # descending by copy length
    classes = _dma_classes(buf)[::-1]
    o = off[0, 0]
    assert o.shape == (len(classes) + 1,)
    assert (np.diff(o) >= 0).all()
    for i, ln in enumerate(classes):
        assert (lens[o[i]:o[i + 1]] == ln).all(), (ln, o)
    assert (lens[o[-1]:] == 0).all()  # only pads past the last offset
    assert o[-1] == int((lens > 0).sum())


def test_sort_segments_by_class_known():
    """Exact sorted table + offsets on the hand-written winmap of
    ``test_winmap_segments_known`` (stable within a length class)."""
    wm = np.array([[[5, 6, 7, 8, 9, 20, 9, 10, 11]]], np.int32)
    srt, off = sort_segments_by_class(winmap_segments(wm), 9)
    want = [(5, 0, 4), (9, 6, 2), (9, 4, 1), (20, 5, 1), (11, 8, 1)]
    assert [tuple(t) for t in srt[0, 0] if t[2] > 0] == want
    # classes descending for BUF=9: 8, 4, 2, 1; no len-8 segment
    np.testing.assert_array_equal(off[0, 0], [0, 0, 1, 2, 5])


def test_sorted_segments_bitexact_and_validated(small_system):
    """The class-sorted table + offsets drive the kernel to the same
    bits as the unsorted table, and a segoff whose class axis does not
    match BUF raises a named error instead of corrupting copies."""
    _, _, plan = small_system
    op = plan.proj
    inds = jnp.asarray(op.inds[0])
    vals = jnp.asarray(op.vals[0])
    wm = jnp.asarray(op.winmap[0])
    x = jnp.asarray(
        np.random.default_rng(5).normal(
            size=(op.cols_per_dev, 4)
        ).astype(np.float32)
    )
    legacy = apply_operator(
        inds, vals, wm, x, winsegs=jnp.asarray(op.winsegs[0]),
        dma="coalesced",
    )
    sorted_ = apply_operator(
        inds, vals, wm, x, winsegs=jnp.asarray(op.winsegs[0]),
        segoff=jnp.asarray(op.segoff[0]), dma="coalesced",
    )
    np.testing.assert_array_equal(
        np.asarray(legacy), np.asarray(sorted_)
    )
    with pytest.raises(ValueError, match="segoff"):
        apply_operator(
            inds, vals, wm, x, winsegs=jnp.asarray(op.winsegs[0]),
            segoff=jnp.asarray(op.segoff[0][..., :2]), dma="coalesced",
        )


@pytest.mark.parametrize("name", ["float16", "bfloat16"])
def test_widen_decodes_every_16bit_pattern(name):
    """16-bit value tiles reach the kernel as int16 bits (Mosaic cannot
    load an f16 tile on v5e); the in-kernel integer decode must equal
    the float widening for EVERY bit pattern: normals, subnormals,
    signed zeros, infinities (NaNs stay NaN)."""
    from repro.kernels.xct_spmm import _widen

    bits = jnp.asarray(
        np.arange(-(1 << 15), 1 << 15, dtype=np.int32).astype(np.int16)
    )
    want = np.asarray(bits.view(jnp.dtype(name)).astype(jnp.float32))
    got = np.asarray(_widen(bits, name))
    nan = np.isnan(want)
    np.testing.assert_array_equal(
        got[~nan].view(np.int32), want[~nan].view(np.int32)
    )
    assert np.isnan(got[nan]).all()


@pytest.mark.parametrize(
    "rung", [("single", jnp.float32, 2e-4), ("mixed", jnp.float16, 2e-2)],
    ids=lambda r: r[0],
)
def test_fused_kernel_on_bridged_plan_matches_scipy(rung):
    """The fused kernel, in interpret mode, on a plan whose stage
    windows bridge their column gaps: ``A @ x`` and ``A.T @ y`` from
    every device's shard, summed into the output rows, match SciPy.
    Single stores f32, mixed f16; both compute in f32."""
    from repro.core.geometry import XCTGeometry, build_system_matrix
    from repro.core.partition import PartitionConfig, build_plan

    _, storage, tol = rung
    geo = XCTGeometry(n=24, n_angles=32)
    a = build_system_matrix(geo)
    plan = build_plan(geo, PartitionConfig(
        n_data=2, tile=4, rows_per_block=8, nnz_per_stage=8), a=a)
    ap = a[plan.row_perm][:, plan.col_perm].tocsr()
    rng = np.random.default_rng(_seed("bridged", rung[0]))
    for op, mat in ((plan.proj, ap), (plan.back, ap.T.tocsr())):
        # the windows are bridged: some slot below a window's last named
        # slot is named by no nonzero
        named = np.zeros(op.winmap.shape, bool)
        nz = np.nonzero(op.vals)
        named[(*nz[:3], op.inds[nz])] = True
        below = np.cumsum(named[..., ::-1], -1)[..., ::-1] > 0
        assert (below & ~named).any()
        x = np.zeros((op.n_cols_pad, 4), np.float32)
        x[: mat.shape[1]] = rng.random((mat.shape[1], 4))
        out = np.zeros((op.n_rows_pad + 1, 4))
        cpd = op.cols_per_dev
        for p in range(op.inds.shape[0]):
            part = apply_operator(
                jnp.asarray(op.inds[p]), jnp.asarray(op.vals[p]),
                jnp.asarray(op.winmap[p]), jnp.asarray(x[p * cpd:][:cpd]),
                storage_dtype=storage, compute_dtype=jnp.float32,
                winsegs=jnp.asarray(op.winsegs[p]),
                segoff=jnp.asarray(op.segoff[p]),
            )
            np.add.at(out, op.row_map[p].reshape(-1), np.asarray(part))
        ref = mat @ x[: mat.shape[1]].astype(np.float64)
        np.testing.assert_allclose(
            out[: mat.shape[0]], ref, rtol=tol, atol=tol * np.abs(ref).max()
        )


# --------------------------------------------------------------------- #
# one grid step per row block: the stages loop inside the step
# --------------------------------------------------------------------- #
def _runs_ell(rng, b, s, r, k, buf, c, f):
    """Random blocked-ELL operands whose windows are made of runs, so
    the coalesced tables hold multi-row segments."""
    inds = rng.integers(0, buf, size=(b, s, r, k)).astype(np.int16)
    vals = (rng.random((b, s, r, k)) * (rng.random((b, s, r, k)) > 0.3)
            ).astype(np.float32)
    wm = np.stack([
        np.stack([_winmap_from_runs(rng, buf, c, 1, 5) for _ in range(s)])
        for _ in range(b)
    ])
    x = rng.normal(size=(c, f)).astype(np.float32)
    return inds, vals, wm, x


def _fused_tables(dma, wm, buf):
    """``(winsegs, segoff)`` of one window-DMA mode of the fused kernel."""
    if dma == "per_row":
        return None, None
    segs = winmap_segments(wm)
    if dma == "coalesced":
        return segs, None
    return sort_segments_by_class(segs, buf)


def _chunk_budget(dma, b_chunk, s, wm, buf, scales=False):
    """An SMEM budget that fits ``b_chunk`` row blocks' descriptors."""
    from repro.kernels.xct_spmm import _dma_classes

    if dma == "per_row":
        return smem_bytes(b_chunk, s, buf, scales=scales)
    nseg = winmap_segments(wm).shape[-2]
    noff = len(_dma_classes(buf)) + 1 if dma == "sorted" else 0
    return seg_smem_bytes(b_chunk, s, nseg, noff=noff, scales=scales)


def _per_stage_gather(inds, vals, wm, x, storage):
    """The legacy kernel, a grid step per (row block, stage), on the
    same operands: windows gathered up front, fp32 accumulation in
    stage order."""
    window = jnp.take(jnp.asarray(x).astype(storage), jnp.asarray(wm),
                      axis=0)
    return spmm_block_ell_staged(
        jnp.asarray(inds), jnp.asarray(vals).astype(storage), window,
        compute_dtype=jnp.float32,
    )


@pytest.mark.parametrize("chunked", [False, True], ids=["one-call", "chunks"])
@pytest.mark.parametrize("dma", ["sorted", "coalesced", "per_row"])
@pytest.mark.parametrize("storage", [jnp.float32, jnp.float16],
                         ids=["f32", "f16"])
@pytest.mark.parametrize("s", [1, 3, 13])
def test_folded_stages_bitexact_vs_per_stage_kernel(s, storage, dma,
                                                    chunked):
    """The fused kernel loops a row block's S stages inside one grid
    step; the legacy kernel takes a grid step per stage.  Both sum the
    stages in order in fp32, so they agree BIT for bit -- with the
    descriptors split over several calls too, where the window prefetch
    crosses row-block and call boundaries."""
    b, r, k, buf, c, f = 6, 8, 8, 16, 64, 4
    rng = np.random.default_rng(_seed("fold", s, storage, dma, chunked))
    inds, vals, wm, x = _runs_ell(rng, b, s, r, k, buf, c, f)
    segs, off = _fused_tables(dma, wm, buf)
    budget = _chunk_budget(dma, 2, s, wm, buf) if chunked else None
    got = spmm_block_ell(
        jnp.asarray(inds), jnp.asarray(vals).astype(storage),
        jnp.asarray(wm), jnp.asarray(x).astype(storage),
        compute_dtype=jnp.float32, smem_budget=budget,
        winsegs=None if segs is None else jnp.asarray(segs),
        segoff=None if off is None else jnp.asarray(off),
    )
    want = _per_stage_gather(inds, vals, wm, x, storage)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("dma", ["sorted", "coalesced", "per_row"])
@pytest.mark.parametrize("s", [3, 13])
def test_folded_quantized_bitexact_vs_per_stage_kernel(s, dma):
    """int8 values with per-(block, stage) scales through the folded
    kernel, chunked: each stage takes the scale of its own linear step,
    bit for bit what the per-stage kernel gives on the values
    dequantized up front (power-of-two scales make dequantizing exact)."""
    from repro.core.precision import (
        dequantize_block_vals,
        quantize_block_vals,
    )

    b, r, k, buf, c, f = 6, 8, 8, 16, 64, 4
    rng = np.random.default_rng(_seed("fold-q8", s, dma))
    inds, vals, wm, x = _runs_ell(rng, b, s, r, k, buf, c, f)
    vals = vals * np.exp2(rng.integers(-6, 7, size=(b, s, 1, 1)))
    q, exp = quantize_block_vals(jnp.asarray(vals, jnp.float32), jnp.int8)
    segs, off = _fused_tables(dma, wm, buf)
    got = spmm_block_ell(
        jnp.asarray(inds), q, jnp.asarray(wm),
        jnp.asarray(x).astype(jnp.float16), compute_dtype=jnp.float32,
        smem_budget=_chunk_budget(dma, 2, s, wm, buf, scales=True),
        winsegs=None if segs is None else jnp.asarray(segs),
        segoff=None if off is None else jnp.asarray(off), scales=exp,
    )
    wide = np.asarray(dequantize_block_vals(q, exp, jnp.float32))
    window = jnp.take(jnp.asarray(x).astype(jnp.float16), jnp.asarray(wm),
                      axis=0)
    want = spmm_block_ell_staged(jnp.asarray(inds), jnp.asarray(wide),
                                 window, compute_dtype=jnp.float32)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def _pallas_grids(jaxpr, out):
    """The grid of every ``pallas_call`` in a jaxpr tree."""
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            out.append(tuple(eqn.params["grid_mapping"].grid))
        for p in eqn.params.values():
            for sub in jax.tree.leaves(
                p, is_leaf=lambda x: hasattr(x, "eqns")
            ):
                if hasattr(sub, "eqns"):
                    _pallas_grids(sub, out)
                elif hasattr(sub, "jaxpr"):
                    _pallas_grids(sub.jaxpr, out)
    return out


@pytest.mark.parametrize("chunked", [False, True], ids=["one-call", "chunks"])
def test_fused_grid_is_one_step_per_row_block(chunked):
    """The fused ``pallas_call`` has a one-axis grid over its call's
    row blocks: the stages are no grid axis."""
    b, s, r, k, buf, c, f = 6, 5, 8, 8, 16, 64, 4
    rng = np.random.default_rng(_seed("grid", chunked))
    inds, vals, wm, x = _runs_ell(rng, b, s, r, k, buf, c, f)
    budget = _chunk_budget("per_row", 2, s, wm, buf) if chunked else None

    def fn(i, v, w, xx):
        return spmm_block_ell(i, v, w, xx, smem_budget=budget)

    jaxpr = jax.make_jaxpr(fn)(*(jnp.asarray(a) for a in (inds, vals, wm, x)))
    (grid,) = _pallas_grids(jaxpr.jaxpr, [])
    if chunked:  # a scan over calls of a divisor of B's row blocks each
        (bpc,) = grid
        assert bpc < b and b % bpc == 0
    else:
        assert grid == (b,)


def test_vmem_bytes_counts_the_stages_a_step_holds():
    """``stages=S`` holds S stages' index and value tiles, double
    buffered; ``stages=1`` is the per-stage footprint."""
    one = vmem_bytes(32, 32, 784, 128, 4, win_bytes=4)
    assert vmem_bytes(32, 32, 784, 128, 4, win_bytes=4, stages=1) == one
    folded = vmem_bytes(32, 32, 784, 128, 4, win_bytes=4, stages=18)
    assert folded - one == 17 * (2 * 32 * 32 * 2 + 2 * 32 * 32 * 4)
    assert folded - (one - 2 * 32 * 32 * (2 + 4)) <= 221 << 10
    assert folded < VMEM_BUDGET
