"""Out-of-core streaming: store integrity, slab parity, budget, resume.

Acceptance pins (ISSUE 4):
  * a streaming solve under a budget smaller than the full
    sinogram+volume working set completes and matches the in-memory
    ``Reconstructor.reconstruct`` slice for slice;
  * a run killed after slab k and restarted skips the finished slabs
    and produces a volume *identical* to an uninterrupted run.
"""
import os

import numpy as np
import pytest

from repro.core.recon import ReconConfig, Reconstructor, StagedSlab
from repro.data.phantom import phantom_slices, simulate_measurements
from repro.stream import (
    PrefetchError,
    Prefetcher,
    SlabStore,
    reconstruct_streaming,
    simulate_to_store,
    suggest_slab,
)

Y = 8  # slices in the streaming fixtures (multiple of fuse=2)


@pytest.fixture(scope="module")
def rec(small_system):
    _, _, plan = small_system
    return Reconstructor(
        plan, cfg=ReconConfig(precision="single", comm_mode="rs", fuse=2)
    )


@pytest.fixture(scope="module")
def sino8(small_system):
    geo, a, _ = small_system
    x = phantom_slices(geo.n, Y, seed=5)
    return simulate_measurements(a, x, noise=0.01, seed=5)


@pytest.fixture()
def sino_store(small_system, sino8, tmp_path):
    geo, a, _ = small_system
    store = SlabStore.create(str(tmp_path / "sino"), geo.n_rays, Y, 2)
    simulate_to_store(a, geo.n, store, noise=0.01, seed=5)
    return store


# --------------------------------------------------------------------- #
# store
# --------------------------------------------------------------------- #
def test_slab_store_roundtrip(tmp_path):
    rng = np.random.default_rng(0)
    arr = rng.standard_normal((13, 10)).astype(np.float32)
    store = SlabStore.from_array(str(tmp_path / "s"), arr, slab=3)
    assert store.slabs() == [(0, 3), (3, 6), (6, 9), (9, 10)]
    assert store.complete()
    np.testing.assert_array_equal(store.to_array(), arr)
    # cross-shard range read
    np.testing.assert_array_equal(store.read(2, 8), arr[:, 2:8])
    # reopen sees the same manifest + data
    again = SlabStore.open(str(tmp_path / "s"))
    np.testing.assert_array_equal(again.read(9, 10), arr[:, 9:])


def test_slab_store_guards(tmp_path):
    store = SlabStore.create(str(tmp_path / "s"), 4, 8, 4)
    with pytest.raises(ValueError):  # unaligned start
        store.write(2, np.zeros((4, 4), np.float32))
    with pytest.raises(ValueError):  # wrong shape
        store.write(0, np.zeros((4, 3), np.float32))
    with pytest.raises(FileNotFoundError):  # unwritten slab
        store.read(0, 4)
    assert not store.complete()
    with pytest.raises(ValueError):  # conflicting re-create
        SlabStore.create(str(tmp_path / "s"), 4, 8, 2)


def test_simulate_to_store_matches_oneshot(small_system, sino_store,
                                           sino8):
    """Slab-by-slab simulation == one-shot, bit for bit (chunk-invariant
    noise streams + slab-ranged phantoms)."""
    np.testing.assert_array_equal(sino_store.to_array(), sino8)


def test_phantom_slab_range_invariant():
    full = phantom_slices(16, 6, seed=2)
    parts = [
        phantom_slices(16, 6, seed=2, start=j, stop=min(j + 4, 6))
        for j in (0, 4)
    ]
    np.testing.assert_array_equal(np.concatenate(parts, axis=1), full)


def test_simulate_chunk_kwarg_invariant(small_system):
    geo, a, _ = small_system
    x = phantom_slices(geo.n, 6, seed=1)
    y1 = simulate_measurements(a, x, noise=0.05, seed=1, chunk=1)
    y64 = simulate_measurements(a, x, noise=0.05, seed=1, chunk=64)
    np.testing.assert_array_equal(y1, y64)


# --------------------------------------------------------------------- #
# scheduler
# --------------------------------------------------------------------- #
def test_suggest_slab_formula_and_guard(small_system, rec):
    _, _, plan = small_system
    topo = rec.topology
    # operator footprint (incl. the winsegs DMA tables) + some slack
    budget = plan.proj.hbm_bytes() + plan.back.hbm_bytes() + 1_000_000
    sp = suggest_slab(plan, rec.cfg, topo, budget, n_slices=Y)
    assert sp.granule == 2 and sp.y_slab % 2 == 0
    assert sp.slab_bytes <= budget
    # 5 host copies + the overlap-staged device sinogram of slab i+1
    per = (
        4 * 5 * (plan.proj.n_rows_pad + plan.proj.n_cols_pad)
        + 4 * plan.proj.n_rows_pad
    )
    assert sp.per_slice_bytes == per
    with pytest.raises(ValueError):  # operator alone overflows
        suggest_slab(plan, rec.cfg, topo, sp.fixed_bytes)
    sync = suggest_slab(
        plan, rec.cfg, topo, budget, n_slices=Y, overlap=False
    )
    assert sync.per_slice_bytes < sp.per_slice_bytes  # one staging copy


def test_prefetcher_orders_and_propagates_errors():
    seen = []

    def fetch(i):
        seen.append(i)
        if i == 3:
            raise RuntimeError("boom")
        return i * 10

    items = [0, 1, 2]
    out = list(Prefetcher(fetch, items, depth=1))
    assert out == [(0, 0), (1, 10), (2, 20)]
    with pytest.raises(RuntimeError, match="boom"):
        list(Prefetcher(fetch, [3], depth=1))
    # disabled -> plain synchronous order
    assert list(Prefetcher(lambda i: i, [5, 6], enabled=False)) == [
        (5, 5), (6, 6),
    ]


def test_prefetcher_error_names_failing_item():
    """Satellite: a dead fetch thread surfaces at the consuming next()
    as PrefetchError carrying the failing item + position -- mid-drain,
    not a hang, and not attributed to the wrong slab."""

    def fetch(i):
        if i == 12:
            raise OSError("disk gone")
        return i

    got = []
    with pytest.raises(PrefetchError, match=r"item 12 .*disk gone") as e:
        for item, val in Prefetcher(fetch, [4, 8, 12, 16], depth=1):
            got.append(item)
    assert got == [4, 8]  # slabs before the failure were delivered
    assert e.value.item == 12 and e.value.index == 2
    assert isinstance(e.value.__cause__, OSError)
    # the synchronous path wraps identically
    with pytest.raises(PrefetchError, match="item 12"):
        list(Prefetcher(fetch, [12], enabled=False))


def test_prefetcher_stage_applies_and_times():
    """The device-stage callable runs in the worker (overlap) and
    inline (sync) with identical results, and per-item load/stage wall
    times are recorded either way (keyed by position, so unhashable or
    duplicated items are fine)."""
    for enabled in (True, False):
        pre = Prefetcher(
            lambda i: i * 10, [1, 1], stage=lambda v: v + 5,
            enabled=enabled,
        )
        assert list(pre) == [(1, 15), (1, 15)]
        assert set(pre.times) == {0, 1}  # positions, not item values
        for t in pre.times.values():
            assert t["load"] >= 0.0 and t["stage"] >= 0.0
    # unhashable items are accepted
    pre = Prefetcher(lambda a: float(a.sum()), [np.zeros(2)], depth=1)
    out = list(pre)
    assert len(out) == 1 and out[0][1] == 0.0 and 0 in pre.times
    # a failing stage is attributed like a failing fetch
    with pytest.raises(PrefetchError, match="item 7"):
        list(Prefetcher(
            lambda i: i, [7],
            stage=lambda v: (_ for _ in ()).throw(ValueError("up")),
        ))


# --------------------------------------------------------------------- #
# driver: parity, budget, resume
# --------------------------------------------------------------------- #
def test_streaming_matches_in_memory_slicewise(
    rec, sino_store, sino8, tmp_path
):
    """Pinned parity: each streamed slab is BIT-identical to the
    in-memory ``Reconstructor.reconstruct`` of that slab, and the
    assembled volume tracks the full-Y in-memory solve (which XLA may
    reassociate per compile shape) to well under the phantom scale."""
    res = reconstruct_streaming(
        rec, sino_store, str(tmp_path / "vol"), iters=8, y_slab=4
    )
    assert res.complete and res.solved == [0, 4]
    for j0, j1 in res.volume.slabs():
        x_mem, r_mem = rec.reconstruct(sino8[:, j0:j1], iters=8)
        np.testing.assert_array_equal(res.volume.read(j0, j1), x_mem)
        np.testing.assert_array_equal(res.resnorms[:, j0:j1], r_mem)
    x_full, _ = rec.reconstruct(sino8, iters=8)
    num = np.linalg.norm(res.volume.to_array() - x_full, axis=0)
    den = np.linalg.norm(x_full, axis=0)
    assert (num / den).max() < 1e-2


def test_streaming_budget_smaller_than_volume_completes(
    rec, small_system, sino_store, sino8, tmp_path
):
    """Acceptance: a budget that cannot hold the full sinogram+volume
    working set still completes, in several slabs, matching in-memory."""
    _, _, plan = small_system
    sp = suggest_slab(plan, rec.cfg, rec.topology, 1 << 40)
    full_need = sp.fixed_bytes + Y * sp.per_slice_bytes
    budget = sp.fixed_bytes + (Y // 2) * sp.per_slice_bytes
    assert budget < full_need
    res = reconstruct_streaming(
        rec, sino_store, str(tmp_path / "vol"), iters=6,
        mem_budget=budget,
    )
    assert res.complete and len(res.solved) >= 2
    assert res.y_slab * res.volume.rows  # sanity
    for j0, j1 in res.volume.slabs():
        x_mem, _ = rec.reconstruct(sino8[:, j0:j1], iters=6)
        np.testing.assert_array_equal(res.volume.read(j0, j1), x_mem)


def test_streaming_resume_skips_and_matches(rec, sino_store, tmp_path):
    """Acceptance: killed after slab k + restarted == uninterrupted,
    identically, with the finished slabs skipped (not re-solved)."""
    base = reconstruct_streaming(
        rec, sino_store, str(tmp_path / "v0"), iters=6, y_slab=2
    )
    ck = str(tmp_path / "ck")
    part = reconstruct_streaming(
        rec, sino_store, str(tmp_path / "v1"), iters=6, y_slab=2,
        ckpt_dir=ck, checkpoint_every=1, max_slabs=2,
    )
    assert part.solved == [0, 2] and not part.complete
    rest = reconstruct_streaming(
        rec, sino_store, str(tmp_path / "v1"), iters=6, y_slab=2,
        ckpt_dir=ck,
    )
    assert rest.skipped == [0, 2]  # finished slabs not re-solved
    assert rest.solved == [4, 6] and rest.complete
    np.testing.assert_array_equal(
        rest.volume.to_array(), base.volume.to_array()
    )
    np.testing.assert_array_equal(rest.resnorms, base.resnorms)
    # guards: mismatched slab size on resume is an error -- from the
    # volume store's manifest (same out dir) or the ckpt manifest
    # (fresh out dir, stale ckpt_dir)
    with pytest.raises(ValueError, match="manifest"):
        reconstruct_streaming(
            rec, sino_store, str(tmp_path / "v1"), iters=6, y_slab=4,
            ckpt_dir=ck,
        )
    with pytest.raises(ValueError, match="y_slab|checkpoint"):
        reconstruct_streaming(
            rec, sino_store, str(tmp_path / "v2"), iters=6, y_slab=4,
            ckpt_dir=ck,
        )


def test_streaming_overlap_is_pure_schedule(rec, sino_store, tmp_path):
    """Prefetching and device-upload double-buffering must not change
    results (same discipline as the Fig. 8 overlap test): every cell of
    the (disk overlap) x (device upload) A/B grid is bit-identical."""
    outs = {}
    for overlap in (False, True):
        for upload in ("sync", "overlap"):
            tag = f"{overlap}-{upload}"
            outs[tag] = reconstruct_streaming(
                rec, sino_store, str(tmp_path / tag), iters=5, y_slab=4,
                overlap=overlap, device_upload=upload,
            )
    base = outs["False-sync"].volume.to_array()
    for tag, res in outs.items():
        np.testing.assert_array_equal(base, res.volume.to_array())
    # only the fully overlapped schedule hides the upload
    assert outs["True-overlap"].upload_overlapped
    assert not outs["True-sync"].upload_overlapped
    assert not outs["False-overlap"].upload_overlapped


def test_streaming_timing_split(rec, sino_store, tmp_path):
    """The per-slab load/upload/solve split is recorded for every
    solved slab, in both upload modes (ISSUE 5: BENCH_stream derives
    upload-hidden-under-solve from these fields)."""
    for upload in ("sync", "overlap"):
        res = reconstruct_streaming(
            rec, sino_store, str(tmp_path / f"t_{upload}"), iters=4,
            y_slab=4, overlap=True, device_upload=upload,
        )
        n = len(res.solved)
        assert n == 2
        assert len(res.load_s) == n
        assert len(res.upload_s) == n
        assert len(res.solve_s) == n
        assert all(t > 0 for t in res.solve_s)
        assert all(t >= 0 for t in res.load_s)
        assert all(t >= 0 for t in res.upload_s)
        # solve dominates this CPU workload: the hidden upload fits
        # under it, which is what "upload hidden under solve" means
        if upload == "overlap":
            assert res.upload_overlapped
    with pytest.raises(ValueError, match="device_upload"):
        reconstruct_streaming(
            rec, sino_store, str(tmp_path / "bad"), iters=2, y_slab=4,
            device_upload="nope",
        )


def test_staged_slab_reconstruct_matches(rec, sino8):
    """Reconstructor.stage_sino + reconstruct(StagedSlab) is the same
    computation as reconstruct(numpy), bit for bit."""
    y = sino8[:, :4]
    staged = rec.stage_sino(y)
    assert isinstance(staged, StagedSlab) and staged.n_slices == 4
    x_direct, r_direct = rec.reconstruct(y, iters=5)
    x_staged, r_staged = rec.reconstruct(staged, iters=5)
    np.testing.assert_array_equal(x_direct, x_staged)
    np.testing.assert_array_equal(r_direct, r_staged)


def test_streaming_guards(rec, sino_store, tmp_path):
    with pytest.raises(ValueError, match="exactly one"):
        reconstruct_streaming(
            rec, sino_store, str(tmp_path / "v"), iters=2
        )
    with pytest.raises(ValueError, match="multiple"):
        reconstruct_streaming(
            rec, sino_store, str(tmp_path / "v"), iters=2, y_slab=3
        )
    bad = SlabStore.create(str(tmp_path / "bad"), 7, Y, 2)
    with pytest.raises(ValueError, match="rows"):
        reconstruct_streaming(
            rec, bad, str(tmp_path / "v"), iters=2, y_slab=2
        )
    assert os.path.isdir(sino_store.directory)


def test_slab_store_concurrent_range_reads(tmp_path):
    """Memmap-backed range reads are safe under concurrency: two threads
    reading overlapping ranges of the same store must both see exactly
    the published bytes (the serve layer streams previews off shards
    other readers may be scanning)."""
    import threading

    rng = np.random.default_rng(3)
    arr = rng.standard_normal((17, 12)).astype(np.float32)
    store = SlabStore.from_array(str(tmp_path / "c"), arr, slab=4)

    ranges = [(0, 8), (4, 12), (2, 10), (0, 12)]
    results = {}
    errors = []

    def reader(tid, j0, j1):
        try:
            acc = [store.read(j0, j1) for _ in range(20)]
            for a in acc[1:]:  # every re-read identical
                np.testing.assert_array_equal(acc[0], a)
            results[tid] = acc[0]
        except Exception as e:  # noqa: BLE001
            errors.append((tid, e))

    threads = [
        threading.Thread(target=reader, args=(i, j0, j1))
        for i, (j0, j1) in enumerate(ranges)
    ]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert not errors, errors
    for i, (j0, j1) in enumerate(ranges):
        np.testing.assert_array_equal(results[i], arr[:, j0:j1])


# --------------------------------------------------------------------- #
# ragged scans: any slice count, the short last slab padded to whole
# fused passes
# --------------------------------------------------------------------- #
Y_RAGGED = 9  # slabs of 4, 4 and 1: the last is half a fused pass (fuse=2)
# few iterations: CG's loss of orthogonality amplifies rounding with
# every step (at 10 iterations a float32 solve of this scan departs 3%
# from the float64 one), and the test is of the slabs, not of CG
RAGGED_ITERS = 4
# widest relative gap to the float64 CGNR, per slice, of the volume and
# of the reported residuals: the single rung (float32 throughout) reads
# 8.4e-6 and 9.2e-8, so 1e-4; the mixed rung (float16 values and
# vectors) reads 9.5e-4 and 1.9e-4, so 1e-2.  A slice left unsolved
# (gap 1) or scaled back with another slice's scale fails either.
RAGGED_TOL = {"single": 1e-4, "mixed": 1e-2}


def _cgnr64(a, y, iters):
    """The plain float64 CGNR from x0 = 0 on the scipy ``A``: the
    reference the ragged drains are held to."""
    a = a.astype(np.float64)
    r = np.array(y, np.float64)
    x = np.zeros((a.shape[1], r.shape[1]))
    s = a.T @ r
    p = s.copy()
    gamma = (s * s).sum(0)
    res = []
    for _ in range(iters):
        q = a @ p
        alpha = gamma / (q * q).sum(0)
        x += alpha * p
        r -= alpha * q
        s = a.T @ r
        gamma_new = (s * s).sum(0)
        p = s + gamma_new / gamma * p
        gamma = gamma_new
        res.append(np.sqrt((r * r).sum(0)))
    return x, np.asarray(res)


def _rec(plan, rung):
    return Reconstructor(
        plan, cfg=ReconConfig(precision=rung, comm_mode="rs", fuse=2)
    )


@pytest.fixture(scope="module")
def sino9(small_system):
    geo, a, _ = small_system
    x = phantom_slices(geo.n, Y_RAGGED, seed=7)
    return simulate_measurements(a, x, noise=0.01, seed=7)


@pytest.fixture()
def ragged_store(small_system, sino9, tmp_path):
    geo, _, _ = small_system
    store = SlabStore.create(
        str(tmp_path / "sino9"), geo.n_rays, Y_RAGGED, 4
    )
    for j0, j1 in store.slabs():
        store.write(j0, sino9[:, j0:j1])
    return store


@pytest.mark.parametrize("rung", ["single", "mixed"])
def test_ragged_scan_matches_float64_cgnr(
    small_system, sino9, ragged_store, tmp_path, rung
):
    """9 slices in slabs of 4 end in a 1-slice slab: every slice of the
    streamed volume, and its residuals, agree with the float64 CGNR,
    and the short slab is the in-memory solve of that slice, bit for
    bit."""
    _, a, plan = small_system
    rec = _rec(plan, rung)
    res = reconstruct_streaming(
        rec, ragged_store, str(tmp_path / "vol"), iters=RAGGED_ITERS,
        y_slab=4,
    )
    assert res.complete and res.solved == [0, 4, 8]
    assert res.volume.slabs() == [(0, 4), (4, 8), (8, 9)]
    x = res.volume.to_array()
    x64, r64 = _cgnr64(a, sino9, RAGGED_ITERS)
    gap = np.linalg.norm(x - x64, axis=0) / np.linalg.norm(x64, axis=0)
    assert gap.max() < RAGGED_TOL[rung], gap
    assert np.abs(res.resnorms / r64 - 1).max() < RAGGED_TOL[rung]
    x_mem, r_mem = rec.reconstruct(sino9[:, 8:9], iters=RAGGED_ITERS)
    np.testing.assert_array_equal(res.volume.read(8, 9), x_mem)
    np.testing.assert_array_equal(res.resnorms[:, 8:9], r_mem)


@pytest.mark.parametrize("rung", ["single", "mixed"])
def test_padding_lanes_stay_zero_through_the_solve(
    small_system, sino9, rung
):
    """A 3-slice slab is staged as 4 lanes, the last zero with scale 1;
    the jitted solve keeps that lane exactly zero and finite (the CG
    scalars divide by at least ``tiny``; the mixed rung's per-slice
    renormalization scales a zero column by a finite power of two)."""
    _, _, plan = small_system
    rec = _rec(plan, rung)
    staged = rec.stage_sino(sino9[:, :3])
    assert (staged.n_slices, staged.pad_slices) == (3, 1)
    assert staged.y.shape == (rec.sino_pad, 4)
    assert staged.scale.shape == (4,) and staged.scale[3] == 1.0
    assert not np.asarray(staged.y)[:, 3].any()
    x0 = np.zeros((rec.tomo_pad, 4), np.float32)
    x, r = rec._get_fn("cg", RAGGED_ITERS)(rec._arrays, staged.y, x0)
    x, r = np.asarray(x), np.asarray(r)
    assert np.isfinite(x).all() and np.isfinite(r).all()
    assert not x[:, 3].any() and not r[:, 3].any()
    assert x[:, :3].any()
    x_nat, r_nat = rec.reconstruct(staged, iters=RAGGED_ITERS)
    assert x_nat.shape == (rec.plan.geo.n_vox, 3)
    assert r_nat.shape == (RAGGED_ITERS, 3)


def test_full_slab_solves_unpadded_as_before(rec, sino8):
    """A slab of whole fused passes is not padded, and its solve is the
    computation from before padding existed -- pack, scale, solve on
    exactly its lanes, unpack, scale back -- bit for bit."""
    y = sino8[:, :4]
    staged = rec.stage_sino(y)
    assert staged.pad_slices == 0 and staged.y.shape[1] == 4
    assert staged.scale.shape == (4,)
    packed = rec.pack_sino(y)
    m = np.abs(packed).max(axis=0)
    scale = np.exp2(
        np.round(np.log2(1.0 / np.maximum(m, 1e-30)))
    ).astype(np.float32)
    np.testing.assert_array_equal(staged.scale, scale)
    x, r = rec._get_fn("cg", 5)(
        rec._arrays, packed * scale,
        np.zeros((rec.tomo_pad, 4), np.float32),
    )
    x_new, r_new = rec.reconstruct(staged, iters=5)
    np.testing.assert_array_equal(x_new, rec.unpack_tomo(x) / scale)
    np.testing.assert_array_equal(r_new, np.asarray(r) / scale)


def test_ragged_drain_counts_whole_padded_passes(
    small_system, ragged_store, tmp_path, fresh_obs
):
    """Slabs of 4, 4 and 1 slices solve 2, 2 and 1 fused passes (fuse
    2): the applies, the window DMAs, the kernel grid steps and the
    modeled exchange count the padded pass whole, and the short slab's
    one zero lane is counted (``padded_slices_total``, ``pad_slices`` on
    ``recon/stage`` and ``recon/dispatch``)."""
    from repro.kernels.ops import dma_issue_count

    tracer, m = fresh_obs
    _, _, plan = small_system
    rec = _rec(plan, "single")
    rec._obs_traffic = {"ici": 1.0, "dci": 0.0}  # one byte a minibatch
    iters = 3
    reconstruct_streaming(
        rec, ragged_store, str(tmp_path / "vol"), iters=iters, y_slab=4
    )
    passes = 2 + 2 + 1
    applies = (iters + 1) * passes
    for op in ("proj", "back"):
        want = dma_issue_count(getattr(plan, op).winsegs) * applies
        assert want and m.get("spmm_dma_segments_total", op=op) == want
        p, b = getattr(plan, op).inds.shape[:2]
        assert m.get("spmm_grid_steps_total", op=op) == p * b * applies
    assert m.get("padded_slices_total") == 1
    events = tracer.events
    pads = {name: sorted(e["attrs"]["pad_slices"] for e in events
                         if e["name"] == name and e["kind"] == "span")
            for name in ("recon/stage", "recon/dispatch")}
    assert pads == {"recon/stage": [0, 0, 1], "recon/dispatch": [0, 0, 1]}
    ici = sum(e["attrs"]["ici_bytes"] for e in events
              if e["name"] == "recon/exchange")
    assert ici == applies
    assert m.get("comm_bytes_total", link="ici") == applies


def test_ragged_resume_solves_the_short_slab(rec, ragged_store, tmp_path):
    """Killed after the two full slabs and restarted: the short slab is
    solved from the manifest, the volume is identical to an
    uninterrupted run, and a manifest with the short slab done leaves
    nothing to solve."""
    base = reconstruct_streaming(
        rec, ragged_store, str(tmp_path / "v0"), iters=6, y_slab=4
    )
    ck = str(tmp_path / "ck")
    part = reconstruct_streaming(
        rec, ragged_store, str(tmp_path / "v1"), iters=6, y_slab=4,
        ckpt_dir=ck, checkpoint_every=1, max_slabs=2,
    )
    assert part.solved == [0, 4] and not part.complete
    rest = reconstruct_streaming(
        rec, ragged_store, str(tmp_path / "v1"), iters=6, y_slab=4,
        ckpt_dir=ck,
    )
    assert rest.skipped == [0, 4] and rest.solved == [8] and rest.complete
    np.testing.assert_array_equal(
        rest.volume.to_array(), base.volume.to_array()
    )
    np.testing.assert_array_equal(rest.resnorms, base.resnorms)
    again = reconstruct_streaming(
        rec, ragged_store, str(tmp_path / "v1"), iters=6, y_slab=4,
        ckpt_dir=ck,
    )
    assert again.solved == [] and again.skipped == [0, 4, 8]


def test_ragged_short_slab_escalates_one_rung(
    small_system, sino9, ragged_store, tmp_path
):
    """A short slab whose q8 solve keeps blowing up is re-solved at f32
    from the same padded staging, as a full slab would be."""
    from repro.resil import FaultPlan, RetryPolicy, inject

    _, _, plan = small_system
    fplan = FaultPlan(seed=9).add(
        "recon/solve", "nonfinite", key=2, attempts=None,
        when={"precision": "q8"},
    )
    with inject.activate(fplan):
        res = reconstruct_streaming(
            _rec(plan, "q8"), ragged_store, str(tmp_path / "v"), iters=5,
            y_slab=4, retry=RetryPolicy(max_attempts=2, base_delay_s=0.0),
        )
    assert res.complete and res.failed_slabs == [] and res.escalated == [8]
    x_f32, _ = _rec(plan, "single").reconstruct(sino9[:, 8:9], iters=5)
    np.testing.assert_array_equal(res.volume.read(8, 9), x_f32)
