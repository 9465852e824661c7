"""The reader of the window DMAs a slab costs: on made-up records, on
the spans of a program that counts no DMA, and on a small traced drain
on the CPU, where it reads the operators' segment tables to the
segment."""
import numpy as np
import pytest

from chipbench.metrics import dma_segments_per_slab


def _span(name, **attrs):
    return {"kind": "span", "name": name, "t0": 0.0, "t1": 1.0,
            "thread_id": 1, "thread": "MainThread", "attrs": attrs}


RECORD = {
    "slabs": 2,
    "spans": [
        _span("stream/slab", slab=0),
        _span("recon/dispatch", h2d_bytes=700, dma_segments=1000),
        _span("recon/unpack"),
        _span("stream/slab", slab=1),
        _span("recon/dispatch", h2d_bytes=700, dma_segments=3000),
        _span("recon/stage", h2d_bytes=50),
    ],
}


def test_segments_per_slab():
    assert dma_segments_per_slab.read(RECORD) == 2000


@pytest.mark.parametrize("record", [
    dict(RECORD, spans=None),
    dict(RECORD, slabs=0),
    # the parent program: its dispatch spans carry bytes, no segments
    dict(RECORD, spans=[
        dict(s, attrs={k: v for k, v in s["attrs"].items()
                       if k != "dma_segments"})
        for s in RECORD["spans"]]),
], ids=["no spans", "no slabs", "no counter"])
def test_nothing_to_read_is_none(record):
    assert dma_segments_per_slab.read(record) is None


def test_spans_of_a_traced_drain(tmp_path):
    from repro import obs
    from repro.core.geometry import XCTGeometry
    from repro.core.partition import PartitionConfig, build_plan
    from repro.core.recon import ReconConfig, Reconstructor
    from repro.kernels.ops import dma_issue_count
    from repro.stream import SlabStore, reconstruct_streaming

    geo = XCTGeometry(n=16, n_angles=24)
    plan = build_plan(geo, PartitionConfig(tile=4, rows_per_block=16,
                                           nnz_per_stage=16))
    fuse, slab, n, iters = 2, 4, 8, 2
    rec = Reconstructor(plan, cfg=ReconConfig(
        precision="single", comm_mode="rs", fuse=fuse, interpret=True))
    store = SlabStore.create(str(tmp_path / "sino"), geo.n_rays, n, slab)
    rng = np.random.default_rng(0)
    for j0, j1 in store.slabs():
        store.write(j0, rng.random((geo.n_rays, j1 - j0), np.float32))
    tracer = obs.enable()
    try:
        reconstruct_streaming(rec, store, str(tmp_path / "vol"),
                              iters=iters, y_slab=slab)
    finally:
        obs.disable()
    record = {"spans": list(tracer.events), "slabs": n // slab}
    per_apply = (dma_issue_count(plan.proj.winsegs)
                 + dma_issue_count(plan.back.winsegs))
    assert dma_segments_per_slab.read(record) == (
        per_apply * (iters + 1) * (slab // fuse))
