"""The readers of the per-operator kernel rooflines, the bytes a slab
uploads and the stream's wait and unpack: on made-up records, on the
recorded v5e trace (whose kernel calls have no operator tag), and on the
spans of a small traced drain on the CPU."""
import pathlib

import numpy as np
import pytest

from chipbench import peaks, per_op, trace, work
from chipbench.metrics import (h2d_bytes_per_slab, spmm_back_roofline,
                               spmm_proj_roofline, spmm_roofline,
                               stream_wait_s_per_slab, unpack_s_per_slab)

V5E = peaks.peaks("TPU v5 lite")
DATA = pathlib.Path(__file__).parent / "data" / "preview3_v5e.xplane.pb"
READERS = [spmm_proj_roofline, spmm_back_roofline, h2d_bytes_per_slab,
           stream_wait_s_per_slab, unpack_s_per_slab]


def _span(name, t0, t1, tid=1, **attrs):
    return {"kind": "span", "name": name, "t0": t0, "t1": t1,
            "thread_id": tid, "thread": "MainThread", "attrs": attrs}


RECORD = {
    "slabs": 2,
    "peaks": V5E,
    "work": {"applies": work.Work(flops=197e9, bytes=819e6)},
    "spans": [
        _span("stream/wait", 0.0, 0.3),
        _span("stream/slab", 0.3, 1.0, slab=0),
        _span("recon/dispatch", 0.35, 0.4, h2d_bytes=700),
        _span("recon/unpack", 0.8, 0.85),
        _span("stream/wait", 1.0, 1.1),
        _span("stream/slab", 1.1, 2.0, slab=1),
        _span("recon/dispatch", 1.15, 1.2, h2d_bytes=700),
        _span("recon/unpack", 1.8, 1.95),
        _span("recon/stage", 0.5, 0.6, tid=2, h2d_bytes=50),
        _span("recon/stage", 1.5, 1.6, tid=2, h2d_bytes=50),
    ],
    "trace": {"window_s": 4.0, "busy_s": 3.0,
              "ops": {"xct_spmm_proj.12 [tpu_custom_call]": [0.25, 10],
                      "xct_spmm_proj.13 [tpu_custom_call]": [0.15, 10],
                      "xct_spmm_back [tpu_custom_call]": [0.1, 10],
                      "xct_spmm_backward.1 [tpu_custom_call]": [9.0, 1],
                      "fusion.1": [2.0, 5]}},
}


def test_per_operator_rooflines():
    # each operator: half of 2 slabs x 1 ms least (compute and memory
    # alike), over 0.4 s of proj and 0.1 s of back
    assert spmm_proj_roofline.read(RECORD) == pytest.approx(100 * 1e-3 / 0.4)
    assert spmm_back_roofline.read(RECORD) == pytest.approx(100 * 1e-3 / 0.1)


def test_tagged_seconds_cover_the_kernel():
    ops = dict(RECORD["trace"]["ops"])
    del ops["xct_spmm_backward.1 [tpu_custom_call]"]
    both = per_op.seconds(ops, "proj") + per_op.seconds(ops, "back")
    assert both == pytest.approx(spmm_roofline.kernel_seconds(ops))


def test_bytes_wait_and_unpack_per_slab():
    assert h2d_bytes_per_slab.read(RECORD) == 750
    assert stream_wait_s_per_slab.read(RECORD) == pytest.approx(0.2)
    assert unpack_s_per_slab.read(RECORD) == pytest.approx(0.1)


@pytest.mark.parametrize("reader", READERS)
def test_nothing_to_read_is_none(reader):
    empty = dict(RECORD, spans=None, trace=None, peaks=None)
    assert reader.read(empty) is None


@pytest.mark.parametrize("reader", READERS)
def test_a_program_without_the_spans_or_names_reads_none(reader):
    """What the readers find in a run of a program that names no
    kernel call, counts no transfer and opens none of the new spans."""
    old = dict(RECORD, spans=[
        dict(s, attrs={}) for s in RECORD["spans"]
        if s["name"] not in ("stream/wait", "recon/unpack")
    ], trace=dict(RECORD["trace"], ops={
        "closed_call.26 [tpu_custom_call]": [0.5, 10]}))
    assert reader.read(old) is None


@pytest.fixture(scope="module")
def recorded():
    if not DATA.exists():
        pytest.fail(f"missing recorded trace {DATA}")
    return trace.reduce(DATA)


def test_recorded_trace_has_no_operator_tags(recorded):
    """The committed trace predates the names: the kernel is there, the
    per-operator readers find nothing."""
    assert spmm_roofline.kernel_seconds(recorded["ops"]) > 0
    assert set(recorded) == {"window_s", "busy_s", "chips", "ops", "gaps"}
    rec = dict(RECORD, trace=recorded)
    assert spmm_proj_roofline.read(rec) is None
    assert spmm_back_roofline.read(rec) is None


def test_spans_of_a_traced_drain(tmp_path):
    """A small drain on the CPU, traced: the readers find one wait and
    one unpack a slab, and the uploaded bytes to the byte."""
    from repro import obs
    from repro.core.geometry import XCTGeometry
    from repro.core.partition import PartitionConfig, build_plan
    from repro.core.recon import ReconConfig, Reconstructor
    from repro.stream import SlabStore, reconstruct_streaming

    geo = XCTGeometry(n=16, n_angles=24)
    plan = build_plan(geo, PartitionConfig(tile=4, rows_per_block=16,
                                           nnz_per_stage=16))
    rec = Reconstructor(plan, cfg=ReconConfig(
        precision="single", comm_mode="rs", fuse=2, interpret=True))
    slab, n = 4, 8
    store = SlabStore.create(str(tmp_path / "sino"), geo.n_rays, n, slab)
    rng = np.random.default_rng(0)
    for j0, j1 in store.slabs():
        store.write(j0, rng.random((geo.n_rays, j1 - j0), np.float32))
    tracer = obs.enable()
    try:
        reconstruct_streaming(rec, store, str(tmp_path / "vol"), iters=2,
                              y_slab=slab)
    finally:
        obs.disable()
    record = dict(RECORD, spans=list(tracer.events), slabs=n // slab)
    operator = sum(np.asarray(a).nbytes for a in rec._arrays.values())
    vectors = (rec.tomo_pad + rec.sino_pad) * slab * 4  # x0 and sino
    assert h2d_bytes_per_slab.read(record) == operator + vectors
    assert stream_wait_s_per_slab.read(record) > 0
    assert unpack_s_per_slab.read(record) > 0
