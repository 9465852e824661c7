"""Each per-layer reader on a made-up record, and nothing where it has
nothing to read."""
import pytest

from chipbench import peaks, work
from chipbench.metrics import (device_idle_share, host_s_per_slab,
                               solve_roofline, spmm_roofline)

V5E = peaks.peaks("TPU v5 lite")


def _span(name, t0, t1, depth, tid=1):
    return {"kind": "span", "name": name, "t0": t0, "t1": t1,
            "depth": depth, "thread_id": tid, "thread": "MainThread"}


RECORD = {
    "slabs": 2,
    "peaks": V5E,
    "work": {"applies": work.Work(flops=197e9, bytes=819e6),
             "solve": work.Work(flops=1.0, bytes=2 * 819e6)},
    "spans": [
        _span("stream/slab", 0.0, 1.0, 1),
        _span("recon/solve", 0.1, 0.8, 3),
        _span("stream/slab", 1.0, 2.5, 1),
        _span("recon/solve", 1.2, 2.2, 3),
        _span("recon/stage", 1.0, 1.1, 0, tid=2),
    ],
    "trace": {"window_s": 4.0, "busy_s": 3.0,
              "ops": {"closed_call.3 [tpu_custom_call]": [0.5, 10],
                      "fusion.1": [2.0, 5]}},
}


def test_host_seconds_per_slab():
    # (1.0 - 0.7 + 1.5 - 1.0) / 2
    assert host_s_per_slab.read(RECORD) == pytest.approx(0.4)


def test_solve_roofline():
    # 2 slabs x 2 ms least (memory-bound) over 1.7 s of recon/solve
    assert solve_roofline.read(RECORD) == pytest.approx(100 * 4e-3 / 1.7)


def test_spmm_roofline_and_idle_share():
    # 2 slabs x 1 ms least (compute and memory alike) over 0.5 s
    assert spmm_roofline.read(RECORD) == pytest.approx(100 * 2e-3 / 0.5)
    assert device_idle_share.read(RECORD) == pytest.approx(25.0)


@pytest.mark.parametrize("reader", [host_s_per_slab, solve_roofline,
                                    spmm_roofline, device_idle_share])
def test_nothing_to_read_is_none(reader):
    empty = dict(RECORD, spans=None, trace=None, peaks=None)
    assert reader.read(empty) is None


def test_no_kernel_event_is_none():
    rec = dict(RECORD, trace=dict(RECORD["trace"], ops={"fusion.1": [2, 5]}))
    assert spmm_roofline.read(rec) is None
