"""The reduction from a profiler trace to metrics, on a small trace
recorded on a TPU v5e (``shale-b8`` at 3 CG iterations, one scan of two
slabs in a 1.4 s window, seed 2147483711)
and on made-up intervals."""
import pathlib

import pytest

from chipbench import trace
from chipbench.metrics import device_idle_share, spmm_roofline

DATA = pathlib.Path(__file__).parent / "data" / "preview3_v5e.xplane.pb"


def test_self_times_subtract_nested_events():
    got = trace._self_times([(0, 10, "while"), (1, 3, "k"), (4, 8, "k"),
                             (5, 6, "f"), (12, 13, "g")])
    assert sorted(got) == [("f", 1), ("g", 1), ("k", 2), ("k", 3),
                           ("while", 4)]


def test_op_names():
    assert trace.op_name("%fusion.35 = (f32[128]) fusion(%x), kind=kLoop") \
        == "fusion.35"
    assert trace.op_name(
        '%closed_call.24 = f32[12] custom-call(%a), '
        'custom_call_target="tpu_custom_call", x={}'
    ) == "closed_call.24 [tpu_custom_call]"


def test_union_merges_overlaps_and_nesting():
    got = trace._union([(5, 7), (0, 2), (1, 3), (6, 6), (10, 12), (11, 11)])
    assert got == [[0, 3], [5, 7], [10, 12]]


def test_gaps_are_named_by_the_innermost_main_thread_span():
    spans = [
        {"kind": "span", "thread": "MainThread", "name": "stream/slab",
         "t0": 100.0, "t1": 101.0, "depth": 1},
        {"kind": "span", "thread": "MainThread", "name": "stream/write",
         "t0": 100.6, "t1": 100.9, "depth": 2},
        {"kind": "span", "thread": "prefetch", "name": "stream/load",
         "t0": 100.0, "t1": 101.0, "depth": 0},
    ]
    got = trace.name_gaps([[0.1, 0.2], [0.7, 0.8], [1.5, 1.6]], spans,
                          t_open=100.0)
    assert got == pytest.approx({"stream/slab": 0.1, "stream/write": 0.1,
                                 "no host span": 0.1})


@pytest.fixture(scope="module")
def reduced():
    if not DATA.exists():
        pytest.fail(f"missing recorded trace {DATA}")
    return trace.reduce(DATA)


def test_recorded_trace_reduces(reduced):
    assert reduced["chips"] == 1
    assert 0 < reduced["busy_s"] <= reduced["window_s"]
    idle = sum(b - a for a, b in reduced["gaps"])
    assert idle == pytest.approx(reduced["window_s"] - reduced["busy_s"],
                                 rel=1e-6, abs=1e-9)
    assert all(0 <= a < b <= reduced["window_s"] + 1e-9
               for a, b in reduced["gaps"])


def test_recorded_trace_holds_the_kernel(reduced):
    kernel_s = spmm_roofline.kernel_seconds(reduced["ops"])
    assert 0 < kernel_s <= reduced["busy_s"]
    share = device_idle_share.read({"trace": reduced})
    assert 0 < share < 100
