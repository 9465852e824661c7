"""A scan whose slice count is not a multiple of the slab, through the
stream driver on the CPU: its short last slab is solved padded to a
whole pass, sampled, compared with the float64 CGNR, and read by
``pad_lane_share`` and ``short_solve_share``."""
import numpy as np
import pytest
from conftest import make_root, run_cell

from chipbench.drivers import stream
from chipbench.metrics import pad_lane_share, short_solve_share

# 128 + 6 slices: one whole slab and a short one of 6, padded to 128
RAGGED = {"slices": 134, "published": {"angles": 48, "slices": 134,
                                       "channels": 32}}


@pytest.fixture(scope="module")
def ragged_run(tmp_path_factory):
    """The run, with the program's spans recorded from its warm-up on
    (the CPU has no device trace to take)."""
    from repro import obs

    root = make_root(tmp_path_factory.mktemp("ragged"), config=RAGGED)
    tracer = obs.enable()
    try:
        cell, record = run_cell(root, seed=2**31 + 5)
    finally:
        obs.disable()
    return cell, dict(record, spans=list(tracer.events))


def test_short_slab_is_solved_sampled_and_correct(ragged_run):
    cell, record = ragged_run
    assert record["correct"], record["check"]
    assert record["attempted"] == 134 and record["failed"] == 0
    assert record["slabs"] == 2
    assert record["phases"]["compiles_in_window"] == 0
    ctx = stream.Context(cell=cell.name, config=cell.config,
                         traffic=cell.traffic, limits={}, seed=2**31 + 5,
                         seconds=0.0, trace=False, root=None, t_start=0.0)
    assert stream.sample(ctx).max() >= 128  # a slice of the short slab


def test_padding_readers_on_the_drain(ragged_run):
    _, record = ragged_run
    # the warm-up's and the first slab's 128 real lanes each, and the
    # short slab's 6 real and 122 zero lanes
    assert pad_lane_share.read(record) == pytest.approx(100 * 122 / 384)
    share = short_solve_share.read(record)
    assert 0 < share < 100


def _span(name, t0, t1, ident, parent=None, **attrs):
    return {"kind": "span", "name": name, "t0": t0, "t1": t1, "id": ident,
            "parent_id": parent, "thread_id": 1, "attrs": attrs}


# a 262-slice scan in slabs of 128: two full solves and one padded one
SPANS = [
    _span("recon/stage", 0.0, 0.1, 1, slices=128, pad_slices=0),
    _span("recon/solve", 0.2, 3.2, 2, slices=128),
    _span("recon/dispatch", 0.2, 0.3, 3, parent=2, pad_slices=0),
    _span("recon/solve", 3.5, 6.5, 4, slices=128),
    _span("recon/dispatch", 3.5, 3.6, 5, parent=4, pad_slices=0),
    _span("recon/solve", 7.0, 10.0, 6, slices=6),
    _span("recon/dispatch", 7.0, 7.1, 7, parent=6, pad_slices=122),
    {"kind": "instant", "name": "recon/exchange", "t0": 10.0, "t1": 10.0,
     "id": 8, "parent_id": None, "thread_id": 1, "attrs": {}},
]


def test_padding_readers_on_made_up_spans():
    record = {"spans": SPANS, "slabs": 3}
    assert pad_lane_share.read(record) == pytest.approx(100 * 122 / 384)
    assert short_solve_share.read(record) == pytest.approx(100 / 3)


@pytest.mark.parametrize("reader", [pad_lane_share, short_solve_share])
@pytest.mark.parametrize("spans", [
    None, [],
    # a program that pads nothing: its dispatch carries no pad_slices
    [dict(s, attrs={k: v for k, v in s["attrs"].items()
                    if k != "pad_slices"}) for s in SPANS],
], ids=["no_trace", "no_spans", "no_pad_attr"])
def test_nothing_to_read_is_none(reader, spans):
    assert reader.read({"spans": spans, "slabs": 3}) is None


def test_full_slabs_read_no_padding():
    full = [s for s in SPANS if s["id"] < 6]
    record = {"spans": full, "slabs": 2}
    assert pad_lane_share.read(record) == 0.0
    assert short_solve_share.read(record) == 0.0
    assert np.isfinite(pad_lane_share.read(record))
