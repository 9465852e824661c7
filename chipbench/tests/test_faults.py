"""The comparison that decides ``correct`` passes a sound run and fails
the program broken underneath, and fails the control: a tiny cell
driven end to end on the CPU (kernel in interpret mode), without the
harness's look for a chip.  The faults are those of
``chipbench.faults``; ``frozen`` needs more iterations than the first
ones that ``res_gap`` reads, so it runs the 30-iteration mix.
"""
import pytest
from conftest import (SINGLE, SINGLE_LIMITS, TINY30_LIMITS, make_root,
                      run_cell)

from chipbench import control, faults, harness


def test_sound_run_is_correct(tiny_root):
    _, rec = run_cell(tiny_root)
    assert rec["correct"], rec["check"]
    assert rec["attempted"] == 256 and rec["failed"] == 0
    assert rec["phases"]["compiles_in_window"] == 0


@pytest.mark.parametrize("fault", faults.NAMES)
def test_fault_is_not_correct(tmp_path, fault):
    if fault == "frozen":
        root = make_root(tmp_path, traffic={"iters": 30},
                         limits=TINY30_LIMITS)
        _, rec = run_cell(root)
        assert rec["correct"], rec["check"]  # sound at these limits
        (tmp_path / "faulty").mkdir()
        root = make_root(tmp_path / "faulty", traffic={"iters": 30},
                         limits=TINY30_LIMITS)
    else:
        root = make_root(tmp_path)
    with faults.plant(fault):
        _, rec = run_cell(root)
    assert not rec["correct"], rec["check"]
    if fault == "frozen":  # the early residuals alone would pass it
        assert rec["check"]["res_gap"]["value"] <= \
            rec["check"]["res_gap"]["limit"]


@pytest.mark.parametrize("config,traffic,limits", [
    ({}, {}, None),
    (SINGLE, {"iters": 30}, SINGLE_LIMITS),
], ids=["mixed-bf16", "single-high"])
def test_control_is_not_correct(tmp_path, config, traffic, limits):
    root = make_root(tmp_path, config, traffic, limits)
    cell = harness.resolve("tiny.quick3", root)
    sound = control.readings(cell, root, [1], interpret=True)
    ctl = control.readings(cell, root, [4, 5, 6], "control",
                           interpret=True)
    assert sound[0]["correct"], sound
    assert not any(r["correct"] for r in ctl), ctl
