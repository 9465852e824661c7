"""``shale-b8.recon30`` compares only the numbers its limits file names,
and ``res_gap`` is not among them: the control's smallest reading is
under three times the program's largest.  The numbers it keeps still
fail every planted fault and the control on a mixed-rung cell: the CPU
limits of ``conftest``, cut to the keys of that file."""
import pytest
from conftest import ROOT, TINY30_LIMITS, TINY_LIMITS, make_root, run_cell

from chipbench import control, faults, harness

KEPT = harness.load_json(
    ROOT / "chipbench" / "limits" / "shale-b8.recon30.json")["limits"]


def _cut(limits: dict) -> dict:
    return {k: v for k, v in limits.items() if k in KEPT}


def test_shale_compares_no_res_gap():
    assert "res_gap" not in KEPT and set(KEPT) == {"traj_gap", "claim_gap"}


@pytest.mark.parametrize("fault", faults.NAMES)
def test_fault_is_not_correct_without_res_gap(tmp_path, fault):
    traffic = {"iters": 30} if fault == "frozen" else {}
    limits = _cut(TINY30_LIMITS if fault == "frozen" else TINY_LIMITS)
    (tmp_path / "sound").mkdir()
    _, rec = run_cell(make_root(tmp_path / "sound", traffic=traffic,
                                limits=limits))
    assert rec["correct"], rec["check"]
    (tmp_path / "faulty").mkdir()
    root = make_root(tmp_path / "faulty", traffic=traffic, limits=limits)
    with faults.plant(fault):
        _, rec = run_cell(root)
    assert not rec["correct"], rec["check"]
    assert "res_gap" not in rec["check"]


def test_control_is_not_correct_without_res_gap(tmp_path):
    root = make_root(tmp_path, limits=_cut(TINY_LIMITS))
    cell = harness.resolve("tiny.quick3", root)
    ctl = control.readings(cell, root, [4, 5, 6], "control", interpret=True)
    assert not any(r["correct"] for r in ctl), ctl
