"""Cells, configurations, traffic mixes and metrics are found by name;
files that do not fit are refused; no TPU, no result."""
import json
import os
import shutil
import subprocess
import sys

import pytest
from conftest import ROOT, TINY_LIMITS, make_root, run_cell

from chipbench import harness

sys.path.insert(0, str(ROOT / "chipbench"))
import run  # noqa: E402


def _bench(root):
    return json.loads((root / "BENCHMARK.json").read_text())


def test_every_cell_of_the_benchmark_resolves():
    bench = _bench(ROOT)
    for w in bench["workloads"]:
        cell = harness.resolve(w["name"])
        assert cell.config["name"] == w["config"]
        assert cell.traffic["name"] == w["traffic"]
        assert [m["name"] for m in cell.end_to_end] == [
            "slices_per_s", "setup_s"]
        for m in cell.per_layer:
            assert callable(cell.reader(m["name"]).read)


def test_new_cell_config_traffic_and_metric_are_found_by_name(tmp_path):
    root = make_root(tmp_path)
    (root / "chipbench" / "metrics" / "slabs_done.py").write_text(
        "def read(record):\n    return float(record['slabs'])\n")
    bench = _bench(root)
    bench["per_layer"].append({
        "name": "slabs_done", "unit": "slabs", "better": "higher",
        "source": "program_counter", "layer": "stream",
        "moves": "slices_per_s", "workloads": ["tiny.quick3"]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    cell, record = run_cell(root)
    assert cell.config["channels"] == 32 and cell.traffic["iters"] == 3
    got = run.metrics_of(cell, record, traced=True)
    assert got["slabs_done"] == {"value": 2.0, "unit": "slabs"}
    # the readers that need a device trace find nothing on the CPU
    assert "spmm_roofline" not in got and "device_idle_share" not in got
    e2e = run.metrics_of(cell, record, traced=False)
    assert set(e2e) == {"slices_per_s", "setup_s"}
    assert e2e["slices_per_s"]["value"] > 0


@pytest.mark.parametrize("where,bad", [
    ("config", {"experts": 8}),
    ("traffic", {"arrival_rate": 3.0}),
])
def test_unknown_field_is_an_error(tmp_path, where, bad):
    root = make_root(tmp_path, **{where: bad})
    with pytest.raises(harness.SpecError, match="unknown field"):
        harness.resolve("tiny.quick3", root)


def test_unknown_cell_and_missing_driver_are_errors(tmp_path):
    root = make_root(tmp_path, traffic={"driver": "serve"})
    with pytest.raises(harness.SpecError, match="no workload"):
        harness.resolve("tiny.nothing", root)
    with pytest.raises(harness.SpecError, match="missing file"):
        harness.resolve("tiny.quick3", root)


def _run_py(cwd, root=ROOT):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, str(root / "chipbench" / "run.py"),
         "--workload", "shale-b8.recon30", "--seed", str(2**31 + 5),
         "--seconds", "10", "--trace", "0"],
        capture_output=True, text=True, env=env, timeout=300, cwd=cwd,
    )


def test_run_refuses_without_a_tpu(tmp_path):
    r = _run_py(tmp_path)
    assert r.returncode != 0
    assert "no TPU" in r.stderr
    assert r.stdout.strip() == ""


def test_run_refuses_with_only_the_benchmark_files(tmp_path):
    """A directory with BENCHMARK.json and chipbench/ but no program."""
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "chipbench", tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns(".cache", "__pycache__"))
    r = _run_py(tmp_path, tmp_path)
    assert r.returncode != 0
    assert r.stdout.strip() == ""


def test_result_line_keys_and_check_last(tiny_root):
    class Dev:
        platform, device_kind = "tpu", "TPU v5 lite"

    cell, record = run_cell(tiny_root)
    out = run.result(cell, record, [Dev()], traced=False)
    assert list(out) == ["correct", "attempted", "failed", "metrics",
                         "device", "check"]
    assert set(out["check"]) == set(TINY_LIMITS)
    for v in out["check"].values():
        assert set(v) == {"value", "limit"}
    json.dumps(out)
