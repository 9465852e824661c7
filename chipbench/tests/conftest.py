"""CPU tests of the benchmark: JAX held to the CPU, the kernel in
Pallas interpret mode, a tiny geometry in a checkout of its own."""
import json
import os
import pathlib
import shutil
import sys
import time

os.environ.setdefault("JAX_PLATFORMS", "cpu")
ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

import pytest  # noqa: E402

TINY_CONFIG = {
    "name": "tiny",
    "source": "a 32-pixel test geometry",
    "dataset": "test",
    "binning": 1,
    "angles": 48,
    "channels": 32,
    "slices": 256,
    "published": {"angles": 48, "slices": 256, "channels": 32},
    "rung": "mixed",
    "fuse": 128,
    "tile": 4,
    "rows_per_block": 16,
    "nnz_per_stage": 16,
    "control": {"kind": "program", "rung": "mixed_bf16"},
    "reduced": [],
    "assumed": {},
}
# CPU readings at this size (program on seeds 1-3, control on 4-6, the
# frozen fault on 7-9), the largest sound reading / the smallest control
# or fault reading.  Mixed rung, 3 iterations, bf16 control: res_gap
# 1.24e-4 / 5.84e-4, traj_gap 1.97e-5 / 8.41e-5, claim_gap 9.87e-5 /
# 6.08e-4.
TINY_LIMITS = {"res_gap": 2.5e-4, "traj_gap": 4e-5, "claim_gap": 2.5e-4}
# Mixed, 30 iterations: res_gap 4.77e-2 / 7.89e-2 (bf16), traj_gap
# 1.67e-2 / 6.33e-2 (frozen), claim_gap 8.92e-3 / 1.52e-1 (bf16).
TINY30_LIMITS = {"res_gap": 7e-2, "traj_gap": 3.3e-2, "claim_gap": 3.5e-2}
# Single rung, 30 iterations, the high-precision control: res_gap
# 2.06e-6 / 1.76e-5, traj_gap 5.46e-3 / 6.30e-2 (frozen), claim_gap
# 8.97e-7 / 1.58e-5.
SINGLE = {"rung": "single",
          "control": {"kind": "reference", "precision": "high"}}
SINGLE_LIMITS = {"res_gap": 6e-6, "traj_gap": 1.8e-2, "claim_gap": 4e-6}
TINY_TRAFFIC = {"name": "quick3", "driver": "stream", "iters": 3,
                "slab": 128}


def make_root(tmp: pathlib.Path, config=None, traffic=None,
              limits=None) -> pathlib.Path:
    """A checkout with one cell, ``tiny.quick3``: the repo's drivers,
    metrics and program, a tiny configuration, a traffic mix and its
    limits."""
    config = dict(TINY_CONFIG, **(config or {}))
    traffic = dict(TINY_TRAFFIC, **(traffic or {}))
    limits = TINY_LIMITS if limits is None else limits
    cell = f"{config['name']}.{traffic['name']}"
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    bench["workloads"] = [{"name": cell, "config": config["name"],
                           "traffic": traffic["name"], "chips": 1,
                           "why": "test"}]
    for m in bench["per_layer"]:
        m["workloads"] = [cell]
    (tmp / "chipbench" / "configs").mkdir(parents=True)
    (tmp / "chipbench" / "traffic").mkdir()
    (tmp / "chipbench" / "limits").mkdir()
    (tmp / "chipbench" / "limits" / f"{cell}.json").write_text(
        json.dumps({"limits": limits, "readings": {}}))
    (tmp / "BENCHMARK.json").write_text(json.dumps(bench))
    (tmp / "chipbench" / "configs" / f"{config['name']}.json").write_text(
        json.dumps(config))
    (tmp / "chipbench" / "traffic" / f"{traffic['name']}.json").write_text(
        json.dumps(traffic))
    for sub in ("drivers", "metrics", "reference"):
        shutil.copytree(ROOT / "chipbench" / sub, tmp / "chipbench" / sub)
    (tmp / "src").symlink_to(ROOT / "src")
    return tmp


def run_cell(root, seed=3, seconds=0.0, trace=False, **ctx):
    """The driver's run of the root's one cell, as ``run.py`` makes it,
    without the look for a chip."""
    from chipbench import harness

    bench = json.loads((root / "BENCHMARK.json").read_text())
    cell = harness.resolve(bench["workloads"][0]["name"], root)
    c = cell.driver.Context(
        cell=cell.name, config=cell.config, traffic=cell.traffic,
        limits=cell.limits, seed=seed, seconds=seconds, trace=trace, root=root,
        t_start=time.perf_counter(), interpret=True, **ctx,
    )
    return cell, cell.driver.run(c)


@pytest.fixture
def tiny_root(tmp_path):
    return make_root(tmp_path)
