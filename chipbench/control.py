"""Readings that set a cell's limits: the program on many seeds, the
configuration's control, planted faults and a second witness on a few,
in one process.

    python chipbench/control.py --workload shale-b8.recon30 \\
        --seeds 1-12 --control-seeds 101-103 \\
        --faults frozen --fault-seeds 201-203 --witness-seeds 1-3

For each seed one scan is drained through the cell's timed path (set-up
shared across seeds) and its sampled slices compared with the float64
CGNR, as a run of ``run.py`` compares them.  The control stands in for
the program in the nearest precision below the configuration's:

* ``{"kind": "program", "rung": R}``: the program's own path at rung
  ``R`` (bfloat16 storage for the float16 ``mixed`` rung);
* ``{"kind": "reference", "precision": "high"}``: the reference CGNR
  with every product taken as three bfloat16 passes, for float32
  products at ``Precision.HIGHEST`` (the ``single`` rung).

A fault (``chipbench.faults``) is planted in the program's path.  The
witness is the reference CGNR in float32 on the host, in the program's
place: where the program departs from the float64 CGNR by as much as
it does, that departure is float32 rounding.  The benchmark's own runs
never run this.  Each reading is printed as it comes and all of them go
to ``chiprun_out/control_<cell>.json``.
"""
import time

T_START = time.perf_counter()  # noqa: E402

import argparse  # noqa: E402
import contextlib  # noqa: E402
import dataclasses  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]
# the TPU runtime would log under /tmp, outside the checkout
os.environ.setdefault("TPU_LOG_DIR", "disabled")

import numpy as np  # noqa: E402

from chipbench import check, faults, harness  # noqa: E402
from chipbench.reference import cgnr as ref_cgnr  # noqa: E402


def seeds(text: str) -> list:
    out = []
    for part in text.split(","):
        lo, _, hi = part.partition("-")
        out += range(int(lo), int(hi or lo) + 1)
    return out


def readings(cell, root, seed_list, mode: str = "program",
             interpret=None, log=harness.log) -> list:
    """``[{seed, correct, numbers...}]`` of one scan per seed through
    the cell's path (``mode`` ``"program"``), its control
    (``"control"``), the float32 reference (``"witness"``), or the
    program with a fault of ``faults.NAMES`` planted."""
    if mode not in ("program", "control", "witness") + faults.NAMES:
        raise ValueError(f"unknown mode {mode!r}")
    drv = cell.driver
    spec = cell.config["control"]
    if mode == "control" and spec["kind"] == "program":
        rung, ref = spec["rung"], None
    elif mode == "control":
        if spec != {"kind": "reference", "precision": "high"}:
            raise harness.SpecError(f"unknown control {spec}")
        rung, ref = None, "high"
    else:
        rung, ref = None, "f32" if mode == "witness" else None
    plant = (faults.plant(mode) if mode in faults.NAMES
             else contextlib.nullcontext())

    def context(seed):
        return drv.Context(
            cell=cell.name, config=cell.config, traffic=cell.traffic,
            limits=cell.limits, seed=seed, seconds=0.0, trace=False,
            root=root, t_start=time.perf_counter(), interpret=interpret,
            rung=rung,
        )

    ctx = context(seed_list[0])
    setup = drv.prepare(ctx)
    if ref == "high":
        op_ref = ref_cgnr.HighOperator(drv.load_matrix(ctx)[0])
    elif ref == "f32":
        a, at = drv.load_matrix(ctx)
        op_ref = ref_cgnr.Operator(a, np.float32, at=at)
    iters = cell.traffic["iters"]
    out = []
    with plant:
        rec = None if ref else drv.reconstructor(ctx, setup.plan)
        for seed in seed_list:
            ctx = context(seed)
            y, store, work_dir = drv.make_data(ctx, setup.op64)
            run = dataclasses.replace(setup, y=y, store=store,
                                      work_dir=work_dir)
            idx = drv.sample(ctx)
            if ref:
                got = [ref_cgnr.cgnr(op_ref, y[:, idx], iters)]
            else:
                scans, _, _ = drv.drain(ctx, rec, run)
                got = drv.answers(scans, idx)
            ok, _, found = drv.compare(ctx, run, got, idx)
            row = dict(seed=seed, mode=mode, correct=ok, **found)
            log(json.dumps(row))
            out.append(row)
    setup.op64.close()
    if ref:
        op_ref.close()
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="1-12")
    ap.add_argument("--control-seeds", default="101-103")
    ap.add_argument("--faults", default="",
                    help=f"comma-separated, of {', '.join(faults.NAMES)}")
    ap.add_argument("--fault-seeds", default="201-203")
    ap.add_argument("--witness-seeds", default="")
    args = ap.parse_args(argv)
    cell = harness.resolve(args.workload)
    harness.require_chips(cell.chips)
    harness.enable_compile_cache()
    runs = {"program": readings(cell, harness.ROOT, seeds(args.seeds)),
            "control": readings(cell, harness.ROOT,
                                seeds(args.control_seeds), "control")}
    for name in filter(None, args.faults.split(",")):
        runs[name] = readings(cell, harness.ROOT, seeds(args.fault_seeds),
                              name)
    if args.witness_seeds:
        runs["witness"] = readings(cell, harness.ROOT,
                                   seeds(args.witness_seeds), "witness")
    summary = {
        k: dict({"program_max": max(r[k] for r in runs["program"]),
                 "limit": cell.limits.get(k)},
                **{f"{mode}_min": min(r[k] for r in rows)
                   for mode, rows in runs.items() if mode != "program"},
                **({"witness_max": max(r[k] for r in runs["witness"])}
                   if "witness" in runs else {}))
        for k in check.NUMBERS
    }
    out = ROOT / "chiprun_out"
    out.mkdir(exist_ok=True)
    (out / f"control_{cell.name}.json").write_text(json.dumps(
        {"runs": runs, "summary": summary,
         "control_spec": cell.config["control"]}, indent=1))
    print(json.dumps(summary))
    return 0


if __name__ == "__main__":
    sys.exit(main())
