"""Chip benchmark of the XCT reconstructor: one cell, one run, one line.

    python chipbench/run.py --workload shale-b8.recon30 --seed 7 \\
        --seconds 10 --trace 0

Runs the cell named in ``BENCHMARK.json`` on the machine it is started
on, which must hold a TPU with as many chips as the cell asks for: with
none it exits non-zero and prints no result.  Set-up (plan, data,
compile, warm-up) is timed from the start of the process to the start of
the window; the window then drives the cell's traffic for ``--seconds``.
``--trace 1`` records a device trace of the window and reports the
cell's per-layer metrics instead of its end-to-end ones.

Set-up phases and the compared numbers go to standard error; the last
line of standard output is the result as one JSON object, its last key
``check`` holding each compared number beside its limit.
"""
import time

T_START = time.perf_counter()  # noqa: E402  (before the heavy imports)

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]
# the TPU runtime would log under /tmp, outside the checkout
os.environ.setdefault("TPU_LOG_DIR", "disabled")

from chipbench import harness, trace  # noqa: E402


def metrics_of(cell, record: dict, traced: bool) -> dict:
    """The cell's end-to-end metrics, or with a trace its per-layer ones
    (a reader that finds nothing returns ``None``: left out)."""
    out = {}
    if not traced:
        for m in cell.end_to_end:
            out[m["name"]] = {"value": record["end_to_end"][m["name"]],
                              "unit": m["unit"]}
        return out
    for m in cell.per_layer:
        value = cell.reader(m["name"]).read(record)
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def result(cell, record: dict, devices, traced: bool) -> dict:
    dev = devices[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices),
              "memory_peak_bytes": record["memory_peak_bytes"]}
    out = {
        "correct": bool(record["correct"]),
        "attempted": record["attempted"],
        "failed": record["failed"],
        "metrics": metrics_of(cell, record, traced),
        "device": device,
    }
    if traced:
        red = record["trace"]
        device["busy_s"] = red["busy_s"]
        device["window_s"] = red["window_s"]
        gaps = trace.name_gaps(red["gaps"], record["spans"],
                               record["window"]["t_open"])
        out["breakdown"] = trace.breakdown(red, gaps)
    out["check"] = record["check"]
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        cell = harness.resolve(args.workload)
        devices = harness.require_chips(cell.chips)
    except (harness.SpecError, harness.NoChip) as e:
        harness.log(f"chipbench: {e}; nothing was run")
        return 2
    harness.log(f"chipbench: {cell.name} seed={args.seed} "
                f"device={devices[0].device_kind} x{len(devices)} "
                f"compile_cache={harness.enable_compile_cache()}")
    ctx = cell.driver.Context(
        cell=cell.name, config=cell.config, traffic=cell.traffic,
        limits=cell.limits, seed=args.seed, seconds=args.seconds,
        trace=bool(args.trace), root=harness.ROOT, t_start=T_START,
    )
    record = cell.driver.run(ctx)
    out = result(cell, record, devices, bool(args.trace))
    harness.log("setup " + " ".join(
        f"{k}={v}" for k, v in record["phases"].items()))
    for k, scan in enumerate(record["stream"]):
        harness.log(f"scan {k} " + " ".join(
            f"{name}=" + ",".join(f"{v:.4f}" for v in vals)
            for name, vals in scan.items()))
    harness.log("readings " + " ".join(
        f"{k}={v}" for k, v in record["readings"].items()))
    for name, v in out["check"].items():
        harness.log(f"check {name}={v['value']} limit={v['limit']}")
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
