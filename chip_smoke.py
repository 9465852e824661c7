#!/usr/bin/env python3
"""Bring-up smoke: the reconstruction path, end to end, on TPU.

    python chip_smoke.py             # one chip
    python chip_smoke.py --chips 4   # only the 2x2 (four-chip) phase

One chip: a parallel-beam scan of n=256 pixels and 384 angles (A has
3.0e7 nonzeros; the largest geometry the host-side global-A plan build
serves in about a minute).  256 slices are simulated from ``--seed`` into
a ``SlabStore`` and drained by ``reconstruct_streaming`` (30 CG
iterations, fail-fast) on the ``mixed`` and ``single`` rungs.  For each
rung it prints plan-build, compile, warm-up and drain seconds, slices/s,
the relative error against the phantom, the agreement with a float64
NumPy/SciPy CGNR on 4 slices, and the device's ``peak_bytes_in_use``.

``--chips 4`` runs the same drain on a 2x2 mesh (4-way in-slice data
parallelism) in ``hier`` and ``hier-sparse`` (native and ``q8`` wire)
and compares each volume with the one-chip volume and the float64
reference.

The script fails -- and prints no result line -- unless JAX's first
device is a TPU, the compiled solve contains the Pallas kernel, every
volume is finite and matches the reference at the tolerance of
``tests/test_recon_system.py``, and no slab was retried, escalated or
quarantined.  There is no CPU fallback and no interpret mode.  Its last
line is ``{"ok": true, "device": {"platform", "kind", "count"}}``.
Work files go to ``.smoke_work/`` in the checkout (removed at the end);
the metrics also land in ``chiprun_out/chip_smoke*.json``.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import pathlib
import shutil
import sys
import time

import numpy as np

ROOT = pathlib.Path(__file__).resolve().parent
WORK = ROOT / ".smoke_work"
OUT = ROOT / "chiprun_out"
# The repo's tolerances.  Operators (tests/test_dist_equivalence.py):
# max |A x - A64 x| / max |A64 x| below 1e-4 in single, 5e-3 with the
# f16 storage/wire of the reduced rungs, 2.5e-2 through the int8 (q8)
# wire.  Solves (tests/test_recon_system.py): residuals agree to 5e-3
# over the first iterations (single), and the error to the phantom
# tracks the reference within 0.03.  Later iterates are not compared
# elementwise: CGNR's Krylov path is sensitive to rounding, and a
# float32 NumPy CGNR already departs from the float64 one by ~3% of
# max |x| after 10 iterations at n=32.
OP_TOL = {4: 1e-4, 2: 5e-3, "q8": 2.5e-2}  # storage bytes or wire
RTOL = 5e-3
EARLY_ITERS = 5
TRACK = 0.03
REF_SLICES = 4
# A 2x2 volume against the one-chip volume, on the reference slices:
# max |x4 - x1| / max |x1|.  Both runs are 30-iteration mixed CGNRs that
# differ only in summation order and wire rounding; one chip's mixed
# volume sits 2.6% of max |x| from the float64 CGNR (TPU v5e), so twice
# that bounds a healthy exchange while a lost or doubled partial, which
# moves the volume by its whole share, fails.
VOL_TOL = 0.05


@dataclasses.dataclass(frozen=True)
class Size:
    """The smoke's scan: n=256 pixels, 384 angles, 256 slices, the
    paper's 30 CG iterations, F=128 fused slices (one vreg of lanes)."""

    n: int = 256
    angles: int = 384
    slices: int = 256
    iters: int = 30
    fuse: int = 128
    seed: int = 0


class SmokeFailure(RuntimeError):
    pass


def check(ok: bool, what: str):
    if not ok:
        raise SmokeFailure(what)


def cgnr64(a, y, iters: int):
    """Float64 CGNR from x0 = 0, the recurrence of ``core.solver.cgnr``:
    returns ``(x, resnorms [iters, Y])``."""
    a = a.astype(np.float64)
    r = y.astype(np.float64)
    x = np.zeros((a.shape[1], y.shape[1]))
    s = a.T @ r
    p = s.copy()
    gamma = (s * s).sum(0)
    res = []
    for _ in range(iters):
        q = a @ p
        alpha = gamma / np.maximum((q * q).sum(0), 1e-300)
        x += alpha * p
        r -= alpha * q
        s = a.T @ r
        gamma_new = (s * s).sum(0)
        p = s + gamma_new / np.maximum(gamma, 1e-300) * p
        gamma = gamma_new
        res.append(np.sqrt((r * r).sum(0)))
    return x, np.asarray(res)


def rel_err(x, x_true):
    return np.linalg.norm(x - x_true, axis=0) / np.linalg.norm(
        x_true, axis=0
    )


def max_rel(got, ref) -> float:
    return float(np.abs(got - ref).max() / np.abs(ref).max())


def drain(rec, scan, size, name: str, plan_s: float,
          track_ref: bool = True) -> dict:
    """Check the operators, compile, warm up and drain the scan's store
    through ``rec``; check the volume.  ``track_ref=False`` leaves the error
    tracking to the caller (the 2x2 runs track the one-chip volume)."""
    from repro.stream import reconstruct_streaming

    store, ref, ref_idx = scan.store, scan.ref, scan.ref_idx
    x_true, a = scan.x_true, scan.a
    fuse = rec.cfg.fuse
    n_op = fuse * rec.n_batch
    y_op = store.read(0, n_op)
    x_op = x_true[:, :n_op]
    op_tol = OP_TOL[
        "q8" if rec.cfg.wire == "q8" else rec.policy.storage_bytes
    ]
    proj_diff = max_rel(rec.project(x_op), a @ x_op.astype(np.float64))
    back_diff = max_rel(
        rec.backproject(y_op), a.T @ y_op.astype(np.float64)
    )
    check(
        max(proj_diff, back_diff) < op_tol,
        f"{name}: A x / A^T y differ from SciPy float64 by "
        f"{proj_diff:.3e} / {back_diff:.3e} (limit {op_tol})",
    )
    t = time.perf_counter()
    _, compiled = rec.lower_cg(fuse * rec.n_batch, size.iters)
    compile_s = time.perf_counter() - t
    text = compiled.as_text()
    check("tpu_custom_call" in text,
          f"{name}: the compiled solve has no Pallas kernel")
    t = time.perf_counter()  # first dispatch: compile (or cache) + solve
    rec.reconstruct(store.read(0, fuse * rec.n_batch), iters=size.iters)
    warmup_s = time.perf_counter() - t

    out = WORK / f"vol_{name}"
    shutil.rmtree(out, ignore_errors=True)
    t = time.perf_counter()
    res = reconstruct_streaming(
        rec, store, str(out), iters=size.iters,
        y_slab=fuse * rec.n_batch, fail_fast=True,
    )
    drain_s = time.perf_counter() - t
    check(res.complete and not res.failed_slabs,
          f"{name}: quarantined slabs {res.failed_slabs}")
    check(not res.escalated and res.retries == 0,
          f"{name}: escalated {res.escalated}, retries {res.retries}")
    vol = res.volume.to_array()
    shutil.rmtree(out, ignore_errors=True)
    check(bool(np.isfinite(vol).all()), f"{name}: non-finite volume")
    err = rel_err(vol, x_true)
    ref_x, ref_res = ref
    early = res.resnorms[:EARLY_ITERS, ref_idx]
    early_diff = float(np.abs(early / ref_res[:EARLY_ITERS] - 1).max())
    check(
        rec.policy.storage_bytes < 4
        or np.allclose(early, ref_res[:EARLY_ITERS], rtol=RTOL, atol=0),
        f"{name}: residuals of the first {EARLY_ITERS} iterations differ "
        f"from the float64 CGNR by up to {early_diff:.3e}",
    )
    ref_err = rel_err(ref_x, x_true[:, ref_idx])
    track = float(err[ref_idx].mean() - ref_err.mean())
    check(
        not track_ref or track < TRACK,
        f"{name}: mean relative error to the phantom {err[ref_idx]} "
        f"exceeds the float64 CGNR's {ref_err} by more than {TRACK}",
    )
    diff = max_rel(vol[:, ref_idx], ref_x)
    stats = rec.mesh.devices.flat[0].memory_stats() or {}
    row = {
        "run": name,
        "plan_s": plan_s,
        "proj_max_rel_diff": proj_diff,
        "back_max_rel_diff": back_diff,
        "compile_s": compile_s,
        "warmup_s": warmup_s,
        "drain_s": drain_s,
        "solve_s": float(sum(res.solve_s)),
        "slices": int(store.n_slices),
        "slices_per_s": store.n_slices / drain_s,
        "rel_err_phantom": float(err.mean()),
        "ref_rel_err_phantom": float(ref_err.mean()),
        "ref_early_res_diff": early_diff,
        "ref_err_excess": track,
        "ref_max_rel_diff": diff,
        "peak_bytes_in_use": stats.get("peak_bytes_in_use"),
        "kernel_calls": text.count("tpu_custom_call"),
    }
    print(" ".join(f"{k}={v}" for k, v in row.items()), flush=True)
    row["resnorms"] = res.resnorms
    row["vol_ref"] = vol[:, ref_idx]
    return row


@dataclasses.dataclass
class Scan:
    """The seeded scan every run drains, with its float64 reference."""

    geo: object
    a: object  # scipy CSR system matrix
    plan: object
    plan_s: float
    store: object  # SlabStore of the simulated sinogram
    x_true: np.ndarray
    ref: tuple  # (x64, resnorms64) of the float64 CGNR on ref_idx
    ref_idx: np.ndarray


def prepare(size) -> Scan:
    from repro.core.geometry import XCTGeometry, build_system_matrix
    from repro.core.partition import PartitionConfig, build_plan
    from repro.data.phantom import phantom_slices
    from repro.stream import SlabStore, simulate_to_store

    geo = XCTGeometry(n=size.n, n_angles=size.angles)
    t = time.perf_counter()
    a = build_system_matrix(geo)
    plan = build_plan(geo, PartitionConfig(), a=a)
    plan_s = time.perf_counter() - t
    store = SlabStore.create(
        str(WORK / "sino"), geo.n_rays, size.slices, size.fuse
    )
    t = time.perf_counter()
    simulate_to_store(a, geo.n, store, seed=size.seed)
    x_true = phantom_slices(geo.n, size.slices, seed=size.seed)
    ref_idx = np.linspace(0, size.slices - 1, REF_SLICES).astype(int)
    ref = cgnr64(a, store.to_array()[:, ref_idx], size.iters)
    print(f"geometry n={geo.n} angles={geo.n_angles} nnz={a.nnz} "
          f"slices={size.slices} plan_s={plan_s} "
          f"simulate_and_reference_s={time.perf_counter() - t}",
          flush=True)
    return Scan(geo, a, plan, plan_s, store, x_true, ref, ref_idx)


def one_chip(scan: Scan, size, **cfg) -> list:
    """The mixed and single rungs on the default one-device mesh."""
    from repro.core.recon import ReconConfig, Reconstructor

    return [
        drain(
            Reconstructor(scan.plan, cfg=ReconConfig(
                precision=rung, fuse=size.fuse, **cfg)),
            scan, size, rung, scan.plan_s,
        )
        for rung in ("mixed", "single")
    ]


def four_chips(scan: Scan, size, devices, **cfg) -> list:
    """The 2x2 mesh (4-way in-slice data parallelism) in hier and
    hier-sparse (native and q8 wire), each against one chip's volume."""
    import jax

    from repro.core.partition import PartitionConfig, build_plan
    from repro.core.recon import ReconConfig, Reconstructor
    from repro.dist import Topology

    base = drain(
        Reconstructor(scan.plan, cfg=ReconConfig(
            precision="mixed", fuse=size.fuse, **cfg)),
        scan, size, "mixed-1chip", scan.plan_s,
    )
    rows = [base]
    t = time.perf_counter()
    plan4 = build_plan(
        scan.geo, PartitionConfig(n_data=4, socket=2), a=scan.a
    )
    plan4_s = time.perf_counter() - t
    mesh = jax.make_mesh((2, 2), ("data", "model"), devices=devices[:4])
    topo = Topology.from_mesh(
        mesh, data_axes=("model", "data"), batch_axes=()
    )
    for comm, wire in (("hier", "native"), ("hier-sparse", "native"),
                       ("hier-sparse", "q8")):
        name = f"{comm}-{wire}-2x2"
        rec = Reconstructor(plan4, topology=topo, cfg=ReconConfig(
            precision="mixed", comm_mode=comm, wire=wire,
            fuse=size.fuse, **cfg))
        staged = rec.stage_sino(scan.store.read(0, size.fuse))
        shards = {
            (sh.device.id, str(sh.index))
            for sh in staged.y.addressable_shards
        }
        check(
            len({d for d, _ in shards}) == 4
            and len({i for _, i in shards}) == 4,
            f"{name}: the sinogram is not split over 4 chips "
            f"({sorted(shards)})",
        )
        row = drain(
            rec, scan, size, name, plan4_s, track_ref=False,
        )
        excess = row["rel_err_phantom"] - base["rel_err_phantom"]
        vol_diff = max_rel(row["vol_ref"], base["vol_ref"])
        print(f"run={name} vs_1chip_err_excess={excess} "
              f"vs_1chip_max_rel_diff={vol_diff}", flush=True)
        check(
            abs(excess) < TRACK,
            f"{name}: relative error to the phantom differs from one "
            f"chip's by {excess:.3e}",
        )
        check(
            vol_diff < VOL_TOL,
            f"{name}: the volume differs from one chip's by {vol_diff:.3e} "
            f"of max |x| on the reference slices (limit {VOL_TOL})",
        )
        row.update(vs_1chip_err_excess=excess,
                   vs_1chip_max_rel_diff=vol_diff)
        rows.append(row)
    return rows


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4))
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of the phantom and the measurements")
    args = ap.parse_args(argv)
    size = Size(seed=args.seed)

    import jax

    devs = jax.devices()
    dev = devs[0]
    if dev.platform != "tpu":
        sys.exit(f"chip_smoke: JAX found no TPU (device 0 is "
                 f"{dev.platform}); nothing was run")
    if len(devs) < args.chips:
        sys.exit(f"chip_smoke: --chips {args.chips} but JAX sees "
                 f"{len(devs)} device(s)")
    sys.path.insert(0, str(ROOT / "src"))
    try:
        from repro.launch import compile_cache
    except ImportError as e:
        sys.exit(f"chip_smoke: the repro package is not next to this "
                 f"script ({e})")

    cache = compile_cache.enable()
    print(f"device={dev.device_kind} count={len(devs)} "
          f"compile_cache={cache}", flush=True)
    shutil.rmtree(WORK, ignore_errors=True)
    WORK.mkdir(parents=True)
    OUT.mkdir(exist_ok=True)
    try:
        scan = prepare(size)
        rows = (
            one_chip(scan, size, interpret=False) if args.chips == 1
            else four_chips(scan, size, devs, interpret=False)
        )
    except SmokeFailure as e:
        sys.exit(f"chip_smoke: FAILED: {e}")
    finally:
        shutil.rmtree(WORK, ignore_errors=True)

    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devs)}
    suffix = "" if args.chips == 1 else f"_{args.chips}"
    with open(OUT / f"chip_smoke{suffix}.json", "w") as f:
        json.dump({
            "device": device, "n": scan.geo.n,
            "angles": scan.geo.n_angles, "nnz": int(scan.a.nnz),
            "iters": size.iters,
            "runs": [{k: v for k, v in r.items()
                      if k not in ("resnorms", "vol_ref")}
                     for r in rows],
        }, f, indent=1)
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
