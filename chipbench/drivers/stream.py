"""Driver ``stream``: whole scans of one geometry, back to back, through
``repro.stream.reconstruct_streaming`` on one ``Reconstructor``.

Set-up: the program's plan (cached in the checkout per configuration
and program version), the benchmark's own float64 system matrix (cached
per configuration and reference version), the seeded phantom and its
sinogram in a ``SlabStore``, the ``Reconstructor``, and one slab solved
through the same call and iteration count, which compiles (or loads from
the persistent cache) the cell's one program.

Window: scans are drained one after another, each into a volume store of
its own, until ``seconds`` have passed; the scan in progress then
finishes.  The drain runs with the program's defaults: slab ``i+1`` is
loaded and staged on the device while slab ``i`` solves.
``slices_per_s`` is every slice drained over the whole window, each slab
counted by the slices it holds (a scan's last slab may be short).  After
the window the device's peak memory is read, the program is freed, and
the sampled slices of every drained volume are compared with the
float64 CGNR (``chipbench.check``).
"""
from __future__ import annotations

import dataclasses
import gc
import hashlib
import json
import pickle
import shutil
import time

import numpy as np

from chipbench import check, harness, trace, work
from chipbench.peaks import peaks
from chipbench.reference import cgnr as ref_cgnr
from chipbench.reference.phantom import phantom
from chipbench.reference.siddon import system_matrix

FIELDS = {
    "name": str,
    "driver": str,
    "iters": int,  # CG iterations per slab
    "slab": int,  # slices per slab (a multiple of the config's fuse)
}
SAMPLE = 8  # slices of every scan compared with the reference
WINDOW_SPAN = "chipbench/scan"


@dataclasses.dataclass
class Context:
    """What one run of a cell is given."""

    cell: str
    config: dict
    traffic: dict
    limits: dict
    seed: int
    seconds: float
    trace: bool
    root: object  # the checkout, a pathlib.Path
    t_start: float  # perf_counter at process start
    interpret: bool | None = None  # Pallas interpret mode: CPU tests only
    rung: str | None = None  # a rung other than the config's: controls only


@dataclasses.dataclass
class Setup:
    plan: object
    op64: object  # reference.cgnr.Operator over the benchmark's own A
    nnz: int
    y: np.ndarray  # [n_rays, slices] float32 sinogram
    store: object  # the sinogram's SlabStore
    work_dir: object  # pathlib.Path of this run's stores
    phases: dict  # set-up seconds by phase


def _key(*parts) -> str:
    return hashlib.sha256(
        json.dumps(parts, sort_keys=True, default=str).encode()
    ).hexdigest()[:16]


def _atomic_pickle(obj, path):
    tmp = path.with_suffix(".tmp")
    with open(tmp, "wb") as f:
        pickle.dump(obj, f, protocol=pickle.HIGHEST_PROTOCOL)
    tmp.replace(path)


def load_plan(ctx):
    """The program's plan, from its own ``build_plan``: built once per
    checkout, configuration and version of the program."""
    from repro.core.geometry import XCTGeometry
    from repro.core.partition import PartitionConfig, build_plan

    cfg = ctx.config
    part = PartitionConfig(
        rows_per_block=cfg["rows_per_block"],
        nnz_per_stage=cfg["nnz_per_stage"],
        **{k: cfg[k] for k in ("n_data", "tile", "socket") if k in cfg},
    )
    key = _key(cfg["channels"], cfg["angles"], repr(part),
               harness.program_hash(ctx.root))
    path = harness.cache_dir(ctx.root, "plan") / f"{cfg['name']}-{key}.pkl"
    if path.exists():
        with open(path, "rb") as f:
            return pickle.load(f)
    plan = build_plan(
        XCTGeometry(n=cfg["channels"], n_angles=cfg["angles"]), part
    )
    _atomic_pickle(plan, path)
    return plan


def load_matrix(ctx):
    """The benchmark's own float64 Siddon matrix and its transpose, both
    CSR, built once per checkout, geometry and version of the
    reference."""
    cfg = ctx.config
    ref = sorted((ctx.root / harness.PKG / "reference").glob("*.py"))
    key = _key(cfg["channels"], cfg["angles"], harness.files_hash(ref), "AT")
    path = harness.cache_dir(ctx.root, "matrix") / f"{cfg['name']}-{key}.pkl"
    if path.exists():
        with open(path, "rb") as f:
            return pickle.load(f)
    a = system_matrix(cfg["channels"], cfg["angles"])
    pair = (a, a.T.tocsr())
    _atomic_pickle(pair, path)
    return pair


def make_data(ctx, op64):
    """The seed's phantom, its sinogram, and the sinogram in a store in
    this run's work directory (emptied first)."""
    from repro.stream import SlabStore

    cfg = ctx.config
    x_true = phantom(cfg["channels"], cfg["slices"], ctx.seed)
    y = op64.matvec(x_true).astype(np.float32)
    work_dir = harness.cache_dir(ctx.root, "work", ctx.cell)
    shutil.rmtree(work_dir)
    store = SlabStore.create(
        str(work_dir / "sino"), y.shape[0], cfg["slices"],
        ctx.traffic["slab"],
    )
    for j0, j1 in store.slabs():
        store.write(j0, y[:, j0:j1])
    return y, store, work_dir


def prepare(ctx) -> Setup:
    """Plan, reference matrix and the seed's sinogram in a store."""
    phases = {}
    t = time.perf_counter()
    plan = load_plan(ctx)
    phases["plan_s"] = time.perf_counter() - t
    t = time.perf_counter()
    a, at = load_matrix(ctx)
    op64 = ref_cgnr.Operator(a, at=at)
    y, store, work_dir = make_data(ctx, op64)
    phases["data_s"] = time.perf_counter() - t
    return Setup(plan, op64, int(a.nnz), y, store, work_dir, phases)


def reconstructor(ctx, plan):
    from repro.core.recon import ReconConfig, Reconstructor

    cfg = ctx.config
    if cfg.get("mesh", [1, 1]) != [1, 1] or cfg.get("n_data", 1) != 1:
        raise harness.SpecError(
            f"config {cfg['name']}: the stream driver runs one chip "
            f"(mesh [1, 1], n_data 1)"
        )
    return Reconstructor(plan, cfg=ReconConfig(
        precision=ctx.rung or cfg["rung"], fuse=cfg["fuse"],
        interpret=ctx.interpret,
        **{k: cfg[k] for k in ("comm_mode", "wire") if k in cfg},
    ))


def drain(ctx, rec, setup) -> tuple[list, float, float]:
    """The window: whole scans until ``ctx.seconds`` have passed.
    Returns ``([(volume dir, StreamResult)], t0, t1)``."""
    from repro.obs.trace import span
    from repro.stream import reconstruct_streaming

    tr = ctx.traffic
    scans = []
    t0 = time.perf_counter()
    while True:
        out = setup.work_dir / f"vol{len(scans):04d}"
        with span(WINDOW_SPAN, scan=len(scans)):
            res = reconstruct_streaming(
                rec, setup.store, str(out), iters=tr["iters"],
                y_slab=tr["slab"],
            )
        scans.append((out, res))
        t1 = time.perf_counter()
        if t1 - t0 >= ctx.seconds:
            return scans, t0, t1


def width(j0: int, slab: int, n_slices: int) -> int:
    """Slices of the slab that starts at ``j0``: the last may be short."""
    return min(j0 + slab, n_slices) - j0


def sample(ctx) -> np.ndarray:
    """The compared slices: drawn from the seed, the same for every scan.
    Where the last slab is short and no drawn slice lies in it, the
    largest drawn slice gives way to one of that slab, drawn from the
    same generator."""
    rng = np.random.default_rng([ctx.seed, 7])
    n, slab = ctx.config["slices"], ctx.traffic["slab"]
    idx = np.sort(rng.choice(n, min(SAMPLE, n), replace=False))
    last = n - n % slab
    if last < n and idx[-1] < last:
        idx[-1] = rng.integers(last, n)
    return idx


def answers(scans, idx):
    """Each scan's sampled volume columns and reported residuals; a
    slice that never came is ``None``."""
    from repro.stream import SlabStore

    out = []
    for path, res in scans:
        store = SlabStore.open(str(path))
        cols = {}
        try:
            for j0, j1 in store.slabs():
                mine = idx[(idx >= j0) & (idx < j1)]
                if len(mine):
                    block = store.read(j0, j1)
                    cols.update((j, block[:, j - j0]) for j in mine)
            x = np.stack([cols[j] for j in idx], 1)
        except FileNotFoundError:
            x = None
        out.append((x, res.resnorms[:, idx]))
    return out


def compare(ctx, setup, got, idx) -> tuple[bool, dict, dict]:
    """Gaps of every scan's sampled answers against the float64 CGNR."""
    y = setup.y[:, idx].astype(np.float64)
    x64, res64 = ref_cgnr.cgnr(setup.op64, y, ctx.traffic["iters"])
    rows, missing = [], 0
    for x, res in got:
        if x is None:
            missing += 1
            continue
        rows.append(check.numbers(setup.op64, y, x, res, x64, res64))
    found = check.widest(rows) if rows else {
        k: float("inf") for k in check.NUMBERS
    }
    ok, shown = check.verdict(found, ctx.limits)
    return ok and not missing, shown, dict(found, missing_scans=missing)


def work_per_slab(ctx, setup, rung: str, starts) -> dict:
    """The algorithm's work of the window's solved slabs, starting at
    ``starts``, over their number: its applies and its solve.  A slab of
    ``s`` slices is ``ceil(s / fuse)`` passes, each of as many fused
    slices as are left, up to ``fuse``; padding lanes do not count."""
    from repro.core.precision import get_policy

    cfg, tr = ctx.config, ctx.traffic
    pol = get_policy(rung)
    n_vox = cfg["channels"] ** 2
    n_rays = cfg["channels"] * cfg["angles"]
    applies = solve = work.Work(0.0, 0.0)
    for j0 in starts:
        s = width(j0, tr["slab"], cfg["slices"])
        for k in range(0, s, cfg["fuse"]):
            args = dict(slices=min(cfg["fuse"], s - k),
                        value_bytes=pol.vals_bytes,
                        vector_bytes=pol.storage_bytes)
            applies += (tr["iters"] + 1) * (
                work.apply(setup.nnz, n_vox, n_rays, **args)
                + work.apply(setup.nnz, n_rays, n_vox, **args))
            solve += work.cgnr(setup.nnz, n_vox, n_rays, iters=tr["iters"],
                               **args)
    per = 1.0 / max(len(starts), 1)
    return {"applies": applies * per, "solve": solve * per}


def counts(ctx, scans) -> tuple[int, int, int]:
    """``(attempted, slices, failed)`` of the window's scans: each slab
    counts the slices it holds; a retry, which names no slab, a whole
    slab."""
    slab, n = ctx.traffic["slab"], ctx.config["slices"]
    attempted = len(scans) * n
    slices = sum(width(j0, slab, n) for _, r in scans for j0 in r.solved)
    failed = min(attempted, sum(
        sum(width(j0, slab, n) for j0 in r.escalated + r.failed_slabs)
        + slab * r.retries
        for _, r in scans
    ))
    return attempted, slices, failed


def _peak_bytes(device):
    stats = device.memory_stats() or {}
    return stats.get("peak_bytes_in_use")


def run(ctx) -> dict:
    """One run of the cell; returns the record the harness reports."""
    import jax

    from repro import obs

    clock = harness.CompileClock()
    t_enter = time.perf_counter()
    setup = prepare(ctx)
    setup.phases["start_s"] = t_enter - ctx.t_start
    rung = ctx.rung or ctx.config["rung"]
    t = time.perf_counter()
    rec = reconstructor(ctx, setup.plan)
    setup.phases["reconstructor_s"] = time.perf_counter() - t
    if ctx.trace:
        tracer = obs.enable()  # before the warm-up: its first-use work too
    t = time.perf_counter()
    staged = rec.stage_sino(setup.store.read(0, ctx.traffic["slab"]))
    rec.reconstruct(staged, iters=ctx.traffic["iters"])
    setup.phases["warmup_s"] = time.perf_counter() - t
    setup.phases["compile_s"] = clock.seconds
    compiles_before = clock.count
    trace_dir = None
    if ctx.trace:
        tracer.reset()
        trace_dir = harness.cache_dir(ctx.root, "trace", ctx.cell)
        shutil.rmtree(trace_dir)
        jax.profiler.start_trace(
            str(trace_dir),
            profiler_options=_profile_options(jax),
        )
    t_window = time.perf_counter()
    with jax.profiler.TraceAnnotation(trace.WINDOW):
        t_open = time.perf_counter()
        scans, t0, t1 = drain(ctx, rec, setup)
    reduced = spans = None
    if ctx.trace:
        jax.profiler.stop_trace()
        spans = list(tracer.events)
        obs.disable()
    device = rec.mesh.devices.flat[0]
    peak = _peak_bytes(device)
    compiles_in_window = clock.count - compiles_before
    clock.close()
    del rec, staged
    gc.collect()
    if ctx.trace:
        reduced = trace.reduce(trace.find(trace_dir))
    attempted, slices, failed = counts(ctx, scans)
    starts = [j0 for _, r in scans for j0 in r.solved]
    t = time.perf_counter()
    idx = sample(ctx)
    ok, shown, found = compare(ctx, setup, answers(scans, idx), idx)
    reference_s = time.perf_counter() - t
    shutil.rmtree(setup.work_dir, ignore_errors=True)
    setup.op64.close()
    return {
        "correct": ok,
        "attempted": attempted,
        "failed": failed,
        "end_to_end": {
            "slices_per_s": slices / (t1 - t0),
            "setup_s": t_window - ctx.t_start,
        },
        "check": shown,
        "readings": found,
        "window": {"t0": t0, "t1": t1, "t_open": t_open,
                   "scans": len(scans)},
        "slabs": len(starts),
        "stream": [{"slab_s": r.slab_s, "solve_s": r.solve_s,
                    "load_s": r.load_s, "upload_s": r.upload_s}
                   for _, r in scans],
        "work": work_per_slab(ctx, setup, rung, starts),
        "peaks": peaks(device.device_kind) if device.platform == "tpu"
        else None,
        "spans": spans,
        "trace": reduced,
        "memory_peak_bytes": peak,
        "phases": dict(setup.phases, reference_s=reference_s,
                       compiles_in_window=compiles_in_window),
    }


def _profile_options(jax):
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0  # Python calls would swamp the trace
    return opts
