"""What every cell shares: finding a cell's files by name, the chip
check, the caches and the result line.

A cell ``<config>.<traffic>`` of ``BENCHMARK.json`` resolves to
``chipbench/configs/<config>.json``, ``chipbench/traffic/<traffic>.json``
and the limits of its correctness check, ``chipbench/limits/<cell>.json``;
the traffic file names its driver, ``chipbench/drivers/<driver>.py``,
and each metric is read by ``chipbench/metrics/<metric>.py``.  So a
later cell, configuration, traffic mix or metric is new files plus
``BENCHMARK.json`` entries, never an edit here.
"""
from __future__ import annotations

import dataclasses
import hashlib
import importlib.util
import json
import os
import pathlib
import sys

ROOT = pathlib.Path(__file__).resolve().parents[1]
PKG = "chipbench"
CACHE = ".cache"  # under chipbench/, ignored by chipbench/.gitignore

# Every key a configuration file has to hold.
CONFIG_FIELDS = {
    "name": str,
    "source": str,
    "dataset": str,
    "binning": int,
    "angles": int,
    "channels": int,
    "slices": int,
    "published": dict,
    "rung": str,
    "fuse": int,
    "rows_per_block": int,
    "nnz_per_stage": int,
    "control": dict,
    "reduced": list,
    "assumed": dict,
}
# Keys it may hold besides; where one is left out, the program's default
# holds (one chip: n_data 1, socket 1, mesh [1, 1]).
CONFIG_OPTIONAL = {
    "tile": int,
    "n_data": int,
    "socket": int,
    "comm_mode": str,
    "wire": str,
    "mesh": list,
}


class SpecError(ValueError):
    """A cell, configuration, traffic or metric file that does not fit."""


class NoChip(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


def load_json(path: pathlib.Path) -> dict:
    try:
        with open(path) as f:
            return json.load(f)
    except FileNotFoundError:
        raise SpecError(f"missing file {path}") from None


def check_fields(obj: dict, fields: dict, what: str,
                 optional: dict | None = None) -> dict:
    """Refuse a key that is not known, a missing one that ``fields``
    asks for, or a value of the wrong type."""
    known = dict(fields, **(optional or {}))
    unknown = sorted(set(obj) - set(known))
    if unknown:
        raise SpecError(f"{what}: unknown field(s) {unknown}")
    missing = sorted(set(fields) - set(obj))
    if missing:
        raise SpecError(f"{what}: missing field(s) {missing}")
    for key in obj:
        typ = known[key]
        if not isinstance(obj[key], typ) or (
            typ is int and isinstance(obj[key], bool)
        ):
            raise SpecError(
                f"{what}: {key}={obj[key]!r} is not a {typ.__name__}"
            )
    return obj


LIMITS_FIELDS = {"limits": dict, "readings": dict}


def load_module(path: pathlib.Path, name: str):
    """Import one file of the benchmark by its path."""
    if not path.is_file():
        raise SpecError(f"missing file {path}")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod  # dataclasses look their module up there
    spec.loader.exec_module(mod)
    return mod


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    limits: dict  # compared number -> its limit
    driver: object  # the traffic's driver module
    end_to_end: list  # BENCHMARK.json entries this cell reports
    per_layer: list
    root: pathlib.Path

    def reader(self, metric: str):
        """The module that reads one per-layer metric."""
        return load_module(
            self.root / PKG / "metrics" / f"{metric}.py",
            f"{PKG}_metric_{metric}",
        )


def resolve(name: str, root: pathlib.Path = ROOT) -> Cell:
    bench = load_json(root / "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SpecError(f"no workload {name!r}; one of {sorted(cells)}")
    w = cells[name]
    config = check_fields(
        load_json(root / PKG / "configs" / f"{w['config']}.json"),
        CONFIG_FIELDS, f"config {w['config']}", CONFIG_OPTIONAL,
    )
    traffic = load_json(root / PKG / "traffic" / f"{w['traffic']}.json")
    if "driver" not in traffic:
        raise SpecError(f"traffic {w['traffic']}: no driver named")
    driver = load_module(
        root / PKG / "drivers" / f"{traffic['driver']}.py",
        f"{PKG}_driver_{traffic['driver']}",
    )
    check_fields(traffic, driver.FIELDS, f"traffic {w['traffic']}")
    limits = check_fields(
        load_json(root / PKG / "limits" / f"{name}.json"),
        LIMITS_FIELDS, f"limits {name}",
    )["limits"]
    return Cell(
        name=name,
        chips=int(w["chips"]),
        config=config,
        traffic=traffic,
        limits=limits,
        driver=driver,
        end_to_end=[m for m in bench["end_to_end"] if _applies(m, name)],
        per_layer=[m for m in bench["per_layer"] if _applies(m, name)],
        root=root,
    )


def require_chips(n: int):
    """The devices of the cell, or ``NoChip``: no CPU fallback."""
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise NoChip(
            f"JAX found no TPU (device 0 is {devices[0].platform})"
        )
    if len(devices) < n:
        raise NoChip(f"the cell needs {n} chips, JAX sees {len(devices)}")
    return devices


def enable_compile_cache(root: pathlib.Path = ROOT) -> str:
    """JAX's persistent cache: ``JAX_COMPILATION_CACHE_DIR`` where set,
    else a fixed directory in the checkout, so that only the first run
    of a cell there compiles."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(root / PKG / CACHE / "jax")
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    return path


class CompileClock:
    """Seconds and count of compilations, from JAX's own events."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax

        self.seconds = 0.0
        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **_):
        if event == self.EVENT:
            self.seconds += duration
            self.count += 1

    def close(self):
        import jax

        jax.monitoring.unregister_event_duration_listener(self._on)


def files_hash(paths) -> str:
    """sha256 over the names and bytes of ``paths`` (sorted)."""
    h = hashlib.sha256()
    for p in sorted(paths):
        h.update(str(p.name).encode())
        h.update(p.read_bytes())
    return h.hexdigest()[:16]


def program_hash(root: pathlib.Path = ROOT) -> str:
    """Hash of every ``.py`` of the program: a changed plan build gets a
    fresh plan."""
    return files_hash(sorted((root / "src").rglob("*.py")))


def cache_dir(root: pathlib.Path, *parts) -> pathlib.Path:
    d = root.joinpath(PKG, CACHE, *parts)
    d.mkdir(parents=True, exist_ok=True)
    return d


def log(*parts):
    print(*parts, file=sys.stderr, flush=True)
