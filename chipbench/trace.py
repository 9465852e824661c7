"""Reduce a JAX profiler trace (``.xplane.pb``) to what the metrics read.

The window is the host event ``chipbench/window`` (a
``jax.profiler.TraceAnnotation`` the driver opens at the window's start
and closes at its end).  Device operations are the events of the line
``XLA Ops`` of each plane ``/device:TPU:<i>``, clipped to the window.
They nest: a ``while`` loop's event spans its body's.  From them:

* ``busy_s``: the union of the operations' intervals, averaged over the
  chips;
* ``ops``: self seconds (less the events nested inside) and count of
  each operation, summed over the chips.  An operation is named by its
  HLO instruction (``closed_call.24``), with the custom-call target in
  brackets where there is one: the Pallas SpMM kernel reads
  ``closed_call.<k> [tpu_custom_call]`` on a TPU v5e;
* ``gaps``: the idle intervals of the first chip, in seconds from the
  window's start.

Host spans (``repro.obs`` events, on ``time.perf_counter``) are put on
the trace's clock by the window: the driver reads ``perf_counter`` as it
opens the annotation, so host time ``t`` is trace time
``window_start + (t - t_open)``, to within microseconds.
"""
from __future__ import annotations

import pathlib
import re

WINDOW = "chipbench/window"
DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
_TARGET = re.compile(r'custom_call_target="([^"]+)"')


def op_name(hlo: str) -> str:
    """``%fusion.3 = f32[..] fusion(..), ..`` -> ``fusion.3``; a custom
    call also names its target: ``closed_call.24 [tpu_custom_call]``."""
    head = hlo.split(" = ", 1)[0].lstrip("%")
    target = _TARGET.search(hlo)
    return f"{head} [{target.group(1)}]" if target else head


def find(log_dir) -> pathlib.Path:
    """The newest ``.xplane.pb`` under ``log_dir``."""
    found = sorted(pathlib.Path(log_dir).rglob("*.xplane.pb"),
                   key=lambda p: p.stat().st_mtime)
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return found[-1]


def _union(intervals) -> list:
    out = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return out


def _self_times(events) -> list:
    """``[(name, self_ns)]`` of nested ``(start, end, name)`` intervals:
    each less the time of the events directly inside it."""
    events = sorted(events, key=lambda e: (e[0], -e[1]))
    inner = [0] * len(events)
    stack = []  # indices of the open events
    for i, (a, b, _) in enumerate(events):
        while stack and events[stack[-1]][1] <= a:
            stack.pop()
        if stack:
            inner[stack[-1]] += min(b, events[stack[-1]][1]) - a
        stack.append(i)
    return [(n, b - a - inner[i]) for i, (a, b, n) in enumerate(events)]


def reduce(path) -> dict:
    """The reduced trace: ``window_s``, ``busy_s``, ``chips``, ``ops``
    ``{name: [seconds, count]}`` and ``gaps`` ``[[start_s, end_s]]``."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(str(path))
    window = None
    for plane in data.planes:
        if plane.name.startswith("/host"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name == WINDOW:
                        window = (ev.start_ns, ev.end_ns)
    if window is None:
        raise ValueError(f"{path}: no {WINDOW!r} host event")
    w0, w1 = window
    ops: dict = {}
    busy = []
    gaps = None
    chips = 0
    for plane in sorted(data.planes, key=lambda p: p.name):
        if not DEVICE_PLANE.match(plane.name):
            continue
        chips += 1
        spans = []
        for line in plane.lines:
            if line.name != OPS_LINE:
                continue
            for ev in line.events:
                a = max(ev.start_ns, w0)
                b = min(ev.start_ns + ev.duration_ns, w1)
                if b > a:
                    spans.append((a, b, op_name(ev.name)))
        for name, ns in _self_times(spans):
            tot = ops.setdefault(name, [0.0, 0])
            tot[0] += ns * 1e-9
            tot[1] += 1
        merged = _union((a, b) for a, b, _ in spans)
        busy.append(sum(b - a for a, b in merged) * 1e-9)
        if gaps is None:
            edges = [w0] + [t for ab in merged for t in ab] + [w1]
            gaps = [[(edges[i] - w0) * 1e-9, (edges[i + 1] - w0) * 1e-9]
                    for i in range(0, len(edges), 2)
                    if edges[i + 1] > edges[i]]
    if not chips:
        raise ValueError(f"{path}: no {DEVICE_PLANE.pattern} plane")
    return {
        "window_s": (w1 - w0) * 1e-9,
        "busy_s": sum(busy) / chips,
        "chips": chips,
        "ops": ops,
        "gaps": gaps,
    }


def name_gaps(gaps, spans, t_open: float) -> dict:
    """Idle seconds by what the host was doing: each gap goes to the
    innermost host span open on the main thread at its midpoint
    (``spans`` are ``repro.obs`` events; ``t_open`` the host clock at
    the window's start)."""
    main = [s for s in spans
            if s.get("kind") == "span" and s.get("thread") == "MainThread"]
    out: dict = {}
    for a, b in gaps:
        mid = t_open + 0.5 * (a + b)
        inside = [s for s in main if s["t0"] <= mid <= s["t1"]]
        name = (max(inside, key=lambda s: s["depth"])["name"]
                if inside else "no host span")
        out[name] = out.get(name, 0.0) + (b - a)
    return out


def breakdown(reduced: dict, named_gaps: dict, top: int = 10) -> dict:
    ops = sorted(((n, v[0]) for n, v in reduced["ops"].items()),
                 key=lambda kv: -kv[1])
    gaps = sorted(named_gaps.items(), key=lambda kv: -kv[1])
    return {
        "device_ops": [[n, s] for n, s in ops[:top]],
        "idle_gaps": [[n, s] for n, s in gaps[:top]],
    }
