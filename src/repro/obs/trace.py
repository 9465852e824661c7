"""Zero-dependency span tracer: the timing spine of the whole stack.

Every host-side hot path -- ``Reconstructor.reconstruct``/``stage_sino``,
the streaming driver and its prefetch thread, serve's batch drain -- times
itself through :func:`span` instead of ad-hoc ``time.perf_counter()``
pairs, so one run produces one coherent, nestable, thread-aware timeline
on one monotonic clock.  Design rules:

* **Spans always measure, the tracer optionally records.**  A
  :class:`Span` reads the clock on enter/exit regardless of tracing
  state (its ``duration_s`` is what populates ``StreamResult`` /
  ``JobTelemetry``), but the finished event is appended to the tracer
  only while :func:`enable` is active -- with tracing off the cost is
  two clock reads per span, on paths that run once per *slab*, never
  per row (``bench_spmm``'s kernel path is untouched; the bench gate
  pins that).
* **Thread-aware lanes.**  Events carry the recording thread (the
  prefetch worker's loads land on their own lane) plus an optional
  explicit ``lane=`` (serve uses ``tenant:<name>`` so a multi-tenant
  drain renders one row per tenant in Perfetto).
* **Nesting is tracked, not inferred.**  Each event records its
  ``depth``, an integer ``id`` and its ``parent`` span's name and
  ``parent_id`` (per-thread stack), which is what lets ``obs.drift``
  sum a phase without double-counting a ``recon/solve`` nested inside a
  ``stream/solve``.  A span without its own ``slab`` or ``scan`` attr
  takes its parent's, so every span of one slab carries that slab's id.
* **On the profiler's clock too.**  While recording, each span also
  opens a ``jax.profiler.TraceAnnotation`` of the same name (with its
  ``slab``/``scan`` ids as metadata), so a device trace taken with
  ``jax.profiler`` holds every program span on the host plane, on the
  device's clock, on its own thread.  Disabled spans never touch jax.
* **Deterministic under a fake clock.**  ``Tracer(clock=...)`` injects
  the time source; tests assert exact timestamps with no ``time.*``
  calls (see ``tests/test_obs.py``).
* **Device-true timings on demand.**  ``Span.fence(value)`` blocks on
  ``jax.block_until_ready`` so an async dispatch cannot end a span
  early; it is a no-op when jax is absent.

Span taxonomy (the names ``obs.drift`` and the CI obs-smoke assert on)
is tabulated in ``docs/observability.md``.

Doctest -- nesting, fake clock, exact math:

>>> t = Tracer(enabled=True, clock=iter(range(100)).__next__)
>>> with t.span("stream/slab", slab=0):
...     with t.span("stream/solve") as sp:
...         pass
>>> [(e["name"], e["t0"], e["t1"], e["parent"]) for e in t.events]
[('stream/solve', 1, 2, 'stream/slab'), ('stream/slab', 0, 3, None)]
>>> [(e["id"], e["parent_id"], e["attrs"]) for e in t.events]
[(2, 1, {'slab': 0}), (1, None, {'slab': 0})]
>>> sp.duration_s
1
"""
from __future__ import annotations

import itertools
import threading

__all__ = [
    "Span",
    "Tracer",
    "get_tracer",
    "set_tracer",
    "enable",
    "disable",
    "span",
    "instant",
    "reset",
]


# attrs a child span takes from its parent, and the ones its profiler
# annotation carries
IDS = ("slab", "scan")
_annotation = None  # jax.profiler.TraceAnnotation once first needed


def _default_clock():
    import time

    return time.perf_counter()


def _annotation_cls():
    """``jax.profiler.TraceAnnotation``, imported on the first recorded
    span (``False`` without jax)."""
    global _annotation
    if _annotation is None:
        try:
            from jax.profiler import TraceAnnotation
        except ImportError:  # pragma: no cover - jax is a repo dep
            TraceAnnotation = False
        _annotation = TraceAnnotation
    return _annotation


class Span:
    """One timed region.  Use as a context manager; read ``duration_s``
    after exit.  An exception propagating through the span is recorded
    in its attrs as ``exception=<type name>`` (the serve failure-
    telemetry contract: the failing span names what killed it)."""

    __slots__ = ("name", "attrs", "lane", "t0", "t1", "id", "_parent",
                 "_ann", "_tracer")

    def __init__(self, tracer: "Tracer", name: str, lane, attrs: dict):
        self._tracer = tracer
        self.name = name
        self.lane = lane
        self.attrs = attrs
        self.t0 = None
        self.t1 = None
        self.id = None  # set when a recording tracer opens the span
        self._parent = None
        self._ann = None

    @property
    def duration_s(self):
        """Wall seconds between enter and exit (``None`` while open)."""
        if self.t0 is None or self.t1 is None:
            return None
        return self.t1 - self.t0

    def fence(self, value):
        """Block until ``value``'s device computation lands (device-true
        span ends).  Returns ``value``; no-op without jax."""
        try:
            import jax

            jax.block_until_ready(value)
        except ImportError:  # pragma: no cover - jax is a repo dep
            pass
        return value

    def __enter__(self):
        if self._tracer.enabled:
            self._tracer._open(self)
        self.t0 = self._tracer._clock()
        return self

    def __exit__(self, exc_type, exc, tb):
        if exc_type is not None:
            self.attrs["exception"] = exc_type.__name__
        self.t1 = self._tracer._clock()
        if self.id is not None or self._tracer.enabled:
            self._tracer._close(self)
        return False


class Tracer:
    """Collects finished spans + instants; exported by ``obs.export``.

    Args:
      enabled: record events (spans still *measure* when ``False``).
      clock: monotonic-seconds callable (default ``time.perf_counter``;
        inject a fake for deterministic tests).
    """

    def __init__(self, enabled: bool = False, clock=None):
        self.enabled = bool(enabled)
        self._clock = clock or _default_clock
        self._lock = threading.Lock()
        self._local = threading.local()
        self._ids = itertools.count(1)
        self.events: list[dict] = []

    # ------------------------------------------------------------------ #
    # recording
    # ------------------------------------------------------------------ #
    def span(self, name: str, *, lane: str | None = None, **attrs) -> Span:
        """A nestable timed region; see :class:`Span`."""
        return Span(self, name, lane, attrs)

    def instant(self, name: str, *, lane: str | None = None, **attrs):
        """A zero-duration marker event (Chrome ``ph="i"``): annotations
        like the modeled exchange volumes a solve just implied."""
        if not self.enabled:
            return
        now = self._clock()
        st = self._stack()
        parent = st[-1] if st else None
        self._record(name, now, now, lane, len(st), next(self._ids),
                     parent, attrs, "instant")

    def _record(self, name, t0, t1, lane, depth, ident, parent, attrs,
                kind):
        th = threading.current_thread()
        with self._lock:
            self.events.append(
                {
                    "name": name,
                    "t0": t0,
                    "t1": t1,
                    "lane": lane,
                    "thread": th.name,
                    "thread_id": th.ident,
                    "depth": depth,
                    "id": ident,
                    "parent": None if parent is None else parent.name,
                    "parent_id": None if parent is None else parent.id,
                    "attrs": dict(attrs),
                    "kind": kind,
                }
            )

    def _stack(self) -> list:
        st = getattr(self._local, "stack", None)
        if st is None:
            st = self._local.stack = []
        return st

    def _open(self, sp: Span):
        """Push a span entered while recording: its id, its parent's
        ``slab``/``scan`` ids, and its profiler annotation."""
        st = self._stack()
        if st:
            sp._parent = parent = st[-1]
            for k in IDS:
                if k not in sp.attrs and k in parent.attrs:
                    sp.attrs[k] = parent.attrs[k]
        sp.id = next(self._ids)
        st.append(sp)
        ann = _annotation_cls()
        if ann:
            sp._ann = ann(sp.name, **{k: sp.attrs[k] for k in IDS
                                      if k in sp.attrs})
            sp._ann.__enter__()

    def _close(self, sp: Span):
        if sp._ann is not None:
            sp._ann.__exit__(None, None, None)
            sp._ann = None
        st = self._stack()
        if st and st[-1] is sp:
            st.pop()
        if self.enabled:
            # a span entered before recording began has no id: it is
            # recorded at the top of its thread, as it was never pushed
            self._record(sp.name, sp.t0, sp.t1, sp.lane, len(st),
                         sp.id if sp.id is not None else next(self._ids),
                         sp._parent, sp.attrs, "span")

    # ------------------------------------------------------------------ #
    # interrogation
    # ------------------------------------------------------------------ #
    def spans(self, name: str | None = None) -> list[dict]:
        """Finished span events (optionally filtered by exact name)."""
        with self._lock:
            evs = [e for e in self.events if e["kind"] == "span"]
        if name is not None:
            evs = [e for e in evs if e["name"] == name]
        return evs

    def total_s(self, name: str) -> float:
        """Summed duration of every span with ``name``."""
        return sum(e["t1"] - e["t0"] for e in self.spans(name))

    def reset(self):
        with self._lock:
            self.events.clear()


# --------------------------------------------------------------------- #
# the process-default tracer (what the instrumented hot paths use)
# --------------------------------------------------------------------- #
_tracer = Tracer(enabled=False)


def get_tracer() -> Tracer:
    return _tracer


def set_tracer(tracer: Tracer) -> Tracer:
    """Swap the process-default tracer (tests); returns the old one."""
    global _tracer
    old, _tracer = _tracer, tracer
    return old


def enable(clock=None) -> Tracer:
    """Turn on recording on the default tracer (fresh event list)."""
    global _tracer
    _tracer = Tracer(enabled=True, clock=clock)
    return _tracer


def disable() -> Tracer:
    """Stop recording (spans keep measuring for their callers)."""
    _tracer.enabled = False
    return _tracer


def reset():
    _tracer.reset()


def span(name: str, *, lane: str | None = None, **attrs) -> Span:
    """A span on the process-default tracer (the instrumentation entry
    point: ``with span("stream/solve", slab=j0) as sp: ...``)."""
    return _tracer.span(name, lane=lane, **attrs)


def instant(name: str, *, lane: str | None = None, **attrs):
    """An instant marker on the process-default tracer."""
    _tracer.instant(name, lane=lane, **attrs)
