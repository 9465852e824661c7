"""Program spans on the profiler's clock, their ids, and the transfer
counters of a solve.

  * a recording span opens and closes one ``jax.profiler``
    ``TraceAnnotation`` carrying its ``slab``/``scan`` ids; a disabled
    tracer opens none and reads the clock twice per span, nothing more;
  * ``id``/``parent_id`` nest under a fake clock, per thread;
  * a streaming drain emits ``stream/open``, ``stream/wait``,
    ``recon/dispatch`` (inside ``recon/solve``) and ``recon/unpack``
    (inside ``stream/solve``, after ``recon/solve``), with one ``slab``
    id per slab on both threads and one ``scan`` id per drain;
  * ``h2d_bytes_total`` / ``d2h_bytes_total`` equal the bytes of the
    arrays a solve moves, reckoned here from the arrays themselves.
  * ``spmm_dma_segments_total`` equals the window DMAs of the
    operators' segment tables times the applies the drain ran.
"""
import threading

import numpy as np
import pytest

from repro.obs import metrics, trace


def counting_clock():
    it = iter(range(10_000))
    return lambda: float(next(it))


class Recorder:
    """Stands in for ``jax.profiler.TraceAnnotation``."""

    log: list = []

    def __init__(self, name, **kwargs):
        self.name, self.kwargs = name, kwargs

    def __enter__(self):
        Recorder.log.append(("enter", self.name, self.kwargs))

    def __exit__(self, *exc):
        Recorder.log.append(("exit", self.name))


@pytest.fixture()
def recorder(monkeypatch):
    Recorder.log = []
    monkeypatch.setattr(trace, "_annotation", Recorder)
    return Recorder.log


def test_recording_span_opens_one_annotation(recorder):
    t = trace.Tracer(enabled=True, clock=counting_clock())
    with t.span("stream/slab", slab=3, scan=7, j0=12):
        with t.span("stream/solve", iters=5):
            pass
    assert recorder == [
        ("enter", "stream/slab", {"slab": 3, "scan": 7}),
        ("enter", "stream/solve", {"slab": 3, "scan": 7}),
        ("exit", "stream/solve"),
        ("exit", "stream/slab"),
    ]
    solve, slab = t.events
    assert (slab["id"], slab["parent_id"], slab["parent"]) == (1, None, None)
    assert (solve["id"], solve["parent_id"], solve["parent"]) == (
        2, 1, "stream/slab")
    # the child takes its parent's ids, keeps its own attrs
    assert solve["attrs"] == {"iters": 5, "slab": 3, "scan": 7}
    # the perf_counter reads sit inside the annotation
    assert (solve["t0"], solve["t1"], slab["t0"], slab["t1"]) == (
        1.0, 2.0, 0.0, 3.0)


def test_disabled_span_opens_nothing_and_reads_two_clocks(monkeypatch,
                                                           recorder):
    def refuse():
        raise AssertionError("a disabled span reached for jax")

    monkeypatch.setattr(trace, "_annotation_cls", refuse)
    clock = iter([5.0, 7.5]).__next__  # a third read would raise
    t = trace.Tracer(enabled=False, clock=clock)
    with t.span("stream/slab", slab=1) as sp:
        pass
    assert sp.duration_s == 2.5
    assert sp.id is None and sp.attrs == {"slab": 1}
    assert recorder == [] and t.events == []


def test_span_closed_after_disable_is_popped_not_recorded(recorder):
    t = trace.Tracer(enabled=True, clock=counting_clock())
    with t.span("stream/slab", slab=0):
        t.enabled = False
    assert recorder[-1] == ("exit", "stream/slab")
    assert t._stack() == [] and t.events == []
    t.enabled = True
    with t.span("stream/slab", slab=1):
        pass
    (e,) = t.events
    assert e["parent_id"] is None and e["depth"] == 0


def test_ids_are_unique_and_parents_stay_on_their_thread(recorder):
    t = trace.Tracer(enabled=True, clock=counting_clock())

    def worker():
        with t.span("stream/load", slab=1):
            t.instant("resil/retry")

    with t.span("stream/slab", slab=0):
        th = threading.Thread(target=worker, name="prefetch-0")
        th.start()
        th.join()
    by_name = {e["name"]: e for e in t.events}
    assert len({e["id"] for e in t.events}) == 3
    load, slab = by_name["stream/load"], by_name["stream/slab"]
    assert load["parent_id"] is None and load["attrs"] == {"slab": 1}
    assert by_name["resil/retry"]["parent_id"] == load["id"]
    assert slab["attrs"] == {"slab": 0}


# --------------------------------------------------------------------- #
# a streaming drain
# --------------------------------------------------------------------- #
ITERS, SLICES, SLAB = 3, 8, 4


@pytest.fixture(scope="module")
def drain(small_system, tmp_path_factory):
    """Two slabs through ``reconstruct_streaming``, traced, with a fresh
    metrics registry: ``(rec, events, registry)``."""
    from repro.core.recon import ReconConfig, Reconstructor
    from repro.stream import (SlabStore, reconstruct_streaming,
                              simulate_to_store)

    geo, a, plan = small_system
    tmp = tmp_path_factory.mktemp("drain")
    rec = Reconstructor(
        plan, cfg=ReconConfig(precision="single", comm_mode="rs", fuse=2)
    )
    store = SlabStore.create(str(tmp / "sino"), geo.n_rays, SLICES, SLAB)
    simulate_to_store(a, geo.n, store, noise=0.01, seed=5)
    old_t = trace.set_tracer(trace.Tracer(enabled=True))
    old_m = metrics.set_metrics(metrics.Metrics())
    try:
        res = reconstruct_streaming(
            rec, store, str(tmp / "vol"), iters=ITERS, y_slab=SLAB,
        )
        events = list(trace.get_tracer().events)
        registry = metrics.get_metrics()
    finally:
        trace.set_tracer(old_t)
        metrics.set_metrics(old_m)
    assert len(res.solved) == SLICES // SLAB
    return rec, events, registry


def _spans(events, name):
    return [e for e in events if e["kind"] == "span" and e["name"] == name]


def _inside(inner, outer):
    return outer["t0"] <= inner["t0"] and inner["t1"] <= outer["t1"]


def test_drain_emits_the_host_spans(drain):
    _, events, _ = drain
    (opened,) = _spans(events, "stream/open")
    slabs = _spans(events, "stream/slab")
    assert opened["thread"] == "MainThread" and opened["parent"] is None
    assert opened["t1"] <= min(s["t0"] for s in slabs)
    # one wait a slab, and the last one that finds the prefetcher done
    waits = _spans(events, "stream/wait")
    assert len(waits) == len(slabs) + 1
    assert all(w["thread"] == "MainThread" for w in waits)
    by_id = {e["id"]: e for e in events}
    for d in _spans(events, "recon/dispatch"):
        solve = by_id[d["parent_id"]]
        assert solve["name"] == "recon/solve" and _inside(d, solve)
    for u in _spans(events, "recon/unpack"):
        outer = by_id[u["parent_id"]]
        assert outer["name"] == "stream/solve" and _inside(u, outer)
        (solve,) = [s for s in _spans(events, "recon/solve")
                    if s["parent_id"] == outer["id"]]
        assert solve["t1"] <= u["t0"]


def test_drain_pins_the_nesting_the_metrics_read(drain):
    """``stream/slab`` > ``stream/solve`` > ``recon/solve`` >
    ``recon/dispatch``; ``stream/write`` in the slab, after its solve."""
    _, events, _ = drain
    by_id = {e["id"]: e for e in events}
    parent_of = {name: {by_id[e["parent_id"]]["name"]
                        for e in _spans(events, name)}
                 for name in ("stream/solve", "recon/solve",
                              "recon/dispatch", "recon/unpack",
                              "stream/write")}
    assert parent_of == {
        "stream/solve": {"stream/slab"},
        "recon/solve": {"stream/solve"},
        "recon/dispatch": {"recon/solve"},
        "recon/unpack": {"stream/solve"},
        "stream/write": {"stream/slab"},
    }
    for w in _spans(events, "stream/write"):
        slab = by_id[w["parent_id"]]
        (solve,) = [s for s in _spans(events, "stream/solve")
                    if s["parent_id"] == slab["id"]]
        assert _inside(w, slab) and solve["t1"] <= w["t0"]


def test_drain_carries_one_slab_id_on_both_threads(drain):
    _, events, _ = drain
    spans = [e for e in events if e["kind"] == "span"]
    (scan,) = {e["attrs"]["scan"] for e in spans}
    assert scan == _spans(events, "stream/open")[0]["attrs"]["scan"]
    for i in range(SLICES // SLAB):
        mine = [e for e in spans if e["attrs"].get("slab") == i]
        names = {(e["name"], e["thread"] == "MainThread") for e in mine}
        assert names >= {
            ("stream/load", False), ("stream/stage", False),
            ("recon/stage", False), ("stream/slab", True),
            ("stream/solve", True), ("recon/solve", True),
            ("recon/dispatch", True), ("recon/unpack", True),
            ("stream/write", True),
        }, i


def test_transfer_counters_equal_the_arrays_moved(drain):
    rec, events, m = drain
    solves = SLICES // SLAB
    f32 = np.dtype(np.float32).itemsize
    operator = sum(np.asarray(v).nbytes for v in rec._arrays.values())
    want = {
        "operator": solves * operator,
        "x0": solves * rec.tomo_pad * SLAB * f32,
        "sino": solves * rec.sino_pad * SLAB * f32,
    }
    got = {w: m.get("h2d_bytes_total", what=w) for w in want}
    assert got == want
    # the spans that transfer carry the same bytes
    assert sum(e["attrs"].get("h2d_bytes", 0) for e in events
               if e["kind"] == "span") == sum(want.values())
    assert m.get("d2h_bytes_total", what="volume") == (
        solves * rec.tomo_pad * SLAB * f32)
    assert m.get("d2h_bytes_total", what="resnorm") == (
        solves * ITERS * SLAB * f32)
    assert not any(k.startswith("dma_issues_total")
                   for k in m.snapshot()["counters"])


def test_dma_segment_counter_equals_the_tables_issues(drain):
    """``spmm_dma_segments_total{op}``: each operator's real window
    segments an apply, reckoned here from its table, times the applies
    the drain ran (the initial pair and one an iteration, for each
    fused minibatch of each slab); ``recon/dispatch`` carries the sum."""
    from repro.kernels.ops import dma_issue_count

    rec, events, m = drain
    applies = (SLICES // SLAB) * (ITERS + 1) * (SLAB // rec.cfg.fuse)
    want = {op: dma_issue_count(getattr(rec.plan, op).winsegs) * applies
            for op in ("proj", "back")}
    assert all(want.values())
    got = {op: m.get("spmm_dma_segments_total", op=op) for op in want}
    assert got == want
    assert sum(e["attrs"].get("dma_segments", 0)
               for e in _spans(events, "recon/dispatch")) == sum(
                   want.values())
