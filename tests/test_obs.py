"""repro.obs acceptance: spans, exporters, metrics, drift (ISSUE 9).

Pins the observability contract:

  * span nesting / thread lanes / exception recording are exact under
    a fake clock (no ``time.*`` in any assertion);
  * the Chrome trace exporter emits deterministic, schema-valid JSON
    (validated against the checked-in ``chrome_trace.schema.json``,
    which also rejects malformed documents);
  * the Prometheus exposition is byte-deterministic;
  * the drift report joins measured vs modeled per phase, dedups
    nested same-phase spans, and prices a real ``Reconstructor`` with
    the same decomposition the autotuner sums;
  * a traced streaming drain agrees with ``StreamResult``'s ``*_s``
    fields to <1% (they are the same span durations by construction);
  * a failed serve job still carries terminal telemetry and its
    failing span records the exception type;
  * the deprecated ``*_seconds`` aliases are gone (the one-release
    window closed; only the ``*_s`` names remain).
"""
import json
import threading

import numpy as np
import pytest

from repro.obs import drift, export, metrics, trace


def fake_clock(*vals):
    return iter([float(v) for v in vals]).__next__


def counting_clock():
    it = iter(range(10_000))
    return lambda: float(next(it))


# --------------------------------------------------------------------- #
# trace: spans
# --------------------------------------------------------------------- #
def test_span_nesting_exact_under_fake_clock():
    t = trace.Tracer(enabled=True, clock=counting_clock())
    with t.span("stream/slab", slab=3) as outer:
        with t.span("stream/solve") as inner:
            pass
    # children close (and record) before parents; parent/depth tracked
    assert [(e["name"], e["t0"], e["t1"], e["depth"], e["parent"])
            for e in t.events] == [
        ("stream/solve", 1.0, 2.0, 1, "stream/slab"),
        ("stream/slab", 0.0, 3.0, 0, None),
    ]
    assert inner.duration_s == 1.0 and outer.duration_s == 3.0
    assert t.events[1]["attrs"] == {"slab": 3}
    assert t.total_s("stream/solve") == 1.0
    assert len(t.spans("stream/slab")) == 1


def test_disabled_tracer_measures_but_records_nothing():
    t = trace.Tracer(enabled=False, clock=fake_clock(5.0, 7.5))
    with t.span("stream/solve") as sp:
        pass
    assert sp.duration_s == 2.5  # callers still get their timing
    assert t.events == []
    t.instant("recon/exchange", ici_bytes=1)
    assert t.events == []


def test_span_records_exception_type_and_still_measures():
    t = trace.Tracer(enabled=True, clock=fake_clock(0.0, 1.0))
    with pytest.raises(KeyError):
        with t.span("serve/slab", job=7) as sp:
            raise KeyError("boom")
    assert sp.duration_s == 1.0
    (e,) = t.events
    assert e["attrs"] == {"job": 7, "exception": "KeyError"}


def test_thread_lanes_are_separate():
    t = trace.Tracer(enabled=True, clock=counting_clock())
    with t.span("stream/solve"):
        pass

    def worker():
        with t.span("stream/load"):
            pass

    th = threading.Thread(target=worker, name="prefetch-0")
    th.start()
    th.join()
    by_name = {e["name"]: e for e in t.events}
    load, solve = by_name["stream/load"], by_name["stream/solve"]
    assert load["thread"] == "prefetch-0"
    assert load["thread_id"] != solve["thread_id"]
    # the worker's span is top-of-stack on ITS OWN thread, not nested
    # under whatever the main thread had open
    assert load["parent"] is None and load["depth"] == 0
    doc = export.chrome_trace(t)
    tids = {e["name"]: e["tid"] for e in doc["traceEvents"]
            if e["ph"] == "X"}
    assert tids["stream/load"] != tids["stream/solve"]


def test_explicit_lane_groups_events():
    t = trace.Tracer(enabled=True, clock=counting_clock())
    with t.span("serve/slab", lane="tenant:alice"):
        pass
    with t.span("serve/slab", lane="tenant:bob"):
        pass
    doc = export.chrome_trace(t)
    lanes = {e["args"]["name"]: e["tid"] for e in doc["traceEvents"]
             if e["ph"] == "M"}
    assert set(lanes) == {"tenant:alice", "tenant:bob"}
    xs = [e for e in doc["traceEvents"] if e["ph"] == "X"]
    assert {e["tid"] for e in xs} == set(lanes.values())


# --------------------------------------------------------------------- #
# export: schema + determinism
# --------------------------------------------------------------------- #
def _small_tracer():
    t = trace.Tracer(enabled=True, clock=fake_clock(10.0, 11.0, 11.5))
    with t.span("stream/solve", slab=0):
        pass
    t.instant("recon/exchange", ici_bytes=128.0, dci_bytes=0.0)
    return t


def test_chrome_trace_schema_valid_and_deterministic(tmp_path):
    doc = export.validate_chrome_trace(export.chrome_trace(_small_tracer()))
    # timestamps rebase to the earliest event; micros
    (x,) = [e for e in doc["traceEvents"] if e["ph"] == "X"]
    assert x["ts"] == 0.0 and x["dur"] == pytest.approx(1e6)
    (i,) = [e for e in doc["traceEvents"] if e["ph"] == "i"]
    assert i["ts"] == pytest.approx(1.5e6) and i["s"] == "t"
    # identical tracers -> byte-identical files
    p1, p2 = tmp_path / "a.json", tmp_path / "b.json"
    export.write_chrome_trace(str(p1), _small_tracer())
    export.write_chrome_trace(str(p2), _small_tracer())
    assert p1.read_bytes() == p2.read_bytes()
    export.validate_chrome_trace(json.loads(p1.read_text()))


def test_schema_rejects_malformed_documents():
    good = export.chrome_trace(_small_tracer())
    with pytest.raises(export.SchemaError, match="missing required"):
        export.validate_chrome_trace({"traceEvents": []})
    with pytest.raises(export.SchemaError, match="not in"):
        bad = json.loads(json.dumps(good))
        bad["traceEvents"][0]["ph"] = "Q"
        export.validate_chrome_trace(bad)
    with pytest.raises(export.SchemaError, match="minimum"):
        bad = json.loads(json.dumps(good))
        bad["traceEvents"][-1]["ts"] = -1.0
        export.validate_chrome_trace(bad)
    with pytest.raises(export.SchemaError, match="expected integer"):
        bad = json.loads(json.dumps(good))
        bad["traceEvents"][0]["tid"] = "one"
        export.validate_chrome_trace(bad)
    with pytest.raises(export.SchemaError, match="missing ts/dur"):
        bad = json.loads(json.dumps(good))
        for e in bad["traceEvents"]:
            if e["ph"] == "X":
                del e["dur"]
        export.validate_chrome_trace(bad)


# --------------------------------------------------------------------- #
# metrics
# --------------------------------------------------------------------- #
def test_metrics_exposition_is_deterministic():
    def build():
        m = metrics.Metrics()
        m.inc("serve_jobs_total", 2, status="done")
        m.inc("serve_jobs_total", status="failed")
        m.set_gauge("serve_queue_depth", 4)
        m.observe("batch_s", 0.05, buckets=(0.01, 0.1, 1.0))
        m.observe("batch_s", 0.5, buckets=(0.01, 0.1, 1.0))
        return m

    text = build().render_prometheus()
    assert text == build().render_prometheus()
    assert 'serve_jobs_total{status="done"} 2' in text
    assert "# TYPE batch_s histogram" in text
    # cumulative buckets: 0.05 lands in le=0.1 AND le=1
    assert 'batch_s_bucket{le="0.1"} 1' in text
    assert 'batch_s_bucket{le="1"} 2' in text
    assert 'batch_s_bucket{le="+Inf"} 2' in text
    assert build().get("serve_jobs_total", status="done") == 2.0
    assert build().get("nope") == 0.0


def test_counters_cannot_decrease():
    m = metrics.Metrics()
    with pytest.raises(ValueError, match="cannot decrease"):
        m.inc("x_total", -1)


# --------------------------------------------------------------------- #
# drift
# --------------------------------------------------------------------- #
def test_drift_report_pins_on_injected_model():
    t = trace.Tracer(enabled=True, clock=fake_clock(0.0, 2.0, 2.0, 2.5))
    with t.span("stream/solve"):
        pass
    with t.span("stream/load"):
        pass
    rep = drift.drift_report(
        t,
        modeled={"solve": 1.0, "hbm": 0.5, "dma_issue": 0.3,
                 "exchange_ici": 0.2, "exchange_dci": 0.0},
        threshold=0.5,
    )
    assert [r.phase for r in rep.rows] == list(drift.PHASES)
    solve = rep.row("solve")
    assert (solve.measured_s, solve.modeled_s, solve.ratio,
            solve.source, solve.flagged) == (2.0, 1.0, 2.0, "span", True)
    # sub-phases: attributed share of the measured solve, never flagged
    hbm = rep.row("hbm")
    assert hbm.measured_s == pytest.approx(1.0)
    assert hbm.share == pytest.approx(0.5)
    assert hbm.source == "attributed" and not hbm.flagged
    assert rep.row("exchange_dci").ratio is None  # modeled 0: no ratio
    assert rep.row("load").measured_s == 0.5
    assert rep.row("load").modeled_s is None
    assert [r.phase for r in rep.flagged] == ["solve"]
    # a measured solve inside the band does not flag
    t2 = trace.Tracer(enabled=True, clock=fake_clock(0.0, 1.2))
    with t2.span("stream/solve"):
        pass
    rep2 = drift.drift_report(t2, modeled={"solve": 1.0}, threshold=0.5)
    assert rep2.flagged == []
    # render + json round out the report object
    assert "DRIFT" in rep.render()
    parsed = json.loads(rep.to_json())
    assert parsed["rows"][0]["phase"] == "solve"


def test_drift_dedups_nested_same_phase_spans():
    t = trace.Tracer(enabled=True, clock=counting_clock())
    with t.span("stream/solve"):        # 0 .. 3
        with t.span("recon/solve"):     # 1 .. 2: same phase, nested
            pass
    measured = drift.measured_phases(t)
    assert measured == {"solve": 3.0}  # NOT 3 + 1
    # the same inner span at top level DOES count
    t2 = trace.Tracer(enabled=True, clock=fake_clock(0.0, 1.0))
    with t2.span("recon/solve"):
        pass
    assert drift.measured_phases(t2) == {"solve": 1.0}


def test_drift_requires_model_or_reconstructor():
    t = trace.Tracer(enabled=True)
    with pytest.raises(ValueError, match="modeled= or all of"):
        drift.drift_report(t)


def test_modeled_phases_prices_real_reconstructor(small_system):
    from repro.core.recon import ReconConfig, Reconstructor

    _, _, plan = small_system
    rec = Reconstructor(
        plan, cfg=ReconConfig(precision="single", comm_mode="rs", fuse=2)
    )
    phases, meta = drift.modeled_phases(rec, iters=4, n_slices=8)
    # the same decomposition the autotuner's modeled tier sums
    assert phases["solve"] == pytest.approx(
        phases["hbm"] + phases["dma_issue"]
        + phases["exchange_ici"] + phases["exchange_dci"]
    )
    assert phases["hbm"] > 0 and phases["dma_issue"] > 0
    assert meta["overhead_source"] == "default"
    assert meta["per_copy_overhead_s"] > 0
    # iters scale linearly in applications: (iters+1)
    p2, _ = drift.modeled_phases(rec, iters=9, n_slices=8)
    assert p2["solve"] == pytest.approx(phases["solve"] * 2.0)
    # a calibrated overhead changes only the issue term + provenance
    p3, m3 = drift.modeled_phases(
        rec, iters=4, n_slices=8,
        per_copy_overhead_s=2 * meta["per_copy_overhead_s"],
    )
    assert p3["dma_issue"] == pytest.approx(2 * phases["dma_issue"])
    assert p3["hbm"] == phases["hbm"]
    assert m3["overhead_source"] == "measured"
    with pytest.raises(ValueError, match="granule"):
        drift.modeled_phases(rec, iters=4, n_slices=7)


# --------------------------------------------------------------------- #
# wired paths: streaming + serve
# --------------------------------------------------------------------- #
@pytest.fixture()
def fresh_tracer():
    """Swap in an enabled tracer + fresh metrics; restore after."""
    old_t = trace.set_tracer(trace.Tracer(enabled=True))
    old_m = metrics.set_metrics(metrics.Metrics())
    try:
        yield trace.get_tracer(), metrics.get_metrics()
    finally:
        trace.set_tracer(old_t)
        metrics.set_metrics(old_m)


def test_streaming_trace_agrees_with_result_fields(
    small_system, tmp_path, fresh_tracer
):
    from repro.core.recon import ReconConfig, Reconstructor
    from repro.data.phantom import phantom_slices, simulate_measurements
    from repro.stream import (
        SlabStore,
        reconstruct_streaming,
        simulate_to_store,
    )

    tracer, m = fresh_tracer
    geo, a, plan = small_system
    rec = Reconstructor(
        plan, cfg=ReconConfig(precision="single", comm_mode="rs", fuse=2)
    )
    store = SlabStore.create(str(tmp_path / "sino"), geo.n_rays, 8, 2)
    simulate_to_store(a, geo.n, store, noise=0.01, seed=5)
    res = reconstruct_streaming(
        rec, store, str(tmp_path / "vol"), iters=3, y_slab=4,
    )
    assert len(res.solved) == 2
    # acceptance: per-slab span sums agree with the result fields to
    # <1% -- by construction they are the SAME span durations
    for name, field in (
        ("stream/solve", res.solve_s),
        ("stream/load", res.load_s),
        ("stream/stage", res.upload_s),
        ("stream/slab", res.slab_s),
    ):
        assert tracer.total_s(name) == pytest.approx(
            sum(field), rel=0.01
        ), name
    # exchange instants + counters rode along
    ex = [e for e in tracer.events if e["name"] == "recon/exchange"]
    assert len(ex) == 2 and all(
        e["attrs"]["ici_bytes"] > 0 for e in ex
    )
    assert m.get("stream_slabs_total") == 2.0
    assert m.get("comm_bytes_total", link="ici") == pytest.approx(
        sum(e["attrs"]["ici_bytes"] for e in ex)
    )
    # the whole trace exports schema-valid
    export.validate_chrome_trace(export.chrome_trace(tracer))
    # and the drift report covers the acceptance phases from a live rec
    rep = drift.drift_report(tracer, rec=rec, iters=3, n_slices=8)
    assert rep.row("solve").source == "span"
    assert rep.row("dma_issue").source == "attributed"
    assert rep.row("exchange_ici").source == "attributed"


def test_failed_serve_job_reports_terminal_telemetry(
    small_system, tmp_path, fresh_tracer
):
    from repro.core.partition import PartitionConfig
    from repro.core.recon import ReconConfig
    from repro.data.phantom import phantom_slices, simulate_measurements
    from repro.serve import JobSpec, ReconServer
    from repro.stream import SlabStore

    tracer, m = fresh_tracer
    geo, a, _ = small_system
    x = phantom_slices(geo.n, 8, seed=5)
    sino = simulate_measurements(a, x, noise=0.01, seed=5)
    pcfg = PartitionConfig(
        n_data=1, tile=4, rows_per_block=16, nnz_per_stage=16
    )
    rcfg = ReconConfig(precision="single", comm_mode="rs", fuse=2)
    # a sinogram store missing its second shard: slab 1 solves, slab 2's
    # fetch raises inside the stream/load span
    holey = SlabStore.create(str(tmp_path / "holey"), geo.n_rays, 8, 4)
    holey.write(0, sino[:, :4])
    srv = ReconServer(2 * 2**30, workdir=str(tmp_path / "srv"))
    bad = srv.submit(JobSpec(geo=geo, sino=holey, pcfg=pcfg, rcfg=rcfg,
                             iters=3, y_slab=4))
    srv.drain()
    assert bad.status == "failed"
    t = bad.telemetry
    # the telemetry gap, closed: a failed job still reports terminal
    # timing and what killed it, plus the split up to the failure point
    assert t.total_s > 0
    assert t.error_type == "FileNotFoundError"
    assert t.n_slabs == 1 and t.solve_s > 0
    # the failing span recorded the exception type
    failed_loads = [
        e for e in tracer.spans("stream/load")
        if "exception" in e["attrs"]
    ]
    assert [e["attrs"]["exception"] for e in failed_loads] == [
        "FileNotFoundError"
    ]
    # slabs that DID run sit on the tenant lane
    assert tracer.spans("serve/slab")[0]["lane"] == "tenant:default"
    assert m.get("serve_jobs_total", status="failed") == 1.0
    assert m.get("plan_cache_misses_total") == 1.0
    # the server's scrape endpoint renders the same registry
    text = srv.metrics_text()
    assert 'serve_jobs_total{status="failed"} 1' in text
    assert "serve_queue_depth 0" in text


def test_seconds_aliases_are_gone():
    """The deprecated ``*_seconds`` aliases completed their one-release
    deprecation window: only the ``*_s`` names remain."""
    from repro.serve.jobs import JobTelemetry
    from repro.stream.driver import StreamResult

    res = StreamResult(
        volume=None, resnorms=np.zeros((1, 1)), y_slab=4,
        solved=[0], skipped=[], slab_s=[1.5],
    )
    assert not hasattr(res, "slab_seconds")
    assert not hasattr(JobTelemetry(), "queue_seconds")
