"""Chaos acceptance (ISSUE 10): injection, retry, quarantine, self-heal.

Pins the tentpole criteria:

  * a streaming run under a seeded ``FaultPlan`` (one transient read
    error, one corrupt shard, one injected NaN) completes with a volume
    BIT-IDENTICAL to the clean run, with ``retries > 0``;
  * when retries are exhausted, exactly the poison slab is quarantined
    (``StreamResult.failed_slabs`` + ``slabs_quarantined_total``), the
    drain finishes the rest, and a later resume re-attempts it;
  * a non-finite quantized solve escalates one precision rung and
    succeeds; a dead prefetch worker recovers via the driver's
    synchronous re-try; a flagged straggler shrinks the lookahead;
  * the serve path retries transient loads per job, enforces deadlines,
    and trips a per-plan circuit breaker on repeated build failures;
  * ``obs.drift`` excludes retried attempts from the model join.
"""
import os
import time

import numpy as np
import pytest

from repro.core.recon import ReconConfig, Reconstructor
from repro.data.phantom import phantom_slices, simulate_measurements
from repro.resil import (
    CircuitBreaker,
    CorruptShardError,
    FaultPlan,
    InjectedIOError,
    InjectedThreadDeath,
    NonFiniteSolveError,
    RetryPolicy,
    call_with_retry,
    inject,
)
from repro.resil.inject import hash01
from repro.stream import SlabStore, reconstruct_streaming, simulate_to_store

Y = 8  # slices in the streaming fixtures (multiple of fuse=2)


@pytest.fixture(scope="module")
def rec(small_system):
    _, _, plan = small_system
    return Reconstructor(
        plan, cfg=ReconConfig(precision="single", comm_mode="rs", fuse=2)
    )


@pytest.fixture(scope="module")
def sino8(small_system):
    geo, a, _ = small_system
    x = phantom_slices(geo.n, Y, seed=5)
    return simulate_measurements(a, x, noise=0.01, seed=5)


@pytest.fixture()
def sino_store(small_system, tmp_path):
    geo, a, _ = small_system
    store = SlabStore.create(str(tmp_path / "sino"), geo.n_rays, Y, 2)
    simulate_to_store(a, geo.n, store, noise=0.01, seed=5)
    return store


FAST = RetryPolicy(max_attempts=3, base_delay_s=0.0, jitter=0.0)


# --------------------------------------------------------------------- #
# injection registry
# --------------------------------------------------------------------- #
def test_hash01_deterministic_in_range():
    a = hash01(0, "site", 3, 1)
    assert a == hash01(0, "site", 3, 1)
    assert 0.0 <= a < 1.0
    # any argument perturbs the draw
    assert a != hash01(1, "site", 3, 1)
    assert a != hash01(0, "site", 3, 2)


def test_inactive_sites_are_passthrough():
    arr = np.ones(4, np.float32)
    assert inject.mutate("store/read", arr, key=0) is arr  # same object
    inject.fire("stream/load", key=0)  # no-op, no error
    assert not inject.active()


def test_transient_vs_persistent_attempts(fresh_obs):
    _, m = fresh_obs
    plan = (
        FaultPlan(seed=1)
        .add("stream/load", "io_error", key=2, attempts=(0,))
        .add("stream/stage", "io_error", key=7, attempts=None)
    )
    with inject.activate(plan) as h:
        with pytest.raises(InjectedIOError):
            inject.fire("stream/load", key=2)
        inject.fire("stream/load", key=2)  # attempt 1: healed
        inject.fire("stream/load", key=3)  # other key: never fires
        for _ in range(3):  # persistent: every consultation fires
            with pytest.raises(InjectedIOError):
                inject.fire("stream/stage", key=7)
    assert [f[:3] for f in h.fired] == [
        ("stream/load", 2, 0),
        ("stream/stage", 7, 0),
        ("stream/stage", 7, 1),
        ("stream/stage", 7, 2),
    ]
    assert m.get(
        "faults_injected_total", site="stream/load", kind="io_error"
    ) == 1
    assert not inject.active()  # deactivated on exit


def test_ctx_match_scope_and_mutations(fresh_obs):
    plan = (
        FaultPlan(seed=3)
        .add("recon/solve", "nonfinite", attempts=None,
             when={"precision": "q8"})
        .add("store/read", "corrupt", key=0, attempts=(0,))
    )
    x = np.arange(8, dtype=np.float32)
    with inject.activate(plan):
        with inject.scope(5):  # keyless site resolves via scope
            bad = inject.mutate("recon/solve", x, ctx={"precision": "q8"})
            ok = inject.mutate(
                "recon/solve", x, ctx={"precision": "single"}
            )
        assert np.isnan(bad).sum() == 1 and bad is not x  # copy poisoned
        assert np.isfinite(x).all()  # caller's array untouched
        assert np.array_equal(ok, x)
        flipped = inject.mutate("store/read", x, key=0)
        assert (flipped != x).sum() == 1  # one byte-flipped element
    # replaying the same plan fires identically (counters reset)
    with inject.activate(plan):
        with inject.scope(5):
            again = inject.mutate(
                "recon/solve", x, ctx={"precision": "q8"}
            )
        np.testing.assert_array_equal(again, bad)  # same element poisoned


def test_activate_is_exclusive():
    with inject.activate(FaultPlan()):
        with pytest.raises(RuntimeError, match="already active"):
            with inject.activate(FaultPlan()):
                pass


# --------------------------------------------------------------------- #
# retry policy
# --------------------------------------------------------------------- #
def test_retry_backoff_deterministic_and_bounded():
    p = RetryPolicy(base_delay_s=0.1, backoff=2.0, jitter=0.5, seed=3)
    d = [p.delay_s("stream/load", 4, a) for a in (1, 2, 3)]
    assert d == [p.delay_s("stream/load", 4, a) for a in (1, 2, 3)]
    for a, nominal in zip((1, 2, 3), (0.1, 0.2, 0.4)):
        assert 0.5 * nominal <= d[a - 1] <= 1.5 * nominal
    # different keys de-synchronize two workers' backoff
    assert p.delay_s("stream/load", 4, 1) != p.delay_s("stream/load", 5, 1)


def test_call_with_retry_transient_then_success(fresh_obs):
    _, m = fresh_obs
    calls = []

    def flaky(attempt):
        calls.append(attempt)
        if attempt < 2:
            raise OSError("disk hiccup")
        return "ok"

    assert call_with_retry(
        flaky, policy=FAST, site="stream/load", key=1
    ) == "ok"
    assert calls == [0, 1, 2]
    assert m.get("retries_total", site="stream/load") == 2


def test_call_with_retry_exhaustion_reraises_last():
    def dead(attempt):
        raise OSError(f"gone {attempt}")

    with pytest.raises(OSError, match="gone 2"):
        call_with_retry(dead, policy=FAST, site="s", sleep=lambda d: None)


def test_corrupt_shard_retried_exactly_once():
    calls = []

    def corrupt(attempt):
        calls.append(attempt)
        raise CorruptShardError("crc mismatch")

    with pytest.raises(CorruptShardError):
        call_with_retry(corrupt, policy=FAST, site="store/read")
    assert calls == [0, 1]  # one re-read, not max_attempts


def test_nonretryable_propagates_immediately():
    with pytest.raises(ValueError):
        call_with_retry(
            lambda a: (_ for _ in ()).throw(ValueError("bug")),
            policy=FAST, site="s",
        )


def test_retry_timeout_budget():
    t = {"n": 0}

    def slow(attempt):
        t["n"] += 1
        time.sleep(0.05)
        raise OSError("still down")

    p = RetryPolicy(max_attempts=100, base_delay_s=0.0, timeout_s=0.01)
    with pytest.raises(OSError):
        call_with_retry(slow, policy=p, site="s")
    assert t["n"] <= 2  # budget cut it off long before 100 attempts


# --------------------------------------------------------------------- #
# store integrity
# --------------------------------------------------------------------- #
def test_store_records_and_verifies_checksums(tmp_path):
    rng = np.random.default_rng(0)
    arr = rng.standard_normal((6, 8)).astype(np.float32)
    store = SlabStore.from_array(str(tmp_path / "s"), arr, slab=4)
    import json

    with open(tmp_path / "s" / "manifest.json") as f:
        man = json.load(f)
    assert man["checksum_algo"] == "crc32"
    assert set(man["checksums"]) == {"0_4", "4_8"}
    # re-open (create with matching shape) keeps the recorded checksums
    again = SlabStore.create(str(tmp_path / "s"), 6, 8, 4)
    assert again._checksums == {
        k: int(v) for k, v in man["checksums"].items()
    }
    np.testing.assert_array_equal(again.to_array(), arr)


def test_store_detects_on_disk_corruption(tmp_path):
    arr = np.ones((4, 4), np.float32)
    store = SlabStore.from_array(str(tmp_path / "s"), arr, slab=4)
    path = store._shard_path(0, 4)
    with open(path, "r+b") as f:  # flip one payload byte on disk
        f.seek(-3, os.SEEK_END)
        b = f.read(1)
        f.seek(-3, os.SEEK_END)
        f.write(bytes([b[0] ^ 0xFF]))
    fresh = SlabStore.open(str(tmp_path / "s"))
    with pytest.raises(CorruptShardError, match="crc"):
        fresh.read(0, 4)
    # a re-write replaces the shard and its recorded crc: reads heal
    fresh.write(0, arr)
    np.testing.assert_array_equal(fresh.read(0, 4), arr)


def test_store_verify_cache_bypassed_while_injecting(tmp_path):
    arr = np.full((3, 2), 7.0, np.float32)
    store = SlabStore.from_array(str(tmp_path / "s"), arr, slab=2)
    np.testing.assert_array_equal(store.read(0, 2), arr)  # verified+cached
    plan = FaultPlan(seed=2).add(
        "store/read", "corrupt", key=0, attempts=(0,)
    )
    with inject.activate(plan):
        with pytest.raises(CorruptShardError):
            store.read(0, 2)  # cache must not mask the injected flip
        np.testing.assert_array_equal(store.read(0, 2), arr)  # healed
    np.testing.assert_array_equal(store.read(0, 2), arr)


# --------------------------------------------------------------------- #
# streaming chaos scenarios (tentpole acceptance)
# --------------------------------------------------------------------- #
def test_streaming_transient_faults_bit_exact(
    rec, sino_store, tmp_path, fresh_obs
):
    """One transient read error + one corrupt shard + one injected NaN:
    the drain absorbs all three and the volume is BIT-IDENTICAL to the
    clean run's."""
    _, m = fresh_obs
    clean = reconstruct_streaming(
        rec, sino_store, str(tmp_path / "clean"), iters=6, y_slab=2
    )
    plan = (
        FaultPlan(seed=7)
        .add("store/read", "io_error", key=0, attempts=(0,))
        .add("store/read", "corrupt", key=4, attempts=(0,))
        .add("recon/solve", "nonfinite", key=1, attempts=(0,))
    )
    with inject.activate(plan) as h:
        chaos = reconstruct_streaming(
            rec, sino_store, str(tmp_path / "chaos"), iters=6, y_slab=2,
            retry=FAST,
        )
    assert chaos.complete and chaos.failed_slabs == []
    assert chaos.retries >= 3  # each fault cost at least one retry
    kinds = sorted(f[3] for f in h.fired)
    assert kinds == ["corrupt", "io_error", "nonfinite"]
    np.testing.assert_array_equal(
        chaos.volume.to_array(), clean.volume.to_array()
    )
    np.testing.assert_array_equal(chaos.resnorms, clean.resnorms)
    assert m.get("retries_total", site="stream/load") >= 1
    assert m.get("retries_total", site="stream/solve") >= 1
    assert m.get(
        "faults_injected_total", site="store/read", kind="io_error"
    ) == 1


def test_streaming_quarantines_poison_slab_and_resumes(
    rec, sino_store, tmp_path, fresh_obs
):
    """Retries exhausted on one shard: exactly that slab is quarantined,
    the rest completes bit-exact, and a resume (fault gone) finishes the
    volume identically to a clean run."""
    _, m = fresh_obs
    clean = reconstruct_streaming(
        rec, sino_store, str(tmp_path / "clean"), iters=6, y_slab=2
    )
    plan = FaultPlan(seed=11).add(
        "store/read", "io_error", key=4, attempts=None  # persistent
    )
    ck = str(tmp_path / "ck")
    with inject.activate(plan):
        part = reconstruct_streaming(
            rec, sino_store, str(tmp_path / "vol"), iters=6, y_slab=2,
            retry=FAST, ckpt_dir=ck,
        )
    assert part.failed_slabs == [4]  # exactly the poison slab
    assert not part.complete
    assert sorted(part.solved) == [0, 2, 6]  # drain continued past it
    assert part.retries > 0
    assert m.get("slabs_quarantined_total") == 1
    for j0, j1 in clean.volume.slabs():
        if j0 == 4:
            continue
        np.testing.assert_array_equal(
            part.volume.read(j0, j1), clean.volume.read(j0, j1)
        )
    # resume without the fault plan: the quarantined slab is re-attempted
    rest = reconstruct_streaming(
        rec, sino_store, str(tmp_path / "vol"), iters=6, y_slab=2,
        retry=FAST, ckpt_dir=ck,
    )
    assert rest.solved == [4] and rest.complete
    assert sorted(rest.skipped) == [0, 2, 6]
    np.testing.assert_array_equal(
        rest.volume.to_array(), clean.volume.to_array()
    )


def test_streaming_fail_fast_propagates(rec, sino_store, tmp_path):
    plan = FaultPlan(seed=1).add(
        "store/read", "io_error", key=0, attempts=None
    )
    with inject.activate(plan):
        with pytest.raises((InjectedIOError, Exception)) as e:
            reconstruct_streaming(
                rec, sino_store, str(tmp_path / "v"), iters=3, y_slab=2,
                fail_fast=True,
            )
    # the original error is reachable (PrefetchError wraps it)
    exc = e.value
    assert isinstance(exc, InjectedIOError) or isinstance(
        getattr(exc, "cause", exc.__cause__), InjectedIOError
    )


def test_streaming_thread_death_recovers_via_sync_retry(
    rec, sino_store, tmp_path, fresh_obs
):
    """A dying prefetch worker is not retryable in-worker: it surfaces
    as PrefetchError and the driver's one synchronous re-try heals it."""
    _, m = fresh_obs
    clean = reconstruct_streaming(
        rec, sino_store, str(tmp_path / "clean"), iters=5, y_slab=2
    )
    plan = FaultPlan(seed=5).add(
        "stream/load", "thread_death", key=1, attempts=(0,)
    )
    with inject.activate(plan):
        res = reconstruct_streaming(
            rec, sino_store, str(tmp_path / "v"), iters=5, y_slab=2,
            retry=FAST,
        )
    assert res.complete and res.failed_slabs == []
    assert res.retries >= 1
    assert m.get("retries_total", site="stream/slab") == 1
    np.testing.assert_array_equal(
        res.volume.to_array(), clean.volume.to_array()
    )


def test_streaming_nonfinite_escalates_one_rung(
    small_system, sino_store, tmp_path, fresh_obs
):
    """A quantized solve that keeps blowing up re-solves at f32 (the
    `when=` ctx match scopes the poison to the q8 rung) and the drain
    completes without quarantining."""
    _, m = fresh_obs
    _, _, plan = small_system
    rec_q8 = Reconstructor(
        plan, cfg=ReconConfig(precision="q8", comm_mode="rs", fuse=2)
    )
    fplan = FaultPlan(seed=9).add(
        "recon/solve", "nonfinite", key=2, attempts=None,
        when={"precision": "q8"},
    )
    with inject.activate(fplan):
        res = reconstruct_streaming(
            rec_q8, sino_store, str(tmp_path / "v"), iters=5, y_slab=2,
            retry=RetryPolicy(max_attempts=2, base_delay_s=0.0),
        )
    assert res.complete and res.failed_slabs == []
    assert res.escalated == [4]  # slab index 2 -> j0=4, solved at f32
    assert m.get("stream_escalations_total") == 1
    # f64 has no rung to escalate to: the same poison quarantines
    rec_f64 = Reconstructor(
        plan, cfg=ReconConfig(precision="double", comm_mode="rs", fuse=2)
    )
    fplan2 = FaultPlan(seed=9).add(
        "recon/solve", "nonfinite", key=2, attempts=None
    )
    with inject.activate(fplan2):
        res2 = reconstruct_streaming(
            rec_f64, sino_store, str(tmp_path / "v2"), iters=5, y_slab=2,
            retry=RetryPolicy(max_attempts=2, base_delay_s=0.0),
        )
    assert res2.failed_slabs == [4] and not res2.complete


def test_streaming_straggler_shrinks_lookahead(
    rec, sino_store, tmp_path, fresh_obs
):
    """A slow slab load (injected stall, way past the robust threshold)
    flags the straggler and the drain drops to synchronous prefetch --
    pinned by the gauge, the result stays bit-exact."""
    _, m = fresh_obs
    clean = reconstruct_streaming(
        rec, sino_store, str(tmp_path / "clean"), iters=4, y_slab=2
    )
    m.reset()  # ms-scale load jitter may flag the clean run too
    plan = FaultPlan(seed=4).add(
        "stream/load", "slow", key=2, attempts=(0,), delay_s=0.5
    )
    with inject.activate(plan):
        res = reconstruct_streaming(
            rec, sino_store, str(tmp_path / "v"), iters=4, y_slab=2,
            retry=FAST, straggler_k_mad=4.0,
        )
    assert res.complete
    assert 2 in res.stragglers
    assert m.get("stream_stragglers_total") == 1
    assert m.get("stream_prefetch_lookahead") == 0.0
    np.testing.assert_array_equal(
        res.volume.to_array(), clean.volume.to_array()
    )


# --------------------------------------------------------------------- #
# crash-resume property (satellite c)
# --------------------------------------------------------------------- #
def test_crash_resume_bit_exact_at_every_slab(rec, sino_store, tmp_path):
    """Kill the drain via injected preemption after EVERY slab k in
    turn; the resumed run must skip exactly the finished slabs and the
    final volume must be bit-identical to the uninterrupted run."""
    from repro.resil import InjectedPreemption

    base = reconstruct_streaming(
        rec, sino_store, str(tmp_path / "base"), iters=4, y_slab=2
    )
    n_slabs = len(base.volume.slabs())
    for k in range(n_slabs):
        out = str(tmp_path / f"v{k}")
        ck = str(tmp_path / f"ck{k}")
        plan = FaultPlan(seed=k).add(
            "stream/after_slab", "preempt", key=k, attempts=(0,)
        )
        with inject.activate(plan):
            with pytest.raises(InjectedPreemption):
                reconstruct_streaming(
                    rec, sino_store, out, iters=4, y_slab=2,
                    ckpt_dir=ck, checkpoint_every=1,
                )
        rest = reconstruct_streaming(
            rec, sino_store, out, iters=4, y_slab=2, ckpt_dir=ck
        )
        assert rest.complete
        assert len(rest.skipped) == k + 1  # slabs 0..k were durable
        assert len(rest.solved) == n_slabs - k - 1
        np.testing.assert_array_equal(
            rest.volume.to_array(), base.volume.to_array()
        )
        np.testing.assert_array_equal(rest.resnorms, base.resnorms)


# --------------------------------------------------------------------- #
# circuit breaker
# --------------------------------------------------------------------- #
def test_circuit_breaker_state_machine():
    t = {"now": 0.0}
    cb = CircuitBreaker(threshold=2, cooldown_s=30.0,
                        clock=lambda: t["now"])
    assert cb.allow("k")
    cb.record_failure("k")
    assert cb.allow("k")  # one failure: still closed
    cb.record_failure("k")
    assert not cb.allow("k")  # threshold: open
    assert cb.allow("other")  # per-key isolation
    t["now"] = 31.0
    assert cb.allow("k")  # cooldown lapsed: half-open probe
    cb.record_failure("k")  # probe failed: re-open immediately
    assert not cb.allow("k")
    t["now"] = 62.0
    assert cb.allow("k")
    cb.record_success("k")  # probe succeeded: closed, counters clear
    cb.record_failure("k")
    assert cb.allow("k")  # needs `threshold` consecutive fails again


# --------------------------------------------------------------------- #
# serve resilience
# --------------------------------------------------------------------- #
@pytest.fixture(scope="module")
def serve_bits(small_system):
    from repro.core.partition import PartitionConfig

    geo, a, _ = small_system
    pcfg = PartitionConfig(
        n_data=1, tile=4, rows_per_block=16, nnz_per_stage=16
    )
    rcfg = ReconConfig(precision="single", comm_mode="rs", fuse=2)
    x = phantom_slices(geo.n, Y, seed=21)
    sino = simulate_measurements(a, x, noise=0.01, seed=21)
    return geo, pcfg, rcfg, sino


def _spec(serve_bits, **kw):
    from repro.serve import JobSpec

    geo, pcfg, rcfg, sino = serve_bits
    kw.setdefault("iters", 3)
    kw.setdefault("y_slab", 4)
    kw.setdefault("sino", sino)
    return JobSpec(geo=geo, pcfg=pcfg, rcfg=rcfg, **kw)


def test_serve_retries_transient_load(serve_bits, tmp_path, fresh_obs):
    from repro.serve import ReconServer

    _, m = fresh_obs
    geo, pcfg, rcfg, sino = serve_bits
    store = SlabStore.from_array(str(tmp_path / "sino"), sino, slab=4)
    srv = ReconServer(2 * 2**30, workdir=str(tmp_path / "srv"))
    spec = _spec(serve_bits, sino=store, retry=FAST)
    plan = FaultPlan(seed=2).add(
        "store/read", "io_error", key=4, attempts=(0,)
    )
    with inject.activate(plan):
        job = srv.submit(spec)
        srv.drain()
    assert job.status == "done"
    assert job.telemetry.retries == 1
    assert m.get("retries_total", site="serve/load") == 1


def test_serve_deadline_fails_job_not_batch(serve_bits, tmp_path):
    from repro.serve import ReconServer

    srv = ReconServer(2 * 2**30, workdir=str(tmp_path / "srv"))
    doomed = srv.submit(_spec(serve_bits, deadline_s=0.0))
    mate = srv.submit(_spec(serve_bits, tenant="b"))
    srv.drain()
    assert doomed.status == "failed"
    assert "deadline" in doomed.error
    assert doomed.telemetry.error_type == "DeadlineExceeded"
    assert mate.status == "done"  # batch mate unaffected


def test_serve_circuit_breaker_trips_and_recovers(serve_bits, tmp_path):
    from repro.serve import ReconServer

    t = {"now": 0.0}
    srv = ReconServer(
        2 * 2**30, workdir=str(tmp_path / "srv"),
        breaker=CircuitBreaker(threshold=2, cooldown_s=30.0,
                               clock=lambda: t["now"]),
    )
    plan = FaultPlan(seed=1).add("serve/build", "error", attempts=None)
    with inject.activate(plan):
        for _ in range(2):  # two failed builds trip the breaker
            j = srv.submit(_spec(serve_bits))
            srv.drain()
            assert j.status == "failed"
            assert "plan build failed" in j.error
        rejected = srv.submit(_spec(serve_bits))
        srv.drain()
    assert rejected.status == "rejected_circuit"
    assert srv.stats()["rejected_circuit"] == 1
    # cooldown lapses and the fault is gone: the probe job closes it
    t["now"] = 31.0
    probe = srv.submit(_spec(serve_bits))
    srv.drain()
    assert probe.status == "done"
    after = srv.submit(_spec(serve_bits))
    srv.drain()
    assert after.status == "done"


# --------------------------------------------------------------------- #
# drift excludes retried attempts
# --------------------------------------------------------------------- #
def test_drift_measured_phases_skip_retried_spans():
    from repro.obs.drift import measured_phases

    events = [
        {"kind": "span", "name": "stream/solve", "t0": 0.0, "t1": 1.0,
         "parent": None, "attrs": {"retry": 0}},
        {"kind": "span", "name": "stream/solve", "t0": 1.0, "t1": 9.0,
         "parent": None, "attrs": {"retry": 1}},  # retried: excluded
        {"kind": "span", "name": "stream/load", "t0": 0.0, "t1": 0.5,
         "parent": None, "attrs": {}},
    ]
    ph = measured_phases(events)
    assert ph["solve"] == 1.0  # only the attempt-0 span counts
    assert ph["load"] == 0.5
