"""BlsVerificationPipeline: shape-bucketed accumulate-and-flush feed.

Stub-verifier (host-only) tests of the ISSUE 11 tentpole contract:
per-(kind, K, lane) accumulators, exact-N-bucket immediate flush,
oldest-set-anchored deadlines, priority lanes, set-based high-water
backpressure, flush-reason/fill-ratio observability, the escape hatch —
plus the acceptance oracle: mean bucket occupancy >= 2x the PR 10 flat
coalescer at equal p99 submit->verdict latency for block-critical sets.
"""

import threading
import time

import pytest

from lodestar_tpu.bls.pipeline import (
    BlsVerificationPipeline,
    create_bls_service,
)
from lodestar_tpu.bls.service import BlsVerifierService
from lodestar_tpu.bls.signature_set import SignatureSet, WireSignatureSet
from lodestar_tpu.bls.verifier import VerifyOptions
from lodestar_tpu.utils.metrics import BlsPoolMetrics

pytestmark = pytest.mark.smoke


class HandleStub:
    """IBlsVerifier with the begin/finish device-handle protocol; every
    begun job is recorded as (n_sets, batchable, t_begin)."""

    max_job_sets = 512

    class _Handle:
        def __init__(self, sets):
            self.sets = sets
            self.ok_big = True
            self.batch_retries = 0
            self.batch_sigs_success = len(sets)
            self.verdicts = None

    def __init__(self, finish_delay=0.0):
        self.metrics = BlsPoolMetrics()
        self.calls = []
        self.finish_delay = finish_delay
        self._lock = threading.Lock()

    def verify_signature_sets(self, sets, opts=None):
        with self._lock:
            self.calls.append((len(sets), True, time.perf_counter()))
        return True

    def begin_job(self, sets, batchable):
        with self._lock:
            self.calls.append((len(sets), batchable, time.perf_counter()))
        return self._Handle(sets)

    def finish_job(self, handle):
        if self.finish_delay:
            time.sleep(self.finish_delay)
        return True

    def close(self):
        pass


def single(i):
    return SignatureSet.single(i, ("m", i), ("s", i))


def wire_single(i):
    return WireSignatureSet.single(i, b"m" * 32, b"\xc0" + b"\x00" * 95)


def agg(i, k=10):
    return SignatureSet.aggregate(list(range(k)), ("m", i), ("s", i))


def submit(svc, s, priority=False):
    return svc.verify_signature_sets_async(
        [s], VerifyOptions(batchable=True, priority=priority)
    )


def test_exact_bucket_fill_flushes_immediately():
    stub = HandleStub()
    svc = BlsVerificationPipeline(stub, standard_wait_ms=10_000)
    t0 = time.perf_counter()
    futs = [submit(svc, single(i)) for i in range(128)]
    assert all(f.result(timeout=5) for f in futs)
    assert time.perf_counter() - t0 < 5  # did not wait out the window
    svc.close()
    assert [c[0] for c in stub.calls] == [128]
    stats = svc.flush_stats()
    assert len(stats) == 1 and stats[0]["reason"] == "fill"
    assert stats[0]["fill_ratio"] == 1.0
    assert stub.metrics.flush_reason.get("fill") == 1.0


def test_shape_buckets_accumulate_separately():
    """Wire vs decoded and K=1 vs K=16 sets land in DIFFERENT buckets:
    127 decoded singles + 1 aggregate total 128 but neither bucket
    fills, so nothing dispatches until the 128th single arrives."""
    stub = HandleStub()
    svc = BlsVerificationPipeline(stub, standard_wait_ms=8_000)
    futs = [submit(svc, single(i)) for i in range(127)]
    futs.append(submit(svc, agg(0)))
    futs.append(submit(svc, wire_single(0)))
    time.sleep(0.05)
    assert stub.calls == []  # three partial buckets, no fill
    futs.append(submit(svc, single(999)))  # singles bucket: 127 -> 128
    assert futs[0].result(timeout=5)
    time.sleep(0.1)
    svc.close()
    # exactly the singles bucket dispatched (one 128-set job); the
    # aggregate and wire buckets flushed only at close
    fill_calls = [c for c in stub.calls if c[0] == 128]
    assert len(fill_calls) == 1
    reasons = [r["reason"] for r in svc.flush_stats()]
    assert reasons.count("fill") == 1
    assert reasons.count("close") == 2


def test_deadline_flush_reports_reason_and_ratio():
    stub = HandleStub()
    svc = BlsVerificationPipeline(stub, standard_wait_ms=40)
    futs = [submit(svc, single(i)) for i in range(32)]
    assert all(f.result(timeout=5) for f in futs)
    svc.close()
    stats = svc.flush_stats()
    assert stats and stats[0]["reason"] == "deadline"
    assert stats[0]["sets"] == 32 and stats[0]["n_bucket"] == 128
    assert stats[0]["fill_ratio"] == pytest.approx(0.25)
    assert stub.metrics.flush_reason.get("deadline") >= 1.0
    assert stub.metrics.bucket_fill_ratio.count >= 1


def test_deadline_anchors_on_oldest_set():
    """Regression (ISSUE 11 satellite): staggered submits into one
    bucket must flush when the OLDEST set's window expires — a timer
    re-anchored on the newest submit would stretch p99 submit->flush
    beyond the window."""
    stub = HandleStub()
    svc = BlsVerificationPipeline(stub, standard_wait_ms=400)
    t0 = time.perf_counter()
    fa = submit(svc, single(0))
    time.sleep(0.35)  # inside the window
    fb = submit(svc, single(1))
    assert fa.result(timeout=5) and fb.result(timeout=5)
    elapsed = time.perf_counter() - t0
    svc.close()
    # correct anchor: ~0.40s from the first submit; re-anchored-on-B
    # would be ~0.75s
    assert elapsed < 0.62, f"flush took {elapsed:.3f}s — deadline re-anchored?"
    assert sum(c[0] for c in stub.calls) == 2


def test_critical_lane_is_not_starved_by_standard_fill():
    stub = HandleStub()
    svc = BlsVerificationPipeline(
        stub, critical_wait_ms=30, standard_wait_ms=10_000
    )
    std = [submit(svc, single(i)) for i in range(20)]
    crit = submit(svc, agg(0, k=3), priority=True)
    assert crit.result(timeout=5)  # short lane flushed by deadline
    assert all(not f.done() for f in std)  # standard lane still filling
    svc.close()
    lanes = {r["lane"]: r["reason"] for r in svc.flush_stats()}
    assert lanes.get("critical") == "deadline"
    assert lanes.get("standard") == "close"


def test_high_water_backpressure_counts_sets():
    stub = HandleStub()
    svc = BlsVerificationPipeline(
        stub, standard_wait_ms=150, high_water_sets=8
    )
    assert svc.can_accept_work()
    futs = [submit(svc, single(i)) for i in range(10)]
    assert not svc.can_accept_work()  # 10 buffered sets >= 8
    assert svc.pending_sets() == 10
    assert all(f.result(timeout=5) for f in futs)
    deadline = time.time() + 5
    while not svc.can_accept_work() and time.time() < deadline:
        time.sleep(0.01)
    assert svc.can_accept_work()  # drained below the high-water mark
    assert svc.pending_sets() == 0
    svc.close()


def test_escape_hatch_falls_back_to_flat_buffer(monkeypatch):
    monkeypatch.setenv("LODESTAR_TPU_BLS_PIPELINE", "0")
    svc = create_bls_service(HandleStub())
    assert type(svc) is BlsVerifierService
    svc.close()
    monkeypatch.setenv("LODESTAR_TPU_BLS_PIPELINE", "1")
    svc = create_bls_service(HandleStub())
    assert isinstance(svc, BlsVerificationPipeline)
    svc.close()


def test_non_batchable_jobs_bypass_buckets():
    stub = HandleStub()
    svc = BlsVerificationPipeline(stub, standard_wait_ms=10_000)
    fut = svc.verify_signature_sets_async([single(0)], VerifyOptions())
    assert fut.result(timeout=5)
    svc.close()
    assert stub.calls and stub.calls[0][0] == 1
    assert svc.flush_stats() == []  # never touched an accumulator


def _p99(latencies):
    xs = sorted(latencies)
    return xs[min(len(xs) - 1, int(0.99 * (len(xs) - 1)))] if xs else None


def test_occupancy_beats_flat_coalescer_at_equal_critical_p99():
    """ISSUE 11 acceptance oracle (fast stub): a trickling multi-subnet
    flood — 16 waves of 8 attestations, one block-critical aggregate on
    waves 0 and 8 — through BOTH feeds concurrently.

      - the PR 10 flat coalescer's 40 ms window flushes each wave as its
        own ~8-set job padded to the 128 bucket (occupancy ~0.06),
      - the pipeline accumulates the standard lane across waves to an
        exact 128 fill (occupancy 1.0 there), criticals riding the
        short lane,

    asserting set-weighted mean occupancy >= 2x the coalescer while the
    critical sets' p99 submit->verdict latency stays equal (the short
    lane undercuts the flat window)."""
    old_stub, new_stub = HandleStub(), HandleStub()
    old = BlsVerifierService(old_stub, buffer_wait_ms=40, max_buffered_sigs=512)
    new = BlsVerificationPipeline(
        new_stub, critical_wait_ms=30, standard_wait_ms=5_000
    )
    crit_lat = {"old": [], "new": []}
    futs = []

    def track(svc, s, bucket_key=None, priority=False):
        t0 = time.perf_counter()
        f = svc.verify_signature_sets_async(
            [s], VerifyOptions(batchable=True, priority=priority)
        )
        if bucket_key is not None:
            f.add_done_callback(
                lambda _f, t0=t0: crit_lat[bucket_key].append(
                    time.perf_counter() - t0
                )
            )
        futs.append(f)

    idx = 0
    for wave in range(16):
        for _ in range(8):  # 8 subnet atts per wave
            track(old, single(idx))
            track(new, single(idx), priority=False)
            idx += 1
        if wave in (0, 8):  # a block-critical aggregate
            track(old, agg(wave, k=3), bucket_key="old")
            track(new, agg(wave, k=3), bucket_key="new", priority=True)
        time.sleep(0.08)
    assert all(f.result(timeout=10) for f in futs)

    # flat-coalescer occupancy from its job records (sets per padded
    # 128-lane bucket, set-weighted)
    from lodestar_tpu.bls.pipeline import _pad_bucket

    old_jobs = old.job_timings()
    assert old_jobs, "flat coalescer dispatched nothing"
    occ_old = sum(j["sig_sets"] for j in old_jobs) / sum(
        _pad_bucket(j["sig_sets"]) for j in old_jobs
    )
    occ_new = new.mean_fill_ratio()
    old.close()
    new.close()
    assert occ_new is not None
    assert occ_new >= 2 * occ_old, (
        f"pipeline occupancy {occ_new:.3f} < 2x coalescer {occ_old:.3f}"
    )
    # the standard lane filled at least one exact bucket
    assert any(r["reason"] == "fill" for r in new.flush_stats())
    # equal (or better) p99 submit->verdict for block-critical sets;
    # generous slack absorbs scheduler jitter
    p99_old, p99_new = _p99(crit_lat["old"]), _p99(crit_lat["new"])
    assert p99_old is not None and p99_new is not None
    assert p99_new <= p99_old + 0.20, (
        f"critical p99 regressed: pipeline {p99_new:.3f}s vs "
        f"coalescer {p99_old:.3f}s"
    )


def test_close_rejects_buffered_jobs_and_records_close_flush():
    stub = HandleStub()
    svc = BlsVerificationPipeline(stub, standard_wait_ms=60_000)
    fut = submit(svc, single(0))
    svc.close()
    with pytest.raises(RuntimeError):
        fut.result(timeout=5)
    assert [r["reason"] for r in svc.flush_stats()] == ["close"]
    assert svc.pending_sets() == 0


def test_closed_rejection_settles_outside_lock(monkeypatch):
    """Regression (tpulint async-lock-safety): submitting to a closed
    service used to settle the job future INSIDE `with self._lock:`.
    set_exception runs done-callbacks synchronously, so a continuation
    (DeferredVerdict, aggregate-forward fan-out) would execute under
    the service Condition — re-entering the service deadlocks."""
    import lodestar_tpu.bls.service as service_mod

    violations = []
    locks = []

    class ProbeFuture(service_mod.Future):
        def set_exception(self, exc):
            if any(lk._is_owned() for lk in locks):
                violations.append(repr(exc))
            super().set_exception(exc)

    monkeypatch.setattr(service_mod, "Future", ProbeFuture)
    stub = HandleStub()
    svc = BlsVerificationPipeline(stub, standard_wait_ms=60_000)
    locks.append(svc._lock)
    svc.close()
    fut = svc.verify_signature_sets_async(
        [single(0)], VerifyOptions(batchable=True)
    )
    with pytest.raises(RuntimeError, match="verifier closed"):
        fut.result(timeout=5)
    # the continuation can even re-enter the service safely
    reentered = []
    fut2 = svc.verify_signature_sets_async(
        [single(1)], VerifyOptions(batchable=True)
    )
    fut2.add_done_callback(
        lambda f: reentered.append(
            svc.verify_signature_sets_async(
                [single(2)], VerifyOptions(batchable=True)
            )
        )
    )
    assert len(reentered) == 1
    assert violations == []


def test_bench_pipeline_probe_skip_semantics(capsys):
    """bench.py's `bls_pipeline_verified_atts_per_s` probe: any failure
    emits ONE machine-readable skip record (value null, skipped true) —
    never a traceback-only exit and never a measured-looking zero."""
    import json

    import bench

    class Broken:
        _use_rlc = True
        table = []  # len() == 0 -> the probe blows up deterministically

    bench._probe_pipeline(Broken())
    out = capsys.readouterr().out.strip().splitlines()
    recs = [json.loads(l) for l in out if l.startswith("{")]
    assert len(recs) == 1
    rec = recs[0]
    assert rec["metric"] == "bls_pipeline_verified_atts_per_s"
    assert rec["value"] is None and rec["skipped"] is True
    assert rec["unit"] == "atts/s"
    assert "pipeline-probe" in rec["error"]
    assert "phases" in rec


def test_bench_pipeline_probe_respects_rlc_escape_hatch(capsys):
    import json

    import bench

    class RlcOff:
        _use_rlc = False

    bench._probe_pipeline(RlcOff())
    recs = [
        json.loads(l)
        for l in capsys.readouterr().out.strip().splitlines()
        if l.startswith("{")
    ]
    assert len(recs) == 1 and recs[0]["skipped"] is True
    assert "RLC disabled" in recs[0]["error"]


def test_bench_pipeline_probe_happy_path_emits_record(capsys, monkeypatch):
    """The probe's gossip->processor->pipeline loop end-to-end with a
    stub device: one measured JSON record with throughput, occupancy,
    and critical-lane p99 populated."""
    import json

    import bench

    class FakeMessages:
        def get_many(self, roots):
            return [None] * len(roots)

    class FakeVerifier(HandleStub):
        _use_rlc = True
        table = list(range(512))
        messages = FakeMessages()

    monkeypatch.setattr(bench, "BENCH_PIPELINE_ATTS", 32)
    monkeypatch.setattr(bench, "BENCH_PIPELINE_SUBNETS", 4)
    monkeypatch.setattr(bench, "BENCH_PIPELINE_WAVES", 2)
    bench._probe_pipeline(FakeVerifier())
    recs = [
        json.loads(l)
        for l in capsys.readouterr().out.strip().splitlines()
        if l.startswith("{")
    ]
    assert len(recs) == 1
    rec = recs[0]
    assert rec["metric"] == "bls_pipeline_verified_atts_per_s"
    assert rec.get("skipped") is None and rec["value"] > 0
    assert rec["unit"] == "atts/s"
    assert 0 < rec["bucket_occupancy_mean"] <= 1.0
    assert rec["critical_p99_submit_to_verdict_s"] > 0
    assert sum(rec["flush_reasons"].values()) >= 1


def test_flush_emits_pipeline_span():
    from lodestar_tpu import observability as OB

    OB.configure(enabled=True)
    OB.get_tracer().clear()
    try:
        stub = HandleStub()
        svc = BlsVerificationPipeline(stub, standard_wait_ms=10_000)
        futs = [submit(svc, single(i)) for i in range(128)]
        assert all(f.result(timeout=5) for f in futs)
        svc.close()
        spans = [
            r
            for r in OB.get_tracer().snapshot()
            if r.name == "bls.pipeline.flush"
        ]
        assert spans, "no bls.pipeline.flush span recorded"
        attrs = spans[0].attrs
        assert attrs["reason"] == "fill" and attrs["sets"] == 128
        assert attrs["n_bucket"] == 128 and attrs["lane"] == "standard"
    finally:
        OB.configure(enabled=False)
        OB.get_tracer().clear()


def test_multi_set_job_crossing_a_boundary_flushes_prefix():
    """Review fix: a 3-set job arriving on a 127-set bucket overshoots
    the 128 boundary — the near-boundary jobs dispatch immediately
    (occupancy ~1.0) and the new job starts a fresh accumulation,
    instead of the whole bucket stranding until the deadline at half
    occupancy."""
    stub = HandleStub()
    svc = BlsVerificationPipeline(stub, standard_wait_ms=10_000)
    futs = [submit(svc, single(i)) for i in range(127)]
    fut3 = svc.verify_signature_sets_async(
        [single(200), single(201), single(202)],
        VerifyOptions(batchable=True),
    )
    assert all(f.result(timeout=5) for f in futs)  # prefix dispatched
    assert not fut3.done()  # the overshooting job keeps accumulating
    stats = svc.flush_stats()
    assert stats and stats[-1]["reason"] == "spill"
    assert stats[-1]["sets"] == 127
    assert stats[-1]["fill_ratio"] == pytest.approx(127 / 128)
    svc.close()


def test_job_exactly_filling_a_bucket_after_spill_flushes_immediately():
    """Review fix: after a spill the fresh accumulator re-runs the fill
    check, so a job that alone exactly fills a bucket dispatches now
    instead of waiting out the lane deadline."""
    stub = HandleStub()
    svc = BlsVerificationPipeline(stub, standard_wait_ms=10_000)
    futs = [submit(svc, single(i)) for i in range(100)]
    big = svc.verify_signature_sets_async(
        [single(1000 + i) for i in range(128)],
        VerifyOptions(batchable=True),
    )
    assert all(f.result(timeout=5) for f in futs)
    assert big.result(timeout=5)  # did NOT wait for the 10s window
    svc.close()
    reasons = [(r["reason"], r["sets"]) for r in svc.flush_stats()]
    assert ("spill", 100) in reasons and ("fill", 128) in reasons


def test_padded_lanes_splits_oversized_flushes():
    from lodestar_tpu.bls.pipeline import _padded_lanes

    assert _padded_lanes(1, 512) == 128
    assert _padded_lanes(128, 512) == 128
    assert _padded_lanes(130, 512) == 256
    assert _padded_lanes(512, 512) == 512
    assert _padded_lanes(513, 512) == 512 + 128  # 512-run + padded 1
    assert _padded_lanes(1024, 512) == 1024


def test_pending_sets_gauge_tracks_transitions():
    """Review fix: the lodestar_bls_pipeline_pending_sets gauge follows
    every transition (submit/resolve), not just flushes — an idle
    pipeline reads 0."""
    stub = HandleStub()
    svc = BlsVerificationPipeline(stub, standard_wait_ms=10_000)
    futs = [submit(svc, single(i)) for i in range(128)]
    assert all(f.result(timeout=5) for f in futs)
    deadline = time.time() + 5
    while stub.metrics.pipeline_pending_sets.value != 0 and time.time() < deadline:
        time.sleep(0.01)
    assert stub.metrics.pipeline_pending_sets.value == 0
    svc.close()


def test_job_cap_does_not_bind_before_set_high_water():
    """Review fix: backpressure is counted in SETS — 600 buffered
    single-set gossip jobs must NOT trip the inherited 512-job cap when
    the set high-water mark (1000) has headroom."""
    stub = HandleStub()
    svc = BlsVerificationPipeline(
        stub, standard_wait_ms=60_000, high_water_sets=1000
    )
    futs = [submit(svc, single(i)) for i in range(600)]
    del futs
    # 512 flushed on exact fills resolve; the 88-set remainder stays
    # buffered toward the (long) deadline
    deadline = time.time() + 5
    while svc.pending_sets() > 600 - 512 and time.time() < deadline:
        time.sleep(0.01)
    assert svc.pending_sets() == 600 - 512
    # top back up past the old job cap with fresh buffered jobs
    futs2 = [submit(svc, single(1000 + i)) for i in range(520)]
    del futs2
    assert svc.can_accept_work()  # < 1000 sets: still accepting
    svc.close()


# ---------------------------------------------------------------------------
# ISSUE 12: gossip handlers route block-critical verification through the
# service's critical lane (PR 11 ROADMAP leftover) + flush-record telemetry
# ---------------------------------------------------------------------------


class RawSpy:
    """Raw-verifier stand-in that counts synchronous calls."""

    def __init__(self):
        self.calls = 0

    def verify_signature_sets(self, sets, opts=None):
        self.calls += 1
        return True


def test_gossip_validators_priority_rides_critical_lane_under_flood():
    """Regression (ISSUE 12 satellite): a flood of subnet attestations
    filling the standard lane cannot starve an aggregate verification
    past the critical window.  GossipValidators with a wired service
    routes `priority=True` verifications through the pipeline's 25 ms
    lane; the raw verifier is NOT called for them."""
    from lodestar_tpu.chain.validation import GossipValidators

    stub = HandleStub()
    pipe = BlsVerificationPipeline(
        stub, critical_wait_ms=30, standard_wait_ms=10_000
    )
    raw = RawSpy()
    v = GossipValidators(chain=None, verifier=raw, bls_service=pipe)
    # the flood: 100 subnet attestations parked on the standard lane
    # (far from the 128 fill, 10 s window — they are going nowhere)
    std = [submit(pipe, single(i)) for i in range(100)]
    t0 = time.perf_counter()
    v._verify([agg(0, k=3)], priority=True)  # no exception = verified
    dt = time.perf_counter() - t0
    assert dt < 2.0, f"critical verification took {dt:.3f}s — starved?"
    assert raw.calls == 0  # routed through the service, not raw
    assert all(not f.done() for f in std)  # flood still parked
    # the flush that carried it rode the critical lane within window
    lanes = [r for r in pipe.flush_stats() if r["lane"] == "critical"]
    assert lanes and lanes[0]["oldest_wait_s"] < 1.0
    # non-priority verification still uses the raw verifier (subnet
    # attestations must not pay the standard lane's window here)
    v._verify([single(999)], priority=False)
    assert raw.calls == 1
    pipe.close()


def test_gossip_validators_without_service_keep_raw_path():
    from lodestar_tpu.chain.validation import GossipValidators

    raw = RawSpy()
    v = GossipValidators(chain=None, verifier=raw)
    v._verify([single(0)], priority=True)  # no service: raw fallback
    assert raw.calls == 1


def test_flush_records_carry_seq_and_oldest_wait():
    """The SLO engine consumes flush records incrementally by `seq` and
    judges the critical lane by `oldest_wait_s` (ISSUE 12)."""
    stub = HandleStub()
    # a window long enough that 128 submissions land inside it even on
    # a loaded test host (a 40 ms window let some flush on deadline)
    svc = BlsVerificationPipeline(stub, standard_wait_ms=400)
    futs = [submit(svc, single(i)) for i in range(128)]  # exact fill
    assert all(f.result(timeout=5) for f in futs)
    fut = submit(svc, single(999))  # deadline flush
    assert fut.result(timeout=5)
    svc.close()
    stats = svc.flush_stats()
    assert [r["seq"] for r in stats] == sorted(
        r["seq"] for r in stats
    ) and len({r["seq"] for r in stats}) == len(stats)
    fill = next(r for r in stats if r["reason"] == "fill")
    assert 0.0 <= fill["oldest_wait_s"] < 5.0
    deadline_rec = next(r for r in stats if r["reason"] == "deadline")
    # the deadline flush waited out (about) the 400 ms window
    assert deadline_rec["oldest_wait_s"] >= 0.35


def test_bench_failure_records_carry_slo_snapshot_and_flight_record(
    capsys, monkeypatch, tmp_path
):
    """ISSUE 12 acceptance: a bench skip/failure record carries the SLO
    snapshot and a flight-record path, so a future r06 backend-init
    failure leaves a forensic artifact instead of a bare null."""
    import json
    import os

    import bench

    monkeypatch.setenv("BENCH_FLIGHTREC_DIR", str(tmp_path / "fr"))
    monkeypatch.setattr(bench, "_FLIGHT_RECORDER", None)
    bench._emit_failure("device-check", "stub backend death")
    out = capsys.readouterr().out.strip().splitlines()
    rec = json.loads(out[-1])
    assert rec["skipped"] is True and rec["value"] is None
    assert "slo" in rec and "breaches" in rec["slo"]
    assert rec["flight_record"] is not None
    assert os.path.isdir(rec["flight_record"])
    from lodestar_tpu.observability.flight_recorder import load_bundle

    bundle = load_bundle(rec["flight_record"])
    assert bundle["manifest"]["reason"] == "bench.device-check"
    assert "stub backend death" in bundle["manifest"]["context"]["detail"]
    # the bundle carries the phase timings + SLO counters
    assert "phases.json" in bundle["files"]
    assert "slo.json" in bundle["files"]
    # traces parse even with tracing off (empty event list)
    assert isinstance(
        bundle["files"]["trace.json"]["traceEvents"], list
    )


def test_bench_failure_without_recorder_env_writes_nothing(
    capsys, monkeypatch, tmp_path
):
    import json

    import bench

    monkeypatch.delenv("BENCH_FLIGHTREC_DIR", raising=False)
    monkeypatch.setattr(bench, "_FLIGHT_RECORDER", None)
    monkeypatch.setattr(bench, "_FLIGHTREC_ON", False)
    monkeypatch.chdir(tmp_path)
    bench._emit_failure("run", "in-process stub failure")
    rec = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert rec["flight_record"] is None
    assert "slo" in rec  # the snapshot still attaches
    assert not (tmp_path / "flightrec_bench").exists()


def test_bench_measured_records_carry_slo_snapshot(capsys, monkeypatch):
    import json

    import bench

    class FakeMessages:
        def get_many(self, roots):
            return [None] * len(roots)

    class FakeVerifier(HandleStub):
        _use_rlc = True
        table = list(range(512))
        messages = FakeMessages()

    monkeypatch.setattr(bench, "BENCH_PIPELINE_ATTS", 16)
    monkeypatch.setattr(bench, "BENCH_PIPELINE_SUBNETS", 4)
    monkeypatch.setattr(bench, "BENCH_PIPELINE_WAVES", 1)
    bench._probe_pipeline(FakeVerifier())
    recs = [
        json.loads(l)
        for l in capsys.readouterr().out.strip().splitlines()
        if l.startswith("{")
    ]
    assert len(recs) == 1 and recs[0].get("skipped") is None
    assert "slo" in recs[0] and "breaches" in recs[0]["slo"]


def test_lone_critical_job_flushes_immediately_when_idle():
    """Review fix: a critical job submitted into an otherwise-idle
    pipeline must NOT serialize the full lane window — the synchronous
    gossip loop verifies aggregates one at a time, and a pure 25 ms
    wait per message would add >1 s/slot of idle to the scheduler."""
    stub = HandleStub()
    svc = BlsVerificationPipeline(
        stub, critical_wait_ms=5_000, standard_wait_ms=10_000
    )
    t0 = time.perf_counter()
    fut = submit(svc, agg(0, k=3), priority=True)
    assert fut.result(timeout=5)
    dt = time.perf_counter() - t0
    assert dt < 1.0, f"idle critical job waited {dt:.3f}s (lane window?)"
    stats = svc.flush_stats()
    assert stats and stats[0]["reason"] == "idle"
    assert stats[0]["lane"] == "critical"
    # with standard work ACCUMULATING, criticals coalesce toward the
    # deadline as before (the idle fast path must not fire under load)
    futs = [submit(svc, single(i)) for i in range(8)]
    crit = submit(svc, agg(1, k=3), priority=True)
    time.sleep(0.05)
    assert not crit.done()  # parked on the (long) critical deadline
    svc.close()
    del futs
    lanes = [r for r in svc.flush_stats() if r["lane"] == "critical"]
    assert [r["reason"] for r in lanes] == ["idle", "close"]


# -- ISSUE 14: device-fault recovery probe ----------------------------------


class FaultableProbeVerifier:
    """bench breaker-probe stub: the supervised begin/finish protocol
    over a no-crypto oracle whose device leg goes through
    `_device_call` — the exact seam the probe wraps to inject the
    mid-flood fault — with a real DeviceSupervisor + auto re-probe."""

    max_job_sets = 512
    _use_rlc = True
    table = list(range(64))

    class _Messages:
        def get_many(self, roots):
            return [None] * len(roots)

    class _Handle:
        def __init__(self, sets, host):
            self.sets = sets
            self.ok_big = True
            self.batch_retries = 0
            self.batch_sigs_success = len(sets)
            self.verdicts = None
            self.host = host

    def __init__(self):
        from lodestar_tpu.bls.supervisor import DeviceSupervisor

        self.metrics = BlsPoolMetrics()
        self.messages = self._Messages()
        self.supervisor = DeviceSupervisor(
            registry=self.metrics.registry,
            enabled=True,
            auto_probe=True,
            backoff_initial_s=0.05,
            canary=self._canary,
        )

    def _device_call(self, name, fn, args):
        return fn(*args)

    def _canary(self):
        return bool(self._device_call("canary", lambda: True, ()))

    def begin_job(self, sets, batchable):
        return self._Handle(sets, host=not self.supervisor.device_allowed())

    def finish_job(self, handle):
        from lodestar_tpu.bls.supervisor import classify_failure

        if handle.host:
            self.supervisor.note_host_fallback(len(handle.sets))
            return True  # host oracle: all probe atts are valid
        try:
            self._device_call("each", lambda: True, ())
            self.supervisor.record_success()
            return True
        except Exception as e:  # noqa: BLE001 — the production seam
            self.supervisor.record_failure(
                classify_failure(e), "finish_job", str(e)
            )
            return True  # host fallback verdict

    def verify_signature_sets(self, sets, opts=None):
        job = self.begin_job(list(sets), True)
        return self.finish_job(job)

    def can_accept_work(self):
        return True

    def close(self):
        self.supervisor.close()


def test_bench_breaker_probe_measures_recovery(capsys, monkeypatch):
    """ISSUE 14 satellite: the bls_device_fault_recovery_seconds probe
    injects a fault mid-flood, loses zero verdicts, and reports the
    trip->device-verdict wall clock once the auto canary restores the
    device path."""
    import json

    import bench

    monkeypatch.setattr(bench, "BENCH_BREAKER_FLOOD_ATTS", 32)
    v = FaultableProbeVerifier()
    bench._probe_breaker_recovery(v)
    recs = [
        json.loads(l)
        for l in capsys.readouterr().out.strip().splitlines()
        if l.startswith("{")
    ]
    assert len(recs) == 1, recs
    rec = recs[0]
    assert rec["metric"] == "bls_device_fault_recovery_seconds"
    assert rec.get("skipped") is None, rec
    assert rec["unit"] == "s" and rec["value"] > 0
    assert rec["breaker_trips"] == 1
    assert rec["time_in_degraded_s"] > 0
    assert rec["breaker"]["trips"] >= 1  # the per-record snapshot
    v.close()


def test_bench_breaker_probe_skips_when_disabled(capsys):
    import json

    import bench

    class NoSup:
        supervisor = None

    bench._probe_breaker_recovery(NoSup())
    recs = [
        json.loads(l)
        for l in capsys.readouterr().out.strip().splitlines()
        if l.startswith("{")
    ]
    assert len(recs) == 1
    assert recs[0]["metric"] == "bls_device_fault_recovery_seconds"
    assert recs[0]["skipped"] is True
    assert "disabled" in recs[0]["error"]


def test_bench_records_carry_breaker_snapshot(capsys, monkeypatch):
    """Every bench record — measured and skipped — carries the
    `breaker` snapshot (state, trips, time-in-degraded)."""
    import json

    import bench

    monkeypatch.delenv("BENCH_FLIGHTREC_DIR", raising=False)
    monkeypatch.setattr(bench, "_FLIGHTREC_ON", False)
    bench._emit_failure("backend-init-probe", "stub death")
    rec = json.loads(capsys.readouterr().out.strip().splitlines()[0])
    assert rec["breaker"]["state"] in ("closed", "half_open", "open")
    assert "trips" in rec["breaker"]
    assert "time_in_degraded_s" in rec["breaker"]
