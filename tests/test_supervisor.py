"""Device circuit breaker (bls/supervisor.py) — state machine,
failure classification, watchdog, canary re-probe, and the ISSUE 14
verdict-equivalence property: a breaker trip landing at ANY pipeline
stage boundary leaves every verdict bit-identical to the device path,
for in-flight and newly submitted sets alike.
"""

import random
import threading
import time

import numpy as np
import pytest

from lodestar_tpu.bls.pipeline import BlsVerificationPipeline
from lodestar_tpu.bls.signature_set import WireSignatureSet
from lodestar_tpu.bls.supervisor import (
    OUTCOME_BACKEND_INIT,
    OUTCOME_BAD_OUTPUT,
    OUTCOME_ERROR,
    OUTCOME_TIMEOUT,
    STATE_CLOSED,
    STATE_OPEN,
    BadDeviceOutput,
    DeviceSupervisor,
    DeviceTimeout,
    breaker_snapshot,
    check_verdict_plane,
    classify_failure,
)
from lodestar_tpu.bls.verifier import VerifyOptions
from lodestar_tpu.utils.metrics import BlsPoolMetrics

from chaos.harness import ChaosVerifier, FakeClock, chaos_sig

pytestmark = pytest.mark.smoke


def make_supervisor(**kw):
    metrics = BlsPoolMetrics()
    fake = FakeClock()
    kw.setdefault("registry", metrics.registry)
    kw.setdefault("clock", fake)
    kw.setdefault("auto_probe", False)
    kw.setdefault("enabled", True)
    kw.setdefault("rng", random.Random(0))
    return DeviceSupervisor(**kw), fake, metrics


# ---------------------------------------------------------------------------
# classification
# ---------------------------------------------------------------------------


def test_failure_classification():
    assert classify_failure(DeviceTimeout("x")) == OUTCOME_TIMEOUT
    assert classify_failure(BadDeviceOutput("x")) == OUTCOME_BAD_OUTPUT
    assert (
        classify_failure(RuntimeError("TPU backend UNAVAILABLE"))
        == OUTCOME_BACKEND_INIT
    )
    assert (
        classify_failure(RuntimeError("failed to initialize backend"))
        == OUTCOME_BACKEND_INIT
    )
    assert (
        classify_failure(RuntimeError("TPU device reset by peer"))
        == OUTCOME_BACKEND_INIT
    )
    assert classify_failure(ValueError("shape mismatch")) == OUTCOME_ERROR


def test_check_verdict_plane():
    ok = check_verdict_plane(np.ones(8, bool), 8)
    assert ok.shape == (8,)
    with pytest.raises(BadDeviceOutput):
        check_verdict_plane(np.ones(3, bool), 8)
    with pytest.raises(BadDeviceOutput):
        check_verdict_plane(np.float64(1.0), 1)


# ---------------------------------------------------------------------------
# state machine
# ---------------------------------------------------------------------------


def test_threshold_trips_and_canary_recovers():
    probes = {"n": 0, "ok": False}

    def canary():
        probes["n"] += 1
        return probes["ok"]

    sup, fake, metrics = make_supervisor(
        canary=canary, failure_threshold=2, backoff_initial_s=1.0
    )
    trips, recoveries = [], []
    sup.on_trip = trips.append
    sup.on_recover = recoveries.append

    sup.record_failure(OUTCOME_ERROR, "finish_job", "boom")
    assert sup.state == STATE_CLOSED  # below threshold
    sup.record_success()
    sup.record_failure(OUTCOME_ERROR, "finish_job", "boom")
    assert sup.state == STATE_CLOSED  # success reset the streak
    sup.record_failure(OUTCOME_ERROR, "finish_job", "boom")
    assert sup.state == STATE_OPEN and sup.trip_count == 1
    assert trips and trips[0]["trip_count"] == 1
    assert not sup.device_allowed() and sup.is_open()

    # not due yet: poll is a no-op
    sup.poll()
    assert probes["n"] == 0 and sup.state == STATE_OPEN
    # due, but the canary fails: backoff doubles
    fake.advance(2.0)
    sup.poll()
    assert probes["n"] == 1 and sup.state == STATE_OPEN
    st1 = sup.status()
    assert st1["next_probe_in_s"] > 1.0  # doubled (with jitter >= 1.5)
    # eventually the canary passes: breaker closes, degraded time books
    probes["ok"] = True
    fake.advance(10.0)
    sup.poll()
    assert sup.state == STATE_CLOSED and sup.device_allowed()
    assert recoveries and recoveries[0]["degraded_s"] > 0
    assert sup.time_in_degraded_s() == pytest.approx(12.0)
    assert metrics.registry.get(
        "lodestar_bls_breaker_degraded_seconds_total"
    ).value == pytest.approx(12.0)


def test_backoff_is_jittered_and_capped():
    sup, fake, _ = make_supervisor(
        canary=lambda: False,
        backoff_initial_s=1.0,
        backoff_max_s=4.0,
        rng=random.Random(3),
    )
    sup.record_failure(OUTCOME_ERROR, "x")
    waits = []
    for _ in range(6):
        fake.advance(1000.0)
        sup.poll()
        waits.append(sup.status()["next_probe_in_s"])
    # jitter stays inside +/- 25%, and the cap holds
    for w in waits:
        assert w <= 4.0 * 1.25
    assert waits[-1] >= 4.0 * 0.75
    assert len(set(waits)) > 1  # actually jittered


def test_disabled_supervisor_is_a_passthrough():
    sup, _, _ = make_supervisor(enabled=False)
    sup.record_failure(OUTCOME_ERROR, "x")
    assert sup.device_allowed() and not sup.is_open()
    assert sup.run_guarded(lambda: 42) == 42
    assert sup.status()["enabled"] is False


def test_breaker_env_escape_hatch(monkeypatch):
    from lodestar_tpu.bls.supervisor import breaker_enabled_env

    monkeypatch.setenv("LODESTAR_TPU_BLS_BREAKER", "0")
    assert breaker_enabled_env() is False
    sup = DeviceSupervisor(registry=BlsPoolMetrics().registry)
    sup.record_failure(OUTCOME_ERROR, "x")
    assert sup.device_allowed()  # supervision off
    monkeypatch.setenv("LODESTAR_TPU_BLS_BREAKER", "1")
    assert breaker_enabled_env() is True


def test_run_guarded_watchdog_times_out_and_recovers():
    sup, _, _ = make_supervisor(job_deadline_s=0.1)
    release = threading.Event()
    with pytest.raises(DeviceTimeout):
        sup.run_guarded(lambda: release.wait(timeout=10.0), "hang")
    release.set()  # let the abandoned worker die
    # the poisoned executor was replaced: the next call works
    assert sup.run_guarded(lambda: "fine") == "fine"
    sup.close()


def test_breaker_snapshot_aggregates_live_supervisors():
    sup, fake, _ = make_supervisor()
    snap = breaker_snapshot()
    assert snap["supervisors"] >= 1 and snap["state"] in (
        "closed", "half_open", "open",
    )
    sup.record_failure(OUTCOME_ERROR, "x")
    fake.advance(5.0)
    snap = breaker_snapshot()
    assert snap["state"] == "open" and snap["trips"] >= 1
    assert snap["time_in_degraded_s"] >= 5.0
    sup.close()


# ---------------------------------------------------------------------------
# verifier integration
# ---------------------------------------------------------------------------


def _chaos_world(deadline=None, seed=0, threshold=1):
    metrics = BlsPoolMetrics()
    fake = FakeClock()
    sup = DeviceSupervisor(
        registry=metrics.registry,
        clock=fake,
        auto_probe=False,
        enabled=True,
        job_deadline_s=deadline,
        failure_threshold=threshold,
        rng=random.Random(seed),
    )
    v = ChaosVerifier(supervisor=sup, metrics=metrics)
    return v, sup, fake


def test_open_breaker_routes_individually_through_host():
    v, sup, _ = _chaos_world()
    root = b"r" * 32
    sets = [
        WireSignatureSet.single(1, root, chaos_sig(root, (1,))),
        WireSignatureSet.single(2, root, b"\x01" * 96),
    ]
    sup.record_failure(OUTCOME_ERROR, "x")
    assert v.verify_signature_sets_individually(sets) == [True, False]
    assert v.host_sets == 2 and v.device_jobs == 0


def test_begin_job_fault_degrades_without_losing_the_job():
    v, sup, _ = _chaos_world()
    root = b"q" * 32
    sets = [WireSignatureSet.single(3, root, chaos_sig(root, (3,)))]
    v.fault = {"begin": "raise"}
    job = v.begin_job(sets, True)
    assert job.host_mode is True
    assert sup.state == STATE_OPEN
    assert v.finish_job(job) is True
    assert list(job.verdicts) == [True]


def test_aggregate_seam_records_failure_and_falls_back(monkeypatch):
    v, sup, _ = _chaos_world()
    monkeypatch.setattr(v, "_use_agg_device", lambda: True)

    def boom(groups):
        raise RuntimeError("UNAVAILABLE: TPU backend")

    monkeypatch.setattr(v, "_aggregate_wire_device", boom)
    out = v.aggregate_wire_signatures([[b"\x01" * 96]])
    # fake bytes don't decompress: host fallback reports None (caller
    # dispatches unaggregated) — the point is no exception escaped
    assert out == [None]
    assert sup.state == STATE_OPEN
    assert sup.status()["last_failure"]["seam"] == "agg_g2_sum"
    assert (
        sup.status()["last_failure"]["outcome"] == OUTCOME_BACKEND_INIT
    )
    # open breaker: the device leg is not attempted at all
    calls = {"n": 0}
    monkeypatch.setattr(
        v, "_aggregate_wire_device",
        lambda groups: calls.__setitem__("n", calls["n"] + 1),
    )
    v.aggregate_wire_signatures([[b"\x01" * 96]])
    assert calls["n"] == 0


def test_service_breaker_status_passthrough():
    from lodestar_tpu.bls.service import BlsVerifierService

    v, sup, _ = _chaos_world()
    svc = BlsVerifierService(v)
    try:
        st = svc.breaker_status()
        assert st is not None and st["state"] == "closed"
    finally:
        svc.close()

    class Bare:
        metrics = BlsPoolMetrics()

        def close(self):
            pass

    svc2 = BlsVerifierService(Bare())
    try:
        assert svc2.breaker_status() is None
    finally:
        svc2.close()


# ---------------------------------------------------------------------------
# ISSUE 14 satellite: verdict equivalence under mid-job breaker trips
# ---------------------------------------------------------------------------

STAGES = ("open_before_submit", "begin", "finish", "output", "hang")


def _random_messages(rng, n):
    msgs = []
    for _ in range(n):
        root = bytes(rng.integers(0, 256, 32, dtype=np.uint8).tobytes())
        vi = int(rng.integers(0, 64))
        valid = bool(rng.random() > 0.3)
        sig = chaos_sig(root, (vi,)) if valid else b"\x77" * 96
        msgs.append((WireSignatureSet.single(vi, root, sig), valid))
    return msgs


@pytest.mark.parametrize("stage", STAGES)
def test_verdict_equivalence_under_mid_job_trip(stage):
    """Randomized property: whatever pipeline stage boundary the trip
    lands on, every in-flight and newly submitted set resolves with the
    verdict the device path would have produced (the oracle truth)."""
    rng = np.random.default_rng(hash(stage) % (2**32))
    trials = 1 if stage == "hang" else 2  # hang leaves a parked thread
    expected_outcome = {
        "begin": OUTCOME_ERROR,
        "finish": OUTCOME_BACKEND_INIT,
        "output": OUTCOME_BAD_OUTPUT,
        "hang": OUTCOME_TIMEOUT,
    }
    for trial in range(trials):
        n = int(rng.integers(6, 40))
        msgs = _random_messages(rng, n)
        expected = [valid for _, valid in msgs]
        v, sup, _ = _chaos_world(
            deadline=(0.2 if stage == "hang" else None),
            seed=trial,
        )
        pipe = BlsVerificationPipeline(
            v, preagg=False, standard_wait_ms=20.0
        )
        try:
            futs = []
            half = n // 2
            for i, (ws, _valid) in enumerate(msgs):
                if i == half:
                    if stage == "open_before_submit":
                        sup.record_failure(OUTCOME_ERROR, "test", "forced")
                    elif stage == "begin":
                        v.fault = {"begin": "raise"}
                    elif stage == "finish":
                        v.fault = {"finish": "backend"}
                    elif stage == "output":
                        v.fault = {"output": "truncated"}
                    elif stage == "hang":
                        v.fault = {"finish": "hang"}
                futs.append(
                    pipe.verify_signature_sets_async(
                        [ws], VerifyOptions(batchable=True)
                    )
                )
            got = [f.result(timeout=60) for f in futs]
            assert got == expected, (stage, trial)
            assert sup.trip_count >= 1, (stage, trial)
            if stage in expected_outcome:
                assert (
                    sup.status()["last_failure"]["outcome"]
                    == expected_outcome[stage]
                ), sup.status()["last_failure"]
        finally:
            v.heal()
            pipe.close()


def test_breaker_metrics_registered_with_lodestar_prefix():
    v, sup, _ = _chaos_world()
    reg = v.metrics.registry
    for name in (
        "lodestar_bls_breaker_state",
        "lodestar_bls_breaker_trips_total",
        "lodestar_bls_breaker_failures_total",
        "lodestar_bls_breaker_probes_total",
        "lodestar_bls_breaker_degraded_seconds_total",
        "lodestar_bls_breaker_host_fallback_sets_total",
    ):
        assert reg.get(name) is not None, name
    v.fault = {"finish": "raise"}
    job = v.begin_job(
        [WireSignatureSet.single(0, b"m" * 32, chaos_sig(b"m" * 32, (0,)))],
        True,
    )
    v.finish_job(job)
    assert reg.get("lodestar_bls_breaker_trips_total").value == 1
    assert (
        reg.get("lodestar_bls_breaker_failures_total").get("error") == 1
    )
    assert (
        reg.get("lodestar_bls_breaker_host_fallback_sets_total").value == 1
    )
    # wall-time watchdog defaults stay OFF on the CPU test backend (a
    # first-dispatch compile must never be classified as a hang)
    assert DeviceSupervisor(
        registry=BlsPoolMetrics().registry, enabled=True
    ).job_deadline_s is None


def test_run_guarded_does_not_count_compile_windows():
    """A shape's first dispatch compiles for minutes on the chip: time
    spent inside a compile window extends the deadline of any guarded
    call it overlaps, while the same wait outside one is a hang."""
    sup, _, _ = make_supervisor(job_deadline_s=0.2)

    def first_dispatch():
        with sup.compile_window():
            time.sleep(0.5)
        return "compiled"

    assert sup.run_guarded(first_dispatch, "first") == "compiled"
    release = threading.Event()
    with pytest.raises(DeviceTimeout):
        sup.run_guarded(lambda: release.wait(timeout=10.0), "hang")
    release.set()
    assert sup.compile_seconds() >= 0.5
    sup.close()


def test_verifier_opens_a_compile_window_per_new_shape():
    """TpuBlsVerifier._device_call wraps only the first dispatch of each
    (entry, argument shapes) in the breaker's compile window."""
    import contextlib

    import jax.numpy as jnp

    v = ChaosVerifier(capacity=4)
    opened = []
    inner = v.supervisor.compile_window

    @contextlib.contextmanager
    def window():
        opened.append(1)
        with inner():
            yield

    v.supervisor.compile_window = window
    for width in (2, 2, 3):
        v._device_call("x", lambda a: a + 1, (jnp.ones(width),))
    assert len(opened) == 2
    v.close()


def test_message_hash_dispatches_through_the_verifier():
    """The device hash of signing roots is one more verifier entry: the
    message cache dispatches it through _device_call, so one tracker
    opens the compile window for every entry's first shape."""
    from lodestar_tpu.bls.ingest import MessageCache

    v = ChaosVerifier(capacity=4)
    assert v.messages._dispatch == v._device_call
    assert MessageCache()._dispatch("x", lambda a: a + 1, (1,)) == 2
    v.close()


def test_run_guarded_concurrent_calls_have_independent_deadlines():
    """Review fix: thread-per-call — a guarded call queued while
    another (healthy but slow) call runs must NOT have that wait
    counted against its own deadline."""
    sup, _, _ = make_supervisor(job_deadline_s=0.25)
    results = []

    def slow_ok():
        time.sleep(0.15)
        return "a"

    t = threading.Thread(
        target=lambda: results.append(sup.run_guarded(slow_ok, "a"))
    )
    t.start()
    time.sleep(0.02)  # overlap: a shared 1-worker executor would queue
    assert sup.run_guarded(slow_ok, "b") == "a"
    t.join()
    assert results == ["a"]
    sup.close()


def test_abandoned_device_thread_cannot_corrupt_host_verdicts():
    """Review fix: the guarded device finish runs on a shallow CLONE —
    an orphan thread that out-lives its watchdog deadline and then
    writes (wrong) verdicts mutates only the clone, never the job the
    service reads."""
    v, sup, _ = _chaos_world(deadline=0.1)
    root = b"z" * 32
    sets = [WireSignatureSet.single(1, root, chaos_sig(root, (1,)))]
    release = threading.Event()

    def evil_finish(job):
        release.wait(timeout=5.0)  # hang past the watchdog...
        job.verdicts = np.zeros(len(job.sets), bool)  # ...then lie
        return False

    v._finish_job = evil_finish
    job = v.begin_job(sets, True)
    assert v.finish_job(job) is True  # host fallback: the set IS valid
    assert list(job.verdicts) == [True]
    assert sup.status()["last_failure"]["outcome"] == OUTCOME_TIMEOUT
    release.set()
    time.sleep(0.3)  # let the orphan complete its late mutation
    assert list(job.verdicts) == [True]  # it only touched the clone
