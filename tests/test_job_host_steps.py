"""The verifier's per-job host steps: `MessageCache`'s field /
device_hash / affine timings, trace_span on the profiler's clock, and
the benchmark readers of `lodestar_bls_job_host_step_seconds`."""

from pathlib import Path

import numpy as np
import pytest

from lodestar_tpu import observability as OB
from lodestar_tpu.bls.ingest import MessageCache
from lodestar_tpu.crypto import curves as C
from lodestar_tpu.crypto import fields as GT
from lodestar_tpu.crypto import hash_to_curve as HC
from lodestar_tpu.kernels import layout as LY
from lodestar_tpu.utils.metrics import BlsPoolMetrics, Registry

REPO = Path(__file__).resolve().parents[1]
MSG_STEPS = ("field", "device_hash", "affine")
ROOTS = [b"\x11" * 32, b"\x22" * 32]


@pytest.fixture
def tracing():
    tracer = OB.configure(enabled=True)
    tracer.clear()
    try:
        yield OB
    finally:
        OB.configure(enabled=False)
        OB.get_tracer().clear()


def _host_hash_dispatch():
    """A stand-in for the device hash: map each lane's u on the host and
    return Jacobian planes (Z != 1, Montgomery limbs) as the kernel
    does, memoized per distinct u (padding lanes repeat the last root)."""
    memo = {}

    def dispatch(_name, _fn, args):
        u00, u01, u10, u11, _sgn = (np.asarray(a) for a in args)
        cols = [[], [], [], [], [], []]
        for j in range(u00.shape[-1]):
            u = tuple(LY.from_limbs(a[:, j]) for a in (u00, u01, u10, u11))
            if u not in memo:
                q0 = HC.iso3_map(HC.map_to_curve_sswu_g2((u[0], u[1])))
                q1 = HC.iso3_map(HC.map_to_curve_sswu_g2((u[2], u[3])))
                x, y = HC.clear_cofactor_g2(C.affine_add(C.FP2_OPS, q0, q1))
                z = (3 + j, 5)
                z2 = GT.fp2_sqr(z)
                memo[u] = (
                    *GT.fp2_mul(x, z2), *GT.fp2_mul(y, GT.fp2_mul(z2, z)), *z
                )
            for col, v in zip(cols, memo[u]):
                col.append(v)
        planes = [LY.encode_batch(c) for c in cols]
        return planes, np.ones(u00.shape[-1], bool)

    return dispatch


def _steps(metrics):
    return {s: metrics.job_host_step.count(s) for s in MSG_STEPS}


def test_message_cache_times_three_steps_on_a_miss_only():
    metrics = BlsPoolMetrics(Registry())
    cache = MessageCache(dispatch=_host_hash_dispatch(), metrics=metrics)
    got = cache.get_many(ROOTS)
    assert got == [HC.hash_to_g2(r) for r in ROOTS]
    assert _steps(metrics) == dict.fromkeys(MSG_STEPS, 1)
    assert all(metrics.job_host_step.sum(s) > 0 for s in MSG_STEPS)
    assert cache.get_many(ROOTS[::-1]) == got[::-1]  # hits only
    assert _steps(metrics) == dict.fromkeys(MSG_STEPS, 1)
    assert cache.hits == 2 and cache.misses == 2
    # the verifier's own steps are not the cache's to observe
    assert metrics.job_host_step.label_values() == sorted(MSG_STEPS)


def test_message_cache_without_metrics_records_nothing(tracing):
    cache = MessageCache(dispatch=_host_hash_dispatch())
    assert cache.get_many(ROOTS[:1]) == [HC.hash_to_g2(ROOTS[0])]
    assert len(tracing.get_tracer()) == 0


def test_message_cache_spans_are_children_of_the_enclosing_span(tracing):
    metrics = BlsPoolMetrics(Registry())
    cache = MessageCache(dispatch=_host_hash_dispatch(), metrics=metrics)
    with tracing.trace_span("bls.begin_job"):
        cache.get_many(ROOTS)
    recs = {r.name: r for r in tracing.get_tracer().snapshot()}
    outer = recs["bls.begin_job"]
    names = ("bls.msg.field", "bls.msg.device_hash", "bls.msg.affine")
    for name in names:
        assert recs[name].parent_id == outer.span_id
    # in order, without overlap, inside the enclosing span
    starts = [recs[n].ts_us for n in names]
    ends = [recs[n].ts_us + recs[n].dur_us for n in names]
    assert outer.ts_us <= starts[0] and ends[-1] <= outer.ts_us + outer.dur_us
    assert all(e <= s for e, s in zip(ends, starts[1:]))
    # the span and the histogram come from one timing
    for name, step in zip(names, MSG_STEPS):
        assert abs(metrics.job_host_step.sum(step) * 1e6 - recs[name].dur_us) <= 1


STEPS = MSG_STEPS + ("encode", "launch")


def _stub_verifier():
    """A TpuBlsVerifier whose device programs are stubbed: the hash
    through the host stand-in, each_wire as an all-true verdict plane."""
    from lodestar_tpu.bls.pubkey_table import PubkeyTable
    from lodestar_tpu.bls.verifier import TpuBlsVerifier

    v = TpuBlsVerifier(PubkeyTable(capacity=8), metrics=BlsPoolMetrics(Registry()))
    v.messages._dispatch = _host_hash_dispatch()
    v._device_call = lambda _name, _fn, args: np.ones(args[-1].shape[0], bool)
    return v


def _wire_sets():
    from lodestar_tpu.bls.signature_set import WireSignatureSet

    sig = C.g2_compress(C.G2_GEN)
    # out of root order, so the job sorts its lanes
    return [WireSignatureSet.single(i, ROOTS[1 - i % 2], sig) for i in range(3)]


def test_begin_job_observes_each_step_once_and_within_host_phase():
    v = _stub_verifier()
    job = v.begin_job(_wire_sets(), batchable=False)
    assert job.unsort is not None
    steps = v.metrics.job_host_step
    assert {s: steps.count(s) for s in STEPS} == dict.fromkeys(STEPS, 1)
    assert sum(steps.sum(s) for s in STEPS) <= v.metrics.verify_seconds.sum("host")
    assert v.finish_job(job)
    # the roots are cached now: only encode and launch run again
    v.begin_job(_wire_sets(), batchable=False)
    assert {s: steps.count(s) for s in STEPS} == {
        **dict.fromkeys(MSG_STEPS, 1), "encode": 2, "launch": 2
    }


def test_begin_job_step_spans_are_children_of_begin_job(tracing):
    v = _stub_verifier()
    v.begin_job(_wire_sets(), batchable=False)
    recs = {r.name: r for r in tracing.get_tracer().snapshot()}
    outer = recs["bls.begin_job"].span_id
    for name in (
        "bls.msg.field", "bls.msg.device_hash", "bls.msg.affine",
        "bls.job.encode", "bls.job.launch",
    ):
        assert recs[name].parent_id == outer, name


def test_timed_span_observes_with_tracing_off():
    metrics = BlsPoolMetrics(Registry())
    assert not OB.enabled()
    with OB.timed_span("bls.job.encode", (metrics.job_host_step, "encode")):
        pass
    assert metrics.job_host_step.count("encode") == 1
    assert len(OB.get_tracer()) == 0


def _host_events(path, name):
    from jax.profiler import ProfileData

    out = []
    for plane in ProfileData.from_file(str(path)).planes:
        if plane.name.startswith("/device:"):
            continue
        for line in plane.lines:
            out.extend(ev.duration_ns for ev in line.events if ev.name == name)
    return out


@pytest.mark.parametrize("on", [True, False])
def test_trace_span_lands_on_the_profiler_clock(tmp_path, on):
    import time

    import jax

    OB.configure(enabled=on)
    OB.get_tracer().clear()
    name = f"test.shared_clock.{on}"
    try:
        jax.profiler.start_trace(str(tmp_path))
        try:
            with OB.trace_span(name):
                time.sleep(0.02)
        finally:
            jax.profiler.stop_trace()
        recs = [r for r in OB.get_tracer().snapshot() if r.name == name]
    finally:
        OB.configure(enabled=False)
        OB.get_tracer().clear()
    files = sorted(tmp_path.rglob("*.xplane.pb"))
    assert files
    durs = _host_events(files[-1], name)
    if not on:
        assert durs == [] and recs == []
        return
    assert len(durs) == 1 and len(recs) == 1
    assert abs(durs[0] / 1e3 - recs[0].dur_us) <= 1000


READERS = {
    "msg_field_ms.sync": "field",
    "msg_device_ms.sync": "device_hash",
    "msg_affine_ms.sync": "affine",
    "job_encode_ms.sync": "encode",
    "job_launch_ms.sync": "launch",
}


@pytest.mark.parametrize("name,step", sorted(READERS.items()))
def test_step_reader(name, step):
    from benchmark.run import load_reader

    reader = load_reader(REPO, name)
    metrics = BlsPoolMetrics(Registry())
    node = {"registry": metrics.registry}
    metrics.job_host_step.observe(step, 0.5)  # before the window
    before = reader.sample(node)
    for secs in (0.010, 0.020, 0.045):
        metrics.job_host_step.observe(step, secs)
    metrics.job_host_step.observe("other", 9.0)
    after = reader.sample(node)
    assert reader.read({"samples": {name: (before, after)}}) == pytest.approx(25.0)
    # nothing observed in the window
    assert reader.read({"samples": {name: (after, after)}}) is None
    # a program that does not time its steps
    bare = {"registry": Registry()}
    assert reader.read({"samples": {name: (reader.sample(bare), reader.sample(bare))}}) is None
