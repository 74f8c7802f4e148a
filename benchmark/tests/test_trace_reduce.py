"""The trace reduction, on a hand-made event list and on a slice of a
recorded chip trace."""

from pathlib import Path

from benchmark import trace_reduce as TR

DEV = "/device:TPU:0"
HOST = "/host:CPU"


def _events():
    ms = 1_000_000
    return [
        (HOST, "python", "bench.window", 0, 100 * ms),
        (HOST, "python", "bench.await", 0, 100 * ms),
        (HOST, "t1", "bench.begin_job", 10 * ms, 20 * ms),
        (DEV, "XLA Ops", "miller", 5 * ms, 5 * ms),       # busy 5-10
        (DEV, "XLA Ops", "final", 8 * ms, 4 * ms),        # overlaps: busy to 12
        (DEV, "XLA Ops", "miller", 40 * ms, 10 * ms),     # busy 40-50
        (DEV, "XLA Ops", "outside", 150 * ms, 10 * ms),   # after the window
        (DEV, "XLA Modules", "module", 0, 100 * ms),      # not the op line
    ]


def test_busy_ops_and_gaps():
    r = TR.reduce(_events())
    assert abs(r["window_s"] - 0.1) < 1e-12
    assert abs(r["busy_s"] - 0.017) < 1e-12  # 5-12 and 40-50
    ops = dict(r["device_ops"])
    assert abs(ops["miller"] - 0.015) < 1e-12 and abs(ops["final"] - 0.004) < 1e-12
    assert "outside" not in ops
    gaps = dict(r["idle_gaps"])
    # 0-5 and 50-100 under bench.await; 12-40 (midpoint 26) in begin_job
    assert abs(gaps["bench.begin_job"] - 0.028) < 1e-12
    assert abs(gaps["bench.await"] - 0.055) < 1e-12
    assert r["gap_count"] == 3


def test_nothing_to_read():
    assert TR.reduce([(HOST, "python", "bench.x", 0, 1)]) is None
    assert TR.reduce([(HOST, "python", "bench.window", 0, 1)]) is None


# a 300 ms slice of a traced mainnet-500k.range-sync window on one v5e:
# the device plane and the harness's spans, event stats dropped
RECORDED = Path(__file__).resolve().parent / "data" / "range_sync_slice.xplane.pb"


def test_recorded_trace():
    events = TR.load(str(RECORDED))
    r = TR.reduce(events)
    assert r["devices"] == 1 and abs(r["window_s"] - 0.3) < 1e-9
    assert abs(r["busy_s"] - 0.159052304) < 1e-9 and r["op_count"] == 251
    # busy time and the gaps tile the window
    assert abs(r["busy_s"] + sum(s for _n, s in r["idle_gaps"]) - r["window_s"]) < 1e-6
    ops = dict(r["device_ops"])
    # the signing-root hash kernel (4 Fp2 planes + sign bits, 256 lanes)
    assert r["device_ops"][0][0] == "custom-call(4xs32[33,256],s32[2,256])"
    assert abs(ops["custom-call(4xs32[33,256],s32[2,256])"] - 0.065385743) < 1e-9
    assert r["idle_gaps"][0][0] == "bench.hash_roots"
    # the op line alone: a union of its events never exceeds their sum
    lo = next(s for _p, _l, n, s, _d in events if n == TR.WINDOW_SPAN)
    total = sum(
        min(s + d, lo + 3e8) - max(s, lo)
        for p, line, _n, s, d in events
        if p.startswith("/device:") and line == TR.OP_LINE and s + d > lo and s < lo + 3e8
    )
    assert r["busy_s"] <= total / 1e9 + 1e-12
