"""dev/bench_compare.py: run-over-run trajectory diff (ISSUE 12
satellite) — per-metric delta table, explicit skipped/null handling
(the r03–r05 shapes), nonzero exit on regression."""

import importlib.util
import json
import sys
from pathlib import Path

import pytest

pytestmark = pytest.mark.smoke

_SPEC = importlib.util.spec_from_file_location(
    "bench_compare",
    Path(__file__).resolve().parent.parent / "dev" / "bench_compare.py",
)
bench_compare = importlib.util.module_from_spec(_SPEC)
sys.modules.setdefault("bench_compare", bench_compare)
_SPEC.loader.exec_module(bench_compare)


def _round(path, tail_records=None, parsed=None, rc=0):
    tail = ""
    if tail_records is not None:
        tail = "\n".join(
            ["# some stderr noise"] + [json.dumps(r) for r in tail_records]
        )
    path.write_text(
        json.dumps({"n": 1, "cmd": "bench", "rc": rc, "tail": tail,
                    "parsed": parsed})
    )
    return str(path)


def test_extracts_multi_record_tail_and_parsed_fallback(tmp_path):
    multi = _round(
        tmp_path / "BENCH_r07.json",
        tail_records=[
            {"metric": "a_per_s", "value": 10.0, "unit": "s"},
            {"metric": "b_per_s", "value": None, "skipped": True,
             "error": "probe: dead"},
        ],
    )
    legacy = _round(
        tmp_path / "BENCH_r01.json",
        parsed={"metric": "a_per_s", "value": 9.0, "unit": "s"},
    )
    recs = bench_compare.extract_records(json.loads(Path(multi).read_text()))
    assert recs["a_per_s"]["value"] == 10.0
    assert recs["b_per_s"]["skipped"] and recs["b_per_s"]["value"] is None
    recs = bench_compare.extract_records(json.loads(Path(legacy).read_text()))
    assert recs["a_per_s"]["value"] == 9.0


def test_legacy_error_zero_counts_as_skip():
    """r04/r05 published value 0.0 WITH an error field before the skip
    schema existed; treating that as a measured zero would claim a
    100% regression."""
    rec = bench_compare._normalize(
        {"metric": "x", "value": 0.0, "error": "backend-init-probe: dead"}
    )
    assert rec["skipped"] and rec["value"] is None
    # an honestly measured zero (no error) stays a measurement
    rec = bench_compare._normalize({"metric": "x", "value": 0.0})
    assert not rec["skipped"] and rec["value"] == 0.0


def test_malformed_value_degrades_to_skip_not_crash(tmp_path):
    """Review fix: a record whose value is a non-numeric string (or a
    dict) must become a skip cell, not a traceback."""
    rec = bench_compare._normalize({"metric": "m", "value": "err"})
    assert rec["skipped"] and rec["value"] is None
    assert "unparseable value" in rec["error"]
    rec = bench_compare._normalize({"metric": "m", "value": {"nested": 1}})
    assert rec["skipped"]
    r1 = _round(
        tmp_path / "BENCH_r01.json", parsed={"metric": "m", "value": "err"}
    )
    r2 = _round(
        tmp_path / "BENCH_r02.json", parsed={"metric": "m", "value": 5.0}
    )
    assert bench_compare.main([r1, r2]) == 0  # one measurement, no delta


def test_dead_and_skip_rounds_excluded_from_delta(tmp_path):
    r1 = _round(
        tmp_path / "BENCH_r01.json",
        parsed={"metric": "m", "value": 100.0},
    )
    r2 = _round(tmp_path / "BENCH_r02.json", parsed=None, rc=1)  # r03 shape
    r3 = _round(
        tmp_path / "BENCH_r03.json",
        tail_records=[
            {"metric": "m", "value": None, "skipped": True, "error": "x"}
        ],
    )
    r4 = _round(
        tmp_path / "BENCH_r04.json",
        tail_records=[{"metric": "m", "value": 101.0}],
    )
    table = bench_compare.build_table([r1, r2, r3, r4])
    states = [c["state"] for c in table["metrics"]["m"]]
    assert states == ["measured", "dead", "skip", "measured"]
    d = bench_compare.deltas(table)["m"]
    # the delta steps over the dead/skip rounds: r01 -> r04
    assert d["prev_round"] == "r01" and d["last_round"] == "r04"
    assert d["ratio"] == pytest.approx(1.01)


def test_regression_beyond_threshold_exits_nonzero(tmp_path, capsys):
    r1 = _round(
        tmp_path / "BENCH_r01.json", parsed={"metric": "m", "value": 100.0}
    )
    r2 = _round(
        tmp_path / "BENCH_r02.json", parsed={"metric": "m", "value": 80.0}
    )
    rc = bench_compare.main([r1, r2, "--threshold", "0.05"])
    assert rc == 1
    err = capsys.readouterr().err
    assert "REGRESSION m" in err and "-20.0%" in err
    # a generous threshold tolerates the same drop
    assert bench_compare.main([r1, r2, "--threshold", "0.25"]) == 0
    # improvements always pass
    assert bench_compare.main([r2, r1, "--threshold", "0.05"]) == 0


def test_time_metrics_gate_inverts_direction(tmp_path, capsys):
    """Review fix: bls_rlc_bisect_seconds (unit 's') is lower-is-better
    — growing is the regression, shrinking is the improvement."""
    r1 = _round(
        tmp_path / "BENCH_r01.json",
        tail_records=[
            {"metric": "bls_rlc_bisect_seconds", "value": 1.0, "unit": "s"}
        ],
    )
    r2 = _round(
        tmp_path / "BENCH_r02.json",
        tail_records=[
            {"metric": "bls_rlc_bisect_seconds", "value": 2.0, "unit": "s"}
        ],
    )
    assert bench_compare.main([r1, r2, "--threshold", "0.05"]) == 1
    assert "time grew" in capsys.readouterr().err
    # the same ratio the other way round is an improvement
    assert bench_compare.main([r2, r1, "--threshold", "0.05"]) == 0


def test_json_output_shape(tmp_path, capsys):
    r1 = _round(
        tmp_path / "BENCH_r01.json", parsed={"metric": "m", "value": 100.0}
    )
    r2 = _round(
        tmp_path / "BENCH_r02.json", parsed={"metric": "m", "value": 50.0}
    )
    rc = bench_compare.main([r1, r2, "--json"])
    assert rc == 1
    doc = json.loads(capsys.readouterr().out)
    assert doc["rounds"] == ["r01", "r02"]
    assert doc["regressions"] == ["m"]
    assert doc["deltas"]["m"]["ratio"] == pytest.approx(0.5)
    assert doc["metrics"]["m"][0]["state"] == "measured"


def test_no_files_is_usage_error(tmp_path, monkeypatch, capsys):
    monkeypatch.chdir(tmp_path)
    assert bench_compare.main([]) == 2


def test_single_measurement_yields_no_delta(tmp_path):
    r1 = _round(
        tmp_path / "BENCH_r01.json", parsed={"metric": "m", "value": 100.0}
    )
    table = bench_compare.build_table([r1])
    assert bench_compare.deltas(table)["m"] is None
    assert bench_compare.main([r1]) == 0


def test_effective_atts_metric_direction_registered(tmp_path, capsys):
    """ISSUE 13 satellite: `bls_pipeline_effective_atts_per_s` is a
    throughput metric — a drop beyond threshold exits 1, a rise exits 0,
    and the direction holds even when archived cells lost their unit
    (the _METRIC_UNITS registry pins it)."""
    m = "bls_pipeline_effective_atts_per_s"
    assert bench_compare._METRIC_UNITS[m] == "atts/s"
    drop = [
        _round(tmp_path / "BENCH_r01.json",
               tail_records=[{"metric": m, "value": 9000.0,
                              "unit": "atts/s"}]),
        _round(tmp_path / "BENCH_r02.json",
               tail_records=[{"metric": m, "value": 4000.0,
                              "unit": "atts/s"}]),
    ]
    assert bench_compare.main(drop + ["--threshold", "0.05"]) == 1
    capsys.readouterr()
    rise = [
        _round(tmp_path / "BENCH_r03.json",
               tail_records=[{"metric": m, "value": 4000.0}]),  # no unit
        _round(tmp_path / "BENCH_r04.json",
               tail_records=[{"metric": m, "value": 9000.0}]),
    ]
    assert bench_compare.main(rise + ["--threshold", "0.05"]) == 0
    capsys.readouterr()
    unitless_drop = [
        _round(tmp_path / "BENCH_r05.json",
               tail_records=[{"metric": m, "value": 9000.0}]),
        _round(tmp_path / "BENCH_r06.json",
               tail_records=[{"metric": m, "value": 4000.0}]),
    ]
    assert bench_compare.main(unitless_drop + ["--threshold", "0.05"]) == 1
    capsys.readouterr()


def test_state_roots_device_metric_direction_registered(tmp_path, capsys):
    """ISSUE 16 satellite: `state_roots_per_s_device` is a throughput
    metric — a drop beyond threshold exits 1, a rise exits 0, even when
    archived cells lost their unit (the registry pins roots/s)."""
    m = "state_roots_per_s_device"
    assert bench_compare._METRIC_UNITS[m] == "roots/s"
    drop = [
        _round(tmp_path / "BENCH_r01.json",
               tail_records=[{"metric": m, "value": 50.0}]),  # no unit
        _round(tmp_path / "BENCH_r02.json",
               tail_records=[{"metric": m, "value": 20.0}]),
    ]
    assert bench_compare.main(drop + ["--threshold", "0.05"]) == 1
    capsys.readouterr()
    rise = [
        _round(tmp_path / "BENCH_r03.json",
               tail_records=[{"metric": m, "value": 20.0,
                              "unit": "roots/s"}]),
        _round(tmp_path / "BENCH_r04.json",
               tail_records=[{"metric": m, "value": 50.0}]),
    ]
    assert bench_compare.main(rise + ["--threshold", "0.05"]) == 0
    capsys.readouterr()


def test_regen_pressure_metric_direction_registered(tmp_path, capsys):
    """ISSUE 15 satellite: `regen_under_pressure_states_per_s` is a
    throughput floor — a drop beyond threshold exits 1 even when the
    archived cells lost their unit (the registry pins states/s)."""
    m = "regen_under_pressure_states_per_s"
    assert bench_compare._METRIC_UNITS[m] == "states/s"
    drop = [
        _round(tmp_path / "BENCH_r01.json",
               tail_records=[{"metric": m, "value": 20.0}]),  # no unit
        _round(tmp_path / "BENCH_r02.json",
               tail_records=[{"metric": m, "value": 8.0}]),
    ]
    assert bench_compare.main(drop + ["--threshold", "0.05"]) == 1
    capsys.readouterr()
    rise = [
        _round(tmp_path / "BENCH_r03.json",
               tail_records=[{"metric": m, "value": 8.0,
                              "unit": "states/s"}]),
        _round(tmp_path / "BENCH_r04.json",
               tail_records=[{"metric": m, "value": 20.0}]),
    ]
    assert bench_compare.main(rise + ["--threshold", "0.05"]) == 0
    capsys.readouterr()


def test_aggregate_forward_metric_directions_registered(tmp_path, capsys):
    """ISSUE 19 satellite: `gossip_bytes_per_verified_att` regresses UP
    (bytes are lower-is-better — a rise beyond threshold exits 1, a
    drop exits 0, even unit-less via the registry) while
    `aggregate_forward_factor` is a ratio — a drop regresses."""
    m = "gossip_bytes_per_verified_att"
    assert bench_compare._METRIC_UNITS[m] == "bytes/att"
    assert "bytes/att" in bench_compare._LOWER_IS_BETTER_UNITS
    grow = [
        _round(tmp_path / "BENCH_r01.json",
               tail_records=[{"metric": m, "value": 100.0,
                              "unit": "bytes/att"}]),
        _round(tmp_path / "BENCH_r02.json",
               tail_records=[{"metric": m, "value": 400.0,
                              "unit": "bytes/att"}]),
    ]
    assert bench_compare.main(grow + ["--threshold", "0.05"]) == 1
    capsys.readouterr()
    # the same ratio the other way round is the improvement the ISSUE
    # 19 tentpole buys; unit-less cells resolve through the registry
    shrink = [
        _round(tmp_path / "BENCH_r03.json",
               tail_records=[{"metric": m, "value": 400.0}]),  # no unit
        _round(tmp_path / "BENCH_r04.json",
               tail_records=[{"metric": m, "value": 100.0}]),
    ]
    assert bench_compare.main(shrink + ["--threshold", "0.05"]) == 0
    capsys.readouterr()
    f = "aggregate_forward_factor"
    assert bench_compare._METRIC_UNITS[f] == "ratio"
    factor_drop = [
        _round(tmp_path / "BENCH_r05.json",
               tail_records=[{"metric": f, "value": 6.0, "unit": "ratio"}]),
        _round(tmp_path / "BENCH_r06.json",
               tail_records=[{"metric": f, "value": 2.0}]),  # unit-less
    ]
    assert bench_compare.main(factor_drop + ["--threshold", "0.05"]) == 1
    capsys.readouterr()


def test_unitless_time_metric_direction_resolved_by_registry(tmp_path, capsys):
    """A unit-less bls_rlc_bisect_seconds GROWTH still gates (the
    registry knows it is lower-is-better)."""
    m = "bls_rlc_bisect_seconds"
    grow = [
        _round(tmp_path / "BENCH_r01.json",
               tail_records=[{"metric": m, "value": 1.0}]),
        _round(tmp_path / "BENCH_r02.json",
               tail_records=[{"metric": m, "value": 3.0}]),
    ]
    assert bench_compare.main(grow + ["--threshold", "0.05"]) == 1
    capsys.readouterr()

def test_breaker_recovery_metric_direction_registered(tmp_path, capsys):
    """ISSUE 14 satellite: `bls_device_fault_recovery_seconds` is a
    time metric — GROWTH beyond threshold regresses (exit 1), shrink
    passes, and the registry pins the direction for unit-less cells."""
    m = "bls_device_fault_recovery_seconds"
    assert bench_compare._METRIC_UNITS[m] == "s"
    grow = [
        _round(tmp_path / "BENCH_r01.json",
               tail_records=[{"metric": m, "value": 0.5, "unit": "s"}]),
        _round(tmp_path / "BENCH_r02.json",
               tail_records=[{"metric": m, "value": 2.0}]),  # unit-less
    ]
    assert bench_compare.main(grow + ["--threshold", "0.05"]) == 1
    capsys.readouterr()
    shrink = [
        _round(tmp_path / "BENCH_r03.json",
               tail_records=[{"metric": m, "value": 2.0}]),
        _round(tmp_path / "BENCH_r04.json",
               tail_records=[{"metric": m, "value": 0.5}]),
    ]
    assert bench_compare.main(shrink + ["--threshold", "0.05"]) == 0
    capsys.readouterr()
