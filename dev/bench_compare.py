#!/usr/bin/env python3
"""bench_compare — run-over-run trajectory diff across BENCH_r*.json.

The driver archives every bench round as BENCH_rNN.json:

    {"n": 5, "cmd": ..., "rc": 1, "tail": "<last stdout/stderr>",
     "parsed": {<the last JSON record bench.py printed>} | null}

Newer bench.py runs print SEVERAL records (headline, RLC, pipeline,
state roots), all present as JSON lines inside "tail"; older rounds
only carry "parsed"; dead rounds carry neither.  This tool
normalizes all three shapes into a per-metric trajectory and diffs it:

  - one row per metric, one column per round: the measured value,
    ``skip`` for an explicit skip record (``"skipped": true`` or
    ``value: null``), ``dead`` for a round that produced no parseable
    record at all, and ``-``
    when the metric did not exist yet,
  - the delta column compares the LATEST measured value against the
    PREVIOUS measured value of the same metric, skipping over
    skip/dead rounds (a skip is "no data", never "zero"),
  - exit 1 when any metric regressed beyond ``--threshold`` (default
    5%), exit 0 otherwise, exit 2 on usage errors.  ``--json`` emits
    the table machine-readably for CI.

Usage:
    python dev/bench_compare.py                      # all BENCH_r*.json
    python dev/bench_compare.py BENCH_r06.json BENCH_r07.json
    python dev/bench_compare.py --threshold 0.10 --json
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import re
import sys
from typing import Dict, List, Optional

# legacy pre-skip-schema failure records: r04/r05 published value 0.0
# WITH an "error" field before bench.py learned `"skipped": true`;
# a measured zero with an error attached is a failure, not a datum
_LEGACY_ERROR_ZERO = 0.0


def round_label(path: str) -> str:
    m = re.search(r"(r\d+)", os.path.basename(path))
    return m.group(1) if m else os.path.basename(path)


def _normalize(rec: dict) -> Optional[dict]:
    """One bench JSON record -> {value, skipped, error} or None when it
    isn't a bench record at all."""
    if not isinstance(rec, dict) or "metric" not in rec:
        return None
    value = rec.get("value")
    skipped = bool(rec.get("skipped")) or value is None
    if not skipped:
        try:
            value = float(value)
        except (TypeError, ValueError):
            # a malformed archived record must degrade to a skip cell,
            # never crash the whole comparison
            skipped = True
            rec = dict(rec, error=f"unparseable value {value!r}")
        else:
            if value == _LEGACY_ERROR_ZERO and rec.get("error"):
                skipped = True
    return {
        "metric": rec["metric"],
        "value": None if skipped else value,
        "skipped": skipped,
        "error": rec.get("error"),
        "unit": rec.get("unit"),
    }


def extract_records(doc: dict) -> Dict[str, dict]:
    """metric -> normalized record for one round document.  Prefers the
    JSON lines embedded in "tail" (multi-record rounds); falls back to
    "parsed"; {} for a dead round."""
    out: Dict[str, dict] = {}
    tail = doc.get("tail") or ""
    for line in tail.splitlines():
        line = line.strip()
        if not line.startswith("{"):
            continue
        try:
            rec = _normalize(json.loads(line))
        except ValueError:
            continue
        if rec is not None:
            out[rec["metric"]] = rec  # last occurrence wins
    if not out:
        rec = _normalize(doc.get("parsed") or {})
        if rec is not None:
            out[rec["metric"]] = rec
    return out


def build_table(paths: List[str]) -> dict:
    """{"rounds": [labels], "metrics": {metric: [cell...]}} where a
    cell is {"value": float|None, "state": measured|skip|dead|absent,
    "error": ...}."""
    rounds: List[str] = []
    per_round: List[Dict[str, dict]] = []
    for path in paths:
        rounds.append(round_label(path))
        try:
            with open(path) as f:
                doc = json.load(f)
        except (OSError, ValueError) as e:
            per_round.append({"__load_error__": {"error": str(e)}})
            continue
        per_round.append(extract_records(doc))
    metrics = sorted(
        {m for recs in per_round for m in recs if not m.startswith("__")}
    )
    table: Dict[str, List[dict]] = {}
    for metric in metrics:
        row = []
        for recs in per_round:
            rec = recs.get(metric)
            if rec is None:
                # a round that produced NOTHING is dead; a round that
                # produced other metrics simply predates this one
                state = "dead" if not any(
                    not k.startswith("__") for k in recs
                ) else "absent"
                row.append({"value": None, "state": state, "error": None})
            elif rec["skipped"]:
                row.append(
                    {"value": None, "state": "skip", "error": rec["error"]}
                )
            else:
                row.append(
                    {
                        "value": rec["value"],
                        "state": "measured",
                        "error": None,
                        "unit": rec.get("unit"),
                    }
                )
        table[metric] = row
    return {"rounds": rounds, "metrics": table}


# units where a SMALLER value is the better one (wall-clock probes like
# bls_rlc_bisect_seconds, downstream bytes like
# gossip_bytes_per_verified_att) — the regression gate inverts for these
_LOWER_IS_BETTER_UNITS = {"s", "seconds", "ms", "us", "bytes/att"}

# authoritative unit registry for metrics whose archived records might
# predate (or drop) the "unit" field — keeps the regression gate
# direction-aware even for unit-less cells.  New probes register here.
_METRIC_UNITS = {
    "bls_signature_sets_verified_per_s": "sets/s",
    "bls_rlc_signature_sets_verified_per_s": "sets/s",
    "bls_rlc_bisect_seconds": "s",
    "bls_pipeline_verified_atts_per_s": "atts/s",
    # ISSUE 13: effective throughput AFTER pre-verify aggregation —
    # atts/s, higher is better; a drop beyond threshold exits 1
    "bls_pipeline_effective_atts_per_s": "atts/s",
    # ISSUE 14: injected-device-fault -> back-to-device-verdicts wall
    # clock (breaker trip + degraded routing + canary re-probe); a
    # time metric — growth beyond threshold regresses
    "bls_device_fault_recovery_seconds": "s",
    "state_roots_per_s": "roots/s",
    # ISSUE 16: the same mutate-k-per-slot cadence with the device
    # merkleization backend (kernels/sha256.py hash forest) — roots/s,
    # higher is better
    "state_roots_per_s_device": "roots/s",
    # ISSUE 15: fork-churn regen throughput at 0.25x budget — the
    # evict-and-regenerate floor; throughput, higher is better
    "regen_under_pressure_states_per_s": "states/s",
    # ISSUE 17: light-client horde serving off the proof plane —
    # throughput, higher is better
    "proofs_per_s": "proofs/s",
    # bundle-cache hit rate rides its own metric in comparisons
    # (ratio 0..1, higher is better)
    "proof_bundle_hit_rate": "ratio",
    # ISSUE 19: downstream gossip bytes carried per distinct verified
    # attestation with aggregate-forward on — bytes regress UP (a rise
    # beyond threshold exits 1)
    "gossip_bytes_per_verified_att": "bytes/att",
    # ISSUE 19: raw-sync downstream cost / aggregate-forward cost for
    # the same flood (ratio, higher is better; acceptance bounds >= 3)
    "aggregate_forward_factor": "ratio",
}


def _lower_is_better(row: List[dict], metric: Optional[str] = None) -> bool:
    unit = next(
        (c.get("unit") for c in reversed(row) if c.get("unit")), None
    )
    if unit is None and metric is not None:
        unit = _METRIC_UNITS.get(metric)
    return unit in _LOWER_IS_BETTER_UNITS


def is_regression(
    metric_row: List[dict],
    delta: Optional[dict],
    threshold: float,
    metric: Optional[str] = None,
) -> bool:
    """Direction-aware: throughput (sets/s, atts/s, roots/s, ...)
    regresses when it DROPS beyond the threshold; time metrics (unit
    's') regress when they GROW beyond it.  `metric` resolves the
    direction through _METRIC_UNITS when the cells carry no unit."""
    if delta is None or delta["ratio"] is None:
        return False
    if _lower_is_better(metric_row, metric):
        return delta["ratio"] > 1.0 + threshold
    return delta["ratio"] < 1.0 - threshold


def deltas(table: dict) -> Dict[str, Optional[dict]]:
    """metric -> {prev_round, last_round, prev, last, ratio} over the
    two most recent MEASURED cells (None with < 2 measurements —
    skip/dead rounds are stepped over, never treated as zero)."""
    out: Dict[str, Optional[dict]] = {}
    rounds = table["rounds"]
    for metric, row in table["metrics"].items():
        measured = [
            (rounds[i], cell["value"])
            for i, cell in enumerate(row)
            if cell["state"] == "measured"
        ]
        if len(measured) < 2:
            out[metric] = None
            continue
        (pr, pv), (lr, lv) = measured[-2], measured[-1]
        out[metric] = {
            "prev_round": pr,
            "last_round": lr,
            "prev": pv,
            "last": lv,
            "ratio": (lv / pv) if pv else None,
        }
    return out


def main(argv: Optional[List[str]] = None) -> int:
    ap = argparse.ArgumentParser(prog="python dev/bench_compare.py")
    ap.add_argument("files", nargs="*", help="BENCH_r*.json, oldest first")
    ap.add_argument(
        "--threshold",
        type=float,
        default=0.05,
        help="regression gate: latest measured value below previous by "
        "more than this fraction exits 1 (default 0.05)",
    )
    ap.add_argument("--json", action="store_true", help="machine output")
    args = ap.parse_args(argv)

    paths = args.files or sorted(glob.glob("BENCH_r*.json"))
    if not paths:
        print("error: no BENCH_r*.json files found", file=sys.stderr)
        return 2

    table = build_table(paths)
    dts = deltas(table)
    regressions = {
        m: d
        for m, d in dts.items()
        if is_regression(table["metrics"][m], d, args.threshold, metric=m)
    }

    if args.json:
        json.dump(
            {
                "rounds": table["rounds"],
                "metrics": table["metrics"],
                "deltas": dts,
                "regressions": sorted(regressions),
                "threshold": args.threshold,
            },
            sys.stdout,
            indent=2,
        )
        print()
        return 1 if regressions else 0

    width = max((len(m) for m in table["metrics"]), default=6)
    cols = "".join(f"{r:>14}" for r in table["rounds"])
    print(f"{'metric':<{width}}{cols}{'Δ last/prev':>14}")
    for metric, row in sorted(table["metrics"].items()):
        cells = ""
        for cell in row:
            if cell["state"] == "measured":
                cells += f"{cell['value']:>14.2f}"
            else:
                cells += f"{cell['state']:>14}"
        d = dts[metric]
        if d is None or d["ratio"] is None:
            delta = f"{'n/a':>14}"
        else:
            delta = f"{(d['ratio'] - 1.0) * 100:>+13.1f}%"
        flag = "  << REGRESSION" if metric in regressions else ""
        print(f"{metric:<{width}}{cells}{delta}{flag}")
    skips = sum(
        1
        for row in table["metrics"].values()
        for cell in row
        if cell["state"] in ("skip", "dead")
    )
    if skips:
        print(
            f"# {skips} skip/dead cells (null or no record) excluded "
            f"from deltas — see the round's 'error' field for why"
        )
    if regressions:
        for m in sorted(regressions):
            d = regressions[m]
            direction = (
                "time grew" if _lower_is_better(table["metrics"][m], m)
                else "throughput dropped"
            )
            print(
                f"REGRESSION {m}: {d['prev']:.2f} ({d['prev_round']}) -> "
                f"{d['last']:.2f} ({d['last_round']}), "
                f"{(d['ratio'] - 1.0) * 100:+.1f}% ({direction}; "
                f"threshold {args.threshold * 100:.0f}%)",
                file=sys.stderr,
            )
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
