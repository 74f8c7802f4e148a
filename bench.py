"""Headline benchmark: BLS signature-sets verified per second on one chip.

Measures the WIRE path end-to-end per job — the work the reference's
BlsMultiThreadWorkerPool performs per job (reference:
packages/beacon-node/src/chain/bls/multithread/worker.ts:30-106) plus the
deserialization it pays inside blst:

  host:   96B compressed signature -> flag bits + x-coordinate limb split,
          wire checks (length/compression/range), randomizer CSPRNG,
  device: signing-root hash-to-curve (SSWU, batched per distinct root —
          the per-slot SeenAttestationDatas cadence), signature
          decompression (Fp2 sqrt), pubkey-table gather, the full
          random-linear-combination batch verification (scalar muls,
          Miller loops, final exponentiation), verdict sync.

Fresh signing roots are hashed inside the timed region (one device batch
per job, modelling the per-slot cadence: mainnet has ~64 distinct
attestation datas per slot amortized over ~15k single sets — this bench
is ~4x more conservative at 8 fresh roots per 512-set job).

Baseline: the reference's CPU thread-pool ceiling, ~32 workers x ~1.1k
sigs/s x <=2 batching gain = 3-7e4 sig-sets/s (SURVEY.md section 6;
packages/beacon-node/src/metrics/metrics/lodestar.ts:427).  We take the
midpoint 5.0e4 sets/s as the baseline denominator.

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline"}.
BENCH_MODE=decoded runs the pre-decoded-planes benchmark instead.

Failure modes are BOUNDED:
  - the device check runs FIRST, in this process: without a TPU the
    bench emits skip records and exits non-zero at once — it never
    measures the CPU in the chip's place.  This process then holds the
    chip; the only children it starts are CPU-only (JAX_PLATFORMS=cpu),
  - a watchdog thread bounds the whole run (BENCH_DEADLINE) and emits
    a JSON diagnosis if anything blocks mid-run.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import threading
import time

os.environ.setdefault("XLA_FLAGS", "")

# The deadline is a last-resort diagnostic, not a budget: it must not
# kill a bench whose cold compiles would finish (PERF.md, PR 21).
BENCH_DEADLINE_S = float(os.environ.get("BENCH_DEADLINE", "3300"))


def _metric_name() -> str:
    if os.environ.get("BENCH_MODE", "wire") == "decoded":
        return "bls_signature_sets_verified_per_s_decoded"
    return "bls_signature_sets_verified_per_s"


# -- phase-timing snapshot (ISSUE 8) ----------------------------------------
# Every emitted record — measured AND skipped/null — carries a "phases"
# dict: per-stage wall-clock (device check, world build, warmup, timed
# region) with start offsets, plus the in-process kernel compile/cache
# tallies from the observability registry, so a failed run is
# diagnosable from its record alone (which stage ate the budget,
# whether any compile happened before it died).
_PHASES = {"t_start": time.time(), "stages": {}}


def _phase_mark(stage: str, seconds: float, **extra) -> None:
    rec = {
        "seconds": round(seconds, 3),
        "t_offset_s": round(time.time() - _PHASES["t_start"], 3),
    }
    rec.update(extra)
    _PHASES["stages"][stage] = rec


def _phase_snapshot() -> dict:
    snap = {
        "t_start_unix": round(_PHASES["t_start"], 3),
        "t_emit_offset_s": round(time.time() - _PHASES["t_start"], 3),
        "stages": dict(_PHASES["stages"]),
    }
    try:
        # compile-vs-cache tallies (kernels/export_cache.py counters);
        # import stays lazy so the pre-jax probe stages can emit too
        from lodestar_tpu.observability import kernel_compile_snapshot

        snap["kernels"] = kernel_compile_snapshot()
    except Exception as e:  # noqa: BLE001 — diagnostics must not fail a run
        snap["kernels"] = {"error": str(e)[:200]}
    return snap


def _breaker_snapshot() -> dict:
    """The BLS device circuit breaker's aggregate state (ISSUE 14) —
    state / trip count / cumulative time-in-degraded.  A bench round
    whose numbers were produced with the breaker open measured the
    HOST fallback, not the device path; this field makes that visible
    in the record itself.  Lazy + failure-proof like the SLO snapshot."""
    try:
        from lodestar_tpu.bls.supervisor import breaker_snapshot

        return breaker_snapshot()
    except Exception as e:  # noqa: BLE001 — diagnostics must not fail a run
        return {"error": str(e)[:200]}


def _memory_snapshot() -> dict:
    """Aggregate state-plane governor state (ISSUE 15) — budget,
    ledger bytes, evictions by tier, pressure episodes.  A bench round
    that ran under memory pressure measured the evict-and-regenerate
    path, not the warm caches; this field makes that visible in the
    record itself.  Lazy + failure-proof like the breaker snapshot."""
    try:
        from lodestar_tpu.chain.memory_governor import memory_snapshot

        return memory_snapshot()
    except Exception as e:  # noqa: BLE001 — diagnostics must not fail a run
        return {"error": str(e)[:200]}


def _slo_snapshot() -> dict:
    """The lodestar_slo_* breach counters from the process-global
    registry (ISSUE 12) — zeros unless an SLO engine ran in-process,
    but the shape is uniform so BENCH trend consumers can diff it.
    Import stays lazy and failure-proof: the snapshot must attach even
    on pre-jax probe failures."""
    try:
        from lodestar_tpu.observability.slo import breach_snapshot

        return breach_snapshot()
    except Exception as e:  # noqa: BLE001 — diagnostics must not fail a run
        return {"error": str(e)[:200]}


# Flight recording on bench failure (ISSUE 12): a dead probe leaves a
# loadable bundle (span ring, phase timings, SLO counters) instead of a
# bare null.  On by default only under `python bench.py` (the __main__
# blocks flip _FLIGHTREC_ON) or when BENCH_FLIGHTREC_DIR names a
# directory — in-process stub tests stay side-effect-free.
_FLIGHTREC_ON = False
_FLIGHT_RECORDER = None


def _bench_flight_record(stage: str, detail: str):
    """Capture one failure bundle; returns its path or None (recorder
    disabled, rate-limited, or itself broken)."""
    global _FLIGHT_RECORDER
    directory = os.environ.get("BENCH_FLIGHTREC_DIR")
    if directory is None and not _FLIGHTREC_ON:
        return None
    try:
        if _FLIGHT_RECORDER is None:
            from lodestar_tpu.observability.flight_recorder import (
                FlightRecorder,
            )

            _FLIGHT_RECORDER = FlightRecorder(
                directory or "flightrec_bench",
                # every distinct failure stage in one run matters; the
                # caps still bound a crash loop re-running bench
                min_interval_s=0.0,
                max_bundles=8,
            )
            _FLIGHT_RECORDER.add_provider("phases", _phase_snapshot)
            _FLIGHT_RECORDER.add_provider("slo", _slo_snapshot)
            _FLIGHT_RECORDER.add_provider("breaker", _breaker_snapshot)
        return _FLIGHT_RECORDER.record(
            f"bench.{stage}", {"detail": detail[-2000:]}
        )
    except Exception as e:  # noqa: BLE001 — the recorder must never
        print(f"# flight record failed: {e}", file=sys.stderr)
        return None


def _emit_failure(
    stage: str, detail: str, metric: str = None, unit: str = "sets/s"
) -> None:
    """One machine-readable diagnosis line on stdout (the driver parses
    stdout for the JSON record; a traceback alone parses to nothing).

    A failed run is SKIPPED, not measured: value is null (a `0.0` would
    read as a measured zero), and "skipped": true marks the record so
    BENCH_*.json consumers never average a failure into a trend.
    `metric`/`unit` default to the headline BLS metric; secondary probes
    (state_roots_per_s) pass their own so every skip record shares ONE
    schema.  Every skip also carries the SLO snapshot and — when the
    recorder is on — the path of a flight-record bundle, so a dead
    round is diagnosable from its artifacts alone (r03–r05 were not)."""
    print(
        json.dumps(
            {
                "metric": metric or _metric_name(),
                "value": None,
                "unit": unit,
                "vs_baseline": None,
                "skipped": True,
                "error": f"{stage}: {detail}"[-2000:],
                "phases": _phase_snapshot(),
                "slo": _slo_snapshot(),
                "breaker": _breaker_snapshot(),
                "memory": _memory_snapshot(),
                "flight_record": _bench_flight_record(stage, detail),
            }
        ),
        flush=True,
    )


def _emit_rlc_skip(stage: str, detail: str) -> None:
    """A failure before the RLC probes ran skips BOTH rlc metrics — a
    missing record reads as "old bench without the probe", a skip
    record reads as "probe present, run unusable".  (Defined before
    _require_tpu's module-level call site so that path can use it.)"""
    _emit_failure(
        stage, detail, metric="bls_rlc_signature_sets_verified_per_s"
    )
    _emit_failure(stage, detail, metric="bls_rlc_bisect_seconds", unit="s")


def _emit_pipeline_skip(stage: str, detail: str) -> None:
    _emit_failure(
        stage,
        detail,
        metric="bls_pipeline_verified_atts_per_s",
        unit="atts/s",
    )


def _emit_effective_skip(stage: str, detail: str) -> None:
    _emit_failure(
        stage,
        detail,
        metric="bls_pipeline_effective_atts_per_s",
        unit="atts/s",
    )


def _emit_aggfwd_skip(stage: str, detail: str) -> None:
    """Aggregate-forward probe failure skips BOTH of its metrics (the
    _emit_rlc_skip convention: a missing record reads as 'old bench
    without the probe', a skip record as 'probe present, run unusable')."""
    _emit_failure(
        stage,
        detail,
        metric="gossip_bytes_per_verified_att",
        unit="bytes/att",
    )
    _emit_failure(
        stage, detail, metric="aggregate_forward_factor", unit="ratio"
    )


def _require_tpu() -> None:
    """Fail at once, in THIS process, unless JAX's device is a TPU.  The
    bench never measures the CPU in the chip's place, and a chip belongs
    to one process: this one keeps it, so no child may touch it."""
    t0 = time.monotonic()
    import jax

    try:
        dev = jax.devices()[0]
        detail = (
            None
            if dev.platform == "tpu"
            else f"no TPU found (JAX's device is {dev.platform!r})"
        )
    except RuntimeError as e:
        detail = f"no TPU found: {e}"
    _phase_mark("device_check", time.monotonic() - t0, ok=detail is None)
    if detail is None:
        print(f"# device: {dev.device_kind} ({dev.platform})", file=sys.stderr)
        return
    print(f"bench: {detail}", file=sys.stderr)
    _emit_failure("device-check", detail)
    # the secondary probes ride the same process; emit their skip
    # records too so consumers see "skipped" rather than a missing
    # metric (wire mode only — a healthy decoded run emits no RLC
    # records, so a skip record there would claim a probe that never
    # runs)
    if (
        os.environ.get("BENCH_RLC", "1") != "0"
        and os.environ.get("BENCH_MODE", "wire") != "decoded"
    ):
        _emit_rlc_skip("device-check", detail)
    if (
        os.environ.get("BENCH_PIPELINE", "1") != "0"
        and os.environ.get("BENCH_MODE", "wire") != "decoded"
    ):
        _emit_pipeline_skip("device-check", detail)
        if os.environ.get("BENCH_PREAGG", "1") != "0":
            _emit_effective_skip("device-check", detail)
        if os.environ.get("BENCH_AGGFWD", "1") != "0":
            _emit_aggfwd_skip("device-check", detail)
    sys.exit(1)


_WATCHDOG_ARMED = False


def _arm_watchdog() -> None:
    """Bound the whole bench run: emit a JSON diagnosis and hard-exit if
    anything (device sync, compile) blocks past the deadline."""
    global _WATCHDOG_ARMED
    if _WATCHDOG_ARMED:
        return
    _WATCHDOG_ARMED = True

    def _fire():
        _emit_failure(
            "deadline",
            f"bench exceeded {BENCH_DEADLINE_S:.0f}s "
            "(device sync or compile blocked?)",
        )
        os._exit(1)

    t = threading.Timer(BENCH_DEADLINE_S, _fire)
    t.daemon = True
    t.start()


# state_roots_per_s probe: synthetic large state, mutate-k-per-slot
# cadence (dev/microbench_htr.py).  Pure-CPU in a subprocess with
# JAX_PLATFORMS=cpu.  The DEVICE variant (state_roots_per_s_device,
# ISSUE 16) runs the same cadence through the hash forest in THIS
# process, which holds the chip (_probe_state_roots_device).
BENCH_HTR_TIMEOUT_S = float(os.environ.get("BENCH_HTR_TIMEOUT", "420"))
# the device probe runs in this process, so a hang cannot be killed:
# past this deadline it emits its skip record and the bench exits
BENCH_HTR_DEVICE_TIMEOUT_S = float(
    os.environ.get("BENCH_HTR_DEVICE_TIMEOUT", "600")
)
BENCH_HTR_VALIDATORS = int(os.environ.get("BENCH_HTR_VALIDATORS", "100000"))
_HTR_SCRIPT = os.path.join(
    os.path.dirname(os.path.abspath(__file__)), "dev", "microbench_htr.py"
)


def _probe_state_roots() -> None:
    metric, stage, phase = (
        "state_roots_per_s", "state-roots-probe", "state_roots_probe"
    )
    timeout = BENCH_HTR_TIMEOUT_S
    # the host probe never touches a device
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    t0 = time.monotonic()
    try:
        p = subprocess.run(
            [
                sys.executable,
                _HTR_SCRIPT,
                "--json",
                "--validators",
                str(BENCH_HTR_VALIDATORS),
                "--slots",
                "16",
                "--full-reps",
                "2",
            ],
            capture_output=True,
            text=True,
            timeout=timeout,
            env=env,
        )
    except subprocess.TimeoutExpired:
        _phase_mark(phase, time.monotonic() - t0, ok=False)
        _emit_failure(
            stage,
            f"exceeded {timeout:.0f}s",
            metric=metric,
            unit="roots/s",
        )
        return
    _phase_mark(
        phase,
        time.monotonic() - t0,
        ok=p.returncode == 0,
        rc=p.returncode,
    )
    lines = [l for l in p.stdout.splitlines() if l.startswith("{")]
    if p.returncode != 0 or not lines:
        detail = (
            (p.stderr or p.stdout).strip().splitlines()[-1]
            if (p.stderr or p.stdout).strip()
            else f"probe exited rc={p.returncode}"
        )
        _emit_failure(stage, detail, metric=metric, unit="roots/s")
        return
    try:
        record = json.loads(lines[-1])
        # keep the record schema uniform with every other bench emit:
        # {metric, value, unit, vs_baseline, phases} (no baseline is
        # defined for state roots — the old full recompute is reported
        # alongside; the device record additionally carries the "htr"
        # dispatch-accounting snapshot the microbench embeds)
        record.setdefault("vs_baseline", None)
        record["phases"] = _phase_snapshot()
        record["slo"] = _slo_snapshot()
        record["memory"] = _memory_snapshot()
        print(json.dumps(record), flush=True)
    except ValueError:
        _emit_failure(
            stage, "unparseable probe output",
            metric=metric, unit="roots/s",
        )


def _probe_state_roots_device() -> None:
    """state_roots_per_s_device in THIS process: the chip belongs to it,
    so the device hash forest cannot run in a child."""
    import importlib.util

    from lodestar_tpu.ssz import device_backend

    metric = "state_roots_per_s_device"
    t0 = time.monotonic()

    def _hung():
        _phase_mark("state_roots_device_probe", time.monotonic() - t0, ok=False)
        _emit_failure(
            "state-roots-device-probe",
            f"device hash forest exceeded {BENCH_HTR_DEVICE_TIMEOUT_S:.0f}s",
            metric=metric,
            unit="roots/s",
        )
        os._exit(1)

    timer = threading.Timer(BENCH_HTR_DEVICE_TIMEOUT_S, _hung)
    timer.daemon = True
    timer.start()
    os.environ["LODESTAR_TPU_HTR_BACKEND"] = "jax"
    device_backend.reset_backend()
    try:
        spec = importlib.util.spec_from_file_location("microbench_htr", _HTR_SCRIPT)
        htr = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(htr)
        record = htr.run(BENCH_HTR_VALIDATORS, 16, 256, 2, backend="jax")
    except Exception as e:  # noqa: BLE001 — a failed probe is a skip record
        _phase_mark("state_roots_device_probe", time.monotonic() - t0, ok=False)
        _emit_failure(
            "state-roots-device-probe",
            f"{type(e).__name__}: {e}",
            metric=metric,
            unit="roots/s",
        )
        return
    finally:
        timer.cancel()
        os.environ.pop("LODESTAR_TPU_HTR_BACKEND", None)
        device_backend.reset_backend()
    _phase_mark("state_roots_device_probe", time.monotonic() - t0, ok=True)
    record["vs_baseline"] = None
    record["phases"] = _phase_snapshot()
    record["slo"] = _slo_snapshot()
    record["memory"] = _memory_snapshot()
    print(json.dumps(record), flush=True)


# regen_under_pressure_states_per_s probe (ISSUE 15): fork-churn regen
# throughput at budgets {unbounded, 0.5x, 0.25x of the working set} —
# the throughput floor the governor's evict-and-regenerate ladder
# guarantees under memory pressure.  Pure-CPU subprocess like the HTR
# probe.
BENCH_REGEN_TIMEOUT_S = float(os.environ.get("BENCH_REGEN_TIMEOUT", "420"))
BENCH_REGEN_KEYS = int(os.environ.get("BENCH_REGEN_KEYS", "16"))
BENCH_REGEN_SLOTS = int(os.environ.get("BENCH_REGEN_SLOTS", "12"))
BENCH_REGEN_TOUCHES = int(os.environ.get("BENCH_REGEN_TOUCHES", "24"))


def _emit_regen_skip(stage: str, detail: str) -> None:
    _emit_failure(
        stage,
        detail,
        metric="regen_under_pressure_states_per_s",
        unit="states/s",
    )


def _probe_regen_pressure() -> None:
    script = os.path.join(
        os.path.dirname(os.path.abspath(__file__)),
        "dev",
        "microbench_regen.py",
    )
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    t0 = time.monotonic()
    try:
        p = subprocess.run(
            [
                sys.executable,
                script,
                "--json",
                "--keys",
                str(BENCH_REGEN_KEYS),
                "--slots",
                str(BENCH_REGEN_SLOTS),
                "--touches",
                str(BENCH_REGEN_TOUCHES),
            ],
            capture_output=True,
            text=True,
            timeout=BENCH_REGEN_TIMEOUT_S,
            env=env,
        )
    except subprocess.TimeoutExpired:
        _phase_mark("regen_pressure_probe", time.monotonic() - t0, ok=False)
        _emit_regen_skip(
            "regen-pressure-probe",
            f"exceeded {BENCH_REGEN_TIMEOUT_S:.0f}s",
        )
        return
    _phase_mark(
        "regen_pressure_probe",
        time.monotonic() - t0,
        ok=p.returncode == 0,
        rc=p.returncode,
    )
    lines = [l for l in p.stdout.splitlines() if l.startswith("{")]
    if p.returncode != 0 or not lines:
        detail = (
            (p.stderr or p.stdout).strip().splitlines()[-1]
            if (p.stderr or p.stdout).strip()
            else f"probe exited rc={p.returncode}"
        )
        _emit_regen_skip("regen-pressure-probe", detail)
        return
    try:
        record = json.loads(lines[-1])
        record.setdefault("vs_baseline", None)
        record["phases"] = _phase_snapshot()
        record["slo"] = _slo_snapshot()
        record["memory"] = _memory_snapshot()
        print(json.dumps(record), flush=True)
    except ValueError:
        _emit_regen_skip(
            "regen-pressure-probe", "unparseable probe output"
        )


# proofs_per_s probe (ISSUE 17): light-client horde serving throughput
# against the proof plane — bundle/plane/host source accounting and the
# bundle-cache hit rate ride the record.  Pure-CPU subprocess like the
# regen probe, run BEFORE the backend probe.
BENCH_PROOFS_TIMEOUT_S = float(os.environ.get("BENCH_PROOFS_TIMEOUT", "420"))
BENCH_PROOFS_KEYS = int(os.environ.get("BENCH_PROOFS_KEYS", "16"))
BENCH_PROOFS_SLOTS = int(os.environ.get("BENCH_PROOFS_SLOTS", "8"))
BENCH_PROOFS_CLIENTS = int(os.environ.get("BENCH_PROOFS_CLIENTS", "8"))
BENCH_PROOFS_ROUNDS = int(os.environ.get("BENCH_PROOFS_ROUNDS", "6"))


def _emit_proofs_skip(stage: str, detail: str) -> None:
    _emit_failure(stage, detail, metric="proofs_per_s", unit="proofs/s")


def _probe_proofs() -> None:
    script = os.path.join(
        os.path.dirname(os.path.abspath(__file__)),
        "dev",
        "microbench_proofs.py",
    )
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    t0 = time.monotonic()
    try:
        p = subprocess.run(
            [
                sys.executable,
                script,
                "--json",
                "--keys",
                str(BENCH_PROOFS_KEYS),
                "--slots",
                str(BENCH_PROOFS_SLOTS),
                "--clients",
                str(BENCH_PROOFS_CLIENTS),
                "--rounds",
                str(BENCH_PROOFS_ROUNDS),
            ],
            capture_output=True,
            text=True,
            timeout=BENCH_PROOFS_TIMEOUT_S,
            env=env,
        )
    except subprocess.TimeoutExpired:
        _phase_mark("proofs_probe", time.monotonic() - t0, ok=False)
        _emit_proofs_skip(
            "proofs-probe", f"exceeded {BENCH_PROOFS_TIMEOUT_S:.0f}s"
        )
        return
    _phase_mark(
        "proofs_probe",
        time.monotonic() - t0,
        ok=p.returncode == 0,
        rc=p.returncode,
    )
    lines = [l for l in p.stdout.splitlines() if l.startswith("{")]
    if p.returncode != 0 or not lines:
        detail = (
            (p.stderr or p.stdout).strip().splitlines()[-1]
            if (p.stderr or p.stdout).strip()
            else f"probe exited rc={p.returncode}"
        )
        _emit_proofs_skip("proofs-probe", detail)
        return
    try:
        record = json.loads(lines[-1])
        record.setdefault("vs_baseline", None)
        record["phases"] = _phase_snapshot()
        record["slo"] = _slo_snapshot()
        record["memory"] = _memory_snapshot()
        print(json.dumps(record), flush=True)
    except ValueError:
        _emit_proofs_skip("proofs-probe", "unparseable probe output")


if __name__ == "__main__":
    # the driver invocation records failure bundles by default
    # (./flightrec_bench or BENCH_FLIGHTREC_DIR); in-process stub
    # tests only record when they set the env var.  Flipped BEFORE the
    # first possible _emit_failure (the device check below) so even
    # that failure leaves a bundle.
    _FLIGHTREC_ON = True
    _require_tpu()

import numpy as np

import jax
import jax.numpy as jnp

from lodestar_tpu.utils import compile_cache

compile_cache.configure()

if __name__ == "__main__" and os.environ.get("BENCH_HTR", "1") != "0":
    _probe_state_roots()

if __name__ == "__main__" and os.environ.get("BENCH_REGEN", "1") != "0":
    _probe_regen_pressure()

if __name__ == "__main__" and os.environ.get("BENCH_PROOFS", "1") != "0":
    _probe_proofs()

if __name__ == "__main__":
    _arm_watchdog()
    if os.environ.get("BENCH_HTR_DEVICE", "1") != "0":
        _probe_state_roots_device()

from lodestar_tpu.bls.pubkey_table import PubkeyTable
from lodestar_tpu.bls.signature_set import WireSignatureSet
from lodestar_tpu.bls.verifier import TpuBlsVerifier
from lodestar_tpu.crypto import bls as GTB
from lodestar_tpu.crypto import curves as GCC
from lodestar_tpu.crypto.hash_to_curve import hash_to_g2
from lodestar_tpu.kernels import layout as LY
from lodestar_tpu.kernels import verify as KV
from lodestar_tpu.ops import bls_kernels as BK

BASELINE_SETS_PER_S = 5.0e4

# Batch size per device job: the TPU analog of the reference's 128-set job
# cap (chain/bls/multithread/index.ts:39), raised because one chip replaces
# the whole worker pool.  Overridable for experiments.
BATCH = int(os.environ.get("BENCH_BATCH", "512"))
DISTINCT = 32  # distinct signing keys tiled across the batch
ROOTS_PER_ITER = 8  # distinct fresh signing roots per job
REPEATS = int(os.environ.get("BENCH_REPEATS", "16"))


def build_wire_world():
    sks = [GTB.keygen(b"bench-%d" % i) for i in range(DISTINCT)]
    pks = [GTB.sk_to_pk(sk) for sk in sks]
    table = PubkeyTable(capacity=max(BATCH, DISTINCT))
    table.register_points_unchecked(pks, tile_to=max(BATCH, DISTINCT))
    table.device_planes()

    jobs = []
    for r in range(REPEATS + 1):  # +1 warmup job with its own roots
        roots = [b"bench root %d %d" % (r, c) for c in range(ROOTS_PER_ITER)]
        sig_cache = {}
        sets = []
        for j in range(BATCH):
            key = j % DISTINCT
            root = roots[j % ROOTS_PER_ITER]
            if (key, root) not in sig_cache:
                sig_cache[(key, root)] = GCC.g2_compress(GTB.sign(sks[key], root))
            sets.append(WireSignatureSet.single(j, root, sig_cache[(key, root)]))
        jobs.append(sets)
    return table, jobs


def main_wire():
    t_build0 = time.perf_counter()
    table, jobs = build_wire_world()
    verifier = TpuBlsVerifier(table, max_job_sets=BATCH)
    t_build = time.perf_counter() - t_build0

    # Warm-up / compile on the throwaway job (its own roots, so the timed
    # region still pays its own hash-to-curve batches).
    _phase_mark("world_build", t_build)
    t_warm0 = time.perf_counter()
    warm = verifier.begin_job(jobs[0], batchable=True)
    assert verifier.finish_job(warm), "bench warmup failed verification"
    t_warm = time.perf_counter() - t_warm0
    _phase_mark("warmup", t_warm)
    print(
        f"# breakdown: world-build {t_build:.1f}s, warmup (trace+compile+run) "
        f"{t_warm:.1f}s",
        file=sys.stderr,
    )

    t0 = time.perf_counter()
    # hash all fresh signing roots in ONE device batch (the per-slot
    # cadence: SeenAttestationDatas misses are hashed together)
    fresh = list(dict.fromkeys(s.signing_root for job in jobs[1:] for s in job))
    verifier.messages.get_many(fresh)
    handles = [verifier.begin_job(job, batchable=True) for job in jobs[1:]]
    ok_all = True
    for h in handles:
        ok_all &= verifier.finish_job(h)
    dt = time.perf_counter() - t0
    assert ok_all, "bench jobs failed verification"
    _phase_mark("timed_region", dt, jobs=REPEATS, sets=BATCH * REPEATS)

    sets_per_s = BATCH * REPEATS / dt
    print(
        json.dumps(
            {
                "metric": _metric_name(),
                "value": round(sets_per_s, 2),
                "unit": "sets/s",
                "vs_baseline": round(sets_per_s / BASELINE_SETS_PER_S, 4),
                "phases": _phase_snapshot(),
                "slo": _slo_snapshot(),
                "breaker": _breaker_snapshot(),
                "memory": _memory_snapshot(),
            }
        )
    )
    if os.environ.get("BENCH_RLC", "1") != "0":
        _probe_rlc(verifier, jobs)
    if os.environ.get("BENCH_PIPELINE", "1") != "0":
        _probe_pipeline(verifier)
        if os.environ.get("BENCH_PREAGG", "1") != "0":
            _probe_effective_atts(verifier)
        if os.environ.get("BENCH_AGGFWD", "1") != "0":
            _probe_aggregate_forward(verifier)
    if os.environ.get("BENCH_BREAKER", "1") != "0":
        _probe_breaker_recovery(verifier)


# -- RLC amortization + adversarial-floor probes (ISSUE 10) -----------------
# Two secondary records with the headline's skip/null semantics:
#   bls_rlc_signature_sets_verified_per_s — all-valid jobs resolved by the
#     ONE-multi-pairing batch check (the amortization the tentpole buys),
#   bls_rlc_bisect_seconds — wall-clock to resolve a job with tampered
#     sets via the bisection fallback (the adversarial floor: a flood of
#     bad signatures degrades throughput to ~this per poisoned job, it
#     does not reject honest sets).
BENCH_RLC_REPEATS = int(os.environ.get("BENCH_RLC_REPEATS", "4"))


def _probe_rlc(verifier, jobs) -> None:
    t0 = time.monotonic()
    try:
        # the metrics claim RLC throughput — never publish the per-set
        # path under that name (escape hatch set, or 1-set jobs that are
        # never batchable under BENCH_BATCH=1)
        if not getattr(verifier, "_use_rlc", True):
            _emit_rlc_skip("rlc-probe", "LODESTAR_TPU_BLS_RLC=0: RLC disabled")
            return
        reps = jobs[1 : 1 + max(1, min(BENCH_RLC_REPEATS, len(jobs) - 1))]
        if not reps:  # BENCH_REPEATS=0: only the warmup job exists
            _emit_rlc_skip("rlc-probe", "no post-warmup jobs to measure")
            return
        if min(len(j) for j in reps) < 2:
            _emit_rlc_skip("rlc-probe", "jobs too small to batch (BENCH_BATCH<2)")
            return
        t1 = time.perf_counter()
        handles = [verifier.begin_job(list(job), batchable=True) for job in reps]
        ok = all(verifier.finish_job(h) for h in handles)
        dt = time.perf_counter() - t1
        n_sets = sum(len(j) for j in reps)
        _phase_mark("rlc_probe", time.monotonic() - t0, ok=ok)
        if not ok:
            _emit_rlc_skip("rlc-probe", "valid RLC jobs failed verification")
            return
        sets_per_s = n_sets / dt
        print(
            json.dumps(
                {
                    "metric": "bls_rlc_signature_sets_verified_per_s",
                    "value": round(sets_per_s, 2),
                    "unit": "sets/s",
                    "vs_baseline": round(sets_per_s / BASELINE_SETS_PER_S, 4),
                    "phases": _phase_snapshot(),
                    "slo": _slo_snapshot(),
                    "breaker": _breaker_snapshot(),
                    "memory": _memory_snapshot(),
                }
            ),
            flush=True,
        )
    except Exception as e:  # noqa: BLE001 — probe failures emit a skip record
        _emit_rlc_skip("rlc-probe", f"{type(e).__name__}: {e}")
        return
    try:
        # adversarial floor: swap the signatures of two sets with
        # different signing roots — both stay decodable and in-subgroup,
        # both are WRONG, so the batch check fails and the verifier
        # bisects down to per-set verdicts for the poisoned leaf
        bad_job = list(jobs[1])
        i, j = 0, 1
        while (
            j < len(bad_job)
            and bad_job[i].signing_root == bad_job[j].signing_root
        ):
            j += 1
        if j >= len(bad_job):
            _emit_failure(
                "rlc-bisect-probe",
                "job has no two sets with distinct signing roots to swap",
                metric="bls_rlc_bisect_seconds", unit="s",
            )
            return
        a, b = bad_job[i], bad_job[j]
        bad_job[i] = WireSignatureSet.single(
            a.indices[0], a.signing_root, b.signature
        )
        bad_job[j] = WireSignatureSet.single(
            b.indices[0], b.signing_root, a.signature
        )
        # warmup (untimed): bisection halves dispatch the INTERMEDIATE
        # N-bucket pipelines (e.g. 256 — neither the registered 128
        # bucket nor the replay-captured 512), so the first run pays
        # their trace/compile; the timed run below must measure the
        # adversarial floor, not compilation — same reason the headline
        # probe warms the batch pipeline before timing.
        if verifier.finish_job(verifier.begin_job(bad_job, batchable=True)):
            _emit_failure(
                "rlc-bisect-probe", "tampered job verified as valid",
                metric="bls_rlc_bisect_seconds", unit="s",
            )
            return
        t1 = time.perf_counter()
        h = verifier.begin_job(bad_job, batchable=True)
        ok = verifier.finish_job(h)
        dt = time.perf_counter() - t1
        _phase_mark(
            "rlc_bisect_probe",
            time.monotonic() - t0,
            ok=not ok,
            batch_retries=getattr(h, "batch_retries", None),
        )
        if ok:
            _emit_failure(
                "rlc-bisect-probe", "tampered job verified as valid",
                metric="bls_rlc_bisect_seconds", unit="s",
            )
            return
        print(
            json.dumps(
                {
                    "metric": "bls_rlc_bisect_seconds",
                    "value": round(dt, 4),
                    "unit": "s",
                    "vs_baseline": None,
                    "phases": _phase_snapshot(),
                    "slo": _slo_snapshot(),
                    "breaker": _breaker_snapshot(),
                    "memory": _memory_snapshot(),
                }
            ),
            flush=True,
        )
    except Exception as e:  # noqa: BLE001
        _emit_failure(
            "rlc-bisect-probe", f"{type(e).__name__}: {e}",
            metric="bls_rlc_bisect_seconds", unit="s",
        )


# -- accumulate-and-flush pipeline probe (ISSUE 11) -------------------------
# End-to-end gossip->pipeline->RLC under a synthetic multi-subnet flood:
# attestations spread over BENCH_PIPELINE_SUBNETS distinct roots (the
# per-slot attestation-data cadence) trickle through the NetworkProcessor
# into the shape-bucketed accumulate-and-flush pipeline, with a few
# block-critical aggregate submissions riding the short-deadline lane.
# Reports verified-atts/s plus the two numbers the ISSUE 11 tentpole is
# judged on: set-weighted mean bucket occupancy and p99 submit->verdict
# latency for the critical lane.  The ISSUE 13 probe below reuses the
# same flood harness with a DUPLICATE-heavy shape.
BENCH_PIPELINE_ATTS = int(os.environ.get("BENCH_PIPELINE_ATTS", "2048"))
BENCH_PIPELINE_SUBNETS = int(os.environ.get("BENCH_PIPELINE_SUBNETS", "64"))
BENCH_PIPELINE_WAVES = int(os.environ.get("BENCH_PIPELINE_WAVES", "8"))


def _att_factory(verifier, sks, roots):
    """j -> the j-th distinct WireSignatureSet over `roots`, signed with
    the deterministic bench keys the verifier's table was built from
    (index j -> pks[j % DISTINCT], tiled); signatures memoized so
    repeated j yields byte-identical messages."""
    capacity = len(verifier.table)
    sig_cache = {}

    def att(j):
        vi = j % capacity
        root = roots[j % len(roots)]
        key = vi % DISTINCT
        if (key, root) not in sig_cache:
            sig_cache[(key, root)] = GCC.g2_compress(GTB.sign(sks[key], root))
        return WireSignatureSet.single(vi, root, sig_cache[(key, root)])

    return att


def _drive_flood(pipeline, att, distinct, waves, dup):
    """The shared flood harness (both pipeline probes): `distinct`
    standard attestations in `waves` waves, each published `dup` times
    (relay fan-in), plus two block-critical submissions per wave on the
    short-deadline lane, all through a NetworkProcessor honoring the
    pipeline's backpressure.  Returns (verdicts, dt_s, sorted crit
    submit->verdict latencies)."""
    import threading as _threading

    from lodestar_tpu.bls.verifier import VerifyOptions
    from lodestar_tpu.network.gossip_queues import GossipType
    from lodestar_tpu.network.processor import (
        NetworkProcessor,
        PendingGossipMessage,
    )
    from lodestar_tpu.utils.metrics import Registry

    lat_lock = _threading.Lock()
    crit_lat, futs = [], []

    def submit(ws, critical, peer):
        t0 = time.perf_counter()
        fut = pipeline.verify_signature_sets_async(
            [ws],
            VerifyOptions(batchable=True, priority=critical, peer_id=peer),
        )
        if critical:
            def _done(_f, t0=t0):
                with lat_lock:
                    crit_lat.append(time.perf_counter() - t0)
            fut.add_done_callback(_done)
        futs.append(fut)

    def worker(msg):
        ws, critical = msg.data
        submit(ws, critical, msg.peer_id)

    # private registry: the probe's queue series must not leak into
    # the process-global exposition (tests call this in-process)
    proc = NetworkProcessor(
        worker, [pipeline.can_accept_work], registry=Registry()
    )
    per_wave = max(1, distinct // waves)
    t1 = time.perf_counter()
    j = 0
    for _wave in range(waves):
        for _ in range(per_wave):
            ws = att(j)
            for d in range(dup):
                proc.on_gossip_message(
                    PendingGossipMessage(
                        GossipType.beacon_attestation,
                        (ws, False),
                        peer_id="bench-peer-%d" % d,
                    )
                )
            j += 1
        # block-critical submissions ride the aggregate topic + the
        # pipeline's short-deadline lane (the p99 the records report)
        for _ in range(2):
            proc.on_gossip_message(
                PendingGossipMessage(
                    GossipType.beacon_aggregate_and_proof,
                    (att(j), True),
                    peer_id="bench-peer",
                )
            )
            j += 1
        # drain anything backpressure parked, then next wave
        while any(len(q) for q in proc.queues.values()):
            proc.execute_work()
            time.sleep(0.001)
    verdicts = [f.result(timeout=600) for f in futs]
    dt = time.perf_counter() - t1
    return verdicts, dt, sorted(crit_lat)


def _flood_p99(sorted_lat):
    if not sorted_lat:
        return None
    return sorted_lat[min(len(sorted_lat) - 1, int(0.99 * (len(sorted_lat) - 1)))]


def _probe_pipeline(verifier) -> None:
    t_stage0 = time.monotonic()
    try:
        from lodestar_tpu.bls.pipeline import BlsVerificationPipeline
        from lodestar_tpu.bls.verifier import VerifyOptions

        if not getattr(verifier, "_use_rlc", True):
            _emit_pipeline_skip(
                "pipeline-probe", "LODESTAR_TPU_BLS_RLC=0: RLC disabled"
            )
            return
        sks = [GTB.keygen(b"bench-%d" % i) for i in range(DISTINCT)]
        roots = [
            b"pipeline subnet root %d" % s
            for s in range(BENCH_PIPELINE_SUBNETS)
        ]
        att = _att_factory(verifier, sks, roots)
        pipeline = BlsVerificationPipeline(verifier)

        # hash all subnet roots in one device batch + warm the critical
        # lane's bucket before the timed region (compile/trace is the
        # export cache's job, not this probe's)
        verifier.messages.get_many(roots)
        warm = [att(j) for j in range(128)]
        assert pipeline.verify_signature_sets(
            warm, VerifyOptions(batchable=True)
        ), "pipeline warmup failed verification"
        pipeline.reset_flush_stats()

        verdicts, dt, crit_lat = _drive_flood(
            pipeline, att, BENCH_PIPELINE_ATTS, BENCH_PIPELINE_WAVES, dup=1
        )
        occupancy = pipeline.mean_fill_ratio()
        reasons = {}
        for rec in pipeline.flush_stats():
            reasons[rec["reason"]] = reasons.get(rec["reason"], 0) + 1
        pipeline.close()
        n_ok = sum(1 for v in verdicts if v)
        _phase_mark(
            "pipeline_probe",
            time.monotonic() - t_stage0,
            ok=n_ok == len(verdicts),
            atts=len(verdicts),
        )
        if n_ok != len(verdicts):
            _emit_pipeline_skip(
                "pipeline-probe",
                f"{len(verdicts) - n_ok} valid atts failed verification",
            )
            return
        p99 = _flood_p99(crit_lat)
        atts_per_s = len(verdicts) / dt
        print(
            json.dumps(
                {
                    "metric": "bls_pipeline_verified_atts_per_s",
                    "value": round(atts_per_s, 2),
                    "unit": "atts/s",
                    "vs_baseline": round(atts_per_s / BASELINE_SETS_PER_S, 4),
                    "bucket_occupancy_mean": (
                        round(occupancy, 4) if occupancy is not None else None
                    ),
                    "critical_p99_submit_to_verdict_s": (
                        round(p99, 4) if p99 is not None else None
                    ),
                    "flush_reasons": reasons,
                    "phases": _phase_snapshot(),
                    "slo": _slo_snapshot(),
                    "breaker": _breaker_snapshot(),
                    "memory": _memory_snapshot(),
                }
            ),
            flush=True,
        )
    except Exception as e:  # noqa: BLE001 — probe failures emit a skip record
        _emit_pipeline_skip("pipeline-probe", f"{type(e).__name__}: {e}")


# -- pre-verify aggregation probe (ISSUE 13) --------------------------------
# The same flood harness, DUPLICATE-heavy: every distinct (validator,
# root) message is published BENCH_PREAGG_DUP times (gossip relay
# fan-in) and each subnet root is attested by a committee's worth of
# validators, so the aggregation stage has both dedupe and same-root
# point-adds to exploit.  Reports the tentpole's three numbers:
# effective atts/s (every verdict delivered), verified sets/s (what
# actually reached the pairing), and their ratio — the mean aggregation
# factor the acceptance criteria bound at >= 3.
BENCH_PREAGG_ATTS = int(os.environ.get("BENCH_PREAGG_ATTS", "2048"))
BENCH_PREAGG_SUBNETS = int(os.environ.get("BENCH_PREAGG_SUBNETS", "64"))
BENCH_PREAGG_DUP = int(os.environ.get("BENCH_PREAGG_DUP", "2"))
BENCH_PREAGG_WAVES = int(os.environ.get("BENCH_PREAGG_WAVES", "8"))


def _probe_effective_atts(verifier) -> None:
    t_stage0 = time.monotonic()
    try:
        from lodestar_tpu.bls.pipeline import BlsVerificationPipeline
        from lodestar_tpu.bls.verifier import VerifyOptions

        if not getattr(verifier, "_use_rlc", True):
            _emit_effective_skip(
                "preagg-probe", "LODESTAR_TPU_BLS_RLC=0: RLC disabled"
            )
            return
        if os.environ.get(
            "LODESTAR_TPU_BLS_PREAGG", "1"
        ).strip().lower() in ("0", "false", "no", "off"):
            _emit_effective_skip(
                "preagg-probe", "LODESTAR_TPU_BLS_PREAGG=0: stage disabled"
            )
            return
        sks = [GTB.keygen(b"bench-%d" % i) for i in range(DISTINCT)]
        roots = [
            b"preagg subnet root %d" % s for s in range(BENCH_PREAGG_SUBNETS)
        ]
        att = _att_factory(verifier, sks, roots)
        pipeline = BlsVerificationPipeline(verifier)
        if pipeline._agg is None:
            _emit_effective_skip(
                "preagg-probe", "verifier cannot aggregate (no stage)"
            )
            pipeline.close()
            return

        # warm on a DISJOINT root namespace: warmup messages must never
        # seed the seen-map/buckets the measured flood then hits, or
        # the dedupe would flatter the timed region
        warm_roots = [
            b"preagg warm root %d" % s for s in range(BENCH_PREAGG_SUBNETS)
        ]
        verifier.messages.get_many(roots + warm_roots)
        warm_att = _att_factory(verifier, sks, warm_roots)
        warm = [warm_att(j) for j in range(128)]
        assert pipeline.verify_signature_sets(
            warm, VerifyOptions(batchable=True)
        ), "preagg warmup failed verification"
        base_stats = pipeline.agg_stats()

        distinct = max(1, BENCH_PREAGG_ATTS // BENCH_PREAGG_DUP)
        verdicts, dt, crit_lat = _drive_flood(
            pipeline, att, distinct, BENCH_PREAGG_WAVES, dup=BENCH_PREAGG_DUP
        )
        stats = pipeline.agg_stats()
        pipeline.close()
        n_ok = sum(1 for v in verdicts if v)
        _phase_mark(
            "preagg_probe",
            time.monotonic() - t_stage0,
            ok=n_ok == len(verdicts),
            atts=len(verdicts),
        )
        if n_ok != len(verdicts):
            _emit_effective_skip(
                "preagg-probe",
                f"{len(verdicts) - n_ok} valid atts failed verification",
            )
            return
        contributions = stats["contributions"] - base_stats["contributions"]
        sets_out = stats["sets"] - base_stats["sets"]
        if sets_out <= 0:
            _emit_effective_skip(
                "preagg-probe", "aggregation stage produced no sets"
            )
            return
        factor = contributions / sets_out
        p99 = _flood_p99(crit_lat)
        atts_per_s = len(verdicts) / dt
        print(
            json.dumps(
                {
                    "metric": "bls_pipeline_effective_atts_per_s",
                    "value": round(atts_per_s, 2),
                    "unit": "atts/s",
                    "vs_baseline": round(atts_per_s / BASELINE_SETS_PER_S, 4),
                    "verified_sets_per_s": round(sets_out / dt, 2),
                    "aggregation_factor_mean": round(factor, 4),
                    "dedup": stats["dedup"] - base_stats["dedup"],
                    "seen_served": (
                        stats["seen_served"] - base_stats["seen_served"]
                    ),
                    "bisections": (
                        stats["bisections"] - base_stats["bisections"]
                    ),
                    "critical_p99_submit_to_verdict_s": (
                        round(p99, 4) if p99 is not None else None
                    ),
                    "phases": _phase_snapshot(),
                    "slo": _slo_snapshot(),
                    "breaker": _breaker_snapshot(),
                    "memory": _memory_snapshot(),
                }
            ),
            flush=True,
        )
    except Exception as e:  # noqa: BLE001 — probe failures emit a skip record
        _emit_effective_skip("preagg-probe", f"{type(e).__name__}: {e}")


# -- aggregate-forward probe (ISSUE 19) -------------------------------------
# The preagg flood again, but with an AggregateForwarder on the layer
# hook and an in-memory bus downstream: every verified multi-member
# layer re-publishes as ONE packed SignedAggregateAndProof instead of
# its members' individual subnet messages.  Reports the tentpole's two
# numbers with the headline's skip/null semantics:
#   gossip_bytes_per_verified_att — downstream bytes carried per
#     distinct verified attestation (packs + raw forwards for any
#     attestation no pack covered; lower is better),
#   aggregate_forward_factor — the raw-sync downstream cost for the
#     same attestations divided by the aggregate-forward cost (the
#     acceptance criteria bound this at >= 3).


def _probe_aggregate_forward(verifier) -> None:
    t_stage0 = time.monotonic()
    try:
        from lodestar_tpu.bls.pipeline import BlsVerificationPipeline
        from lodestar_tpu.bls.verifier import VerifyOptions
        from lodestar_tpu.network.forwarding import (
            AggregateForwarder,
            aggfwd_enabled,
        )
        from lodestar_tpu.network.gossip import (
            GossipTopicName,
            InMemoryGossipBus,
            encode_message,
            topic_string,
        )
        from lodestar_tpu.types import Attestation

        if not getattr(verifier, "_use_rlc", True):
            _emit_aggfwd_skip(
                "aggfwd-probe", "LODESTAR_TPU_BLS_RLC=0: RLC disabled"
            )
            return
        if os.environ.get(
            "LODESTAR_TPU_BLS_PREAGG", "1"
        ).strip().lower() in ("0", "false", "no", "off"):
            _emit_aggfwd_skip(
                "aggfwd-probe", "LODESTAR_TPU_BLS_PREAGG=0: stage disabled"
            )
            return
        if not aggfwd_enabled():
            _emit_aggfwd_skip(
                "aggfwd-probe",
                "LODESTAR_TPU_BLS_AGGFWD=0: aggregate-forward disabled",
            )
            return
        sks = [GTB.keygen(b"bench-%d" % i) for i in range(DISTINCT)]
        roots = [
            b"aggfwd subnet root %d" % s for s in range(BENCH_PREAGG_SUBNETS)
        ]
        att = _att_factory(verifier, sks, roots)
        pipeline = BlsVerificationPipeline(verifier)
        if pipeline._agg is None:
            _emit_aggfwd_skip(
                "aggfwd-probe", "verifier cannot aggregate (no stage)"
            )
            pipeline.close()
            return

        # the downstream side: an in-memory bus with one subscriber
        # counting what actually crosses the wire
        digest = b"\xbe\x4c\x19\x00"
        bus = InMemoryGossipBus()
        agg_topic = topic_string(
            digest, GossipTopicName.beacon_aggregate_and_proof
        )
        downstream = {"msgs": 0, "bytes": 0}

        def _rx(_topic, payload):
            downstream["msgs"] += 1
            downstream["bytes"] += len(payload)

        bus.subscribe("bench-downstream", agg_topic, _rx)
        fwd = AggregateForwarder(
            bus=bus, node_id="bench-self", fork_digest=digest
        )
        committee = tuple(range(len(verifier.table)))
        zero = b"\x00" * 32
        for s, root in enumerate(roots):
            fwd.register_root(
                root,
                0,
                {
                    "slot": 0,
                    "index": s,
                    "beacon_block_root": zero,
                    "source": {"epoch": 0, "root": zero},
                    "target": {"epoch": 0, "root": zero},
                },
                committee,
            )
        pipeline.set_layer_forward(fwd.on_layer_verified)

        # what the raw-sync path forwards downstream per attestation: one
        # encoded single-bit Attestation gossip message (committee-width
        # bits, so the size is the honest apples-to-apples baseline).
        # The signature must be INCOMPRESSIBLE like a real G2 point — an
        # all-zero placeholder would let snappy flatter the baseline
        import hashlib as _hashlib

        opaque_sig = b"".join(
            _hashlib.sha256(b"aggfwd raw sig %d" % i).digest()
            for i in range(3)
        )
        raw_single = {
            "aggregation_bits": [i == 0 for i in range(len(committee))],
            "data": {
                "slot": 0,
                "index": 0,
                "beacon_block_root": zero,
                "source": {"epoch": 0, "root": zero},
                "target": {"epoch": 0, "root": zero},
            },
            "signature": opaque_sig,
        }
        raw_att_bytes = len(encode_message(Attestation.serialize(raw_single)))

        # warm on a DISJOINT root namespace (same rule as the preagg
        # probe): unregistered warm roots hit the forwarder's skip path,
        # never its publish path
        warm_roots = [
            b"aggfwd warm root %d" % s for s in range(BENCH_PREAGG_SUBNETS)
        ]
        verifier.messages.get_many(roots + warm_roots)
        warm_att = _att_factory(verifier, sks, warm_roots)
        warm = [warm_att(j) for j in range(128)]
        assert pipeline.verify_signature_sets(
            warm, VerifyOptions(batchable=True)
        ), "aggfwd warmup failed verification"
        base = fwd.stats_snapshot()

        distinct = max(1, BENCH_PREAGG_ATTS // BENCH_PREAGG_DUP)
        verdicts, dt, crit_lat = _drive_flood(
            pipeline, att, distinct, BENCH_PREAGG_WAVES, dup=BENCH_PREAGG_DUP
        )
        stats = fwd.stats_snapshot()
        pipeline.close()
        n_ok = sum(1 for v in verdicts if v)
        _phase_mark(
            "aggfwd_probe",
            time.monotonic() - t_stage0,
            ok=n_ok == len(verdicts),
            atts=len(verdicts),
        )
        if n_ok != len(verdicts):
            _emit_aggfwd_skip(
                "aggfwd-probe",
                f"{len(verdicts) - n_ok} valid atts failed verification",
            )
            return
        published = stats["published"] - base["published"]
        packed_bytes = stats["bytes_published"] - base["bytes_published"]
        covered = stats["members_forwarded"] - base["members_forwarded"]
        if published <= 0:
            _emit_aggfwd_skip(
                "aggfwd-probe", "forwarder published no packed layers"
            )
            return
        # distinct standard-lane singles the flood submitted: replay
        # _drive_flood's j sequence (per-wave singles, +2 critical) and
        # count distinct (validator, root) messages — the att factory
        # wraps at table capacity, so large floods repeat earlier
        # messages byte-for-byte, and duplicates are seen-cache hits in
        # BOTH modes (neither forwards them)
        capacity = len(verifier.table)
        per_wave = max(1, distinct // BENCH_PREAGG_WAVES)
        singles = set()
        j = 0
        for _wave in range(BENCH_PREAGG_WAVES):
            for _ in range(per_wave):
                singles.add((j % capacity, j % len(roots)))
                j += 1
            j += 2  # the wave's critical-lane submissions
        n_atts = len(singles)
        uncovered = max(0, n_atts - covered)
        raw_bytes = raw_att_bytes * n_atts
        aggfwd_bytes = packed_bytes + raw_att_bytes * uncovered
        bytes_per_att = aggfwd_bytes / n_atts
        factor = raw_bytes / aggfwd_bytes
        p99 = _flood_p99(crit_lat)
        common = {
            "raw_bytes_per_att": raw_att_bytes,
            "packs_published": published,
            "atts_covered_by_packs": covered,
            "atts_uncovered": uncovered,
            "downstream_msgs": downstream["msgs"],
            "downstream_bytes": downstream["bytes"],
            "critical_p99_submit_to_verdict_s": (
                round(p99, 4) if p99 is not None else None
            ),
            "phases": _phase_snapshot(),
            "slo": _slo_snapshot(),
            "breaker": _breaker_snapshot(),
            "memory": _memory_snapshot(),
        }
        print(
            json.dumps(
                {
                    "metric": "gossip_bytes_per_verified_att",
                    "value": round(bytes_per_att, 2),
                    "unit": "bytes/att",
                    "vs_baseline": None,
                    **common,
                }
            ),
            flush=True,
        )
        print(
            json.dumps(
                {
                    "metric": "aggregate_forward_factor",
                    "value": round(factor, 4),
                    "unit": "ratio",
                    "vs_baseline": None,
                    **common,
                }
            ),
            flush=True,
        )
    except Exception as e:  # noqa: BLE001 — probe failures emit a skip record
        _emit_aggfwd_skip("aggfwd-probe", f"{type(e).__name__}: {e}")


def build_decoded_inputs():
    sks = [GTB.keygen(b"bench-%d" % i) for i in range(DISTINCT)]
    pks = [GTB.sk_to_pk(sk) for sk in sks]
    msgs = [b"bench signing root %d" % (i % 4) for i in range(DISTINCT)]
    hms = [hash_to_g2(m) for m in msgs]
    sigs = [GTB.sign(sk, m) for sk, m in zip(sks, msgs)]

    reps = BATCH // DISTINCT
    tx = jnp.asarray(LY.encode_batch([p[0] for p in pks]))
    ty = jnp.asarray(LY.encode_batch([p[1] for p in pks]))
    idx = jnp.asarray(np.tile(np.arange(DISTINCT, dtype=np.int32), reps)[:, None])
    kmask = jnp.ones((BATCH, 1), jnp.int32)

    def enc(vals):
        return jnp.asarray(np.tile(LY.encode_plain_batch(vals), (1, reps)))

    planes = (
        enc([m[0][0] for m in hms]), enc([m[0][1] for m in hms]),
        enc([m[1][0] for m in hms]), enc([m[1][1] for m in hms]),
        enc([s[0][0] for s in sigs]), enc([s[0][1] for s in sigs]),
        enc([s[1][0] for s in sigs]), enc([s[1][1] for s in sigs]),
    )
    sig_inf = jnp.zeros((BATCH,), jnp.int32)
    valid = jnp.ones((BATCH,), jnp.int32)
    return (tx, ty, idx, kmask) + planes + (sig_inf,), valid


# -- device-fault recovery probe (ISSUE 14) ---------------------------------
# bls_device_fault_recovery_seconds: inject a device-dispatch fault
# mid-flood (every _device_call raises), wait for the breaker to trip
# into the degraded host path, heal the device, and report the time
# from trip to the first confirmed DEVICE-path verdict after the canary
# re-probe restores dispatch.  Lower is better (unit "s").

BENCH_BREAKER_FLOOD_ATTS = int(
    os.environ.get("BENCH_BREAKER_FLOOD_ATTS", "256")
)


def _emit_breaker_skip(stage: str, detail: str) -> None:
    _emit_failure(
        stage, detail, metric="bls_device_fault_recovery_seconds", unit="s"
    )


def _probe_breaker_recovery(verifier) -> None:
    t_stage0 = time.monotonic()
    try:
        from lodestar_tpu.bls.pipeline import BlsVerificationPipeline
        from lodestar_tpu.bls.verifier import VerifyOptions

        sup = getattr(verifier, "supervisor", None)
        if sup is None or not sup.active:
            _emit_breaker_skip(
                "breaker-probe",
                "LODESTAR_TPU_BLS_BREAKER=0: supervision disabled",
            )
            return
        if sup.is_open():
            _emit_breaker_skip(
                "breaker-probe", "breaker already open before the probe"
            )
            return
        # DISJOINT root namespaces per stage (the PR 13 probe's lesson):
        # on the real verifier the aggregation stage's seen-map serves
        # exact repeats with zero device work, so reused identities
        # would flatter both the flood and the device-path confirmation
        sks = [GTB.keygen(b"bench-%d" % i) for i in range(DISTINCT)]
        warm_att = _att_factory(
            verifier, sks, [b"breaker warm root %d" % s for s in range(16)]
        )
        flood_roots = [b"breaker flood root %d" % s for s in range(16)]
        att = _att_factory(verifier, sks, flood_roots)
        confirm_att = _att_factory(
            verifier,
            sks,
            [b"breaker confirm root %d" % s for s in range(16)],
        )
        pipeline = BlsVerificationPipeline(verifier)
        verifier.messages.get_many(flood_roots)
        warm = [warm_att(j) for j in range(128)]
        assert pipeline.verify_signature_sets(
            warm, VerifyOptions(batchable=True)
        ), "breaker-probe warmup failed verification"

        # shrink the re-probe backoff so the number measures trip ->
        # canary -> device verdict, not a production-sized wait
        sup.backoff_initial_s = 0.1
        real_call = verifier._device_call
        fail = {"on": False}

        def flaky(name, fn, args):
            if fail["on"]:
                raise RuntimeError(
                    "bench-injected device fault: backend UNAVAILABLE"
                )
            return real_call(name, fn, args)

        verifier._device_call = flaky
        try:
            futs = []
            half = BENCH_BREAKER_FLOOD_ATTS // 2
            for j in range(BENCH_BREAKER_FLOOD_ATTS):
                if j == half:
                    fail["on"] = True  # the fault lands MID-flood
                    t_fault = time.perf_counter()
                futs.append(
                    pipeline.verify_signature_sets_async(
                        [att(j)], VerifyOptions(batchable=True)
                    )
                )
            # zero lost verdicts: every submission resolves (valid atts
            # stay valid through the host fallback)
            verdicts = [f.result(timeout=300) for f in futs]
            if not all(verdicts):
                _emit_breaker_skip(
                    "breaker-probe",
                    f"{len(verdicts) - sum(verdicts)} valid atts failed "
                    "under the fault",
                )
                return
            deadline = time.perf_counter() + 120.0
            while not sup.is_open() and time.perf_counter() < deadline:
                time.sleep(0.005)
            if not sup.is_open():
                _emit_breaker_skip(
                    "breaker-probe", "fault never tripped the breaker"
                )
                return
            # heal: the auto re-probe canary restores the device path
            fail["on"] = False
            while sup.is_open() and time.perf_counter() < deadline:
                time.sleep(0.005)
            if sup.is_open():
                _emit_breaker_skip(
                    "breaker-probe", "breaker never re-closed after heal"
                )
                return
            # confirm an actual device-path verdict post-recovery —
            # FRESH identities, so neither the aggregation seen-map nor
            # any warm cache can serve them without touching the device
            ok = pipeline.verify_signature_sets(
                [confirm_att(j) for j in range(64)],
                VerifyOptions(batchable=True),
            )
            t_recovered = time.perf_counter()
            if not ok:
                _emit_breaker_skip(
                    "breaker-probe", "post-recovery device verify failed"
                )
                return
            # snapshot while the supervisor is still alive (close()
            # deregisters it from the process-wide breaker registry)
            breaker_field = _breaker_snapshot()
        finally:
            verifier._device_call = real_call
            pipeline.close()
        recovery = t_recovered - t_fault
        _phase_mark(
            "breaker_probe", time.monotonic() - t_stage0, ok=True
        )
        print(
            json.dumps(
                {
                    "metric": "bls_device_fault_recovery_seconds",
                    "value": round(recovery, 4),
                    "unit": "s",
                    "vs_baseline": None,
                    "breaker_trips": sup.trip_count,
                    "time_in_degraded_s": round(
                        sup.time_in_degraded_s(), 4
                    ),
                    "phases": _phase_snapshot(),
                    "slo": _slo_snapshot(),
                    "breaker": breaker_field,
                    "memory": _memory_snapshot(),
                }
            ),
            flush=True,
        )
    except Exception as e:  # noqa: BLE001 — probe failures emit a skip record
        _emit_breaker_skip("breaker-probe", f"{type(e).__name__}: {e}")


def main_decoded():
    t_build0 = time.perf_counter()
    args, valid = build_decoded_inputs()
    fn = KV.verify_batch_device
    _phase_mark("world_build", time.perf_counter() - t_build0)

    t_warm0 = time.perf_counter()
    rand = jnp.asarray(BK.make_rand_words(BATCH))
    ok, _ = fn(*args, rand, valid)
    assert bool(ok), "bench inputs failed verification"
    _phase_mark("warmup", time.perf_counter() - t_warm0)

    t0 = time.perf_counter()
    ok_list = []
    for _ in range(REPEATS):
        rand = jnp.asarray(BK.make_rand_words(BATCH))
        ok, _sub = fn(*args, rand, valid)
        ok_list.append(ok)
    for ok in ok_list:
        ok.block_until_ready()
    dt = time.perf_counter() - t0
    assert all(bool(o) for o in ok_list)
    _phase_mark("timed_region", dt, jobs=REPEATS, sets=BATCH * REPEATS)

    sets_per_s = BATCH * REPEATS / dt
    print(
        json.dumps(
            {
                "metric": _metric_name(),
                "value": round(sets_per_s, 2),
                "unit": "sets/s",
                "vs_baseline": round(sets_per_s / BASELINE_SETS_PER_S, 4),
                "phases": _phase_snapshot(),
                "slo": _slo_snapshot(),
                "breaker": _breaker_snapshot(),
                "memory": _memory_snapshot(),
            }
        )
    )


if __name__ == "__main__":
    _arm_watchdog()
    try:
        if os.environ.get("BENCH_MODE", "wire") == "decoded":
            sys.exit(main_decoded())
        sys.exit(main_wire())
    except Exception as e:  # noqa: BLE001 — diagnosis line, then re-raise
        _emit_failure("run", f"{type(e).__name__}: {e}")
        raise
