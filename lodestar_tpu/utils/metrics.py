"""Minimal Prometheus-style metrics registry.

Reproduces the reference's `lodestar_bls_thread_pool_*` metric family
(reference: packages/beacon-node/src/metrics/metrics/lodestar.ts:357-430 —
queueLength, jobWaitTime, timePerSigSet, batchRetries, batchSigsSuccess,
latencyToWorker/FromWorker, per-worker jobsWorkerTime) so the shipped
Grafana dashboard (reference: dashboards/lodestar_bls_thread_pool.json)
reads identically against the TPU backend.  Text exposition follows the
Prometheus format; no external dependency.
"""

from __future__ import annotations

import threading
from typing import Dict, List, Optional, Sequence, Tuple


class Counter:
    def __init__(self, name: str, help_: str):
        self.name, self.help = name, help_
        self._v = 0.0
        self._lock = threading.Lock()

    def inc(self, amount: float = 1.0) -> None:
        with self._lock:
            self._v += amount

    @property
    def value(self) -> float:
        return self._v

    def expose(self) -> List[str]:
        return [
            f"# HELP {self.name} {self.help}",
            f"# TYPE {self.name} counter",
            f"{self.name} {self._v}",
        ]


class Gauge:
    def __init__(self, name: str, help_: str):
        self.name, self.help = name, help_
        self._v = 0.0

    def set(self, v: float) -> None:
        self._v = v

    def inc(self, amount: float = 1.0) -> None:
        self._v += amount

    def dec(self, amount: float = 1.0) -> None:
        self._v -= amount

    @property
    def value(self) -> float:
        return self._v

    def expose(self) -> List[str]:
        return [
            f"# HELP {self.name} {self.help}",
            f"# TYPE {self.name} gauge",
            f"{self.name} {self._v}",
        ]


class LabeledGauge:
    """Gauge with one label dimension (the reference's per-worker
    jobsWorkerTime gauge, labelNames: ["workerId"])."""

    TYPE = "gauge"

    def __init__(self, name: str, help_: str, label: str):
        self.name, self.help, self.label = name, help_, label
        self._v: Dict[str, float] = {}
        self._lock = threading.Lock()

    def inc(self, label_value: str, amount: float) -> None:
        with self._lock:
            self._v[label_value] = self._v.get(label_value, 0.0) + amount

    def get(self, label_value: str) -> float:
        return self._v.get(label_value, 0.0)

    def set(self, label_value: str, v: float) -> None:
        """Idempotent resample (ledger mirroring)."""
        with self._lock:
            self._v[label_value] = v

    def label_values(self) -> List[str]:
        with self._lock:
            return sorted(self._v)

    def expose(self) -> List[str]:
        out = [
            f"# HELP {self.name} {self.help}",
            f"# TYPE {self.name} {self.TYPE}",
        ]
        with self._lock:  # hot paths insert labels concurrently
            items = sorted(self._v.items())
        for lv, v in items:
            out.append(f'{self.name}{{{self.label}="{lv}"}} {v}')
        return out


class LabeledCounter(LabeledGauge):
    """Monotonic labeled counter (exposition TYPE counter — rate() and
    increase() in Prometheus need the counter contract)."""

    TYPE = "counter"


def _fmt_le(bound: float) -> str:
    """Prometheus-text-format `le` label value, matching the official
    python client's floatToGoString style: `+Inf` for the terminal
    bucket, else the float repr (`1.0`, `0.005`, `1e-05`) — NOT the
    raw python value (`le="1"` for an int bucket is what made the old
    exposition non-conformant across clients)."""
    if bound == float("inf"):
        return "+Inf"
    return repr(float(bound))


class Histogram:
    def __init__(self, name: str, help_: str, buckets: Sequence[float]):
        self.name, self.help = name, help_
        # finite, deduplicated bounds; +Inf is always emitted explicitly
        self.buckets = sorted(
            {float(b) for b in buckets if float(b) != float("inf")}
        )
        self._counts = [0] * (len(self.buckets) + 1)
        self._sum = 0.0
        self._n = 0
        self._lock = threading.Lock()

    def observe(self, v: float) -> None:
        with self._lock:
            self._sum += v
            self._n += 1
            for i, b in enumerate(self.buckets):
                if v <= b:
                    self._counts[i] += 1
                    return
            self._counts[-1] += 1

    @property
    def count(self) -> int:
        return self._n

    @property
    def sum(self) -> float:
        return self._sum

    def _sample_lines(self, label_prefix: str = "") -> List[str]:
        """The `_bucket`/`_sum`/`_count` sample lines; `label_prefix`
        holds extra `k="v",` pairs to merge ahead of `le` (the
        LabeledHistogram path)."""
        out: List[str] = []
        cum = 0
        for b, c in zip(self.buckets, self._counts):
            cum += c
            out.append(
                f'{self.name}_bucket{{{label_prefix}le="{_fmt_le(b)}"}} {cum}'
            )
        cum += self._counts[-1]
        out.append(f'{self.name}_bucket{{{label_prefix}le="+Inf"}} {cum}')
        if label_prefix:
            bare = label_prefix.rstrip(",")
            out.append(f"{self.name}_sum{{{bare}}} {self._sum}")
            out.append(f"{self.name}_count{{{bare}}} {self._n}")
        else:
            out.append(f"{self.name}_sum {self._sum}")
            out.append(f"{self.name}_count {self._n}")
        return out

    def expose(self) -> List[str]:
        return [
            f"# HELP {self.name} {self.help}",
            f"# TYPE {self.name} histogram",
        ] + self._sample_lines()


class LabeledHistogram:
    """Histogram with one label dimension (the import-phase breakdown's
    `phase`, the gossip queues' `topic`).  Exposition emits ONE
    HELP/TYPE pair and per-label-value bucket/sum/count series with
    the extra label merged ahead of `le` — conformant text format."""

    def __init__(self, name: str, help_: str, label: str, buckets: Sequence[float]):
        self.name, self.help, self.label = name, help_, label
        self._buckets = list(buckets)
        self._children: Dict[str, Histogram] = {}
        self._lock = threading.Lock()

    def child(self, label_value: str) -> Histogram:
        h = self._children.get(label_value)
        if h is None:
            with self._lock:
                h = self._children.setdefault(
                    label_value, Histogram(self.name, self.help, self._buckets)
                )
        return h

    def observe(self, label_value: str, v: float) -> None:
        self.child(label_value).observe(v)

    def count(self, label_value: str) -> int:
        c = self._children.get(label_value)
        return c.count if c is not None else 0

    def sum(self, label_value: str) -> float:
        c = self._children.get(label_value)
        return c.sum if c is not None else 0.0

    def label_values(self) -> List[str]:
        with self._lock:
            return sorted(self._children)

    def expose(self) -> List[str]:
        out = [
            f"# HELP {self.name} {self.help}",
            f"# TYPE {self.name} histogram",
        ]
        with self._lock:  # hot paths insert children concurrently
            children = sorted(self._children.items())
        for lv, child in children:
            out.extend(child._sample_lines(f'{self.label}="{lv}",'))
        return out


class Registry:
    def __init__(self) -> None:
        self._metrics: Dict[str, object] = {}
        # the process-global instance is registered into from hot-path
        # threads (kernel builds, export-cache lookups): creation must
        # be atomic or a racing first registration loses its counts
        self._lock = threading.Lock()

    def counter(self, name: str, help_: str) -> Counter:
        return self._get(name, lambda: Counter(name, help_))

    def gauge(self, name: str, help_: str) -> Gauge:
        return self._get(name, lambda: Gauge(name, help_))

    def histogram(self, name: str, help_: str, buckets) -> Histogram:
        return self._get(name, lambda: Histogram(name, help_, buckets))

    def labeled_gauge(self, name: str, help_: str, label: str) -> LabeledGauge:
        return self._get(name, lambda: LabeledGauge(name, help_, label))

    def labeled_counter(self, name: str, help_: str, label: str) -> "LabeledCounter":
        return self._get(name, lambda: LabeledCounter(name, help_, label))

    def labeled_histogram(
        self, name: str, help_: str, label: str, buckets
    ) -> LabeledHistogram:
        return self._get(
            name, lambda: LabeledHistogram(name, help_, label, buckets)
        )

    def get(self, name: str) -> Optional[object]:
        """Registered metric by name (None when absent) — the public
        read path for snapshot consumers (observability/sinks.py)."""
        return self._metrics.get(name)

    def _get(self, name, factory):
        with self._lock:
            if name not in self._metrics:
                self._metrics[name] = factory()
            return self._metrics[name]

    def expose(self) -> str:
        with self._lock:
            metrics = list(self._metrics.values())
        lines: List[str] = []
        for m in metrics:
            lines.extend(m.expose())  # type: ignore[attr-defined]
        return "\n".join(lines) + "\n"


# Process-global registry: instrumentation that is inherently
# per-PROCESS — kernel compiles, export-cache hits, tracer-derived span
# histograms — lands here so it reaches /metrics without threading a
# per-node Registry through the kernel layers.  utils/metrics_server.py
# merges it into every exposition.
_GLOBAL_REGISTRY = Registry()


def global_registry() -> Registry:
    return _GLOBAL_REGISTRY


_SECONDS = [0.001, 0.005, 0.01, 0.05, 0.1, 0.5, 1, 5]


class BlsPoolMetrics:
    """The lodestar_bls_thread_pool_* family, verbatim names.

    Reference: packages/beacon-node/src/metrics/metrics/lodestar.ts:357-430.
    """

    def __init__(self, registry: Optional[Registry] = None):
        r = registry or Registry()
        self.registry = r
        p = "lodestar_bls_thread_pool_"
        self.queue_length = r.gauge(p + "queue_length", "Queued verification jobs")
        self.workers_busy = r.gauge(p + "workers_busy", "Busy device streams")
        self.job_wait_time = r.histogram(
            p + "queue_job_wait_time_seconds", "Time a job waits in queue", _SECONDS
        )
        self.job_time = r.histogram(
            p + "job_time_seconds", "Device time per job", _SECONDS
        )
        # reference name: lodestar_bls_worker_thread_time_per_sigset_seconds
        self.time_per_sig_set = r.histogram(
            "lodestar_bls_worker_thread_time_per_sigset_seconds",
            "Time to verify each sigset on the device path",
            [1e-5, 1e-4, 0.5e-3, 1e-3, 2e-3, 5e-3, 1e-2],
        )
        # main thread <-> device boundary latencies + per-worker time
        # (reference: lodestar.ts:407-424, multithread/types.ts:26-38)
        self.latency_to_worker = r.histogram(
            p + "latency_to_worker",
            "Time from submitting the job to the device dispatch starting",
            [0.001, 0.003, 0.01, 0.03, 0.1],
        )
        self.latency_from_worker = r.histogram(
            p + "latency_from_worker",
            "Time from the device result being ready to futures settling",
            [0.001, 0.003, 0.01, 0.03, 0.1],
        )
        self.jobs_worker_time = r.labeled_gauge(
            p + "time_seconds_sum",
            "Total time spent verifying signature sets on the device",
            "workerId",
        )
        self.main_thread_time = r.histogram(
            p + "main_thread_time_seconds",
            "Time to verify signatures on the main thread (fast path)",
            [0],
        )
        self.total_job_groups_started = r.counter(
            p + "job_groups_started_total", "Job groups started"
        )
        self.total_jobs_started = r.counter(
            p + "jobs_started_total", "Jobs started"
        )
        self.total_sig_sets_started = r.counter(
            p + "sig_sets_started_total", "Signature sets started"
        )
        self.success_jobs = r.counter(
            p + "success_jobs_signature_sets_count", "Sig sets verified OK"
        )
        self.error_jobs = r.counter(
            p + "error_jobs_signature_sets_count", "Error-ed signature sets"
        )
        self.batch_retries = r.counter(
            p + "batch_retries_total", "Batches re-verified set-by-set"
        )
        self.batch_sigs_success = r.counter(
            p + "batch_sigs_success_total", "Sig sets verified in a batch"
        )
        self.batchable_sigs = r.counter(
            p + "batchable_sigs_count", "Sig sets submitted as batchable"
        )
        # RLC batch-mode observability (ISSUE 10): how often the one-
        # multi-pairing fast path fails and what the bisection fallback
        # costs when it does
        self.rlc_fallback = r.counter(
            "lodestar_bls_rlc_fallback_total",
            "RLC batch checks that failed and fell back to bisection "
            "or per-set retry",
        )
        self.rlc_bisect_depth = r.histogram(
            "lodestar_bls_rlc_bisect_depth",
            "Halving depth needed to isolate bad sets in a failed RLC batch",
            [1, 2, 3, 4, 5, 6, 8, 11],
        )
        self.invalid_sets = r.counter(
            p + "invalid_sig_sets_count", "Sig sets that failed verification"
        )
        # hot-path shape observability (ISSUE 8): per-call batch size and
        # host-vs-device wall time — the series the batching ROADMAP
        # items need to prove their wins
        self.batch_size = r.histogram(
            "lodestar_bls_batch_size",
            "Signature sets per verify_signature_sets call",
            [1, 2, 4, 8, 16, 32, 64, 128, 256, 512, 1024, 2048],
        )
        self.verify_seconds = r.labeled_histogram(
            "lodestar_bls_verify_seconds",
            "Wall time per verify call by phase (host prep, device sync, total)",
            "phase",
            _SECONDS,
        )
        # the steps of phase="host", timed where the work happens
        # (observability.timed_span): field, device_hash and affine in
        # MessageCache on a miss; encode and launch in begin_job
        self.job_host_step = r.labeled_histogram(
            "lodestar_bls_job_host_step_seconds",
            "Host wall time per verifier job step (field, device_hash, "
            "affine, encode, launch)",
            "step",
            _SECONDS,
        )
        # accumulate-and-flush pipeline observability (ISSUE 11): how
        # full the shape buckets are when they dispatch, why they
        # dispatched, and how much work is resident end-to-end
        self.bucket_fill_ratio = r.histogram(
            "lodestar_bls_bucket_fill_ratio",
            "Signature sets per flush over the padded device N-bucket",
            [0.125, 0.25, 0.375, 0.5, 0.625, 0.75, 0.875, 1.0],
        )
        self.flush_reason = r.labeled_counter(
            "lodestar_bls_flush_reason_total",
            "Pipeline bucket flushes by trigger (fill = exact bucket | "
            "spill = partial, pushed out by an overshooting job | "
            "deadline | idle = lone critical job with nothing to "
            "coalesce against | close)",
            "reason",
        )
        self.pipeline_pending_sets = r.gauge(
            "lodestar_bls_pipeline_pending_sets",
            "Buffered + queued + in-flight signature sets (high-water unit)",
        )
        # pre-verify aggregation stage (ISSUE 13, bls/aggregator.py):
        # how many gossip messages each verified set carries
        self.aggregation_factor = r.histogram(
            "lodestar_bls_aggregation_factor",
            "Contributions per verified signature set at each "
            "aggregation-stage flush (dedupe + same-root point-adds)",
            [1.0, 1.5, 2.0, 3.0, 4.0, 6.0, 8.0, 12.0, 16.0, 32.0],
        )
        self.preagg_contributions = r.counter(
            "lodestar_bls_preagg_contributions_total",
            "Signature-set submissions routed through the pre-verify "
            "aggregation stage",
        )
        self.preagg_sets = r.counter(
            "lodestar_bls_preagg_sets_total",
            "Aggregated/leaf signature sets the stage handed to the "
            "verify path",
        )
        self.preagg_dedup = r.counter(
            "lodestar_bls_preagg_dedup_total",
            "Exact-duplicate contributions sharing an in-flight twin's "
            "verdict",
        )
        self.preagg_seen_served = r.counter(
            "lodestar_bls_preagg_seen_served_total",
            "Contributions served from the resolved-verdict seen-map "
            "with zero device work",
        )
        self.preagg_bisections = r.counter(
            "lodestar_bls_preagg_bisections_total",
            "Failed aggregates split contributor-wise for attribution",
        )


class BlsSingleThreadMetrics:
    """The lodestar_bls_single_thread_* family (reference:
    lodestar.ts:433-446) — the CPU fallback verifier's timings."""

    def __init__(self, registry: Optional[Registry] = None):
        r = registry or Registry()
        self.registry = r
        self.duration = r.histogram(
            "lodestar_bls_single_thread_time_seconds",
            "Time to verify signatures with single thread mode",
            [0],
        )
        self.time_per_sig_set = r.histogram(
            "lodestar_bls_single_thread_time_per_sigset_seconds",
            "Time to verify each sigset with single thread mode",
            [0.5e-3, 0.75e-3, 1e-3, 1.5e-3, 2e-3, 5e-3],
        )
