"""Accumulate-and-flush verification pipeline — shape-bucketed,
deadline-driven batching from gossip to pairing (ISSUE 11 tentpole).

PR 10 made the device side cheap (one final exponentiation per N-set RLC
job); this module builds the FEED.  The flat 100 ms window of
`BlsVerifierService` coalesces whatever arrives, so at mainnet rates
(~1.8k atts/s spread over 64 subnets) the 128/512 N-buckets dispatch
mostly padding.  *Aggregated Signature Gossip* (arXiv:1911.04698) and
the EdDSA/BLS committee-consensus study (arXiv:2302.00418) both locate
the batch-verification win at the ACCUMULATION layer, not the pairing —
so the pipeline accumulates:

  - **Shape buckets.**  Batchable submissions coalesce ACROSS gossip
    topics/subnets into per-(kind, K-bucket, lane) accumulators — the
    exact shape classes the verifier's stage programs are built for
    (each saved to the export cache, `kernels/launch.py`).  A bucket
    that exactly fills an N-bucket (verifier.N_BUCKETS) flushes
    IMMEDIATELY: waiting longer can only burn deadline latency or spill
    into the next, twice-as-large bucket.
  - **Priority lanes.**  Block-critical sets (proposer signatures,
    aggregate-and-proof — `VerifyOptions(priority=True)`) ride a SHORT
    deadline lane so they are never starved behind subnet-attestation
    fill; plain subnet attestations ride a longer window to maximize
    bucket occupancy.  Non-batchable jobs (block import) bypass
    buffering entirely, exactly as in the base service.  A critical
    job submitted into an otherwise-IDLE pipeline (no queued groups,
    no in-flight device work, no other accumulating bucket) flushes
    immediately (`reason=idle`): the window only buys occupancy when
    something could coalesce with it, and synchronous submitters —
    the full-node gossip loop verifying aggregates one at a time —
    must not serialize a pure lane-window wait per message.
  - **Deadlines anchor on the oldest set.**  Each accumulator's flush
    timer is `oldest_job.t_submit + lane_wait` (stamped before lock
    acquisition), so p99 submit->flush latency is bounded by the lane
    window regardless of contention (ISSUE 11 satellite).
  - **End-to-end backpressure.**  `can_accept_work()` goes False when
    buffered + queued + in-flight SETS cross the high-water mark — the
    signal `network/processor.py` throttles on; queue overflow drops
    then charge the flooding peer through
    `network/scoring.py::GossipPeerScorer.on_backpressure_drop` and
    surface on the existing `gossip_queues.py` drop/depth metrics.

Observability: every flush emits a `bls.pipeline.flush` span
(reason/lane/kind/sets/n_bucket) and feeds
`lodestar_bls_bucket_fill_ratio` +
`lodestar_bls_flush_reason_total{reason=fill|spill|deadline|close}`
(utils/metrics.py); `flush_stats()` exposes the same records to tests
and the `bench.py bls_pipeline_verified_atts_per_s` probe.

Ahead of the accumulators sits the PRE-VERIFY AGGREGATION stage
(ISSUE 13, bls/aggregator.py): batchable standard-lane wire sets are
bucketed by signing root, exact duplicates deduped (in-flight followers
+ a resolved-verdict seen-map), and each bucket's disjoint-index layers
point-add their signatures in G2 to verify as ONE set through the same
RLC batch path — per-message verdicts fan back out, and a failed layer
bisects contributor-wise.  The stage engages only when the verifier can
aggregate (`aggregate_wire_signatures`) and `LODESTAR_TPU_BLS_PREAGG`
is not 0; off, every message verifies as its own set exactly as in
PR 11.

Fault tolerance (ISSUE 14): the pipeline needs no fault path of its
own — the verifier's device circuit breaker (bls/supervisor.py) sits
BELOW the flush boundary, so a tripped breaker resolves every flushed
job through the host ground-truth seam with identical verdicts while
accumulation, lane deadlines, and the high-water backpressure keep
operating unchanged.  `breaker_status()` (inherited from the base
service) is the health surface's read path.

Escape hatch: `LODESTAR_TPU_BLS_PIPELINE=0` makes `create_bls_service`
return the PR 10 flat-buffer `BlsVerifierService` instead.
"""

from __future__ import annotations

import os
import time
from collections import deque
from typing import Dict, List, Optional, Tuple

from ..observability import trace_span as _trace_span
from .service import BlsVerifierService, _Job
from .signature_set import WireSignatureSet
from .verifier import K_BUCKETS, N_BUCKETS

# Lane windows.  The critical lane undercuts the reference's flat 100 ms
# window (multithread/index.ts:57) — a proposer/aggregate set must reach
# the device before attestation fill, not after it; the standard lane
# stretches past it because subnet attestations are latency-tolerant
# (ATTESTATION_PROPAGATION_SLOT_RANGE is measured in slots) and bucket
# occupancy is what the RLC final-exp amortization pays for.
CRITICAL_WAIT_MS = 25.0
STANDARD_WAIT_MS = 250.0
# Backpressure high-water: buffered + queued + in-flight signature sets.
# Sized at 8 full 512-set device jobs — past this the node is saturated
# and the gossip processor must stop pulling (and start charging peers).
HIGH_WATER_SETS = 4096

LANE_CRITICAL = "critical"
LANE_STANDARD = "standard"


def _pad_bucket(n: int) -> int:
    """The padded device N-bucket one job of `n` <= max-bucket sets
    dispatches into (verifier._prepare pads up to N_BUCKETS)."""
    for b in N_BUCKETS:
        if n <= b:
            return b
    return N_BUCKETS[-1]


def _padded_lanes(n: int, cap: int) -> int:
    """Total device lanes a flush of `n` sets occupies after the
    dispatcher splits it into <= `cap`-set runs: full cap-sized jobs
    plus the padded bucket of the remainder.  This is the occupancy
    denominator — a single _pad_bucket would overstate fill for
    oversized flushes."""
    full, rem = divmod(n, cap)
    return full * cap + (_pad_bucket(rem) if rem else 0)


class _Accumulator:
    """One shape bucket's pending jobs + its oldest-set-anchored
    deadline."""

    __slots__ = ("jobs", "sets", "deadline")

    def __init__(self):
        self.jobs: List[_Job] = []
        self.sets = 0
        self.deadline: Optional[float] = None


class BlsVerificationPipeline(BlsVerifierService):
    """The per-shape-bucket accumulate-and-flush front of the verifier.

    Drop-in for `BlsVerifierService` (same submission/backpressure/
    shutdown contract); only the buffering-policy seams are replaced.
    """

    def __init__(
        self,
        verifier,
        critical_wait_ms: float = CRITICAL_WAIT_MS,
        standard_wait_ms: float = STANDARD_WAIT_MS,
        high_water_sets: int = HIGH_WATER_SETS,
        preagg: Optional[bool] = None,
        scorer=None,
        **kwargs,
    ):
        # attrs the dispatcher thread reads must exist before
        # super().__init__ starts it
        self._buckets: Dict[Tuple[bool, int, str], _Accumulator] = {}
        self._lane_wait = {
            LANE_CRITICAL: critical_wait_ms / 1000.0,
            LANE_STANDARD: standard_wait_ms / 1000.0,
        }
        self._high_water_sets = high_water_sets
        self._flush_records: deque = deque(maxlen=512)
        # monotone per-flush sequence: incremental consumers (the SLO
        # engine's per-slot critical-lane p99) remember the last seq
        # they saw instead of re-counting the ring
        self._flush_seq = 0
        # pre-verify aggregation stage (ISSUE 13): requires a verifier
        # that can point-add wire signatures; LODESTAR_TPU_BLS_PREAGG=0
        # restores per-message verification
        self._agg = None
        sum_fn = getattr(verifier, "aggregate_wire_signatures", None)
        if preagg is None:
            env = os.environ.get("LODESTAR_TPU_BLS_PREAGG", "1")
            preagg = env.strip().lower() not in ("0", "false", "no", "off")
        kwargs.setdefault("max_buffered_sigs", N_BUCKETS[-1])
        kwargs.setdefault("buffer_wait_ms", standard_wait_ms)
        # backpressure is counted in SETS here: the inherited job cap
        # must not bind first (512 one-set gossip jobs are 1/8 of the
        # high-water work, not a full queue) — one job holds >= 1 set,
        # so a job cap equal to the set mark keeps _pending_sets the
        # binding constraint while still bounding bookkeeping
        kwargs.setdefault("max_pending_jobs", high_water_sets)
        super().__init__(verifier, **kwargs)
        if preagg and sum_fn is not None:
            from .aggregator import PreVerifyAggregator

            self._agg = PreVerifyAggregator(
                self,
                self._lane_wait[LANE_STANDARD],
                sum_fn,
                scorer=scorer,
            )
        # full-window cap per bucket: the largest exact fill the device
        # accepts — past it the flush can only split into capped runs
        self._max_fill = (
            max(self._bucket_fills) if self._bucket_fills else self._max_buffered
        )

    # -- backpressure -----------------------------------------------------

    def can_accept_work(self) -> bool:
        with self._lock:
            return (
                not self._closed
                and self._pending < self._max_pending
                and self._pending_sets < self._high_water_sets
            )

    def pending_sets(self) -> int:
        """Buffered + queued + in-flight signature sets (the high-water
        unit); exported as `lodestar_bls_pipeline_pending_sets`."""
        with self._lock:
            return self._pending_sets

    # -- pre-verify aggregation seams (ISSUE 13) ---------------------------

    def set_scorer(self, scorer) -> None:
        """Late-bind the gossip peer scorer (the node builds it after
        the service): isolated invalid contributors then charge their
        publisher (bls/aggregator.py attribution)."""
        if self._agg is not None:
            self._agg.scorer = scorer

    def set_layer_forward(self, fn) -> None:
        """Late-bind the aggregate-forward hook (ISSUE 19):
        `fn(wire, n_members)` fires for every VERIFIED materialized
        multi-member layer — the network plane's AggregateForwarder
        re-packs it onto the aggregate topic.  No-op when the
        aggregation stage is off."""
        if self._agg is not None:
            self._agg.on_layer_verified = fn

    def verify_signature_sets_async(self, sets, opts=None):
        fut = super().verify_signature_sets_async(sets, opts)
        if self._agg is not None and self._agg._deferred:
            # deliver verdicts the aggregation stage settled under the
            # submission lock (seen-map serves) outside it.  The
            # lock-free emptiness read keeps the common no-settlement
            # submit at one lock acquisition; a racy stale read is
            # harmless — every settling path drains its own deferrals
            # (_on_layer_done) or is followed by a drain (close)
            self._agg.drain()
        return fut

    def preagg_verdict(self, wire_set) -> Optional[bool]:
        """Resolved verdict for an exact (root, indices, signature)
        match in the aggregation stage's seen-map, else None (the
        gossip handlers' suppressed-duplicate fast path)."""
        if self._agg is None:
            return None
        return self._agg.seen_verdict(wire_set)

    def agg_stats(self) -> Optional[dict]:
        if self._agg is None:
            return None
        return self._agg.stats_snapshot()

    def mean_aggregation_factor(self) -> Optional[float]:
        """Contributions per verified set through the aggregation stage
        (None when the stage is off or idle) — the ISSUE 13 acceptance
        number."""
        if self._agg is None:
            return None
        return self._agg.mean_aggregation_factor()

    def _dispatch(self, group) -> None:
        if self._agg is not None:
            for job in group:
                # collapse pending layers into their aggregated set
                # OUTSIDE the lock, in the dispatcher thread (the G2
                # point-add is host/device work no submitter should
                # serialize behind)
                self._agg.materialize_job(job)
        super()._dispatch(group)

    # -- the accumulate side ----------------------------------------------

    @staticmethod
    def _k_bucket(job: _Job) -> int:
        kmax = max((len(s.indices) for s in job.sets), default=1)
        for b in K_BUCKETS:
            if kmax <= b:
                return b
        return K_BUCKETS[-1]  # oversized aggregates CPU-route anyway

    def _bucket_key(self, job: _Job) -> Tuple[bool, int, str]:
        wire = bool(job.sets) and isinstance(job.sets[0], WireSignatureSet)
        lane = (
            LANE_CRITICAL
            if getattr(job.opts, "priority", False)
            else LANE_STANDARD
        )
        return (wire, self._k_bucket(job), lane)

    def _submit_buffered_locked(self, job: _Job) -> None:
        if self._agg is not None and self._agg.eligible(job):
            # standard-lane wire sets route through the aggregation
            # stage: bucketed by signing root, deduped, layered, and
            # verified as aggregated sets (bls/aggregator.py)
            self._agg.add_locked(job)
            return
        key = self._bucket_key(job)
        acc = self._buckets.get(key)
        if acc is None:
            acc = self._buckets[key] = _Accumulator()
        new_total = acc.sets + len(job.sets)
        if new_total in self._bucket_fills or new_total >= self._max_fill:
            # exact fill (or past the largest device job): padding-free
            # dispatch, flush everything now
            acc.jobs.append(job)
            acc.sets = new_total
            self._flush_bucket_locked(key, "fill")
            return
        if acc.sets and any(
            acc.sets < b <= new_total for b in self._bucket_fills
        ):
            # a multi-set job OVERSHOOTS a bucket boundary: appending it
            # would strand ~a full bucket of sets waiting on the
            # deadline at half occupancy — SPILL the near-boundary jobs
            # as-is and start a fresh accumulation with this job
            self._flush_bucket_locked(key, "spill")
            acc = self._buckets[key] = _Accumulator()
        acc.jobs.append(job)
        acc.sets += len(job.sets)
        if acc.sets in self._bucket_fills or acc.sets >= self._max_fill:
            # the job alone exactly fills a bucket (reachable right
            # after a spill): same padding-free dispatch, no deadline
            self._flush_bucket_locked(key, "fill")
            return
        if key[2] == LANE_CRITICAL and self._pipeline_idle_locked(key):
            # adaptive batching (ISSUE 12 review fix): waiting out the
            # critical window only buys occupancy when OTHER work could
            # join or the device is busy anyway.  A lone critical job
            # submitted into an otherwise-idle pipeline — the full-node
            # gossip loop verifying aggregates SYNCHRONOUSLY, one at a
            # time — would serialize a pure 25 ms idle wait per
            # message; flush it now instead.  Under load (queued
            # groups, in-flight device work, or other accumulating
            # buckets) criticals still coalesce toward the deadline.
            self._flush_bucket_locked(key, "idle")
            return
        if acc.deadline is None:
            # anchor on the oldest buffered set's enqueue time (stamped
            # in _Job.__init__, before lock acquisition)
            acc.deadline = job.t_submit + self._lane_wait[key[2]]

    def _pipeline_idle_locked(self, key: Tuple[bool, int, str]) -> bool:
        """Nothing for a critical job to overlap with: no dispatch-
        queued groups, no in-flight device work, and no OTHER
        accumulator holding sets that will flush soon."""
        if self._queue or self._inflight_groups:
            return False
        if self._agg is not None and self._agg.pending_contributions():
            return False  # buffered aggregation work will flush soon
        return not any(
            acc.sets for k, acc in self._buckets.items() if k != key
        )

    # -- the flush side ---------------------------------------------------

    def _flush_bucket_locked(self, key: Tuple[bool, int, str], reason: str) -> None:
        acc = self._buckets.pop(key, None)
        if acc is None or not acc.jobs:
            return
        self._queue.append(acc.jobs)
        pad = _padded_lanes(acc.sets, self._max_fill)
        ratio = min(acc.sets / pad, 1.0)
        wire, k_bucket, lane = key
        # submit->flush wait of the OLDEST buffered job — the quantity
        # the lane deadline bounds, and the series the SLO engine's
        # pipeline_critical_p99 objective evaluates per slot (jobs
        # append in arrival order, so jobs[0] is the anchor)
        oldest_wait = time.perf_counter() - acc.jobs[0].t_submit
        self.metrics.bucket_fill_ratio.observe(ratio)
        self.metrics.flush_reason.inc(reason, 1.0)
        self._flush_seq += 1
        with _trace_span(
            "bls.pipeline.flush",
            reason=reason,
            lane=lane,
            wire=wire,
            k_bucket=k_bucket,
            sets=acc.sets,
            n_bucket=pad,
            oldest_wait_s=oldest_wait,
        ):
            self._flush_records.append(
                {
                    "seq": self._flush_seq,
                    "reason": reason,
                    "lane": lane,
                    "wire": wire,
                    "k_bucket": k_bucket,
                    "sets": acc.sets,
                    "n_bucket": pad,
                    "fill_ratio": ratio,
                    "oldest_wait_s": oldest_wait,
                }
            )

    def _poll_buffers_locked(self, now: float) -> Optional[float]:
        next_deadline: Optional[float] = None
        for key in list(self._buckets):
            acc = self._buckets.get(key)
            if acc is None or not acc.jobs:
                self._buckets.pop(key, None)
                continue
            if acc.deadline is not None and now >= acc.deadline:
                self._flush_bucket_locked(key, "deadline")
                continue
            if acc.deadline is not None and (
                next_deadline is None or acc.deadline < next_deadline
            ):
                next_deadline = acc.deadline
        if self._agg is not None:
            agg_wait = self._agg.poll_locked(now)
            if agg_wait is not None and (
                next_deadline is None or now + agg_wait < next_deadline
            ):
                next_deadline = now + agg_wait
        if next_deadline is None:
            return None
        return max(next_deadline - now, 0.0)

    def _close_flush_locked(self) -> None:
        if self._agg is not None:
            # buffered contributions reject like queued jobs; layer
            # jobs already queued/in-flight credit their members
            # through the standard rejection/resolution callbacks
            self._agg.close_locked()
        for key in list(self._buckets):
            self._flush_bucket_locked(key, "close")

    def close(self) -> None:
        super().close()
        if self._agg is not None:
            self._agg.drain()

    # -- introspection ----------------------------------------------------

    def flush_stats(self) -> List[dict]:
        """Recent flush records (reason/lane/sets/n_bucket/fill_ratio) —
        the bench probe's and tests' occupancy source."""
        with self._lock:
            return list(self._flush_records)

    def reset_flush_stats(self) -> None:
        """Drop the recorded flushes (bench probes reset after warmup so
        occupancy reflects only the measured flood)."""
        with self._lock:
            self._flush_records.clear()

    def mean_fill_ratio(self) -> Optional[float]:
        """Set-weighted mean bucket occupancy over the recent flushes:
        sum(sets) / sum(padded bucket) — the acceptance number ISSUE 11
        compares against the flat coalescer."""
        with self._lock:
            recs = list(self._flush_records)
        total = sum(r["sets"] for r in recs)
        padded = sum(r["n_bucket"] for r in recs)
        if padded == 0:
            return None
        return total / padded


def create_bls_service(verifier, **kwargs) -> BlsVerifierService:
    """The node's service factory: the accumulate-and-flush pipeline by
    default; `LODESTAR_TPU_BLS_PIPELINE=0` falls back to the PR 10 flat
    coalescing buffer (same submission contract, 100 ms single window)."""
    env = os.environ.get("LODESTAR_TPU_BLS_PIPELINE", "1")
    if env.strip().lower() in ("0", "false", "no", "off"):
        return BlsVerifierService(verifier, **kwargs)
    return BlsVerificationPipeline(verifier, **kwargs)


__all__ = [
    "BlsVerificationPipeline",
    "create_bls_service",
    "CRITICAL_WAIT_MS",
    "STANDARD_WAIT_MS",
    "HIGH_WATER_SETS",
    "LANE_CRITICAL",
    "LANE_STANDARD",
]
