"""Pre-verify attestation aggregation — verify fewer sets, not just
verify sets faster (ISSUE 13 tentpole).

PR 10/11 cut the cost of each verified set (one multi-pairing per RLC
job) and fed the device fuller batches; every duplicate-heavy subnet
attestation still costs a full signature set.  *Aggregated Signature
Gossip* (arXiv:1911.04698) shows the remaining multiplier: k messages
sharing one signing root aggregate into ONE verifiable statement

    e(sum_i pk_i, H(m)) == e(G1, sum_i sig_i)

cutting required verification throughput by up to k — multiplying
whatever the RLC path delivers — and the EdDSA/BLS committee-consensus
study (arXiv:2302.00418) locates exactly this aggregate-then-verify
step as where BLS wins at committee scale.  This module is that stage,
sitting AHEAD of the pipeline's accumulators:

  - **Bucketing.**  Batchable standard-lane WIRE sets are bucketed by
    `signing_root` — for attestations that root is derived from
    `AttestationData.hash_tree_root` plus the attester domain, so one
    bucket == one (slot, committee, vote) AttestationData.
  - **Dedupe.**  An exact duplicate (same root, indices, signature
    bytes — the shape of a gossip duplicate flood) never re-enters the
    math: while its twin is pending it becomes a follower sharing the
    verdict; after resolution it is served straight from the bucket
    seen-map with zero device work.
  - **Disjoint layers.**  Contributors with OVERLAPPING aggregation
    bits cannot merge into one sum (c-fold indices would need c*pk on
    the gather side — the same reason the eth2 spec refuses overlapping
    aggregates), so `pubkey_table.plan_disjoint_gathers` packs them
    into layers with pairwise-disjoint index sets: every pubkey row is
    gathered ONCE per layer (ISSUE 13 satellite).
  - **One set per layer.**  A layer's signatures are point-added in G2
    — on device through `verifier.aggregate_wire_signatures` (the
    `agg_g2_sum` dispatch of kernels/verify.py's segmented jacobian
    sum) with a host ground-truth fallback — and the
    layer verifies as ONE `WireSignatureSet.aggregate` through the
    existing RLC batch path, K-bucketed and message-grouped like any
    other set.  Both legs sit under the device circuit breaker
    (ISSUE 14, bls/supervisor.py): the sum seam skips the device and a
    sum-stage fault classifies + trips inside
    `aggregate_wire_signatures`, and the layer's verify job degrades to
    host verdicts like any other job — the stage itself never needs a
    fault path of its own.
  - **Attribution.**  Every contributor's own future resolves from the
    layer verdict (gossip forwarding, peer scoring, slasher ingestion
    all key on per-message verdicts).  A FAILED layer bisects
    contributor-wise exactly like PR 10's batch bisection: halves
    re-aggregate and re-verify as smaller sets, recursing into failing
    halves, and single-contributor leaves verify the original wire set
    as submitted — one bad message in a k-contributor bucket costs
    O(log k) extra sets.  An isolated invalid contributor charges its
    publisher through the gossip peer scorer when the submission
    carried a `peer_id` (`VerifyOptions`).

Soundness (documented in README "Pre-verify aggregation"): within a
bucket the pairing check attests to the SUM of contributions, not each
one — per-contributor RLC randomizers would cost the per-set G2 scalar
mul this stage exists to remove.  A crafted pair (sig+D, sig'-D) can
therefore pass aggregated where both parts fail individually; the
blast radius is bounded (the attacker must beat the honest messages to
the seen caches, the corrupted votes still fail block-level
verification, and cross-bucket forgery stays blocked by the RLC
randomizers downstream), and `LODESTAR_TPU_BLS_PREAGG=0` restores
per-message verification wholesale.
"""

from __future__ import annotations

import time
from collections import OrderedDict
from typing import Dict, List, Optional, Tuple

from ..observability import trace_span as _trace_span
from .ingest import parse_signature_bytes
from .pubkey_table import plan_disjoint_gathers
from .service import _Job
from .signature_set import SignatureSetType, WireSignatureSet
from .verifier import VerifyOptions

# A layer never grows past one full K-bucket below the CPU-routing cap:
# the gather cost of the aggregated set stays device-bucketed, and the
# pairing win has long saturated by then.
MAX_LAYER_INDICES = 512
# Stage-wide flush caps: distinct output sets reaching one device lane
# tile, or raw contributions reaching a memory/latency bound.
MAX_STAGE_SETS = 128
MAX_STAGE_CONTRIBUTIONS = 4096
# Bounded verdict memory: (root, indices, signature) -> bool of recent
# resolutions, the seen-map gossip handlers consult for suppressed
# duplicates (network/gossip_handlers._recover_suppressed_double_vote).
SEEN_VERDICTS = 8192


class _Parent:
    """One submitted job awaiting its contributions' verdicts.

    Pending-sets accounting contract: the submitting path counted this
    job's sets into `_pending_sets`; each set's unit is RELEASED exactly
    once — at stage flush when its contribution hands off into a layer
    job (which carries its own accounting through the dispatch queue),
    or at credit time for sets judged without ever flushing (unparsable
    bytes, seen-map serves, close rejects).  The job unit itself stays
    in `_pending` until the future settles, mirroring the base service.
    """

    __slots__ = ("job", "remaining", "ok", "exc", "settled")

    def __init__(self, job: _Job):
        self.job = job
        self.remaining = len(job.sets)
        self.ok = True
        self.exc: Optional[BaseException] = None
        self.settled = False


class _Contribution:
    """One distinct (root, indices, signature) statement plus every
    submission awaiting its verdict (the original + exact duplicates)."""

    __slots__ = ("wire", "targets")

    def __init__(self, wire: WireSignatureSet, target) -> None:
        self.wire = wire
        self.targets: List[Tuple[_Parent, Optional[str], Optional[str]]] = [
            target
        ]


class _Bucket:
    __slots__ = ("contribs", "index")

    def __init__(self) -> None:
        self.contribs: List[_Contribution] = []
        # (indices, signature) -> position in contribs, the dedupe index
        self.index: Dict[Tuple, int] = {}


class PreVerifyAggregator:
    """The aggregation stage.  All `_locked` methods run under the
    owning pipeline's condition lock; future settlement is DEFERRED to
    `drain()` so no caller-visible callback ever fires under it."""

    def __init__(
        self,
        pipeline,
        lane_wait_s: float,
        sum_fn,
        scorer=None,
        max_layer_indices: int = MAX_LAYER_INDICES,
        max_stage_sets: int = MAX_STAGE_SETS,
        max_stage_contributions: int = MAX_STAGE_CONTRIBUTIONS,
    ):
        self._pipeline = pipeline
        self._lane_wait = lane_wait_s
        # aggregate-forward hook (ISSUE 19, network/forwarding.py):
        # `fn(wire, n_members)` fires OUTSIDE the pipeline lock for
        # every VERIFIED materialized multi-member layer — the network
        # plane re-packs it onto the aggregate topic
        self.on_layer_verified = None
        # List[List[bytes]] -> List[Optional[bytes]]: the G2 point-add of
        # each group's compressed signatures (TpuBlsVerifier's device/
        # host implementation, or a test stub's oracle)
        self._sum_fn = sum_fn
        self.scorer = scorer
        self._max_layer_indices = max_layer_indices
        self._max_stage_sets = max_stage_sets
        self._max_stage_contributions = max_stage_contributions
        self.metrics = pipeline.metrics
        self._buckets: "OrderedDict[bytes, _Bucket]" = OrderedDict()
        self._n_contribs = 0
        self._deadline: Optional[float] = None
        self._oldest_t: Optional[float] = None
        # settled-but-not-yet-delivered futures (see class docstring)
        self._deferred: List[Tuple] = []
        self._seen: "OrderedDict[Tuple, bool]" = OrderedDict()
        # cumulative stage stats (the bench probe's aggregation-factor
        # source): contributions = every submission routed through the
        # stage (followers and seen-serves included), sets = signature
        # sets handed to the verify path on its behalf (layers, bisect
        # re-aggregates, and leaves)
        self.stats = {
            "contributions": 0,
            "sets": 0,
            "dedup": 0,
            "seen_served": 0,
            "flushes": 0,
            "bisections": 0,
        }

    # -- eligibility -------------------------------------------------------

    def eligible(self, job: _Job) -> bool:
        """Standard-lane, registry-indexed wire sets only.  All-or-
        nothing per job: one ineligible set keeps the whole job on the
        plain accumulator path (the service's positional verdict
        slicing stays untouched)."""
        if not job.sets or getattr(job.opts, "priority", False):
            return False
        for s in job.sets:
            if not isinstance(s, WireSignatureSet):
                return False
            if s.pubkeys is not None or not s.indices:
                return False
            if len(s.indices) > self._max_layer_indices:
                return False
            if s.type not in (
                SignatureSetType.single,
                SignatureSetType.aggregate,
            ):
                return False
        return True

    # -- the accumulate side ----------------------------------------------

    def add_locked(self, job: _Job) -> None:
        parent = _Parent(job)
        peer = getattr(job.opts, "peer_id", None)
        topic = getattr(job.opts, "topic", None)
        for s in job.sets:
            target = (parent, peer, topic)
            self.stats["contributions"] += 1
            self.metrics.preagg_contributions.inc()
            _x0, _x1, _sgn, inf, wire_ok = parse_signature_bytes(s.signature)
            if not wire_ok or inf:
                # unparsable / infinity signatures can never verify and
                # must never poison a sum — verdict now
                self._credit_locked(target, False, release=True)
                continue
            key = s.dedupe_key()
            served = self._seen.get(key)
            if served is not None:
                self._seen.move_to_end(key)
                self.stats["seen_served"] += 1
                self.metrics.preagg_seen_served.inc()
                self._credit_locked(target, served, release=True)
                continue
            bucket = self._buckets.get(s.signing_root)
            if bucket is None:
                bucket = self._buckets[s.signing_root] = _Bucket()
            pos = bucket.index.get((s.indices, s.signature))
            if pos is not None:
                # in-flight exact duplicate: follow the twin's verdict
                bucket.contribs[pos].targets.append(target)
                self.stats["dedup"] += 1
                self.metrics.preagg_dedup.inc()
                continue
            bucket.index[(s.indices, s.signature)] = len(bucket.contribs)
            bucket.contribs.append(_Contribution(s, target))
            self._n_contribs += 1
            if self._deadline is None:
                # anchor on the OLDEST buffered contribution's enqueue
                # time (same rule as the accumulator deadlines)
                self._deadline = job.t_submit + self._lane_wait
                self._oldest_t = job.t_submit
        if self._n_contribs >= self._max_stage_contributions:
            self.flush_locked("cap")
        elif len(self._buckets) >= self._max_stage_sets:
            self.flush_locked("fill")

    def pending_contributions(self) -> int:
        return self._n_contribs

    # -- the flush side ----------------------------------------------------

    def poll_locked(self, now: float) -> Optional[float]:
        """Dispatcher hook: flush on the stage deadline; return seconds
        until it, or None when nothing is buffered."""
        if self._deadline is None:
            return None
        if now >= self._deadline:
            self.flush_locked("deadline")
            return None
        return max(self._deadline - now, 0.0)

    def flush_locked(self, reason: str) -> None:
        buckets = self._buckets
        if not buckets:
            self._deadline = self._oldest_t = None
            return
        self._buckets = OrderedDict()
        self._n_contribs = 0
        oldest_t, self._oldest_t = self._oldest_t, None
        self._deadline = None
        jobs: List[_Job] = []
        contributions = 0
        for _root, bucket in buckets.items():
            contributions += sum(len(c.targets) for c in bucket.contribs)
            layers = plan_disjoint_gathers(
                [c.wire.indices for c in bucket.contribs],
                self._max_layer_indices,
            )
            for layer in layers:
                jobs.append(
                    self._make_layer_job(
                        [bucket.contribs[p] for p in layer], oldest_t
                    )
                )
        # accounting handoff: the contributor sets leaving the stage are
        # now represented by the layer jobs' own pending counts (added
        # in _enqueue_locked, removed by the resolver) — release the
        # submission-side units so nothing is counted twice and the
        # high-water mark keeps meaning real in-flight sets
        self._release_sets_locked(contributions)
        factor = contributions / len(jobs)
        self.metrics.aggregation_factor.observe(factor)
        self.stats["flushes"] += 1
        oldest_wait = (
            time.perf_counter() - oldest_t if oldest_t is not None else 0.0
        )
        with _trace_span(
            "bls.preagg.flush",
            reason=reason,
            buckets=len(buckets),
            contributions=contributions,
            sets=len(jobs),
            factor=factor,
            oldest_wait_s=oldest_wait,
        ):
            self._enqueue_locked(jobs)

    def _make_layer_job(
        self, members: List[_Contribution], t_anchor: Optional[float]
    ) -> _Job:
        """One pending signature set for `members` (all sharing a root,
        pairwise-disjoint indices).  Multi-member layers carry their
        member wire sets until the dispatcher materializes the SUM
        (materialize_job, OUTSIDE the pipeline lock) so no submitter
        ever waits on point arithmetic."""
        job = _Job([c.wire for c in members], VerifyOptions(batchable=True))
        job.agg_members = members
        if t_anchor is not None:
            job.t_submit = t_anchor  # wait metrics span the full stage
        self.stats["sets"] += 1
        self.metrics.preagg_sets.inc()
        job.future.add_done_callback(
            lambda fut, job=job: self._on_layer_done(job, fut)
        )
        return job

    def _enqueue_locked(self, jobs: List[_Job]) -> None:
        """Queue layer jobs as ONE dispatch group (they merge into one
        RLC device job, splitting at the verifier cap) and take over
        their pending accounting."""
        if not jobs:
            return
        p = self._pipeline
        p._queue.append(jobs)
        p._pending += len(jobs)
        p._pending_sets += sum(len(j.sets) for j in jobs)
        p.metrics.pipeline_pending_sets.set(p._pending_sets)
        p.metrics.queue_length.set(p._pending)
        p._lock.notify_all()

    def materialize_job(self, job: _Job) -> None:
        """Dispatcher hook (called OUTSIDE the lock, before the device
        job begins): collapse a multi-member layer into its ONE
        aggregated wire set via the G2 sum.  If the sum is unavailable
        (an off-curve member the cheap host parse cannot see), the
        layer dispatches as its members' own sets instead — the merged
        verdict still bisects correctly on failure."""
        members = getattr(job, "agg_members", None)
        if members is None or len(job.sets) <= 1:
            return
        sig = None
        try:
            sig = self._sum_fn([[c.wire.signature for c in members]])[0]
        except Exception:  # noqa: BLE001 — aggregation is an optimization;
            sig = None  # verification must proceed without it
        if sig is None:
            return  # dispatch the members as their own sets
        root = members[0].wire.signing_root
        indices = tuple(i for c in members for i in c.wire.indices)
        before = len(job.sets)
        job.sets = [WireSignatureSet.aggregate(indices, root, sig)]
        # the group was accounted at the member count; reconcile to the
        # one aggregated set actually dispatching
        p = self._pipeline
        with p._lock:
            p._pending_sets -= before - 1
            p.metrics.pipeline_pending_sets.set(p._pending_sets)

    # -- verdict fan-out + contributor-wise bisection ----------------------

    def _on_layer_done(self, job: _Job, fut) -> None:
        """Future callback (resolver/closer thread, no pipeline lock
        held): credit members on success, bisect on failure."""
        members = getattr(job, "agg_members", None) or []
        exc = fut.exception() if fut.done() else None
        attribute: List[Tuple[Optional[str], Optional[str]]] = []
        forward: Optional[Tuple[WireSignatureSet, int]] = None
        with self._pipeline._lock:
            if exc is not None:
                for c in members:
                    for target in c.targets:
                        self._credit_locked(target, exc)
            elif fut.result():
                for c in members:
                    self._record_seen_locked(c, True)
                    for target in c.targets:
                        self._credit_locked(target, True)
                if len(members) > 1 and len(job.sets) == 1:
                    # a materialized multi-member layer VERIFIED: its
                    # union set is a re-publishable pack.  Mark the
                    # aggregated (root, indices, signature) in the
                    # seen-map too — an echoed copy of our own pack (or
                    # the same pack from a peer) serves with zero
                    # device work
                    union = job.sets[0]
                    self._seen[union.dedupe_key()] = True
                    self._seen.move_to_end(union.dedupe_key())
                    while len(self._seen) > SEEN_VERDICTS:
                        self._seen.popitem(last=False)
                    if self.on_layer_verified is not None:
                        forward = (union, len(members))
            elif len(members) <= 1:
                for c in members:
                    self._record_seen_locked(c, False)
                    for target in c.targets:
                        self._credit_locked(target, False)
                        if target[1] is not None:
                            attribute.append((target[1], target[2]))
            else:
                # contributor-wise bisection (the PR 10 shape): both
                # halves re-aggregate and dispatch as ONE group so they
                # pipeline on the device stream; failing halves recurse
                # through this same callback, leaves verify the
                # original wire set
                self.stats["bisections"] += 1
                self.metrics.preagg_bisections.inc()
                mid = (len(members) + 1) // 2
                halves = [members[:mid], members[mid:]]
                if not self._pipeline._closed:
                    self._enqueue_locked(
                        [self._make_layer_job(h, None) for h in halves]
                    )
                else:
                    err = RuntimeError("verifier closed")
                    for c in members:
                        for target in c.targets:
                            self._credit_locked(target, err)
        for peer, topic in attribute:
            # an isolated invalid contributor charges its publisher
            # (gossipsub P4 invalid-delivery, network/scoring.py) —
            # outside the pipeline lock, the scorer has its own state
            if self.scorer is not None:
                try:
                    self.scorer.on_invalid_message(peer, topic)
                except Exception:  # noqa: BLE001 — scoring must never
                    pass  # break verdict delivery
        if forward is not None:
            # re-publication is an optimization running on the resolver
            # thread: a forwarder fault must never break verdict fan-out
            try:
                self.on_layer_verified(forward[0], forward[1])
            except Exception:  # noqa: BLE001
                pass
        self.drain()

    def _record_seen_locked(self, c: _Contribution, verdict: bool) -> None:
        key = c.wire.dedupe_key()
        self._seen[key] = verdict
        self._seen.move_to_end(key)
        while len(self._seen) > SEEN_VERDICTS:
            self._seen.popitem(last=False)

    def _release_sets_locked(self, n: int) -> None:
        """Release `n` submission-side set units from the pipeline's
        pending accounting (see _Parent's contract: exactly once per
        set — at stage flush for sets handing off into layer jobs, or
        at credit time for sets judged without flushing)."""
        if not n:
            return
        p = self._pipeline
        p._pending_sets -= n
        p.metrics.pipeline_pending_sets.set(p._pending_sets)
        p._lock.notify_all()

    def _credit_locked(self, target, verdict, release: bool = False) -> None:
        parent, _peer, _topic = target
        if release:
            # this set never flushed into a layer job (unparsable,
            # seen-served, or rejected while buffered): its unit is
            # released here instead of at the flush handoff
            self._release_sets_locked(1)
        if isinstance(verdict, BaseException):
            parent.exc = verdict
        elif not verdict:
            parent.ok = False
        parent.remaining -= 1
        if parent.remaining > 0 or parent.settled:
            return
        parent.settled = True
        p = self._pipeline
        p._pending -= 1
        p.metrics.pipeline_pending_sets.set(p._pending_sets)
        p.metrics.queue_length.set(p._pending)
        p._lock.notify_all()
        self._deferred.append(
            (parent.job.future, parent.exc if parent.exc is not None else parent.ok)
        )

    def seen_verdict(self, wire: WireSignatureSet) -> Optional[bool]:
        """Resolved verdict for an EXACT (root, indices, signature)
        match, else None.  The gossip handlers' suppressed-duplicate
        recovery serves from here instead of paying a standalone
        verification (ISSUE 13 satellite); exact-match-only so a forged
        duplicate with a different signature can never ride an honest
        verdict."""
        with self._pipeline._lock:
            return self._seen.get(wire.dedupe_key())

    # -- settlement + shutdown --------------------------------------------

    def drain(self) -> None:
        """Deliver deferred verdicts (never called under the lock)."""
        with self._pipeline._lock:
            pending, self._deferred = self._deferred, []
        for fut, verdict in pending:
            if fut.done():
                continue
            if isinstance(verdict, BaseException):
                fut.set_exception(verdict)
            else:
                fut.set_result(verdict)

    def close_locked(self) -> None:
        """Reject every buffered contribution (the pipeline is closing;
        queued/in-flight layer jobs are rejected by the base path and
        credit their members through the future callbacks)."""
        buckets, self._buckets = self._buckets, OrderedDict()
        self._n_contribs = 0
        self._deadline = self._oldest_t = None
        err = RuntimeError("verifier closed")
        for bucket in buckets.values():
            for c in bucket.contribs:
                for target in c.targets:
                    # still buffered => never flushed => release here
                    self._credit_locked(target, err, release=True)

    def mean_aggregation_factor(self) -> Optional[float]:
        """contributions per verified set over the stage lifetime — the
        ISSUE 13 acceptance number (>= 3 under a duplicate-heavy
        flood)."""
        with self._pipeline._lock:
            if not self.stats["sets"]:
                return None
            return self.stats["contributions"] / self.stats["sets"]

    def stats_snapshot(self) -> dict:
        with self._pipeline._lock:
            return dict(self.stats)


__all__ = [
    "PreVerifyAggregator",
    "MAX_LAYER_INDICES",
    "MAX_STAGE_SETS",
    "MAX_STAGE_CONTRIBUTIONS",
]
