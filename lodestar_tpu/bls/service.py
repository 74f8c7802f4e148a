"""BlsVerifierService — the async job-queue front of the TPU verifier.

The service reproduces the reference `BlsMultiThreadWorkerPool` contract
(packages/beacon-node/src/chain/bls/multithread/index.ts):

  - callers submit jobs and receive futures; a single dispatcher thread
    owns the device (one TPU stream replaces the N worker threads),
  - small batchable jobs are COALESCED: a job buffer flushes when it
    reaches MAX_BUFFERED_SIGS sets or after MAX_BUFFER_WAIT_MS
    (index.ts:48-57 — the 100 ms / 32-sig window),
  - backpressure: `can_accept_work()` is False once MAX_PENDING_JOBS jobs
    are queued or buffered (index.ts:143-149), the signal the gossip
    NetworkProcessor throttles on (processor/index.ts:357-371),
  - the buffering POLICY is a seam: `_submit_buffered_locked`,
    `_poll_buffers_locked`, and `_close_flush_locked` are the three
    hooks the accumulate-and-flush pipeline (bls/pipeline.py) overrides
    to replace this flat window with per-shape-bucket accumulators,
  - a failed merged batch re-verifies per job so one bad signature cannot
    poison other jobs' verdicts (worker.ts:74-96),
  - `verify_on_main_thread` bypasses the queue and verifies synchronously
    on the host CPU (the proposer-signature latency fast path,
    validation/block.ts:146),
  - `close()` rejects queued jobs and stops the dispatcher
    (index.ts:193-214),
  - metrics: queue_length, job_wait_time, workers_busy populated here;
    verification counters inside the verifier.
"""

from __future__ import annotations

import threading
import time
from concurrent.futures import Future
from queue import SimpleQueue
from typing import List, Optional, Sequence

from ..observability import current_id as _trace_current_id
from ..observability import trace_span as _trace_span
from .signature_set import SignatureSet, WireSignatureSet
from .verifier import (
    MAX_PENDING_JOBS,
    N_BUCKETS,
    TpuBlsVerifier,
    VerifyOptions,
)

# Raised from the reference's 32 to one full kernel lane tile: RLC batch
# verification amortizes ONE final exponentiation over the whole device
# job, so a coalescing window that stops at 32 sets leaves 3/4 of the
# smallest (128-lane) N-bucket as padding.  Latency stays bounded by
# MAX_BUFFER_WAIT_MS, and an exact bucket fill flushes immediately
# (_maybe_flush_buffer_locked).
MAX_BUFFERED_SIGS = 128
MAX_BUFFER_WAIT_MS = 100    # reference: multithread/index.ts:57
# Device jobs dispatched but not yet resolved.  JAX dispatch is async, so
# in-flight jobs overlap one job's host work with another's device work;
# the bound keeps retry latency and memory in check and is the
# backpressure coupling between the resolver and the dispatcher.
MAX_INFLIGHT_JOBS = 4


class _Job:
    # `agg_members` is set only on the pre-verify aggregation stage's
    # internal layer jobs (bls/aggregator.py): the contributions whose
    # verdicts the job's future fans out to
    __slots__ = ("sets", "opts", "future", "t_submit", "t_submit_ns",
                 "trace_parent", "agg_members")

    def __init__(self, sets, opts):
        self.sets = sets
        self.opts = opts
        self.future: Future = Future()
        self.t_submit = time.perf_counter()
        self.t_submit_ns = time.time_ns()
        # submitting context's span id: the dispatcher/resolver threads
        # do NOT inherit contextvars, so the device-side spans link back
        # to the gossip/import span that queued the work explicitly
        self.trace_parent = _trace_current_id()


class BlsVerifierService:
    def __init__(
        self,
        verifier: TpuBlsVerifier,
        max_pending_jobs: int = MAX_PENDING_JOBS,
        max_buffered_sigs: int = MAX_BUFFERED_SIGS,
        buffer_wait_ms: float = MAX_BUFFER_WAIT_MS,
        max_inflight_jobs: int = MAX_INFLIGHT_JOBS,
    ):
        self.verifier = verifier
        self.metrics = verifier.metrics
        if hasattr(verifier, "observe_single_thread"):
            # pooled worker, not single-thread mode (see CpuBlsVerifier)
            verifier.observe_single_thread = False
        self._max_pending = max_pending_jobs
        self._max_buffered = max_buffered_sigs
        self._buffer_wait = buffer_wait_ms / 1000.0
        self._lock = threading.Condition()
        self._queue: List[List[_Job]] = []
        self._buffer: List[_Job] = []
        self._buffer_deadline: Optional[float] = None
        # exact N-bucket fills flush immediately (no padding to gain by
        # waiting); stubs without a device job cap use every bucket
        self._bucket_fills = frozenset(
            b
            for b in N_BUCKETS
            if b <= getattr(verifier, "max_job_sets", N_BUCKETS[-1])
        )
        # trailing dispatch-run tracker for the exact-fill trigger (the
        # buffer is append-only between flushes, so O(new sets) updates
        # in _buffer_append_locked replace an O(buffer) rescan per
        # submission under the lock)
        self._buffered_sets = 0
        self._tail_run_len = 0
        self._tail_run_wire: Optional[bool] = None
        self._pending = 0  # queued + buffered + in-flight jobs
        # queued + buffered + in-flight SETS — the unit the pipeline's
        # high-water backpressure is measured in (a 1-set gossip job and
        # a 512-set range-sync job are very different work)
        self._pending_sets = 0
        self._closed = False
        # dispatcher begins device jobs; resolver syncs them in order.
        # The bounded in-flight queue pipelines dispatch latency.
        self._inflight: "SimpleQueue" = SimpleQueue()
        self._inflight_slots = threading.Semaphore(max_inflight_jobs)
        # groups begun but not yet resolved — the pipeline's critical-
        # lane idle test reads this (under the lock): batching is only
        # worth waiting for while the device has work to overlap with
        self._inflight_groups = 0
        # BlsWorkResult-parity records of recent device jobs (reference:
        # multithread/types.ts:26-38 — workerId, batchRetries,
        # batchSigsSuccess, workerStartNs, workerEndNs)
        from collections import deque

        self.recent_job_timings: "deque" = deque(maxlen=64)
        self._timings_lock = threading.Lock()
        self._thread = threading.Thread(
            target=self._run, name="bls-verifier-dispatch", daemon=True
        )
        self._resolver = threading.Thread(
            target=self._resolve_loop, name="bls-verifier-resolve", daemon=True
        )
        self._thread.start()
        self._resolver.start()

    def job_timings(self) -> List[dict]:
        """Thread-safe snapshot of the BlsWorkResult-parity records."""
        with self._timings_lock:
            return list(self.recent_job_timings)

    def breaker_status(self) -> Optional[dict]:
        """The verifier's device-circuit-breaker status (ISSUE 14), or
        None for verifiers without a supervisor (CPU fallback/stubs) —
        the health endpoint's and bench's read path."""
        sup = getattr(self.verifier, "supervisor", None)
        return sup.status() if sup is not None else None

    # -- submission -------------------------------------------------------

    def can_accept_work(self) -> bool:
        with self._lock:
            return not self._closed and self._pending < self._max_pending

    def verify_signature_sets_async(
        self, sets: Sequence[SignatureSet], opts: Optional[VerifyOptions] = None
    ) -> "Future[bool]":
        opts = opts or VerifyOptions()
        if opts.verify_on_main_thread:
            fut: Future = Future()
            t0 = time.perf_counter()
            try:
                fut.set_result(
                    self.verifier.verify_signature_sets(list(sets), opts)
                )
            except Exception as e:  # pragma: no cover
                fut.set_exception(e)
            self.metrics.main_thread_time.observe(time.perf_counter() - t0)
            return fut
        job = _Job(list(sets), opts)
        with self._lock:
            closed = self._closed
            if not closed:
                self._pending += 1
                self._pending_sets += len(job.sets)
                self.metrics.pipeline_pending_sets.set(self._pending_sets)
                if opts.batchable and len(job.sets) < self._max_buffered:
                    self._submit_buffered_locked(job)
                else:
                    self._queue.append([job])
                self.metrics.queue_length.set(self._pending)
                self._lock.notify_all()
        if closed:
            # settle AFTER the lock releases: set_exception runs
            # done-callbacks synchronously on this thread, and a
            # continuation must never run inside the service Condition
            job.future.set_exception(RuntimeError("verifier closed"))
        return job.future

    def verify_signature_sets(
        self, sets: Sequence[SignatureSet], opts: Optional[VerifyOptions] = None
    ) -> bool:
        """Synchronous wrapper (blocks on the service future)."""
        return self.verify_signature_sets_async(sets, opts).result()

    def _submit_buffered_locked(self, job: _Job) -> None:
        """Buffering-policy hook: route one batchable job into the
        coalescing buffer.  The flush timer anchors on the OLDEST
        buffered set's enqueue time (`job.t_submit` is stamped before
        lock acquisition), so p99 submit->flush latency stays bounded by
        the window even when lock contention or a busy dispatcher delays
        the append (ISSUE 11 satellite)."""
        self._buffer_append_locked(job)
        if self._buffer_deadline is None:
            self._buffer_deadline = job.t_submit + self._buffer_wait
        self._maybe_flush_buffer_locked()

    def _buffer_append_locked(self, job: _Job) -> None:
        """Append to the buffer, advancing the trailing-run tracker with
        the same rules as _dispatch's run split (contiguous same-kind
        runs, wire vs decoded, capped at max_job_sets)."""
        self._buffer.append(job)
        self._buffered_sets += len(job.sets)
        cap = getattr(self.verifier, "max_job_sets", N_BUCKETS[-1])
        for s in job.sets:
            is_wire = isinstance(s, WireSignatureSet)
            if is_wire == self._tail_run_wire and self._tail_run_len < cap:
                self._tail_run_len += 1
            else:
                self._tail_run_len, self._tail_run_wire = 1, is_wire

    def _maybe_flush_buffer_locked(self) -> None:
        """Flush on a full window OR an exact N-bucket fill.

        The RLC device job pads its sets up to a fixed N-bucket
        (verifier.N_BUCKETS); when the bucket is exactly filled, more
        waiting can only (a) burn the remaining `_buffer_deadline`
        latency and (b) spill the job into the next, twice-as-large
        bucket — so flush immediately.  The fill test keys on the
        TRAILING dispatch run: only it can still grow — earlier runs'
        padding is locked in however long we wait.  For the common
        homogeneous buffer this is just "total sets == a bucket".
        """
        if (
            self._buffered_sets >= self._max_buffered
            or self._tail_run_len in self._bucket_fills
        ):
            self._flush_buffer_locked()

    def _flush_buffer_locked(self) -> None:
        if self._buffer:
            self._queue.append(self._buffer)
            self._buffer = []
        self._buffered_sets = 0
        self._tail_run_len, self._tail_run_wire = 0, None
        self._buffer_deadline = None

    # -- dispatcher -------------------------------------------------------

    def _poll_buffers_locked(self, now: float) -> Optional[float]:
        """Buffering-policy hook: flush any deadline-due buffers into
        the dispatch queue; return seconds until the next deadline (the
        dispatcher's wait timeout), or None when nothing is buffered."""
        if self._buffer and (
            self._buffer_deadline is not None
            and now >= self._buffer_deadline
        ):
            self._flush_buffer_locked()
        if self._buffer_deadline is None:
            return None
        return max(self._buffer_deadline - now, 0.0)

    def _run(self) -> None:
        """Dispatcher: pull groups, begin device jobs, hand to resolver."""
        while True:
            with self._lock:
                while True:
                    if self._closed:
                        self._inflight.put(None)  # wake + stop resolver
                        return
                    now = time.perf_counter()
                    timeout = self._poll_buffers_locked(now)
                    if self._queue:
                        group = self._queue.pop(0)
                        break
                    self._lock.wait(timeout=timeout)
                self.metrics.queue_length.set(self._pending)
            self._dispatch(group)

    def _dispatch(self, group: List[_Job]) -> None:
        t0 = time.perf_counter()
        dispatch_start_ns = time.time_ns()
        for j in group:
            self.metrics.job_wait_time.observe(t0 - j.t_submit)
            # submit -> device dispatch (reference latencyToWorker)
            self.metrics.latency_to_worker.observe(
                max(dispatch_start_ns - j.t_submit_ns, 0) / 1e9
            )
        self.metrics.total_job_groups_started.inc()
        self.metrics.total_jobs_started.inc(len(group))
        self.metrics.total_sig_sets_started.inc(
            sum(len(j.sets) for j in group)
        )
        try:
            if len(group) == 1 and not group[0].opts.batchable:
                batchable = False
            else:
                batchable = True
            merged = [s for j in group for s in j.sets]
            begin = getattr(self.verifier, "begin_job", None)
            if begin is None:
                # verifier without async dispatch (CPU fallback/stubs):
                # the whole job runs at resolve time
                handles = (merged, batchable)
            else:
                # device jobs must be homogeneous (wire vs decoded sets);
                # a buffer window can legally mix submitters of both kinds
                cap = self.verifier.max_job_sets
                runs: List[List] = []
                for s in merged:
                    is_wire = isinstance(s, WireSignatureSet)
                    if (
                        runs
                        and isinstance(runs[-1][0], WireSignatureSet) == is_wire
                        and len(runs[-1]) < cap
                    ):
                        runs[-1].append(s)
                    else:
                        runs.append([s])
                handles = [begin(run, batchable) for run in runs]
        except Exception as e:
            for j in group:
                if not j.future.done():
                    j.future.set_exception(e)
            self.metrics.error_jobs.inc(len(group))
            with self._lock:
                self._pending -= len(group)
                self._pending_sets -= sum(len(j.sets) for j in group)
                self.metrics.pipeline_pending_sets.set(self._pending_sets)
                self.metrics.queue_length.set(self._pending)
                self._lock.notify_all()
            return
        self._inflight_slots.acquire()  # backpressure: bounded in-flight
        with self._lock:
            self._inflight_groups += 1
        self._inflight.put((group, handles, t0, dispatch_start_ns))

    def _resolve_loop(self) -> None:
        """Resolver: sync begun jobs in dispatch order, settle futures."""
        while True:
            item = self._inflight.get()
            if item is None:
                return
            group, handles, t0, worker_start_ns = item
            self._inflight_slots.release()
            self.metrics.workers_busy.set(1)
            worker_end_ns = None
            # explicit enter/exit (not `with`): the span must close at
            # the TOP of the finally so it brackets only the device
            # resolution, parented to the submitting context's span
            span = _trace_span(
                "bls.job",
                parent_id=group[0].trace_parent if group else None,
                jobs=len(group),
                sets=sum(len(j.sets) for j in group),
            )
            span.__enter__()
            try:
                if isinstance(handles, tuple):
                    merged, batchable = handles
                    ok = self.verifier.verify_signature_sets(
                        merged, VerifyOptions(batchable=batchable)
                    )
                else:
                    ok = True
                    for h in handles:
                        ok &= self.verifier.finish_job(h)
                worker_end_ns = time.time_ns()
                if ok:
                    for j in group:
                        j.future.set_result(True)
                elif len(group) == 1:
                    group[0].future.set_result(False)
                elif isinstance(handles, tuple):
                    # no-begin_job fallback: re-verify per job so one bad
                    # signature cannot poison other jobs' verdicts
                    # (reference: worker.ts:74-96)
                    for j in group:
                        j.future.set_result(
                            self.verifier.verify_signature_sets(j.sets, j.opts)
                        )
                else:
                    # a failed merged batch: finish_job already produced
                    # per-set verdicts for failed handles (the device
                    # retry pass) — slice them back to the submitting
                    # jobs by position instead of re-verifying
                    # (reference accounting: worker.ts:74-96)
                    per_set = []
                    aligned = True
                    for h in handles:
                        if not bool(h.ok_big):
                            aligned = False  # a CPU-routed set failed in
                            break  # this handle; positions ambiguous
                        if getattr(h, "verdicts", None) is not None:
                            per_set.extend(bool(v) for v in h.verdicts)
                        else:
                            per_set.extend([True] * len(h.sets))
                    total = sum(len(j.sets) for j in group)
                    if aligned and len(per_set) == total:
                        pos = 0
                        for j in group:
                            nj = len(j.sets)
                            j.future.set_result(all(per_set[pos : pos + nj]))
                            pos += nj
                    else:
                        # CPU-routed sets (oversized aggregates, external
                        # keys) broke positional alignment: re-verify per
                        # job to attribute failures correctly
                        for j in group:
                            j.future.set_result(
                                self.verifier.verify_signature_sets(
                                    j.sets, j.opts
                                )
                            )
            except Exception as e:
                for j in group:
                    if not j.future.done():
                        j.future.set_exception(e)
                self.metrics.error_jobs.inc(len(group))
            finally:
                span.__exit__(None, None, None)
                self.metrics.workers_busy.set(0)
                settled_ns = time.time_ns()
                if worker_end_ns is not None:
                    # device result ready -> futures settled (reference
                    # latencyFromWorker), device-bracket ns timestamps
                    # (reference workerStartNs/workerEndNs)
                    self.metrics.latency_from_worker.observe(
                        max(settled_ns - worker_end_ns, 0) / 1e9
                    )
                    self.metrics.jobs_worker_time.inc(
                        "0", (worker_end_ns - worker_start_ns) / 1e9
                    )
                    with self._timings_lock:
                        self.recent_job_timings.append(
                            {
                                "worker_id": 0,
                                # per-job fields carried on the device
                                # handles themselves (no racy global
                                # counter diffs).  KNOWN GAP: the
                                # no-begin_job tuple path and the
                                # misaligned re-verify fallback create
                                # internal jobs whose counters are not
                                # attributed here (global counters stay
                                # correct; only the per-job record
                                # underreports on those rare paths)
                                "batch_retries": sum(
                                    getattr(h, "batch_retries", 0)
                                    for h in (
                                        handles
                                        if not isinstance(handles, tuple)
                                        else ()
                                    )
                                ),
                                "batch_sigs_success": sum(
                                    getattr(h, "batch_sigs_success", 0)
                                    for h in (
                                        handles
                                        if not isinstance(handles, tuple)
                                        else ()
                                    )
                                ),
                                "worker_start_ns": worker_start_ns,
                                "worker_end_ns": worker_end_ns,
                                "sig_sets": sum(len(j.sets) for j in group),
                            }
                        )
                # verify_signature_sets observes job_time itself; only the
                # begin/finish handle path accounts here (no double count)
                if not isinstance(handles, tuple):
                    dt = time.perf_counter() - t0
                    nsets = sum(len(j.sets) for j in group)
                    self.metrics.job_time.observe(dt)
                    if nsets:
                        self.metrics.time_per_sig_set.observe(dt / nsets)
                with self._lock:
                    self._pending -= len(group)
                    self._pending_sets -= sum(len(j.sets) for j in group)
                    self._inflight_groups -= 1
                    self.metrics.pipeline_pending_sets.set(self._pending_sets)
                    self.metrics.queue_length.set(self._pending)
                    self._lock.notify_all()

    # -- shutdown (reference: multithread/index.ts:193-214) ---------------

    def _close_flush_locked(self) -> None:
        """Buffering-policy hook: drain every buffer into the dispatch
        queue at shutdown (the queued jobs are then rejected)."""
        self._flush_buffer_locked()

    def close(self) -> None:
        with self._lock:
            if self._closed:
                return
            self._closed = True
            self._close_flush_locked()
            rejected = [j for g in self._queue for j in g]
            self._queue = []
            self._pending -= len(rejected)
            self._pending_sets -= sum(len(j.sets) for j in rejected)
            self.metrics.pipeline_pending_sets.set(self._pending_sets)
            self._lock.notify_all()
        for j in rejected:
            j.future.set_exception(RuntimeError("verifier closed"))
        self._thread.join(timeout=5)
        self._resolver.join(timeout=30)  # drains in-flight device jobs
        self.verifier.close()
