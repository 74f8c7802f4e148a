"""Deterministic chaos-scenario harness (ISSUE 14 tentpole, part 2).

ROADMAP's "scenario diversity" item asks for seeded, replayable fault
scripts over the real node stack.  This module provides the shared
machinery; the `test_*.py` scenarios in this package drive it:

  - **ScenarioTrace** — the record/replay spine.  A scenario emits
    deterministic checkpoint events (verdict summaries, breaker states,
    SLO statuses — never wall-clock values); `save()` writes the trace,
    `assert_replay()` re-runs the scenario against a fresh trace and
    asserts a bit-identical event stream.  A failing scenario therefore
    reproduces from its artifact alone.
  - **FakeClock** — injectable monotonic time for the breaker's backoff
    arithmetic, so re-probe schedules are script-driven, not
    sleep-driven.
  - **ChaosVerifier** — `TpuBlsVerifier` with the crypto replaced by a
    deterministic truth oracle (`chaos_sig`): the device path and the
    host ground-truth path (`_verify_set_host`) read the SAME oracle,
    so degraded-mode verdicts are bit-identical *by construction of the
    real routing code* — begin/finish supervision, breaker gating, and
    host fallback are the production seams, only the pairing is
    stubbed.  Faults inject per stage (`begin` / `finish` / `canary`):
    ``raise`` (generic error), ``backend`` (backend-init-classified
    error), ``hang`` (blocks until the watchdog deadline fires),
    ``truncated`` (malformed verdict plane -> bad_output).
  - **FloodWorld** — ChaosVerifier + DeviceSupervisor +
    BlsVerificationPipeline + SloEngine + FlightRecorder wired exactly
    as node.py wires them (degraded source, trip anomaly ->
    rate-limited bundle), for the fast data-plane scenarios.
  - **build_devnet** — N FullBeaconNodes over one InMemoryGossipBus
    with real crypto (the consensus-level slow scenarios: fork storm,
    partition/heal, crash/restart).
"""

from __future__ import annotations

import hashlib
import json
import threading

import numpy as np

from lodestar_tpu.bls.pipeline import BlsVerificationPipeline
from lodestar_tpu.bls.pubkey_table import PubkeyTable
from lodestar_tpu.bls.signature_set import WireSignatureSet
from lodestar_tpu.bls.supervisor import DeviceSupervisor, check_verdict_plane
from lodestar_tpu.bls.verifier import (
    TpuBlsVerifier,
    VerifyOptions,
    _DeviceJob,
)
from lodestar_tpu.chain.clock import Clock
from lodestar_tpu.observability.flight_recorder import FlightRecorder
from lodestar_tpu.observability.slo import SloEngine
from lodestar_tpu.utils.metrics import BlsPoolMetrics


# ---------------------------------------------------------------------------
# record / replay
# ---------------------------------------------------------------------------


class ScenarioTrace:
    """Ordered checkpoint events + a content digest.  Everything a
    scenario emits must be deterministic under its seed."""

    def __init__(self, seed: int):
        self.seed = int(seed)
        self.events = []

    def emit(self, kind: str, **data) -> None:
        self.events.append({"kind": kind, **data})

    def digest(self) -> str:
        blob = json.dumps(
            {"seed": self.seed, "events": self.events},
            sort_keys=True,
            default=str,
        )
        return hashlib.sha256(blob.encode()).hexdigest()

    def save(self, path) -> str:
        with open(path, "w") as f:
            json.dump(
                {
                    "seed": self.seed,
                    "digest": self.digest(),
                    "events": self.events,
                },
                f,
                indent=1,
                default=str,
            )
        return str(path)

    @staticmethod
    def load(path) -> dict:
        with open(path) as f:
            return json.load(f)


def assert_replay(record_path, scenario_fn) -> None:
    """Re-run `scenario_fn(trace)` against the saved record: the replay
    must reproduce the recorded event stream bit-for-bit."""
    rec = ScenarioTrace.load(record_path)
    fresh = ScenarioTrace(rec["seed"])
    scenario_fn(fresh)
    assert fresh.events == rec["events"], (
        "replay diverged from the recorded scenario"
    )
    assert fresh.digest() == rec["digest"]


# ---------------------------------------------------------------------------
# deterministic time
# ---------------------------------------------------------------------------


class FakeClock:
    """Injectable monotonic clock for the breaker's backoff schedule."""

    def __init__(self, t: float = 0.0):
        self.t = float(t)

    def __call__(self) -> float:
        return self.t

    def advance(self, dt: float) -> None:
        self.t += dt


# ---------------------------------------------------------------------------
# the oracle verifier
# ---------------------------------------------------------------------------


def chaos_sig(signing_root: bytes, indices) -> bytes:
    """The oracle's notion of THE valid signature for a statement —
    deterministic 96 bytes derived from (root, indices)."""
    h = hashlib.sha256(
        b"chaos-sig" + bytes(signing_root) + bytes(list(indices))
    ).digest()
    return (h * 3)[:96]


class ChaosVerifier(TpuBlsVerifier):
    """TpuBlsVerifier with an oracle replacing the crypto (see module
    docstring).  `fault` maps stage -> mode; clear it to heal."""

    def __init__(self, capacity: int = 64, supervisor=None, metrics=None):
        metrics = metrics or BlsPoolMetrics()
        super().__init__(
            PubkeyTable(capacity=capacity),
            metrics=metrics,
            rng=np.random.default_rng(0),
            supervisor=supervisor,
        )
        self.capacity = capacity
        self.fault = {}
        self.hang_release = threading.Event()
        self.device_jobs = 0  # jobs that finished via the device path
        self.host_sets = 0  # sets resolved via the host fallback seam

    # -- fault injection ---------------------------------------------------

    def _maybe_fault(self, stage: str) -> None:
        mode = self.fault.get(stage)
        if mode is None:
            return
        if mode == "raise":
            raise RuntimeError("injected device fault (chaos)")
        if mode == "backend":
            raise RuntimeError(
                "injected: TPU backend UNAVAILABLE, device lost"
            )
        if mode == "hang":
            # blocks until released or the watchdog deadline fires (the
            # supervisor abandons this thread); bounded for safety
            self.hang_release.wait(timeout=30.0)
            raise RuntimeError("injected hang released without recovery")

    def heal(self) -> None:
        self.fault = {}
        self.hang_release.set()

    # -- oracle truth ------------------------------------------------------

    def _truth(self, s) -> bool:
        if isinstance(s, WireSignatureSet):
            return s.signature == chaos_sig(s.signing_root, s.indices)
        return bool(getattr(s, "ok", False))

    # -- the device seams, oracle-stubbed ----------------------------------

    def _begin_job(self, sets, batchable, span=None) -> "_DeviceJob":
        self._maybe_fault("begin")
        wire = bool(sets) and isinstance(sets[0], WireSignatureSet)
        job = _DeviceJob(list(sets), batchable, True, wire)
        job.decodable = np.ones(len(sets), bool)
        return job

    def _finish_job(self, job) -> bool:
        self._maybe_fault("finish")
        plane = np.array([self._truth(s) for s in job.sets], bool)
        if self.fault.get("output") == "truncated":
            plane = plane[: max(len(job.sets) - 1, 0)]
        v = check_verdict_plane(plane, len(job.sets), "chaos-device")
        job.verdicts = v
        self.device_jobs += 1
        good = int(v.sum())
        self.metrics.success_jobs.inc(good)
        self.metrics.invalid_sets.inc(len(job.sets) - good)
        return bool(v.all())

    def _verify_set_host(self, s) -> bool:
        # the degraded-mode seam: same oracle -> bit-identical verdicts
        self.host_sets += 1
        return self._truth(s)

    def _device_canary(self) -> bool:
        def _probe() -> bool:
            self._maybe_fault("canary")
            self._maybe_fault("begin")
            self._maybe_fault("finish")
            return True

        return bool(self.supervisor.run_guarded(_probe, "canary"))


class OkSet:
    """Truth-flagged stand-in set for the RLC bisection planner."""

    __slots__ = ("ok",)

    def __init__(self, ok: bool):
        self.ok = bool(ok)


class RlcOracleVerifier(TpuBlsVerifier):
    """The REAL RLC bisection machinery over an ok-flag oracle — the
    gossip-DoS scenarios' bisection-floor leg (an invalid-signature
    flood must cost O(log N) batch checks per bad set, not a full
    per-set sweep)."""

    def __init__(self, bisect_leaf: int = 16):
        super().__init__(
            PubkeyTable(capacity=2),
            rng=np.random.default_rng(0),
            bisect_leaf=bisect_leaf,
        )
        self.batch_calls = []
        self.leaf_calls = []

    def _dispatch_batch(self, sets, wire):
        self.batch_calls.append(len(sets))
        return all(s.ok for s in sets)

    def _batch_verdict(self, handle):
        return handle

    def _per_set_verdicts(self, sets, wire):
        self.leaf_calls.append(len(sets))
        return np.array([s.ok for s in sets])


# ---------------------------------------------------------------------------
# the fast data-plane world
# ---------------------------------------------------------------------------


class FloodWorld:
    """ChaosVerifier + breaker + pipeline + SLO engine + flight
    recorder, wired the way node.py wires a FullBeaconNode (degraded
    source, trip/recovery anomalies, breaker bundle provider)."""

    def __init__(
        self,
        flightrec_dir,
        seed: int = 0,
        backoff_s: float = 2.0,
        standard_wait_ms: float = 30.0,
        job_deadline_s=None,
    ):
        import random

        self.fake = FakeClock()
        self.metrics = BlsPoolMetrics()
        self.registry = self.metrics.registry
        self.supervisor = DeviceSupervisor(
            registry=self.registry,
            clock=self.fake,
            auto_probe=False,  # scenarios drive poll() deterministically
            backoff_initial_s=backoff_s,
            job_deadline_s=job_deadline_s,
            enabled=True,
            rng=random.Random(seed),
        )
        self.verifier = ChaosVerifier(
            supervisor=self.supervisor, metrics=self.metrics
        )
        # the aggregation stage's breaker interplay is covered at the
        # agg seam directly (tests/test_supervisor.py); the flood
        # scenarios keep preagg off so the oracle's fake signature
        # bytes never hit real G2 decompression
        self.pipeline = BlsVerificationPipeline(
            self.verifier, preagg=False, standard_wait_ms=standard_wait_ms
        )
        self.clock = Clock(genesis_time=0.0)
        self.recorder = FlightRecorder(
            str(flightrec_dir), registry=self.registry
        )
        self.recorder.add_provider("breaker", self.supervisor.status)
        self.slo = SloEngine(
            self.clock,
            registry=self.registry,
            recorder=self.recorder,
            pipeline=self.pipeline,
        )
        # node.py's breaker wiring, reproduced verbatim
        self.slo.add_degraded_source(
            "bls_breaker", self.supervisor.is_open
        )
        self.supervisor.on_trip = lambda info: self.slo.anomaly(
            "bls_breaker_trip", info
        )
        self.supervisor.on_recover = lambda info: self.slo.anomaly(
            "bls_breaker_recovery", info
        )
        self.clock.on_slot(self.slo.on_slot)
        self._slot = 0
        self.futures = []  # (label, expected, future)

    # -- drivers -----------------------------------------------------------

    def tick_slot(self) -> int:
        """Advance the node clock one slot (drains SLO captures)."""
        from lodestar_tpu import params

        self._slot += 1
        self.clock.set_time(self._slot * params.SECONDS_PER_SLOT)
        return self._slot

    def submit_wave(
        self, n: int, wave: int, invalid_every: int = 0, priority=False
    ) -> None:
        """One flood wave: `n` distinct wire sets (every
        `invalid_every`-th carries a wrong signature)."""
        cap = self.verifier.capacity
        for j in range(n):
            vi = (wave * n + j) % cap
            root = b"chaos root %04d/%04d" % (wave, j)
            sig = chaos_sig(root, (vi,))
            expected = True
            if invalid_every and j % invalid_every == 0:
                sig = b"\x99" * 96
                expected = False
            ws = WireSignatureSet.single(vi, root, sig)
            fut = self.pipeline.verify_signature_sets_async(
                [ws],
                VerifyOptions(
                    batchable=True,
                    priority=priority,
                    peer_id="chaos-peer-%d" % (j % 4),
                ),
            )
            self.futures.append((f"w{wave}m{j}", expected, fut))

    def drain(self, timeout: float = 60.0) -> dict:
        """Resolve every outstanding future.  Returns the zero-lost-
        verdicts summary: counts + any mismatches (deterministic)."""
        total = len(self.futures)
        mismatches = []
        ok_true = ok_false = 0
        for label, expected, fut in self.futures:
            got = fut.result(timeout=timeout)
            if got != expected:
                mismatches.append(label)
            elif expected:
                ok_true += 1
            else:
                ok_false += 1
        self.futures = []
        return {
            "submitted": total,
            "valid_confirmed": ok_true,
            "invalid_rejected": ok_false,
            "mismatches": mismatches,
        }

    def close(self) -> None:
        self.pipeline.close()


# ---------------------------------------------------------------------------
# the consensus-level world (slow scenarios)
# ---------------------------------------------------------------------------


def build_devnet(
    n_nodes: int,
    n_keys: int = 8,
    db_paths=None,
    flightrec_dirs=None,
    genesis_time: int = 10,
):
    """N FullBeaconNodes with real crypto over one InMemoryGossipBus —
    the consensus-level chaos world (fork storms, partitions,
    crash/restart).  Returns a dict world."""
    from lodestar_tpu.bls.single_thread import CpuBlsVerifier
    from lodestar_tpu.config import MAINNET_CHAIN_CONFIG, create_chain_config
    from lodestar_tpu.crypto import bls as B
    from lodestar_tpu.crypto import curves as C
    from lodestar_tpu.network.gossip import InMemoryGossipBus
    from lodestar_tpu.node import FullBeaconNode, NodeOptions
    from lodestar_tpu.params import ForkName
    from lodestar_tpu.state_transition import create_genesis_state
    from lodestar_tpu.validator import ValidatorStore

    cfg = create_chain_config(
        MAINNET_CHAIN_CONFIG,
        fork_epochs={ForkName.altair: 0},
        genesis_time=genesis_time,
    )
    sks = [B.keygen(b"chaos-%d" % i) for i in range(n_keys)]
    pk_points = [B.sk_to_pk(sk) for sk in sks]
    pks = [C.g1_compress(p) for p in pk_points]
    genesis = create_genesis_state(cfg, pks, genesis_time=genesis_time)
    bus = InMemoryGossipBus()
    digest = cfg.fork_digest(0)

    nodes = {}
    names = [f"node-{i}" for i in range(n_nodes)]
    for i, name in enumerate(names):
        nodes[name] = FullBeaconNode.init(
            cfg,
            genesis,
            NodeOptions(
                serve_api=False,
                verifier=CpuBlsVerifier(pubkeys=pk_points),
                gossip_bus=bus,
                node_id=name,
                active_validator_count_hint=n_keys,
                subscribe_all_subnets=True,
                db_path=(db_paths or {}).get(name),
                flightrec_dir=(flightrec_dirs or {}).get(name),
            ),
        )
    owners = {i: names[i % n_nodes] for i in range(n_keys)}
    stores = {
        name: ValidatorStore(
            cfg, {i: sks[i] for i in range(n_keys) if owners[i] == name}
        )
        for name in names
    }
    return {
        "cfg": cfg,
        "genesis": genesis,
        "bus": bus,
        "digest": digest,
        "nodes": nodes,
        "names": names,
        "owners": owners,
        "stores": stores,
        "sks": sks,
        "pk_points": pk_points,
        "genesis_time": genesis_time,
        "block_ledger": {},  # slot -> signed block (the publish log)
    }


def set_clocks(world, slot: int, frac: float = 0.0) -> None:
    from lodestar_tpu import params

    t = world["genesis_time"] + (slot + frac) * params.SECONDS_PER_SLOT
    for n in world["nodes"].values():
        n.clock.set_time(t)


def produce_signed_block(world, ref_chain, slot: int, graffiti=None):
    """Produce + sign one block for `slot` on `ref_chain`'s head."""
    from lodestar_tpu.state_transition.accessors import (
        get_beacon_proposer_index,
    )
    from lodestar_tpu.state_transition.slot import process_slots

    st = ref_chain.head_state.clone()
    if st.slot < slot:
        process_slots(st, slot)
    proposer = int(get_beacon_proposer_index(st))
    owner = world["stores"][world["owners"][proposer]]
    kwargs = {}
    if graffiti is not None:
        kwargs["graffiti"] = graffiti
    block = ref_chain.produce_block(
        slot, owner.sign_randao(proposer, slot), **kwargs
    )
    return (
        {"message": block, "signature": owner.sign_block(proposer, block)},
        proposer,
    )


def publish_block(
    world, signed, slot: int, from_node="proposer", ledger: bool = True
) -> int:
    from lodestar_tpu.network.gossip import (
        GossipTopicName,
        encode_message,
        topic_string,
    )

    if ledger:
        world["block_ledger"][slot] = signed
    return world["bus"].publish(
        from_node,
        topic_string(world["digest"], GossipTopicName.beacon_block),
        encode_message(
            world["cfg"].get_fork_types(slot)[1].serialize(signed)
        ),
    )


def publish_attestations(
    world,
    ref_chain,
    slot: int,
    quiet=(),
    aggregates: bool = True,
    individuals: bool = True,
) -> int:
    """Every committee member (minus `quiet`) attests over gossip; the
    first member aggregates (block production packs the aggregated
    pool, so justification needs this leg).  Publisher ids are the
    OWNING node names, so bus partitions apply to validator traffic.
    `individuals=False` publishes only the aggregates — the consensus-
    relevant leg — which long soak scenarios use to keep N-epoch
    real-crypto runs inside the slow-tier budget (each node otherwise
    pays one pairing per member per slot for subnet copies that never
    feed the pools)."""
    from lodestar_tpu import types as T
    from lodestar_tpu.crypto import bls as B
    from lodestar_tpu.crypto import curves as C
    from lodestar_tpu.network.gossip import (
        GossipTopicName,
        encode_message,
        topic_string,
    )
    from lodestar_tpu.network.subnets import compute_subnet_for_attestation
    from lodestar_tpu.state_transition.accessors import (
        get_beacon_committee,
        get_committee_count_per_slot,
    )
    from lodestar_tpu.state_transition.util import compute_epoch_at_slot

    epoch = compute_epoch_at_slot(slot)
    st = ref_chain.head_state
    committees = int(get_committee_count_per_slot(st, epoch))
    published = 0
    for ci in range(committees):
        committee = get_beacon_committee(st, slot, ci)
        if len(committee) == 0:
            continue
        data = ref_chain.produce_attestation_data(ci, slot)
        subnet = compute_subnet_for_attestation(committees, slot, ci)
        member_sigs = {}
        for pos, v in enumerate(committee):
            v = int(v)
            if v in quiet:
                continue
            sig = world["stores"][world["owners"][v]].sign_attestation(
                v, data
            )
            member_sigs[pos] = sig
            if not individuals:
                continue
            att = {
                "aggregation_bits": [p == pos for p in range(len(committee))],
                "data": data,
                "signature": sig,
            }
            world["bus"].publish(
                f"{world['owners'][v]}:val-{v}",
                topic_string(
                    world["digest"],
                    GossipTopicName.beacon_attestation,
                    subnet=subnet,
                ),
                encode_message(T.Attestation.serialize(att)),
            )
            published += 1
        if not aggregates or not member_sigs:
            continue
        aggregator = int(committee[0])
        if aggregator in quiet:
            continue
        agg_sig = C.g2_compress(
            B.aggregate_signatures(
                [C.g2_decompress(s) for s in member_sigs.values()]
            )
        )
        agg_store = world["stores"][world["owners"][aggregator]]
        message = {
            "aggregator_index": aggregator,
            "aggregate": {
                "aggregation_bits": [
                    p in member_sigs for p in range(len(committee))
                ],
                "data": data,
                "signature": agg_sig,
            },
            "selection_proof": agg_store.sign_selection_proof(
                aggregator, slot
            ),
        }
        signed_agg = {
            "message": message,
            "signature": agg_store.sign_aggregate_and_proof(
                aggregator, message
            ),
        }
        world["bus"].publish(
            f"{world['owners'][aggregator]}:agg-{aggregator}",
            topic_string(
                world["digest"], GossipTopicName.beacon_aggregate_and_proof
            ),
            encode_message(T.SignedAggregateAndProof.serialize(signed_agg)),
        )
    return published


class LedgerSource:
    """BlockSource over the world's publish ledger (+ optionally a
    restarted node's own re-opened db, the crash/restart scenario's
    resume-from-db leg).  The harness's stand-in for a peer's req/resp
    server — the wire layer itself is covered by test_reqresp."""

    def __init__(self, world, db=None):
        self.world = world
        self.db = db
        self._roots = {}
        for slot, signed in world["block_ledger"].items():
            root = world["cfg"].get_fork_types(slot)[0].hash_tree_root(
                signed["message"]
            )
            self._roots[bytes(root)] = signed

    def get_blocks_by_range(self, start_slot: int, count: int):
        out = []
        for slot in sorted(self.world["block_ledger"]):
            if start_slot <= slot < start_slot + count:
                signed = None
                if self.db is not None:
                    root = self.world["cfg"].get_fork_types(slot)[
                        0
                    ].hash_tree_root(
                        self.world["block_ledger"][slot]["message"]
                    )
                    signed = self.db.get_block_anywhere(bytes(root))
                out.append(
                    signed or self.world["block_ledger"][slot]
                )
        return out

    def get_blocks_by_root(self, roots):
        out = []
        for r in roots:
            signed = self._roots.get(bytes(r))
            if signed is not None:
                out.append(signed)
        return out


def close_devnet(world) -> None:
    for n in world["nodes"].values():
        n.close()


# ---------------------------------------------------------------------------
# the state-plane world (memory-squeeze scenarios, ISSUE 15)
# ---------------------------------------------------------------------------


class _StubBlsService:
    """Always-true signature service: the squeeze scenarios stress the
    STATE plane (regen, caches, the governor's ladder); with a service
    injected the chain skips in-STF signature checks — the exact
    contract regen replay already runs under."""

    def verify_signature_sets(self, sets):
        return True

    def close(self) -> None:
        pass


class StateWorld:
    """BeaconChain + StateMemoryGovernor + SLO engine + flight
    recorder, wired the way node.py wires them (degraded source,
    pressure anomaly -> rate-limited bundle, governor on the slot
    tick) — the state-plane analog of FloodWorld.  Fork churn is
    scripted: each slot imports a head block plus (optionally) a
    competing side-fork block on the previous head, which keeps extra
    branch states resident exactly like a real churn burst."""

    GRAFFITI_FORK = b"\x42" * 32

    def __init__(
        self,
        flightrec_dir,
        seed: int = 0,
        n_keys: int = 16,
        budget_bytes=None,
        db_path=None,
    ):
        from lodestar_tpu.chain.chain import BeaconChain
        from lodestar_tpu.config import (
            MAINNET_CHAIN_CONFIG,
            create_chain_config,
        )
        from lodestar_tpu.crypto import bls as B
        from lodestar_tpu.crypto import curves as C
        from lodestar_tpu.db import BeaconDb
        from lodestar_tpu.observability.timeseries import (
            MetricsSampler,
            TimeSeriesRing,
        )
        from lodestar_tpu.params import ForkName
        from lodestar_tpu.state_transition import create_genesis_state
        from lodestar_tpu.utils.metrics import Registry

        self.seed = int(seed)
        self.cfg = create_chain_config(
            MAINNET_CHAIN_CONFIG,
            fork_epochs={ForkName.altair: 0},
            genesis_time=0,
        )
        # real pubkey points (genesis decompresses them for the sync
        # committee); every SIGNATURE stays stubbed — the scenarios
        # stress the state plane, not the pairing
        pks = [
            C.g1_compress(B.sk_to_pk(B.keygen(b"squeeze-%d" % i)))
            for i in range(n_keys)
        ]
        genesis = create_genesis_state(self.cfg, pks, genesis_time=0)
        self.registry = Registry()
        self.db = BeaconDb(db_path)
        self.chain = BeaconChain(
            self.cfg,
            genesis,
            db=self.db,
            bls_verifier=_StubBlsService(),
            state_budget_bytes=budget_bytes,
            registry=self.registry,
        )
        self.governor = self.chain.memory_governor
        self.clock = Clock(genesis_time=0.0)
        self.recorder = FlightRecorder(
            str(flightrec_dir), registry=self.registry
        )
        ring = TimeSeriesRing()
        sampler = MetricsSampler(ring)
        if self.governor is not None:
            self.recorder.add_provider("memory", self.governor.status)
            sampler.add_gauge(
                "state_resident_bytes",
                lambda: float(self.governor.ledger.resident_bytes),
            )
        self.slo = SloEngine(
            self.clock, registry=self.registry, recorder=self.recorder,
            sampler=sampler,
        )
        # node.py's governor wiring, reproduced verbatim
        if self.governor is not None:
            gov = self.governor
            self.slo.add_degraded_source(
                "state_memory", lambda: gov.pressure_active
            )
            gov.on_pressure = lambda info: self.slo.anomaly(
                "state_memory_pressure", info
            )
            self.clock.on_slot(gov.on_slot)
        self.clock.on_slot(self.slo.on_slot)
        self.chain.on_import_complete = self.slo.on_block_imported
        self._slot = 0
        self._prev_head = self.chain.head_root_hex
        # block_root_hex -> expected post-state root hex: the
        # never-evicted twin ledger every regen result checks against
        self.expected_roots = {
            self.chain.anchor_root_hex: genesis.hash_tree_root().hex()
        }

    # -- drivers -----------------------------------------------------------

    def tick_slot(self) -> int:
        from lodestar_tpu import params

        self._slot += 1
        self.clock.set_time(self._slot * params.SECONDS_PER_SLOT)
        return self._slot

    def _attestations_for(self, parent_root_hex: str):
        """Full-participation attestations voting the parent block as
        head (fake signatures — the stub service accepts, the STF skips
        sig checks): enough FFG weight to justify and finalize, so the
        scenarios can exercise the finalization sweeps."""
        from lodestar_tpu import params as _p
        from lodestar_tpu.state_transition.accessors import (
            get_beacon_committee,
            get_block_root_at_slot,
            get_committee_count_per_slot,
        )
        from lodestar_tpu.state_transition.util import (
            compute_epoch_at_slot,
        )

        post = self.chain.regen._get_post_state(parent_root_hex)
        slot = int(post.slot)
        if slot == 0:
            return []
        head_root = bytes.fromhex(parent_root_hex)
        epoch = compute_epoch_at_slot(slot)
        start = epoch * _p.ACTIVE_PRESET.SLOTS_PER_EPOCH
        target_root = (
            head_root
            if start >= slot
            else get_block_root_at_slot(post, start)
        )
        atts = []
        for index in range(get_committee_count_per_slot(post, epoch)):
            committee = get_beacon_committee(post, slot, index)
            atts.append(
                {
                    "aggregation_bits": [True] * len(committee),
                    "data": {
                        "slot": slot,
                        "index": index,
                        "beacon_block_root": head_root,
                        "source": dict(post.current_justified_checkpoint),
                        "target": {"epoch": epoch, "root": target_root},
                    },
                    "signature": bytes([0xC0]) + b"\x00" * 95,
                }
            )
        return atts

    def _produce_on(
        self, parent_root_hex: str, slot: int, graffiti, attest=False
    ):
        import hashlib as _hl

        from lodestar_tpu.chain.produce_block import produce_block

        parent_state = self.chain.regen._get_post_state(parent_root_hex)
        randao = (
            _hl.sha256(b"squeeze randao %d" % slot).digest() * 3
        )
        block, _post = produce_block(
            parent_state,
            slot,
            randao,
            graffiti=graffiti,
            attestations=(
                self._attestations_for(parent_root_hex) if attest else None
            ),
        )
        return {"message": block, "signature": b"\x00" * 96}

    def churn_slot(
        self, slot: int, fork: bool = True, attest: bool = False
    ) -> dict:
        """Import one head block (+ one side-fork block on the previous
        head when `fork`).  Returns deterministic import stats."""
        prev_head = self.chain.head_root_hex
        signed = self._produce_on(prev_head, slot, b"\x00" * 32, attest)
        root = self.chain.process_block(signed)
        self.expected_roots[root.hex()] = (
            signed["message"]["state_root"].hex()
        )
        forked = False
        if fork and self._prev_head != prev_head:
            signed2 = self._produce_on(
                self._prev_head, slot, self.GRAFFITI_FORK
            )
            root2 = self.chain.process_block(signed2)
            self.expected_roots[root2.hex()] = (
                signed2["message"]["state_root"].hex()
            )
            forked = True
        self._prev_head = prev_head
        return {"slot": slot, "forked": forked}

    def warm_checkpoint(self, epoch: int) -> None:
        """Populate the checkpoint cache on the head chain (the entry
        attestation validation would create)."""
        self.chain.regen.get_checkpoint_state(
            {"epoch": epoch, "root": self.chain.get_head_root()}
        )

    def verify_regen(self, block_root_hex: str) -> bool:
        """Regen the block's post-state (possibly rehydrating a spill
        or replaying from db) and check it against the never-evicted
        twin's recorded root — bit-identical or bust."""
        st = self.chain.regen._get_post_state(block_root_hex)
        return (
            st.hash_tree_root().hex() == self.expected_roots[block_root_hex]
        )

    def close(self) -> None:
        self.db.close()


def heads(world) -> dict:
    return {
        name: n.chain.head_root_hex for name, n in world["nodes"].items()
    }
