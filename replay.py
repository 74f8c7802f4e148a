"""Gossip replay harness — BASELINE configs 4-5.

Reproduces the reference's attestation-gossip hot loop end to end
(reference call stack: SURVEY.md §3.2): synthesized mainnet-shaped
traffic at N validators flows through

    NetworkProcessor gossip queues (LIFO 24,576, ratio drop, priority
    order, <=128 jobs/tick, backpressure on the BLS service)
      -> per-message validation (seen-attester dedup, SeenAttestationDatas
         signing-root + hashed-message reuse)
      -> BlsVerifierService (coalescing buffer -> pipelined device jobs)
      -> TPU batch verification

and reports sustained signature-sets/s, drop ratios, and queue stats.

Usage:
    python replay.py --validators 500000 --slots 2          # config 4
    python replay.py --validators 1000000 --slots 2         # config 5
    python replay.py --validators 4096 --slots 1 --distinct-keys 16  # smoke

Synthesis notes (documented deviations, all conservative):
  - the registry tiles --distinct-keys real keypairs across N validator
    indices (key material is not the scaling axis; the device pubkey
    table and gathers are full-size),
  - sets flow as WireSignatureSets (32B root + 96B compressed sig):
    signing roots are hashed to G2 in device batches via the verifier's
    MessageCache, signatures decompress on device inside the verify
    pipeline — the full byte-level ingest is on the measured path,
  - traffic is generated slot by slot: each slot, every committee's
    members attest (one single-pubkey set each) plus one sync-committee
    message per sync-committee member (reference: config "beacon_
    attestation_{subnet} + sync_committee").
"""

from __future__ import annotations

import argparse
import json
import os
import time

os.environ.setdefault("XLA_FLAGS", "")

import numpy as np

from lodestar_tpu.utils import compile_cache  # noqa: E402

compile_cache.configure()

from lodestar_tpu import params
from lodestar_tpu.bls.pubkey_table import PubkeyTable
from lodestar_tpu.bls.service import BlsVerifierService
from lodestar_tpu.bls.signature_set import WireSignatureSet
from lodestar_tpu.bls.verifier import TpuBlsVerifier, VerifyOptions
from lodestar_tpu.chain.seen_cache import SeenAttestationDatas, SeenAttesters
from lodestar_tpu.crypto import bls as B
from lodestar_tpu.network.gossip_queues import GossipType
from lodestar_tpu.network.processor import NetworkProcessor, PendingGossipMessage
from lodestar_tpu.state_transition.util import compute_committee_count_per_slot

def build_world(n_validators: int, distinct_keys: int, slots: int):
    """Keys, table, and per-(key, root) signatures."""
    sks = [B.keygen(b"replay-%d" % i) for i in range(distinct_keys)]
    pks = [B.sk_to_pk(sk) for sk in sks]

    from lodestar_tpu.crypto.curves import g2_compress

    committees = compute_committee_count_per_slot(n_validators)
    roots = {}
    sigs = {}
    for slot in range(slots):
        for c in range(committees):
            root = (b"att-%d-%d" % (slot, c)).ljust(32, b"\x00")[:32]
            roots[(slot, c)] = root
            for k in range(distinct_keys):
                sigs[(k, slot, c)] = g2_compress(B.sign(sks[k], root))
        sync_root = (b"sync-%d" % slot).ljust(32, b"\x00")[:32]
        roots[(slot, "sync")] = sync_root
        for k in range(distinct_keys):
            sigs[(k, slot, "sync")] = g2_compress(B.sign(sks[k], sync_root))
    return {
        "pks": pks,
        "committees": committees,
        "roots": roots,
        "sigs": sigs,
    }


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--validators", type=int, default=500_000)
    ap.add_argument("--slots", type=int, default=2)
    ap.add_argument("--distinct-keys", type=int, default=64)
    ap.add_argument("--job-sets", type=int, default=512)
    ap.add_argument("--buffer-sigs", type=int, default=512)
    ap.add_argument("--burst", type=int, default=2048,
                    help="messages pushed per scheduler tick (mainnet "
                    "observed 1-2k per tick, SURVEY.md §6)")
    args = ap.parse_args()

    V = args.validators
    t0 = time.perf_counter()
    world = build_world(V, args.distinct_keys, args.slots)
    print(f"# world built in {time.perf_counter() - t0:.1f}s "
          f"({world['committees']} committees/slot)", flush=True)

    # device pubkey table: tile the distinct keys across V rows
    table = PubkeyTable(capacity=V)
    K = args.distinct_keys
    t0 = time.perf_counter()
    table.register_points_unchecked(world["pks"], tile_to=V)
    table.device_planes()  # push to HBM
    print(f"# table of {V} rows resident in {time.perf_counter() - t0:.1f}s",
          flush=True)

    verifier = TpuBlsVerifier(table, max_job_sets=args.job_sets)
    service = BlsVerifierService(
        verifier,
        max_buffered_sigs=args.buffer_sigs,
        buffer_wait_ms=100,
    )

    seen_att = SeenAttesters()
    seen_data = SeenAttestationDatas(max_per_slot=world["committees"] + 8)
    futures = []
    stats = {"submitted": 0, "skipped_seen": 0}

    def worker(msg: PendingGossipMessage) -> None:
        kind, slot, c, validator_idx = msg.data
        epoch = slot // params.SLOTS_PER_EPOCH
        if seen_att.is_known(epoch, validator_idx):
            stats["skipped_seen"] += 1
            return
        # SeenAttestationDatas caches the derived signing root per data
        # (hash-to-curve itself batches in the verifier's MessageCache)
        data_key = b"%d-%s" % (slot, str(c).encode())
        root = seen_data.get(slot, data_key)
        if root is None:
            root = world["roots"][(slot, c)]
            seen_data.put(slot, data_key, root)
        sig = world["sigs"][(validator_idx % K, slot, c)]
        s = WireSignatureSet.single(validator_idx, root, sig)
        futures.append(
            service.verify_signature_sets_async(
                [s], VerifyOptions(batchable=True)
            )
        )
        seen_att.add(epoch, validator_idx)
        stats["submitted"] += 1

    proc = NetworkProcessor(worker, [service.can_accept_work])

    # synthesize arrival order: per slot, committees attest + sync msgs
    committees = world["committees"]
    rng = np.random.default_rng(0)
    t_start = time.perf_counter()
    pushed = 0
    for slot in range(args.slots):
        proc.on_clock_slot(slot)
        members = np.arange(V, dtype=np.int64)
        # per-slot attesters: V/SLOTS_PER_EPOCH validators split into
        # `committees` committees
        per_slot = members[
            (members % params.SLOTS_PER_EPOCH) == (slot % params.SLOTS_PER_EPOCH)
        ]
        rng.shuffle(per_slot)
        msgs = [
            (GossipType.beacon_attestation,
             ("att", slot, int(i) % committees, int(i)))
            for i in per_slot
        ]
        # sync committee messages
        sync_members = members[: params.SYNC_COMMITTEE_SIZE]
        msgs.extend(
            (GossipType.sync_committee, ("sync", slot, "sync", int(i)))
            for i in sync_members
        )
        for start in range(0, len(msgs), args.burst):
            for topic, payload in msgs[start : start + args.burst]:
                proc.on_gossip_message(
                    PendingGossipMessage(topic, payload, slot=slot)
                )
            proc.execute_work()
            pushed += min(args.burst, len(msgs) - start)
        # drain the slot: keep executing until queues empty
        while any(proc.queue_lengths().values()):
            if proc.execute_work() == 0:
                time.sleep(0.002)  # wait for backpressure to lift

    # resolve all verdicts
    ok = sum(1 for f in futures if f.result(timeout=600))
    dt = time.perf_counter() - t_start
    service.close()

    verified = stats["submitted"]
    out = {
        "metric": "replay_sig_sets_verified_per_s",
        "value": round(verified / dt, 2),
        "unit": "sets/s",
        "validators": V,
        "slots": args.slots,
        "submitted": verified,
        "verified_ok": ok,
        "dropped": proc.stats.dropped,
        "seen_skipped": stats["skipped_seen"],
        "att_drop_ratio": proc.queues[GossipType.beacon_attestation].drop_ratio,
        "wall_s": round(dt, 2),
        "seen_data_hits": seen_data.hits,
        "seen_data_misses": seen_data.misses,
    }
    print(json.dumps(out), flush=True)


if __name__ == "__main__":
    main()
