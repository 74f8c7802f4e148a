"""BASELINE configs 1-3 — the reference's perf-test shapes on the TPU verifier.

  1. verifySignatureSets: 128 single-pubkey attestation sets per job
     (reference harness: packages/beacon-node/test/perf/bls/bls.test.ts:37-64)
  2. aggregate attestation: 1 signature over 128 aggregated pubkeys,
     batched x256 (device gather + point-add per set)
  3. full Altair block: proposer + RANDAO + attestations + sync committee
     via get_block_signature_sets
     (reference: state-transition/src/signatureSets/index.ts:26-73;
      45 ms/100-sig block extraction noted verifyBlocksSignatures.ts:41)

Configs 4-5 (gossip replay at 500k/1M validators) live in replay.py.
Prints one JSON line per config.
"""

from __future__ import annotations

import json
import os
import time

os.environ.setdefault("XLA_FLAGS", "")

import numpy as np

from lodestar_tpu.utils import compile_cache  # noqa: E402

compile_cache.configure()

from lodestar_tpu import params
from lodestar_tpu import types as T
from lodestar_tpu.bls.pubkey_table import PubkeyTable
from lodestar_tpu.bls.signature_set import WireSignatureSet
from lodestar_tpu.bls.verifier import TpuBlsVerifier, VerifyOptions
from lodestar_tpu.config import MAINNET_CHAIN_CONFIG, create_chain_config
from lodestar_tpu.crypto import bls as B
from lodestar_tpu.crypto import curves as C
from lodestar_tpu.params import ForkName
from lodestar_tpu.state_transition import EpochCache, get_block_signature_sets
from lodestar_tpu.state_transition.signature_sets import (
    BeaconStateView,
    get_attestation_data_signing_root,
)

REPEATS = int(os.environ.get("BENCH_REPEATS", "8"))
KEYS = 64


def emit(metric, sets, dt, extra=None):
    out = {
        "metric": metric,
        "value": round(sets / dt, 2),
        "unit": "sets/s",
        "sets": sets,
        "wall_s": round(dt, 3),
    }
    out.update(extra or {})
    print(json.dumps(out), flush=True)


def build():
    sks = [B.keygen(b"cfg-%d" % i) for i in range(KEYS)]
    pks = [B.sk_to_pk(sk) for sk in sks]
    table = PubkeyTable(capacity=4096)
    table.register_points_unchecked(pks, tile_to=4096)
    table.device_planes()
    verifier = TpuBlsVerifier(table, max_job_sets=512)
    return sks, table, verifier


def config1(sks, verifier):
    """128 single-pubkey sets per job, REPEATS jobs pipelined."""
    jobs = []
    for r in range(REPEATS + 1):
        root = (b"c1-%d" % r).ljust(32, b"\x00")
        sets = [
            WireSignatureSet.single(
                j, root, C.g2_compress(B.sign(sks[j % KEYS], root))
            )
            for j in range(128)
        ]
        jobs.append(sets)
    h = verifier.begin_job(jobs[0], True)
    assert verifier.finish_job(h)
    t0 = time.perf_counter()
    hs = [verifier.begin_job(j, True) for j in jobs[1:]]
    ok = all(verifier.finish_job(h) for h in hs)
    dt = time.perf_counter() - t0
    assert ok
    emit("config1_single_128_sets_per_s", 128 * REPEATS, dt)


def config2(sks, verifier):
    """256 aggregate sets, each 1 sig over 128 aggregated pubkeys."""
    root = b"c2-root".ljust(32, b"\x00")
    members = list(range(128))
    agg_sig = C.g2_compress(
        B.aggregate_signatures(
            [B.sign(sks[i % KEYS], root) for i in members]
        )
    )
    sets = [
        WireSignatureSet.aggregate(members, root, agg_sig) for _ in range(256)
    ]
    h = verifier.begin_job(sets[:256], True)
    assert verifier.finish_job(h)
    t0 = time.perf_counter()
    hs = [verifier.begin_job(sets, True) for _ in range(max(REPEATS // 2, 1))]
    ok = all(verifier.finish_job(h) for h in hs)
    dt = time.perf_counter() - t0
    assert ok
    n = 256 * max(REPEATS // 2, 1)
    emit("config2_aggregate_128x256_sets_per_s", n, dt)


def config3(sks, verifier):
    """Full Altair block signature sets via the extractors."""
    cfg = create_chain_config(
        MAINNET_CHAIN_CONFIG, fork_epochs={ForkName.altair: 0}
    )
    pk_bytes = [C.g1_compress(B.sk_to_pk(sk)) for sk in sks]
    cache = EpochCache(pk_bytes, epoch=0, seed=b"\x07" * 32)
    state = BeaconStateView(cfg, 1, cache, block_roots={0: b"\x33" * 32})

    slot, proposer = 1, 3
    atts = []
    for ci in range(cache.committees_per_slot):
        committee = cache.get_beacon_committee(slot, ci)
        if len(committee) == 0:
            continue
        data = {
            "slot": slot, "index": ci, "beacon_block_root": b"\x33" * 32,
            "source": {"epoch": 0, "root": bytes(32)},
            "target": {"epoch": 0, "root": b"\x33" * 32},
        }
        root = get_attestation_data_signing_root(state, data)
        sig = B.aggregate_signatures(
            [B.sign(sks[int(v) % KEYS], root) for v in committee]
        )
        atts.append({
            "aggregation_bits": [True] * len(committee),
            "data": data,
            "signature": C.g2_compress(sig),
        })

    randao_root = cfg.compute_signing_root(
        T.Epoch.hash_tree_root(0), cfg.get_domain(slot, params.DOMAIN_RANDAO, slot)
    )
    body = T.BeaconBlockBodyAltair.default()
    body["randao_reveal"] = C.g2_compress(B.sign(sks[proposer], randao_root))
    body["attestations"] = atts
    sync_bits = [False] * params.SYNC_COMMITTEE_SIZE
    for i in range(16):
        sync_bits[i] = True
    participants = [cache.sync_committee_indices[i] for i in range(16)]
    sync_signing = cfg.compute_signing_root(
        T.Root.hash_tree_root(b"\x33" * 32),
        cfg.get_domain(slot, params.DOMAIN_SYNC_COMMITTEE, slot - 1),
    )
    body["sync_aggregate"] = {
        "sync_committee_bits": sync_bits,
        "sync_committee_signature": C.g2_compress(
            B.aggregate_signatures(
                [B.sign(sks[int(v) % KEYS], sync_signing) for v in participants]
            )
        ),
    }
    block = {
        "slot": slot, "proposer_index": proposer,
        "parent_root": b"\x33" * 32, "state_root": bytes(32), "body": body,
    }
    proposer_root = cfg.compute_signing_root(
        T.BeaconBlockAltair.hash_tree_root(block),
        cfg.get_domain(slot, params.DOMAIN_BEACON_PROPOSER, slot),
    )
    signed = {
        "message": block,
        "signature": C.g2_compress(B.sign(sks[proposer], proposer_root)),
    }

    # extraction timing (the reference notes 45 ms/100-sig block)
    t0 = time.perf_counter()
    sets = get_block_signature_sets(state, signed)
    t_extract = time.perf_counter() - t0

    h = verifier.begin_job(sets, True)
    assert verifier.finish_job(h)
    t0 = time.perf_counter()
    hs = [verifier.begin_job(sets, True) for _ in range(REPEATS)]
    ok = all(verifier.finish_job(h) for h in hs)
    dt = time.perf_counter() - t0
    assert ok
    emit(
        "config3_altair_block_sets_per_s",
        len(sets) * REPEATS,
        dt,
        {"sets_per_block": len(sets), "extract_ms": round(t_extract * 1e3, 2)},
    )


def main():
    sks, _table, verifier = build()
    config1(sks, verifier)
    config2(sks, verifier)
    config3(sks, verifier)


if __name__ == "__main__":
    main()
