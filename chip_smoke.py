"""BLS verification end to end on one TPU, through the node's own path.

    python3 chip_smoke.py [--seed N]

The quickest proof that the system still starts on the chip.  One
process, one chip:

1. Device check: exits non-zero at once unless JAX's first device is a
   TPU, and unless pallas kernels lower through Mosaic (not interpret).
2. World: a 1,000,000-row device pubkey table (BASELINE config 5, the
   264 MB the node keeps in HBM) tiling a few dozen real keypairs, with
   validator indices drawn from the whole table; wire attestations
   (32-byte signing root, 96-byte compressed signature) over mainnet's
   64 committees per slot.
3. The node's path, composed as `FullBeaconNode.init` composes it:
   `TpuBlsVerifier(table, metrics=...)` behind `create_bls_service`, every
   default kept (pre-verify aggregation, lanes, breaker).  Honest waves,
   one set per submission as gossip submits them, then one 128-set wave
   holding tampered sets (wrong key, flipped signature byte), which
   fails its RLC batch and bisects down to per-set verdicts.
4. Checks: every verdict against the one known by construction, the
   tampered sets plus a seeded sample of valid ones against the host
   oracle (`crypto.bls.verify`), no breaker trip, no set resolved on the
   host path.
5. Prints per-phase seconds, cache counters and shapes on earlier lines;
   the last line is `{"ok": true, "device": {...}}` and nothing more.

Any failure exits non-zero without that line.  Rates printed are a
smoke reading of a cold process, not a benchmark metric.
"""

from __future__ import annotations

import argparse
import collections
import json
import os
import sys
import threading
import time

VALIDATORS = 1_000_000  # BASELINE config 5
COMMITTEES = 64  # mainnet committees per slot at this registry size
KEYS = 32  # real keypairs tiled across the table
HONEST_WAVES = 8
# 64 x 4 = 256 attestations a wave; 4 per committee keeps every
# aggregated layer in the K=4 bucket the tampered wave needs anyway
# (one pubkey-aggregation compile fewer on a cold chip)
HONEST_PER_COMMITTEE = 4
TAMPER_COMMITTEES = 32
TAMPER_PER_COMMITTEE = 4  # 32 x 4 = 128 sets: one lane tile
ORACLE_SAMPLE = 16
BUDGET_S = 1150  # the driver allows 1200 s, compilation included


def _fail(msg: str) -> None:
    print(f"chip_smoke: FAIL: {msg}", file=sys.stderr, flush=True)
    sys.exit(1)


def _arm_budget() -> None:
    """A hung device or compile must not outlive the driver's limit:
    exit non-zero (no result line) when the budget runs out."""

    def _expire():
        print(
            f"chip_smoke: FAIL: exceeded {BUDGET_S}s", file=sys.stderr, flush=True
        )
        os._exit(1)

    t = threading.Timer(BUDGET_S, _expire)
    t.daemon = True
    t.start()


class _Durations:
    """Sums of JAX's own compile-phase duration events."""

    def __init__(self):
        import jax

        self.s = collections.Counter()
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, name, secs, **_kw):
        self.s[name] += secs

    def get(self, suffix: str) -> float:
        return sum(v for k, v in self.s.items() if k.endswith(suffix))


def _build_world(rng):
    """Keys, the 1M-row table on device, and the attestation waves.

    Returns (table, waves, pks): each wave is a list of (WireSignatureSet,
    expected verdict); row i of the table holds pks[i % KEYS].
    """
    import numpy as np

    from lodestar_tpu.bls.pubkey_table import PubkeyTable
    from lodestar_tpu.bls.signature_set import WireSignatureSet
    from lodestar_tpu.crypto import bls as B
    from lodestar_tpu.crypto import curves as C
    from lodestar_tpu.crypto.hash_to_curve import hash_to_g2

    sks = [B.keygen(rng.bytes(32)) for _ in range(KEYS)]
    pks = [B.sk_to_pk(sk) for sk in sks]
    table = PubkeyTable(capacity=VALIDATORS)
    table.register_points_unchecked(pks, tile_to=VALIDATORS)
    tx, ty = table.device_planes()
    tx.block_until_ready()
    ty.block_until_ready()

    sig_cache = {}
    hashed = {}

    def sign(key: int, root: bytes) -> bytes:
        if (key, root) not in sig_cache:
            if root not in hashed:
                hashed[root] = hash_to_g2(root)
            sig_cache[(key, root)] = C.g2_compress(
                C.scalar_mul(C.FP2_OPS, hashed[root], sks[key])
            )
        return sig_cache[(key, root)]

    waves = []
    n_honest = HONEST_WAVES * COMMITTEES * HONEST_PER_COMMITTEE
    n_tamper = TAMPER_COMMITTEES * TAMPER_PER_COMMITTEE
    # every attester once across the run, drawn from the whole registry;
    # one slot: each committee votes for one signing root, and its
    # attestations arrive over the waves
    attesters = rng.choice(VALIDATORS, n_honest + n_tamper, replace=False)
    roots = [rng.bytes(32) for _ in range(COMMITTEES)]
    pos = 0
    for _ in range(HONEST_WAVES):
        wave = []
        for root in roots:
            for _m in range(HONEST_PER_COMMITTEE):
                i = int(attesters[pos])
                pos += 1
                wave.append(
                    (WireSignatureSet.single(i, root, sign(i % KEYS, root)), True)
                )
        waves.append(wave)
    wave = []
    for c, root in enumerate(roots[:TAMPER_COMMITTEES]):
        for m in range(TAMPER_PER_COMMITTEE):
            i = int(attesters[pos])
            pos += 1
            sig = sign(i % KEYS, root)
            expected = True
            if m == 1 and c in (3, 17):
                # wrong key: signed by the next keypair, claimed by row i
                sig = sign((i + 1) % KEYS, root)
                expected = False
            elif m == 2 and c in (9, 26):
                # one flipped bit in the compressed x coordinate
                b = bytearray(sig)
                b[60] ^= 0x10
                sig = bytes(b)
                expected = False
            wave.append((WireSignatureSet.single(i, root, sig), expected))
    waves.append(wave)
    return table, waves, pks


def _run_wave(service, wave, deadline):
    from lodestar_tpu.bls.verifier import VerifyOptions

    futs = [
        service.verify_signature_sets_async([s], VerifyOptions(batchable=True))
        for s, _ in wave
    ]
    out = []
    for f in futs:
        remaining = deadline - time.monotonic()
        if remaining <= 0:
            _fail("a wave's verdicts did not arrive within the budget")
        out.append(bool(f.result(timeout=remaining)))
    return out


def _shape(key):
    """(entry, n-bucket, k-bucket) of one verifier dispatch key."""
    name, shapes = key[0], [s for s, _ in key[1:]]
    if name == "agg_g2_sum":
        return name, shapes[3][0], None
    if name == "hash_to_g2":  # distinct signing roots, padded
        return name, shapes[0][1], None
    return name, shapes[-1][0], shapes[2][1]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--seed", type=int, default=21)
    args = ap.parse_args(argv)
    t_start = time.monotonic()
    _arm_budget()

    t0 = time.perf_counter()
    import jax

    devices = jax.devices()
    dev = devices[0]
    t_init = time.perf_counter() - t0
    if dev.platform != "tpu":
        print(
            f"chip_smoke: no TPU found (JAX's device is {dev.platform!r}); "
            "this check runs only on the chip",
            file=sys.stderr,
        )
        return 2
    print(f"# device: {dev.device_kind} x{len(devices)} ({dev.platform})", flush=True)

    import numpy as np

    from lodestar_tpu.utils import compile_cache

    print(f"# compile cache: {compile_cache.configure()}", flush=True)
    from lodestar_tpu.kernels import launch

    if launch.interpret():
        _fail("pallas kernels would run in interpret mode on this backend")
    durations = _Durations()

    t0 = time.perf_counter()
    rng = np.random.default_rng(args.seed)
    table, waves, pks = _build_world(rng)
    t_world = time.perf_counter() - t0
    n_sets = sum(len(w) for w in waves)
    print(
        f"# world: {VALIDATORS} table rows on device, {n_sets} wire sets "
        f"in {len(waves)} waves over {COMMITTEES} committees, "
        f"{t_world:.3f}s",
        flush=True,
    )

    from lodestar_tpu.bls.pipeline import create_bls_service
    from lodestar_tpu.bls.verifier import TpuBlsVerifier
    from lodestar_tpu.utils.metrics import BlsPoolMetrics, Registry

    registry = Registry()
    verifier = TpuBlsVerifier(table, metrics=BlsPoolMetrics(registry))
    service = create_bls_service(verifier)

    deadline = t_start + BUDGET_S - 30
    verdicts = []
    t_traffic = time.perf_counter()
    fallbacks_rlc = verifier.metrics.rlc_fallback
    try:
        for k, wave in enumerate(waves):
            t0 = time.perf_counter()
            verdicts.append(_run_wave(service, wave, deadline))
            print(
                f"# wave {k}: {len(wave)} sets in "
                f"{time.perf_counter() - t0:.3f}s (first dispatches so far "
                f"{verifier.supervisor.compile_seconds():.3f}s)",
                flush=True,
            )
            if k == HONEST_WAVES - 1 and fallbacks_rlc.value:
                # a broken batch path would hide behind the per-set
                # fallback: honest waves must pass their RLC batches
                _fail(f"{fallbacks_rlc.value} honest RLC batches failed")
    finally:
        service.close()
    if not fallbacks_rlc.value:
        _fail("the tampered wave never failed an RLC batch")
    t_traffic = time.perf_counter() - t_traffic

    # -- verdicts ------------------------------------------------------
    wrong = [
        (k, j)
        for k, wave in enumerate(waves)
        for j, (_s, expected) in enumerate(wave)
        if verdicts[k][j] != expected
    ]
    if wrong:
        _fail(f"{len(wrong)} verdicts differ from construction: {wrong[:8]}")
    from lodestar_tpu.crypto import bls as B
    from lodestar_tpu.crypto import curves as C

    tampered = [s for s, e in waves[-1] if not e]
    valid = [s for w in waves for s, e in w if e]
    sample = [valid[int(i)] for i in rng.choice(len(valid), ORACLE_SAMPLE, replace=False)]
    for s in tampered + sample:
        try:
            sig = C.g2_decompress(s.signature)
            oracle = B.verify(pks[s.indices[0] % KEYS], s.signing_root, sig)
        except ValueError:
            oracle = False
        if oracle != (s in sample):
            _fail(f"host oracle disagrees on set {s.indices}")

    # -- nothing hidden --------------------------------------------------
    breaker = verifier.supervisor.status()
    host_sets = registry.get("lodestar_bls_breaker_host_fallback_sets_total").value
    if not breaker["enabled"] or breaker["trips"] or host_sets:
        _fail(f"breaker {breaker}, host-fallback sets {host_sets}")

    # -- report ----------------------------------------------------------
    from lodestar_tpu.utils.metrics import global_registry

    first = verifier.supervisor.compile_seconds()
    phases = {
        "device_init": t_init,
        "world": t_world,
        "first_dispatches": first,
        "run": max(t_traffic - first, 0.0),
    }
    print("# phases, wall (s): " + json.dumps(phases), flush=True)
    # what the first dispatches (compile windows) spent, by JAX's events
    work = {
        "trace": durations.get("jaxpr_trace_duration"),
        "lower": durations.get("jaxpr_to_mlir_module_duration"),
        "compile": durations.get("backend_compile_duration"),
    }
    print("# first dispatches, by step (s): " + json.dumps(work), flush=True)
    g = global_registry()
    for name in ("hits", "misses"):
        c = g.get(f"lodestar_tpu_export_cache_{name}_total")
        per = {lv: c.get(lv) for lv in c.label_values()} if c else {}
        print(f"# export cache {name}: {json.dumps(per)}", flush=True)
    builds = g.get("lodestar_tpu_pallas_builds_total")
    shapes = sorted({_shape(k) for k in verifier._dispatched}, key=str)
    print(
        f"# traced shapes: {len(shapes)} (entry, n, k) {shapes}; "
        f"pallas wrappers built: "
        f"{ {lv: builds.get(lv) for lv in builds.label_values()} if builds else {} }",
        flush=True,
    )
    agg = service.agg_stats() or {}
    print(
        f"# sets verified: {n_sets} ({len(tampered)} tampered rejected); "
        f"pre-verify aggregation: {json.dumps(agg)}; breaker: "
        f"{json.dumps(breaker)}",
        flush=True,
    )
    print(f"# total {time.monotonic() - t_start:.3f}s", flush=True)
    verifier.close()
    print(
        json.dumps(
            {
                "ok": True,
                "device": {
                    "platform": dev.platform,
                    "kind": dev.device_kind,
                    "count": len(devices),
                },
            }
        ),
        flush=True,
    )
    return 0


if __name__ == "__main__":
    sys.exit(main())
