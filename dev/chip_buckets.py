"""Warm device-call seconds at the largest buckets a node reaches, on one TPU.

    python3 dev/chip_buckets.py [--seed N] [--repeats R] [--only a,b]

chip_smoke.py runs every dispatch at the 128-lane bucket.  This
measures what the breaker's watchdog deadline
(bls/supervisor.py DEFAULT_TPU_JOB_DEADLINE_S) has to allow at the top,
through TpuBlsVerifier on chip_smoke's world (1M-row table).  Phases:

- scan512: the G1 segmented scan alone at 512 lanes, segments crossing
  lane tiles, against the host oracle (the tile-to-tile carry);
- job512: one 512-set RLC job (MAX_JOB_SETS; block import and range
  sync submit batches this large), message groups crossing lane tiles;
- agg2048: one 2048-signature aggregation dispatch (N_BUCKETS[-1]; a
  multi-group `aggregate_wire_signatures` call), groups crossing the
  16 lane tiles of its G2 scan.

Checks every result against the host and that the breaker never
tripped; prints per-call wall seconds, the supervisor's slowest guarded
call, and a JSON line last.  Exits non-zero without a TPU.
"""

from __future__ import annotations

import argparse
import collections
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _uneven(sizes, total):
    """Cycle `sizes` into group lengths summing to `total`: group ends
    then fall inside lane tiles, not on their edges."""
    out, i = [], 0
    while sum(out) < total:
        out.append(min(sizes[i % len(sizes)], total - sum(out)))
        i += 1
    return out


def _scan512():
    """G1 segmented scan at 512 lanes vs the host oracle: True if every
    segment total (at its last lane) is right."""
    import jax.numpy as jnp
    import numpy as np

    from lodestar_tpu.crypto import curves as GC
    from lodestar_tpu.crypto import fields as GF
    from lodestar_tpu.kernels import layout as LY
    from lodestar_tpu.kernels import verify as KV

    n = 512
    pts = [GC.scalar_mul(GC.FP_OPS, GC.G1_GEN, 3 + i) for i in range(n)]
    lens = _uneven([5, 9, 10], n)
    group = np.repeat(np.arange(len(lens), dtype=np.int32), lens)
    enc = lambda v: jnp.asarray(LY.encode_batch(v))  # noqa: E731
    (X, Y, Z), _inf = KV._seg_sum_g1(
        enc([p[0] for p in pts]), enc([p[1] for p in pts]), enc([1] * n),
        jnp.zeros(n, bool), jnp.asarray(group),
    )
    xs, ys, zs = (LY.decode_batch(np.asarray(a)) for a in (X, Y, Z))
    ends = np.cumsum(lens)
    for lo, hi in zip([0, *ends[:-1]], ends):
        zi = GF.fp_inv(zs[hi - 1])
        zi2 = GF.fp_mul(zi, zi)
        got = (GF.fp_mul(xs[hi - 1], zi2), GF.fp_mul(ys[hi - 1], GF.fp_mul(zi2, zi)))
        if got != GC.multi_add(GC.FP_OPS, pts[lo:hi]):
            return False
    return True


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--seed", type=int, default=21)
    ap.add_argument("--repeats", type=int, default=4)
    ap.add_argument("--only", default="scan512,job512,agg2048")
    args = ap.parse_args(argv)

    import chip_smoke as CS

    CS._arm_budget()
    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_buckets: no TPU found ({dev.platform!r})", file=sys.stderr)
        return 2
    import numpy as np

    from lodestar_tpu.bls.verifier import TpuBlsVerifier, VerifyOptions
    from lodestar_tpu.utils import compile_cache
    from lodestar_tpu.utils.metrics import BlsPoolMetrics, Registry

    print(f"# compile cache: {compile_cache.configure()}", flush=True)
    table, waves, _pks = CS._build_world(np.random.default_rng(args.seed))
    by_root = collections.defaultdict(list)
    for w in waves[: CS.HONEST_WAVES]:
        for s, _ in w:
            by_root[s.signing_root].append(s)
    # 512 sets whose message groups (13, 11, ... per root) cross tiles
    job = [
        s
        for m, sets in zip(_uneven([13, 11], 512), by_root.values())
        for s in sets[:m]
    ]
    # all 2048 signatures in groups of 33 (the last one shorter)
    sigs = [s.signature for sets in by_root.values() for s in sets]
    ends = np.cumsum(_uneven([33], len(sigs)))
    groups = [sigs[lo:hi] for lo, hi in zip([0, *ends[:-1]], ends)]
    registry = Registry()
    verifier = TpuBlsVerifier(table, metrics=BlsPoolMetrics(registry))

    def check_agg(out):
        # groups 3 and 31 span lanes 128 and 1024
        return len(out) == len(groups) and all(
            out[i] == verifier._aggregate_wire_host(groups[i])
            for i in (0, 3, 31, len(groups) - 1)
        )

    calls = {
        "scan512": (_scan512, bool),
        "job512": (
            lambda: verifier.verify_signature_sets(
                job, VerifyOptions(batchable=True)
            ),
            bool,
        ),
        "agg2048": (lambda: verifier.aggregate_wire_signatures(groups), check_agg),
    }
    assert len(job) == 512 and len(sigs) == 2048
    results = {}
    for name in args.only.split(","):
        call, check = calls[name]
        walls = []
        for _ in range(1 if name == "scan512" else args.repeats + 1):
            t0 = time.perf_counter()
            out = call()
            walls.append(time.perf_counter() - t0)
            if not check(out):
                CS._fail(f"{name}: wrong result")
        results[name] = {"first_s": walls[0], "warm_s": walls[1:]}
        print(f"# {name}: first {walls[0]:.3f}s, warm {walls[1:]}", flush=True)

    status = verifier.supervisor.status()
    host_sets = registry.get("lodestar_bls_breaker_host_fallback_sets_total").value
    rlc_fallbacks = verifier.metrics.rlc_fallback.value
    if status["trips"] or host_sets or rlc_fallbacks:
        CS._fail(f"breaker {status}, host sets {host_sets}, rlc {rlc_fallbacks}")
    verifier.close()
    print(
        json.dumps(
            {
                "ok": True,
                "buckets": results,
                "slowest_call_s": status["slowest_call_s"],
                "device": {"platform": dev.platform, "kind": dev.device_kind},
            }
        ),
        flush=True,
    )
    return 0


if __name__ == "__main__":
    sys.exit(main())
