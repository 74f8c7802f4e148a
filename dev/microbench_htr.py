"""Incremental vs full BeaconState merkleization microbenchmark.

Models the per-slot replay cadence: a synthetic large registry, k
validators touched per slot (attestation participation bits, balance
deltas, the occasional slash), plus the per-slot bookkeeping writes
(block/state root vectors, randao mix, header).  Measures

  - state_roots_per_s     : warm incremental engine over that cadence
  - full_roots_per_s      : today's cold full recompute (to_value +
                            recursive merkleization)
  - speedup               : the ratio (the acceptance bar is >=10x at
                            >=100k validators)

`--backend jax` routes merkleization through the device hash forest
(kernels/sha256.py via ssz/device_backend.py) and reports the metric
as `state_roots_per_s_device` with an "htr" dispatch-accounting
snapshot — per-slot device dispatches, bytes — so the O(k log n)
per-slot claim is checkable from the record alone.  The default host
backend stays pure CPU (JAX_PLATFORMS=cpu; nothing touches a device):
bench.py runs it as a subprocess for `state_roots_per_s` (--json emits
the one-line record bench.py forwards), and calls `run(backend="jax")`
in its own process, which holds the chip, for
`state_roots_per_s_device`.

`--derive-cutoff` instead measures the native-batch vs hashlib
crossover for ssz/hasher.py::hash_pairs and prints the recommended
LODESTAR_TPU_SHA_NATIVE_CUTOFF (the shipped default of 4 came from
this mode on the 1-core driver host).

Usage:
  python dev/microbench_htr.py [--validators N] [--slots K]
                               [--touched M] [--full-reps R] [--json]
                               [--backend {host,jax}] [--derive-cutoff]
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np


def build_state(n_validators: int, seed: int = 0):
    from lodestar_tpu import params
    from lodestar_tpu.config import MAINNET_CHAIN_CONFIG, create_chain_config
    from lodestar_tpu.params import ForkName
    from lodestar_tpu.state_transition.state import BeaconState

    P = params.ACTIVE_PRESET
    cfg = create_chain_config(
        MAINNET_CHAIN_CONFIG, fork_epochs={ForkName.altair: 0}
    )
    rng = np.random.default_rng(seed)
    st = BeaconState(config=cfg)
    raw = rng.integers(0, 256, (n_validators, 48), dtype=np.uint8).tobytes()
    st.pubkeys = [raw[i * 48 : (i + 1) * 48] for i in range(n_validators)]
    craw = rng.integers(0, 256, (n_validators, 32), dtype=np.uint8).tobytes()
    st.withdrawal_credentials = [
        craw[i * 32 : (i + 1) * 32] for i in range(n_validators)
    ]
    st.effective_balance = np.full(
        n_validators, P.MAX_EFFECTIVE_BALANCE, np.uint64
    )
    st.slashed = np.zeros(n_validators, bool)
    st.activation_eligibility_epoch = np.zeros(n_validators, np.uint64)
    st.activation_epoch = np.zeros(n_validators, np.uint64)
    st.exit_epoch = np.full(n_validators, params.FAR_FUTURE_EPOCH, np.uint64)
    st.withdrawable_epoch = np.full(
        n_validators, params.FAR_FUTURE_EPOCH, np.uint64
    )
    st.balances = rng.integers(
        31_000_000_000, 33_000_000_000, n_validators
    ).astype(np.uint64)
    st.previous_epoch_participation = rng.integers(
        0, 8, n_validators
    ).astype(np.uint8)
    st.current_epoch_participation = rng.integers(0, 8, n_validators).astype(
        np.uint8
    )
    st.inactivity_scores = np.zeros(n_validators, np.uint64)
    return st


def mutate_slot(st, rng, touched: int) -> None:
    """One slot's worth of state churn at the replay cadence."""
    from lodestar_tpu import params

    P = params.ACTIVE_PRESET
    n = st.num_validators
    idx = rng.integers(0, n, touched)
    st.current_epoch_participation[idx] |= np.uint8(
        1 << int(rng.integers(0, 3))
    )
    st.balances[idx[: max(1, touched // 4)]] += np.uint64(1_000)
    st.slot = int(st.slot) + 1
    st.block_roots[st.slot % P.SLOTS_PER_HISTORICAL_ROOT] = bytes(
        rng.integers(0, 256, 32, dtype=np.uint8)
    )
    st.state_roots[st.slot % P.SLOTS_PER_HISTORICAL_ROOT] = bytes(
        rng.integers(0, 256, 32, dtype=np.uint8)
    )
    epoch = st.slot // P.SLOTS_PER_EPOCH
    st.randao_mixes[epoch % P.EPOCHS_PER_HISTORICAL_VECTOR] = bytes(
        rng.integers(0, 256, 32, dtype=np.uint8)
    )
    st.latest_block_header["state_root"] = bytes(
        rng.integers(0, 256, 32, dtype=np.uint8)
    )


def _htr_snapshot() -> dict:
    from lodestar_tpu.ssz.device_backend import device_memory_snapshot

    return device_memory_snapshot()


def run(
    n_validators: int,
    slots: int,
    touched: int,
    full_reps: int,
    backend: str = "host",
):
    rng = np.random.default_rng(42)
    st = build_state(n_validators)

    t0 = time.perf_counter()
    root = st.hash_tree_root()  # cold: builds the engine
    t_cold = time.perf_counter() - t0

    # sanity: incremental == full on the live state (cheap insurance —
    # a benchmark of a wrong root is worse than no benchmark); the full
    # recompute goes through the same hash_pairs_plane seam, so under
    # --backend jax this also proves device == host bit-identity
    full = st._container().hash_tree_root(st.to_value())
    assert root == full, "incremental root != full recompute"

    d0 = _htr_snapshot().get("dispatches", 0) if backend == "jax" else 0
    t0 = time.perf_counter()
    for _ in range(slots):
        mutate_slot(st, rng, touched)
        st.hash_tree_root()
    t_incremental = time.perf_counter() - t0
    incremental_rps = slots / t_incremental

    t0 = time.perf_counter()
    for _ in range(full_reps):
        st._container().hash_tree_root(st.to_value())
    t_full = time.perf_counter() - t0
    full_rps = full_reps / t_full

    out = {
        "metric": (
            "state_roots_per_s_device"
            if backend == "jax"
            else "state_roots_per_s"
        ),
        "value": round(incremental_rps, 2),
        "unit": "roots/s",
        "backend": backend,
        "validators": n_validators,
        "touched_per_slot": touched,
        "slots": slots,
        "cold_build_s": round(t_cold, 3),
        "full_roots_per_s": round(full_rps, 4),
        "speedup_vs_full": round(incremental_rps / full_rps, 2),
    }
    if backend == "jax":
        snap = _htr_snapshot()
        snap["dispatches_per_slot"] = round(
            (snap.get("dispatches", 0) - d0) / max(1, slots), 2
        )
        out["htr"] = snap
    return out


# -- native-cutoff derivation (ssz/hasher.py) --------------------------------


def derive_cutoff(reps: int = 2000) -> dict:
    """Measure the pair count where the native batch hasher overtakes
    the hashlib loop; the winner-by-n table justifies hasher._CUTOFF."""
    import hashlib

    from lodestar_tpu.ssz import hasher

    if not hasher.native_available():
        return {
            "metric": "sha_native_cutoff",
            "value": None,
            "note": "native batch hasher not built (make -C lodestar_tpu/native)",
        }
    import ctypes

    rng = np.random.default_rng(7)
    table = {}
    cutoff = None
    for n in (1, 2, 3, 4, 6, 8, 12, 16, 32):
        data = rng.integers(0, 256, 64 * n, dtype=np.uint8).tobytes()

        def native():
            out = ctypes.create_string_buffer(32 * n)
            hasher._native.sha256_hash_pairs(data, out, n)
            return out.raw

        def pure():
            sha = hashlib.sha256
            mv = memoryview(data)
            return b"".join(
                sha(mv[i * 64 : i * 64 + 64]).digest() for i in range(n)
            )

        assert native() == pure(), "native batch hasher mismatch"
        times = []
        for f in (native, pure):
            t0 = time.perf_counter()
            for _ in range(reps):
                f()
            times.append((time.perf_counter() - t0) / reps)
        table[n] = {
            "native_us": round(times[0] * 1e6, 3),
            "hashlib_us": round(times[1] * 1e6, 3),
        }
        if cutoff is None and times[0] <= times[1]:
            cutoff = n
    return {
        "metric": "sha_native_cutoff",
        "value": cutoff,
        "current_default": hasher._CUTOFF,
        "per_n_us": table,
        "note": (
            "export LODESTAR_TPU_SHA_NATIVE_CUTOFF to override "
            "ssz/hasher.py's default"
        ),
    }


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--validators", type=int, default=100_000)
    ap.add_argument("--slots", type=int, default=32)
    ap.add_argument("--touched", type=int, default=256)
    ap.add_argument("--full-reps", type=int, default=3)
    ap.add_argument(
        "--backend",
        choices=("host", "jax"),
        default="host",
        help="merkleization backend: host hash_pairs or the device "
        "hash forest (ssz/device_backend.py)",
    )
    ap.add_argument(
        "--derive-cutoff",
        action="store_true",
        help="measure the hasher's native-vs-hashlib crossover instead",
    )
    ap.add_argument(
        "--json", action="store_true", help="one JSON line only (bench probe)"
    )
    args = ap.parse_args()
    if args.backend == "host":
        # nothing here touches a device; must precede the first jax import
        os.environ.setdefault("JAX_PLATFORMS", "cpu")
    if args.derive_cutoff:
        out = derive_cutoff()
        print(json.dumps(out) if args.json else json.dumps(out, indent=2))
        return 0
    if args.backend == "jax":
        # must precede any lodestar import that resolves the backend
        os.environ["LODESTAR_TPU_HTR_BACKEND"] = "jax"
    out = run(
        args.validators, args.slots, args.touched, args.full_reps,
        backend=args.backend,
    )
    if args.json:
        print(json.dumps(out), flush=True)
    else:
        print(json.dumps(out, indent=2))
        print(
            f"\nincremental {out['value']:.1f} roots/s vs full "
            f"{out['full_roots_per_s']:.3f} roots/s -> "
            f"{out['speedup_vs_full']:.0f}x",
            file=sys.stderr,
        )
    return 0


if __name__ == "__main__":
    sys.exit(main())
