"""Pre-trace the verify pipeline and persist AOT export artifacts.

Runs the bench's exact job assembly through the verifier, CAPTURES the
device dispatches (name, fn, arg specs) without executing them, then
traces each for the requested platform and writes jax.export artifacts
into the export cache (kernels/export_cache.py).

The point: tracing costs ~10 minutes per process on this 1-core host
(dev/NOTES.md).  This script pays it once, offline; bench.py and any
node process then deserializes in milliseconds.  TPU-platform artifacts
are traced on this CPU host with the real Mosaic lowering forced.

Usage:
  python dev/export_pipeline.py [tpu|cpu]      (default: tpu)
"""

import os
import sys
import time

sys.path.insert(0, ".")

# the sharded export needs 8 virtual devices; must precede backend init
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import jax
import jax.numpy as jnp

jax.config.update("jax_platforms", "cpu")  # tracing host; artifacts target TPU

import bench_configs  # noqa: F401  (shared world shapes if present)
from lodestar_tpu.kernels import export_cache as EC

PLATFORM = sys.argv[1] if len(sys.argv) > 1 else "tpu"


def capture_bench_dispatches():
    """Build the bench world and record every device dispatch the
    verifier would make for its job shapes."""
    from lodestar_tpu.bls.pubkey_table import PubkeyTable
    from lodestar_tpu.bls.signature_set import WireSignatureSet
    from lodestar_tpu.bls.verifier import TpuBlsVerifier
    from lodestar_tpu.crypto import bls as GTB
    from lodestar_tpu.crypto import curves as GCC

    BATCH = int(os.environ.get("BENCH_BATCH", "512"))
    DISTINCT = 32
    ROOTS = 8

    sks = [GTB.keygen(b"bench-%d" % i) for i in range(DISTINCT)]
    pks = [GTB.sk_to_pk(sk) for sk in sks]
    table = PubkeyTable(capacity=max(BATCH, DISTINCT))
    table.register_points_unchecked(pks, tile_to=max(BATCH, DISTINCT))
    table.device_planes()

    roots = [b"bench root 0 %d" % c for c in range(ROOTS)]
    sig_cache = {}
    sets = []
    for j in range(BATCH):
        key = j % DISTINCT
        root = roots[j % ROOTS]
        if (key, root) not in sig_cache:
            sig_cache[(key, root)] = GCC.g2_compress(GTB.sign(sks[key], root))
        sets.append(WireSignatureSet.single(j, root, sig_cache[(key, root)]))

    verifier = TpuBlsVerifier(table, max_job_sets=BATCH)
    # host-side hashing for the capture: the device hash kernel would
    # drag XLA:CPU into a pathological compile (measured: >25 min for
    # jit_hash_to_g2_device on this host) and the capture needs VALUES,
    # not device performance
    verifier.messages.use_device = False
    captured = []

    def fake_call(name, fn, args):
        specs = tuple(
            jax.ShapeDtypeStruct(jnp.shape(a), jnp.asarray(a).dtype)
            for a in args
        )
        captured.append((name, fn, specs))
        # shape-compatible dummies so begin_job completes
        n = args[-1].shape[0]
        if name.startswith("batch"):
            return jnp.zeros((), bool), jnp.zeros((n,), bool)
        return jnp.ones((n,), bool)

    verifier._device_call = fake_call
    verifier.begin_job(sets, batchable=True)

    # ALSO capture the retry path (each_wire) for the same shapes: a
    # batch failure on chip must not pay a fresh trace
    job = verifier.begin_job(sets[: BATCH // 2] + sets[BATCH // 2 :], batchable=False)
    del job
    return captured


def export_sharded_program(n_devices: int = 8):
    """Trace + export the PRODUCTION sharded wire verifier over an
    n-device mesh for the TPU platform.  The dryrun validates this
    artifact loads (kernels path certified to trace + Mosaic-lower +
    SPMD-partition) without paying the XLA:CPU compile pathology."""
    import numpy as np
    from jax.sharding import Mesh

    from lodestar_tpu.kernels import verify as KV

    devices = np.array(jax.devices()[:n_devices])
    if devices.size < n_devices:
        raise SystemExit(f"need {n_devices} virtual devices")
    mesh = Mesh(devices, ("sets",))
    n = KV.BT * n_devices
    NL = KV.NL
    i32 = jnp.int32

    def sds(shape):
        return jax.ShapeDtypeStruct(shape, i32)

    # the 13 positional args of make_sharded_wire_verifier (global
    # shapes; see KV.wire_shard_specs)
    specs = [
        sds((NL, n)), sds((NL, n)),          # table planes (capacity=n)
        jax.ShapeDtypeStruct((n, 1), i32),    # idx
        jax.ShapeDtypeStruct((n, 1), i32),    # kmask
        sds((NL, n)), sds((NL, n)), sds((NL, n)), sds((NL, n)),  # msg
        sds((NL, n)), sds((NL, n)),          # sig_x0/x1
        sds((2, n)),                          # sig_flags
        sds((KV.RAND_WORDS, n)),              # rwords
        jax.ShapeDtypeStruct((n,), i32),      # valid
    ]
    sharded = KV.make_sharded_wire_verifier(mesh)
    t1 = time.time()
    call = EC.load_or_export(
        f"sharded_wire_{n_devices}dev", sharded, specs, platform="tpu"
    )
    print(
        f"sharded program ({n_devices} devices) exported for tpu in "
        f"{time.time() - t1:.1f}s"
    )
    return call


def export_entry():
    """Pre-trace __graft_entry__.entry()'s exact fn+shapes so the
    driver's single-chip compile check re-traces only a thin wrapper.
    Exports the fn _wire_example actually RETURNS under a name carrying
    its identity — a future pipeline swap cannot alias the artifact."""
    import __graft_entry__ as g

    fn, args = g._wire_example(128)
    name = g.entry_artifact_name(fn)
    specs = [jax.ShapeDtypeStruct(a.shape, a.dtype) for a in args]
    t1 = time.time()
    EC.load_or_export(name, fn, specs, "tpu")
    print(f"{name} ready in {time.time() - t1:.1f}s")


def export_replay_shapes(n_validators: int, batch: int = 512):
    """Pre-trace the grouped batch + retry paths at replay.py's table
    capacity (the pubkey planes are [NL, V], so configs 4-5 key
    different artifacts than the bench's 512-capacity table)."""
    from lodestar_tpu.kernels import verify as KV

    NL = KV.NL
    i32 = jnp.int32

    def sds(shape):
        return jax.ShapeDtypeStruct(shape, i32)

    common = [
        sds((NL, n_validators)), sds((NL, n_validators)),  # table planes
        jax.ShapeDtypeStruct((batch, 1), i32),             # idx
        jax.ShapeDtypeStruct((batch, 1), i32),             # kmask
        sds((NL, batch)), sds((NL, batch)),                # msg planes
        sds((NL, batch)), sds((NL, batch)),
        sds((NL, batch)), sds((NL, batch)),                # sig_x0/x1
        sds((2, batch)),                                    # sig_flags
    ]
    grouping = [
        jax.ShapeDtypeStruct((batch,), i32),               # group
        jax.ShapeDtypeStruct((KV.BT,), i32),               # head_lanes
        jax.ShapeDtypeStruct((KV.BT,), i32),               # glive
    ]
    rwords = sds((KV.RAND_WORDS, batch))
    valid = jax.ShapeDtypeStruct((batch,), i32)
    t1 = time.time()
    EC.load_or_export(
        "batch_wire_grouped",
        KV.verify_batch_device_wire_grouped,
        common + grouping + [rwords, valid],
        "tpu",
    )
    EC.load_or_export(
        "each_wire", KV.verify_each_device_wire, common + [valid], "tpu"
    )
    print(
        f"replay shapes ({n_validators} validators) ready in "
        f"{time.time() - t1:.1f}s"
    )


def main():
    t0 = time.time()
    if os.environ.get("EXPORT_SHARDED", "1") != "0" and PLATFORM == "tpu":
        try:
            export_sharded_program(8)
        except Exception as e:  # noqa: BLE001
            print(f"sharded export failed: {type(e).__name__}: {e}")
    if PLATFORM == "tpu":  # independent of the sharded gate
        try:
            export_entry()
        except Exception as e:  # noqa: BLE001
            print(f"entry export failed: {type(e).__name__}: {e}")
        # replay configs 4-5 table capacities (opt-out: EXPORT_REPLAY=0)
        if os.environ.get("EXPORT_REPLAY", "1") != "0":
            for v in (500_000, 1_000_000):
                try:
                    export_replay_shapes(v)
                except Exception as e:  # noqa: BLE001
                    print(
                        f"replay export ({v}) failed: "
                        f"{type(e).__name__}: {e}"
                    )
    # standalone registry entries (kernels outside the verify pipeline's
    # dispatch capture — e.g. the slasher's whole-window span update)
    if os.environ.get("EXPORT_REGISTERED", "1") != "0":
        try:
            for name, key in EC.export_registered(PLATFORM).items():
                print(f"registered entry {name} ready ({key})")
        except Exception as e:  # noqa: BLE001
            print(f"registered-entry export failed: {type(e).__name__}: {e}")
    captured = capture_bench_dispatches()
    seen = set()
    for name, fn, specs in captured:
        key = EC.artifact_key(name, specs, PLATFORM)
        if key in seen:
            continue
        seen.add(key)
        if EC.load(name, specs, PLATFORM) is not None:
            print(f"cached: {name} ({key})")
            continue
        t1 = time.time()
        EC.export_and_save(name, fn, specs, PLATFORM)
        print(
            f"exported {name} for {PLATFORM} in {time.time() - t1:.1f}s "
            f"({key})"
        )
    print(f"total {time.time() - t0:.1f}s, {len(seen)} artifacts")


if __name__ == "__main__":
    main()
