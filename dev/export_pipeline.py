"""Pre-trace whole programs and persist AOT export artifacts.

Exports, for the requested platform, the programs that are not stage
launches: the sharded wire verifier over an 8-device mesh and
__graft_entry__'s single-chip step (both loaded by __graft_entry__.py),
and every standalone registry entry (kernels/export_cache.py).  The
BLS verifier's stages need no pre-trace: each process that builds a
stage saves it to the export cache (kernels/launch.py), and later
processes load it.  TPU-platform artifacts are traced on this CPU host
with the real Mosaic lowering forced.

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

from lodestar_tpu.kernels import export_cache as EC

PLATFORM = sys.argv[1] if len(sys.argv) > 1 else "tpu"


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
    # standalone registry entries (e.g. the slasher's whole-window span
    # update, the HTR merkleization kernels)
    if os.environ.get("EXPORT_REGISTERED", "1") != "0":
        try:
            for name, key in EC.export_registered(PLATFORM).items():
                print(f"registered entry {name} ready ({key})")
        except Exception as e:  # noqa: BLE001
            print(f"registered-entry export failed: {type(e).__name__}: {e}")
    print(f"total {time.time() - t0:.1f}s")


if __name__ == "__main__":
    main()
