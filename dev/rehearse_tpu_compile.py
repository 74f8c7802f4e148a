"""Compile the verify pipeline for a described TPU v5e, without a chip.

The `on-chip-measurement` guide's third rehearsal: every program here is
traced, lowered (pallas launches forced through the real Mosaic path,
`launch.force_mosaic`) and compiled by the TPU compiler for device 0 of
a described `v5e:2x2` topology, so a refusal (VMEM, unaligned slice,
HBM) shows up before chip time is spent.  Nothing runs: no verdict, no
timing of the device.  The fast kernels also live in
tests/test_tpu_compile.py; the slow stages and the whole programs the
node dispatches live here.

    JAX_PLATFORMS=cpu python dev/rehearse_tpu_compile.py [program ...]

Programs: stages (every stage below, one lane tile) or one stage (mont8,
decompress, hash, gather_k4/k16/k64, sum_g2, prod, affine, sub_only,
sum_lanes, prod_lanes, seg_sum_g1/g2 and their @512/@1024/@2048 widths, g1_rpk,
g2_rsig_sub, miller, final), and the whole programs grouped512,
grouped128, batch128_k64, each128, each128_k64, agg128.  Default: all.
`direct program...` compiles each stage of the named whole programs
once, as the verifier's un-jitted dispatch does.
Prints trace / lower / compile seconds and the compiled memory analysis
of each; exits non-zero if any program is refused.
"""

from __future__ import annotations

import os
import sys
import time
import traceback

os.environ.setdefault("TPU_LOG_DIR", "disabled")
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

# mainnet registry size (BASELINE config 5): the table planes the node
# holds in HBM are part of every whole-program compile
TABLE_ROWS = 1_000_000


def _compile(name, fn, specs, dev):
    from jax.sharding import SingleDeviceSharding

    from lodestar_tpu.kernels import launch

    sh = SingleDeviceSharding(dev)
    specs = [jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sh) for s in specs]
    with launch.force_mosaic():
        t0 = time.perf_counter()
        traced = jax.jit(fn).trace(*specs)
        t1 = time.perf_counter()
        lowered = traced.lower()
        t2 = time.perf_counter()
        compiled = lowered.compile()
        t3 = time.perf_counter()
    text = compiled.as_text() or ""
    mem = compiled.memory_analysis()
    print(
        f"{name:14s} trace {t1 - t0:8.1f}s  lower {t2 - t1:8.1f}s  "
        f"compile {t3 - t2:8.1f}s  custom_calls "
        f"{text.count('tpu_custom_call')}",
        flush=True,
    )
    if mem is not None:
        print(
            f"{'':14s} args {mem.argument_size_in_bytes} B  out "
            f"{mem.output_size_in_bytes} B  temp {mem.temp_size_in_bytes} B",
            flush=True,
        )


def _split(name, fn, specs, dev, seen):
    """Direct-dispatch cost of one program: the verifier calls the
    pipeline un-jitted, so every pallas stage and every glue jit is its
    own compiled program, shared by every program that uses it.  Compile
    each distinct one once (`seen` dedupes across programs)."""
    import jax.extend as jex
    from jax.sharding import SingleDeviceSharding

    from lodestar_tpu.kernels import launch

    sh = SingleDeviceSharding(dev)
    specs = [jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sh) for s in specs]
    with launch.force_mosaic():
        t0 = time.perf_counter()
        closed = jax.jit(fn).trace(*specs).jaxpr
        print(f"{name:14s} trace {time.perf_counter() - t0:8.1f}s", flush=True)
        for eqn in closed.jaxpr.eqns:
            prim = eqn.primitive.name
            if prim not in ("pallas_call", "pjit", "jit"):
                continue
            avals = tuple(str(v.aval) for v in eqn.invars)
            if prim == "pallas_call":
                key = (prim, id(eqn.params["jaxpr"]), avals)
                label = eqn.params.get("name_and_src_info", "pallas")
                label = str(getattr(label, "name", label))
            else:
                key = (prim, str(eqn.params["name"]), avals)
                label = str(eqn.params["name"])
            if key in seen:
                continue
            seen.add(key)
            sub = jex.core.ClosedJaxpr(
                jex.core.Jaxpr(
                    [],
                    [v for v in eqn.invars if isinstance(v, jex.core.Var)],
                    eqn.outvars,
                    [eqn],
                ),
                [],
            )
            args = [
                jax.ShapeDtypeStruct(v.aval.shape, v.aval.dtype, sharding=sh)
                for v in sub.jaxpr.invars
            ]
            t1 = time.perf_counter()
            lowered = jax.jit(jex.core.jaxpr_as_fun(sub)).trace(*args).lower()
            t2 = time.perf_counter()
            lowered.compile()
            t3 = time.perf_counter()
            print(
                f"  {label[:40]:40s} {avals[0][:24]:24s} lower {t2 - t1:7.1f}s"
                f"  compile {t3 - t2:7.1f}s",
                flush=True,
            )


def _stages():
    from lodestar_tpu.kernels import ingest as IG
    from lodestar_tpu.kernels import verify as KV

    nl, bt, rw = KV.NL, KV.BT, KV.RAND_WORDS
    s = lambda r: jax.ShapeDtypeStruct((r, bt), jnp.int32)  # noqa: E731

    def tiled(kernel, in_rows, out_rows):
        return (
            lambda *a: KV._tiled(kernel, a, in_rows, out_rows, bt),
            [s(r) for r in in_rows],
        )

    def seg(wrapper, planes, n):
        """A segmented scan launch at n lanes (tiles of BT, carried)."""
        return (
            lambda *a: wrapper(*a[:planes], a[planes] != 0, a[planes + 1]),
            [jax.ShapeDtypeStruct((nl, n), jnp.int32)] * planes
            + [jax.ShapeDtypeStruct((n,), jnp.int32)] * 2,
        )

    def rows(*r):
        return [s(x) for x in r]

    def gather(k):
        table = jax.ShapeDtypeStruct((nl, TABLE_ROWS), jnp.int32)
        idx = jax.ShapeDtypeStruct((bt, k), jnp.int32)
        return KV._gather_pk, [table, table, idx, idx]

    return {
        "mont8": tiled(KV._k_mont8, [nl] * 8, [nl] * 8),
        "decompress": (
            lambda a, b, c: KV._decompress_sig(a, b, c, bt), rows(nl, nl, 2)
        ),
        "hash": (IG.hash_to_g2_device, rows(nl, nl, nl, nl, 2)),
        "gather_k4": gather(4),
        "gather_k16": gather(16),
        "gather_k64": gather(64),
        "sum_g2": (lambda *a: KV._sum_g2(*a, bt), rows(*[nl] * 6, 1)),
        "prod": (lambda v, *f: KV._prod(list(f), v, bt), rows(1, *[nl] * 12)),
        "affine": tiled(KV._k_affine_g2, [nl] * 6 + [1], [nl] * 4 + [1]),
        "sub_only": tiled(KV._k_sub_only, [nl] * 4 + [1], [1]),
        "sum_lanes": tiled(KV._k_sum_lanes, [nl] * 6 + [1], [nl] * 6 + [1]),
        "prod_lanes": tiled(KV._k_prod_lanes, [nl] * 12, [nl] * 12),
        # the scans tile at BT lanes: the buckets a node reaches (jobs to
        # 512, a multi-group aggregation to 2048) compile the same tile
        **{
            f"seg_sum_{g}{'' if n == bt else f'@{n}'}": seg(w, p, n)
            for g, w, p in (
                ("g1", KV._seg_sum_g1, 3), ("g2", KV._seg_sum_g2, 4)
            )
            for n in (bt, 4 * bt, 8 * bt, 16 * bt)
        },
        "g1_rpk": tiled(KV._k_g1_rpk, [nl] * 3 + [1, rw], [nl] * 3 + [1]),
        "g2_rsig_sub": tiled(
            KV._k_g2_rsig_sub, [nl] * 4 + [1, rw], [nl] * 6 + [1, 1]
        ),
        "miller": tiled(KV._k_miller, [nl] * 7, [nl] * 12),
        "final": tiled(KV._k_final, [1] + [nl] * 24, [1]),
    }


def _programs():
    """The whole programs the verifier dispatches, at the registry size:
    (fn, specs) in bls/verifier's argument order."""
    from lodestar_tpu.kernels import verify as KV

    nl, t = KV.NL, TABLE_ROWS

    def sds(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32)

    def wire(n, k):  # table planes, index/mask, message planes, signature
        return [sds(nl, t)] * 2 + [sds(n, k)] * 2 + [sds(nl, n)] * 6 + [sds(2, n)]

    def rand_valid(n):
        return [sds(KV.RAND_WORDS, n), sds(n)]

    def grouped(n):
        return (
            KV.verify_batch_device_wire_grouped,
            wire(n, 1) + [sds(n), sds(KV.BT), sds(KV.BT)] + rand_valid(n),
        )

    return {
        "grouped512": grouped(512),
        "grouped128": grouped(128),
        "batch128_k64": (
            KV.verify_batch_device_wire, wire(128, 64) + rand_valid(128)
        ),
        "each128": (KV.verify_each_device_wire, wire(128, 1) + [sds(128)]),
        "each128_k64": (KV.verify_each_device_wire, wire(128, 64) + [sds(128)]),
        "agg128": (
            KV.aggregate_g2_sum_device,
            [sds(nl, 128)] * 2 + [sds(2, 128), sds(128), sds(KV.BT), sds(KV.BT)],
        ),
    }


def main(argv):
    from jax.experimental import topologies

    jax.config.update("jax_enable_compilation_cache", False)
    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    dev = topo.devices[0]
    print(f"target: {dev.device_kind} (described, not attached)", flush=True)
    programs = _programs()
    if argv and argv[0] == "direct":
        seen = set()
        t0 = time.perf_counter()
        for name in argv[1:] or list(programs):
            _split(name, *programs[name], dev, seen)
        print(f"direct total {time.perf_counter() - t0:.1f}s", flush=True)
        return 0
    stages = _stages()
    wanted = argv or ["stages", *programs]
    failed = []
    for name in wanted:
        if name == "stages":
            jobs = stages.items()
        elif name in stages:
            jobs = [(name, stages[name])]
        else:
            jobs = [(name, programs[name])]
        for sub, (fn, specs) in jobs:
            try:
                _compile(sub, fn, specs, dev)
            except Exception:  # noqa: BLE001 — report every refusal
                failed.append(sub)
                print(f"{sub}: REFUSED", flush=True)
                traceback.print_exc()
    if failed:
        print(f"refused: {failed}", flush=True)
        return 1
    print("all programs compiled", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
