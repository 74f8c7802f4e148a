"""Run each verify-pipeline kernel on the real chip, one at a time.

Dev tool: isolates Mosaic lowering failures to a specific kernel and
reports per-stage wall time for one 128-lane tile (the numbers behind
dev/NOTES.md).  Usage:  python dev/probe_tpu_kernels.py [stage ...]
Stages: mont gather rpk rsig sum affine miller prod final each
"""

from __future__ import annotations

import os
import sys
import time

os.environ.setdefault("XLA_FLAGS", "")

import numpy as np
import jax
import jax.numpy as jnp


sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from lodestar_tpu.utils import compile_cache  # noqa: E402

compile_cache.configure()

from lodestar_tpu.crypto import bls as GTB
from lodestar_tpu.crypto.hash_to_curve import hash_to_g2
from lodestar_tpu.kernels import layout as LY
from lodestar_tpu.kernels import verify as KV
from lodestar_tpu.ops import bls_kernels as BK

N = 128


def build():
    v = 8
    sks = [GTB.keygen(b"probe-%d" % i) for i in range(v)]
    pks = [GTB.sk_to_pk(sk) for sk in sks]
    msgs = [b"probe root %d" % (i % 2) for i in range(v)]
    hms = [hash_to_g2(m) for m in msgs]
    sigs = [GTB.sign(sk, m) for sk, m in zip(sks, msgs)]
    sel = [i % v for i in range(N)]
    enc = lambda vals: jnp.asarray(LY.encode_plain_batch([vals[i] for i in sel]))
    args = dict(
        table_x=jnp.asarray(LY.encode_batch([p[0] for p in pks])),
        table_y=jnp.asarray(LY.encode_batch([p[1] for p in pks])),
        idx=jnp.asarray(np.asarray(sel, np.int32)[:, None]),
        kmask=jnp.ones((N, 1), jnp.int32),
        msg_x0=enc([m[0][0] for m in hms]), msg_x1=enc([m[0][1] for m in hms]),
        msg_y0=enc([m[1][0] for m in hms]), msg_y1=enc([m[1][1] for m in hms]),
        sig_x0=enc([s[0][0] for s in sigs]), sig_x1=enc([s[0][1] for s in sigs]),
        sig_y0=enc([s[1][0] for s in sigs]), sig_y1=enc([s[1][1] for s in sigs]),
        sig_inf=jnp.zeros((N,), jnp.int32),
        bits=jnp.asarray(BK.make_rand_words(N, np.random.default_rng(3))),
        valid=jnp.ones((N,), jnp.int32),
    )
    return args


def timed(name, fn, *a):
    t0 = time.perf_counter()
    out = jax.block_until_ready(fn(*a))
    t1 = time.perf_counter()
    out = jax.block_until_ready(fn(*a))
    t2 = time.perf_counter()
    print(f"{name:8s} compile+run {t1-t0:8.2f}s   warm {t2-t1:8.4f}s", flush=True)
    return out


def main():
    stages = sys.argv[1:] or [
        "mont", "gather", "rpk", "rsig", "sum", "affine", "miller",
        "prod", "final", "each",
    ]
    print("backend:", jax.default_backend(), jax.devices(), flush=True)
    a = build()
    zero_row = jnp.zeros((1, N), jnp.int32)

    planes = (a["msg_x0"], a["msg_x1"], a["msg_y0"], a["msg_y1"],
              a["sig_x0"], a["sig_x1"], a["sig_y0"], a["sig_y1"])
    if "mont" in stages:
        mont = timed("mont", jax.jit(lambda *p: KV._to_mont8(p, N)), *planes)
    else:
        mont = KV._to_mont8(planes, N)
    mx0, mx1, my0, my1, sx0, sx1, sy0, sy1 = mont

    if "gather" in stages:
        timed(
            "gather",
            jax.jit(lambda tx, ty, i, m: KV._gather_pk(tx, ty, i, m)),
            a["table_x"], a["table_y"], a["idx"], a["kmask"],
        )
    (pk, pk_inf) = KV._gather_pk(a["table_x"], a["table_y"], a["idx"], a["kmask"])
    px, py, pz = pk

    if "rpk" in stages:
        rpk = timed(
            "rpk",
            jax.jit(lambda px, py, pz, b: KV._tiled(
                KV._k_g1_rpk, (px, py, pz, zero_row, b),
                [KV.NL] * 3 + [1, KV.RAND_WORDS], [KV.NL] * 3 + [1], N)),
            px, py, pz, a["bits"],
        )
        rx, ry, rz = rpk[0], rpk[1], rpk[2]
    else:
        rx, ry, rz = px, py, pz

    if "rsig" in stages:
        rsig = timed(
            "rsig",
            jax.jit(lambda x0, x1, y0, y1, b: KV._tiled(
                KV._k_g2_rsig_sub, (x0, x1, y0, y1, zero_row, b),
                [KV.NL] * 4 + [1, KV.RAND_WORDS], [KV.NL] * 6 + [1, 1], N)),
            sx0, sx1, sy0, sy1, a["bits"],
        )
    else:
        rsig = None

    if "sum" in stages and rsig is not None:
        jx = timed(
            "sum",
            jax.jit(lambda *t: KV._sum_g2(*t, N)),
            rsig[0], rsig[1], rsig[2], rsig[3], rsig[4], rsig[5], rsig[6],
        )
        if "affine" in stages:
            timed(
                "affine",
                jax.jit(lambda *t: KV._tiled(
                    KV._k_affine_g2, t, [KV.NL] * 6 + [1],
                    [KV.NL] * 4 + [1], KV.BT)),
                *jx,
            )

    if "miller" in stages:
        fN = timed(
            "miller",
            jax.jit(lambda *t: KV._tiled(
                KV._k_miller, t, [KV.NL] * 7, [KV.NL] * 12, N)),
            rx, ry, rz, mx0, mx1, my0, my1,
        )
        if "prod" in stages:
            live = jnp.ones((1, N), jnp.int32)
            fp_ = timed(
                "prod",
                jax.jit(lambda l, *f: KV._prod(list(f), l, N)),
                live, *fN,
            )
            if "final" in stages:
                timed(
                    "final",
                    jax.jit(lambda ai, *f: KV._tiled(
                        KV._k_final, (ai,) + f,
                        [1] + [KV.NL] * 24, [1], KV.BT)),
                    jnp.zeros((1, KV.BT), jnp.int32), *(list(fp_) + list(fN)),
                )

    if "each" in stages:
        timed(
            "each",
            KV.verify_each_device,
            a["table_x"], a["table_y"], a["idx"], a["kmask"],
            a["msg_x0"], a["msg_x1"], a["msg_y0"], a["msg_y1"],
            a["sig_x0"], a["sig_x1"], a["sig_y0"], a["sig_y1"],
            a["sig_inf"], a["valid"],
        )

    print("probe done", flush=True)


if __name__ == "__main__":
    main()
