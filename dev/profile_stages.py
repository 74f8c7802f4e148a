"""Per-stage profiling of verify_batch on the real chip (dev tool).

Times each pipeline stage of `verify_batch` separately so optimization
effort goes where the time is.  Run: python profile_stages.py [BATCH]
"""

from __future__ import annotations

import os
import sys
import time

os.environ.setdefault("XLA_FLAGS", "")

import numpy as np

import jax
import jax.numpy as jnp

from lodestar_tpu.utils import compile_cache

compile_cache.configure()

from lodestar_tpu.crypto import bls as GTB
from lodestar_tpu.crypto.hash_to_curve import hash_to_g2
from lodestar_tpu.ops import bls_kernels as BK
from lodestar_tpu.ops import curve as K
from lodestar_tpu.ops import fp, fp2, fp12
from lodestar_tpu.ops import pairing as KP

BATCH = int(sys.argv[1]) if len(sys.argv) > 1 else 512
DISTINCT = 8
REPS = 3


def _force(out):
    """Copy every output leaf to the host: the timing ends when the
    device has produced all of them."""
    for leaf in jax.tree_util.tree_leaves(out):
        np.asarray(leaf)


def timeit(name, fn, *args):
    out = fn(*args)  # compile
    _force(out)
    t0 = time.perf_counter()
    for _ in range(REPS):
        out = fn(*args)
        _force(out)
    dt = (time.perf_counter() - t0) / REPS
    print(f"{name:40s} {dt*1e3:10.2f} ms")
    return out, dt


def main():
    print(f"BATCH={BATCH} on {jax.devices()[0]}")
    pks, hms, sigs = [], [], []
    for i in range(DISTINCT):
        sk = GTB.keygen(b"prof-%d" % i)
        msg = b"prof root %d" % i
        pks.append(GTB.sk_to_pk(sk))
        hms.append(hash_to_g2(msg))
        sigs.append(GTB.sign(sk, msg))
    reps = BATCH // DISTINCT

    def enc1(pts):
        return (
            jnp.asarray(np.stack([fp.const(p[0]) for p in pts] * reps)),
            jnp.asarray(np.stack([fp.const(p[1]) for p in pts] * reps)),
        )

    def enc2(pts):
        return (
            jnp.asarray(fp2.stack_consts([p[0] for p in pts] * reps)),
            jnp.asarray(fp2.stack_consts([p[1] for p in pts] * reps)),
        )

    pk_aff = enc1(pks)
    msg_aff = enc2(hms)
    sig_aff = enc2(sigs)
    rng = np.random.default_rng(1)
    rand = jnp.asarray(BK.make_rand_bits(BATCH, rng))
    valid = jnp.ones((BATCH,), bool)

    one_fp2 = fp2.broadcast_to(fp2.ONE, (BATCH,))
    pk_jac = (pk_aff[0], pk_aff[1], fp.broadcast_to_limbs((BATCH,)))
    sig_jac = (sig_aff[0], sig_aff[1], one_fp2)

    # individual field ops at batch for scale
    a = pk_aff[0]
    timeit("fp.mont_mul [B]", jax.jit(fp.mont_mul), a, a)
    timeit("fp2.mul_stacked [B]", jax.jit(fp2.mul_stacked), msg_aff[0], msg_aff[1])
    f0 = jax.jit(lambda p, q: KP.miller_loop(p, q))(pk_aff, msg_aff)
    timeit("fp12.sqr12 [B]", jax.jit(fp12.sqr12), f0)
    timeit("fp12.mul12 [B]", jax.jit(fp12.mul12), f0, f0)

    timeit("g2_subgroup_check_fast", jax.jit(BK.g2_subgroup_check_fast), sig_jac)
    rpk, _ = timeit(
        "scalar_mul_bits G1",
        jax.jit(lambda p, r: K.scalar_mul_bits(K.FP_OPS, p, r)),
        pk_jac,
        rand,
    )
    rsig, _ = timeit(
        "scalar_mul_bits G2",
        jax.jit(lambda p, r: K.scalar_mul_bits(K.FP2_OPS, p, r)),
        sig_jac,
        rand,
    )
    timeit(
        "sum_points G2 + to_affine",
        jax.jit(
            lambda p, v: K.to_affine(
                K.FP2_OPS,
                jax.tree_util.tree_map(
                    lambda a: a[None], K.sum_points(K.FP2_OPS, p, valid=v)
                ),
            )
        ),
        rsig,
        valid,
    )
    timeit(
        "to_affine G1 [B]",
        jax.jit(lambda p: K.to_affine(K.FP_OPS, p)),
        rpk,
    )
    fs, _ = timeit("miller_loop [B]", jax.jit(KP.miller_loop), pk_aff, msg_aff)
    f, _ = timeit("product12", jax.jit(KP.product12), fs)
    timeit("final_exponentiation [1]", jax.jit(KP.final_exponentiation), f[None])
    # final exp pieces
    m = f[None]
    timeit(
        "  easy part (inv12+frob)",
        jax.jit(
            lambda m: fp12.mul12(
                fp12.frobenius12(
                    fp12.mul12(fp12.conj12(m), fp12.inv12(m)), 2
                ),
                fp12.mul12(fp12.conj12(m), fp12.inv12(m)),
            )
        ),
        m,
    )
    timeit(
        "  one pow_static z [1]",
        jax.jit(lambda m: KP._pow_static(m, KP._Z_ABS)),
        m,
    )
    timeit("verify_batch (full)", jax.jit(BK.verify_batch), pk_aff, msg_aff, sig_aff, rand, valid)


if __name__ == "__main__":
    main()
