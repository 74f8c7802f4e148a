"""Bisect the rpk-stage cost anomaly (dev tool, needs the real chip).

Round-4 found the G1 randomizer scalar-mul stage at 78 ms / 128-lane
tile — ~30-100x over the op-count estimate (~1.5k mont_muls x ~1 us).
This script times each candidate cost in isolation:

  mul-chain   K chained mont_muls              -> per-mult cost
  prod-chain  K chained RAW column products    -> product vs REDC split
  i32-mul     K chained elementwise int32 muls -> int32 multiply rate
  f32-mul     same in f32                      -> the native-rate baseline
  loop        fori_loop with a trivial body    -> per-iteration overhead
  dblchain    K chained jac_dbl (G1)           -> curve-op composition cost

Usage: python dev/microbench_int32.py [K]
"""

from __future__ import annotations

import os
import sys
import time

os.environ.setdefault("XLA_FLAGS", "")

import numpy as np
import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl


sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from lodestar_tpu.utils import compile_cache  # noqa: E402

compile_cache.configure()

from lodestar_tpu.crypto import fields as GT
from lodestar_tpu.kernels import core as C
from lodestar_tpu.kernels import core_f32 as F32
from lodestar_tpu.kernels import curve as CV
from lodestar_tpu.kernels import layout as LY

K = int(sys.argv[1]) if len(sys.argv) > 1 else 256
NL = LY.NL
B = 128


def timed(name, fn, *a, per=1):
    t0 = time.perf_counter()
    out = jax.block_until_ready(fn(*a))
    t1 = time.perf_counter()
    out = jax.block_until_ready(fn(*a))
    t2 = time.perf_counter()
    print(
        f"{name:10s} compile {t1-t0:7.2f}s  warm {t2-t1:9.6f}s  "
        f"per-op {(t2-t1)/per*1e6:9.3f} us",
        flush=True,
    )
    return out


def k_mul_chain(a, b, o):
    def body(_i, acc):
        return C.mont_mul(acc, b[...])

    o[...] = lax.fori_loop(0, K, body, a[...])


def k_f32core_chain(a, b, t_np, t_p, o):
    """The f32/MXU engine's mont_mul chained K times (core_f32).

    The Toeplitz REDC matrices ride as kernel inputs — pallas rejects
    captured array constants."""
    mode = "mxu" if jax.default_backend() == "tpu" else "f32"
    mats = (t_np[...], t_p[...])

    def body(_i, acc):
        return F32.mont_mul(acc, b[...], matmul_mode=mode, toeplitz=mats)

    o[...] = lax.fori_loop(0, K, body, a[...])


def k_prod_chain(a, b, o):
    def body(_i, acc):
        # raw column product folded back to NL rows (no REDC)
        return C.fold3(C.mul_cols(acc, b[...]))[..., :NL, :]

    o[...] = lax.fori_loop(0, K, body, a[...])


def k_i32_chain(a, b, o):
    def body(_i, acc):
        return acc * b[...] + jnp.int32(1)

    o[...] = lax.fori_loop(0, K * 33, body, a[...])


def k_loop_only(a, b, o):
    def body(_i, acc):
        return acc + jnp.int32(1)

    o[...] = lax.fori_loop(0, K, body, a[...])


def k_dbl_chain(x, y, z, ox, oy, oz):
    def body(_i, pt):
        return CV.jac_dbl(CV.FP_OPS, pt)

    X, Y, Z = lax.fori_loop(0, K, body, (x[...], y[...], z[...]))
    ox[...], oy[...], oz[...] = X, Y, Z


def run(kernel, n_in, n_out, args, name, per):
    fn = pl.pallas_call(
        kernel,
        out_shape=[jax.ShapeDtypeStruct((NL, B), jnp.int32)] * n_out,
        interpret=jax.default_backend() != "tpu",
    )
    timed(name, jax.jit(lambda *a: fn(*a)), *args, per=per)


def main():
    print("backend:", jax.default_backend(), jax.devices(), flush=True)
    rng = np.random.default_rng(7)
    a = jnp.asarray(rng.integers(0, 4096, (NL, B), dtype=np.int32))
    b = jnp.asarray(rng.integers(0, 4096, (NL, B), dtype=np.int32))
    run(k_loop_only, 2, 1, (a, b), "loop", K)
    run(k_i32_chain, 2, 1, (a, b), "i32-mul", K * 33)
    # f32 comparison in plain XLA (dtype parity check of raw multiply)
    af = a.astype(jnp.float32)
    bf = b.astype(jnp.float32)

    def f32_chain(x, y):
        def body(_i, acc):
            return acc * y + jnp.float32(1)

        return lax.fori_loop(0, K * 33, body, x)

    def k_f32(x, y, o):
        o[...] = f32_chain(x[...], y[...]).astype(jnp.int32)

    fnf = pl.pallas_call(
        k_f32,
        out_shape=[jax.ShapeDtypeStruct((NL, B), jnp.int32)],
        interpret=jax.default_backend() != "tpu",
    )
    timed("f32-mul", jax.jit(lambda x, y: fnf(x, y)), af, bf, per=K * 33)
    run(k_prod_chain, 2, 1, (a, b), "prod-chain", K)
    run(k_mul_chain, 2, 1, (a, b), "mul-chain", K)
    # the f32/MXU candidate engine at the same chain length
    xs = [int(v) for v in rng.integers(1, 1 << 62, B)]
    ys = [int(v) for v in rng.integers(1, 1 << 62, B)]
    af = jnp.asarray(F32.encode_batch(xs))
    bf = jnp.asarray(F32.encode_batch(ys))
    t_np = jnp.asarray(F32.T_NPRIME)
    t_p = jnp.asarray(F32.T_P)
    fnf32 = pl.pallas_call(
        k_f32core_chain,
        out_shape=[jax.ShapeDtypeStruct((F32.K, B), jnp.float32)],
        interpret=jax.default_backend() != "tpu",
    )
    out = timed(
        "f32core",
        jax.jit(lambda x, y, tn, tp: fnf32(x, y, tn, tp)),
        af, bf, t_np, t_p,
        per=K,
    )
    # correctness spot-check against the oracle through the chain
    got = F32.decode_batch(np.asarray(out[0]))
    want = list(xs)
    for _ in range(K):
        want = [x * y % GT.P for x, y in zip(want, ys)]
    assert got == want, "f32core chain diverged from the oracle!"
    print("f32core chain matches the oracle", flush=True)
    one = jnp.asarray(
        np.broadcast_to(np.asarray(LY.MONT_ONE, np.int32)[:, None], (NL, B))
    ).copy()
    run(k_dbl_chain, 3, 3, (a, b, one), "dblchain", K)
    print("done", flush=True)


if __name__ == "__main__":
    main()
