"""kernels/pairing.py (Miller loop + final exp) vs the crypto/ oracle.

The kernel pairing returns e(P,Q)^3 (see pairing.py docstring), so oracle
values are cubed before comparison; is-one checks need no adjustment.
"""

import random

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from lodestar_tpu.crypto import bls as GB
from lodestar_tpu.crypto import curves as GC
from lodestar_tpu.crypto import fields as GT
from lodestar_tpu.crypto import pairing as GP
from lodestar_tpu.crypto.hash_to_curve import hash_to_g2
from lodestar_tpu.kernels import curve as CV
from lodestar_tpu.kernels import layout as LY
from lodestar_tpu.kernels import pairing as KP
from lodestar_tpu.kernels import tower as TW

pytestmark = pytest.mark.slow

random.seed(0xBEEF)
P = LY.P


def enc1(xs):
    return jnp.asarray(LY.encode_batch(xs))


def enc2(vals):
    return (
        jnp.asarray(LY.encode_batch([v[0] for v in vals])),
        jnp.asarray(LY.encode_batch([v[1] for v in vals])),
    )


def dec1(t):
    return LY.decode_batch(np.asarray(t))


def dec2(t):
    return list(zip(dec1(t[0]), dec1(t[1])))


def dec12(t):
    def dec6(c):
        return list(zip(*[dec2(x) for x in c]))

    return list(zip(*[dec6(c) for c in t]))


def enc_g1_aff(pts):
    return (enc1([p[0] for p in pts]), enc1([p[1] for p in pts]))


def enc_g2_aff(pts):
    return (enc2([p[0] for p in pts]), enc2([p[1] for p in pts]))


def test_pairing_matches_oracle_cubed():
    n = 2
    ps = [
        GC.scalar_mul(GC.FP_OPS, GC.G1_GEN, random.randrange(2, GT.R))
        for _ in range(n)
    ]
    qs = [
        GC.scalar_mul(GC.FP2_OPS, GC.G2_GEN, random.randrange(2, GT.R))
        for _ in range(n)
    ]
    px, py = enc_g1_aff(ps)
    qx, qy = enc_g2_aff(qs)
    one1 = CV._one_plane_like(CV.FP_OPS, px)

    @jax.jit
    def f(px, py, qx, qy):
        ml = KP.miller_loop((px, py, one1), (qx, qy))
        return KP.final_exponentiation(ml)

    got = dec12(f(px, py, qx, qy))
    want = [
        GT.fp12_pow(GP.pairing(p, q), 3) for p, q in zip(ps, qs)
    ]
    assert got == want


def test_pairing_jacobian_p_scaling():
    """P given in non-normalized jacobian form gives the same pairing."""
    p = GC.scalar_mul(GC.FP_OPS, GC.G1_GEN, 0xABCDE)
    q = GC.scalar_mul(GC.FP2_OPS, GC.G2_GEN, 0x12345)
    # (X, Y, Z) = (x z^2, y z^3, z) for z = 7
    z = 7
    px = enc1([p[0] * z * z % P])
    py = enc1([p[1] * z**3 % P])
    pz = enc1([z])
    qx, qy = enc_g2_aff([q])

    @jax.jit
    def f(px, py, pz, qx, qy):
        return KP.final_exponentiation(KP.miller_loop((px, py, pz), (qx, qy)))

    got = dec12(f(px, py, pz, qx, qy))[0]
    want = GT.fp12_pow(GP.pairing(p, q), 3)
    assert got == want


def test_signature_relation_and_batch_product():
    """e(pk, H(m)) * e(-G1, sig) == 1 through the lane-product path."""
    sks = [GB.keygen(b"kp-%d" % i) for i in range(2)]
    msgs = [b"kernel pairing %d" % i for i in range(2)]
    pks = [GB.sk_to_pk(sk) for sk in sks]
    hms = [hash_to_g2(m) for m in msgs]
    sigs = [GB.sign(sk, m) for sk, m in zip(sks, msgs)]
    bad_sigs = [sigs[0], GC.scalar_mul(GC.FP2_OPS, sigs[1], 2)]

    neg_g1 = GC.affine_neg(GC.FP_OPS, GC.G1_GEN)
    # lanes: pk0, pk1, -G1, -G1  paired with  H0, H1, sig0, sig1
    px, py = enc_g1_aff(pks + [neg_g1, neg_g1])
    one1 = CV._one_plane_like(CV.FP_OPS, px)

    @jax.jit
    def f(px, py, qx, qy):
        ml = KP.miller_loop((px, py, one1), (qx, qy))
        prod = KP.product12_lanes(ml)
        fe = KP.final_exponentiation(prod)
        return TW.is_one12(fe)

    qx, qy = enc_g2_aff(hms + sigs)
    assert bool(np.asarray(f(px, py, qx, qy))[0])
    qx, qy = enc_g2_aff(hms + bad_sigs)
    assert not bool(np.asarray(f(px, py, qx, qy))[0])


def test_to_affine_g2():
    q = GC.scalar_mul(GC.FP2_OPS, GC.G2_GEN, 0xF00)
    z = (3, 5)
    z2 = GT.fp2_sqr(z)
    qx = enc2([GT.fp2_mul(q[0], z2), (1, 0)])
    qy = enc2([GT.fp2_mul(q[1], GT.fp2_mul(z2, z)), (1, 0)])
    qz = enc2([z, (0, 0)])

    @jax.jit
    def f(qx, qy, qz):
        return KP.to_affine_g2((qx, qy, qz))

    (x, y), inf = f(qx, qy, qz)
    assert list(np.asarray(inf)) == [False, True]
    assert dec2(x)[0] == q[0] and dec2(y)[0] == q[1]
