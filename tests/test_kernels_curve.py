"""kernels/curve.py (jacobian group law, scalar muls, psi test) vs crypto/."""

import random

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax import lax

from lodestar_tpu.crypto import curves as GC
from lodestar_tpu.crypto import fields as GT
from lodestar_tpu.crypto import hash_to_curve as GH
from lodestar_tpu.kernels import curve as CV
from lodestar_tpu.kernels import layout as LY

pytestmark = pytest.mark.smoke

random.seed(0xCAFE)
P = LY.P


def enc1(xs):
    return jnp.asarray(LY.encode_batch(xs))


def enc2(vals):
    return (
        jnp.asarray(LY.encode_batch([v[0] for v in vals])),
        jnp.asarray(LY.encode_batch([v[1] for v in vals])),
    )


def enc_g1_aff(pts):
    return (enc1([p[0] for p in pts]), enc1([p[1] for p in pts]))


def enc_g2_aff(pts):
    return (enc2([p[0] for p in pts]), enc2([p[1] for p in pts]))


def dec1(t):
    return LY.decode_batch(np.asarray(t))


def dec2(t):
    return list(zip(dec1(t[0]), dec1(t[1])))


def jac_to_affine_g1(X, Y, Z, inf):
    out = []
    for x, y, z, i in zip(dec1(X), dec1(Y), dec1(Z), np.asarray(inf)):
        if i:
            out.append(None)
            continue
        zi = pow(z, P - 2, P)
        out.append((x * zi * zi % P, y * zi * zi * zi % P))
    return out


def jac_to_affine_g2(X, Y, Z, inf):
    out = []
    for x, y, z, i in zip(dec2(X), dec2(Y), dec2(Z), np.asarray(inf)):
        if i:
            out.append(None)
            continue
        zi = GT.fp2_inv(z)
        z2 = GT.fp2_mul(zi, zi)
        out.append((GT.fp2_mul(x, z2), GT.fp2_mul(y, GT.fp2_mul(z2, zi))))
    return out


def rand_g1(n):
    return [
        GC.scalar_mul(GC.FP_OPS, GC.G1_GEN, random.randrange(2, GT.R))
        for _ in range(n)
    ]


def rand_g2(n):
    return [
        GC.scalar_mul(GC.FP2_OPS, GC.G2_GEN, random.randrange(2, GT.R))
        for _ in range(n)
    ]


def test_add_full_edge_cases():
    """Generic, doubling, inverse, and infinity cases in one batch."""
    a, b = rand_g1(2)
    na = GC.affine_neg(GC.FP_OPS, a)
    # lanes: a+b, a+a, a+(-a), O+b, a+O, O+O
    ps = [a, a, a, a, a, a]
    qs = [b, a, na, b, b, b]
    p_inf = jnp.asarray([False, False, False, True, False, True])
    q_inf = jnp.asarray([False, False, False, False, True, True])
    px, py = enc_g1_aff(ps)
    qx, qy = enc_g1_aff(qs)
    one = CV._one_plane_like(CV.FP_OPS, px)

    @jax.jit
    def f(px, py, qx, qy, p_inf, q_inf):
        return CV.jac_add_full(
            CV.FP_OPS, (px, py, one), p_inf, (qx, qy, one), q_inf
        )

    (X, Y, Z), inf = f(px, py, qx, qy, p_inf, q_inf)
    got = jac_to_affine_g1(X, Y, Z, inf)
    want = [
        GC.affine_add(GC.FP_OPS, a, b),
        GC.affine_dbl(GC.FP_OPS, a),
        None,
        b,
        a,
        None,
    ]
    assert got == want


def _bit_planes(scalars, nbits=64):
    out = np.zeros((nbits, len(scalars)), np.int32)
    for i in range(nbits):
        out[nbits - 1 - i] = [(s >> i) & 1 for s in scalars]
    return jnp.asarray(out)


def test_scalar_mul_bits_g1_g2():
    n = 4
    g1s, g2s = rand_g1(n), rand_g2(n)
    ks = [random.getrandbits(63) * 2 + 1 for _ in range(n - 1)] + [0]
    bits = _bit_planes(ks)
    px, py = enc_g1_aff(g1s)
    qx, qy = enc_g2_aff(g2s)
    one1 = CV._one_plane_like(CV.FP_OPS, px)
    one2 = CV._one_plane_like(CV.FP2_OPS, qx)
    inf0 = jnp.zeros((n,), bool)

    @jax.jit
    def f(px, py, qx, qy, bits):
        gb = lambda i: lax.dynamic_index_in_dim(bits, i, 0, keepdims=False)
        r1 = CV.scalar_mul_bits_jac(CV.FP_OPS, (px, py, one1), inf0, gb, 64)
        r2 = CV.scalar_mul_bits_jac(CV.FP2_OPS, (qx, qy, one2), inf0, gb, 64)
        return r1, r2

    ((X1, Y1, Z1), i1), ((X2, Y2, Z2), i2) = f(px, py, qx, qy, bits)
    got1 = jac_to_affine_g1(X1, Y1, Z1, i1)
    got2 = jac_to_affine_g2(X2, Y2, Z2, i2)
    assert got1 == [GC.scalar_mul(GC.FP_OPS, p, k) for p, k in zip(g1s, ks)]
    assert got2 == [GC.scalar_mul(GC.FP2_OPS, q, k) for q, k in zip(g2s, ks)]


def test_scalar_mul_bits_jacobian_base():
    """Aggregate-style base: Z != 1 (the doubled representation)."""
    n = 2
    g1s = rand_g1(n)
    ks = [random.getrandbits(63) * 2 + 1 for _ in range(n)]
    bits = _bit_planes(ks)
    # encode P as (X, Y, Z) = (x*4, y*8, 2) — same point, Z=2
    two = enc1([2] * n)
    px = enc1([p[0] * 4 % P for p in g1s])
    py = enc1([p[1] * 8 % P for p in g1s])
    inf0 = jnp.zeros((n,), bool)

    @jax.jit
    def f(px, py, two, bits):
        gb = lambda i: lax.dynamic_index_in_dim(bits, i, 0, keepdims=False)
        return CV.scalar_mul_bits_jac(CV.FP_OPS, (px, py, two), inf0, gb, 64)

    (X, Y, Z), inf = f(px, py, two, bits)
    got = jac_to_affine_g1(X, Y, Z, inf)
    assert got == [GC.scalar_mul(GC.FP_OPS, p, k) for p, k in zip(g1s, ks)]


def test_scalar_mul_static():
    n = 3
    g2s = rand_g2(n)
    k = -GT.X_PARAM
    qx, qy = enc_g2_aff(g2s)

    @jax.jit
    def f(qx, qy):
        return CV.scalar_mul_static(CV.FP2_OPS, (qx, qy), k)

    X, Y, Z = f(qx, qy)
    got = jac_to_affine_g2(X, Y, Z, np.zeros(n, bool))
    assert got == [GC.scalar_mul(GC.FP2_OPS, q, k) for q in g2s]


def test_g2_subgroup_check():
    good = rand_g2(3)
    # on-curve but (overwhelmingly likely) outside the r-subgroup:
    # SvdW-mapped curve points before cofactor clearing
    bad = [
        GH.map_to_curve_svdw(GC.FP2_OPS, GH.hash_to_field_fp2(b"x%d" % i, 1, b"T")[0])
        for i in range(3)
    ]
    for b in bad:
        assert GC.is_on_curve(GC.FP2_OPS, b) and not GC.g2_subgroup_check(b)
    pts = good + bad
    qx, qy = enc_g2_aff(pts)
    inf = jnp.zeros((6,), bool)

    @jax.jit
    def f(qx, qy, inf):
        return CV.g2_subgroup_check((qx, qy), inf)

    got = list(np.asarray(f(qx, qy, inf)))
    assert got == [True] * 3 + [False] * 3


@pytest.mark.parametrize("const, root", [("_CX_INT", 3), ("_CY_INT", 2)])
def test_psi_constants_are_rfc9380s(const, root):
    """psi's constants are RFC 9380 G.3's c1 = 1/(1+I)^((p-1)/3) and
    c2 = 1/(1+I)^((p-1)/2)."""
    assert getattr(CV, const) == GT.fp2_inv(GT.fp2_pow((1, 1), (P - 1) // root))


def _host_psi(q):
    return tuple(
        GT.fp2_mul(GT.fp2_conj(c), k) for c, k in zip(q, (CV._CX_INT, CV._CY_INT))
    )


@pytest.mark.parametrize("msg", [b"cofactor 0", b"cofactor 1", b"cofactor 2"])
def test_g3_cofactor_grouping_is_h_eff(msg):
    """The G.3 grouping the hash kernel evaluates, t2 = [x]P + psi(P),
    then [x]t2 - t2 - P + psi^2(P) + psi^2(P), equals the plain h_eff
    multiplication for an SSWU sum P that is not in G2 (host integers;
    the device loop is checked in tests/test_kernels_ingest.py)."""
    fo, x = GC.FP2_OPS, GT.X_PARAM
    u0, u1 = GH.hash_to_field_fp2(msg, 2, GH.DST_G2)
    p = GC.affine_add(
        fo,
        GH.iso3_map(GH.map_to_curve_sswu_g2(u0)),
        GH.iso3_map(GH.map_to_curve_sswu_g2(u1)),
    )
    assert not GC.g2_subgroup_check(p)
    t2 = GC.affine_add(fo, GC.scalar_mul(fo, p, x), _host_psi(p))
    psi2 = _host_psi(_host_psi(p))
    terms = [GC.scalar_mul(fo, t2, x), GC.affine_neg(fo, t2), GC.affine_neg(fo, p)]
    q = GC.multi_add(fo, terms + [psi2, psi2])
    assert q == GH.clear_cofactor_g2(p)


def test_sum_points_axis0_and_lanes():
    k, n = 5, 4
    pts = [rand_g1(n) for _ in range(k)]
    rng = np.random.default_rng(5)
    mask = rng.random((k, n)) < 0.7
    mask[0, :] = True
    xs = jnp.stack([enc1([p[0] for p in row]) for row in pts])
    ys = jnp.stack([enc1([p[1] for p in row]) for row in pts])
    ones = jnp.broadcast_to(
        CV._one_plane_like(CV.FP_OPS, xs[0]), xs.shape
    )
    inf = jnp.asarray(~mask)

    @jax.jit
    def f(xs, ys, ones, inf):
        return CV.sum_points_axis0(CV.FP_OPS, (xs, ys, ones), inf)

    (X, Y, Z), oinf = f(xs, ys, ones, inf)
    got = jac_to_affine_g1(X, Y, Z, oinf)
    want = [
        GC.multi_add(GC.FP_OPS, [pts[i][j] for i in range(k) if mask[i, j]])
        for j in range(n)
    ]
    assert got == want

    # lane-axis sum of one row
    row = pts[0]
    x0, y0 = enc1([p[0] for p in row]), enc1([p[1] for p in row])
    one = CV._one_plane_like(CV.FP_OPS, x0)

    @jax.jit
    def g(x0, y0, one):
        return CV.sum_points_lanes(
            CV.FP_OPS, (x0, y0, one), jnp.zeros((n,), bool)
        )

    (X, Y, Z), oinf = g(x0, y0, one)
    got = jac_to_affine_g1(X, Y, Z, oinf)[0]
    assert got == GC.multi_add(GC.FP_OPS, row)
