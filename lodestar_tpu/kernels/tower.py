"""Fp6 / Fp12 tower arithmetic for the pallas field engine.

Tower (identical to the CPU ground truth, crypto/fields.py):
    Fp2  = Fp[u]/(u^2 + 1)
    Fp6  = Fp2[v]/(v^3 - xi),  xi = 1 + u
    Fp12 = Fp6[w]/(w^2 - v)

Representations: Fp6 = (c0, c1, c2) of Fp2; Fp12 = (d0, d1) of Fp6.
All value-level (pallas-kernel- and plain-jit-compatible).

Includes the final-exponentiation machinery: Frobenius via baked
Montgomery constants, Granger-Scott cyclotomic squaring, and
exponentiation by static integers with the two-word trick (no dynamic
indexing — see pow_* functions).
"""

from __future__ import annotations

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax

from ..crypto import fields as GT
from . import core as C
from . import fp2 as F2
from . import layout as LY

# ---------------------------------------------------------------------------
# Fp6
# ---------------------------------------------------------------------------


def add6(a, b):
    return tuple(F2.add2(x, y) for x, y in zip(a, b))


def sub6(a, b):
    return tuple(F2.sub2(x, y) for x, y in zip(a, b))


def neg6(a):
    return tuple(F2.neg2(x) for x in a)


def select6(mask, a, b):
    return tuple(F2.select2(mask, x, y) for x, y in zip(a, b))


def mul6_by_v(a):
    """(c0, c1, c2) * v = (xi*c2, c0, c1)."""
    return (F2.mul2_xi(a[2]), a[0], a[1])


def mul6(a, b):
    """Karatsuba Fp6 product: 6 Fp2 multiplies (18 limb products)."""
    a0, a1, a2 = a
    b0, b1, b2 = b
    t0 = F2.mul2(a0, b0)
    t1 = F2.mul2(a1, b1)
    t2 = F2.mul2(a2, b2)
    m12 = F2.mul2(F2.add2(a1, a2), F2.add2(b1, b2))
    m01 = F2.mul2(F2.add2(a0, a1), F2.add2(b0, b1))
    m02 = F2.mul2(F2.add2(a0, a2), F2.add2(b0, b2))
    c0 = F2.add2(t0, F2.mul2_xi(F2.sub2(F2.sub2(m12, t1), t2)))
    c1 = F2.add2(F2.sub2(F2.sub2(m01, t0), t1), F2.mul2_xi(t2))
    c2 = F2.add2(F2.sub2(F2.sub2(m02, t0), t2), t1)
    return (c0, c1, c2)


def sqr6(a):
    """CH-SQR2 Fp6 square: 3 Fp2 squares + 2 Fp2 multiplies (12 products)."""
    a0, a1, a2 = a
    s0 = F2.sqr2(a0)
    s1 = F2.double2(F2.mul2(a0, a1))
    s2 = F2.sqr2(F2.add2(F2.sub2(a0, a1), a2))
    s3 = F2.double2(F2.mul2(a1, a2))
    s4 = F2.sqr2(a2)
    c0 = F2.add2(s0, F2.mul2_xi(s3))
    c1 = F2.add2(s1, F2.mul2_xi(s4))
    c2 = F2.sub2(F2.sub2(F2.add2(F2.add2(s1, s2), s3), s0), s4)
    return (c0, c1, c2)


def mul6_fp2(a, k):
    """Fp6 times a batched Fp2 element."""
    return tuple(F2.mul2(x, k) for x in a)


# ---------------------------------------------------------------------------
# Fp12
# ---------------------------------------------------------------------------


def add12(a, b):
    return (add6(a[0], b[0]), add6(a[1], b[1]))


def sub12(a, b):
    return (sub6(a[0], b[0]), sub6(a[1], b[1]))


def conj12(a):
    """The p^6 Frobenius; for cyclotomic elements this is the inverse."""
    return (a[0], neg6(a[1]))


def select12(mask, a, b):
    return (select6(mask, a[0], b[0]), select6(mask, a[1], b[1]))


def mul12(a, b):
    """Karatsuba Fp12 product: 3 Fp6 multiplies (54 limb products)."""
    t0 = mul6(a[0], b[0])
    t1 = mul6(a[1], b[1])
    tm = mul6(add6(a[0], a[1]), add6(b[0], b[1]))
    return (add6(t0, mul6_by_v(t1)), sub6(sub6(tm, t0), t1))


def sqr12(a):
    """Fp12 square: 2 Fp6 multiplies (36 limb products)."""
    t = mul6(a[0], a[1])
    c0 = sub6(
        sub6(mul6(add6(a[0], a[1]), add6(a[0], mul6_by_v(a[1]))), t),
        mul6_by_v(t),
    )
    return (c0, add6(t, t))


def is_one12(a):
    """Exact equality with the Fp12 one (public-class lazy inputs OK)."""
    one = _one_plane(a[0][0][0])
    ok = C.eq_modp(a[0][0][0], one)
    zero_parts = [a[0][0][1]]
    for c in a[0][1:]:
        zero_parts += [c[0], c[1]]
    for c in a[1]:
        zero_parts += [c[0], c[1]]
    for z in zero_parts:
        ok = ok & C.is_zero_modp(z)
    return ok


def _one_plane(like):
    return jnp.broadcast_to(C.const_plane(LY.MONT_ONE, like), like.shape)


def one12(like):
    """The Fp12 one, broadcast to the batch shape of `like` (an Fp plane)."""
    one = _one_plane(like)
    zero = jnp.zeros_like(like)
    z2 = (zero, zero)
    return ((
        (one, zero), z2, z2), (z2, z2, z2))


# ---------------------------------------------------------------------------
# Frobenius (x -> x^(p^n), n in {1, 2, 3}) via baked constants
# ---------------------------------------------------------------------------

# gamma_n[k] = xi^(k * (p^n - 1) / 6); slot k = 2i + j for coefficient v^i w^j.
_G_INT = {
    n: [GT.fp2_pow(GT.XI, k * (GT.P**n - 1) // 6) for k in range(6)]
    for n in (1, 2, 3)
}
_G_CONST = {
    n: [F2.const2(g) for g in _G_INT[n]] for n in (1, 2, 3)
}
# p^2 constants are in Fp (imaginary part 0) — checked here, exploited below.
assert all(g[1] == 0 for g in _G_INT[2])


def frob12(a, power: int):
    """x -> x^(p^power) for static power in {1, 2, 3}."""
    assert power in (1, 2, 3)
    gam = _G_CONST[power]
    conj = power % 2 == 1

    def coeff(c, k):
        if conj:
            c = F2.conj2(c)
        if k == 0:
            return c
        if power == 2:
            return F2.mul2_fp_const(c, gam[k][0])
        return F2.mul2_const(c, gam[k])

    lo = tuple(coeff(c, 2 * i) for i, c in enumerate(a[0]))
    hi = tuple(coeff(c, 2 * i + 1) for i, c in enumerate(a[1]))
    return (lo, hi)


# ---------------------------------------------------------------------------
# Cyclotomic subgroup ops (Granger-Scott) — valid after the easy part
# ---------------------------------------------------------------------------


def cyclo_sqr(a):
    """Granger-Scott cyclotomic square: 9 Fp2 squares (18 limb products).

    Valid only for elements of the cyclotomic subgroup (a^(p^6+1) = 1).
    """
    (a0, a1, a2), (b0, b1, b2) = a

    def fp4_sqr(z0, z1):
        """(z0 + z1*s)^2 with s^2 = v: returns (z0^2 + xi z1^2, 2 z0 z1)."""
        t0 = F2.sqr2(z0)
        t1 = F2.sqr2(z1)
        tm = F2.sqr2(F2.add2(z0, z1))
        cross = F2.sub2(F2.sub2(tm, t0), t1)  # 2 z0 z1
        return F2.add2(t0, F2.mul2_xi(t1)), cross

    r00, c00 = fp4_sqr(a0, b1)
    r01, c01 = fp4_sqr(b0, a2)
    r02, c02 = fp4_sqr(a1, b2)

    def triple_sub_double(t, x):
        # 3t - 2x = 2(t - x) + t
        return F2.add2(F2.double2(F2.sub2(t, x)), t)

    def triple_add_double(t, x):
        return F2.add2(F2.double2(F2.add2(t, x)), t)

    def sq2(x):
        # The 3t +- 2x outputs feed the next squaring's inputs unreduced;
        # squeeze the top limb so iterated squarings stay in the public
        # limb class (core.squeeze_top docstring).
        return (C.squeeze_top(x[0]), C.squeeze_top(x[1]))

    c0 = (
        sq2(triple_sub_double(r00, a0)),
        sq2(triple_sub_double(r01, a1)),
        sq2(triple_sub_double(r02, a2)),
    )
    c1 = (
        sq2(triple_add_double(F2.mul2_xi(c02), b0)),
        sq2(triple_add_double(c00, b1)),
        sq2(triple_add_double(c01, b2)),
    )
    return (c0, c1)


def pow_static(x, e: int, sqr_fn, mul_fn, one):
    """x^e for a static python int e >= 1.

    ONE rolled square-and-multiply loop over the bits below the leading
    one, MSB first, so the compiled program holds one square and one
    multiply whatever the exponent's length (a loop per 32-bit word
    multiplied the kernel's code, and its compile time, by the word
    count).  Each step's bit comes from the static exponent words
    through a traced shift and a select chain — no dynamic array
    indexing, so this lowers cleanly in Mosaic.
    """
    assert e >= 1
    nbits = e.bit_length() - 1
    if nbits == 0:
        return x
    words = [(e >> (32 * k)) & 0xFFFFFFFF for k in range((nbits + 31) // 32)]

    def body(i, acc):
        acc = sqr_fn(acc)
        p = jnp.int32(nbits - 1) - i  # bit position, LSB = 0
        wi = jnp.right_shift(p, 5)
        word = jnp.uint32(words[0])
        for k in range(1, len(words)):
            word = jnp.where(wi == k, jnp.uint32(words[k]), word)
        bit = jnp.right_shift(word, (p & 31).astype(jnp.uint32)) & jnp.uint32(1)
        cand = mul_fn(acc, x)
        return jax.tree_util.tree_map(
            lambda c, a: jnp.where(bit != 0, c, a), cand, acc
        )

    return lax.fori_loop(0, nbits, body, x)


_X_ABS = -GT.X_PARAM  # 0xd201000000010000


def cyclo_pow_x_neg(a):
    """a^x for the (negative) BLS parameter x, a cyclotomic.

    Computes a^|x| with cyclotomic squarings then conjugates (inverse is
    free in the cyclotomic subgroup).
    """
    r = pow_static(a, _X_ABS, cyclo_sqr, mul12, None)
    return conj12(r)


# ---------------------------------------------------------------------------
# Inversion chain: Fp -> Fp2 -> Fp6 -> Fp12 (one Fp exponentiation total)
# ---------------------------------------------------------------------------


def inv_fp(a):
    """a^(p-2) — the single genuine inversion under everything."""
    return pow_static(a, GT.P - 2, C.mont_sqr, C.mont_mul, None)


def inv2(a):
    """(a0 + a1 u)^-1 = conj(a) / (a0^2 + a1^2)."""
    n = C.add(C.mont_sqr(a[0]), C.mont_sqr(a[1]))
    ninv = inv_fp(n)
    return (C.mont_mul(a[0], ninv), C.neg(C.mont_mul(a[1], ninv)))


def inv6(a):
    """Fp6 inversion via the adjoint/norm method (9 mul + 3 sqr in Fp2)."""
    a0, a1, a2 = a
    c0 = F2.sub2(F2.sqr2(a0), F2.mul2_xi(F2.mul2(a1, a2)))
    c1 = F2.sub2(F2.mul2_xi(F2.sqr2(a2)), F2.mul2(a0, a1))
    c2 = F2.sub2(F2.sqr2(a1), F2.mul2(a0, a2))
    norm = F2.add2(
        F2.mul2(a0, c0),
        F2.mul2_xi(F2.add2(F2.mul2(a2, c1), F2.mul2(a1, c2))),
    )
    ninv = inv2(norm)
    return (F2.mul2(c0, ninv), F2.mul2(c1, ninv), F2.mul2(c2, ninv))


def inv12(a):
    """Fp12 inversion: (a0 - a1 w)/(a0^2 - v a1^2)."""
    norm = sub6(sqr6(a[0]), mul6_by_v(sqr6(a[1])))
    ninv = inv6(norm)
    return (mul6(a[0], ninv), neg6(mul6(a[1], ninv)))
