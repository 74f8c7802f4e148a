"""Optimal ate pairing on the pallas engine layout.

The device replacement for blst's pairing core under the reference's
worker pool (packages/beacon-node/src/chain/bls/multithread/worker.ts:
30-106).  Value-level; runs inside pallas kernels and under plain jit.

Design (vs the affine CPU oracle in crypto/pairing.py):
  - Q (G2, twist) stays AFFINE — the service provides affine signatures/
    messages, and the one aggregate point is normalized with a single
    Fp2 inversion per batch.
  - P (G1) stays JACOBIAN: line evaluations are scaled by powers of P.Z
    (and other Fp/Fp2 factors), all killed by the final exponentiation
    since they lie in proper subfields of Fp12 — so NO per-set inversion
    exists anywhere.
  - Lines are sparse Fp12 elements on slots (1, v*w, v^2*w):
        l = e0*yP * 1 + e1 * vw + e2*xP * v^2 w
    (slot algebra derived from the same untwist map the oracle uses,
    crypto/pairing.py:46-62; the tangent/chord coefficients below are
    scaled by 2Y_T*xi*Z_T^6 and (x2 Z^2 - X)*Z^5 respectively).
  - The T accumulator is JACOBIAN on the twist.
  - Final exponentiation computes f^(3*(p^4-p^2+1)/r) via the
    (x-1)^2 (x+p) (x^2+p^2-1) + 3 chain (identity asserted in
    crypto/pairing.py:34); the cube is harmless for equality/one checks
    because gcd(3, r) = 1.
"""

from __future__ import annotations

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax

from ..crypto import fields as GT
from . import core as C
from . import curve as CV
from . import fp2 as F2
from . import tower as TW

_X_ABS = -GT.X_PARAM
_ATE_BITS = bin(_X_ABS)[3:]  # MSB-first, leading 1 consumed by T = Q init


# ---------------------------------------------------------------------------
# Sparse line container: (a0, b1, b2) on slots (1, v*w, v^2*w)
# ---------------------------------------------------------------------------


def _mul12_sparse(f, line):
    """f * (a0 + b1*vw + b2*v^2 w): 45 limb products (15 Fp2 muls)."""
    a0, b1, b2 = line
    f0, f1 = f
    # A = (a0, 0, 0), B = (0, b1, b2) as Fp6 halves of the line.
    f0A = tuple(F2.mul2(c, a0) for c in f0)
    f10, f11, f12 = f1
    f1B = (
        F2.mul2_xi(F2.add2(F2.mul2(f11, b2), F2.mul2(f12, b1))),
        F2.add2(F2.mul2_xi(F2.mul2(f12, b2)), F2.mul2(f10, b1)),
        F2.add2(F2.mul2(f10, b2), F2.mul2(f11, b1)),
    )
    ab = (a0, b1, b2)  # A + B as a dense Fp6
    fm = TW.mul6(TW.add6(f0, f1), ab)
    lo = TW.add6(f0A, TW.mul6_by_v(f1B))
    hi = TW.sub6(TW.sub6(fm, f0A), f1B)
    return (lo, hi)


# ---------------------------------------------------------------------------
# Miller steps (twist side; T jacobian, Q affine, P jacobian via planes)
# ---------------------------------------------------------------------------


def _p_planes(p_jac):
    """Per-pairing constants (Y1, X1*Z1, Z1^3) replacing (yP, xP, 1)."""
    X1, Y1, Z1 = p_jac
    z2 = C.mont_sqr(Z1)
    return (Y1, C.mont_mul(X1, Z1), C.mont_mul(z2, Z1))


def _dbl_step(T, pw):
    """Tangent line at T evaluated at P, and 2T."""
    X, Y, Z = T
    w_y, w_x, w_1 = pw
    A = F2.sqr2(X)           # X^2
    B = F2.sqr2(Y)           # Y^2
    CC = F2.sqr2(B)
    D = F2.double2(F2.sub2(F2.sub2(F2.sqr2(F2.add2(X, B)), A), CC))
    E = F2.mul2_small(A, 3)
    F = F2.sqr2(E)
    X3 = F2.sub2(F, F2.double2(D))
    Y3 = F2.sub2(F2.mul2(E, F2.sub2(D, X3)), F2.mul2_small(CC, 8))
    Z3 = F2.double2(F2.mul2(Y, Z))

    Z2 = F2.sqr2(Z)
    Z3p = F2.mul2(Z2, Z)     # Z^3
    X3p = F2.mul2(A, X)      # X^3
    e0 = F2.mul2_xi(F2.double2(F2.mul2(Y, Z3p)))   # 2 xi Y Z^3
    e1 = F2.sub2(F2.mul2_small(X3p, 3), F2.double2(B))  # 3X^3 - 2Y^2
    e2 = F2.neg2(F2.mul2_small(F2.mul2(A, Z2), 3))      # -3 X^2 Z^2
    line = (
        F2.mul2_fp(e0, w_y),
        F2.mul2_fp(e1, w_1),
        F2.mul2_fp(e2, w_x),
    )
    return line, (X3, Y3, Z3)


def _add_step(T, q_aff, pw):
    """Chord line through T and Q evaluated at P, and T + Q."""
    X1, Y1, Z1 = T
    x2, y2 = q_aff
    w_y, w_x, w_1 = pw
    Z1Z1 = F2.sqr2(Z1)
    Z1c = F2.mul2(Z1, Z1Z1)  # Z^3
    U2 = F2.mul2(x2, Z1Z1)
    S2 = F2.mul2(y2, Z1c)
    H = F2.sub2(U2, X1)
    J = F2.sub2(S2, Y1)

    HH = F2.sqr2(H)
    I = F2.mul2_small(HH, 4)
    JJ = F2.mul2(H, I)
    rr = F2.double2(J)
    V = F2.mul2(X1, I)
    X3 = F2.sub2(F2.sub2(F2.sqr2(rr), JJ), F2.double2(V))
    Y3 = F2.sub2(
        F2.mul2(rr, F2.sub2(V, X3)), F2.double2(F2.mul2(Y1, JJ))
    )
    Z3 = F2.sub2(F2.sub2(F2.sqr2(F2.add2(Z1, H)), Z1Z1), HH)

    e0 = F2.mul2_xi(F2.mul2(H, Z1c))            # xi H Z^3
    e1 = F2.sub2(F2.mul2(J, X1), F2.mul2(H, Y1))  # J X - H Y
    e2 = F2.neg2(F2.mul2(J, Z1Z1))              # -J Z^2
    line = (
        F2.mul2_fp(e0, w_y),
        F2.mul2_fp(e1, w_1),
        F2.mul2_fp(e2, w_x),
    )
    return line, (X3, Y3, Z3)


def _static_bit(k: int, pos):
    """Bit `pos` (traced int32) of the static python int k (< 2^64)."""
    hi = jnp.uint32((k >> 32) & 0xFFFFFFFF)
    lo = jnp.uint32(k & 0xFFFFFFFF)
    p = pos.astype(jnp.uint32)
    b_hi = (hi >> (p - jnp.uint32(32))) & jnp.uint32(1)
    b_lo = (lo >> p) & jnp.uint32(1)
    return jnp.where(pos >= 32, b_hi, b_lo)


def miller_loop(p_jac, q_aff):
    """f_{|x|,Q}(P) conjugated (x < 0), up to subfield factors.

    p_jac: jacobian G1 point (batched planes), must not be O.
    q_aff: affine G2 twist point (batched Fp2 pairs), must not be O.
    Returns a (lazy) Fp12 value; only meaningful through final_exp.

    One rolled fori_loop over the 63 post-MSB ate bits; the (5) addition
    steps run under lax.cond on the statically-known bit — this keeps the
    Mosaic program one dbl-step + one add-step big instead of unrolling
    the segment structure (compile-time lever, dev/NOTES.md).
    """
    pw = _p_planes(p_jac)
    one2 = CV._one_plane_like(CV.FP2_OPS, q_aff[0])
    T = (q_aff[0], q_aff[1], one2)
    f = TW.one12(pw[0])
    nbits = _X_ABS.bit_length() - 1  # 63

    def body(i, st):
        f, T = st
        line, T = _dbl_step(T, pw)
        f = _mul12_sparse(TW.sqr12(f), line)
        bit = _static_bit(_X_ABS, jnp.int32(nbits - 1) - i)

        def do_add(st):
            f, T = st
            line, T2 = _add_step(T, q_aff, pw)
            return (_mul12_sparse(f, line), T2)

        return lax.cond(bit != 0, do_add, lambda s: s, (f, T))

    f, _T = lax.fori_loop(0, nbits, body, (f, T))
    return TW.conj12(f)


def product12_lanes(f):
    """Product of f's lanes over the batch axis -> FULL width.

    Butterfly over full-width lane rolls (log2(B) mul12 rounds in ONE
    rolled loop with a traced shift) rather than halving lane slices:
    narrow/offset lane slices produce Mosaic layouts later sublane pads
    reject, and half-width ops are not cheaper on the 128-lane VPU.
    EVERY lane of the result holds the product; B must be a power of
    two (the lane tile BT = 128 is).  Runs inside a pallas kernel or
    under plain jit (curve.lane_roll lowers either way).
    """
    b = f[0][0][0].shape[-1]
    assert b & (b - 1) == 0, f"lane width {b} must be a power of two"

    def body(r, f):
        shift = jnp.right_shift(jnp.int32(b // 2), r)
        other = jax.tree_util.tree_map(lambda a: CV.lane_roll(a, shift), f)
        return TW.mul12(f, other)

    return lax.fori_loop(0, b.bit_length() - 1, body, f)


def final_exponentiation(f):
    """f^(3 (p^12-1)/r) — see module docstring for the cube.

    The hard part's five x-powers run as ONE rolled loop, each step
    r_i = r_{i-1}^x * c_i with a step-selected factor c_i, and its
    closing product as a second loop of one Fp12 multiply: the kernel
    holds one power and two multiplies where the straight-line chain
    held five and nine (its compile time was the largest on the cold
    start, PERF.md PR 21).
    """
    # easy part: m = (conj(f) * f^-1)^(p^2) * (conj(f) * f^-1)
    g = TW.mul12(TW.conj12(f), TW.inv12(f))
    m = TW.mul12(TW.frob12(g, 2), g)
    one = TW.one12(m[0][0][0])

    # hard part ((x-1)^2 (x+p) (x^2+p^2-1) + 3 chain):
    #   r1 = m^x conj(m)          = m^(x-1)
    #   r2 = r1^x conj(r1)        = m^((x-1)^2)
    #   r3 = r2^x r2^p            = m^((x-1)^2 (x+p))
    #   r4 = r3^x,  r5 = r4^x     = r3^(x^2)
    def power_step(i, st):
        x, r3 = st
        c = CV.pick(i == 2, TW.frob12(x, 1), TW.conj12(x))
        c = CV.pick(i >= 3, one, c)
        r = TW.mul12(TW.cyclo_pow_x_neg(x), c)
        return r, CV.pick(i == 2, r, r3)

    r5, r3 = lax.fori_loop(0, 5, power_step, (m, m))
    # r3^(x^2 + p^2 - 1) * m^3 = r5 * r3^(p^2) * conj(r3) * m * m^2
    factors = (TW.frob12(r3, 2), TW.conj12(r3), m, TW.cyclo_sqr(m))

    def close_step(i, acc):
        c = factors[-1]
        for k in range(len(factors) - 2, -1, -1):
            c = CV.pick(i == k, factors[k], c)
        return TW.mul12(acc, c)

    return lax.fori_loop(0, len(factors), close_step, r5)


def to_affine_g2(pt_jac):
    """Jacobian -> affine on the twist via ONE Fp2 inversion.

    Returns ((x, y), inf_mask); for inf lanes the affine value is garbage
    and must be substituted by the caller.
    """
    X, Y, Z = pt_jac
    inf = F2.is_zero2(Z)
    zi = TW.inv2(Z)
    zi2 = F2.sqr2(zi)
    x = F2.mul2(X, zi2)
    y = F2.mul2(Y, F2.mul2(zi2, zi))
    return (x, y), inf
