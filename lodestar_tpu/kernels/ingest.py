"""Device-side ingest: G2 decompression and SSWU hash-to-curve.

The byte->point work the reference performs during deserialization
inside blst (signature/pubkey uncompress in
packages/beacon-node/src/chain/bls/multithread/worker.ts:30-50, hashing
inside verify) becomes batched lane-parallel kernels here, so the host
ships only raw coordinate limbs + flag bits:

  - `g2_decompress_y`: y from x + wire sign bit (one Fp2 sqrt chain),
  - `sswu_map_g2` + `iso3_map` + `clear_cofactor_g2_sum`: the device
    mirror of the host RFC 9380 pipeline (crypto/hash_to_curve.py:227-287),
    the cofactor cleared by the psi endomorphism (RFC 9380 G.3) where
    the host multiplies by h_eff;
    expand_message_xmd stays on the host (SHA-256, cheap, amortized by
    the per-slot SeenAttestationDatas cache) and ships u as plain limbs
    plus its sgn0 bit.

Everything is value-level (usable inside pallas kernels) plus jitted
standalone wrappers for the verifier's ingest path.
"""

from __future__ import annotations

import numpy as np

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from ..crypto import fields as GT
from ..crypto import hash_to_curve as HC
from . import canonical as CN
from . import core as C
from . import curve as CV
from . import fp2 as F2
from . import jit_dispatch as JD
from . import layout as LY
from . import sqrt as SQ
from . import tower as TW

NL = LY.NL
BT = 128

# -- constants (python ints; baked into kernels as splats) ------------------

_B2_G2 = (4, 4)  # E2: y^2 = x^3 + 4(1+i)
_A_ISO = HC._A2
_B_ISO = HC._B2
_Z_ISO = HC._Z2
_MINUS_B_OVER_A = GT.fp2_mul(
    GT.fp2_neg(_B_ISO), GT.fp2_inv(_A_ISO)
)
_B_OVER_ZA = GT.fp2_mul(_B_ISO, GT.fp2_inv(GT.fp2_mul(_Z_ISO, _A_ISO)))


def _ml(k01):
    """Fp2 python-int constant -> (mont limb list, mont limb list) for
    mul2_const's shared-constant products."""
    return (
        [int(v) for v in LY.const_mont(k01[0])],
        [int(v) for v in LY.const_mont(k01[1])],
    )


_Z_ML = _ml(_Z_ISO)
_MBA_ML = _ml(_MINUS_B_OVER_A)


def _c2(k01, like):
    """Fp2 python-int constant -> broadcast mont planes."""
    return (
        C.const_plane([int(v) for v in LY.const_mont(k01[0])], like),
        C.const_plane([int(v) for v in LY.const_mont(k01[1])], like),
    )


def _g2_rhs(x):
    """x^3 + 4(1+i) on E2."""
    return F2.add2(F2.mul2(F2.sqr2(x), x), _c2(_B2_G2, x[0]))


# -- decompression ----------------------------------------------------------


def g2_decompress_y(x, sign_bit):
    """y for compressed (x, sign) on E2; (y, on_curve_ok).

    sign_bit: bool/int32 [..., B] — the wire's lexicographic flag.
    Root choice matches the host oracle (crypto/curves.py g2_decompress).
    """
    y, ok = SQ.fp2_sqrt(_g2_rhs(x))
    want = sign_bit != 0 if sign_bit.dtype != jnp.bool_ else sign_bit
    flip = CN.fp2_sgn(y) != want
    y = F2.select2(~flip, y, F2.neg2(y))
    return y, ok


# -- SSWU map + isogeny + cofactor clearing ---------------------------------


def sswu_map_g2(u, u_sgn0):
    """Simplified SWU on E2' for one Fp2 element (mont planes).

    u_sgn0: host-computed RFC sgn0(u) bit (the host already has u as
    integers from hash_to_field).  Mirrors crypto/hash_to_curve.py
    map_to_curve_sswu_g2, branch-free.
    """
    like = u[0]
    A = _c2(_A_ISO, like)
    B = _c2(_B_ISO, like)
    zu2 = F2.mul2_const(F2.sqr2(u), _Z_ML)
    tv1 = F2.add2(F2.sqr2(zu2), zu2)
    tv1_z = F2.is_zero2(tv1)
    one = (C.const_plane([int(v) for v in LY.MONT_ONE], like), jnp.zeros_like(like))
    x1_main = F2.mul2_const(F2.add2(one, TW.inv2(tv1)), _MBA_ML)
    x1 = F2.select2(tv1_z, _c2(_B_OVER_ZA, like), x1_main)

    def g_iso(x):
        return F2.add2(F2.mul2(F2.add2(F2.sqr2(x), A), x), B)

    gx1 = g_iso(x1)
    y1, ok1 = SQ.fp2_sqrt(gx1)
    x2 = F2.mul2(zu2, x1)
    gx2 = g_iso(x2)
    y2, _ok2 = SQ.fp2_sqrt(gx2)
    x = F2.select2(ok1, x1, x2)
    y = F2.select2(ok1, y1, y2)
    want = u_sgn0 != 0 if u_sgn0.dtype != jnp.bool_ else u_sgn0
    flip = CN.fp2_sgn0(y) != want
    y = F2.select2(~flip, y, F2.neg2(y))
    return (x, y)


def _poly2(coeffs, x):
    """Horner eval with python Fp2 coefficients."""
    acc = (jnp.zeros_like(x[0]), jnp.zeros_like(x[0]))
    for c in reversed(coeffs):
        acc = F2.add2(F2.mul2(acc, x), _c2(c, x[0]))
    return acc


def iso3_map(pt):
    """The 3-isogeny E2' -> E2 (host mirror: crypto/hash_to_curve.py
    iso3_map).  Kernel points (vanishing denominators) cannot occur for
    SSWU outputs of hashed inputs; the returned ok flag guards anyway."""
    x, y = pt
    xden = _poly2(HC._ISO3_XDEN, x)
    yden = _poly2(HC._ISO3_YDEN, x)
    ok = ~F2.is_zero2(xden) & ~F2.is_zero2(yden)
    xn = F2.mul2(_poly2(HC._ISO3_XNUM, x), TW.inv2(xden))
    yn = F2.mul2(F2.mul2(y, _poly2(HC._ISO3_YNUM, x)), TW.inv2(yden))
    return (xn, yn), ok


def hash_to_g2_values(u0, u1, u0_sgn0, u1_sgn0):
    """Full map_to_curve for one message: two SSWU points, mapped to E2,
    added and cofactor-cleared.  Returns jacobian planes + ok.

    The two maps run as one rolled 2-step loop, so the kernel holds the
    SSWU + isogeny code once (unrolled it was most of the kernel's cold
    compile, PERF.md PR 21); ok flags ride the carry as int32 (Mosaic
    rejects i1 loop carries)."""

    def one_map(i, st):
        q0, ok0, q1, ok1 = st
        first = i == 0
        q, ok = iso3_map(
            sswu_map_g2(CV.pick(first, u0, u1), CV.pick(first, u0_sgn0, u1_sgn0))
        )
        ok = ok.astype(jnp.int32)
        return (
            CV.pick(first, q, q0), CV.pick(first, ok, ok0),
            CV.pick(first, q1, q), CV.pick(first, ok1, ok),
        )

    no = jnp.zeros(u0[0].shape[-1:], jnp.int32)
    q0, ok0, q1, ok1 = jax.lax.fori_loop(
        0, 2, one_map, ((u0, u0), no, (u0, u0), no)
    )
    ok0, ok1 = ok0 != 0, ok1 != 0
    one = CV._one_plane_like(CV.FP2_OPS, q0[0])
    cleared = clear_cofactor_g2_sum((q0[0], q0[1], one), (q1[0], q1[1], one))
    return cleared, ok0 & ok1


# Steps of clear_cofactor_g2_sum's loop.  |x| has 63 bits below its
# leading one; each [|x|] chain starts from its base and doubles once
# per bit.
_X_BITS = CV._X_ABS.bit_length() - 1
_MUL1 = 1  # 1..63: T = [|x|]P
_T2 = _MUL1 + _X_BITS  # 64: t2 = -T + psi(P) = [x]P + psi(P)
_MUL2 = _T2 + 1  # 65..127: T = [|x|]t2
_TAIL = _MUL2 + _X_BITS  # 128: -T - t2; 129: -P; 130, 131: + psi^2(P)
_STEPS = _TAIL + 4


def clear_cofactor_g2_sum(q0, q1):
    """[h_eff](q0 + q1) for jacobian q0, q1: the same point as the
    host's plain h_eff multiplication (crypto/hash_to_curve.py
    clear_cofactor_g2).

    RFC 9380 Appendix G.3 (Budroni-Pintore): with x the negative BLS
    parameter and psi the endomorphism of CV.g2_psi,
    [h_eff]P = [x^2 - x - 1]P + [x - 1]psi(P) + psi^2(2P), evaluated as
    t2 = [x]P + psi(P), then [x]t2 - t2 - P + psi^2(P) + psi^2(P), with
    [x]T = -[|x|]T.  One rolled loop holds the whole chain, the sum
    P = q0 + q1 first: each step doubles (inside a multiplication),
    then adds its operand (every step outside one; at the set bits of
    |x|, 5 of 63, inside), so the kernel holds one doubling, one
    addition and one psi whatever the chain's length.

    The additions skip the equal/infinity dispatch
    (jac_add_mixed_or_full): hash outputs are (computationally) random
    full-group points, so an accumulator falling on +-operand (or on O)
    along this fixed chain has negligible probability and cannot be
    steered by an adversary (preimage resistance).
    """
    fo = CV.FP2_OPS
    bit_at = CV.static_bit_reader(CV._X_ABS)

    def step(i, st):
        T, P, U, S = st  # accumulator, P, psi register, t2
        in1 = (i >= _MUL1) & (i < _T2)
        in2 = (i >= _MUL2) & (i < _TAIL)
        mul = in1 | in2
        T = jax.lax.cond(mul, lambda t: CV.jac_dbl(fo, t), lambda t: t, T)
        T = CV.pick((i == _T2) | (i == _TAIL), CV.jac_neg(fo, T), T)
        op = CV.pick(i == 0, q1, U)  # U: psi(P) at _T2, psi^2(P) at the end
        op = CV.pick(in1, P, op)
        op = CV.pick(in2, S, op)
        op = CV.pick(i == _TAIL, CV.jac_neg(fo, S), op)
        op = CV.pick(i == _TAIL + 1, CV.jac_neg(fo, P), op)
        bit = bit_at(jnp.where(in1, _T2 - 1 - i, _TAIL - 1 - i))
        T = jax.lax.cond(
            ~mul | (bit != 0),
            lambda t: CV.jac_add_mixed_or_full(fo, t, op),
            lambda t: t,
            T,
        )
        P = CV.pick(i == 0, T, P)
        U = CV.pick(i == 0, T, U)
        U = jax.lax.cond(
            (i == _MUL1) | (i == _MUL2), CV.g2_psi, lambda u: u, U
        )
        S = CV.pick(i == _T2, T, S)
        return T, P, U, S

    return jax.lax.fori_loop(0, _STEPS, step, (q0, q0, q0, q0))[0]


# -- jitted wrappers (ingest entry points) ----------------------------------


def _tiled(kernel, ins, in_rows, out_rows, n):
    # cached launch: a per-call pallas_call re-traces the kernel body
    from . import launch as LA

    return LA.tiled(kernel, ins, in_rows, out_rows, n, BT)


_R2_LIMBS = [int(v) for v in LY.MONT_R2]


def _mont(r):
    return C.redc(C.mul_cols_shared(r, _R2_LIMBS, LY.NC))


def _k_hash_g2(u00, u01, u10, u11, sgn, ox0, ox1, oy0, oy1, oz0, oz1, ook):
    """Plain-limb u planes + sgn0 bits [2, B] -> jacobian G2 planes."""
    u0 = (_mont(u00[...]), _mont(u01[...]))
    u1 = (_mont(u10[...]), _mont(u11[...]))
    bits = sgn[...]
    (X, Y, Z), ok = hash_to_g2_values(u0, u1, bits[0], bits[1])
    ox0[...], ox1[...] = X
    oy0[...], oy1[...] = Y
    oz0[...], oz1[...] = Z
    ook[...] = ok[None, :].astype(jnp.int32)


@JD.ops_jit
def hash_to_g2_device(u00, u01, u10, u11, sgn_bits):
    """Batched map_to_curve: u as PLAIN limbs [NL, n], sgn_bits int32
    [2, n] (sgn0(u0), sgn0(u1) from the host's hash_to_field integers).
    Returns jacobian planes (X0, X1, Y0, Y1, Z0, Z1) + ok[n]."""
    n = u00.shape[-1]
    out = _tiled(
        _k_hash_g2,
        (u00, u01, u10, u11, sgn_bits),
        [NL] * 4 + [2],
        [NL] * 6 + [1],
        n,
    )
    return out[:6], out[6][0] != 0


def _k_g2_decompress(x0, x1, flags, ox0, ox1, oy0, oy1, ook):
    """Plain-limb x planes + (sign, inf) bits [2, B] ->
    mont x planes + y planes + ok."""
    x = (_mont(x0[...]), _mont(x1[...]))
    bits = flags[...]
    y, ok = g2_decompress_y(x, bits[0])
    inf = bits[1] != 0
    ox0[...], ox1[...] = x
    oy0[...], oy1[...] = y
    # infinity encodings skip the curve check (the pipeline handles them
    # through its sig_inf lane masks)
    ook[...] = (ok | inf)[None, :].astype(jnp.int32)


# -- G1 KeyValidate (pubkey registration) -----------------------------------

_B1_G1 = 4  # E1: y^2 = x^3 + 4
_R_ORDER = GT.R


def g1_keyvalidate(x, sign_bit):
    """Decompress + KeyValidate one lane-batch of G1 pubkeys.

    x: mont Fp plane; returns ((x, y) affine mont, ok).  ok means:
    on-curve AND in the r-order subgroup (blst KeyValidate, consumed at
    registration by the reference's pubkey cache —
    packages/state-transition/src/cache/pubkeyCache.ts:29-47).

    The subgroup test is a full [r]P scalar multiplication using the
    COMPLETE masked addition (jac_add_full): adversarial keys can have
    small order (dividing the E1 cofactor), which makes T == +-P
    coincidences reachable mid-chain — the exact-zero dispatch and
    infinity masks keep every step correct, so the final infinity mask
    IS the membership verdict.
    """
    b4 = C.const_plane([int(v) for v in LY.const_mont(_B1_G1)], x)
    rhs = C.add(C.mont_mul(C.mont_sqr(x), x), b4)
    y, on_curve = SQ.fp_sqrt(rhs)
    want = sign_bit != 0 if sign_bit.dtype != jnp.bool_ else sign_bit
    flip = CN.fp_sgn(y) != want
    y = C.select(~flip, y, C.neg(y))

    one = CV._one_plane_like(CV.FP_OPS, x)
    base = (x, y, one)
    no_inf = jnp.zeros(x.shape[-1:], jnp.int32)

    def dbl(st):
        T, t_inf = st
        return (CV.jac_dbl(CV.FP_OPS, T), t_inf)  # dbl keeps Z=0 at O

    def add(st, _b):
        T, t_inf = st
        out, out_inf = CV.jac_add_full(
            CV.FP_OPS, T, t_inf != 0, base, no_inf != 0
        )
        return (out, out_inf.astype(jnp.int32))

    T, t_inf = TW.pow_static((base, no_inf), _R_ORDER, dbl, add, None)
    in_subgroup = (t_inf != 0) | C.is_zero_modp(T[2])
    return (x, y), on_curve & in_subgroup


def _k_g1_keyvalidate(x0, flags, ox, oy, ook):
    x = _mont(x0[...])
    bits = flags[...]
    (x, y), ok = g1_keyvalidate(x, bits[0])
    inf = bits[1] != 0
    ox[...], oy[...] = x, y
    ook[...] = (ok & ~inf)[None, :].astype(jnp.int32)  # infinity never valid


@JD.ops_jit
def g1_keyvalidate_device(x0, flag_bits):
    """Batched pubkey decompression + KeyValidate: x as PLAIN limbs,
    flag_bits int32 [2, n] = (sign, is_infinity).  Returns
    ((x, y) mont affine planes, ok[n])."""
    n = x0.shape[-1]
    ox, oy, ook = _tiled(
        _k_g1_keyvalidate,
        (x0, flag_bits),
        [NL, 2],
        [NL, NL, 1],
        n,
    )
    return (ox, oy), ook[0] != 0


@JD.ops_jit
def g2_decompress_device(x0, x1, flag_bits):
    """Batched G2 decompression: x as PLAIN limbs, flag_bits int32 [2, n]
    = (sign, is_infinity).  Returns ((x, y) mont affine planes, ok[n])."""
    n = x0.shape[-1]
    ox0, ox1, oy0, oy1, ook = _tiled(
        _k_g2_decompress,
        (x0, x1, flag_bits),
        [NL] * 2 + [2],
        [NL] * 4 + [1],
        n,
    )
    return (ox0, ox1, oy0, oy1), ook[0] != 0
