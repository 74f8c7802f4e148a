"""Jacobian curve arithmetic for the pallas engine (G1/Fp and G2/Fp2).

Value-level, generic over the base field via a small ops table.  Points
are (X, Y, Z) jacobian tuples of field elements; the point at infinity is
tracked as an explicit boolean lane mask alongside the point (NO exact
zero-tests in the hot loops — masks propagate through selects).

The scalar multiplies implement the reference pool's per-job work
(random-linear-combination scalars on pubkeys/signatures, reference:
packages/beacon-node/src/chain/bls/multithread/worker.ts:52-87) as shared
windowed double-and-add loops with per-lane digit selects:
scalar_mul_bits_jac (2-bit windows, the legacy 64-bit randomizer path)
and scalar_mul_window_jac (w-bit windows, the 128-bit RLC path).
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Callable

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax

from ..crypto import fields as GT
from . import core as C
from . import fp2 as F2
from . import layout as LY

# ---------------------------------------------------------------------------
# Field ops tables
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class FieldOps:
    mul: Callable
    sqr: Callable
    add: Callable
    sub: Callable
    neg: Callable
    double: Callable
    mul_small: Callable
    select: Callable  # (mask[..., B], a, b)
    is_zero: Callable
    eq: Callable


FP_OPS = FieldOps(
    mul=C.mont_mul,
    sqr=C.mont_sqr,
    add=C.add,
    sub=C.sub,
    neg=C.neg,
    double=lambda a: C.mul_small(a, 2),
    mul_small=C.mul_small,
    select=C.select,
    is_zero=C.is_zero_modp,
    eq=C.eq_modp,
)

FP2_OPS = FieldOps(
    mul=F2.mul2,
    sqr=F2.sqr2,
    add=F2.add2,
    sub=F2.sub2,
    neg=F2.neg2,
    double=F2.double2,
    mul_small=F2.mul2_small,
    select=F2.select2,
    is_zero=F2.is_zero2,
    eq=F2.eq2,
)


def pick(cond, a, b):
    """Select between two pytrees of planes on one traced scalar
    condition (a rolled loop's step index picking its operands)."""
    return jax.tree_util.tree_map(lambda x, y: jnp.where(cond, x, y), a, b)


def select_pt(fo: FieldOps, mask, p, q):
    return tuple(fo.select(mask, a, b) for a, b in zip(p, q))


# ---------------------------------------------------------------------------
# Group law (a = 0 short Weierstrass)
# ---------------------------------------------------------------------------


def jac_dbl(fo: FieldOps, p):
    """2P, 2M + 5S.  Correctly maps infinity (Z=0) to infinity."""
    X, Y, Z = p
    A = fo.sqr(X)
    B = fo.sqr(Y)
    CC = fo.sqr(B)
    D = fo.double(fo.sub(fo.sub(fo.sqr(fo.add(X, B)), A), CC))
    E = fo.mul_small(A, 3)
    F = fo.sqr(E)
    X3 = fo.sub(F, fo.double(D))
    Y3 = fo.sub(fo.mul(E, fo.sub(D, X3)), fo.mul_small(CC, 8))
    Z3 = fo.double(fo.mul(Y, Z))
    return (X3, Y3, Z3)


def jac_add_full(fo: FieldOps, p, p_inf, q, q_inf):
    """Complete-ish addition: (P + Q, inf mask).

    Handles P=O, Q=O via the carried masks, P==Q via an exact-zero-test
    dispatch to doubling, and P==-Q producing infinity.  11M + 5S for the
    generic branch plus one doubling and two zero tests.
    """
    X1, Y1, Z1 = p
    X2, Y2, Z2 = q
    Z1Z1 = fo.sqr(Z1)
    Z2Z2 = fo.sqr(Z2)
    U1 = fo.mul(X1, Z2Z2)
    U2 = fo.mul(X2, Z1Z1)
    S1 = fo.mul(fo.mul(Y1, Z2), Z2Z2)
    S2 = fo.mul(fo.mul(Y2, Z1), Z1Z1)
    H = fo.sub(U2, U1)
    R = fo.sub(S2, S1)
    h_zero = fo.is_zero(H)
    r_zero = fo.is_zero(R)

    HH = fo.sqr(H)
    HHH = fo.mul(H, HH)
    V = fo.mul(U1, HH)
    X3 = fo.sub(fo.sub(fo.sqr(R), HHH), fo.double(V))
    Y3 = fo.sub(fo.mul(R, fo.sub(V, X3)), fo.mul(S1, HHH))
    Z3 = fo.mul(fo.mul(Z1, Z2), H)
    add_pt = (X3, Y3, Z3)

    dbl_pt = jac_dbl(fo, p)

    out = select_pt(fo, h_zero & r_zero, dbl_pt, add_pt)
    # infinity cases: P=O -> Q; Q=O -> P; P=-Q -> O
    out = select_pt(fo, q_inf, p, out)
    out = select_pt(fo, p_inf, q, out)
    out_inf = (p_inf & q_inf) | (h_zero & ~r_zero & ~p_inf & ~q_inf)
    return out, out_inf


def jac_add_mixed(fo: FieldOps, p, q_aff):
    """P + Q with Q affine (Z=1), 7M + 4S.  NO infinity/equal handling —
    callers guarantee P != O, P != +-Q (see scalar_mul bit loops)."""
    X1, Y1, Z1 = p
    X2, Y2 = q_aff
    Z1Z1 = fo.sqr(Z1)
    U2 = fo.mul(X2, Z1Z1)
    S2 = fo.mul(fo.mul(Y2, Z1), Z1Z1)
    H = fo.sub(U2, X1)
    HH = fo.sqr(H)
    I = fo.mul_small(HH, 4)
    J = fo.mul(H, I)
    rr = fo.double(fo.sub(S2, Y1))
    V = fo.mul(X1, I)
    X3 = fo.sub(fo.sub(fo.sqr(rr), J), fo.double(V))
    Y3 = fo.sub(fo.mul(rr, fo.sub(V, X3)), fo.double(fo.mul(Y1, J)))
    Z3 = fo.sub(fo.sub(fo.sqr(fo.add(Z1, H)), Z1Z1), HH)
    return (X3, Y3, Z3)


def jac_neg(fo: FieldOps, p):
    return (p[0], fo.neg(p[1]), p[2])


def jac_eq(fo: FieldOps, p, p_inf, q, q_inf):
    """Equality of jacobian points (cross-multiplied), inf-aware."""
    X1, Y1, Z1 = p
    X2, Y2, Z2 = q
    Z1Z1 = fo.sqr(Z1)
    Z2Z2 = fo.sqr(Z2)
    ex = fo.eq(fo.mul(X1, Z2Z2), fo.mul(X2, Z1Z1))
    ey = fo.eq(
        fo.mul(fo.mul(Y1, Z2), Z2Z2), fo.mul(fo.mul(Y2, Z1), Z1Z1)
    )
    both_fin = ~p_inf & ~q_inf
    return (p_inf & q_inf) | (both_fin & ex & ey)


# ---------------------------------------------------------------------------
# Scalar multiplication
# ---------------------------------------------------------------------------


def scalar_mul_bits_jac(fo: FieldOps, q, q_inf, get_bit, nbits: int):
    """k*Q for per-lane scalars given as MSB-first bit planes.

    2-bit WINDOWED double-and-add: nbits must be even; each of nbits/2
    iterations doubles twice and adds a table-selected multiple from
    {O, Q, 2Q, 3Q}.  Versus bit-at-a-time this halves the (expensive,
    always-computed-then-selected) additions — 64-bit randomizers drop
    from 64 to 32 full adds for three setup additions.

    q is jacobian (aggregate bases allowed).  get_bit(i) -> int32[..., B]
    bit plane (a ref read inside kernels, a dynamic slice under jit).
    Full additions (no mixed shortcut: Z_Q != 1 in general); the
    accumulator-infinity and T==table-entry cases are handled by mask
    selects — no exact zero tests inside the loop.  T == m*Q with the
    window digit d can only collide when m == d, which happens only
    while the accumulator is still infinity (handled by the t_inf mask:
    the digit's multiple is assigned directly).
    """
    assert nbits % 2 == 0, nbits
    # window table: 2Q, 3Q (Q itself is the input).  2Q = dbl, 3Q = 2Q+Q
    # (2Q == +-Q only for 5-torsion — impossible in a prime-order group).
    q2 = jac_dbl(fo, q)
    q3 = jac_add_mixed_or_full(fo, q2, q)

    def digit_multiple(d):
        """table[d] for d in {1,2,3} as masked selects (d==0 is handled
        by the outer bit-select keeping T)."""
        m = select_pt(fo, d == 2, q2, q)
        return select_pt(fo, d == 3, q3, m)

    # The accumulator-infinity mask is carried as int32, not bool: an i1
    # vector as an scf.for loop carry fails Mosaic legalization on real
    # TPUs ("failed to legalize operation 'scf.for'", layout-inconsistent
    # vector<8x128xi1> block argument).
    def body(i, st):
        (T, t_inf) = st
        T = jac_dbl(fo, jac_dbl(fo, T))
        hi = get_bit(2 * i)
        lo = get_bit(2 * i + 1)
        d = 2 * hi + lo
        add = digit_multiple(d)
        cand = jac_add_mixed_or_full(fo, T, add)
        cand = select_pt(fo, t_inf != 0, add, cand)
        nz = d != 0
        T = select_pt(fo, nz, cand, T)
        t_inf = t_inf & (~nz).astype(jnp.int32)
        return (T, t_inf)

    t0 = q  # placeholder value; masked by t_inf
    inf0 = jnp.ones(q_inf.shape, jnp.int32)
    T, t_inf = lax.fori_loop(0, nbits // 2, body, (t0, inf0))
    # k*O = O for infinity bases; k = 0 (all-zero bits) stays infinity.
    return T, (t_inf != 0) | q_inf


def scalar_mul_window_jac(
    fo: FieldOps, q, q_inf, get_digit, nbits: int, w: int = 4
):
    """k*Q for per-lane scalars read as MSB-first w-bit window digits.

    Generalizes scalar_mul_bits_jac to wider windows for the 128-bit
    RLC randomizers: nbits/w iterations of (w doublings + ONE
    always-computed-then-selected addition) against a precomputed
    multiple table {Q .. (2^w-1)Q}.  At w=4 a 128-bit scalar costs
    128 doublings + 32 window adds + 14 table adds — the add count of
    the old 64-bit path at twice the soundness (doublings are the
    cheap half: 2M+5S vs 11M+5S).

    get_digit(t) -> int32[..., B] window digit for window index t
    (MSB-first); the caller owns extraction — in-kernel that must be a
    traced shift over packed scalar words, never a dynamic sublane
    slice (dev/NOTES.md round-3 Mosaic rules).  The table is built with
    masked selects only (no gathers) and the accumulator-infinity mask
    is carried as int32, not bool (i1 fori_loop carries fail Mosaic
    legalization).

    Collision safety at the window add: after the leading doublings the
    accumulator is a·Q with a an even multiple >= 2^w > any digit d, and
    a < 2^nbits << r, so T == ±(d·Q) is impossible while the
    accumulator is live; the still-infinity case is handled by the
    t_inf mask (the digit's multiple is assigned directly).
    """
    assert nbits % w == 0, (nbits, w)
    assert w >= 1
    # multiple table: tbl[m-1] = m*Q for m in 1..2^w-1, built in ONE
    # rolled loop: unrolled, its 2^w-2 point operations were ~4/5 of the
    # kernel's code and cold compile (PERF.md, PR 21).  Even entries
    # double the half entry; odd entries add Q to the previous entry
    # (m*Q == ±Q needs m ≡ ±1 mod r — impossible for 2 <= m < 2^w);
    # each step computes both and its parity selects.
    n_tbl = (1 << w) - 1

    def entry(tbl, idx):
        m = tbl[0]
        for v in range(1, n_tbl):
            m = pick(idx == v, tbl[v], m)
        return m

    def build(i, tbl):
        m = i + 2
        new = pick(
            m % 2 == 0,
            jac_dbl(fo, entry(tbl, m // 2 - 1)),
            jac_add_mixed_or_full(fo, entry(tbl, m - 2), q),
        )
        return tuple(pick(m - 1 == k, new, tbl[k]) for k in range(n_tbl))

    tbl = lax.fori_loop(0, n_tbl - 1, build, (q,) * n_tbl)

    def digit_multiple(d):
        """tbl[d] for d in 1..2^w-1 as a masked-select chain (d == 0
        keeps the accumulator via the outer nz select)."""
        m = tbl[0]
        for v in range(2, 1 << w):
            m = select_pt(fo, d == v, tbl[v - 1], m)
        return m

    def body(t, st):
        (T, t_inf) = st
        T = lax.fori_loop(0, w, lambda _, T: jac_dbl(fo, T), T)
        d = get_digit(t)
        add = digit_multiple(d)
        cand = jac_add_mixed_or_full(fo, T, add)
        cand = select_pt(fo, t_inf != 0, add, cand)
        nz = d != 0
        T = select_pt(fo, nz, cand, T)
        t_inf = t_inf & (~nz).astype(jnp.int32)
        return (T, t_inf)

    t0 = q  # placeholder value; masked by t_inf
    inf0 = jnp.ones(q_inf.shape, jnp.int32)
    T, t_inf = lax.fori_loop(0, nbits // w, body, (t0, inf0))
    # k*O = O for infinity bases; k = 0 (all-zero digits) stays infinity.
    return T, (t_inf != 0) | q_inf


def jac_add_mixed_or_full(fo: FieldOps, p, q):
    """Addition P + Q used inside the bit loop: generic jacobian add
    WITHOUT the equal/infinity dispatch (callers rule those out).
    11M + 5S."""
    X1, Y1, Z1 = p
    X2, Y2, Z2 = q
    Z1Z1 = fo.sqr(Z1)
    Z2Z2 = fo.sqr(Z2)
    U1 = fo.mul(X1, Z2Z2)
    U2 = fo.mul(X2, Z1Z1)
    S1 = fo.mul(fo.mul(Y1, Z2), Z2Z2)
    S2 = fo.mul(fo.mul(Y2, Z1), Z1Z1)
    H = fo.sub(U2, U1)
    R = fo.sub(S2, S1)
    HH = fo.sqr(H)
    HHH = fo.mul(H, HH)
    V = fo.mul(U1, HH)
    X3 = fo.sub(fo.sub(fo.sqr(R), HHH), fo.double(V))
    Y3 = fo.sub(fo.mul(R, fo.sub(V, X3)), fo.mul(S1, HHH))
    Z3 = fo.mul(fo.mul(Z1, Z2), H)
    return (X3, Y3, Z3)


def static_bit_reader(k: int):
    """bit(pos) of a static scalar 0 <= k < 2^64 at a traced int32 bit
    position, read from two uint32 words by shifts and a select: no
    dynamic array indexing, so it lowers in Mosaic."""
    hi = jnp.uint32((k >> 32) & 0xFFFFFFFF)
    lo = jnp.uint32(k & 0xFFFFFFFF)

    def bit(pos):
        p = pos.astype(jnp.uint32)
        b_hi = (hi >> (p - jnp.uint32(32))) & jnp.uint32(1)
        b_lo = (lo >> p) & jnp.uint32(1)
        return jnp.where(pos >= 32, b_hi, b_lo)

    return bit


def scalar_mul_static(fo: FieldOps, q_aff, k: int):
    """k*Q for a STATIC positive scalar (< 2^64), Q affine and not O.

    One rolled fori_loop: always double, conditionally (lax.cond on the
    statically-known bit) mixed-add — the sparse BLS parameter takes the
    add branch 5 times.  T == +-Q never occurs at an add (an add always
    follows a doubling, so the accumulator multiple is even and >= 2).
    """
    assert 2 <= k < 1 << 64
    one = _one_plane_like(fo, q_aff[0])
    T = (q_aff[0], q_aff[1], one)
    nbits = k.bit_length() - 1
    bit_at = static_bit_reader(k)

    def body(i, T):
        T = jac_dbl(fo, T)
        bit = bit_at(jnp.int32(nbits - 1) - i)
        return lax.cond(
            bit != 0, lambda t: jac_add_mixed(fo, t, q_aff), lambda t: t, T
        )

    return lax.fori_loop(0, nbits, body, T)


def _one_plane_like(fo: FieldOps, x):
    if fo is FP2_OPS:
        leaf = x[0]
        one = jnp.broadcast_to(C.const_plane(LY.MONT_ONE, leaf), leaf.shape)
        return (one, jnp.zeros_like(leaf))
    return jnp.broadcast_to(C.const_plane(LY.MONT_ONE, x), x.shape)


def zero_pt(fo: FieldOps, like):
    """A canonical representation of O: (1, 1, 0) in Montgomery form."""
    one = _one_plane_like(fo, like)
    if fo is FP2_OPS:
        zero = (jnp.zeros_like(one[0]), jnp.zeros_like(one[0]))
    else:
        zero = jnp.zeros_like(one)
    return (one, one, zero)


# ---------------------------------------------------------------------------
# Reductions
# ---------------------------------------------------------------------------


def sum_points_axis0(fo: FieldOps, pts, inf):
    """Tree-sum of points over a leading axis: [K, ...] -> [...]."""
    k = inf.shape[0]
    while k > 1:
        half = (k + 1) // 2
        lo = jax.tree_util.tree_map(lambda a: a[:half], (pts, inf))
        hi = jax.tree_util.tree_map(lambda a: a[half:k], (pts, inf))
        n = k - half
        lo_pts, lo_inf = lo
        hi_pts, hi_inf = hi
        head = jax.tree_util.tree_map(lambda a: a[:n], lo_pts)
        head_inf = lo_inf[:n]
        s, s_inf = jac_add_full(fo, head, head_inf, hi_pts, hi_inf)
        if n == half:  # even width: no unpaired middle element
            pts, inf = s, s_inf
        else:
            pts = jax.tree_util.tree_map(
                lambda a, b: jnp.concatenate([a, b[n:half]], axis=0),
                s,
                lo_pts,
            )
            inf = jnp.concatenate([s_inf, lo_inf[n:half]], axis=0)
        k = half
    return (
        jax.tree_util.tree_map(lambda a: a[0], pts),
        inf[0],
    )


def sum_points_lanes(fo: FieldOps, pts, inf):
    """Butterfly-sum over the LANE (batch, last) axis -> FULL width.

    Uses full-width lane rolls instead of halving lane slices: narrow or
    offset lane slices produce Mosaic layouts that later sublane pads
    reject ("result/input offset mismatch on non-concat dimension"), and
    on a 128-lane VPU a half-width op costs the same as a full-width one
    anyway.  log2(B) rounds of jac_add_full in ONE rolled loop (a traced
    shift), so the compiled program holds one addition, not log2(B);
    EVERY lane ends up holding the total (read any one).  B must be a
    power of two (the pipeline's lane tile BT = 128 is).  Runs inside a
    pallas kernel or under plain jit (lane_roll lowers either way).
    """
    b = inf.shape[-1]
    assert b & (b - 1) == 0, f"lane width {b} must be a power of two"

    def body(r, st):
        pts, inf_i = st
        shift = jnp.right_shift(jnp.int32(b // 2), r)
        other = jax.tree_util.tree_map(
            lambda a: lane_roll(a, shift), pts
        )
        # lift 1-D lane masks to 2-D for the rotate (TPU prefers >= 2-D)
        other_inf = lane_roll(inf_i[None, :], shift)[0]
        pts, s_inf = jac_add_full(
            fo, pts, inf_i != 0, other, other_inf != 0
        )
        return pts, s_inf.astype(jnp.int32)

    pts, inf_i = lax.fori_loop(
        0, b.bit_length() - 1, body, (pts, inf.astype(jnp.int32))
    )
    return pts, inf_i != 0


def lane_roll(a, shift):
    """jnp.roll over the lane (last) axis that also lowers inside a
    pallas kernel (pltpu.roll; the shift may be traced)."""
    from jax.experimental.pallas import tpu as pltpu

    return pltpu.roll(a, shift, a.ndim - 1)


def seg_sum_lanes(fo: FieldOps, pts, dead, group, carry):
    """Segmented inclusive jacobian prefix-scan over the lane axis.

    `group` is int32[1, n], nondecreasing (lane-contiguous segments);
    `dead` int32[1, n] lanes count as infinity.  log2(n) rounds of
    jac_add_full in one rolled loop; the LAST lane of each segment
    holds its total.  `carry` = (planes, ok bool[1, n]) is one first
    round of the same loop: lanes where ok add the carry planes (a
    running total handed in from a previous lane tile).  Returns
    (planes, inf bool[n]).  n must be a power of two.
    """
    n = group.shape[-1]
    assert n & (n - 1) == 0, f"lane width {n} must be a power of two"
    lane = lax.broadcasted_iota(jnp.int32, group.shape, group.ndim - 1)

    def body(r, st):
        pts, inf_i = st
        s = jnp.left_shift(jnp.int32(1), jnp.maximum(r, 0))
        prev = jax.tree_util.tree_map(lambda a: lane_roll(a, s), pts)
        # a lane adds its predecessor s lanes back only within its own
        # segment (and not across the wrap-around)
        skip = (lane_roll(lane, s) != lane - s) | (
            lane_roll(group, s) != group
        ) | (lane_roll(inf_i, s) != 0)
        # round -1 adds the carry instead (an int32 select: Mosaic does
        # not select i1 vectors)
        prev = pick(r < 0, carry[0], prev)
        skip = jnp.where(
            r < 0, (~carry[1]).astype(jnp.int32), skip.astype(jnp.int32)
        ) != 0
        pts, t_inf = jac_add_full(fo, pts, inf_i[0] != 0, prev, skip[0])
        return pts, t_inf[None, :].astype(jnp.int32)

    pts, inf_i = lax.fori_loop(-1, n.bit_length() - 1, body, (pts, dead))
    return pts, inf_i[0] != 0


# ---------------------------------------------------------------------------
# psi endomorphism + G2 subgroup check (Scott's test)
# ---------------------------------------------------------------------------

_U = (0, 1)
_CX_INT = GT.fp2_mul(_U, GT.fp2_pow(GT.XI, 2 * (GT.P - 1) // 3))
_CY_INT = GT.fp2_mul(_U, GT.fp2_pow(GT.XI, (GT.P - 1) // 2))
_CX_K = F2.const2(_CX_INT)
_CY_K = F2.const2(_CY_INT)
_X_ABS = -GT.X_PARAM


def g2_psi(q):
    """psi on jacobian twist coordinates."""
    X, Y, Z = q
    return (
        F2.mul2_const(F2.conj2(X), _CX_K),
        F2.mul2_const(F2.conj2(Y), _CY_K),
        F2.conj2(Z),
    )


def g2_subgroup_check(q_aff, q_inf):
    """Q in G2 <=> psi(Q) == [x]Q = -[|x|]Q.  O is in the subgroup."""
    one = _one_plane_like(FP2_OPS, q_aff[0])
    q_jac = (q_aff[0], q_aff[1], one)
    zq = scalar_mul_static(FP2_OPS, q_aff, _X_ABS)
    lhs = g2_psi(q_jac)
    return jac_eq(FP2_OPS, lhs, q_inf, jac_neg(FP2_OPS, zq), q_inf) | q_inf
