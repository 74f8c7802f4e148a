"""The pallas verification pipeline — BLS batch verification on device.

This is the production engine behind `bls/verifier.py`, replacing the
round-1 XLA einsum path and standing in for blst inside the reference's
worker pool (packages/beacon-node/src/chain/bls/multithread/worker.ts:
30-106; batch semantics of maybeBatch.ts:16-27).

Pipeline for one job of N padded signature sets (batch axis = vector
lanes, N a multiple of the 128-lane tile; all kernels are lane-TILED:
one [rows, 128] kernel body serves every bucket size, and each bucket's
grid is its own compiled program):

    [gather]   pubkey table rows -> per-set pubkey (aggregate sets tree-
               add K rows in a (lane, K)-chunked grid kernel)
    k_g1_rpk   r_i * pk_i          (per-lane 128-bit scalars, 4-bit
               windowed double-and-add — curve.scalar_mul_window_jac)
    k_g2_rsig  r_i * sig_i + psi subgroup check of sig_i
    k_sum_g2   sum_i r_i sig_i over lanes (grid-accumulated)
    k_affine   -> ONE affine point (the single Fp2 inversion in the whole
               pipeline; jacobian-P line scaling kills the rest)
    k_miller   N set pairs (rpk_i, H_i) + 1 aggregate pair (-G1, A)
    k_prod     grid-accumulated lane product
    k_final    * aggregate pair -> final exponentiation -> is_one
               (the per-set path shares it)

The verifier dispatches these stages un-jitted: each pallas stage and
each glue jit is its own program, compiled once per process and shared
by every entry point and K-bucket that uses it.  Each pallas stage is
also exported once and loaded by later processes without a trace
(kernels/launch.py).
"""

from __future__ import annotations

import numpy as np

import jax
from jax import lax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from ..crypto import curves as GC
from ..crypto import fields as GT
from . import core as C
from . import curve as CV
from . import fp2 as F2
from . import ingest as IG
from . import jit_dispatch as JD
from . import launch as LA
from . import layout as LY
from . import pairing as KP
from . import tower as TW

NL = LY.NL
# RLC randomizer width: 128-bit scalars bound the batch-forgery
# probability at ~2^-127 (ops/bls_kernels.RLC_RAND_BITS); the 4-bit
# window keeps the scalar-mul add count at the old 64-bit path's level.
RAND_BITS = 128
RAND_WORDS = RAND_BITS // 32  # packed int32[RAND_WORDS, N] scalar rows
WINDOW = 4  # window width; must divide 32 so digits never straddle words
BT = 128  # lane tile: job sizes must be multiples of this


# Baked constants (host-side numpy, python ints)
_G1X = LY.const_mont(GC.G1_GEN[0])
_G1Y = LY.const_mont(GC.G1_GEN[1])
_NEG_G1Y = LY.const_mont(GT.fp_neg(GC.G1_GEN[1]))
_G2X = (LY.const_mont(GC.G2_GEN[0][0]), LY.const_mont(GC.G2_GEN[0][1]))
_G2Y = (LY.const_mont(GC.G2_GEN[1][0]), LY.const_mont(GC.G2_GEN[1][1]))
_ONE = LY.MONT_ONE


def _bcast(c, b):
    return jnp.broadcast_to(
        jnp.asarray(np.asarray(c, np.int32))[:, None], (NL, b)
    )


def _sds(shape):
    return jax.ShapeDtypeStruct(shape, jnp.int32)


def _tiled(kernel, ins, in_rows, out_rows, n):
    """Lane-tiled pallas_call: each operand is [rows, n], blocked to
    [rows, BT].  Launches go through the kernels/launch.py cache — a
    wrapper rebuilt per call re-traces the kernel body every time."""
    return LA.tiled(kernel, ins, in_rows, out_rows, n, BT)


# -- pairing-op tally --------------------------------------------------------
#
# The explicit kernel-call counter behind the RLC acceptance invariant:
# an N-set batch job dispatches exactly N+1 Miller-loop lanes of real
# work and ONE final exponentiation; the per-set retry path pays 2N
# Miller lanes and N final exps.  Counts are derived from static shapes
# at dispatch time, so they tick on the DIRECT call path (tests, CPU
# backend, microbenches).  Inside a whole-program export
# (export_cache.load_or_export) the pipeline body runs once at trace
# time only — tally deltas there describe one traced job, not traffic.

from collections import Counter as _Counter

PIPELINE_TALLY: "_Counter[str]" = _Counter()


def _tally(op: str, n: int) -> None:
    PIPELINE_TALLY[op] += n


def pipeline_tally_snapshot() -> dict:
    return dict(PIPELINE_TALLY)


# ---------------------------------------------------------------------------
# Kernels
# ---------------------------------------------------------------------------


_R2_LIMBS = [int(v) for v in LY.MONT_R2]


def _k_mont8(a0, a1, a2, a3, a4, a5, a6, a7, *outs):
    """Plain-limb planes -> Montgomery form (x -> x*R mod p), 8 at a time.

    The device side of ingest: hosts ship raw 12-bit limb splits of wire
    bytes; one shared-constant product per plane converts them.
    """
    for ref, r in zip(outs, (a0, a1, a2, a3, a4, a5, a6, a7)):
        ref[...] = C.redc(C.mul_cols_shared(r[...], _R2_LIMBS, LY.NC))


def _to_mont8(planes, n):
    return _tiled(_k_mont8, planes, [NL] * 8, [NL] * 8, n)


def _word_digit(rwords, t):
    """Per-lane WINDOW-bit digit (MSB-first window index t) of packed
    big-endian scalar words int32[RAND_WORDS, B].

    Traced vector shift instead of a dynamic sublane slice: indexing a
    bit-plane array with pl.ds lowers to layout-mismatched rotate/select
    chains that crash the Mosaic pass on real TPUs.  The static row
    reads w[k] are constant sublane indices (fine); the word pick is a
    masked-select chain.  WINDOW divides 32, so a digit never straddles
    two words and one shift+mask extracts it whole.
    """
    w = rwords[...].astype(jnp.uint32)  # [RAND_WORDS, B]
    # LSB-first bit offset of the digit: p in {0, WINDOW, .., RAND_BITS-WINDOW}
    p = jnp.uint32(RAND_BITS - WINDOW) - jnp.uint32(WINDOW) * t.astype(
        jnp.uint32
    )
    wi = p >> jnp.uint32(5)  # word index from the LSB end
    sh = p & jnp.uint32(31)
    word = w[RAND_WORDS - 1]  # wi == 0: least-significant word
    for k in range(1, RAND_WORDS):
        word = jnp.where(wi == jnp.uint32(k), w[RAND_WORDS - 1 - k], word)
    mask = jnp.uint32((1 << WINDOW) - 1)
    return ((word >> sh) & mask).astype(jnp.int32)


def _k_g1_rpk(px, py, pz, inf, rwords, ox, oy, oz, oinf):
    p = (px[...], py[...], pz[...])
    q_inf = inf[...][0] != 0

    def gd(t):
        return _word_digit(rwords, t)

    (X, Y, Z), t_inf = CV.scalar_mul_window_jac(
        CV.FP_OPS, p, q_inf, gd, RAND_BITS, WINDOW
    )
    ox[...], oy[...], oz[...] = X, Y, Z
    oinf[...] = t_inf[None, :].astype(jnp.int32)


def _k_g2_rsig_sub(sx0, sx1, sy0, sy1, inf, rwords,
                   ox0, ox1, oy0, oy1, oz0, oz1, oinf, osub):
    q_aff = ((sx0[...], sx1[...]), (sy0[...], sy1[...]))
    q_inf = inf[...][0] != 0
    one2 = CV._one_plane_like(CV.FP2_OPS, q_aff[0])
    q_jac = (q_aff[0], q_aff[1], one2)

    def gd(t):
        return _word_digit(rwords, t)

    (X, Y, Z), t_inf = CV.scalar_mul_window_jac(
        CV.FP2_OPS, q_jac, q_inf, gd, RAND_BITS, WINDOW
    )
    sub = CV.g2_subgroup_check(q_aff, q_inf)
    ox0[...], ox1[...] = X
    oy0[...], oy1[...] = Y
    oz0[...], oz1[...] = Z
    oinf[...] = t_inf[None, :].astype(jnp.int32)
    osub[...] = sub[None, :].astype(jnp.int32)


def _k_sub_only(sx0, sx1, sy0, sy1, inf, osub):
    q_aff = ((sx0[...], sx1[...]), (sy0[...], sy1[...]))
    q_inf = inf[...][0] != 0
    osub[...] = CV.g2_subgroup_check(q_aff, q_inf)[None, :].astype(jnp.int32)


def _k_sum_g2(x0, x1, y0, y1, z0, z1, inf,
              ax0, ax1, ay0, ay1, az0, az1, ainf):
    """Grid-accumulated jacobian sum over lanes, FULL [NL, BT] width.

    Tiles accumulate lane-wise (elementwise jac_add_full) to 128 partial
    sums; the cross-lane butterfly is its own one-tile kernel
    (_k_sum_lanes).  As plain XLA the same math compiled for minutes
    with tens of GB on the TPU compiler (PERF.md, PR 21).
    """
    i = pl.program_id(0)
    pts = ((x0[...], x1[...]), (y0[...], y1[...]), (z0[...], z1[...]))
    infv = inf[...][0] != 0  # [BT] lane mask

    @pl.when(i == 0)
    def _():
        (ax0[...], ax1[...]) = pts[0]
        (ay0[...], ay1[...]) = pts[1]
        (az0[...], az1[...]) = pts[2]
        ainf[...] = infv[None, :].astype(jnp.int32)

    @pl.when(i > 0)
    def _():
        acc = (
            (ax0[...], ax1[...]),
            (ay0[...], ay1[...]),
            (az0[...], az1[...]),
        )
        acc_inf = ainf[...][0] != 0
        t, t_inf = CV.jac_add_full(CV.FP2_OPS, acc, acc_inf, pts, infv)
        (ax0[...], ax1[...]) = t[0]
        (ay0[...], ay1[...]) = t[1]
        (az0[...], az1[...]) = t[2]
        ainf[...] = t_inf[None, :].astype(jnp.int32)


def _k_affine_g2(x0, x1, y0, y1, z0, z1, inf, ax0, ax1, ay0, ay1, ainf):
    """Jacobian -> affine at full width (all lanes hold the aggregate);
    infinity lanes get the generator."""
    pt = ((x0[...], x1[...]), (y0[...], y1[...]), (z0[...], z1[...]))
    (ax, ay), aff_inf = KP.to_affine_g2(pt)
    a_inf = (inf[...][0] != 0) | aff_inf
    gx = (C.const_plane(_G2X[0], ax[0]), C.const_plane(_G2X[1], ax[1]))
    gy = (C.const_plane(_G2Y[0], ay[0]), C.const_plane(_G2Y[1], ay[1]))
    ax = F2.select2(~a_inf, ax, gx)
    ay = F2.select2(~a_inf, ay, gy)
    ax0[...], ax1[...] = ax
    ay0[...], ay1[...] = ay
    ainf[...] = a_inf[None, :].astype(jnp.int32)


def _k_agg_pk(gx, gy, mask, ox, oy, oz, oinf):
    """Pubkey aggregation: grid (lane tiles, K chunks), accumulating the
    jacobian sum over the K dimension (innermost grid axis)."""
    k = pl.program_id(1)
    x, y, m = gx[...], gy[...], mask[...]
    one = CV._one_plane_like(CV.FP_OPS, x[0])
    ones = jnp.broadcast_to(one, x.shape)
    s, s_inf = CV.sum_points_axis0(CV.FP_OPS, (x, y, ones), m == 0)

    @pl.when(k == 0)
    def _():
        ox[...], oy[...], oz[...] = s
        oinf[...] = s_inf[None, :].astype(jnp.int32)

    @pl.when(k > 0)
    def _():
        acc = (ox[...], oy[...], oz[...])
        acc_inf = oinf[...][0] != 0
        t, t_inf = CV.jac_add_full(CV.FP_OPS, acc, acc_inf, s, s_inf)
        ox[...], oy[...], oz[...] = t
        oinf[...] = t_inf[None, :].astype(jnp.int32)


def _k_miller(px, py, pz, qx0, qx1, qy0, qy1, *fout):
    p = (px[...], py[...], pz[...])
    q = ((qx0[...], qx1[...]), (qy0[...], qy1[...]))
    f = KP.miller_loop(p, q)
    for ref, leaf in zip(fout, jax.tree_util.tree_leaves(f)):
        ref[...] = leaf


def _unflatten_f12(leaves):
    l = list(leaves)
    return (
        ((l[0], l[1]), (l[2], l[3]), (l[4], l[5])),
        ((l[6], l[7]), (l[8], l[9]), (l[10], l[11])),
    )


def _k_prod(valid, *f_refs):
    """Grid-accumulated product of valid lanes, FULL [NL, BT] width.

    Tiles multiply lane-wise to 128 partial products; the cross-lane
    butterfly is its own one-tile kernel (_k_prod_lanes — same
    rationale as _k_sum_g2).
    """
    i = pl.program_id(0)
    fN = _unflatten_f12([r[...] for r in f_refs[:12]])
    outs = f_refs[12:]
    v = valid[...][0] != 0  # [BT] lane mask
    one = TW.one12(fN[0][0][0])
    tile = TW.select12(v, fN, one)

    @pl.when(i == 0)
    def _():
        for ref, leaf in zip(outs, jax.tree_util.tree_leaves(tile)):
            ref[...] = leaf

    @pl.when(i > 0)
    def _():
        acc = _unflatten_f12([r[...] for r in outs])
        t = TW.mul12(acc, tile)
        for ref, leaf in zip(outs, jax.tree_util.tree_leaves(t)):
            ref[...] = leaf


def _k_sum_lanes(x0, x1, y0, y1, z0, z1, inf,
                 ox0, ox1, oy0, oy1, oz0, oz1, oinf):
    """Cross-lane jacobian sum of _k_sum_g2's 128 partials (one tile):
    every lane ends holding the total."""
    pts = ((x0[...], x1[...]), (y0[...], y1[...]), (z0[...], z1[...]))
    (X, Y, Z), t_inf = CV.sum_points_lanes(
        CV.FP2_OPS, pts, inf[...][0] != 0
    )
    ox0[...], ox1[...] = X
    oy0[...], oy1[...] = Y
    oz0[...], oz1[...] = Z
    oinf[...] = t_inf[None, :].astype(jnp.int32)


def _k_prod_lanes(*refs):
    """Cross-lane product of _k_prod's 128 partials (one tile): every
    lane ends holding the product."""
    f = KP.product12_lanes(
        _unflatten_f12([r[...] for r in refs[:12]])
    )
    for ref, leaf in zip(refs[12:], jax.tree_util.tree_leaves(f)):
        ref[...] = leaf


def _seg_scan_tile(fo, pts, dead, group, carry, carry_inf, carry_grp):
    """One lane tile of a segmented jacobian scan run over a sequential
    grid.  The previous tile's last lane, rolled onto lane 0 (`carry`,
    `carry_inf`, `carry_grp`), adds into lane 0 when lane 0 continues
    its segment; the in-tile scan then carries it to the rest of that
    segment.  Returns (planes, inf int32[1, bt], next carry triple)."""
    lane = lax.broadcasted_iota(jnp.int32, group.shape, 1)
    ok = (pl.program_id(0) > 0) & (lane == 0) & (carry_grp == group) & (
        carry_inf == 0
    )
    pts, inf = CV.seg_sum_lanes(fo, pts, dead, group, carry=(carry, ok))
    inf = inf[None, :].astype(jnp.int32)
    roll1 = lambda a: CV.lane_roll(a, 1)  # noqa: E731
    nxt = (jax.tree_util.tree_map(roll1, pts), roll1(inf), roll1(group))
    return pts, inf, nxt


def _k_seg_sum_g1(px, py, pz, dead, group, ox, oy, oz, oinf,
                  cx, cy, cz, cinf, cgrp):
    (X, Y, Z), inf, (c, c_inf, c_grp) = _seg_scan_tile(
        CV.FP_OPS, (px[...], py[...], pz[...]), dead[...], group[...],
        (cx[...], cy[...], cz[...]), cinf[...], cgrp[...],
    )
    ox[...], oy[...], oz[...] = X, Y, Z
    oinf[...] = inf
    cx[...], cy[...], cz[...] = c
    cinf[...], cgrp[...] = c_inf, c_grp


def _k_seg_sum_g2(x0, x1, y0, y1, dead, group,
                  ox0, ox1, oy0, oy1, oz0, oz1, oinf,
                  cx0, cx1, cy0, cy1, cz0, cz1, cinf, cgrp):
    x, y = (x0[...], x1[...]), (y0[...], y1[...])
    carry = (
        (cx0[...], cx1[...]), (cy0[...], cy1[...]), (cz0[...], cz1[...])
    )
    (X, Y, Z), inf, ((CX, CY, CZ), c_inf, c_grp) = _seg_scan_tile(
        CV.FP2_OPS, (x, y, CV._one_plane_like(CV.FP2_OPS, x)),
        dead[...], group[...], carry, cinf[...], cgrp[...],
    )
    ox0[...], ox1[...] = X
    oy0[...], oy1[...] = Y
    oz0[...], oz1[...] = Z
    oinf[...] = inf
    cx0[...], cx1[...] = CX
    cy0[...], cy1[...] = CY
    cz0[...], cz1[...] = CZ
    cinf[...], cgrp[...] = c_inf, c_grp


def _k_final(keep, *f_refs):
    """fa * (fb where keep, else 1) -> final exp -> is-one, per lane.

    ONE kernel for both verdict paths, so a process compiles the final
    exponentiation (the costliest stage to compile) once per lane width:
    the batch tail passes (prod, aggregate pair, keep = aggregate not at
    infinity; every lane carries the same values, the host reads lane 0)
    and the per-set path passes (f1, f2, keep = live; dead lanes are
    masked by the caller).
    """
    fa = _unflatten_f12([r[...] for r in f_refs[:12]])
    fb = _unflatten_f12([r[...] for r in f_refs[12:24]])
    ok_ref = f_refs[24]
    k = keep[...][0] != 0  # [BT] lane mask
    one = TW.one12(fb[0][0][0])
    f = TW.mul12(fa, TW.select12(k, fb, one))
    fe = KP.final_exponentiation(f)
    ok_ref[...] = TW.is_one12(fe)[None, :].astype(jnp.int32)


# ---------------------------------------------------------------------------
# Host-jitted pipeline
# ---------------------------------------------------------------------------


def _gather_pk(table_x, table_y, idx, kmask):
    """Per-set pubkey from the table: (jacobian planes, inf mask).

    table planes: [NL, V]; idx: [N, K] int32; kmask: [N, K] int32.
    """
    n, k = idx.shape
    flat = idx.reshape(-1)
    gx = jnp.take(table_x, flat, axis=1).reshape(NL, n, k)
    gy = jnp.take(table_y, flat, axis=1).reshape(NL, n, k)
    if k == 1:
        px, py = gx[:, :, 0], gy[:, :, 0]
        pz = _bcast(_ONE, n)
        return (px, py, pz), jnp.zeros((n,), bool)
    gx = jnp.moveaxis(gx, 2, 0)  # [K, NL, N]
    gy = jnp.moveaxis(gy, 2, 0)
    m = jnp.moveaxis(kmask, 1, 0)  # [K, N]
    kc = min(k, 32)
    ox, oy, oz, oinf = LA.cached(
        ("agg_pk", n, k, kc),
        lambda: pl.pallas_call(
            _k_agg_pk,
            out_shape=[_sds((NL, n))] * 3 + [_sds((1, n))],
            grid=(n // BT, k // kc),
            in_specs=[
                pl.BlockSpec((kc, NL, BT), lambda i, k_: (k_, 0, i)),
                pl.BlockSpec((kc, NL, BT), lambda i, k_: (k_, 0, i)),
                pl.BlockSpec((kc, BT), lambda i, k_: (k_, i)),
            ],
            out_specs=[pl.BlockSpec((NL, BT), lambda i, k_: (0, i))] * 3
            + [pl.BlockSpec((1, BT), lambda i, k_: (0, i))],
            interpret=LA.interpret(),
        ),
        gx, gy, m,
    )
    return (ox, oy, oz), (oinf[0] != 0)


def verify_batch_device(
    table_x, table_y, idx, kmask,
    msg_x0, msg_x1, msg_y0, msg_y1,
    sig_x0, sig_x1, sig_y0, sig_y1,
    sig_inf, rwords, valid,
):
    """Full RLC batch verification of N padded sets on device.

    NOT wrapped in one outer jit on purpose: each pallas stage compiles
    as its OWN program (the monolithic graph OOM-kills the AOT compile
    helper at ~30 min), with the elementwise glue in small jits below.

    Returns (batch_ok: bool[], sig_sub_ok: bool[N]).  Padding/invalid
    lanes are excluded via `valid`; sets whose (aggregate) pubkey or
    signature is the point at infinity fail the batch.

    msg/sig planes arrive as PLAIN limbs (the ingest wire split) and are
    converted to Montgomery form on device; the pubkey table is stored in
    Montgomery form (converted once at registration).  `rwords` is the
    packed int32[RAND_WORDS, N] big-endian 128-bit randomizer layout of
    make_rand_words.
    """
    n = valid.shape[0]
    msg_x0, msg_x1, msg_y0, msg_y1, sig_x0, sig_x1, sig_y0, sig_y1 = _to_mont8(
        (msg_x0, msg_x1, msg_y0, msg_y1, sig_x0, sig_x1, sig_y0, sig_y1), n
    )
    return _batch_core(
        table_x, table_y, idx, kmask,
        (msg_x0, msg_x1, msg_y0, msg_y1),
        (sig_x0, sig_x1, sig_y0, sig_y1),
        (sig_inf != 0), rwords, valid,
    )


def verify_batch_device_wire(
    table_x, table_y, idx, kmask,
    msg_x0, msg_x1, msg_y0, msg_y1,
    sig_x0, sig_x1, sig_flags,
    rwords, valid,
):
    """Batch verification from WIRE signatures: sig arrives as the
    compressed x coordinate (plain limbs) + (sign, infinity) flag bits
    int32[2, N]; decompression (one Fp2 sqrt chain) runs on device.
    An undecodable signature (x off-curve) fails the batch like an
    infinity signature -> callers fall back to per-set verdicts.
    """
    n = valid.shape[0]
    msg_x0, msg_x1, msg_y0, msg_y1 = _tiled(
        _k_mont4, (msg_x0, msg_x1, msg_y0, msg_y1), [NL] * 4, [NL] * 4, n
    )
    (sx0, sx1, sy0, sy1), dec_ok = _decompress_sig(sig_x0, sig_x1, sig_flags, n)
    sig_bad = (sig_flags[1] != 0) | ~dec_ok
    return _batch_core(
        table_x, table_y, idx, kmask,
        (msg_x0, msg_x1, msg_y0, msg_y1),
        (sx0, sx1, sy0, sy1),
        sig_bad, rwords, valid,
    )


def _decompress_sig(sig_x0, sig_x1, sig_flags, n):
    out = _tiled(
        IG._k_g2_decompress,
        (sig_x0, sig_x1, sig_flags),
        [NL, NL, 2],
        [NL] * 4 + [1],
        n,
    )
    return out[:4], out[4][0] != 0


def _k_mont4(a0, a1, a2, a3, *outs):
    """Plain-limb planes -> Montgomery form, 4 at a time."""
    for ref, r in zip(outs, (a0, a1, a2, a3)):
        ref[...] = C.redc(C.mul_cols_shared(r[...], _R2_LIMBS, LY.NC))


# -- jitted elementwise glue (kept OUT of the pallas stages so each
# pallas kernel stays its own bounded compile unit) -------------------------


@JD.ops_jit
def _j_substitute(live, pk0, pk1, pk2, sx0, sx1, sy0, sy1):
    """Dead lanes -> generator points (keeps every lane on-curve)."""
    n = live.shape[0]
    px = C.select(live, pk0, _bcast(_G1X, n))
    py = C.select(live, pk1, _bcast(_G1Y, n))
    pz = C.select(live, pk2, _bcast(_ONE, n))
    sx = F2.select2(
        live, (sx0, sx1), (_bcast(_G2X[0], n), _bcast(_G2X[1], n))
    )
    sy = F2.select2(
        live, (sy0, sy1), (_bcast(_G2Y[0], n), _bcast(_G2Y[1], n))
    )
    return px, py, pz, sx, sy


@JD.ops_jit
def _j_batch_verdict(ok2, sub, live, pk_inf, sig_bad, valid):
    sub_ok = (sub[0] != 0) | ~live
    batch_ok = (
        (ok2[0, 0] != 0)
        & jnp.all(sub_ok)
        & ~jnp.any(pk_inf & (valid != 0))
        & ~jnp.any(sig_bad & (valid != 0))
    )
    return batch_ok, sub_ok


def _batch_local(
    table_x, table_y, idx, kmask, msgM, sigM, sig_bad, rwords, valid
):
    """The per-shard slice of the batch pipeline: everything DATA-
    PARALLEL over the sets axis.  Returns lane-replicated partials ready
    for cross-device combination:

        fprod — 12 Fp12 planes, the product of this shard's set pairs,
        jsum  — (6 planes + inf row) jacobian sum of r_i*sig_i,
        sub   — per-set subgroup-check row [1, n_local],
        live / pk_inf — per-set masks.
    """
    n = valid.shape[0]
    msg_x0, msg_x1, msg_y0, msg_y1 = msgM
    sig_x0, sig_x1, sig_y0, sig_y1 = sigM
    (pk, pk_inf) = _gather_pk(table_x, table_y, idx, kmask)
    live = (valid != 0) & ~pk_inf & ~sig_bad

    # Substitute generators for dead lanes so every lane stays on-curve.
    px, py, pz, sx, sy = _j_substitute(
        live, pk[0], pk[1], pk[2], sig_x0, sig_x1, sig_y0, sig_y1
    )

    live_i = live[None, :].astype(jnp.int32)
    zero_row = jnp.zeros((1, n), jnp.int32)

    # r_i * pk_i
    rx, ry, rz, _rinf = _tiled(
        _k_g1_rpk,
        (px, py, pz, zero_row, rwords),
        [NL, NL, NL, 1, RAND_WORDS],
        [NL, NL, NL, 1],
        n,
    )

    # r_i * sig_i + subgroup checks
    sx0r, sx1r, sy0r, sy1r, sz0r, sz1r, rsinf, sub = _tiled(
        _k_g2_rsig_sub,
        (sx[0], sx[1], sy[0], sy[1], zero_row, rwords),
        [NL, NL, NL, NL, 1, RAND_WORDS],
        [NL] * 6 + [1, 1],
        n,
    )

    # aggregate signature point: dead lanes excluded from the sum
    excl = (~live)[None, :].astype(jnp.int32) | rsinf
    px0, px1, py0, py1, pz0, pz1, pinf = _sum_g2(
        sx0r, sx1r, sy0r, sy1r, sz0r, sz1r, excl, n
    )
    # cross-lane butterfly: 128 partials -> total in every lane
    jsum = tuple(_tiled(
        _k_sum_lanes, (px0, px1, py0, py1, pz0, pz1, pinf),
        [NL] * 6 + [1], [NL] * 6 + [1], BT,
    ))

    # Miller: N set pairs
    _tally("miller_pair", n)
    fN = _tiled(
        _k_miller,
        (rx, ry, rz, msg_x0, msg_x1, msg_y0, msg_y1),
        [NL] * 7,
        [NL] * 12,
        n,
    )
    fpartial = _prod(fN, live_i, n)
    fprod = tuple(
        _tiled(_k_prod_lanes, tuple(fpartial), [NL] * 12, [NL] * 12, BT)
    )
    return fprod, jsum, sub, live, pk_inf


def _batch_tail(fprod, jsum):
    """The per-batch tail: one affine conversion, ONE aggregate Miller
    pair (-G1, A), final exponentiation -> is-one row [1, BT].  In the
    sharded path this runs replicated on every device over the combined
    partials (it is one pair's worth of work)."""
    jx0, jx1, jy0, jy1, jz0, jz1, jinf = jsum
    # [NL, BT] planes: every lane holds the aggregate point
    ax0, ax1, ay0, ay1, ainf = _tiled(
        _k_affine_g2,
        (jx0, jx1, jy0, jy1, jz0, jz1, jinf),
        [NL] * 6 + [1],
        [NL] * 4 + [1],
        BT,
    )
    # Miller: the aggregate pair (-G1, A) — full-width lanes all carry A,
    # so the same compiled tile kernel serves it (ONE pair of distinct
    # work; likewise the single final exponentiation below)
    _tally("miller_pair", 1)
    _tally("final_exp", 1)
    fA = _tiled(
        _k_miller,
        (
            _bcast(_G1X, BT), _bcast(_NEG_G1Y, BT), _bcast(_ONE, BT),
            ax0, ax1, ay0, ay1,
        ),
        [NL] * 7,
        [NL] * 12,
        BT,
    )
    keep = (ainf == 0).astype(jnp.int32)
    ok2 = _tiled(
        _k_final,
        (keep, *fprod, *fA),
        [1] + [NL] * 24,
        [1],
        BT,
    )[0]
    return ok2


def _batch_core(
    table_x, table_y, idx, kmask, msgM, sigM, sig_bad, rwords, valid
):
    """Shared batch pipeline from Montgomery planes onward.

    msgM/sigM: affine G2 planes in Montgomery form; sig_bad: bool[N]
    lanes whose signature cannot participate (infinity or undecodable) —
    they fail the batch and are excluded from the aggregate.
    """
    fprod, jsum, sub, live, pk_inf = _batch_local(
        table_x, table_y, idx, kmask, msgM, sigM, sig_bad, rwords, valid
    )
    ok2 = _batch_tail(fprod, jsum)
    return _j_batch_verdict(ok2, sub, live, pk_inf, sig_bad, valid)


def _sum_g2(x0, x1, y0, y1, z0, z1, excl, n):
    """Lane-tiled grid accumulation wrapper for _k_sum_g2 (full width)."""
    return LA.cached(
        ("sum_g2", n),
        lambda: pl.pallas_call(
            _k_sum_g2,
            out_shape=[_sds((NL, BT))] * 6 + [_sds((1, BT))],
            grid=(n // BT,),
            in_specs=[pl.BlockSpec((NL, BT), lambda i: (0, i))] * 6
            + [pl.BlockSpec((1, BT), lambda i: (0, i))],
            out_specs=[pl.BlockSpec((NL, BT), lambda i: (0, 0))] * 6
            + [pl.BlockSpec((1, BT), lambda i: (0, 0))],
            interpret=LA.interpret(),
        ),
        x0, x1, y0, y1, z0, z1, excl,
    )


def _prod(fN, live_i, n):
    """Lane-tiled grid accumulation wrapper for _k_prod (full width)."""
    return LA.cached(
        ("prod", n),
        lambda: pl.pallas_call(
            _k_prod,
            out_shape=[_sds((NL, BT))] * 12,
            grid=(n // BT,),
            in_specs=[pl.BlockSpec((1, BT), lambda i: (0, i))]
            + [pl.BlockSpec((NL, BT), lambda i: (0, i))] * 12,
            out_specs=[pl.BlockSpec((NL, BT), lambda i: (0, 0))] * 12,
            interpret=LA.interpret(),
        ),
        live_i, *fN,
    )


# ---------------------------------------------------------------------------
# Distinct-message grouping (the SeenAttestationDatas cadence on device)
#
# Gossip attestation sets massively share signing roots: mainnet sees
# ~64 distinct AttestationDatas per slot amortized over ~15k single sets
# (reference: seenCache/seenAttestationData.ts caches committee indices +
# signing roots per distinct data for the same reason).  Batch
# verification with per-set randomizers factors through bilinearity:
#
#   prod_i e(r_i pk_i, H(m_i)) = prod_m e( SUM_{i: m_i=m} r_i pk_i, H(m) )
#
# so the N per-set Miller loops collapse to G per-DISTINCT-message Miller
# loops (G <= 128 -> ONE lane tile) after a cheap segmented jacobian sum
# of the randomized pubkeys.  The G2 side (r_i sig_i sum, subgroup
# checks) is unchanged.  Sets must arrive SORTED by message so groups
# are lane-contiguous (the host sorts; it already owns job assembly).
# ---------------------------------------------------------------------------


def _seg_scan(kernel, planes, n_out, ins, n):
    """Launch a segmented scan kernel over lane tiles of min(n, BT)
    lanes, in order: each tile carries its last lane's running total
    into the next (_seg_scan_tile), so VMEM holds one tile whatever n
    is.  `planes` [NL, n] inputs, then dead and group rows; returns
    `n_out` planes and the inf row, each [rows, n]."""
    bt = min(n, BT)
    tile = lambda r: pl.BlockSpec((r, bt), lambda i: (0, i))  # noqa: E731
    held = lambda r: pl.BlockSpec((r, bt), lambda i: (0, 0))  # noqa: E731
    return LA.cached(
        ("seg_scan", kernel, n),
        lambda: pl.pallas_call(
            kernel,
            out_shape=[_sds((NL, n))] * n_out + [_sds((1, n))]
            + [_sds((NL, bt))] * n_out + [_sds((1, bt))] * 2,
            grid=(n // bt,),
            in_specs=[tile(NL)] * planes + [tile(1)] * 2,
            out_specs=[tile(NL)] * n_out + [tile(1)]
            + [held(NL)] * n_out + [held(1)] * 2,
            interpret=LA.interpret(),
        ),
        *ins,
    )[: n_out + 1]


def _seg_sum_g1(px, py, pz, dead, group):
    """Segmented jacobian prefix-scan of G1 points over the lane axis
    (curve.seg_sum_lanes per tile, _seg_scan across tiles): dead
    bool[n], group int32[n] nondecreasing; the LAST lane of each
    segment holds its total.  Returns (planes, inf bool[n])."""
    n = group.shape[0]
    ox, oy, oz, oinf = _seg_scan(
        _k_seg_sum_g1, 3, 3,
        (px, py, pz, dead[None, :].astype(jnp.int32), group[None, :]),
        n,
    )
    return (ox, oy, oz), oinf[0] != 0


@JD.ops_jit
def _j_group_heads(
    pts, seg_inf, msg_x0, msg_x1, msg_y0, msg_y1, head_lanes, glive
):
    """Gather each group's total (its last lane) + that group's hashed
    message onto one BT-lane tile; dead group lanes get generator pairs
    (excluded from the Fp12 product by the live row)."""
    gx, gy, gz = (jnp.take(a, head_lanes, axis=-1) for a in pts)
    g_inf = jnp.take(seg_inf, head_lanes) | (glive == 0)
    live = ~g_inf
    gx = C.select(live, gx, _bcast(_G1X, BT))
    gy = C.select(live, gy, _bcast(_G1Y, BT))
    gz = C.select(live, gz, _bcast(_ONE, BT))
    q = [
        jnp.take(m, head_lanes, axis=-1)
        for m in (msg_x0, msg_x1, msg_y0, msg_y1)
    ]
    qx = F2.select2(live, (q[0], q[1]), (_bcast(_G2X[0], BT), _bcast(_G2X[1], BT)))
    qy = F2.select2(live, (q[2], q[3]), (_bcast(_G2Y[0], BT), _bcast(_G2Y[1], BT)))
    # a live group whose pk-sum IS infinity contributes e(O, Q) = 1 —
    # excluding it from the product is the exact value, not a fallback
    live_row = live[None, :].astype(jnp.int32)
    return gx, gy, gz, qx[0], qx[1], qy[0], qy[1], live_row


def _batch_local_grouped(
    table_x, table_y, idx, kmask, msgM, sigM, sig_bad, rwords, valid,
    group, head_lanes, glive,
):
    """_batch_local with the G1/Miller side grouped by distinct message.

    group: int32[n] nondecreasing ids; head_lanes: int32[BT] lane index
    of each group's LAST member (padding entries arbitrary); glive:
    int32[BT] 1 for real groups.  Requires distinct messages <= BT.
    """
    n = valid.shape[0]
    msg_x0, msg_x1, msg_y0, msg_y1 = msgM
    sig_x0, sig_x1, sig_y0, sig_y1 = sigM
    (pk, pk_inf) = _gather_pk(table_x, table_y, idx, kmask)
    live = (valid != 0) & ~pk_inf & ~sig_bad

    px, py, pz, sx, sy = _j_substitute(
        live, pk[0], pk[1], pk[2], sig_x0, sig_x1, sig_y0, sig_y1
    )
    live_i = live[None, :].astype(jnp.int32)
    zero_row = jnp.zeros((1, n), jnp.int32)

    rx, ry, rz, rinf = _tiled(
        _k_g1_rpk,
        (px, py, pz, zero_row, rwords),
        [NL, NL, NL, 1, RAND_WORDS],
        [NL, NL, NL, 1],
        n,
    )

    sx0r, sx1r, sy0r, sy1r, sz0r, sz1r, rsinf, sub = _tiled(
        _k_g2_rsig_sub,
        (sx[0], sx[1], sy[0], sy[1], zero_row, rwords),
        [NL, NL, NL, NL, 1, RAND_WORDS],
        [NL] * 6 + [1, 1],
        n,
    )

    excl = (~live)[None, :].astype(jnp.int32) | rsinf
    px0, px1, py0, py1, pz0, pz1, pinf = _sum_g2(
        sx0r, sx1r, sy0r, sy1r, sz0r, sz1r, excl, n
    )
    jsum = tuple(_tiled(
        _k_sum_lanes, (px0, px1, py0, py1, pz0, pz1, pinf),
        [NL] * 6 + [1], [NL] * 6 + [1], BT,
    ))

    # grouped G1 side: segmented sum -> G group pairs -> ONE Miller tile
    # (tallied at the tile's BT lanes: G <= BT distinct groups, dead
    # group lanes padded with generator pairs)
    _tally("miller_pair", BT)
    dead = (~live) | (rinf[0] != 0)
    pts, seg_inf = _seg_sum_g1(rx, ry, rz, dead, group)
    gx, gy, gz, qx0, qx1, qy0, qy1, live_row = _j_group_heads(
        pts, seg_inf, msg_x0, msg_x1, msg_y0, msg_y1, head_lanes, glive
    )
    fG = _tiled(
        _k_miller,
        (gx, gy, gz, qx0, qx1, qy0, qy1),
        [NL] * 7,
        [NL] * 12,
        BT,
    )
    fpartial = _prod(fG, live_row, BT)
    fprod = tuple(
        _tiled(_k_prod_lanes, tuple(fpartial), [NL] * 12, [NL] * 12, BT)
    )
    return fprod, jsum, sub, live, pk_inf


def verify_batch_device_wire_grouped(
    table_x, table_y, idx, kmask,
    msg_x0, msg_x1, msg_y0, msg_y1,
    sig_x0, sig_x1, sig_flags,
    group, head_lanes, glive,
    rwords, valid,
):
    """verify_batch_device_wire with distinct-message grouping: the
    Miller stage runs per distinct signing root (<= BT of them) instead
    of per set.  Same verdict semantics as the ungrouped path."""
    n = valid.shape[0]
    msg_x0, msg_x1, msg_y0, msg_y1 = _tiled(
        _k_mont4, (msg_x0, msg_x1, msg_y0, msg_y1), [NL] * 4, [NL] * 4, n
    )
    (sx0, sx1, sy0, sy1), dec_ok = _decompress_sig(sig_x0, sig_x1, sig_flags, n)
    sig_bad = (sig_flags[1] != 0) | ~dec_ok
    fprod, jsum, sub, live, pk_inf = _batch_local_grouped(
        table_x, table_y, idx, kmask,
        (msg_x0, msg_x1, msg_y0, msg_y1),
        (sx0, sx1, sy0, sy1),
        sig_bad, rwords, valid,
        group, head_lanes, glive,
    )
    ok2 = _batch_tail(fprod, jsum)
    return _j_batch_verdict(ok2, sub, live, pk_inf, sig_bad, valid)


# ---------------------------------------------------------------------------
# Pre-verify signature aggregation (ISSUE 13: bls/aggregator.py)
#
# k wire signatures sharing one signing root point-add into ONE G2
# point before verification: the pairing check then costs one set
# instead of k.  The sum is a segmented jacobian prefix-scan over the
# lane axis (the FP2 twin of _seg_sum_g1), fed by the SAME device
# decompression kernel the wire verify path uses; group totals gather
# onto one BT-lane tile and convert to affine (Montgomery form — the
# host converts limbs to ground-truth ints and re-compresses).
# ---------------------------------------------------------------------------


def _seg_sum_g2(x0, x1, y0, y1, dead, group):
    """The G2 twin of _seg_sum_g1 over AFFINE inputs (z = 1): returns
    (x0, x1, y0, y1, z0, z1 jacobian planes, inf bool[n])."""
    n = group.shape[0]
    out = _seg_scan(
        _k_seg_sum_g2, 4, 6,
        (x0, x1, y0, y1, dead[None, :].astype(jnp.int32), group[None, :]),
        n,
    )
    return (*out[:6], out[6][0] != 0)


@JD.ops_jit
def _j_agg_heads(px0, px1, py0, py1, pz0, pz1, seg_inf, head_lanes, glive):
    """Gather each group's jacobian total (its last lane) onto one
    BT-lane tile; dead group lanes are flagged via the inf row (cheap
    gather/select glue — the affine conversion reuses the tiled
    _k_affine_g2 kernel the batch tail already compiles)."""
    heads = [
        jnp.take(a, head_lanes, axis=-1)
        for a in (px0, px1, py0, py1, pz0, pz1)
    ]
    g_inf = jnp.take(seg_inf, head_lanes) | (glive == 0)
    return (*heads, g_inf[None, :].astype(jnp.int32))


def aggregate_g2_sum_device(sig_x0, sig_x1, sig_flags, group, head_lanes, glive):
    """Batched G2 point-add of compressed wire signatures, grouped.

    sig planes/flags: the encode_wire_planes layout for n signatures (n
    a multiple of BT); group: int32[n] nondecreasing lane-contiguous
    group ids; head_lanes: int32[BT] lane of each group's LAST member;
    glive: int32[BT] 1 for real groups (<= BT groups per dispatch).

    Returns (ax0, ax1, ay0, ay1, g_inf_row, ok_row):
      - affine G2 planes [NL, BT] in MONTGOMERY form, one aggregate per
        group head lane (generator-substituted where g_inf — the same
        dead-lane convention as _k_affine_g2 in the batch tail),
      - g_inf_row int32[1, BT]: the group total is the point at
        infinity (compresses to the infinity encoding),
      - ok_row int32[1, n]: per-input decompression success — a False
        lane means the caller must drop to the host path for that
        group (the flagged signature is off-curve/undecodable and the
        device sum excluded it).
    """
    n = sig_flags.shape[1]
    (sx0, sx1, sy0, sy1), dec_ok = _decompress_sig(sig_x0, sig_x1, sig_flags, n)
    bad = (sig_flags[1] != 0) | ~dec_ok
    px0, px1, py0, py1, pz0, pz1, seg_inf = _seg_sum_g2(
        sx0, sx1, sy0, sy1, bad, group
    )
    hx0, hx1, hy0, hy1, hz0, hz1, hinf = _j_agg_heads(
        px0, px1, py0, py1, pz0, pz1, seg_inf, head_lanes, glive
    )
    ax0, ax1, ay0, ay1, g_inf = _tiled(
        _k_affine_g2,
        (hx0, hx1, hy0, hy1, hz0, hz1, hinf),
        [NL] * 6 + [1],
        [NL] * 4 + [1],
        BT,
    )
    ok_row = (~bad)[None, :].astype(jnp.int32)
    return ax0, ax1, ay0, ay1, g_inf, ok_row


def verify_each_device(
    table_x, table_y, idx, kmask,
    msg_x0, msg_x1, msg_y0, msg_y1,
    sig_x0, sig_x1, sig_y0, sig_y1,
    sig_inf, valid,
):
    """Independent per-set verdicts (the batch-failure retry path).

    e(pk_i, H_i) * e(-G1, sig_i) == 1 per lane; padding lanes True.
    msg/sig planes arrive as PLAIN limbs (see verify_batch_device).
    """
    n = valid.shape[0]
    msg_x0, msg_x1, msg_y0, msg_y1, sig_x0, sig_x1, sig_y0, sig_y1 = _to_mont8(
        (msg_x0, msg_x1, msg_y0, msg_y1, sig_x0, sig_x1, sig_y0, sig_y1), n
    )
    return _each_core(
        table_x, table_y, idx, kmask,
        (msg_x0, msg_x1, msg_y0, msg_y1),
        (sig_x0, sig_x1, sig_y0, sig_y1),
        (sig_inf != 0), valid,
    )


def verify_each_device_wire(
    table_x, table_y, idx, kmask,
    msg_x0, msg_x1, msg_y0, msg_y1,
    sig_x0, sig_x1, sig_flags,
    valid,
):
    """Per-set verdicts from WIRE signatures (see verify_batch_device_wire)."""
    n = valid.shape[0]
    msg_x0, msg_x1, msg_y0, msg_y1 = _tiled(
        _k_mont4, (msg_x0, msg_x1, msg_y0, msg_y1), [NL] * 4, [NL] * 4, n
    )
    (sx0, sx1, sy0, sy1), dec_ok = _decompress_sig(sig_x0, sig_x1, sig_flags, n)
    sig_bad = (sig_flags[1] != 0) | ~dec_ok
    return _each_core(
        table_x, table_y, idx, kmask,
        (msg_x0, msg_x1, msg_y0, msg_y1),
        (sx0, sx1, sy0, sy1),
        sig_bad, valid,
    )


# ---------------------------------------------------------------------------
# Multi-chip sharding (SURVEY §2.4 P1: data parallelism over signature
# sets; the device pubkey table is REPLICATED — 1M keys in limb planes is
# ~260 MB, well under per-chip HBM, so gathers stay local and the only
# cross-device traffic is one all_gather of the Fp12 partial products +
# the aggregate-signature jacobian + violation counts per job)
# ---------------------------------------------------------------------------


def wire_shard_specs(axis: str = "sets"):
    """The PartitionSpec layout for make_sharded_wire_verifier's 13
    positional args — exported so device_put call sites (graft dryrun,
    tests) cannot drift from the verifier's in_specs."""
    from jax.sharding import PartitionSpec as P

    return (
        P(), P(),                      # table planes replicated
        P(axis), P(axis),              # idx [N, K], kmask
        P(None, axis), P(None, axis),  # msg planes [NL, N] x4
        P(None, axis), P(None, axis),
        P(None, axis), P(None, axis),  # sig_x0, sig_x1
        P(None, axis),                 # sig_flags [2, N]
        P(None, axis),                 # rwords [RAND_WORDS, N]
        P(axis),                       # valid [N]
    )


def make_sharded_wire_verifier(mesh, axis: str = "sets"):
    """Build the sharded wire-path batch verifier over `mesh`.

    Returns fn(table_x, table_y, idx, kmask, m0..m3, sig_x0, sig_x1,
    sig_flags, rwords, valid) -> (batch_ok, sig_sub_ok) where the
    per-set operands are sharded over `axis` (each shard a multiple of
    the lane tile) and the table is replicated.  Each device runs the
    FULL local pipeline (ingest -> gather -> RLC scalar muls -> Miller
    -> partial product); the cross-device combine is one all_gather,
    then the one-pair tail (affine + aggregate Miller + final exp) runs
    replicated.  Wrap in jax.jit to compile over the mesh.
    """
    import jax.lax as lax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from jax import shard_map

    n_dev = mesh.shape[axis]

    def _combine_f12(gathered):
        """[D, NL, bt] x12 leaves -> product over D (plain XLA ops)."""
        acc = _unflatten_f12([g[0] for g in gathered])
        for d in range(1, n_dev):
            acc = TW.mul12(acc, _unflatten_f12([g[d] for g in gathered]))
        return acc

    def _combine_jsum(gathered, inf_g):
        """[D, NL, bt] x6 + [D, 1, bt] inf -> jacobian sum over D."""
        acc = (
            (gathered[0][0], gathered[1][0]),
            (gathered[2][0], gathered[3][0]),
            (gathered[4][0], gathered[5][0]),
        )
        acc_inf = inf_g[0][0] != 0
        for d in range(1, n_dev):
            pt = (
                (gathered[0][d], gathered[1][d]),
                (gathered[2][d], gathered[3][d]),
                (gathered[4][d], gathered[5][d]),
            )
            acc, acc_inf = CV.jac_add_full(
                CV.FP2_OPS, acc, acc_inf, pt, inf_g[d][0] != 0
            )
        return (
            acc[0][0], acc[0][1], acc[1][0], acc[1][1], acc[2][0], acc[2][1],
            acc_inf[None, :].astype(jnp.int32),
        )

    def body(
        table_x, table_y, idx, kmask,
        m0, m1, m2, m3, sig_x0, sig_x1, sig_flags,
        rwords, valid,
    ):
        n = valid.shape[0]  # LOCAL shard size
        m0, m1, m2, m3 = _tiled(
            _k_mont4, (m0, m1, m2, m3), [NL] * 4, [NL] * 4, n
        )
        (s0, s1, s2, s3), dec_ok = _decompress_sig(
            sig_x0, sig_x1, sig_flags, n
        )
        sig_bad = (sig_flags[1] != 0) | ~dec_ok
        fprod, jsum, sub, live, pk_inf = _batch_local(
            table_x, table_y, idx, kmask,
            (m0, m1, m2, m3), (s0, s1, s2, s3),
            sig_bad, rwords, valid,
        )
        # -- cross-device combine (the only collectives in the job) ----
        f_g = [lax.all_gather(leaf, axis) for leaf in fprod]
        j_g = [lax.all_gather(p, axis) for p in jsum[:6]]
        inf_g = lax.all_gather(jsum[6], axis)
        fprod_all = tuple(
            jax.tree_util.tree_leaves(_combine_f12(f_g))
        )
        jsum_all = _combine_jsum(j_g, inf_g)
        # local violation counts -> global via psum
        sub_ok = (sub[0] != 0) | ~live
        viol = (
            jnp.sum(~sub_ok)
            + jnp.sum(pk_inf & (valid != 0))
            + jnp.sum(sig_bad & (valid != 0))
        )
        viol_total = lax.psum(viol, axis)
        # -- replicated one-pair tail ----------------------------------
        ok2 = _batch_tail(fprod_all, jsum_all)
        batch_ok = (ok2[0, 0] != 0) & (viol_total == 0)
        return batch_ok, sub_ok

    return shard_map(
        body,
        mesh=mesh,
        in_specs=wire_shard_specs(axis),
        out_specs=(P(), P(axis)),
        check_vma=False,
    )


def _each_core(table_x, table_y, idx, kmask, msgM, sigM, sig_bad, valid):
    n = valid.shape[0]
    msg_x0, msg_x1, msg_y0, msg_y1 = msgM
    sig_x0, sig_x1, sig_y0, sig_y1 = sigM
    (pk, pk_inf) = _gather_pk(table_x, table_y, idx, kmask)
    live = (valid != 0) & ~pk_inf & ~sig_bad

    px, py, pz, sx, sy = _j_substitute(
        live, pk[0], pk[1], pk[2], sig_x0, sig_x1, sig_y0, sig_y1
    )
    g1x, one = _bcast(_G1X, n), _bcast(_ONE, n)
    _tally("miller_pair", 2 * n)
    _tally("final_exp", n)

    zero_row = jnp.zeros((1, n), jnp.int32)
    sub = _tiled(
        _k_sub_only,
        (sx[0], sx[1], sy[0], sy[1], zero_row),
        [NL] * 4 + [1],
        [1],
        n,
    )[0]

    f1 = _tiled(
        _k_miller,
        (px, py, pz, msg_x0, msg_x1, msg_y0, msg_y1),
        [NL] * 7,
        [NL] * 12,
        n,
    )
    f2 = _tiled(
        _k_miller,
        (g1x, _bcast(_NEG_G1Y, n), one, sx[0], sx[1], sy[0], sy[1]),
        [NL] * 7,
        [NL] * 12,
        n,
    )
    live_i = live[None, :].astype(jnp.int32)
    ok = _tiled(
        _k_final,
        (live_i, *f1, *f2),
        [1] + [NL] * 24,
        [1],
        n,
    )[0]
    return ((ok[0] != 0) & (sub[0] != 0) & live) | ~(valid != 0)
