"""Distinct-message grouping: segmented G1 sum + the grouped batch path.

The grouping collapses per-set Miller loops to per-distinct-signing-root
Miller loops via bilinearity (kernels/verify.py rationale block; the
host-side cadence matches the reference's SeenAttestationDatas cache,
packages/beacon-node/src/chain/seenCache/seenAttestationData.ts).

The segmented-scan unit test runs at tiny lane widths in plain XLA on
the CPU platform (fast); the full grouped pipeline equivalence runs in
pallas interpret mode (slow tier, like the other kernel tests).
"""

import random

import numpy as np
import pytest

import jax.numpy as jnp

from lodestar_tpu.crypto import bls as GB
from lodestar_tpu.crypto import curves as GC
from lodestar_tpu.crypto import fields as GF
from lodestar_tpu.crypto.hash_to_curve import hash_to_g2
from lodestar_tpu.kernels import layout as LY
from lodestar_tpu.kernels import verify as KV

random.seed(0xB1E55)


def _jac_decode(planes):
    """[NL, B] Montgomery jacobian planes -> list of affine oracle points."""
    xs = LY.decode_batch(np.asarray(planes[0]))
    ys = LY.decode_batch(np.asarray(planes[1]))
    zs = LY.decode_batch(np.asarray(planes[2]))
    out = []
    for x, y, z in zip(xs, ys, zs):
        if z == 0:
            out.append(None)
            continue
        zi = GF.fp_inv(z)
        zi2 = GF.fp_mul(zi, zi)
        out.append((GF.fp_mul(x, zi2), GF.fp_mul(y, GF.fp_mul(zi2, zi))))
    return out


@pytest.mark.smoke
def test_segmented_g1_sum_matches_oracle():
    n = 8
    ks = [3, 5, 7, 11, 13, 17, 19, 23]
    pts = [GC.scalar_mul(GC.FP_OPS, GC.G1_GEN, k) for k in ks]
    group = np.asarray([0, 0, 0, 1, 1, 2, 3, 3], np.int32)
    dead = np.zeros(n, bool)
    dead[4] = True  # excluded from group 1's sum
    px = jnp.asarray(LY.encode_batch([p[0] for p in pts]))
    py = jnp.asarray(LY.encode_batch([p[1] for p in pts]))
    pz = jnp.asarray(LY.encode_batch([1] * n))
    out_pts, out_inf = KV._seg_sum_g1(
        px, py, pz, jnp.asarray(dead), jnp.asarray(group)
    )
    decoded = _jac_decode(out_pts)
    inf = list(np.asarray(out_inf))
    # segment totals at the LAST lane of each segment
    expected = {
        2: [0, 1, 2],        # group 0
        4: [3],              # group 1 (lane 4 dead)
        5: [5],              # group 2
        7: [6, 7],           # group 3
    }
    for head, members in expected.items():
        want = GC.multi_add(GC.FP_OPS, [pts[i] for i in members])
        assert not inf[head]
        assert decoded[head] == want, f"head lane {head}"
    # an all-dead segment sums to infinity
    dead2 = np.ones(n, bool)
    _, inf2 = KV._seg_sum_g1(
        px, py, pz, jnp.asarray(dead2), jnp.asarray(group)
    )
    assert all(np.asarray(inf2))


@pytest.mark.smoke
@pytest.mark.parametrize(
    "bounds, dead_lanes",
    [
        # one segment through all three tiles, across dead first lanes
        ((0, 100, 300, 384), (128, 256, 301)),
        # segments ending and starting exactly on tile boundaries
        ((0, 128, 256, 384), (5,)),
    ],
)
def test_segmented_g1_sum_carries_across_tiles(bounds, dead_lanes):
    """_seg_scan runs one BT-lane tile at a time and carries each
    tile's running total into the next: segment totals at head lanes
    == the host oracle for segments that span tiles."""
    n = 3 * KV.BT
    pts = [GC.scalar_mul(GC.FP_OPS, GC.G1_GEN, 3 + i) for i in range(n)]
    group = np.zeros(n, np.int32)
    for g, (lo, hi) in enumerate(zip(bounds, bounds[1:])):
        group[lo:hi] = g
    dead = np.zeros(n, bool)
    dead[list(dead_lanes)] = True
    enc = lambda v: jnp.asarray(LY.encode_batch(v))  # noqa: E731
    out_pts, out_inf = KV._seg_sum_g1(
        enc([p[0] for p in pts]), enc([p[1] for p in pts]), enc([1] * n),
        jnp.asarray(dead), jnp.asarray(group),
    )
    decoded = _jac_decode(out_pts)
    assert not np.asarray(out_inf)[[hi - 1 for hi in bounds[1:]]].any()
    for lo, hi in zip(bounds, bounds[1:]):
        want = GC.multi_add(
            GC.FP_OPS, [pts[i] for i in range(lo, hi) if not dead[i]]
        )
        assert decoded[hi - 1] == want, f"segment [{lo}, {hi})"


def _jac_decode_g2(planes):
    """[NL, B] Montgomery jacobian FP2 planes -> affine oracle points."""
    x0 = LY.decode_batch(np.asarray(planes[0]))
    x1 = LY.decode_batch(np.asarray(planes[1]))
    y0 = LY.decode_batch(np.asarray(planes[2]))
    y1 = LY.decode_batch(np.asarray(planes[3]))
    z0 = LY.decode_batch(np.asarray(planes[4]))
    z1 = LY.decode_batch(np.asarray(planes[5]))
    out = []
    for i in range(len(x0)):
        z = (z0[i], z1[i])
        if z == (0, 0):
            out.append(None)
            continue
        zi = GF.fp2_inv(z)
        zi2 = GF.fp2_sqr(zi)
        out.append(
            (
                GF.fp2_mul((x0[i], x1[i]), zi2),
                GF.fp2_mul((y0[i], y1[i]), GF.fp2_mul(zi2, zi)),
            )
        )
    return out


@pytest.mark.smoke
def test_lane_sum_kernel_matches_oracle():
    """_k_sum_lanes (the batch path's cross-lane G2 butterfly, a rolled
    loop over a traced pltpu.roll shift): every lane holds the sum of
    the live lanes."""
    bt, nl = KV.BT, KV.NL
    pts = [GC.scalar_mul(GC.FP2_OPS, GC.G2_GEN, 3 + 2 * i) for i in range(bt)]
    inf = np.zeros((1, bt), np.int32)
    inf[0, 5] = 1
    planes = [
        jnp.asarray(LY.encode_batch(v))
        for v in (
            [p[0][0] for p in pts], [p[0][1] for p in pts],
            [p[1][0] for p in pts], [p[1][1] for p in pts],
            [1] * bt, [0] * bt,
        )
    ]
    out = KV._tiled(
        KV._k_sum_lanes, (*planes, jnp.asarray(inf)),
        [nl] * 6 + [1], [nl] * 6 + [1], bt,
    )
    want = GC.multi_add(GC.FP2_OPS, [p for i, p in enumerate(pts) if i != 5])
    decoded = _jac_decode_g2(out[:6])
    assert decoded[0] == want and decoded[77] == want
    assert not np.asarray(out[6]).any()


@pytest.mark.smoke
def test_lane_product_kernel_matches_oracle():
    """_k_prod_lanes (the batch path's cross-lane Fp12 product): every
    lane holds the product of all lanes."""
    bt, nl = KV.BT, KV.NL
    rng = random.Random(3)
    vals = [[rng.randrange(LY.P) for _ in range(bt)] for _ in range(12)]
    planes = tuple(jnp.asarray(LY.encode_batch(v)) for v in vals)
    out = KV._tiled(KV._k_prod_lanes, planes, [nl] * 12, [nl] * 12, bt)
    dec = [LY.decode_batch(np.asarray(p)) for p in out]

    def lane(src, i):
        g = [src[k][i] for k in range(12)]
        return (
            ((g[0], g[1]), (g[2], g[3]), (g[4], g[5])),
            ((g[6], g[7]), (g[8], g[9]), (g[10], g[11])),
        )

    want = lane(vals, 0)
    for i in range(1, bt):
        want = GF.fp12_mul(want, lane(vals, i))
    assert lane(dec, 0) == want and lane(dec, 99) == want


@pytest.mark.slow
def test_segmented_g2_sum_matches_oracle():
    """The pre-verify aggregation stage's G2 scan (ISSUE 13,
    KV._seg_sum_g2): segment totals at head lanes == the host
    jacobian-add oracle, dead lanes excluded, all-dead segments at
    infinity — the FP2 twin of the G1 test above, at tiny width.

    Slow tier: the FP2 jac_add_full rounds trace ~160 s of XLA graph
    on the 1-core host EVERY run (tracing is uncacheable — dev/NOTES
    round 4), which the tier-1 budget cannot absorb; the algorithm is
    the G1 twin's (fast tier above), only the field ops differ."""
    n = 8
    ks = [3, 5, 7, 11, 13, 17, 19, 23]
    pts = [GC.scalar_mul(GC.FP2_OPS, GC.G2_GEN, k) for k in ks]
    group = np.asarray([0, 0, 0, 1, 1, 2, 3, 3], np.int32)
    dead = np.zeros(n, bool)
    dead[4] = True  # excluded from group 1's sum
    px0 = jnp.asarray(LY.encode_batch([p[0][0] for p in pts]))
    px1 = jnp.asarray(LY.encode_batch([p[0][1] for p in pts]))
    py0 = jnp.asarray(LY.encode_batch([p[1][0] for p in pts]))
    py1 = jnp.asarray(LY.encode_batch([p[1][1] for p in pts]))
    out = KV._seg_sum_g2(
        px0, px1, py0, py1, jnp.asarray(dead), jnp.asarray(group)
    )
    decoded = _jac_decode_g2(out[:6])
    inf = list(np.asarray(out[6]))
    expected = {
        2: [0, 1, 2],        # group 0
        4: [3],              # group 1 (lane 4 dead)
        5: [5],              # group 2
        7: [6, 7],           # group 3
    }
    for head, members in expected.items():
        want = GC.multi_add(GC.FP2_OPS, [pts[i] for i in members])
        assert not inf[head]
        assert decoded[head] == want, f"head lane {head}"
    dead2 = np.ones(n, bool)
    out2 = KV._seg_sum_g2(
        px0, px1, py0, py1, jnp.asarray(dead2), jnp.asarray(group)
    )
    assert all(np.asarray(out2[6]))


# -- full grouped pipeline (interpret mode, one lane tile) ------------------

pytestmark_slow = pytest.mark.slow
N = 128


def _wire_planes(sets, n):
    """sets: list of (index, root, sig_bytes) single-pubkey wire sets."""
    from lodestar_tpu.bls.ingest import MessageCache, encode_wire_planes

    idx = np.zeros((n, 1), np.int32)
    kmask = np.zeros((n, 1), np.int32)
    valid = np.zeros((n,), np.int32)
    for i, (vi, _root, _sig) in enumerate(sets):
        idx[i, 0] = vi
        kmask[i, 0] = 1
        valid[i] = 1
    msgs = MessageCache().get_many([s[1] for s in sets])
    msgs = msgs + [GC.G2_GEN] * (n - len(sets))
    sig_x0, sig_x1, flags, host_bad = encode_wire_planes(
        [s[2] for s in sets], n
    )
    assert not host_bad.any()

    def enc(vals):
        return jnp.asarray(LY.encode_plain_batch(vals))

    return (
        jnp.asarray(idx), jnp.asarray(kmask),
        enc([m[0][0] for m in msgs]), enc([m[0][1] for m in msgs]),
        enc([m[1][0] for m in msgs]), enc([m[1][1] for m in msgs]),
        jnp.asarray(sig_x0), jnp.asarray(sig_x1), jnp.asarray(flags),
        jnp.asarray(valid),
    )


@pytest.mark.slow
def test_grouped_batch_matches_ungrouped():
    from lodestar_tpu.ops import bls_kernels as BK

    v = 6
    sks = [GB.keygen(b"grp-%d" % i) for i in range(v)]
    pks = [GB.sk_to_pk(sk) for sk in sks]
    tx = jnp.asarray(LY.encode_batch([p[0] for p in pks]))
    ty = jnp.asarray(LY.encode_batch([p[1] for p in pks]))

    # 6 sets over 2 distinct roots (sorted by root), all valid
    roots = [b"\x0a" * 32, b"\x0b" * 32]
    sets = [
        (i, roots[0 if i < 4 else 1], GC.g2_compress(
            GB.sign(sks[i], roots[0 if i < 4 else 1])))
        for i in range(v)
    ]
    sets.sort(key=lambda s: s[1])
    idx, kmask, m0, m1, m2, m3, sx0, sx1, flags, valid = _wire_planes(sets, N)
    group = np.zeros(N, np.int32)
    g = 0
    for i in range(1, v):
        if sets[i][1] != sets[i - 1][1]:
            g += 1
        group[i] = g
    group[v:] = np.arange(g + 1, g + 1 + N - v, dtype=np.int32)
    heads = np.zeros(KV.BT, np.int32)
    heads[0] = 3 if sets[0][1] == roots[0] else 1
    heads[1] = v - 1
    glive = np.zeros(KV.BT, np.int32)
    glive[:2] = 1
    rand = jnp.asarray(BK.make_rand_words(N, np.random.default_rng(9)))

    ok_g, sub_g = KV.verify_batch_device_wire_grouped(
        tx, ty, idx, kmask, m0, m1, m2, m3, sx0, sx1, flags,
        jnp.asarray(group), jnp.asarray(heads), jnp.asarray(glive),
        rand, valid,
    )
    ok_u, sub_u = KV.verify_batch_device_wire(
        tx, ty, idx, kmask, m0, m1, m2, m3, sx0, sx1, flags, rand, valid
    )
    assert bool(ok_g) and bool(ok_u)
    assert list(np.asarray(sub_g)) == list(np.asarray(sub_u))

    # one tampered signature fails the grouped batch too
    bad_sig = GC.g2_compress(
        GC.scalar_mul(GC.FP2_OPS, GB.sign(sks[2], sets[2][1]), 2)
    )
    sets_bad = list(sets)
    sets_bad[2] = (sets[2][0], sets[2][1], bad_sig)
    idx, kmask, m0, m1, m2, m3, sx0, sx1, flags, valid = _wire_planes(
        sets_bad, N
    )
    ok_bad, _ = KV.verify_batch_device_wire_grouped(
        tx, ty, idx, kmask, m0, m1, m2, m3, sx0, sx1, flags,
        jnp.asarray(group), jnp.asarray(heads), jnp.asarray(glive),
        rand, valid,
    )
    assert not bool(ok_bad)


@pytest.mark.slow
def test_verifier_uses_grouped_path_with_duplicate_roots():
    """The TpuBlsVerifier end-to-end: duplicate signing roots trigger the
    grouped batch; verdict order survives the sort (unsort mapping)."""
    from lodestar_tpu.bls.pubkey_table import PubkeyTable
    from lodestar_tpu.bls.signature_set import WireSignatureSet
    from lodestar_tpu.bls.verifier import TpuBlsVerifier

    v = 6
    sks = [GB.keygen(b"vgrp-%d" % i) for i in range(v)]
    pks = [GB.sk_to_pk(sk) for sk in sks]
    table = PubkeyTable(capacity=v)
    table.register_points_unchecked(pks, tile_to=v)
    verifier = TpuBlsVerifier(table, rng=np.random.default_rng(5))

    # UNSORTED roots so begin_job must sort + unsort
    roots = [b"\x0c" * 32, b"\x0d" * 32]
    order = [1, 0, 1, 1, 0, 1]
    sets = [
        WireSignatureSet.single(
            i, roots[order[i]],
            GC.g2_compress(GB.sign(sks[i], roots[order[i]])),
        )
        for i in range(v)
    ]
    assert verifier.verify_signature_sets(
        sets, __import__("lodestar_tpu.bls.verifier", fromlist=["VerifyOptions"]).VerifyOptions(batchable=True)
    )

    # tamper set #3 (root group 1): batch fails -> per-set retry; the
    # verdict must land on position 3 after the unsort
    bad = GC.g2_compress(
        GC.scalar_mul(GC.FP2_OPS, GB.sign(sks[3], roots[1]), 2)
    )
    sets_bad = list(sets)
    sets_bad[3] = WireSignatureSet.single(3, roots[1], bad)
    job = verifier.begin_job(sets_bad, batchable=True)
    assert not verifier.finish_job(job)
    assert list(job.verdicts) == [True, True, True, False, True, True]


@pytest.mark.smoke
def test_group_heads_gather_and_liveness():
    """_j_group_heads: each group's last-lane total + its message gather
    onto the BT tile; dead groups (padding or all-dead segments) become
    generator pairs excluded by the live row."""
    n = 8
    ks = [3, 5, 7, 11, 13, 17, 19, 23]
    pts = [GC.scalar_mul(GC.FP_OPS, GC.G1_GEN, k) for k in ks]
    px = jnp.asarray(LY.encode_batch([p[0] for p in pts]))
    py = jnp.asarray(LY.encode_batch([p[1] for p in pts]))
    pz = jnp.asarray(LY.encode_batch([1] * n))
    group = np.asarray([0, 0, 1, 1, 2, 2, 3, 3], np.int32)
    dead = np.zeros(n, bool)
    dead[4] = dead[5] = True  # group 2 entirely dead
    seg_pts, seg_inf = KV._seg_sum_g1(
        px, py, pz, jnp.asarray(dead), jnp.asarray(group)
    )

    # two distinct messages riding lanes (group i uses msg i % 2)
    msgs = [hash_to_g2(b"gh-%d" % (i % 2)) for i in range(n)]
    m = [
        jnp.asarray(LY.encode_batch(v))
        for v in (
            [p[0][0] for p in msgs],
            [p[0][1] for p in msgs],
            [p[1][0] for p in msgs],
            [p[1][1] for p in msgs],
        )
    ]
    head_lanes = np.zeros(KV.BT, np.int32)
    head_lanes[:4] = [1, 3, 5, 7]  # last lane of each group
    glive = np.zeros(KV.BT, np.int32)
    glive[:4] = 1
    gx, gy, gz, qx0, qx1, qy0, qy1, live_row = KV._j_group_heads(
        seg_pts, seg_inf, *m, jnp.asarray(head_lanes), jnp.asarray(glive)
    )
    live = np.asarray(live_row)[0]
    # groups 0, 1, 3 live; group 2 all-dead; padding lanes dead
    assert list(live[:4]) == [1, 1, 0, 1]
    assert not live[4:].any()
    decoded = _jac_decode((gx, gy, gz))
    assert decoded[0] == GC.multi_add(GC.FP_OPS, [pts[0], pts[1]])
    assert decoded[1] == GC.multi_add(GC.FP_OPS, [pts[2], pts[3]])
    assert decoded[3] == GC.multi_add(GC.FP_OPS, [pts[6], pts[7]])
    # dead lanes carry the generator (excluded by live anyway)
    assert decoded[2] == GC.G1_GEN and decoded[4] == GC.G1_GEN
    # the gathered G2 messages match each group's own message
    qx0_d = LY.decode_batch(np.asarray(qx0))
    for g, lane in ((0, 1), (1, 3), (3, 7)):
        assert qx0_d[g] == msgs[lane][0][0], g
