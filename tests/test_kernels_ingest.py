"""Device ingest kernels vs the host oracle.

canonical signs, Fp2 sqrt, G2 decompression, and SSWU hash-to-curve must
match crypto/{fields,curves,hash_to_curve}.py bit-for-bit — the host
path is the consensus-critical reference (reference ingest behavior:
blst uncompress + hash inside packages/beacon-node/src/chain/bls/).
"""

import random

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from lodestar_tpu.crypto import bls as B
from lodestar_tpu.crypto import curves as GC
from lodestar_tpu.crypto import fields as GT
from lodestar_tpu.crypto import hash_to_curve as HC
from lodestar_tpu.kernels import canonical as CN
from lodestar_tpu.kernels import ingest as IN
from lodestar_tpu.kernels import layout as LY
from lodestar_tpu.kernels import sqrt as SQ

pytestmark = pytest.mark.slow

P = GT.P


def enc_mont(vals):
    return jnp.asarray(LY.encode_batch(vals))


def enc_plain(vals):
    return jnp.asarray(LY.encode_plain_batch(vals))


def dec(arr):
    return LY.decode_batch(np.asarray(arr))


def test_canonical_signs_match_host():
    rng = np.random.default_rng(1)
    vals = [0, 1, 2, P - 1, (P - 1) // 2, (P + 1) // 2, P - 2] + [
        int(rng.integers(0, 1 << 62)) ** 3 % P for _ in range(9)
    ]
    x = enc_mont(vals)
    sgn = jax.jit(CN.fp_sgn)(x)
    sgn0 = jax.jit(CN.fp_sgn0)(x)
    for i, v in enumerate(vals):
        assert bool(sgn[i]) == (v > P - v if v else False), (i, v)
        assert bool(sgn0[i]) == (v % 2 == 1), (i, v)

    pairs = [(0, 0), (0, 1), (1, 0), (P - 1, 0), (0, P - 1), (3, P - 1)] + [
        (int(rng.integers(0, 1 << 62)) ** 3 % P,
         int(rng.integers(0, 1 << 62)) ** 3 % P)
        for _ in range(6)
    ]
    x0 = enc_mont([p[0] for p in pairs])
    x1 = enc_mont([p[1] for p in pairs])
    s2 = jax.jit(lambda a, b: CN.fp2_sgn((a, b)))(x0, x1)
    s20 = jax.jit(lambda a, b: CN.fp2_sgn0((a, b)))(x0, x1)
    for i, v in enumerate(pairs):
        assert bool(s2[i]) == bool(GT.fp2_sgn(v)), (i, v)
        exp0 = (v[0] % 2) | ((v[0] == 0) and (v[1] % 2))
        assert bool(s20[i]) == bool(exp0), (i, v)


def test_fp2_sqrt_matches_host():
    rng = np.random.default_rng(2)
    squares = []
    for i in range(6):
        a = (int(rng.integers(1, 1 << 62)), int(rng.integers(0, 1 << 62)))
        squares.append(GT.fp2_sqr(a))
    # a1 == 0 cases: real square, real non-residue (sqrt purely imaginary)
    squares.append((4, 0))
    nonres = None
    v = 2
    while nonres is None:
        if GT.fp_sqrt(v) is None:
            nonres = (v, 0)
        v += 1
    cases = squares + [nonres, (5, 7)]  # last may or may not be square
    x0 = enc_mont([c[0] for c in cases])
    x1 = enc_mont([c[1] for c in cases])
    root, ok = jax.jit(lambda a, b: SQ.fp2_sqrt((a, b)))(x0, x1)
    r0, r1 = dec(root[0]), dec(root[1])
    for i, c in enumerate(cases):
        host = GT.fp2_sqrt(c)
        if host is None:
            assert not bool(ok[i]), (i, c)
        else:
            assert bool(ok[i]), (i, c)
            got = (r0[i], r1[i])
            assert GT.fp2_eq(GT.fp2_sqr(got), c), (i, c)


def test_g2_decompress_matches_host():
    sks = [B.keygen(b"ing-%d" % i) for i in range(8)]
    sigs = [B.sign(sk, b"m%d" % i) for i, sk in enumerate(sks)]
    comp = [GC.g2_compress(s) for s in sigs]
    n = 128
    xs, signs, infs, hosts = [], [], [], []
    i = 0
    while len(xs) < n:
        c = bytearray(comp[i % len(comp)])
        if i % 5 == 4:
            c[5] ^= 0x40  # corrupt x -> usually off-curve
        i += 1
        x1 = int.from_bytes(bytes([c[0] & 0x1F]) + bytes(c[1:48]), "big")
        x0 = int.from_bytes(bytes(c[48:]), "big")
        if x0 >= P or x1 >= P:
            # out-of-range x is rejected by the HOST byte-range check
            # before limbs ever reach the device; not a device case
            continue
        xs.append((x0, x1))
        signs.append(1 if c[0] & 0x20 else 0)
        infs.append(0)
        try:
            hosts.append(GC.g2_decompress(bytes(c)))
        except ValueError:
            hosts.append("invalid")
    flag_bits = jnp.asarray(
        np.stack([np.asarray(signs, np.int32), np.asarray(infs, np.int32)])
    )
    (mx0, mx1, y0, y1), ok = IN.g2_decompress_device(
        enc_plain([x[0] for x in xs]), enc_plain([x[1] for x in xs]), flag_bits
    )
    assert dec(mx0) == [x[0] for x in xs]  # mont x planes round-trip
    d0, d1 = dec(y0), dec(y1)
    for i, h in enumerate(hosts):
        if h == "invalid":
            assert not bool(ok[i]), i
        else:
            assert bool(ok[i]), i
            assert (d0[i], d1[i]) == h[1], i


def test_g1_keyvalidate_device():
    """Device KeyValidate vs the host: valid keys pass; off-curve,
    out-of-subgroup, infinity, and malformed keys fail."""
    import jax.numpy as jnp

    from lodestar_tpu.bls.ingest import encode_pubkey_planes

    valid = [GC.g1_compress(B.sk_to_pk(B.keygen(b"kv-%d" % i))) for i in range(6)]
    # out-of-subgroup: a random on-curve point (full group order w.h.p.)
    x = 5
    while GT.fp_sqrt((x * x * x + 4) % P) is None:
        x += 1
    y = GT.fp_sqrt((x * x * x + 4) % P)
    assert not GC.g1_subgroup_check((x, y))
    out_of_subgroup = GC.g1_compress((x, y))
    # off-curve x
    xc = x
    while GT.fp_sqrt((xc * xc * xc + 4) % P) is not None:
        xc += 1
    off_curve = bytearray(GC.g1_compress((x, y)))
    off = xc.to_bytes(48, "big")
    off_curve = bytes([0x80 | off[0]]) + off[1:]
    inf = bytes([0xC0]) + b"\x00" * 47
    keys = (valid + [out_of_subgroup, off_curve, inf]) * 15  # 135 keys
    keys = keys[:128]
    planes, flags, host_bad = encode_pubkey_planes(keys)
    from lodestar_tpu.kernels import ingest as IN2

    (mx, my), ok = IN2.g1_keyvalidate_device(
        jnp.asarray(planes), jnp.asarray(flags)
    )
    ok = np.asarray(ok) & ~host_bad
    for i, k in enumerate(keys):
        try:
            pt = GC.g1_decompress(k)
            expect = pt is not None and GC.g1_subgroup_check(pt)
        except ValueError:
            expect = False
        assert bool(ok[i]) == expect, (i, expect)
        if expect:
            assert (dec(mx)[i], dec(my)[i]) == pt, i


def test_register_compressed_device():
    from lodestar_tpu.bls.pubkey_table import PubkeyTable

    pts = [B.sk_to_pk(B.keygen(b"rc-%d" % i)) for i in range(5)]
    keys = [GC.g1_compress(p) for p in pts]
    t = PubkeyTable(capacity=8)
    idxs = t.register_compressed(keys)
    assert idxs == list(range(5))
    for i, p in enumerate(pts):
        assert t.host_affine(i) == p
    bad = PubkeyTable(capacity=8)
    with pytest.raises(ValueError):
        bad.register_compressed(keys[:2] + [b"\x00" * 48])


def test_hash_to_g2_device_matches_host():
    n = 128
    msgs = [b"ingest message %d" % (i % 7) for i in range(n)]
    u_pairs = [HC.hash_to_field_fp2(m, 2, HC.DST_G2) for m in msgs]
    host_map = {m: HC.hash_to_g2(m) for m in set(msgs)}
    sgn = np.zeros((2, n), np.int32)
    for i, (u0, u1) in enumerate(u_pairs):
        sgn[0, i] = HC._sgn0_fp2(u0)
        sgn[1, i] = HC._sgn0_fp2(u1)
    planes, ok = IN.hash_to_g2_device(
        enc_plain([u[0][0] for u in u_pairs]),
        enc_plain([u[0][1] for u in u_pairs]),
        enc_plain([u[1][0] for u in u_pairs]),
        enc_plain([u[1][1] for u in u_pairs]),
        jnp.asarray(sgn),
    )
    assert bool(np.asarray(ok).all())
    X0, X1, Y0, Y1, Z0, Z1 = (dec(p) for p in planes)
    for i, m in enumerate(msgs):
        z = (Z0[i], Z1[i])
        zi = GT.fp2_inv(z)
        zi2 = GT.fp2_sqr(zi)
        x = GT.fp2_mul((X0[i], X1[i]), zi2)
        y = GT.fp2_mul((Y0[i], Y1[i]), GT.fp2_mul(zi2, zi))
        assert (x, y) == host_map[m], i


def test_clear_cofactor_g2_sum_matches_host():
    """The G.3 clearing loop on jacobian planes, fed SSWU sums that are
    not in G2 before it, with Z != 1 (Z = 1 in lane 0, as the hash
    kernel feeds it), equals the host's plain h_eff multiplication."""
    rnd = random.Random(26)

    def jac(q, z):
        z2 = GT.fp2_sqr(z)
        return (GT.fp2_mul(q[0], z2), GT.fp2_mul(q[1], GT.fp2_mul(z2, z)), z)

    q0s, q1s, want = [], [], []
    for i in range(4):
        u0, u1 = HC.hash_to_field_fp2(b"cofactor %d" % i, 2, HC.DST_G2)
        q0 = HC.iso3_map(HC.map_to_curve_sswu_g2(u0))
        q1 = HC.iso3_map(HC.map_to_curve_sswu_g2(u1))
        s = GC.affine_add(GC.FP2_OPS, q0, q1)
        assert not GC.g2_subgroup_check(s)
        want.append(HC.clear_cofactor_g2(s))
        z0, z1 = [
            (1, 0) if i == 0 else (rnd.randrange(1, P), rnd.randrange(P))
            for _ in range(2)
        ]
        q0s.append(jac(q0, z0))
        q1s.append(jac(q1, z1))

    def planes(qs):
        return tuple(
            (enc_mont([q[c][0] for q in qs]), enc_mont([q[c][1] for q in qs]))
            for c in range(3)
        )

    X, Y, Z = jax.jit(IN.clear_cofactor_g2_sum)(planes(q0s), planes(q1s))
    (X0, X1), (Y0, Y1), (Z0, Z1) = [(dec(a), dec(b)) for a, b in (X, Y, Z)]
    for i in range(4):
        zi = GT.fp2_inv((Z0[i], Z1[i]))
        zi2 = GT.fp2_sqr(zi)
        x = GT.fp2_mul((X0[i], X1[i]), zi2)
        y = GT.fp2_mul((Y0[i], Y1[i]), GT.fp2_mul(zi2, zi))
        assert (x, y) == want[i], i
