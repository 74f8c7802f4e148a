"""Ground-truth (pure-Python) BLS12-381 tests: field towers, curve groups,
pairing bilinearity, hash-to-curve, and BLS signature semantics including
the random-linear-combination batch path the TPU backend reproduces."""

import random

import pytest

from lodestar_tpu.crypto import bls, fields as F, hash_to_curve as H, pairing as PR
from lodestar_tpu.crypto.curves import (
    FP2_OPS,
    FP_OPS,
    G1_GEN,
    G2_GEN,
    affine_add,
    affine_neg,
    g1_compress,
    g1_decompress,
    g2_compress,
    g2_decompress,
    g1_subgroup_check,
    g2_subgroup_check,
    is_on_curve,
    multi_add,
    scalar_mul,
)

rng = random.Random(0xB15)


def rand_fp():
    return rng.randrange(F.P)


def rand_fp2():
    return (rand_fp(), rand_fp())


class TestFields:
    def test_fp2_mul_inv_roundtrip(self):
        for _ in range(20):
            a = rand_fp2()
            assert F.fp2_eq(F.fp2_mul(a, F.fp2_inv(a)), F.FP2_ONE)

    def test_fp6_mul_inv_roundtrip(self):
        for _ in range(5):
            a = (rand_fp2(), rand_fp2(), rand_fp2())
            assert F.fp6_eq(F.fp6_mul(a, F.fp6_inv(a)), F.FP6_ONE)

    def test_fp12_mul_inv_roundtrip(self):
        for _ in range(5):
            a = (
                (rand_fp2(), rand_fp2(), rand_fp2()),
                (rand_fp2(), rand_fp2(), rand_fp2()),
            )
            assert F.fp12_eq(F.fp12_mul(a, F.fp12_inv(a)), F.FP12_ONE)

    def test_fp12_mul_associative_distributive(self):
        mk = lambda: (
            (rand_fp2(), rand_fp2(), rand_fp2()),
            (rand_fp2(), rand_fp2(), rand_fp2()),
        )
        a, b, c = mk(), mk(), mk()
        assert F.fp12_eq(
            F.fp12_mul(F.fp12_mul(a, b), c), F.fp12_mul(a, F.fp12_mul(b, c))
        )
        assert F.fp12_eq(
            F.fp12_mul(a, F.fp12_add(b, c)),
            F.fp12_add(F.fp12_mul(a, b), F.fp12_mul(a, c)),
        )

    def test_frobenius_is_pth_power(self):
        a = (
            (rand_fp2(), rand_fp2(), rand_fp2()),
            (rand_fp2(), rand_fp2(), rand_fp2()),
        )
        assert F.fp12_eq(F.fp12_frobenius(a), F.fp12_pow(a, F.P))

    def test_fp2_sqrt(self):
        for _ in range(10):
            a = rand_fp2()
            sq = F.fp2_sqr(a)
            s = F.fp2_sqrt(sq)
            assert s is not None
            assert F.fp2_eq(F.fp2_sqr(s), sq)

    @pytest.mark.parametrize("n", [0, 1, 2, 130])
    def test_fp_batch_inv_matches_fp_inv(self, n):
        # unreduced inputs too: fp_inv reduces them
        vals = [rand_fp() for _ in range(n - 1)] + [F.P + 7] * (n > 0)
        assert F.fp_batch_inv(vals) == [F.fp_inv(v) for v in vals]

    @pytest.mark.parametrize("where", [0, 5, 9])
    def test_fp_batch_inv_raises_on_a_zero_anywhere(self, where):
        vals = [rand_fp() or 1 for _ in range(10)]
        vals[where] = F.P  # zero mod p
        with pytest.raises(ZeroDivisionError):
            F.fp_batch_inv(vals)

    @pytest.mark.parametrize("n,calls", [(0, 0), (1, 1), (130, 1)])
    def test_fp_batch_inv_exponentiates_once(self, monkeypatch, n, calls):
        seen = []

        def counting_pow(*args):
            seen.append(args)
            return pow(*args)

        # shadows the builtin inside the module only
        monkeypatch.setattr(F, "pow", counting_pow, raising=False)
        F.fp_batch_inv([rand_fp() or 1 for _ in range(n)])
        assert len(seen) == calls


class TestCurves:
    def test_generators_on_curve_and_in_subgroup(self):
        assert is_on_curve(FP_OPS, G1_GEN)
        assert is_on_curve(FP2_OPS, G2_GEN)
        assert g1_subgroup_check(G1_GEN)
        assert g2_subgroup_check(G2_GEN)

    def test_group_laws_g1(self):
        a = scalar_mul(FP_OPS, G1_GEN, 123456789)
        b = scalar_mul(FP_OPS, G1_GEN, 987654321)
        assert is_on_curve(FP_OPS, a) and is_on_curve(FP_OPS, b)
        assert affine_add(FP_OPS, a, b) == scalar_mul(
            FP_OPS, G1_GEN, 123456789 + 987654321
        )
        assert affine_add(FP_OPS, a, affine_neg(FP_OPS, a)) is None

    def test_group_laws_g2(self):
        a = scalar_mul(FP2_OPS, G2_GEN, 31337)
        b = scalar_mul(FP2_OPS, G2_GEN, 271828)
        assert affine_add(FP2_OPS, a, b) == scalar_mul(
            FP2_OPS, G2_GEN, 31337 + 271828
        )

    def test_multi_add(self):
        ks = [rng.randrange(1, F.R) for _ in range(8)]
        pts = [scalar_mul(FP_OPS, G1_GEN, k) for k in ks]
        assert multi_add(FP_OPS, pts) == scalar_mul(FP_OPS, G1_GEN, sum(ks) % F.R)

    def test_g1_compression_roundtrip(self):
        for k in (1, 2, 31337, F.R - 1):
            p = scalar_mul(FP_OPS, G1_GEN, k)
            assert g1_decompress(g1_compress(p)) == p
        assert g1_decompress(g1_compress(None)) is None

    def test_g2_compression_roundtrip(self):
        for k in (1, 2, 31337, F.R - 1):
            p = scalar_mul(FP2_OPS, G2_GEN, k)
            assert g2_decompress(g2_compress(p)) == p
        assert g2_decompress(g2_compress(None)) is None

    def test_decompress_rejects_bad_x(self):
        with pytest.raises(ValueError):
            g1_decompress(b"\xff" * 48)  # x >= p


class TestPairing:
    def test_bilinearity(self):
        a, b = 6, 7
        e_ab = PR.pairing(
            scalar_mul(FP_OPS, G1_GEN, a), scalar_mul(FP2_OPS, G2_GEN, b)
        )
        e_base = PR.pairing(G1_GEN, G2_GEN)
        assert F.fp12_eq(e_ab, F.fp12_pow(e_base, a * b))
        assert not F.fp12_eq(e_base, F.FP12_ONE)

    def test_pairing_inverse(self):
        e1 = PR.pairing(G1_GEN, G2_GEN)
        e2 = PR.pairing(affine_neg(FP_OPS, G1_GEN), G2_GEN)
        assert F.fp12_eq(F.fp12_mul(e1, e2), F.FP12_ONE)

    def test_multi_pairing_cancellation(self):
        # e(aG1, G2) * e(-G1, aG2) == 1
        a = 424242
        pairs = [
            (scalar_mul(FP_OPS, G1_GEN, a), G2_GEN),
            (affine_neg(FP_OPS, G1_GEN), scalar_mul(FP2_OPS, G2_GEN, a)),
        ]
        assert PR.multi_pairing_is_one(pairs)

    def test_gt_element_has_order_r(self):
        e = PR.pairing(G1_GEN, G2_GEN)
        assert F.fp12_eq(F.fp12_pow(e, F.R), F.FP12_ONE)


class TestHashToCurve:
    def test_expand_message_xmd_shapes(self):
        out = H.expand_message_xmd(b"abc", b"TEST-DST", 256)
        assert len(out) == 256
        # deterministic
        assert out == H.expand_message_xmd(b"abc", b"TEST-DST", 256)
        assert out != H.expand_message_xmd(b"abd", b"TEST-DST", 256)

    def test_hash_to_g2_in_subgroup(self):
        for msg in (b"", b"hello", b"\x00" * 32):
            p = H.hash_to_g2(msg)
            assert is_on_curve(FP2_OPS, p)
            assert g2_subgroup_check(p)

    def test_hash_to_g2_deterministic_and_distinct(self):
        assert H.hash_to_g2(b"m1") == H.hash_to_g2(b"m1")
        assert H.hash_to_g2(b"m1") != H.hash_to_g2(b"m2")

    def test_hash_to_g1_in_subgroup(self):
        p = H.hash_to_g1(b"hello", b"G1-TEST-DST")
        assert is_on_curve(FP_OPS, p)
        assert g1_subgroup_check(p)


class TestBls:
    def test_sign_verify_roundtrip(self):
        sk = bls.keygen(b"validator-0")
        pk = bls.sk_to_pk(sk)
        msg = b"\x5a" * 32
        sig = bls.sign(sk, msg)
        assert bls.verify(pk, msg, sig)
        assert not bls.verify(pk, b"\x5b" * 32, sig)
        pk2 = bls.sk_to_pk(bls.keygen(b"validator-1"))
        assert not bls.verify(pk2, msg, sig)

    def test_bytes_roundtrip(self):
        sk = bls.keygen(b"validator-2")
        pk48 = g1_compress(bls.sk_to_pk(sk))
        msg = b"\x11" * 32
        sig96 = bls.sign_bytes(sk, msg)
        assert bls.verify_bytes(pk48, msg, sig96)
        assert not bls.verify_bytes(pk48, b"\x12" * 32, sig96)

    def test_fast_aggregate_verify(self):
        msg = b"\x22" * 32
        sks = [bls.keygen(bytes([i])) for i in range(4)]
        pks = [bls.sk_to_pk(sk) for sk in sks]
        agg_sig = bls.aggregate_signatures([bls.sign(sk, msg) for sk in sks])
        assert bls.fast_aggregate_verify(pks, msg, agg_sig)
        assert not bls.fast_aggregate_verify(pks[:3], msg, agg_sig)
        # KeyValidate: an infinity pubkey in the set must fail, not be skipped
        assert not bls.fast_aggregate_verify(pks + [None], msg, agg_sig)
        assert not bls.fast_aggregate_verify([], msg, agg_sig)

    def test_verify_multiple_signatures(self):
        sets = []
        for i in range(4):
            sk = bls.keygen(b"batch" + bytes([i]))
            msg = bytes([i]) * 32
            sets.append((bls.sk_to_pk(sk), msg, bls.sign(sk, msg)))
        assert bls.verify_multiple_signatures(sets, entropy=b"fixed")
        # one bad signature poisons the batch
        bad = list(sets)
        pk, msg, _sig = bad[2]
        bad[2] = (pk, msg, sets[1][2])
        assert not bls.verify_multiple_signatures(bad, entropy=b"fixed")
