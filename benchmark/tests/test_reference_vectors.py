"""The plain reference against published known-answer vectors, so that
it does not merely agree with the program it was copied from.

- expand_message_xmd with SHA-256: RFC 9380, appendix K.1
  (DST "QUUX-V01-CS02-with-expander-SHA256-128", 32 bytes out);
- BLS sign and verify with the Ethereum proof-of-possession ciphersuite
  (hash-to-G2 with BLS12381G2_XMD:SHA-256_SSWU_RO_, sgn0, the cofactor
  clearing and point compression all feed the signature bytes):
  ethereum/bls12-381-tests, `sign` cases for the secret key
  0x263dbd79...40e3 and its public key.
"""

import pytest

from benchmark.reference import bls as RB
from benchmark.reference import curves as RC
from benchmark.reference import hash_to_curve as RH

XMD_DST = b"QUUX-V01-CS02-with-expander-SHA256-128"
SK = int("263dbd792f5b1be47ed85f8938c0f29586af0d3ac7b977f21c278fe1462040e3", 16)
PK = bytes.fromhex(
    "a491d1b0ecd9bb917989f0e74f0dea0422eac4a873e5e2644f368dffb9a6e20f"
    "d6e10c1b77654d067c0618f6e5a7f79a"
)
SIGNATURES = {
    bytes(32): "b6ed936746e01f8ecf281f020953fbf1f01debd5657c4a383940b020b26507f6"
    "076334f91e2366c96e9ab279fb5158090352ea1c5b0c9274504f4f0e7053af24802e51e4"
    "568d164fe986834f41e55c8e850ce1f98458c0cfc9ab380b55285a55",
    bytes([0x56]) * 32: "882730e5d03f6b42c3abc26d3372625034e1d871b65a8a6b900a56dae22da98a"
    "bbe1b68f85e49fe7652a55ec3d0591c20767677e33e5cbb1207315c41a9ac03be39c2e76"
    "68edc043d6cb1d9fd93033caa8a1c5b0e84bedaeb6c64972503a43eb",
    bytes([0xAB]) * 32: "91347bccf740d859038fcdcaf233eeceb2a436bcaaee9b2aa3bfb70efe29dfb2"
    "677562ccbea1c8e061fb9971b0753c240622fab78489ce96768259fc01360346da5b9f57"
    "9e5da0d941e4c6ba18a0e64906082375394f337fa1af2b7127b0d121",
}


@pytest.mark.parametrize(
    "msg, want",
    [
        (b"", "68a985b87eb6b46952128911f2a4412bbc302a9d759667f87f7a21d803f07235"),
        (b"abc", "d8ccab23b5985ccea865c6c97b6e5b8350e794e603b4b97902f53a8a0d605615"),
        (b"abcdef0123456789", "eff31487c770a893cfb36f912fbfcbff40d5661771ca4b2cb4eafe524333f5c1"),
    ],
)
def test_expand_message_xmd(msg, want):
    assert RH.expand_message_xmd(msg, XMD_DST, 32).hex() == want


def test_public_key():
    assert RC.g1_compress(RB.sk_to_pk(SK)) == PK


@pytest.mark.parametrize("msg", sorted(SIGNATURES))
def test_sign_and_verify(msg):
    sig = bytes.fromhex(SIGNATURES[msg])
    assert RB.sign_root(SK, msg) == sig
    pk = RB.sk_to_pk(SK)
    assert RB.verify_set([pk], msg, sig)
    assert not RB.verify_set([pk], bytes([0x01]) + msg[1:], sig)
