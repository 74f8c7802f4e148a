"""Keys, signing and set verification for the benchmark, on the host.

A signature set is (pubkeys of its signers, 32-byte signing root,
96-byte compressed signature).  It verifies when the signature decodes
to a point on the curve and in G2's subgroup, the signers' pubkeys add
to a point other than infinity, and e(sum pk, H(root)) == e(G1, sig).
The signers' keys are the benchmark's own, so they are valid by
construction (the registry's KeyValidate is not repeated per set).
"""

from __future__ import annotations

import hashlib
from typing import List, Sequence, Tuple

from . import fields as F
from .curves import (
    FP2_OPS,
    FP_OPS,
    G1_GEN,
    affine_neg,
    g2_compress,
    g2_decompress,
    g2_subgroup_check,
    is_on_curve,
    multi_add,
    scalar_mul,
)
from .hash_to_curve import hash_to_g2
from .pairing import multi_pairing_is_one

NEG_G1_GEN = affine_neg(FP_OPS, G1_GEN)
R = F.R


def keygen(ikm: bytes) -> int:
    """A secret key from seed bytes (a hash reduced mod r, never 0)."""
    sk = int.from_bytes(hashlib.sha256(b"benchmark-keygen" + ikm).digest(), "big") % R
    return sk or 1


def sk_to_pk(sk: int):
    return scalar_mul(FP_OPS, G1_GEN, sk % R)


def sign_root(scalar: int, root: bytes) -> bytes:
    """Compressed scalar * H(root): the signature of `root` by the key
    whose secret is `scalar` (a sum of secrets for an aggregate)."""
    return g2_compress(scalar_mul(FP2_OPS, hash_to_g2(root), scalar % R))


def sign_many(jobs: Sequence[Tuple[int, bytes]]) -> List[bytes]:
    """`sign_root` over a chunk of (scalar, root); roots repeated within
    the chunk are hashed once.  Runs in the generator's worker
    processes."""
    hashed = {}
    out = []
    for scalar, root in jobs:
        if root not in hashed:
            hashed[root] = hash_to_g2(root)
        out.append(g2_compress(scalar_mul(FP2_OPS, hashed[root], scalar % R)))
    return out


def verify_set(pubkeys: Sequence, root: bytes, signature: bytes) -> bool:
    """The verdict of one signature set from its wire bytes."""
    try:
        sig = g2_decompress(signature)
    except ValueError:
        return False
    if sig is None or not is_on_curve(FP2_OPS, sig) or not g2_subgroup_check(sig):
        return False
    agg = multi_add(FP_OPS, list(pubkeys))
    if agg is None:
        return False
    return multi_pairing_is_one([(agg, hash_to_g2(root)), (NEG_G1_GEN, sig)])


def decodes(signature: bytes) -> bool:
    """True when the bytes decode to a curve point (no subgroup check,
    no pairing): the control's weakened verdict."""
    try:
        sig = g2_decompress(signature)
    except ValueError:
        return False
    return sig is not None
