"""Limb layout and Montgomery constants for the pallas field engine.

Representation
--------------
A GF(p) element is 33 little-endian limbs of 12 bits in SIGNED int32,
stored TRANSPOSED relative to the round-1 `ops/` layer: device arrays are
``[..., NL, B]`` with the limb axis second-to-last (sublanes) and the
batch axis last (lanes).  Montgomery radix R = 2^396 (NL * LIMB_BITS).

Design rationale (vs the round-1 `ops/` layer):
  * int32 SIGNED limbs: subtraction/negation are plain vector ops — no
    borrow chains, no conditional subtract, no offset constants.  The
    carry "fold" (t & 4095) + (t >> 12 shifted up) is value-preserving
    for two's-complement ints with arithmetic shift.
  * R = 2^396, i.e. R/p ~ 2^15 slack: REDC maps |v| to |v|/R + p, so the
    value class below is closed under long chains of lazy adds/subs with
    NO reduction logic in the hot path.
  * Transposed layout: batch rides the 128 vector lanes; limb-shift
    operations are sublane shifts; per-limb broadcast multiplies cost
    ~1 ns/element inside a pallas kernel (microbench_product.py).

Bound discipline (kernels rely on it; tests/test_kernels_core.py checks
it empirically against exact integer mirrors):
  L-bound (limbs):  public values have limbs in [-4103, 4103]; the TOP
      limb is special: `fold` leaves it unmasked (value-preserving for
      every input), so it can drift a little beyond 4095 — the T-bound
      keeps it small enough for column exactness.
  T-bound (top limb): public |limb 32| <= ~300.  Closure: p < 2^384 so
      p's limb 32 is zero and REDC's u-columns end at 63; a product of
      two T-bounded inputs has |column 64| <= (8*300)^2 < 2^23 and
      |column 65| ~ 2^11, so the redc output's top limb is ~2^5; 8-term
      sums keep it small.  Consequence: mul_small is capped at |k| <= 8.
  V-bound (values): public |v| < 2^390.
      add/sub chains of <= 8 public values: |v| < 2^393.
      redc of a product of two such: |v| <= 2^786/2^396 + p < 2^390. OK
      tower combines: <= 8-term sums of products of (2-term sums of
      publics): |v| <= 8 * (2^391)^2 = 2^785 -> redc out < 2^390.    OK
  Column exactness: mul inputs have |limbs| <= 5700
      => |columns| <= 33 * 5700^2 < 2^30 — exact in int32, and redc's
      t + u stays < 2^31.

Host codecs here are numpy-only (no jax import) so they are usable from
tests and the service layer without touching a device.
"""

from __future__ import annotations

import numpy as np

from ..crypto import fields as GT

NL = 33  # limbs per element
LIMB_BITS = 12
LIMB_MASK = (1 << LIMB_BITS) - 1
NC = 2 * NL  # columns of a full product
R_BITS = NL * LIMB_BITS  # 396
P = GT.P
R = 1 << R_BITS
R_MOD_P = R % P
R2 = R * R % P
NPRIME = (-pow(P, -1, R)) % R  # -p^-1 mod R
R_INV = pow(R, -1, P)

DTYPE = np.int32


def to_limbs(x: int, n: int = NL) -> np.ndarray:
    """Python int (nonnegative canonical) -> limb vector int32[n]."""
    assert 0 <= x < 1 << (LIMB_BITS * n)
    return np.array(
        [(x >> (LIMB_BITS * i)) & LIMB_MASK for i in range(n)], dtype=DTYPE
    )


def from_limbs(arr) -> int:
    """Limb vector (redundant/signed limbs OK) -> Python int."""
    a = np.asarray(arr)
    assert a.ndim == 1
    return sum(int(a[i]) << (LIMB_BITS * i) for i in range(a.shape[0]))


def encode_batch(xs) -> np.ndarray:
    """Plain ints -> Montgomery transposed batch int32[NL, len(xs)]."""
    return np.stack([to_limbs(x % P * R_MOD_P % P) for x in xs], axis=-1)


_LIMB_WEIGHTS = np.array([1 << (LIMB_BITS * i) for i in range(NL)], dtype=object)


def decode_batch(arr) -> list:
    """Transposed device limbs [NL, B] (lazy form OK) -> plain ints.

    One object-dtype dot over all lanes: exact for signed limbs, as
    `from_limbs` is."""
    a = np.asarray(arr).astype(object)
    return [v * R_INV % P for v in (_LIMB_WEIGHTS[: a.shape[0]] @ a).tolist()]


def const_mont(x: int) -> np.ndarray:
    """Host Montgomery constant limb vector int32[NL] for a plain int."""
    return to_limbs(x % P * R_MOD_P % P)


# Powers 2^11..2^0 for packing 12 MSB-first bits into a limb.
_BITW = (1 << np.arange(11, -1, -1).astype(np.int32)).astype(np.int32)


def encode_plain_batch(vals) -> np.ndarray:
    """Canonical ints -> PLAIN (non-Montgomery) limbs int32[NL, n], fast.

    Vectorized: int.to_bytes (C speed) -> numpy unpackbits -> 12-bit limb
    packing.  ~100x faster than the per-limb python path; the Montgomery
    conversion happens on device (kernels/verify.py _k_mont).  This is
    the ingest hot path standing in for the reference's serialized-set
    handoff ({pubkey, signingRoot, signature} bytes,
    packages/beacon-node/src/chain/bls/multithread/index.ts:177).
    """
    n = len(vals)
    buf = b"".join(int(v).to_bytes(48, "big") for v in vals)
    raw = np.frombuffer(buf, np.uint8).reshape(n, 48)
    bits = np.unpackbits(raw, axis=1)  # MSB-first, 384 bits
    # limb j (little-endian) = value bits [12j, 12j+12) = bit columns
    # [384-12(j+1), 384-12j) in MSB-first order
    limbs = bits.reshape(n, 32, 12) @ _BITW  # [n, 32], limb 31 first? no:
    # reshape groups MSB-first: group g covers value bits 384-12(g+1)..;
    # so limb j = group (31 - j)
    limbs = limbs[:, ::-1]
    out = np.zeros((NL, n), DTYPE)
    out[:32] = limbs.T.astype(DTYPE)
    return out


# ---------------------------------------------------------------------------
# Baked kernel constants (python int lists — inlined as scalar literals,
# no pallas input plumbing needed)
# ---------------------------------------------------------------------------

P_LIMBS = [int(v) for v in to_limbs(P)]
NPRIME_LIMBS = [int(v) for v in to_limbs(NPRIME)]
MONT_ONE = to_limbs(R_MOD_P)
MONT_R2 = to_limbs(R2)
ONE_PLAIN = to_limbs(1)
ZERO_LIMBS = np.zeros(NL, DTYPE)
