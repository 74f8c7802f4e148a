"""Batched SHA-256 for merkleization.

The reference merkleizes through `@chainsafe/as-sha256`, a WASM module
whose core win is hashing many 64-byte sibling pairs per call
(digest64 / batchHash4UintArray64s — reference: SURVEY.md §2.3).  The
equivalent here is `hash_pairs`: one call hashes a whole tree level.

Two backends:
  - a C++ extension (`lodestar_tpu/native/sha256_batch.cpp`) doing the
    whole level in native code, loaded via ctypes when built;
  - a pure-hashlib fallback (OpenSSL C speed per hash, Python loop over
    pairs) that is always available.
"""

from __future__ import annotations

import ctypes
import hashlib
import os
from typing import Optional

from ..native import build

_NATIVE_PATH = build("libsha256_batch.so")

_native: Optional[ctypes.CDLL] = None
if _NATIVE_PATH is not None:
    try:
        _native = ctypes.CDLL(_NATIVE_PATH)
        _native.sha256_hash_pairs.argtypes = [
            ctypes.c_char_p,
            ctypes.c_char_p,
            ctypes.c_size_t,
        ]
        _native.sha256_hash_pairs.restype = None
    except OSError:  # pragma: no cover - load failure falls back to hashlib
        _native = None


def native_available() -> bool:
    return _native is not None


def _native_cutoff() -> int:
    """Minimum pair count routed to the native batch path.  Below it the
    ctypes call overhead beats the per-hash win; the default is measured
    by dev/microbench_htr.py --derive-cutoff, overridable with
    LODESTAR_TPU_SHA_NATIVE_CUTOFF."""
    env = os.environ.get("LODESTAR_TPU_SHA_NATIVE_CUTOFF")
    if env:
        try:
            return max(1, int(env))
        except ValueError:
            pass
    return 4


_CUTOFF = _native_cutoff()


def hash_pairs(data: bytes) -> bytes:
    """Hash consecutive 64-byte blocks: one tree level in one call.

    data: concatenation of n sibling pairs (64 bytes each).
    Returns the n concatenated 32-byte parent nodes.
    """
    n = len(data) // 64
    assert len(data) == 64 * n
    if _native is not None and n >= _CUTOFF:
        out = ctypes.create_string_buffer(32 * n)
        _native.sha256_hash_pairs(data, out, n)
        return out.raw
    sha = hashlib.sha256
    # memoryview slices borrow the buffer — the old bytes-slice-per-pair
    # fallback copied every 64-byte block before hashing it
    mv = memoryview(data)
    return b"".join(sha(mv[i * 64 : i * 64 + 64]).digest() for i in range(n))


def digest(data: bytes) -> bytes:
    return hashlib.sha256(data).digest()
