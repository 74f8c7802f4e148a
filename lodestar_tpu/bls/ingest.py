"""Host-side ingest bridge: wire bytes -> device-ready planes.

The reference pays deserialization + hashing per signature set inside
blst (worker.ts:30-50 uncompress; hashing inside verify).  Here:

  - `MessageCache` hashes signing roots to G2 in device batches
    (kernels/ingest.hash_to_g2_device) and memoizes the affine results —
    the TPU analog of SeenAttestationDatas' signing-root reuse
    (reference: chain/seenCache/seenAttestationData.ts), but keyed by
    root and shared across all set types,
  - `parse_signature_bytes` splits 96-byte compressed signatures into
    x-coordinate limb planes + (sign, infinity) flag bits, with the
    host-side wire checks (length, compression bit, padding, x < p);
    the y-recovery sqrt runs on device inside the verify pipeline
    (kernels/verify.verify_*_device_wire).
"""

from __future__ import annotations

import threading
from collections import OrderedDict
from contextlib import nullcontext
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from ..crypto import fields as GT
from ..crypto import hash_to_curve as HC
from ..kernels import layout as LY
from ..observability import timed_span

P = GT.P
BT = 128

_COMP = 0x80
_INF = 0x40
_SIGN = 0x20


class MessageCache:
    """signing_root -> affine G2 message point (ground-truth ints).

    Misses are hashed in one padded device batch per `get_many` call;
    an LRU bound keeps the cache sized to a few slots of distinct
    attestation/sync data.  With `metrics` (the verifier's
    BlsPoolMetrics), each device batch times its three host steps into
    `lodestar_bls_job_host_step_seconds{step=field|device_hash|affine}`
    and, when tracing is on, the `bls.msg.*` spans.
    """

    def __init__(
        self,
        max_entries: int = 4096,
        use_device: bool = True,
        dispatch=None,
        metrics=None,
    ):
        self.max_entries = max_entries
        self.use_device = use_device
        # dispatch(name, fn, args) runs the device hash: the verifier
        # passes its _device_call, which keeps the breaker's watchdog
        # off a width's first (compiling) dispatch
        self._dispatch = dispatch or (lambda _name, fn, args: fn(*args))
        self._steps = metrics.job_host_step if metrics is not None else None
        self._cache: "OrderedDict[bytes, Tuple]" = OrderedDict()
        # the service's dispatcher and resolver threads both reach the
        # cache (retry path); all OrderedDict mutation happens under here
        self._lock = threading.Lock()
        self.hits = 0
        self.misses = 0

    def get_many(self, roots: Sequence[bytes]) -> List[Tuple]:
        with self._lock:
            resolved = {}
            missing = []
            for r in roots:
                if r in self._cache:
                    # snapshot hits NOW: inserting a large miss set below
                    # may evict them before the final answer is built
                    resolved[r] = self._cache[r]
                    self._cache.move_to_end(r)
                    self.hits += 1
                elif r not in missing:
                    missing.append(r)
            if missing:
                self.misses += len(missing)
                if self.use_device:
                    fetched = self._hash_batch_device(missing)
                else:
                    fetched = {r: HC.hash_to_g2(r) for r in missing}
                for r, pt in fetched.items():
                    self._store(r)
                    self._cache[r] = pt
                resolved.update(fetched)
            return [resolved[r] for r in roots]

    def _store(self, root: bytes) -> None:
        while len(self._cache) >= self.max_entries:
            self._cache.popitem(last=False)

    def _step(self, span: str, step: str):
        if self._steps is None:
            return nullcontext()
        return timed_span(span, (self._steps, step))

    def _hash_batch_device(self, roots: List[bytes]):
        import jax.numpy as jnp

        from ..kernels import ingest as IG

        n = len(roots)
        pad = (-n) % BT
        roots_p = list(roots) + [roots[-1]] * pad
        with self._step("bls.msg.field", "field"):
            us = [HC.hash_to_field_fp2(r, 2, HC.DST_G2) for r in roots_p]
            sgn = np.zeros((2, len(roots_p)), np.int32)
            for i, (u0, u1) in enumerate(us):
                sgn[0, i] = HC._sgn0_fp2(u0)
                sgn[1, i] = HC._sgn0_fp2(u1)
            enc = lambda vals: jnp.asarray(LY.encode_plain_batch(vals))
            args = (
                enc([u[0][0] for u in us]),
                enc([u[0][1] for u in us]),
                enc([u[1][0] for u in us]),
                enc([u[1][1] for u in us]),
                jnp.asarray(sgn),
            )
        # the host waits here for the hash and for whatever the device
        # stream holds ahead of it
        with self._step("bls.msg.device_hash", "device_hash"):
            planes, ok = self._dispatch("hash_to_g2", IG.hash_to_g2_device, args)
            ok = np.asarray(ok)
            planes = [np.asarray(p) for p in planes]
        assert bool(ok.all()), "device hash_to_g2 flagged failure"
        with self._step("bls.msg.affine", "affine"):
            # the live lanes only: padding repeats the last root
            X0, X1, Y0, Y1, Z0, Z1 = (LY.decode_batch(p[:, :n]) for p in planes)
            # z^-1 = conj(z) / norm(z), every norm inverted in one batch
            ninv = GT.fp_batch_inv([z0 * z0 + z1 * z1 for z0, z1 in zip(Z0, Z1)])
            fetched = {}
            for i, r in enumerate(roots):
                zi = (Z0[i] * ninv[i] % P, -Z1[i] * ninv[i] % P)
                zi2 = GT.fp2_sqr(zi)
                x = GT.fp2_mul((X0[i], X1[i]), zi2)
                y = GT.fp2_mul((Y0[i], Y1[i]), GT.fp2_mul(zi2, zi))
                fetched[r] = (x, y)
        return fetched


def parse_signature_bytes(sig: bytes) -> Tuple[int, int, int, int, bool]:
    """96B compressed G2 -> (x0, x1, sign, inf, wire_ok).

    wire_ok=False marks malformed encodings (wrong length, missing
    compression bit, out-of-range x, bad infinity padding) — the set
    then fails without touching the device sqrt.  Mirrors the host
    oracle's checks (crypto/curves.py g2_decompress).
    """
    if len(sig) != 96:
        return 0, 0, 0, 0, False
    flags = sig[0]
    if not flags & _COMP:
        return 0, 0, 0, 0, False
    if flags & _INF:
        if flags & (_SIGN | 0x1F) or any(sig[1:]):
            return 0, 0, 0, 0, False
        return 0, 0, 0, 1, True
    x1 = int.from_bytes(bytes([flags & 0x1F]) + sig[1:48], "big")
    x0 = int.from_bytes(sig[48:], "big")
    if x0 >= P or x1 >= P:
        return 0, 0, 0, 0, False
    return x0, x1, 1 if flags & _SIGN else 0, 0, True


def parse_pubkey_bytes(pk: bytes) -> Tuple[int, int, int, bool]:
    """48B compressed G1 -> (x, sign, inf, wire_ok)."""
    if len(pk) != 48:
        return 0, 0, 0, False
    flags = pk[0]
    if not flags & _COMP:
        return 0, 0, 0, False
    if flags & _INF:
        if flags & (_SIGN | 0x1F) or any(pk[1:]):
            return 0, 0, 0, False
        return 0, 0, 1, True
    x = int.from_bytes(bytes([flags & 0x1F]) + pk[1:], "big")
    if x >= P:
        return 0, 0, 0, False
    return x, 1 if flags & _SIGN else 0, 0, True


def encode_pubkey_planes(
    keys: Sequence[bytes],
) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Pubkeys -> (x_planes, flag_bits[2, n], host_bad[n]) for the device
    KeyValidate kernel (kernels/ingest.g1_keyvalidate_device)."""
    n = len(keys)
    xs = []
    flags = np.zeros((2, n), np.int32)
    host_bad = np.zeros((n,), bool)
    for i, pk in enumerate(keys):
        x, sign, inf, ok = parse_pubkey_bytes(pk)
        xs.append(x)
        flags[0, i] = sign
        flags[1, i] = inf if ok else 1
        host_bad[i] = not ok
    return LY.encode_plain_batch(xs), flags, host_bad


def encode_wire_planes(
    sigs: Sequence[bytes], n: int
) -> Tuple[np.ndarray, np.ndarray, np.ndarray, np.ndarray]:
    """Signatures -> (x0_planes, x1_planes, flag_bits[2, n], host_bad[n]).

    Malformed encodings get the infinity flag so the device marks the
    lane sig_bad; host_bad distinguishes them from honest infinity for
    accounting.
    """
    x0s, x1s = [], []
    flags = np.zeros((2, n), np.int32)
    host_bad = np.zeros((n,), bool)
    for i, sig in enumerate(sigs):
        x0, x1, sign, inf, ok = parse_signature_bytes(sig)
        x0s.append(x0)
        x1s.append(x1)
        flags[0, i] = sign
        flags[1, i] = inf if ok else 1
        host_bad[i] = not ok
    pad = n - len(sigs)
    x0s.extend([0] * pad)
    x1s.extend([0] * pad)
    flags[1, len(sigs):] = 1  # padding lanes: inert
    return (
        LY.encode_plain_batch(x0s),
        LY.encode_plain_batch(x1s),
        flags,
        host_bad,
    )
