"""Range sync: the traffic, the loop that offers it, and what the run
compares.

The traffic file gives the mix; the configuration gives the sizes.  A
pool is `pool_batches` batches of blocks, one batch `epochs_per_batch`
epochs of `slots_per_epoch` blocks.  Each block carries the sets the
traffic's `block` counts, with `max_attestations` attestations:

- proposer: the block's signing root, signed by its proposer (K=1);
- randao: the epoch's RANDAO root, signed by the same proposer (K=1;
  one root per epoch, as the spec has it);
- attestations: each over a committee of `committee_size` validators
  drawn from the whole registry, of which `participation` sign (the
  same count on every seed), each with its own data root;
- sync_aggregate: `participation` of the `sync_committee_size` members
  sign the block's sync root.

An aggregate's signature is (sum of its signers' secret keys) * H(root):
one scalar multiplication per set.  `tampered_blocks_per_batch` blocks
of every batch carry one bad set: one block in each equal stretch of
the batch, at even and odd places in turn, so that both halves of a
batch and both of its interleavings hold one; the bad set in the first
half of its block's sets and in the second half, in turn; the kinds in
turn from `tamper_kinds`: `wrong_key` (signed by the sum plus
one), `wrong_root` (signed over another root) or `flipped_bit` (one bit
of the compressed signature flipped).  Every size and count is fixed by
the files; the seed only draws which validators, roots, keys and
positions.

The loop is closed: one batch in flight, one call per block with
default options (non-batchable), as `chain/block_processor.py` submits
a segment; the next batch goes in when the last verdict of this one is
in.  The pool cycles in a fixed order.

After the window every answer given for a tampered block, and for
`honest_reference_blocks` honest blocks drawn from the seed, is
compared with the plain reference's verdict over the block's exact wire
bytes; answers that never came count with the wrong ones.
"""

from __future__ import annotations

import time
from typing import Dict, List, Tuple

import numpy as np

from benchmark import check
from benchmark.reference import bls as RB

# (indices, signing root, secret scalar, tamper kind or None)
SetSpec = Tuple[Tuple[int, ...], bytes, int, str]


def _scalar(indices, sks: List[int]) -> int:
    counts = np.bincount(np.asarray(indices) % len(sks), minlength=len(sks))
    return sum(int(c) * sk for c, sk in zip(counts, sks) if c) % RB.R


def blocks_per_batch(cfg: dict) -> int:
    return cfg["epochs_per_batch"] * cfg["slots_per_epoch"]


def build_pool(cfg: dict, traffic: dict, rng: np.random.Generator, sks: List[int]):
    """The pool's blocks as lists of SetSpec, and for each block its
    tamper (kind, position of the bad set) or None.  Cheap: no curve
    arithmetic."""
    v = cfg["validators"]
    per_batch = blocks_per_batch(cfg)
    n_blocks = traffic["pool_batches"] * per_batch
    shape = traffic["block"]
    part = traffic["participation"]
    k_att = round(part * cfg["committee_size"])
    k_sync = round(part * cfg["sync_committee_size"])
    sync_members = rng.choice(v, cfg["sync_committee_size"], replace=False)
    randao: Dict[int, bytes] = {}
    blocks: List[List[SetSpec]] = []
    for b in range(n_blocks):
        epoch = b // cfg["slots_per_epoch"]
        if epoch not in randao:
            randao[epoch] = rng.bytes(32)
        proposer = int(rng.integers(v))
        sets: List[Tuple[Tuple[int, ...], bytes]] = []
        for _ in range(shape["proposer"]):
            sets.append(((proposer,), rng.bytes(32)))
        for _ in range(shape["randao"]):
            sets.append(((proposer,), randao[epoch]))
        for _ in range(cfg["max_attestations"]):
            committee = rng.choice(v, cfg["committee_size"], replace=False)
            sets.append((tuple(sorted(int(i) for i in committee[:k_att])), rng.bytes(32)))
        for _ in range(shape["sync_aggregate"]):
            signers = rng.choice(sync_members, k_sync, replace=False)
            sets.append((tuple(sorted(int(i) for i in signers)), rng.bytes(32)))
        blocks.append([(idx, root, _scalar(idx, sks), None) for idx, root in sets])
    kinds = traffic["tamper_kinds"]
    k = traffic["tampered_blocks_per_batch"]
    tamper: List[Tuple[str, int]] = [None] * n_blocks
    turn = 0
    for batch in range(traffic["pool_batches"]):
        for j in range(k):
            stretch = range(j * per_batch // k, (j + 1) * per_batch // k)
            b = batch * per_batch + int(rng.choice([p for p in stretch if p % 2 == j % 2]))
            n = len(blocks[b])
            lo, hi = (0, n // 2) if turn % 2 == 0 else ((n + 1) // 2, n)
            pos = int(rng.integers(lo, hi))
            kind = kinds[turn % len(kinds)]
            turn += 1
            idx, root, scalar, _ = blocks[b][pos]
            blocks[b][pos] = (idx, root, scalar, kind)
            tamper[b] = (kind, pos)
    return blocks, tamper


def signing_jobs(blocks, rng: np.random.Generator) -> List[Tuple[int, bytes]]:
    """One (scalar, root to sign) per set, in pool order."""
    jobs = []
    for sets in blocks:
        for _idx, root, scalar, kind in sets:
            if kind == "wrong_key":
                scalar += 1
            elif kind == "wrong_root":
                root = rng.bytes(32)
            jobs.append((scalar, root))
    return jobs


def flip(signature: bytes) -> bytes:
    """One bit of the compressed x coordinate flipped."""
    b = bytearray(signature)
    b[60] ^= 0x10
    return bytes(b)


def _wire_set(idx, root, sig):
    from lodestar_tpu.bls.signature_set import WireSignatureSet

    if len(idx) == 1:
        return WireSignatureSet.single(idx[0], root, sig)
    return WireSignatureSet.aggregate(idx, root, sig)


def wire_blocks(blocks, signatures: List[bytes]):
    """The node's wire sets for each block, from the signed jobs."""
    out = []
    pos = 0
    for sets in blocks:
        wire = []
        for idx, root, _scalar, kind in sets:
            sig = signatures[pos]
            pos += 1
            if kind == "flipped_bit":
                sig = flip(sig)
            wire.append(_wire_set(idx, root, sig))
        out.append(wire)
    return out


def warmup_block(blocks, rng: np.random.Generator):
    """A block of the pool's shape (same sets, same signer counts) over
    fresh roots, every signature the compressed G2 generator: it drives
    every program the pool drives, and its verdict is not read."""
    from benchmark.reference import curves as RC

    sig = RC.g2_compress(RC.G2_GEN)
    return [_wire_set(idx, rng.bytes(32), sig) for idx, _root, _scalar, _kind in blocks[0]]


def closed_loop(service, wire, batches, t_open, seconds, drain_s, mark=None, span=None):
    """Submit one batch of blocks, one call per block; the next batch
    goes in when the last verdict of this one is in.  Returns the
    answers (block, verdict or None, seconds after open) and the blocks
    submitted.  The window closes at t_open + seconds, mid-batch if it
    falls there; that batch's answers are still awaited, up to drain_s
    past the close.  `mark` = (s, fn): fn() runs once, s seconds after
    the open or at the close, whichever comes first.  `span(name)`, when
    given, opens a profiler span around each submission and each wait."""
    from concurrent.futures import wait
    from contextlib import nullcontext

    span = span or (lambda _name: nullcontext())
    t_close = t_open + seconds
    t_mark, todo = (t_open + mark[0], [mark[1]]) if mark else (t_close, [])

    def fire():
        while todo:
            todo.pop()()

    stamps = {}
    submitted = []
    futures = []
    cycle = 0
    while time.monotonic() < t_close:
        ids = batches[cycle % len(batches)]
        cycle += 1
        pending = set()
        with span("bench.submit_batch"):
            for b in ids:
                fut = service.verify_signature_sets_async(wire[b])
                key = len(futures)
                fut.add_done_callback(lambda _f, key=key: stamps.__setitem__(key, time.monotonic()))
                futures.append((b, fut))
                pending.add(fut)
                submitted.append(b)
        with span("bench.await_batch"):
            while pending:
                now = time.monotonic()
                if now >= t_mark:
                    fire()
                if now >= t_close:
                    break
                horizon = t_close if not todo else min(t_close, t_mark)
                _done, pending = wait(pending, timeout=horizon - now)
        if pending:
            fire()
            wait(pending, timeout=max(t_close + drain_s - time.monotonic(), 0))
    fire()
    answers = []
    for key, (b, fut) in enumerate(futures):
        verdict = None
        if fut.done() and fut.exception() is None:
            verdict = bool(fut.result())
        answers.append((b, verdict, stamps.get(key, float("inf")) - t_open))
    return answers, submitted


def choose_blocks(answered, tamper, honest: int, rng: np.random.Generator) -> List[int]:
    """Every tampered block answered, and `honest` honest ones drawn from
    the seed."""
    answered = sorted(set(answered))
    good = [b for b in answered if tamper[b] is None]
    rng.shuffle(good)
    return sorted([b for b in answered if tamper[b] is not None] + good[:honest])


class Loop:
    """The range-sync cell's traffic and loop, as the harness drives it."""

    def __init__(self, cfg: dict, traffic: dict, rng: np.random.Generator, sks: List[int]):
        self.traffic = traffic
        self.n_keys = len(sks)
        self.blocks, self.tamper = build_pool(cfg, traffic, rng, sks)
        self.signing_jobs = signing_jobs(self.blocks, rng)
        per_batch = blocks_per_batch(cfg)
        self.batches = [list(range(i, i + per_batch)) for i in range(0, len(self.blocks), per_batch)]
        self.shapes = {tuple(s) for s in traffic["shapes"]}
        self.wire = None

    def describe(self) -> str:
        return (
            f"{len(self.blocks)} pool blocks, {sum(len(b) for b in self.blocks)} sets, "
            f"{sum(t is not None for t in self.tamper)} tampered blocks"
        )

    def first_call(self, service, rng: np.random.Generator, timeout: float) -> None:
        """One block of the pool's shape: compiles or loads every program."""
        service.verify_signature_sets_async(warmup_block(self.blocks, rng)).result(timeout=timeout)

    def attach(self, signatures: List[bytes]) -> None:
        self.wire = wire_blocks(self.blocks, signatures)

    def settle(self, service, blocks: int, timeout: float) -> None:
        """Pool blocks with jobs in flight, as in the window."""
        for f in [service.verify_signature_sets_async(self.wire[b]) for b in self.batches[-1][-blocks:]]:
            f.result(timeout=timeout)

    def run(self, service, t_open: float, seconds: float, drain_s: float, mark=None, span=None) -> None:
        self.seconds = seconds
        self.answers, self.submitted = closed_loop(
            service, self.wire, self.batches, t_open, seconds, drain_s, mark, span
        )

    def report(self, traced_s: float) -> dict:
        """What the window did: `attempted`, `failed` and the readers'
        context, in signature sets; `log`, a line for standard output."""
        sizes = [len(b) for b in self.wire]
        answered = [(b, v, t) for b, v, t in self.answers if t <= self.seconds]
        sets_in_window = sum(sizes[b] for b, _v, _t in answered)
        return {
            "attempted": sum(sizes[b] for b in self.submitted),
            "failed": sum(sizes[b] for b, v, _t in self.answers if v is None),
            "ctx": {
                "sets_in_window": sets_in_window,
                "sets_traced": sum(sizes[b] for b, _v, t in answered if t <= traced_s),
                "answers": answered,
            },
            "log": (
                f"{len(answered)} blocks ({sets_in_window} sets) answered in {self.seconds}s, "
                f"{len(self.submitted)} submitted"
            ),
        }

    def check(self, executor, rng: np.random.Generator) -> dict:
        """The comparison with the plain reference: `numbers`, each with
        its value and limit, `compared` and `log`."""
        honest = self.traffic["honest_reference_blocks"]
        chosen = choose_blocks(
            [b for b, v, _t in self.answers if v is not None], self.tamper, honest, rng
        )
        hints = {b: self.tamper[b][1] for b in chosen if self.tamper[b] is not None}
        reference = check.reference_verdicts(executor, self.wire, chosen, self.n_keys, hints)
        result = check.compare([(b, v) for b, v, _t in self.answers], reference, len(self.submitted))
        return {
            "numbers": {"wrong_or_missing": {"value": result["wrong"] + result["missing"], "limit": 0}},
            "compared": result["compared"],
            "log": (
                f"blocks {chosen} (tampered {[self.tamper[b] for b in chosen]}), verdicts "
                f"{[reference[b] for b in chosen]}, {result['compared']} answers compared"
            ),
        }
