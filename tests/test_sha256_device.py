"""Device merkleization equivalence (ISSUE 16).

The batched SHA-256 kernels (kernels/sha256.py) and the supervised
backend seams (ssz/device_backend.py) must be BIT-IDENTICAL to the
host hash path for every input shape — the whole soundness story of
device-side state roots is "same bytes out, or the host path runs".
Randomized equivalence here runs under JAX_PLATFORMS=cpu (conftest),
so the kernels are exercised through real XLA, just not on a TPU.

Covers: hash_pairs_device vs hashlib, the hash_level padding/bucket
seam, the one-dispatch forest sweep through ChunkTree, the validator
leaf-packing kernel vs a host merkleize reference, fault degradation,
and a ChunkTree property test that interleaves backend switches
(host -> device -> host mid-update stream, both directions).
"""

import hashlib

import numpy as np
import pytest

from lodestar_tpu.bls.supervisor import DeviceSupervisor
from lodestar_tpu.ssz import ChunkTree, merkleize_chunks
from lodestar_tpu.ssz import device_backend as DB
from lodestar_tpu.ssz.hasher import hash_pairs
from lodestar_tpu.ssz.merkle_tree import hash_pairs_plane
from lodestar_tpu.utils.metrics import Registry

jax = pytest.importorskip("jax")

from lodestar_tpu.kernels import sha256 as SK  # noqa: E402


def _make_backend(min_level_rows: int = 1) -> DB.DeviceMerkleBackend:
    reg = Registry()
    sup = DeviceSupervisor(registry=reg, auto_probe=False, enabled=True)
    return DB.DeviceMerkleBackend(
        supervisor=sup,
        registry=reg,
        min_level_rows=min_level_rows,
        use_export=False,
    )


@pytest.fixture
def backend():
    b = _make_backend()
    DB.set_backend(b)
    yield b
    DB.reset_backend()


def _host_digests(pairs: np.ndarray) -> np.ndarray:
    return np.frombuffer(
        b"".join(hashlib.sha256(row.tobytes()).digest() for row in pairs),
        np.uint8,
    ).reshape(-1, 32)


# -- the raw kernel vs hashlib ----------------------------------------------


@pytest.mark.parametrize("n", [1, 5, 33])
def test_hash_pairs_device_matches_hashlib(n):
    rng = np.random.default_rng(n)
    pairs = rng.integers(0, 256, (n, 64), dtype=np.uint8)
    out = np.asarray(SK.hash_pairs_device(SK.pairs_to_blocks(pairs)))
    got = SK.digests_to_bytes(out)
    assert got.shape == (n, 32)
    np.testing.assert_array_equal(got, _host_digests(pairs))
    # and the host batch hasher agrees with hashlib too (both seams)
    assert hash_pairs(pairs.tobytes()) == got.tobytes()


def test_byte_conversion_roundtrip():
    rng = np.random.default_rng(0)
    pairs = rng.integers(0, 256, (9, 64), dtype=np.uint8)
    blocks = SK.pairs_to_blocks(pairs)
    assert blocks.dtype == np.uint32 and blocks.shape == (9, 16)
    # big-endian words: block word 0 is bytes 0..3 of the pair
    assert int(blocks[0, 0]) == int.from_bytes(pairs[0, :4].tobytes(), "big")
    rows = rng.integers(0, 256, (9, 32), dtype=np.uint8)
    np.testing.assert_array_equal(
        SK.digests_to_bytes(SK.rows_to_words(rows)), rows
    )
    # empty planes are well-formed no-ops
    assert SK.pairs_to_blocks(np.zeros((0, 64), np.uint8)).shape == (0, 16)
    assert SK.digests_to_bytes(np.zeros((0, 8), np.uint32)).shape == (0, 32)
    assert SK.rows_to_words(np.zeros((0, 32), np.uint8)).shape == (0, 8)


# -- the hash_level seam (padding buckets) ----------------------------------


@pytest.mark.parametrize("n", [5, 512])
def test_hash_level_pads_to_bucket_and_matches(backend, n):
    rng = np.random.default_rng(n)
    pairs = rng.integers(0, 256, (n, 64), dtype=np.uint8)
    before = backend.dispatches
    rows = backend.hash_level(pairs)
    assert rows is not None
    assert backend.dispatches == before + 1
    np.testing.assert_array_equal(rows, _host_digests(pairs))
    # the padded operand is the smallest runtime bucket >= n
    bucket = next(b for b in SK.HTR_RUNTIME_PAIR_BUCKETS if n <= b)
    assert backend.last_dispatch_bytes == bucket * 16 * 4 + bucket * 8 * 4


@pytest.mark.slow
@pytest.mark.parametrize("n", [513, 8192, 8193])
def test_hash_level_bucket_boundaries(n):
    """Crossing a bucket boundary (513 -> the 8192 bucket, 8193 -> the
    65536 bucket) stays bit-identical — padding lanes never leak."""
    backend = _make_backend()
    rng = np.random.default_rng(n)
    pairs = rng.integers(0, 256, (n, 64), dtype=np.uint8)
    rows = backend.hash_level(pairs)
    assert rows is not None
    np.testing.assert_array_equal(rows, _host_digests(pairs))


def test_hash_level_respects_min_rows_gate():
    backend = _make_backend(min_level_rows=1024)
    pairs = np.zeros((8, 64), np.uint8)
    assert backend.hash_level(pairs) is None
    assert backend.dispatches == 0  # gated out, not failed
    assert backend.supervisor.status()["state"] == "closed"


# -- the forest sweep through ChunkTree -------------------------------------


def test_chunktree_cold_build_is_one_sweep_dispatch(backend):
    rng = np.random.default_rng(1)
    leaves = rng.integers(0, 256, (50, 32), dtype=np.uint8)
    tree = ChunkTree(64)
    tree.update(leaves)
    assert backend.dispatches == 1  # the whole build, one round-trip
    assert tree.root == tree.full_root_reference()
    assert tree.root == merkleize_chunks(
        [leaves[i].tobytes() for i in range(50)], 64
    )


def test_chunktree_dirty_sweep_matches_host(backend):
    rng = np.random.default_rng(2)
    leaves = rng.integers(0, 256, (200, 32), dtype=np.uint8)
    tree = ChunkTree(1 << 10)
    tree.update(leaves)
    for step in range(4):
        idx = rng.integers(0, 200, 7)
        leaves[idx] = rng.integers(0, 256, (7, 32), dtype=np.uint8)
        before = backend.dispatches
        tree.update(leaves)
        assert backend.dispatches == before + 1
        assert tree.root == tree.full_root_reference()
    # growth mid-stream: appended chunks ride the same sweep
    leaves = np.concatenate(
        [leaves, rng.integers(0, 256, (30, 32), dtype=np.uint8)]
    )
    tree.update(leaves)
    assert tree.root == tree.full_root_reference()


def test_chunktree_bulk_update_skips_sweep_lane_bucket():
    """A dirty batch past HTR_SWEEP_LANES declines the sweep and runs
    the per-level loop (host here: the row gate keeps small levels
    off-device) — still bit-identical, zero dispatches."""
    backend = _make_backend(min_level_rows=10**9)
    DB.set_backend(backend)
    try:
        rng = np.random.default_rng(3)
        leaves = rng.integers(
            0, 256, (SK.HTR_SWEEP_LANES + 88, 32), dtype=np.uint8
        )
        tree = ChunkTree(1 << 11)
        tree.update(leaves)
        assert backend.dispatches == 0
        assert tree.root == tree.full_root_reference()
    finally:
        DB.reset_backend()


# -- backend interleaving (property test) -----------------------------------


@pytest.mark.parametrize("device_first", [True, False])
def test_chunktree_backend_interleaving(device_first):
    """Switching merkleization backends MID-update-stream (host ->
    device and device -> host, every step) must leave the incremental
    root bit-identical to a host-only twin and to the merkleize_chunks
    oracle — the planes the two paths write are interchangeable."""
    backend = _make_backend()
    rng = np.random.default_rng(17 if device_first else 71)
    n = 120
    leaves = rng.integers(0, 256, (n, 32), dtype=np.uint8)
    tree = ChunkTree(1 << 9)
    twin = ChunkTree(1 << 9)
    try:
        for step in range(10):
            on_device = (step % 2 == 0) == device_first
            k = int(rng.integers(1, 12))
            idx = rng.integers(0, leaves.shape[0], k)
            leaves[idx] = rng.integers(0, 256, (k, 32), dtype=np.uint8)
            if step == 5:  # grow once, mid-stream
                leaves = np.concatenate(
                    [leaves, rng.integers(0, 256, (13, 32), dtype=np.uint8)]
                )
            DB.set_backend(backend if on_device else None)
            tree.update(leaves)
            DB.set_backend(None)
            twin.update(leaves)
            assert tree.root == twin.root, (step, on_device)
            assert tree.root == tree.full_root_reference(), (step, on_device)
        assert backend.dispatches > 0  # the device legs actually ran
    finally:
        DB.reset_backend()


# -- the validators leaf-packing kernel -------------------------------------


def _host_validator_root(pk_root, cred, eb, aee, ae, ee, we, slashed):
    def u64(v):
        return int(v).to_bytes(8, "little") + b"\x00" * 24

    chunks = [
        bytes(pk_root),
        bytes(cred),
        u64(eb),
        (b"\x01" if slashed else b"\x00") + b"\x00" * 31,
        u64(aee),
        u64(ae),
        u64(ee),
        u64(we),
    ]
    return merkleize_chunks(chunks, 8)


def test_validator_roots_device_matches_host(backend):
    d = 7
    rng = np.random.default_rng(4)
    pk_rows = rng.integers(0, 256, (d, 32), dtype=np.uint8)
    cred_rows = rng.integers(0, 256, (d, 32), dtype=np.uint8)
    cols = [
        rng.integers(0, 1 << 62, d).astype(np.uint64) for _ in range(5)
    ]
    slashed = rng.integers(0, 2, d).astype(bool)
    out = backend.validator_roots(pk_rows, cred_rows, cols, slashed)
    assert out is not None and out.shape == (d, 32)
    for i in range(d):
        expected = _host_validator_root(
            pk_rows[i],
            cred_rows[i],
            cols[0][i],
            cols[1][i],
            cols[2][i],
            cols[3][i],
            cols[4][i],
            bool(slashed[i]),
        )
        assert bytes(out[i]) == expected, i
    # the empty plane short-circuits without a dispatch
    before = backend.dispatches
    empty = backend.validator_roots(
        np.zeros((0, 32), np.uint8),
        np.zeros((0, 32), np.uint8),
        [np.zeros(0, np.uint64)] * 5,
        np.zeros(0, bool),
    )
    assert empty.shape == (0, 32) and backend.dispatches == before


# -- fault degradation ------------------------------------------------------


def test_fault_degrades_to_host_and_root_survives(backend):
    rng = np.random.default_rng(5)
    leaves = rng.integers(0, 256, (64, 32), dtype=np.uint8)
    tree = ChunkTree(128)
    tree.update(leaves)
    assert tree.root == tree.full_root_reference()
    backend.fault = "backend"
    leaves[3] = rng.integers(0, 256, 32, dtype=np.uint8)
    tree.update(leaves)  # sweep fails -> breaker trips -> host loop
    assert tree.root == tree.full_root_reference()  # zero lost roots
    assert backend.supervisor.status()["state"] == "open"
    assert backend.supervisor.status()["last_failure"]["outcome"] == (
        "backend_init"
    )
    # a faulted hash_level degrades the same way: None, host hashes
    pairs = rng.integers(0, 256, (16, 64), dtype=np.uint8)
    assert backend.hash_level(pairs) is None
    plane = hash_pairs_plane(pairs)  # the seam falls through to host
    np.testing.assert_array_equal(plane, _host_digests(pairs))


def test_bench_device_state_roots_probe_runs_in_process(capsys, monkeypatch):
    """bench.py measures state_roots_per_s_device in its own process (a
    child could not take the chip the parent holds): the probe switches
    the HTR backend on for itself only and emits one record."""
    import json
    import os

    import bench

    monkeypatch.setattr(bench, "BENCH_HTR_VALIDATORS", 2048)
    monkeypatch.delenv("LODESTAR_TPU_HTR_BACKEND", raising=False)
    bench._probe_state_roots_device()
    rec = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert rec["metric"] == "state_roots_per_s_device"
    assert rec["value"] > 0 and rec["htr"]["dispatches"] > 0
    assert "LODESTAR_TPU_HTR_BACKEND" not in os.environ
    assert DB.maybe_backend() is None
