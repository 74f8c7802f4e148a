"""The harness end to end on the CPU, at the tiny registry.

The device verifier is replaced underneath (the harness's look for a
chip is skipped): by the node's own host verifier, which must come out
correct; by the control and by planted faults, which must not.  The
tiny cells, a loop, a traffic mix and a metric reader are added to a
copy of BENCHMARK.json by files alone.
"""

import json
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

from benchmark import control, run

HERE = Path(__file__).resolve().parent
BENCH = HERE.parent
REPO = BENCH.parent


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    """A checkout-shaped directory: the real BENCHMARK.json plus the tiny
    configuration, cells and metric, the tiny files, and the real loops
    and readers."""
    root = tmp_path_factory.mktemp("cell")
    spec = json.loads((REPO / "BENCHMARK.json").read_text())
    spec["configs"].append(
        {"name": "tiny", "source": "test size", "file": "benchmark/configs/tiny.json",
         "reduced": [], "why": "test registry"}
    )
    for name, traffic in (("tiny.range-sync", "tiny-sync"), ("tiny.one-by-one", "tiny-one-by-one")):
        spec["workloads"].append(
            {"name": name, "config": "tiny", "traffic": traffic, "chips": 1,
             "why": "a cell added by files alone"}
        )
    spec["per_layer"].append(
        {"name": "jobs_in_window.tiny", "unit": "jobs", "better": "higher", "source": "program_counter",
         "layer": "verification service", "moves": spec["end_to_end"][0]["name"],
         "workloads": ["tiny.range-sync", "tiny.one-by-one"]}
    )
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    for sub in ("configs", "traffic", "loops", "metrics"):
        shutil.copytree(BENCH / sub, root / "benchmark" / sub, ignore=shutil.ignore_patterns("__pycache__"))
        for f in (HERE / "tiny" / sub).iterdir():
            shutil.copy(f, root / "benchmark" / sub / f.name)
    return root


def _host_verifier(table, metrics):
    from lodestar_tpu.bls.single_thread import CpuBlsVerifier

    v = CpuBlsVerifier(table=table, metrics=metrics)
    v.max_job_sets = 512
    return v


def _run(root, capsys, make, seed=7, cell="tiny.range-sync", trace=0):
    argv = ["--workload", cell, "--seed", str(seed), "--seconds", "1", "--trace", str(trace)]
    rc = run.main(argv, {"root": root, "any_device": True, "make_verifier": make})
    out, err = capsys.readouterr()
    assert rc == 0
    return json.loads(out.strip().splitlines()[-1]), err


def _faulty(fault):
    def make(table, metrics):
        v = _host_verifier(table, metrics)
        inner = v.verify_signature_sets
        calls = []
        if fault == "host_fallback":
            from lodestar_tpu.bls.supervisor import DeviceSupervisor

            sup = DeviceSupervisor(registry=metrics.registry, auto_probe=False)

        def verify(sets, opts=None):
            calls.append(len(sets))
            half = (len(sets) + 1) // 2
            if fault == "answer_flipped":
                return not inner(sets, opts)
            if fault == "first_half_only":
                return inner(sets[:half], opts)
            if fault == "second_half_only":
                return inner(sets[half:], opts)
            if fault == "every_other_call_unverified":
                return True if len(calls) % 2 else inner(sets, opts)
            if fault == "unchanged":
                return True
            sup.note_host_fallback(len(sets))
            return inner(sets, opts)

        v.verify_signature_sets = verify
        return v

    return make


def test_sound_run_is_correct(root, capsys):
    res, err = _run(root, capsys, _host_verifier)
    assert res["correct"] is True, err
    assert list(res)[-1] == "checks"
    assert set(res["metrics"]) == {"sets_per_s", "setup_s"}
    assert res["checks"]["wrong_or_missing"] == {"value": 0, "limit": 0}
    assert err.strip().splitlines()[-2:] == [
        "check wrong_or_missing 0 limit 0",
        "check host_fallback_sets 0 limit 0",
    ]


def test_every_tampered_block_is_compared(root, capsys):
    argv = ["--workload", "tiny.range-sync", "--seed", "9", "--seconds", "1"]
    assert run.main(argv, {"root": root, "any_device": True, "make_verifier": _host_verifier}) == 0
    out, _err = capsys.readouterr()
    line = next(x for x in out.splitlines() if x.startswith("# reference:"))
    # the batch in flight at the close is awaited: at least the first
    # batch's 2 tampered blocks were answered, and each is compared
    assert line.count("'wrong_key'") + line.count("'wrong_root'") + line.count("'flipped_bit'") >= 2


def test_choose_blocks_takes_every_tampered_block():
    import numpy as np

    from benchmark.loops import range_sync

    tamper = [None, ("wrong_key", 3), None, ("flipped_bit", 90), None, None, ("wrong_root", 7)]
    chosen = range_sync.choose_blocks([0, 1, 2, 3, 4, 5, 6, 1], tamper, 2, np.random.default_rng(3))
    assert {1, 3, 6} < set(chosen) and len(chosen) == 5


def test_bad_sets_cover_both_halves_of_blocks_and_batches():
    import numpy as np

    from benchmark.loops import range_sync

    cfg = {"validators": 4096, "slots_per_epoch": 8, "committee_size": 8, "sync_committee_size": 16,
           "max_attestations": 3, "epochs_per_batch": 1}
    traffic = {"block": {"proposer": 1, "randao": 1, "sync_aggregate": 1}, "participation": 0.75,
               "pool_batches": 2, "tampered_blocks_per_batch": 3,
               "tamper_kinds": ["wrong_key", "wrong_root", "flipped_bit"]}
    for seed in range(20):
        _blocks, tamper = range_sync.build_pool(cfg, traffic, np.random.default_rng(seed), list(range(1, 9)))
        bad = [(b, t) for b, t in enumerate(tamper) if t is not None]
        assert len(bad) == 6
        assert {t[1] < 3 for _b, t in bad} == {True} | {t[1] >= 3 for _b, t in bad}
        for batch in (0, 1):
            places = [b % 8 for b, _t in bad if b // 8 == batch]
            assert {p < 4 for p in places} == {True, False}
            assert {p % 2 for p in places} == {0, 1}


def test_control_is_not_correct(root, capsys):
    argv = ["--workload", "tiny.range-sync", "--seed", "7", "--seconds", "1"]
    assert control.main(argv, {"root": root}) == 0
    out, _err = capsys.readouterr()
    res = json.loads(out.strip().splitlines()[-1])
    assert res["correct"] is False
    assert res["checks"]["wrong_or_missing"]["value"] > 0


@pytest.mark.parametrize("fault", ["answer_flipped", "unchanged", "host_fallback"])
def test_fault_is_not_correct(root, capsys, fault):
    res, _err = _run(root, capsys, _faulty(fault))
    assert res["correct"] is False


# half of each job, or every other job, left out: caught on every seed,
# since bad sets sit in both halves of the blocks and in both halves of
# every batch, and every tampered block answered is compared
@pytest.mark.parametrize("seed", [7, 1234, 2**31 + 5])
@pytest.mark.parametrize("fault", ["first_half_only", "second_half_only", "every_other_call_unverified"])
def test_half_left_out_is_not_correct_on_any_seed(root, capsys, fault, seed):
    res, _err = _run(root, capsys, _faulty(fault), seed=seed)
    assert res["correct"] is False
    assert res["checks"]["wrong_or_missing"]["value"] > 0


def test_no_tpu_exits_without_a_result():
    proc = subprocess.run(
        [sys.executable, "-m", "benchmark.run", "--workload", "mainnet-500k.range-sync",
         "--seed", "1", "--seconds", "1"],
        cwd=REPO, capture_output=True, text=True, timeout=300,
        env={"JAX_PLATFORMS": "cpu", "PATH": "/usr/bin:/bin"},
    )
    assert proc.returncode != 0
    assert "{" not in proc.stdout


def test_traced_run_is_correct(root, capsys):
    res, err = _run(root, capsys, _host_verifier, seed=8, trace=1)
    assert res["correct"] is True, err
    # the CPU has no device plane: nothing to read, so nothing reported
    assert "breakdown" not in res and "busy_s" not in res["device"]
    # the reader added by a file alone, with its samples of the node
    assert res["metrics"]["jobs_in_window.tiny"]["value"] > 0


def test_loop_added_by_files_alone(root, capsys):
    res, err = _run(root, capsys, _host_verifier, seed=10, cell="tiny.one-by-one")
    assert res["correct"] is True, err
    assert set(res["metrics"]) == {"sets_per_s", "setup_s"}
    res, _err = _run(root, capsys, _faulty("answer_flipped"), seed=10, cell="tiny.one-by-one")
    assert res["correct"] is False
