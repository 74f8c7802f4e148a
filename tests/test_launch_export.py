"""Per-stage export: every pallas launch is kept across processes.

kernels/launch.py saves each stage program it builds to the export
cache (kernels/export_cache.py `load_or_export`) and a later process
loads it without tracing or lowering the kernel body.  Here "a later
process" is the same one with the in-process caches emptied; the
export cache points at a tmp dir.
"""

import functools
import pathlib
import shutil

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental import pallas as pl

from lodestar_tpu.kernels import export_cache as EC
from lodestar_tpu.kernels import launch as LA
from lodestar_tpu.kernels import verify as KV
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P
from lodestar_tpu.utils.metrics import global_registry

pytestmark = pytest.mark.smoke

N, BT = 256, 128


def _k_scale_add(x_ref, y_ref, o_ref):
    o_ref[...] = x_ref[...] * 3 + y_ref[...]


def _k_colsum(x_ref, o_ref):
    @pl.when(pl.program_id(0) == 0)
    def _():
        o_ref[...] = jnp.zeros_like(o_ref)

    o_ref[...] += x_ref[...]


def _tiled(x, y):
    return LA.tiled(_k_scale_add, (x, y), [8, 1], [8], N, BT)[0]


def _cached(x, y):
    return LA.cached(
        ("test_colsum", N),
        lambda: pl.pallas_call(
            _k_colsum,
            out_shape=jax.ShapeDtypeStruct((8, BT), jnp.int32),
            grid=(N // BT,),
            in_specs=[pl.BlockSpec((8, BT), lambda i: (0, i))],
            out_specs=pl.BlockSpec((8, BT), lambda i: (0, 0)),
            interpret=LA.interpret(),
        ),
        x,
    ) + y[:, :BT]


def _mont4(x, y):
    planes = [jnp.broadcast_to(x[:1], (KV.NL, N)) + i for i in range(4)]
    return KV._tiled(KV._k_mont4, planes, [KV.NL] * 4, [KV.NL] * 4, N)[3]


def _want(kind, x, y):
    x, y = np.asarray(x), np.asarray(y)
    if kind == "tiled":
        return x * 3 + y
    if kind == "cached":
        return x.reshape(8, N // BT, BT).sum(axis=1) + y[:, :BT]
    return None  # the real stage is checked against its own first pass


LAUNCHES = {"tiled": _tiled, "cached": _cached, "mont4": _mont4}


@pytest.fixture
def stage_cache(tmp_path, monkeypatch):
    """Export cache in a tmp dir and empty in-process caches (the
    modules' own are put back afterwards)."""
    monkeypatch.setenv("LODESTAR_TPU_EXPORT_CACHE", str(tmp_path))
    monkeypatch.setattr(LA, "_CACHE", {})
    monkeypatch.setattr(EC, "_LOADED", {})
    return tmp_path


def _new_process():
    """What a later process starts with: the export cache on disk and
    nothing in memory."""
    LA._CACHE.clear()
    EC._LOADED.clear()


def _operands():
    x = jnp.asarray(np.arange(8 * N).reshape(8, N) % 1000, jnp.int32)
    y = jnp.asarray(np.arange(N).reshape(1, N) % 7, jnp.int32)
    return x, y


def _total(name: str) -> float:
    c = global_registry().get(name)
    return sum(c.get(lv) for lv in c.label_values()) if c else 0.0


def _counts():
    return (
        _total("lodestar_tpu_pallas_builds_total"),
        _total("lodestar_tpu_export_cache_misses_total"),
        _total("lodestar_tpu_export_cache_hits_total"),
    )


def _artifacts(d):
    return sorted(d.glob("*.jaxexport"))


@pytest.mark.parametrize("kind", ["tiled", "cached", "mont4"])
def test_stage_round_trips_through_export_cache(stage_cache, kind):
    x, y = _operands()
    builds0, misses0, _ = _counts()
    got = LAUNCHES[kind](x, y)
    builds1, misses1, _ = _counts()
    assert (builds1 - builds0, misses1 - misses0) == (1, 1)
    (path,) = _artifacts(stage_cache)
    assert path.name.startswith(f"{'cached' if kind == 'cached' else 'tiled'}.")
    assert path.stat().st_size > 0
    want = _want(kind, x, y)
    if want is not None:
        assert np.array_equal(np.asarray(got), want)


@pytest.mark.parametrize("kind", ["tiled", "cached", "mont4"])
def test_second_pass_loads_without_tracing(stage_cache, kind):
    """With the in-process programs gone, the stage comes back from
    disk: identical output, no pallas_call built, no export miss."""
    x, y = _operands()
    first = np.asarray(LAUNCHES[kind](x, y))
    _new_process()
    builds0, misses0, hits0 = _counts()
    second = np.asarray(LAUNCHES[kind](x, y))
    builds1, misses1, hits1 = _counts()
    assert np.array_equal(first, second)
    assert (builds1 - builds0, misses1 - misses0, hits1 - hits0) == (0, 0, 1)
    # warm calls reuse the process's one wrapper
    again = np.asarray(LAUNCHES[kind](x, y))
    assert np.array_equal(first, again) and _counts() == (builds1, misses1, hits1)


@pytest.mark.parametrize("kind", ["tiled", "cached"])
def test_changed_fingerprint_traces_afresh(stage_cache, kind, monkeypatch):
    x, y = _operands()
    LAUNCHES[kind](x, y)
    (old,) = _artifacts(stage_cache)
    _new_process()
    monkeypatch.setattr(EC, "_FINGERPRINT", "0" * 16)  # a kernels/ edit
    builds0, misses0, _ = _counts()
    got = LAUNCHES[kind](x, y)
    builds1, misses1, _ = _counts()
    assert (builds1 - builds0, misses1 - misses0) == (1, 1)
    assert len(_artifacts(stage_cache)) == 2 and old.exists()
    assert np.array_equal(np.asarray(got), _want(kind, x, y))


@pytest.mark.parametrize("kind", ["tiled", "cached"])
def test_truncated_artifact_traces_afresh(stage_cache, kind):
    x, y = _operands()
    LAUNCHES[kind](x, y)
    (path,) = _artifacts(stage_cache)
    size = path.stat().st_size
    path.write_bytes(path.read_bytes()[: size // 2])
    _new_process()
    builds0, misses0, _ = _counts()
    got = LAUNCHES[kind](x, y)
    builds1, misses1, _ = _counts()
    assert (builds1 - builds0, misses1 - misses0) == (1, 1)
    assert np.array_equal(np.asarray(got), _want(kind, x, y))
    assert path.stat().st_size == size  # rewritten whole


def test_stage_inside_an_outer_jit_is_exported(stage_cache):
    """A launch traced inside a jit (the ops_jit glue that wraps the
    hash and decompression stages) saves and loads like a direct one."""
    x, y = _operands()
    outer = jax.jit(lambda a, b: _tiled(a, b) + 1)
    first = np.asarray(outer(x, y))
    assert np.array_equal(first, _want("tiled", x, y) + 1)
    _new_process()
    builds0, misses0, _ = _counts()
    second = np.asarray(jax.jit(lambda a, b: _tiled(a, b) + 1)(x, y))
    assert np.array_equal(first, second)
    assert _counts()[:2] == (builds0, misses0)


def test_artifacts_live_beside_the_compile_cache(monkeypatch, tmp_path):
    monkeypatch.delenv("LODESTAR_TPU_EXPORT_CACHE", raising=False)
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "jc"))
    assert EC.default_dir() == str(tmp_path / "jc" / "exports")
    monkeypatch.setenv("LODESTAR_TPU_EXPORT_CACHE", str(tmp_path / "x"))
    assert EC.default_dir() == str(tmp_path / "x")


def test_kernel_outside_kernels_keys_on_its_source(
    stage_cache, monkeypatch, tmp_path_factory
):
    """A stage whose kernel body lives outside kernels/ (this file) is
    keyed on that module's source too: an edit there re-traces."""
    src = tmp_path_factory.mktemp("src") / "kernel_module.py"
    shutil.copy(__file__, src)
    monkeypatch.setattr(LA.inspect, "getsourcefile", lambda fn: str(src))
    x, y = _operands()
    _tiled(x, y)
    (path,) = _artifacts(stage_cache)
    assert "test_launch_export._k_scale_add" in path.name
    _new_process()
    src.write_text(src.read_text() + "\n# edited\n")
    builds0, misses0, _ = _counts()
    _tiled(x, y)
    assert _counts()[:2] == (builds0 + 1, misses0 + 1)
    assert len(_artifacts(stage_cache)) == 2


@pytest.mark.parametrize("kind", ["tiled", "cached"])
def test_unwritable_cache_still_dispatches(tmp_path, monkeypatch, kind):
    """A cache directory that cannot be made (its parent is a file) is
    a miss on lookup and a warning on write: the stage runs, and the
    lookup creates nothing."""
    blocker = tmp_path / "not_a_dir"
    blocker.write_text("")
    monkeypatch.setenv("LODESTAR_TPU_EXPORT_CACHE", str(blocker / "exports"))
    monkeypatch.setattr(LA, "_CACHE", {})
    monkeypatch.setattr(EC, "_LOADED", {})
    x, y = _operands()
    misses0 = _counts()[1]
    got = LAUNCHES[kind](x, y)
    assert np.array_equal(np.asarray(got), _want(kind, x, y))
    assert _counts()[1] == misses0 + 1
    assert blocker.is_file()


def test_lookup_creates_no_directory(tmp_path):
    where = tmp_path / "absent"
    spec = jax.ShapeDtypeStruct((8, N), jnp.int32)
    assert EC.load("tiled.nothing", [spec], "cpu", str(where)) is None
    assert not where.exists()


def _k_closure_factory(c):
    def _k_closed(x_ref, y_ref, o_ref):
        o_ref[...] = x_ref[...] * c + y_ref[...]

    return _k_closed


def _k_scale_add_by(c, x_ref, y_ref, o_ref):
    o_ref[...] = x_ref[...] * c + y_ref[...]


@pytest.mark.parametrize(
    "kernel",
    [
        _k_closure_factory(5),
        functools.partial(_k_scale_add_by, 5),
        lambda x_ref, y_ref, o_ref: None,
    ],
    ids=["closure", "partial", "lambda"],
)
def test_kernel_without_a_stable_name_is_refused(stage_cache, kernel):
    """Two closures (or partials) of one function share a qualified
    name but not a program, so they cannot key an artifact."""
    x, y = _operands()
    with pytest.raises(TypeError, match="module-level function"):
        LA.tiled(kernel, (x, y), [8, 1], [8], N, BT)
    assert _artifacts(stage_cache) == []


@pytest.mark.parametrize("module", ["fields.py", "curves.py", "hash_to_curve.py"])
def test_crypto_constant_edit_changes_every_key(tmp_path, module):
    """The kernels bake in crypto/'s constants, so an edit there
    changes the fingerprint every artifact key holds."""
    pkg = pathlib.Path(EC.__file__).parent.parent
    for sub in ("kernels", "crypto"):
        shutil.copytree(pkg / sub, tmp_path / sub)
    before = EC._code_fingerprint(tmp_path)
    assert before == EC._code_fingerprint(pkg)
    edited = tmp_path / "crypto" / module
    edited.write_text(edited.read_text() + "\n# edited\n")
    assert EC._code_fingerprint(tmp_path) != before


def test_stage_inside_shard_map_round_trips(stage_cache):
    """The sharded verifier launches every stage inside a shard_map
    body (per-shard shapes): exported on the first pass, loaded on the
    next, same answers."""
    if len(jax.devices()) < 2:
        pytest.skip("needs 2 devices (virtual CPU platform)")
    mesh = Mesh(np.array(jax.devices()[:2]), ("sets",))

    def sharded():
        def body(a, b):
            return LA.tiled(_k_scale_add, (a, b), [8, 1], [8], a.shape[1], BT)[0]

        spec = P(None, "sets")
        return jax.jit(
            shard_map(
                body, mesh=mesh, in_specs=(spec, spec), out_specs=spec,
                check_vma=False,
            )
        )

    x, y = _operands()
    first = np.asarray(sharded()(x, y))
    assert np.array_equal(first, _want("tiled", x, y))
    assert len(_artifacts(stage_cache)) == 1
    _new_process()
    builds0, misses0, _ = _counts()
    assert np.array_equal(np.asarray(sharded()(x, y)), first)
    assert _counts()[:2] == (builds0, misses0)
