"""AOT export cache: trace-once reload, keying, staleness, fallback.

Reference rationale: the per-process trace cost of the unrolled limb
pipeline (~10 min on the 1-core driver host, dev/NOTES.md) is removed
by persisting the traced computation with jax.export and reloading it
without re-tracing (kernels/export_cache.py).
"""

import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental import pallas as pl

from lodestar_tpu.kernels import export_cache as EC

pytestmark = pytest.mark.smoke


def _toy_pipeline():
    """A small pallas-backed function standing in for the verify
    pipeline (full-pipeline artifacts are TPU-platform; XLA:CPU cannot
    compile the monolithic graph — dev/NOTES.md)."""

    def k(x_ref, o_ref):
        acc = x_ref[...]
        for _ in range(8):
            acc = acc * 3 + 1
        o_ref[...] = acc

    call = pl.pallas_call(
        k,
        out_shape=jax.ShapeDtypeStruct((8, 128), jnp.int32),
        interpret=True,
    )

    def fn(x, y):
        return call(x) + y

    return fn


def test_export_reload_matches_direct(tmp_path):
    fn = _toy_pipeline()
    x = jnp.arange(8 * 128, dtype=jnp.int32).reshape(8, 128)
    y = jnp.ones((8, 128), jnp.int32)
    specs = [jax.ShapeDtypeStruct(a.shape, a.dtype) for a in (x, y)]
    call = EC.load_or_export(
        "toy", fn, specs, platform="cpu", cache_dir=str(tmp_path)
    )
    got = call(x, y)
    want = fn(x, y)
    assert np.array_equal(np.asarray(got), np.asarray(want))
    # artifact landed on disk
    files = list(tmp_path.glob("toy-cpu-*.jaxexport"))
    assert len(files) == 1


def test_reload_skips_tracing(tmp_path):
    """The second load must come from disk: the builder is never traced
    again (we prove it with a trace-counting wrapper)."""
    traces = []

    def make_fn():
        def fn(x):
            traces.append(1)  # runs at TRACE time only
            return x * 2 + 1

        return fn

    x = jnp.ones((4,), jnp.int32)
    specs = [jax.ShapeDtypeStruct(x.shape, x.dtype)]
    EC._LOADED.clear()
    c1 = EC.load_or_export(
        "trace-count", make_fn(), specs, platform="cpu", cache_dir=str(tmp_path)
    )
    n_after_first = len(traces)
    assert n_after_first >= 1
    EC._LOADED.clear()  # force the disk path
    c2 = EC.load_or_export(
        "trace-count", make_fn(), specs, platform="cpu", cache_dir=str(tmp_path)
    )
    assert len(traces) == n_after_first  # no new trace
    assert np.array_equal(np.asarray(c2(x)), np.asarray(c1(x)))


def test_key_varies_with_shape_and_platform():
    s1 = [jax.ShapeDtypeStruct((8, 128), jnp.int32)]
    s2 = [jax.ShapeDtypeStruct((8, 256), jnp.int32)]
    assert EC.artifact_key("a", s1, "cpu") != EC.artifact_key("a", s2, "cpu")
    assert EC.artifact_key("a", s1, "cpu") != EC.artifact_key("a", s1, "tpu")
    assert EC.artifact_key("a", s1, "cpu") != EC.artifact_key("b", s1, "cpu")


def test_corrupt_artifact_falls_back(tmp_path):
    fn = _toy_pipeline()
    x = jnp.ones((8, 128), jnp.int32)
    specs = [
        jax.ShapeDtypeStruct(x.shape, x.dtype),
        jax.ShapeDtypeStruct(x.shape, x.dtype),
    ]
    key = EC.artifact_key("corrupt", specs, "cpu")
    (tmp_path / f"{key}.jaxexport").write_bytes(b"garbage")
    EC._LOADED.clear()
    assert EC.load("corrupt", specs, "cpu", cache_dir=str(tmp_path)) is None
    # load_or_export recovers by re-exporting
    call = EC.load_or_export(
        "corrupt", fn, specs, platform="cpu", cache_dir=str(tmp_path)
    )
    assert call(x, x) is not None


def test_cross_platform_tpu_export_from_cpu_host(tmp_path):
    """A REAL (non-interpret) Mosaic kernel exports for the tpu platform
    from this CPU host — the pre-trace workflow the bench relies on."""
    from lodestar_tpu.kernels import launch

    def k(x_ref, o_ref):
        o_ref[...] = x_ref[...] + 7

    def fn(x):
        return launch.cached(
            ("export-test-k", x.shape),
            lambda: pl.pallas_call(
                k,
                out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
                interpret=launch.interpret(),
            ),
            x,
        )

    x = jnp.zeros((8, 128), jnp.int32)
    specs = [jax.ShapeDtypeStruct(x.shape, x.dtype)]
    call = EC.load_or_export(
        "mosaic-x", fn, specs, platform="tpu", cache_dir=str(tmp_path)
    )
    assert call is not None
    files = list(tmp_path.glob("mosaic-x-tpu-*.jaxexport"))
    assert len(files) == 1 and files[0].stat().st_size > 0
    # the artifact declares its platform; running it here would need a
    # TPU — reload only
    EC._LOADED.clear()
    assert EC.load("mosaic-x", specs, "tpu", cache_dir=str(tmp_path)) is not None


# ---------------------------------------------------------------------------
# standalone-entry source fingerprinting (tpulint fingerprint-completeness
# runtime backstop)
# ---------------------------------------------------------------------------


def _entry_cleanup(*names):
    for n in names:
        EC._ENTRY_BUILDERS.pop(n, None)
        EC._ENTRY_SOURCES.pop(n, None)


def _toy_specs():
    def fn(x):
        return x + 1

    return fn, [jax.ShapeDtypeStruct((4,), jnp.int32)]


def test_uncovered_entry_warns_at_registration(caplog):
    """An entry tracing a function outside kernels/ with no registered
    source must warn when its builder runs — the module's edits would
    otherwise never invalidate the cached artifact."""
    import logging

    EC.register_entry("fx-uncovered", _toy_specs)
    try:
        with caplog.at_level(logging.WARNING, logger="lodestar_tpu"):
            fn, specs = EC.registered_entries()["fx-uncovered"]()
        assert any(
            "fx-uncovered" in r.message and "_ENTRY_SOURCES" in r.message
            for r in caplog.records
        ), [r.message for r in caplog.records]
        assert fn(jnp.zeros((4,), jnp.int32)) is not None
    finally:
        _entry_cleanup("fx-uncovered")


def test_covered_entry_does_not_warn(caplog):
    import logging

    EC.register_entry(
        "fx-covered", _toy_specs, sources=(_toy_specs.__module__,)
    )
    try:
        with caplog.at_level(logging.WARNING, logger="lodestar_tpu"):
            EC.registered_entries()["fx-covered"]()
        assert not any(
            "fx-covered" in r.message for r in caplog.records
        ), [r.message for r in caplog.records]
    finally:
        _entry_cleanup("fx-covered")


def test_builtin_slasher_entry_declares_its_import_graph(caplog):
    """The shipped slasher entry must cover device.py AND batch.py (the
    module device.py imports) so an edit to either invalidates the span
    artifact — and must therefore pass the runtime backstop silently."""
    import logging

    declared = EC._ENTRY_SOURCES["slasher_span_update"]
    assert "lodestar_tpu.slasher.device" in declared
    assert "lodestar_tpu.slasher.batch" in declared
    for src in declared:
        p = EC._source_path(src)
        assert p is not None and p.exists(), src
    with caplog.at_level(logging.WARNING, logger="lodestar_tpu"):
        EC.registered_entries()["slasher_span_update"]()
    assert not any(
        "slasher_span_update" in r.message for r in caplog.records
    )


def test_artifact_key_tracks_every_declared_source(tmp_path):
    """Editing ANY registered source must change the entry's artifact
    key (multi-source entries: device.py edit AND batch.py edit both
    invalidate)."""
    a = tmp_path / "dep_a.py"
    b = tmp_path / "dep_b.py"
    a.write_text("A = 1\n")
    b.write_text("B = 1\n")
    specs = [jax.ShapeDtypeStruct((4,), jnp.int32)]
    EC.register_entry("fx-multi", _toy_specs, sources=(str(a), str(b)))
    try:
        k0 = EC.artifact_key("fx-multi", specs, "cpu")
        a.write_text("A = 2\n")
        k1 = EC.artifact_key("fx-multi", specs, "cpu")
        assert k1 != k0
        b.write_text("B = 2\n")
        k2 = EC.artifact_key("fx-multi", specs, "cpu")
        assert k2 != k1
    finally:
        _entry_cleanup("fx-multi")


def test_module_name_sources_resolve_without_import():
    p = EC._source_path("lodestar_tpu.slasher.batch")
    assert p is not None and p.name == "batch.py" and p.exists()
    p = EC._source_path("lodestar_tpu.slasher")
    assert p is not None and p.name == "__init__.py"
    assert EC._source_path("lodestar_tpu.no.such.module") is None


def test_reregistration_without_sources_drops_stale_declaration():
    EC.register_entry("fx-restale", _toy_specs, sources=("lodestar_tpu.slasher.batch",))
    try:
        assert "fx-restale" in EC._ENTRY_SOURCES
        EC.register_entry("fx-restale", _toy_specs)  # no sources now
        assert "fx-restale" not in EC._ENTRY_SOURCES
    finally:
        _entry_cleanup("fx-restale")


def test_export_stage_error_carries_stage_and_classifies():
    """ISSUE 14: a backend death during export trace re-raises as
    ExportStageError naming the stage, and the breaker's classifier
    reads it as a backend-init outcome (the r03-r05 failure shape)."""
    import jax
    import pytest

    from lodestar_tpu.bls.supervisor import (
        OUTCOME_BACKEND_INIT,
        classify_failure,
    )
    from lodestar_tpu.kernels.export_cache import (
        ExportStageError,
        load_or_export,
    )

    def dead_backend(_x):
        raise RuntimeError("TPU backend UNAVAILABLE: device lost")

    spec = jax.ShapeDtypeStruct((4,), "int32")
    with pytest.raises(ExportStageError) as ei:
        load_or_export("chaos_dead_entry", dead_backend, [spec])
    assert ei.value.stage == "trace"
    assert ei.value.entry == "chaos_dead_entry"
    assert classify_failure(ei.value) == OUTCOME_BACKEND_INIT
