"""AOT export cache — trace once, reload without re-tracing.

The verify pipeline unrolls 33-limb schoolbook arithmetic into large
kernel bodies; TRACING and LOWERING them cost minutes of host time per
process, while the XLA/Mosaic compile is served by the persistent
compile cache.  Tracing is pure Python work over static shapes, so it
is paid ONCE, the result serialized with `jax.export`, and every later
process deserializes it and goes straight to (cached) compilation.

`load_or_export` is the one path in and out of the cache.  The BLS
verifier's artifacts are per stage: kernels/launch.py hands every
pallas launch it builds to it, one artifact per (kernel, operand
shapes, grid) signature, exported for the platform the process runs
on.  Each stage stays its own program, so a stage shared by several
entry points and buckets still compiles once.  Whole programs use the
same path (the slasher's span update, the HTR merkleization kernels,
the standalone registry below).

Artifacts are keyed by (entry name, shape/dtype signature, platform,
jax version, code fingerprint, declared sources, detail); a stale key
or an unreadable file falls back to a fresh trace, so an edit can
never run an outdated artifact, and a cache that cannot be read or
written only costs the trace.  They live in `default_dir()`: beside
the compile cache, so a run that finds its compiles warm finds its
exports.

Cross-platform: `platform="tpu"` whole-program artifacts are traced on
a CPU host with the real Mosaic lowering forced (launch.force_mosaic) —
export runs jax lowering only; the Mosaic->TPU-binary compile still
happens on-device at first call, hitting the persistent compile cache.
"""

from __future__ import annotations

import hashlib
import os
import pathlib
import time
from typing import Callable, Dict, Optional, Sequence, Tuple

import jax


def default_dir() -> str:
    """`LODESTAR_TPU_EXPORT_CACHE` when set, else the `exports`
    subdirectory of the compile cache (utils/compile_cache.cache_dir():
    `JAX_COMPILATION_CACHE_DIR` or `<checkout>/.jax_cache`, gitignored),
    so a later process finds what an earlier one wrote wherever it
    finds its compiles."""
    from ..utils.compile_cache import cache_dir

    return os.environ.get("LODESTAR_TPU_EXPORT_CACHE") or os.path.join(
        cache_dir(), "exports"
    )


# in-process cache of deserialized/exported entries
_LOADED: Dict[str, object] = {}


class _Metrics:
    """Per-entry compile-vs-cache instrumentation (process-global
    registry — a cold Mosaic trace shows up as a NAMED number on
    /metrics and in bench.py's phase snapshot, not as a CI timeout)."""

    def __init__(self):
        from ..utils.metrics import global_registry

        r = global_registry()
        self.hits = r.labeled_counter(
            "lodestar_tpu_export_cache_hits_total",
            "Export-cache lookups served from memory or disk, per entry",
            "entry",
        )
        self.misses = r.labeled_counter(
            "lodestar_tpu_export_cache_misses_total",
            "Export-cache lookups that required a fresh trace, per entry",
            "entry",
        )
        self.trace_seconds = r.labeled_histogram(
            "lodestar_tpu_export_trace_seconds",
            "Wall seconds tracing+serializing an export artifact, per entry",
            "entry",
            (0.1, 1, 5, 30, 60, 120, 300, 600, 1200),
        )
        self.load_seconds = r.labeled_histogram(
            "lodestar_tpu_export_load_seconds",
            "Wall seconds deserializing a cached artifact, per entry",
            "entry",
            (0.001, 0.01, 0.1, 1, 5, 30),
        )


_METRICS: Optional[_Metrics] = None


def metrics() -> _Metrics:
    global _METRICS
    if _METRICS is None:
        _METRICS = _Metrics()
    return _METRICS


# Kernel sources OUTSIDE kernels/ and crypto/ whose traced computations
# live in the cache, keyed per entry NAME (declare_sources: standalone
# registry entries declare theirs at registration, launched stages
# whose kernel lives elsewhere at launch).  They fold into THAT entry's artifact key
# only — an edit to slasher/device.py must invalidate the span-update
# artifact without staling every verify-pipeline artifact on the host.
# Values are tuples of dotted module names (preferred: statically
# checkable by tpulint's fingerprint-completeness rule) or file paths.
_ENTRY_SOURCES: Dict[str, Tuple[str, ...]] = {}


def _source_path(src: str) -> Optional[pathlib.Path]:
    """Resolve a declared source (dotted module name or path) to a file."""
    if "/" in src or src.endswith(".py"):
        return pathlib.Path(src)
    parts = src.split(".")
    pkg_root = pathlib.Path(__file__).parent.parent  # lodestar_tpu/
    if parts and parts[0] == pkg_root.name:
        parts = parts[1:]
    if not parts:
        return None
    base = pkg_root.joinpath(*parts)
    if base.with_suffix(".py").exists():
        return base.with_suffix(".py")
    if (base / "__init__.py").exists():
        return base / "__init__.py"
    return None


# packages whose sources every artifact embeds: the kernels, and the
# crypto modules whose constants (Montgomery-encoded curve and
# hash-to-curve constants) the kernels bake in
_FINGERPRINT_PACKAGES = ("kernels", "crypto")


def _code_fingerprint(root: Optional[pathlib.Path] = None) -> str:
    """Hash of every kernels/*.py and crypto/*.py source file under
    `root` (the package): an edit there invalidates all artifacts
    (they embed the traced computation and its constants)."""
    root = root or pathlib.Path(__file__).parent.parent
    h = hashlib.sha256()
    for pkg in _FINGERPRINT_PACKAGES:
        for p in sorted((root / pkg).glob("*.py")):
            if p.name == "export_cache.py":
                continue  # this module does not affect traced computations
            h.update(p.read_bytes())
    return h.hexdigest()[:16]


_FINGERPRINT: Optional[str] = None


def code_fingerprint() -> str:
    global _FINGERPRINT
    if _FINGERPRINT is None:
        _FINGERPRINT = _code_fingerprint()
    return _FINGERPRINT


def artifact_key(
    name: str,
    specs: Sequence[jax.ShapeDtypeStruct],
    platform: str,
    detail: str = "",
) -> str:
    """The artifact's file stem.  `detail` folds in what the name and
    shapes leave out (a stage's grid and block rows, its sources)."""
    sig = ";".join(f"{tuple(s.shape)}:{s.dtype}" for s in specs)
    raw = f"{name}|{sig}|{platform}|{jax.__version__}|{code_fingerprint()}"
    if detail:
        raw += "|" + detail
    for source in sorted(_ENTRY_SOURCES.get(name, ())):
        path = _source_path(source)
        if path is not None and path.exists():
            raw += "|" + hashlib.sha256(path.read_bytes()).hexdigest()[:16]
    return (
        name
        + "-"
        + platform
        + "-"
        + hashlib.sha256(raw.encode()).hexdigest()[:20]
    )


def _path(key: str, cache_dir: Optional[str]) -> pathlib.Path:
    return pathlib.Path(cache_dir or default_dir()) / f"{key}.jaxexport"


def _read(name: str, key: str, platform: str, cache_dir: Optional[str]):
    """The deserialized artifact at `key`, or None when it is absent or
    unreadable (no cache directory, truncated, corrupt: the caller
    traces afresh)."""
    from jax import export as jexport

    from ..observability import trace_span

    t0 = time.perf_counter()
    with trace_span("kernels.export_load", entry=name, platform=platform):
        try:
            exp = jexport.deserialize(_path(key, cache_dir).read_bytes())
        except Exception:  # noqa: BLE001 — absent/stale/corrupt: re-trace
            return None
    metrics().load_seconds.observe(name, time.perf_counter() - t0)
    metrics().hits.inc(name, 1.0)
    return exp


def _write(name: str, path: pathlib.Path, blob: bytes) -> None:
    """Write through a temporary name and rename, so a process reading
    the cache never sees half an artifact.  A cache that cannot be
    written is a warning: the program runs, and later processes trace
    it again."""
    tmp = path.with_name(f"{path.name}.{os.getpid()}.tmp")
    try:
        path.parent.mkdir(parents=True, exist_ok=True)
        tmp.write_bytes(blob)
        os.replace(tmp, path)
    except OSError as e:
        from ..utils.logger import get_logger

        get_logger("kernels/export_cache").warn(
            f"export cache write failed for {name!r}: {e}"
        )


def load(
    name: str,
    specs: Sequence[jax.ShapeDtypeStruct],
    platform: str,
    cache_dir: Optional[str] = None,
    detail: str = "",
) -> Optional[Callable]:
    """Deserialize a cached artifact; None when absent/stale."""
    key = artifact_key(name, specs, platform, detail)
    hit = _LOADED.get(key)
    if hit is not None:
        metrics().hits.inc(name, 1.0)
        return hit.call
    exp = _read(name, key, platform, cache_dir)
    if exp is None:
        return None
    _LOADED[key] = exp
    return exp.call


def export_and_save(
    name: str,
    fn: Callable,
    specs: Sequence[jax.ShapeDtypeStruct],
    platform: str,
    cache_dir: Optional[str] = None,
    detail: str = "",
) -> Callable:
    """Trace `fn` for `platform` at `specs`, persist, return the call.

    For platform="tpu" on a CPU host the pallas launches are forced
    through the real Mosaic lowering (launch.force_mosaic)."""
    from jax import export as jexport

    from ..observability import trace_span
    from . import launch

    key = artifact_key(name, specs, platform, detail)
    jitted = jax.jit(fn)
    t0 = time.perf_counter()
    with trace_span("kernels.export_trace", entry=name, platform=platform):
        if platform == "tpu" and jax.default_backend() != "tpu":
            with launch.force_mosaic():
                exp = jexport.export(jitted, platforms=[platform])(*specs)
        else:
            exp = jexport.export(jitted, platforms=[platform])(*specs)
        _write(name, _path(key, cache_dir), exp.serialize())
    metrics().trace_seconds.observe(name, time.perf_counter() - t0)
    _LOADED[key] = exp
    return exp.call


class ExportStageError(RuntimeError):
    """An export-cache stage failed.  `.stage` ("load" | "trace") and
    `.entry` name WHERE the artifact layer died, and the cause's text
    is embedded so the breaker supervisor's failure classifier
    (bls/supervisor.py classify_failure) can tell a backend-init death
    from a mere stale-artifact problem (ISSUE 14)."""

    def __init__(self, stage: str, entry: str, cause: BaseException):
        super().__init__(
            f"export {stage} for {entry!r} failed: "
            f"{type(cause).__name__}: {cause}"
        )
        self.stage = stage
        self.entry = entry


def load_or_export(
    name: str,
    fn: Callable,
    specs: Sequence[jax.ShapeDtypeStruct],
    platform: Optional[str] = None,
    cache_dir: Optional[str] = None,
    detail: str = "",
) -> Callable:
    """The main entry: cached call if present, else trace+persist.
    `detail` folds into the key what the name and shapes leave out (a
    launched stage's kernel, grid and block rows).  Stage faults
    re-raise as ExportStageError (classification seam)."""
    platform = platform or jax.default_backend()
    try:
        cached = load(name, specs, platform, cache_dir, detail)
    except Exception as e:  # noqa: BLE001 — load() already swallows
        # corrupt artifacts; anything else here is the backend dying
        raise ExportStageError("load", name, e) from e
    if cached is not None:
        return cached
    metrics().misses.inc(name, 1.0)
    try:
        return export_and_save(name, fn, specs, platform, cache_dir, detail)
    except Exception as e:  # noqa: BLE001 — trace/persist faults carry
        # their stage for the breaker's outcome classification
        raise ExportStageError("trace", name, e) from e


# -- standalone entry registry ----------------------------------------------
#
# Whole-program entries register a spec builder here so offline
# pre-tracing (dev/export_pipeline.py) covers them.  A builder returns
# (fn, specs); it is invoked lazily — registration itself must stay
# import-cheap.

_ENTRY_BUILDERS: Dict[str, Callable] = {}


def _check_entry_sources(name: str, fn: Callable) -> None:
    """Runtime backstop for tpulint's fingerprint-completeness rule:
    warn when a standalone entry's traced function lives outside
    kernels/ but is not covered by _ENTRY_SOURCES — an edit to its
    module would then silently run a stale artifact."""
    fn_mod = getattr(fn, "__module__", "") or ""
    if not fn_mod or "kernels" in fn_mod.split("."):
        return
    declared = _ENTRY_SOURCES.get(name, ())
    if fn_mod in declared:
        return
    import sys

    fn_file = getattr(sys.modules.get(fn_mod), "__file__", None)
    if fn_file is not None:
        for src in declared:
            p = _source_path(src)
            if p is not None and str(p) == str(fn_file):
                return
    from ..utils.logger import get_logger

    get_logger("kernels/export_cache").warn(
        f"export entry {name!r} traces {fn_mod} (outside kernels/) "
        f"without registering it in _ENTRY_SOURCES — edits to that "
        f"module will NOT invalidate the cached artifact; pass "
        f"sources=({fn_mod!r},) to register_entry"
    )


def declare_sources(name: str, sources: Sequence[str]) -> None:
    """Declare the modules (dotted names or file paths) OUTSIDE the
    fingerprinted packages whose code entry `name` traces: they fold
    into that entry's artifact key only.  An empty declaration clears
    an earlier one (it would fold unrelated hashes into the key and
    pacify the runtime backstop)."""
    # tpulint: disable=kernel-purity -- host-side registry of module names; launches call it while tracing, never on traced values
    if sources:
        _ENTRY_SOURCES[name] = tuple(sources)
    else:
        _ENTRY_SOURCES.pop(name, None)


def register_entry(
    name: str,
    builder: Callable,
    source: Optional[str] = None,
    sources: Optional[Sequence[str]] = None,
) -> None:
    """Register a standalone entry.  `sources` declares every module
    OUTSIDE kernels/ whose code the traced computation reaches, as
    dotted module names — they fold into this entry's artifact key.
    The declaration is verified statically by tpulint
    (fingerprint-completeness) and dynamically when the builder runs."""
    declared = []
    if source is not None:
        declared.append(source)
    if sources is not None:
        declared.extend(sources)
    declare_sources(name, declared)

    def checked_builder():
        fn, specs = builder()
        _check_entry_sources(name, fn)
        return fn, specs

    _ENTRY_BUILDERS[name] = checked_builder


def registered_entries() -> Dict[str, Callable]:
    return dict(_ENTRY_BUILDERS)


# registration delegate for bucketed_entry's per-bucket loop: tpulint's
# fingerprint rule reads register_entry/bucketed_entry CALL SITES with
# literal entry names; the internal fan-out below registers computed
# "@bucket" keys, which must stay invisible to the static scanner
_register = register_entry

# entry name -> declared shape-bucket table (bucketed_entry only)
_ENTRY_BUCKETS: Dict[str, Tuple[int, ...]] = {}


def entry_buckets() -> Dict[str, Tuple[int, ...]]:
    """Declared shape buckets per bucketed entry (pre-trace coverage)."""
    return dict(_ENTRY_BUCKETS)


def bucketed_entry(
    name: str,
    builder: Callable,
    buckets: Sequence[int],
    source: Optional[str] = None,
    sources: Optional[Sequence[str]] = None,
) -> None:
    """Register ONE logical entry pre-traced at SEVERAL shape buckets.

    `builder(bucket) -> (fn, specs)` — the same traced computation at a
    bucket-parametric shape.  The bare `name` registers at the first
    bucket (the runtime dispatch key stays unchanged:
    `load_or_export(name, ...)` callers keep working); the remaining
    buckets register under `f"{name}@{bucket}"` so export_registered()
    pre-traces every bucket.  Artifact names strip the "@bucket" suffix
    — the artifact key already folds the shape signature, so all
    buckets share the entry's name and source fingerprint.

    `buckets` must be a non-empty strictly-increasing int tuple;
    tpulint's fingerprint-completeness rule verifies the table is
    statically readable at the call site (bucket coverage is part of
    the export contract, ROADMAP cold-compile fix (a))."""
    table = tuple(int(b) for b in buckets)
    if not table:
        raise ValueError(f"bucketed entry {name!r}: empty bucket table")
    if list(table) != sorted(set(table)):
        raise ValueError(
            f"bucketed entry {name!r}: buckets must be strictly "
            f"increasing, got {table}"
        )
    _ENTRY_BUCKETS[name] = table

    def _at(bucket: int) -> Callable:
        def build():
            return builder(bucket)

        return build

    for i, bucket in enumerate(table):
        key = name if i == 0 else f"{name}@{bucket}"
        _register(key, _at(bucket), source=source, sources=sources)


def export_registered(platform: str, cache_dir: Optional[str] = None) -> Dict[str, str]:
    """Trace + persist every registered standalone entry; returns
    registration key -> artifact key (the export pipeline's pre-trace
    hook).  Bucketed registrations ("name@bucket") export under the
    bare entry name — the bucket lives in the shape signature."""
    out = {}
    for name, builder in _ENTRY_BUILDERS.items():
        fn, specs = builder()
        artifact = name.split("@", 1)[0]
        load_or_export(artifact, fn, specs, platform, cache_dir)
        out[name] = artifact_key(artifact, specs, platform)
    return out


def _register_builtin_entries() -> None:
    """Register the subsystem kernels that live outside kernels/ (the
    slasher's whole-window span update) and the HTR device
    merkleization kernels (kernels/sha256.py spec builders)."""

    def _slasher_span():
        from ..slasher.device import export_specs

        return export_specs()

    register_entry(
        "slasher_span_update",
        _slasher_span,
        sources=(
            "lodestar_tpu.slasher.device",
            "lodestar_tpu.slasher.batch",
        ),
    )

    # The HTR device-merkleization kernels (ISSUE 16): hash-pairs at
    # the four headline plane buckets, the per-slot forest sweep, and
    # the validators leaf-pack + 3-level subtree.  Traced code lives
    # entirely in kernels/sha256.py (covered by the wholesale kernels
    # fingerprint) so no sources declarations are needed.
    from .sha256 import (
        HTR_PAIR_BUCKETS,
        HTR_SWEEP_LANES,
        HTR_VALIDATOR_BUCKETS,
    )

    def _htr_hash_pairs(bucket: int):
        from .sha256 import export_specs_hash_pairs

        return export_specs_hash_pairs(bucket)

    def _htr_forest_sweep(lanes: int):
        from .sha256 import export_specs_forest

        return export_specs_forest(lanes=lanes)

    def _htr_validator_roots(bucket: int):
        from .sha256 import export_specs_validator_roots

        return export_specs_validator_roots(bucket)

    bucketed_entry("htr_hash_pairs", _htr_hash_pairs, buckets=HTR_PAIR_BUCKETS)
    bucketed_entry(
        "htr_forest_sweep", _htr_forest_sweep, buckets=(HTR_SWEEP_LANES,)
    )
    bucketed_entry(
        "htr_validator_roots",
        _htr_validator_roots,
        buckets=HTR_VALIDATOR_BUCKETS,
    )


_register_builtin_entries()
