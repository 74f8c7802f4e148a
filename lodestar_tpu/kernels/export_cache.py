"""AOT export cache — trace once, reload without re-tracing.

The wire verify pipeline unrolls 33-limb schoolbook arithmetic into a
large jaxpr; TRACING it costs minutes of host time per process, while
the XLA/Mosaic compile can be served by the persistent compile cache.
Tracing is pure Python work over static shapes, so it can be paid
ONCE, the result serialized with `jax.export`, and every later process
deserializes in milliseconds and goes straight to (cached) compilation.
The BLS verifier does not dispatch through it (PR 21): an artifact is
one monolithic program per (entry, shape), so a COLD start compiles
every stage kernel again for each shape, where direct dispatch
compiles each stage once per process (PERF.md).

Artifacts are keyed by (entry name, shape/dtype signature, platform,
jax version, kernels-code fingerprint); a stale fingerprint falls back
to a fresh trace, so a kernel edit can never run an outdated artifact.

Cross-platform: `platform="tpu"` artifacts are traced on this CPU host
with the real Mosaic lowering forced (launch.force_mosaic) — export
runs jax lowering only; the Mosaic->TPU-binary compile still happens
on-device at first call, hitting the persistent compile cache.
"""

from __future__ import annotations

import hashlib
import os
import pathlib
from typing import Callable, Dict, Optional, Sequence, Tuple

import jax

# a fixed directory inside the checkout (gitignored), so a later
# process on the same checkout finds what an earlier one wrote
DEFAULT_DIR = os.environ.get(
    "LODESTAR_TPU_EXPORT_CACHE",
    str(pathlib.Path(__file__).resolve().parents[2] / ".export_cache"),
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


# Kernel sources OUTSIDE kernels/ whose traced computations live in the
# cache, keyed per entry NAME (standalone registry entries declare
# theirs at registration).  They fold into THAT entry's artifact key
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


def _code_fingerprint() -> str:
    """Hash of every kernels/*.py source file: a kernel edit invalidates
    all artifacts (they embed the traced computation)."""
    h = hashlib.sha256()
    pkg = pathlib.Path(__file__).parent
    for p in sorted(pkg.glob("*.py")):
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
    name: str, specs: Sequence[jax.ShapeDtypeStruct], platform: str
) -> str:
    sig = ";".join(f"{tuple(s.shape)}:{s.dtype}" for s in specs)
    raw = f"{name}|{sig}|{platform}|{jax.__version__}|{code_fingerprint()}"
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
    d = pathlib.Path(cache_dir or DEFAULT_DIR)
    d.mkdir(parents=True, exist_ok=True)
    return d / f"{key}.jaxexport"


def load(
    name: str,
    specs: Sequence[jax.ShapeDtypeStruct],
    platform: str,
    cache_dir: Optional[str] = None,
) -> Optional[Callable]:
    """Deserialize a cached artifact; None when absent/stale."""
    from jax import export as jexport

    import time

    key = artifact_key(name, specs, platform)
    hit = _LOADED.get(key)
    if hit is not None:
        metrics().hits.inc(name, 1.0)
        return hit.call
    path = _path(key, cache_dir)
    if not path.exists():
        return None
    from ..observability import trace_span

    t0 = time.perf_counter()
    with trace_span("kernels.export_load", entry=name, platform=platform):
        try:
            exp = jexport.deserialize(path.read_bytes())
        except Exception:  # stale/corrupt artifact: re-trace
            return None
    metrics().load_seconds.observe(name, time.perf_counter() - t0)
    metrics().hits.inc(name, 1.0)
    _LOADED[key] = exp
    return exp.call


def export_and_save(
    name: str,
    fn: Callable,
    specs: Sequence[jax.ShapeDtypeStruct],
    platform: str,
    cache_dir: Optional[str] = None,
) -> Callable:
    """Trace `fn` for `platform` at `specs`, persist, return the call.

    For platform="tpu" on a CPU host the pallas launches are forced
    through the real Mosaic lowering (launch.force_mosaic)."""
    import time

    from jax import export as jexport

    from ..observability import trace_span
    from . import launch

    key = artifact_key(name, specs, platform)
    jitted = jax.jit(fn)
    t0 = time.perf_counter()
    with trace_span("kernels.export_trace", entry=name, platform=platform):
        if platform == "tpu" and jax.default_backend() != "tpu":
            with launch.force_mosaic():
                exp = jexport.export(jitted, platforms=[platform])(*specs)
        else:
            exp = jexport.export(jitted, platforms=[platform])(*specs)
        _path(key, cache_dir).write_bytes(exp.serialize())
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
) -> Callable:
    """The main entry: cached call if present, else trace+persist.
    Stage faults re-raise as ExportStageError (classification seam)."""
    platform = platform or jax.default_backend()
    try:
        cached = load(name, specs, platform, cache_dir)
    except Exception as e:  # noqa: BLE001 — load() already swallows
        # corrupt artifacts; anything else here is the backend dying
        raise ExportStageError("load", name, e) from e
    if cached is not None:
        return cached
    metrics().misses.inc(name, 1.0)
    try:
        return export_and_save(name, fn, specs, platform, cache_dir)
    except Exception as e:  # noqa: BLE001 — trace/persist faults carry
        # their stage for the breaker's outcome classification
        raise ExportStageError("trace", name, e) from e


# -- standalone entry registry ----------------------------------------------
#
# Entries that don't flow through the verify pipeline's dispatch capture
# (dev/export_pipeline.py) register a spec builder here so offline
# pre-tracing covers them too.  A builder returns (fn, specs); it is
# invoked lazily — registration itself must stay import-cheap.

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
    if declared:
        _ENTRY_SOURCES[name] = tuple(declared)
    else:
        # re-registration without sources must not inherit a stale
        # declaration (it would fold unrelated hashes into the key and
        # pacify the runtime backstop)
        _ENTRY_SOURCES.pop(name, None)

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


# the RLC verify entries' pre-trace buckets: the default service batch
# (rlc_entries.DEF_N — kept literal here so registration stays
# import-cheap) and the bench/replay batch
_RLC_BUCKETS = (128, 512)


def _register_builtin_entries() -> None:
    """Register the subsystem kernels that live outside kernels/ (the
    slasher's whole-window span update), the RLC verification entry
    points (kernels/rlc_entries.py spec builders), and the HTR device
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

    # The RLC verify pipeline's device entries, under the SAME names
    # bls/verifier._device_call dispatches with — registration makes
    # export_registered() pre-trace them at BOTH service buckets
    # (_RLC_BUCKETS) AND folds the crypto constant modules
    # (Montgomery-encoded curve constants bake into the traced kernels)
    # into every artifact key for these names, wire- and decoded-path
    # alike.  Builders spell out literal names + direct function
    # returns so tpulint's fingerprint-completeness rule can chase them
    # statically.
    def _rlc_batch_wire(bucket: int):
        from .rlc_entries import export_specs_batch_wire

        return export_specs_batch_wire(n=bucket)

    def _rlc_batch_wire_grouped(bucket: int):
        from .rlc_entries import export_specs_batch_wire_grouped

        return export_specs_batch_wire_grouped(n=bucket)

    def _rlc_each_wire(bucket: int):
        from .rlc_entries import export_specs_each_wire

        return export_specs_each_wire(n=bucket)

    def _rlc_batch_decoded(bucket: int):
        from .rlc_entries import export_specs_batch_decoded

        return export_specs_batch_decoded(n=bucket)

    def _rlc_each_decoded(bucket: int):
        from .rlc_entries import export_specs_each_decoded

        return export_specs_each_decoded(n=bucket)

    # sources spelled as per-call string-literal tuples: the tpulint
    # fingerprint rule only accepts statically-readable declarations
    bucketed_entry(
        "batch_wire",
        _rlc_batch_wire,
        buckets=_RLC_BUCKETS,
        sources=("lodestar_tpu.crypto.curves", "lodestar_tpu.crypto.fields"),
    )
    bucketed_entry(
        "batch_wire_grouped",
        _rlc_batch_wire_grouped,
        buckets=_RLC_BUCKETS,
        sources=("lodestar_tpu.crypto.curves", "lodestar_tpu.crypto.fields"),
    )
    bucketed_entry(
        "each_wire",
        _rlc_each_wire,
        buckets=_RLC_BUCKETS,
        sources=("lodestar_tpu.crypto.curves", "lodestar_tpu.crypto.fields"),
    )
    bucketed_entry(
        "batch_decoded",
        _rlc_batch_decoded,
        buckets=_RLC_BUCKETS,
        sources=("lodestar_tpu.crypto.curves", "lodestar_tpu.crypto.fields"),
    )
    bucketed_entry(
        "each_decoded",
        _rlc_each_decoded,
        buckets=_RLC_BUCKETS,
        sources=("lodestar_tpu.crypto.curves", "lodestar_tpu.crypto.fields"),
    )

    # the pre-verify aggregation stage's batched G2-sum (ISSUE 13):
    # same crypto-constant fingerprint scope as the verify entries (the
    # decompression + group-law kernels bake the same curve constants)
    def _agg_g2_sum(bucket: int):
        from .rlc_entries import export_specs_agg_g2_sum

        return export_specs_agg_g2_sum(n=bucket)

    bucketed_entry(
        "agg_g2_sum",
        _agg_g2_sum,
        buckets=_RLC_BUCKETS,
        sources=("lodestar_tpu.crypto.curves", "lodestar_tpu.crypto.fields"),
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
