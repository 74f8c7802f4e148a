"""Pallas launches, one persistent program per stage signature.

A `pl.pallas_call(...)` created fresh per invocation RE-TRACES its
kernel body on every call (measured: 3 calls through a rebuilt wrapper
= 3 kernel traces; a wrapper built once = 1).  The verify pipeline's
kernel bodies trace to ~1e5-equation jaxprs, so every pallas launch in
the pipeline goes through this module, which keeps one program per
(kernel, operand shapes, grid) signature for the life of the process.

Tracing and lowering a stage still cost minutes per process across the
pipeline, so the program is also kept across processes: the first
build of a signature exports that one stage with `jax.export` into the
export cache (kernels/export_cache.py `load_or_export`), and a later
process with a matching key deserializes it and never traces or lowers
the kernel body.  The key names each kernel by module and qualified
name, so a kernel must be a module-level function; one defined outside
kernels/ also folds its module's source into the key.  Each stage
stays its own program, compiled once and served by the persistent
compile cache; dispatch goes through one `jax.jit` of the exported
call per stage.
"""

from __future__ import annotations

import inspect
from typing import Callable, Dict, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

_CACHE: Dict[Tuple, Callable] = {}

# When True, launches lower through the REAL Mosaic path regardless of
# the host backend — set while lowering a TPU program on a CPU host
# (kernels/export_cache.py, the compile rehearsals).
_FORCE_MOSAIC = False


class force_mosaic:
    """Context manager: lower pallas launches for the real TPU backend
    even when the process default backend is CPU (cross-platform
    jax.export)."""

    def __enter__(self):
        global _FORCE_MOSAIC
        self._prev = _FORCE_MOSAIC
        _FORCE_MOSAIC = True

    def __exit__(self, *exc):
        global _FORCE_MOSAIC
        _FORCE_MOSAIC = self._prev


def interpret() -> bool:
    if _FORCE_MOSAIC:
        return False
    return jax.default_backend() != "tpu"


def _sds(shape):
    return jax.ShapeDtypeStruct(shape, jnp.int32)


def _count_build(kind: str) -> None:
    """Wrapper-construction tally (one per (kernel, shape) signature
    that no export served): each build implies a kernel trace, so a
    climbing counter is the named symptom of the re-tracing this
    module exists to prevent.  Process-global registry — lands on
    /metrics."""
    from ..utils.metrics import global_registry

    global_registry().labeled_counter(
        "lodestar_tpu_pallas_builds_total",
        "pallas_call wrapper constructions (each implies a kernel trace)",
        "kind",
    ).inc(kind, 1.0)


def _kernel_id(kernel) -> str:
    """`module.qualname` of a launched kernel, which keys its artifact:
    only a module-level function has one that names it alone (a
    closure's captured values, a partial's bound arguments and a
    lambda's body are not in it)."""
    qualname = getattr(kernel, "__qualname__", "")
    if not inspect.isfunction(kernel) or "<" in qualname:
        raise TypeError(
            f"launch kernel {kernel!r} must be a module-level function: "
            "its module and qualified name key the exported stage"
        )
    return f"{kernel.__module__}.{qualname}"


def _program(key: Tuple, builder: Callable[[], Callable], ins) -> Callable:
    """The dispatchable program for one launch signature, built once:
    loaded from (or saved to) the export cache, except when lowering
    for a platform other than this process's (force_mosaic on a CPU
    host), where the wrapper is built directly."""
    kernels = {p: _kernel_id(p) for p in key if callable(p)}
    if _FORCE_MOSAIC and jax.default_backend() != "tpu":
        _count_build(key[0])
        fn = builder()
    else:
        from . import export_cache as EC

        def traced(*args):  # runs only when the stage is exported
            _count_build(key[0])
            return builder()(*args)

        # e.g. "tiled._k_miller"; a kernel outside kernels/ goes by its
        # module too, and declares that module's source
        outside = [k for k in kernels if "kernels" not in k.__module__.split(".")]
        name = ".".join(
            p if isinstance(p, str) else kernels[p] if p in outside else p.__name__
            for p in key
            if isinstance(p, str) or callable(p)
        )
        EC.declare_sources(
            name, [inspect.getsourcefile(k) or k.__module__ for k in outside]
        )
        # the full key: kernels by id, then what the operand shapes do
        # not fix (grid, out rows, interpret mode)
        detail = "|".join(kernels[p] if callable(p) else repr(p) for p in key)
        specs = [jax.ShapeDtypeStruct(jnp.shape(a), a.dtype) for a in ins]
        fn = jax.jit(EC.load_or_export(name, traced, specs, detail=detail))
    _CACHE[key] = fn
    return fn


def tiled(kernel, ins, in_rows, out_rows, n: int, bt: int):
    """Lane-tiled launch: operands [rows, n] blocked to [rows, bt]."""
    assert n % bt == 0, n
    interp = interpret()
    key = ("tiled", kernel, tuple(in_rows), tuple(out_rows), n, bt, interp)
    fn = _CACHE.get(key) or _program(
        key,
        lambda: pl.pallas_call(
            kernel,
            out_shape=[_sds((r, n)) for r in out_rows],
            grid=(n // bt,),
            in_specs=[pl.BlockSpec((r, bt), lambda i: (0, i)) for r in in_rows],
            out_specs=[
                pl.BlockSpec((r, bt), lambda i: (0, i)) for r in out_rows
            ],
            interpret=interp,
        ),
        ins,
    )
    return fn(*ins)


def cached(key: Tuple, builder: Callable[[], Callable], *ins):
    """Launch for non-tiled shapes (grid accumulations,
    gather/aggregate): `builder()` makes the pallas_call, applied to
    `ins`.  `key` must capture everything the builder closes over and,
    with the interpret flag appended here, fixes the operand shapes."""
    full = ("cached",) + key + (interpret(),)
    fn = _CACHE.get(full) or _program(full, builder, ins)
    return fn(*ins)
